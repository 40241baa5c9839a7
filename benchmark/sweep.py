"""Builder's tool, not part of a check: one cell at other rates (to find a
knee), and the control of its ``correct``, through the harness's own
``measure`` and ``conclude``.

    python3 benchmark/sweep.py --workload <cell> [--rates 1e7,60000]
        [--seconds 10] [--seeds 1,2] [--control float8_e4m3fn] [--flood]

Each run uses a temporary copy of the data files in which only the cell's
rate differs; nothing in the repo is edited. A cell whose files exist but
which ``BENCHMARK.json`` does not list (``cells/*.saturate.json``) is
entered into the copy's manifest, so that it can be measured before it is
admitted. A rate far above capacity (``1e7``) sends as fast as the parser's
ingress accepts: completions per second (the ``value: lines_per_s`` line)
is then the knee. ``--control`` also puts the reference in the program's
place in that lower precision (``lib/control.py``) and prints its numbers.
One ``RESULT`` line per run goes to stdout.

A cell of ``max_batch 1024`` cannot drain ``1e7`` within ``run.py``'s 240 s,
so its knee is read from the time to drain a flood at about twice what the
chip completes: ``--flood`` sends at ``--rates`` for the ramp and the window,
waits for the drain and prints one ``FLOOD`` line — the lines sent over
(sending + drain − the quiet second ``run.py`` waits out) — with no
checkpoint, reference or verdict.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import run  # noqa: E402
from benchmark.lib import manifest  # noqa: E402
from benchmark.lib.stages import HarnessFailure  # noqa: E402


def variant_root(workload: str, rate: float) -> str:
    root = tempfile.mkdtemp(prefix="dmb-sweep-")
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "*.py"))
    listed = manifest.read_json(os.path.join(REPO, "BENCHMARK.json"))
    cell_path = os.path.join(root, "benchmark", "cells", workload + ".json")
    cell = manifest.read_json(cell_path)
    if rate:
        cell["rate_lines_per_s"] = rate
    with open(cell_path, "w", encoding="utf-8") as fh:
        json.dump(cell, fh)
    if workload not in {w["name"] for w in listed["workloads"]}:
        listed["workloads"].append({
            "name": workload, "config": cell["config"],
            "traffic": cell["traffic"], "chips": 1, "why": cell["why"]})
        if cell["config"] not in {c["name"] for c in listed["configs"]}:
            file = f"benchmark/configs/{cell['config']}.json"
            config = manifest.read_json(os.path.join(root, file))
            listed["configs"].append({
                "name": cell["config"], "source": config["source"],
                "file": file, "reduced": config["reduced"],
                "why": "not admitted yet"})
        for group in ("end_to_end", "per_layer"):
            for metric in listed[group]:
                if "workloads" in metric:
                    metric["workloads"].append(workload)
    with open(os.path.join(root, "BENCHMARK.json"), "w",
              encoding="utf-8") as fh:
        json.dump(listed, fh)
    return root


def one_run(root: str, workload: str, seed: int, seconds: float,
            control: str = "", platform: str = "tpu") -> dict:
    """One run through ``run.measure`` and ``run.conclude``; with
    ``control``, the control's child over the same work directory."""
    measured = run.measure(root, workload, seed, seconds, False, platform,
                           time.monotonic())
    try:
        result = run.conclude(measured)
        if control:
            child = subprocess.run(
                [sys.executable,
                 os.path.join(REPO, "benchmark", "lib", "control.py"),
                 os.path.join(measured["work"], "reference_request.json"),
                 control],
                env=dict(os.environ, JAX_PLATFORMS="cpu"),
                capture_output=True, text=True, timeout=900)
            print(child.stdout, end="", flush=True)
            if child.returncode not in (0, 1):
                raise HarnessFailure("control child failed:\n"
                                     + child.stderr[-3000:])
            result["control_fails"] = child.returncode == 0
        return result
    finally:
        shutil.rmtree(measured["work"], ignore_errors=True)


def one_flood(root: str, workload: str, seed: int, seconds: float,
              platform: str = "tpu") -> dict:
    """The completed lines/s of one flood, from the time to drain."""
    measured = run.measure(root, workload, seed, seconds, False, platform,
                           time.monotonic(), checkpoint=False)
    shutil.rmtree(measured["work"], ignore_errors=True)
    t, gen = measured["t"], measured["gen"]
    send_s = t["w1"] - t["t0"]
    took_s = send_s + t["drain_s"] - run.QUIET_S
    return {"stream_lines": t["stream_lines"], "send_s": send_s,
            "drain_s": t["drain_s"], "completed_in_s": took_s,
            "lines_per_s": t["stream_lines"] / took_s,
            "setup_s": t["setup_s"], "boot_s": t["boot_s"],
            "warm_s": t["warm_s"], "generator_blocked_s": gen.blocked_s}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", default="0",
                    help="comma-separated lines/s; 0 = the cell's own")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--control", default="",
                    help="also put the reference in the program's place in "
                         "this lower precision, e.g. float8_e4m3fn")
    ap.add_argument("--flood", action="store_true",
                    help="read each rate's completions from the time to "
                         "drain; no checkpoint, reference or verdict")
    args = ap.parse_args()
    failures = 0
    for rate in (float(r) for r in args.rates.split(",")):
        for seed in (int(s) for s in args.seeds.split(",")):
            root = variant_root(args.workload, rate)
            try:
                if args.flood:
                    print("FLOOD " + json.dumps(dict(
                        one_flood(root, args.workload, seed, args.seconds),
                        workload=args.workload, rate=rate, seed=seed)),
                        flush=True)
                    continue
                result = one_run(root, args.workload, seed, args.seconds,
                                 args.control)
            except HarnessFailure as exc:
                failures += 1
                print(f"sweep: no result — {exc}", flush=True)
                continue
            finally:
                shutil.rmtree(root, ignore_errors=True)
            print("RESULT " + json.dumps(
                {"workload": args.workload, "rate": rate, "seed": seed,
                 "result": result}), flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
