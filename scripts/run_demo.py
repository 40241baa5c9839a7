#!/usr/bin/env python
"""End-to-end demo: reader -> parser -> detector -> output writer -> sink,
as separate service processes over ipc sockets.

Role of the reference's ``scripts/run_demo_scenario.sh`` walkthrough
(reference: scripts/run_demo_scenario.sh, scripts/walkthrough.md), Docker-free:
each stage is a ``detectmate`` service process launched from the example
configs in ``examples/``; the demo feeds a synthetic audit log (no fixture
copied from the reference), collects the aggregated OutputSchema records from
the final socket (the output stage also writes them to a dated file, the
reference fluentout role), and prints a summary with throughput and the
admin-plane metrics.

Usage:
    python scripts/run_demo.py                  # NewValueDetector pipeline
    python scripts/run_demo.py --detector scorer  # TPU JaxScorerDetector
    python scripts/run_demo.py -n 10000 --keep
"""
from __future__ import annotations

import argparse
import json
import shutil
import subprocess
import sys
import threading
import time
import urllib.request
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
DEMO_DIR = Path("/tmp/detectmate-demo")
PARSER_PORT, DETECTOR_PORT, OUTPUT_PORT, LLM_PORT = 18111, 18112, 18113, 18114

sys.path.insert(0, str(REPO))


def admin(port: int, verb: str, method: str = "POST"):
    url = f"http://127.0.0.1:{port}/admin/{verb}"
    req = urllib.request.Request(url, method=method, data=b"" if method == "POST" else None)
    with urllib.request.urlopen(req, timeout=5) as resp:
        return json.loads(resp.read())


def wait_running(port: int, deadline_s: float = 180.0) -> None:
    # generous: the scorer service warms the jit compile cache in setup_io
    # before the admin plane reports running (~1 min on a cold TPU)
    end = time.monotonic() + deadline_s
    while time.monotonic() < end:
        try:
            status = admin(port, "status", method="GET")
            if status["status"]["running"]:
                return
        except Exception:
            pass
        time.sleep(0.25)
    raise RuntimeError(f"service on port {port} never reported running")


def launch(settings: Path, log: Path) -> subprocess.Popen:
    import os

    env = dict(os.environ)  # keep the accelerator's env vars intact
    env["PYTHONPATH"] = str(REPO) + (
        ":" + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    with open(log, "wb") as fh:
        return subprocess.Popen(
            [sys.executable, "-m", "detectmateservice_tpu.cli",
             "--settings", str(settings)],
            stdout=fh, stderr=subprocess.STDOUT, env=env, cwd=str(DEMO_DIR),
        )


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("-n", type=int, default=2316, help="log lines to feed")
    ap.add_argument("--detector", choices=["newvalue", "scorer"], default="newvalue")
    ap.add_argument("--llm", action="store_true",
                    help="insert the LLM triage stage between detector and output")
    ap.add_argument("--keep", action="store_true", help="keep the work dir")
    args = ap.parse_args()

    from detectmateservice_tpu.engine.socket import (
        TransportTimeout, ZmqPairSocketFactory,
    )
    from detectmateservice_tpu.schemas import LogSchema, OutputSchema

    sys.path.insert(0, str(REPO / "examples"))
    from gen_audit_log import generate

    if DEMO_DIR.exists():
        shutil.rmtree(DEMO_DIR)
    (DEMO_DIR / "logs").mkdir(parents=True)

    for name in ("parser_settings.yaml", "parser_config.yaml",
                 "detector_config.yaml", "scorer_config.yaml",
                 "output_settings.yaml", "output_config.yaml",
                 "llm_settings.yaml", "llm_config.yaml",
                 "audit_templates.txt"):
        shutil.copy(REPO / "examples" / name, DEMO_DIR / name)
    detector_settings = ("detector_settings.yaml" if args.detector == "newvalue"
                        else "scorer_settings.yaml")
    shutil.copy(REPO / "examples" / detector_settings, DEMO_DIR / detector_settings)
    if args.llm:
        # reroute detector alerts through the triage stage
        import yaml

        det_path = DEMO_DIR / detector_settings
        det_cfg = yaml.safe_load(det_path.read_text())
        det_cfg["out_addr"] = ["ipc:///tmp/detectmate-demo/llm.ipc"]
        det_path.write_text(yaml.safe_dump(det_cfg))

    lines = list(generate(args.n))
    expected_anomalies = sum(1 for _, a in lines if a)
    print(f"[demo] {args.n} synthetic audit lines, {expected_anomalies} anomalous, "
          f"detector={args.detector}")

    procs = []
    factory = ZmqPairSocketFactory()
    try:
        procs.append(launch(DEMO_DIR / "parser_settings.yaml", DEMO_DIR / "parser.out"))
        procs.append(launch(DEMO_DIR / detector_settings, DEMO_DIR / "detector.out"))
        procs.append(launch(DEMO_DIR / "output_settings.yaml", DEMO_DIR / "output.out"))
        if args.llm:
            procs.append(launch(DEMO_DIR / "llm_settings.yaml", DEMO_DIR / "llm.out"))
        # final sink listens where the output stage dials (OutputSchema records)
        sink = factory.create("ipc:///tmp/detectmate-demo/final.ipc")
        sink.recv_timeout = 200
        alerts = []
        stop_sink = threading.Event()

        def drain():
            while not stop_sink.is_set():
                try:
                    alerts.append(OutputSchema.from_bytes(sink.recv()))
                except TransportTimeout:
                    continue
                except Exception:
                    return

        sink_thread = threading.Thread(target=drain, daemon=True)
        sink_thread.start()

        wait_running(PARSER_PORT)
        wait_running(DETECTOR_PORT)
        wait_running(OUTPUT_PORT)
        if args.llm:
            wait_running(LLM_PORT)
        print(f"[demo] all {'four' if args.llm else 'three'} services running; "
              "feeding...")

        ingress = factory.create_output("ipc:///tmp/detectmate-demo/parser.ipc")
        t0 = time.perf_counter()
        for i, (line, _) in enumerate(lines):
            ingress.send(LogSchema(logID=str(i), log=line,
                                   logSource="audit").serialize())
        feed_s = time.perf_counter() - t0
        # allow the pipeline to drain; the scorer path pays first-jit compile
        # (~20-40s on TPU) before anything comes out, so settle on alert-count
        # stability rather than a short fixed sleep
        settle = 180.0 if args.detector == "scorer" else 20.0
        stable_polls_needed = 8 if args.detector == "scorer" else 4
        end = time.monotonic() + settle
        last, stable = -1, 0
        while time.monotonic() < end:
            time.sleep(1.0)
            if len(alerts) != last:
                last, stable = len(alerts), 0
            else:
                stable += 1
                if alerts and stable >= stable_polls_needed:
                    break
        elapsed = time.perf_counter() - t0
        stop_sink.set()
        sink_thread.join(timeout=2)

        print(f"[demo] fed {args.n} lines in {feed_s:.2f}s "
              f"({args.n / feed_s:,.0f} lines/s ingress)")
        print(f"[demo] pipeline settled after {elapsed:.2f}s; "
              f"output records: {len(alerts)} (expected ~{expected_anomalies})")
        for record in alerts[:5]:
            print(f"  record detectorIDs={list(record.detectorIDs)} "
                  f"logIDs={list(record.logIDs)} obtain={dict(record.alertsObtain)}")
        if len(alerts) > 5:
            print(f"  ... and {len(alerts) - 5} more")
        # the output stage also writes the dated file (fluentout role)
        dated = DEMO_DIR / "out" / time.strftime("output.%Y%m%d")
        n_lines = (len(dated.read_text().strip().splitlines())
                   if dated.exists() else 0)
        print(f"[demo] dated sink file {dated}: {n_lines} records")
        ok = len(alerts) > 0 and n_lines > 0
        print("[demo] RESULT:", "OK" if ok else "NO ALERTS (unexpected)")
        return 0 if ok else 1
    finally:
        for port in (PARSER_PORT, DETECTOR_PORT, OUTPUT_PORT, LLM_PORT):
            try:
                admin(port, "shutdown")
            except Exception:
                pass
        for proc in procs:
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
        if not args.keep and DEMO_DIR.exists():
            shutil.rmtree(DEMO_DIR, ignore_errors=True)


if __name__ == "__main__":
    raise SystemExit(main())
