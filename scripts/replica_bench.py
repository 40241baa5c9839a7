#!/usr/bin/env python
"""Replica-tier goodput bench: single scorer vs router + N replicas.

REAL subprocesses over REAL ipc sockets, driven by the PR-8 open-loop load
generator (coordinated-omission-proof: latency is measured from each
frame's *scheduled* arrival). Three runs, one machine-checkable
``BENCH_replicas_*.json``:

1. **probe**   — saturate ONE scorer replica; its achieved rate is the
   single-replica capacity;
2. **single**  — one replica at ``rate_mult ×`` capacity: the baseline
   goodput + p99 under overload;
3. **router**  — the SAME offered rate through parser → router → N
   replicas: the tier must sustain ``≥ 3×`` the single-replica goodput at
   equal-or-better p99 (``goodput_3x_ok`` / ``p99_ok`` in the record).

Scorer modes (recorded, with the core count, in ``environment``):

* ``jax``    — the real ``JaxScorerDetector`` on XLA:CPU. Meaningful only
  when the host has at least ``replicas + 3`` cores: a CPU-bound scorer's
  scale-out ceiling is the core count, not the router.
* ``devsim`` — ``PacedDetector``: each batch occupies "the device" for a
  fixed wall time with no host CPU, the TPU serving regime where replica
  throughput is device-bound and overlaps freely across processes. This
  is what makes the ROUTER's scale-out measurable on a small host — and
  it is what ``--mode auto`` picks there.

Devices: this launcher starts several scorer PROCESSES at once and pins jax
to the CPU (``JAX_PLATFORMS=cpu`` below). A chip belongs to one process at a
time, so on an accelerator host every replica needs a device of its own —
one process per chip, each replica's scorer config naming it
(``device: "tpu:<id>"``). On a one-chip machine the second replica cannot
have the chip; do not point this script at one.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
import urllib.error
import urllib.request
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

AUDIT_LOG_FORMAT = "type=<Type> msg=audit(<Time>): <Content>"
AUDIT_TEMPLATE = ("arch=<*> syscall=<*> success=<*> exit=<*> pid=<*> "
                  "uid=<*> comm=<*> exe=<*>")
BASE_PORT = 18210


def http_json(url, timeout=5):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return json.loads(resp.read())


def wait_until(predicate, timeout, interval=0.25, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            if predicate():
                return
        except (urllib.error.URLError, OSError, ValueError):
            pass
        time.sleep(interval)
    raise RuntimeError(f"timed out waiting for {what}")


class Stage:
    def __init__(self, name, settings, config, tmp):
        import yaml

        self.name = name
        self.port = settings["http_port"]
        settings_path = tmp / f"{name}_settings.yaml"
        settings_path.write_text(yaml.safe_dump(settings))
        cmd = [sys.executable, "-m", "detectmateservice_tpu.cli",
               "--settings", str(settings_path)]
        if config is not None:
            config_path = tmp / f"{name}_config.yaml"
            config_path.write_text(yaml.safe_dump(config))
            cmd += ["--config", str(config_path)]
        env = dict(os.environ)
        env["PYTHONPATH"] = str(REPO) + (
            ":" + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
        env["JAX_PLATFORMS"] = "cpu"
        self.log = tmp / f"{name}.log"
        with open(self.log, "wb") as fh:
            self.proc = subprocess.Popen(cmd, stdout=fh,
                                         stderr=subprocess.STDOUT, env=env)

    def wait_running(self, timeout=120):
        def running():
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"{self.name} died rc={self.proc.returncode}:\n"
                    + self.log.read_text()[-2000:])
            doc = http_json(f"http://127.0.0.1:{self.port}/admin/status")
            return doc["status"]["running"]
        wait_until(running, timeout, what=f"{self.name} running")

    def stop(self):
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                self.proc.kill()


def scorer_config(mode: str, burst: int, service_ms: float):
    if mode == "devsim":
        return ("testing.paced_detector.PacedDetector",
                {"detectors": {"PacedDetector": {
                    "method_type": "paced_detector", "auto_config": False,
                    "service_ms": service_ms}}})
    return ("detectors.jax_scorer.JaxScorerDetector",
            {"detectors": {"JaxScorerDetector": {
                "method_type": "jax_scorer", "auto_config": False,
                "model": "mlp", "data_use_training": 64, "train_epochs": 1,
                "min_train_steps": 8, "seq_len": 8, "dim": 16,
                "max_batch": 2 * burst, "async_fit": False,
                "pipeline_depth": 0, "score_threshold": -1e30}}})


def boot_phase(tmp: Path, mode: str, n_replicas: int, burst: int,
               service_ms: float, collector_addr: str):
    """Spawn the phase's stages; returns (stages, parser_ingress_addr)."""
    common = dict(http_host="127.0.0.1", log_to_file=False,
                  log_to_console=True, engine_trace=True, backend="cpu",
                  engine_batch_size=burst, engine_batch_timeout_ms=5.0,
                  engine_frame_batch=burst, engine_recv_timeout=50)
    templates = tmp / "templates.txt"
    templates.write_text(AUDIT_TEMPLATE + "\n", encoding="utf-8")
    parser_cfg = {"parsers": {"MatcherParser": {
        "method_type": "matcher_parser", "auto_config": False,
        "log_format": AUDIT_LOG_FORMAT, "accept_raw_lines": True,
        "params": {"path_templates": str(templates)}}}}
    component_type, detector_cfg = scorer_config(mode, burst, service_ms)

    stages = []
    scorer_addrs, admin_urls = [], []
    for i in range(n_replicas):
        addr = f"ipc://{tmp}/scorer-{i}.ipc"
        port = BASE_PORT + 1 + i
        scorer_addrs.append(addr)
        admin_urls.append(f"http://127.0.0.1:{port}")
        stages.append(Stage(f"scorer-{i}", dict(
            component_type=component_type, component_id=f"bench-scorer-{i}",
            trace_stage=f"scorer-{i}", engine_addr=addr,
            out_addr=[collector_addr], trace_observe_e2e=True,
            http_port=port, **common), detector_cfg, tmp))

    if n_replicas > 1:
        router_addr = f"ipc://{tmp}/router.ipc"
        stages.append(Stage("router", dict(
            component_type="core", component_id="bench-router",
            trace_stage="router", engine_addr=router_addr,
            router_replicas=scorer_addrs, router_admin_urls=admin_urls,
            router_policy="least_backlog", router_credit_window=128,
            router_drain_timeout_s=5.0, router_health_interval_s=1.0,
            http_port=BASE_PORT + 40, **common), None, tmp))
        downstream = router_addr
    else:
        downstream = scorer_addrs[0]

    parser_addr = f"ipc://{tmp}/parser.ipc"
    stages.append(Stage("parser", dict(
        component_type="parsers.template_matcher.MatcherParser",
        component_id="bench-parser", trace_stage="parser",
        engine_addr=parser_addr, out_addr=[downstream],
        http_port=BASE_PORT + 50, **common), parser_cfg, tmp))
    for stage in stages:
        stage.wait_running()
    return stages, parser_addr, admin_urls


def warm_jax(admin_urls, timeout=300):
    """Wait out every replica's training + jit warm-up: the XLA ledger must
    go compile-quiet on each replica before the measured window starts."""
    for url in admin_urls:
        prev = {"n": -1, "quiet": 0}

        def compile_quiet(url=url, prev=prev):
            doc = http_json(url + "/admin/xla")
            n = doc["totals"]["compiles"]
            prev["quiet"] = prev["quiet"] + 1 if n == prev["n"] else 0
            prev["n"] = n
            return n > 0 and prev["quiet"] >= 3
        wait_until(compile_quiet, timeout, interval=1.0,
                   what=f"compile-quiet on {url}")


def run_load(parser_addr, collector_addr, rate, burst, seconds, settle,
             warm_lines=0):
    from detectmateservice_tpu.loadgen.generator import (
        LoadGenerator,
        LoadProfile,
    )

    profile = LoadProfile(
        target_addr=parser_addr, listen_addr=collector_addr,
        rate=rate, burst=burst, seconds=seconds, settle_s=settle,
        warm_lines=warm_lines)
    generator = LoadGenerator(profile, labels=dict(
        component_type="loadgen", component_id="replica-bench"))
    generator.start()
    generator.wait(timeout=seconds + settle + 300)
    status = generator.stop()
    card = status["scorecard"]
    return {
        "offered_lines_per_s": card["offered_lines_per_s"],
        "achieved_lines_per_s": card["achieved_lines_per_s"],
        "goodput_ratio": card["goodput_ratio"],
        "sent_frames": card["sent_frames"],
        "received_frames": card["received_frames"],
        "loss": card["loss"],
        "p50_ms": card["latency"].get("p50_ms"),
        "p99_ms": card["latency"].get("p99_ms"),
        "latency_count": card["latency"]["count"],
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--mode", choices=["auto", "jax", "devsim"],
                    default="auto")
    ap.add_argument("--replicas", type=int, default=4)
    ap.add_argument("--burst", type=int, default=500,
                    help="lines per frame = rows per scorer batch")
    ap.add_argument("--service-ms", type=float, default=160.0,
                    help="devsim: per-batch device occupancy. Sized so the "
                         "4-replica tier's device-bound ceiling stays under "
                         "the HOST's per-core frame-handling ceiling — on a "
                         "1-core box ~80 ms already host-saturates around "
                         "17k lines/s and caps the measured ratio at ~3x")
    ap.add_argument("--rate-mult", type=float, default=3.6,
                    help="measured offered rate = this x single capacity")
    ap.add_argument("--probe-rate", type=float, default=60000.0)
    ap.add_argument("--probe-seconds", type=float, default=12.0)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--settle", type=float, default=25.0)
    ap.add_argument("--out-dir", default=str(REPO))
    args = ap.parse_args()

    cores = os.cpu_count() or 1
    mode = args.mode
    mode_reason = "explicit"
    if mode == "auto":
        if cores >= args.replicas + 3:
            mode, mode_reason = "jax", f"{cores} cores >= replicas+3"
        else:
            mode, mode_reason = "devsim", (
                f"{cores} core(s) < {args.replicas}+3: a CPU-bound scorer "
                "cannot scale past the core count — measuring the router "
                "against device-bound replicas instead")
    print(f"[replica-bench] mode={mode} ({mode_reason})")

    import tempfile

    record = {
        "schema": "bench-replicas-v1",
        "started_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "environment": {"cores": cores, "mode": mode,
                        "mode_reason": mode_reason,
                        "platform": os.environ.get("JAX_PLATFORMS", "")},
        "profile": {"replicas": args.replicas, "burst": args.burst,
                    "service_ms": args.service_ms,
                    "rate_mult": args.rate_mult,
                    "seconds": args.seconds},
        "runs": {},
    }

    def phase(name, n_replicas, rate, seconds, warm_lines):
        with tempfile.TemporaryDirectory(prefix="dmbench-") as tmp_s:
            tmp = Path(tmp_s)
            collector_addr = f"ipc://{tmp}/collector.ipc"
            stages, parser_addr, admin_urls = boot_phase(
                tmp, mode, n_replicas, args.burst, args.service_ms,
                collector_addr)
            try:
                if mode == "jax" and warm_lines:
                    # prime with an untraced preamble, then wait out the
                    # compile set so no measured frame pays a jit compile
                    run_load(parser_addr, collector_addr, rate=2000.0,
                             burst=args.burst, seconds=2.0, settle=5.0,
                             warm_lines=warm_lines)
                    warm_jax(admin_urls)
                result = run_load(parser_addr, collector_addr, rate=rate,
                                  burst=args.burst, seconds=seconds,
                                  settle=args.settle,
                                  warm_lines=0 if mode == "jax"
                                  else min(warm_lines, args.burst))
                if n_replicas > 1:
                    result["router"] = http_json(
                        f"http://127.0.0.1:{BASE_PORT + 40}/admin/replicas")
                return result
            finally:
                for stage in stages:
                    stage.stop()

    warm_lines = 8 * args.burst * args.replicas
    print("[replica-bench] probe: single-replica capacity...")
    probe = phase("probe", 1, args.probe_rate, args.probe_seconds,
                  warm_lines)
    record["runs"]["probe"] = probe
    capacity = probe["achieved_lines_per_s"] or 1.0
    rate = round(args.rate_mult * capacity, 1)
    print(f"[replica-bench] capacity ~{capacity:.0f} lines/s "
          f"-> measured offered rate {rate:.0f} lines/s")

    print("[replica-bench] measured run: single replica...")
    single = phase("single", 1, rate, args.seconds, warm_lines)
    record["runs"]["single"] = single
    print(f"[replica-bench] single: {single['achieved_lines_per_s']}/s, "
          f"p99={single['p99_ms']}ms")

    print(f"[replica-bench] measured run: router + {args.replicas} "
          "replicas...")
    routed = phase("router", args.replicas, rate, args.seconds, warm_lines)
    record["runs"]["router"] = routed
    print(f"[replica-bench] router: {routed['achieved_lines_per_s']}/s, "
          f"p99={routed['p99_ms']}ms")

    single_rate = single["achieved_lines_per_s"] or 1.0
    ratio = (routed["achieved_lines_per_s"] or 0.0) / single_rate
    record["goodput_ratio_router_vs_single"] = round(ratio, 2)
    record["goodput_3x_ok"] = bool(ratio >= 3.0)
    p99_ok = (routed["p99_ms"] is not None and single["p99_ms"] is not None
              and routed["p99_ms"] <= single["p99_ms"])
    record["p99_ok"] = bool(p99_ok)
    record["pass"] = bool(record["goodput_3x_ok"] and p99_ok)

    out_dir = Path(args.out_dir)
    out_dir.mkdir(parents=True, exist_ok=True)
    out = out_dir / f"BENCH_replicas_{time.strftime('%Y%m%d-%H%M%S')}.json"
    out.write_text(json.dumps(record, indent=2) + "\n", encoding="utf-8")
    print(f"[replica-bench] {'PASS' if record['pass'] else 'FAIL'} "
          f"ratio={ratio:.2f}x p99 {routed['p99_ms']}ms vs "
          f"{single['p99_ms']}ms -> {out}")
    return 0 if record["pass"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
