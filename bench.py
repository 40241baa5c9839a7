"""Benchmark: audit-log lines/sec through the detector on one chip.

One process, on the chip. Prints ONE JSON line: {"metric", "value", "unit",
"vs_baseline", "platform", "device_kind", "device_count", ...}. Baseline
target (BASELINE.md): ≥200,000 lines/s through the detector at <10 ms p50
detect latency on 1× TPU v5e. vs_baseline = value / 200000.

The measured path is the full detector contract — serialized ParserSchema
bytes in, protobuf decode, CPU featurization, batched jit scoring on device,
alert serialization out — i.e. what a service process does per message,
minus the socket hop.

Without an accelerator it exits non-zero and prints no rate: a number from
the CPU backend is not this metric. (``chip_smoke.py --rehearse-cpu`` is the
CPU correctness check.)

Usage: python bench.py [N]
"""
from __future__ import annotations

import json
import os
import sys
import time

TARGET_LINES_PER_S = 200_000.0
FULL_N = int(os.environ.get("DETECTMATE_BENCH_N", "262144"))

# Open-loop arrival mode: after the closed-loop number, the run replays
# production-shaped load — bursts arriving on a wall-clock schedule at a
# configured rate, independent of how fast the detector drains them —
# against the deadline-aware coalescer, and reports occupancy / queue-wait /
# release-reason counters. Closed-loop max throughput cannot see any of
# that: it always hands the detector full buckets.
OPENLOOP_ENABLED = os.environ.get("DETECTMATE_BENCH_OPENLOOP", "1") != "0"
# arrival rate in lines/s; 0 = auto (~60% of the measured closed-loop rate —
# heavy but sustainable, the regime the occupancy target is defined for)
OPENLOOP_RATE = float(os.environ.get("DETECTMATE_BENCH_ARRIVAL_RATE", "0"))
OPENLOOP_BURST = int(os.environ.get("DETECTMATE_BENCH_ARRIVAL_BURST", "256"))
OPENLOOP_SECONDS = float(os.environ.get("DETECTMATE_BENCH_OPENLOOP_SECONDS", "6"))
OPENLOOP_DEADLINE_MS = float(os.environ.get("DETECTMATE_BENCH_DEADLINE_MS", "25"))


# Canonical bench scorer configuration — ONE home. scripts/bench_service.py
# derives from it, so a service-path run always measures the configuration
# the headline bench runs.
BENCH_SCORER_CONFIG = {
    "method_type": "jax_scorer", "auto_config": False, "model": "mlp",
    "data_use_training": 2048, "train_epochs": 2, "async_fit": False,
    "seq_len": 32, "dim": 128, "max_batch": 16384, "pipeline_depth": 8,
    "threshold_sigma": 6.0,
}


def build_bench_detector():
    """Construct the headline-bench detector."""
    from detectmateservice_tpu.library.detectors import JaxScorerDetector

    return JaxScorerDetector(
        config={"detectors": {"JaxScorerDetector": dict(BENCH_SCORER_CONFIG)}})


def make_messages(n: int, anomaly_rate: float = 0.01, seed: int = 0):
    import numpy as np

    from detectmateservice_tpu.schemas import ParserSchema

    rng = np.random.default_rng(seed)
    msgs = []
    for i in range(n):
        if rng.random() < anomaly_rate:
            template, variables = "segfault at <*> ip <*> sp <*>", [
                hex(rng.integers(2**30)), hex(rng.integers(2**30)), hex(rng.integers(2**30))]
        else:
            template, variables = "type=<*> msg=audit(<*>): pid=<*> uid=<*> comm=<*>", [
                "SYSCALL", f"17000{i % 100}.{i % 997}", str(int(rng.integers(300, 500))),
                str(int(rng.integers(0, 4))), ["cron", "sshd", "systemd", "bash"][i % 4]]
        msgs.append(ParserSchema(
            EventID=1, template=template, variables=variables,
            logID=str(i), logFormatVariables={"Time": str(1_700_000_000 + i)},
        ).serialize())
    return msgs


def run(n_bench: int) -> dict:
    """Measure detector throughput + single-message p50 for n_bench messages."""
    import numpy as np

    n_train = BENCH_SCORER_CONFIG["data_use_training"]
    batch = BENCH_SCORER_CONFIG["max_batch"]
    det = build_bench_detector()
    det.setup_io()
    import jax

    devices = jax.devices()

    train_msgs = make_messages(n_train, anomaly_rate=0.0)
    for start in range(0, n_train, batch):
        det.process_batch(train_msgs[start:start + batch])
    det.flush()

    bench_msgs = make_messages(n_bench, anomaly_rate=0.01, seed=1)
    # warmup (compile cache for the bench bucket); flush_final also joins
    # the host-bucket warm thread fit() started — its background XLA:CPU
    # compiles otherwise steal host cycles from featurize/drain inside the
    # timed loop
    det.process_batch(bench_msgs[:batch])
    det.flush_final()

    # measure the fused wire-frame path (process_frames): it is what a
    # service process runs in steady state — packed frames in, native
    # expand+featurize, batched jit scoring, lazy alert construction.
    # Frames are packed OUTSIDE the timed loop: packing is the sender's
    # cost (scripts/bench_service.py measures it within the socket hop).
    from detectmateservice_tpu.engine.framing import pack_batch

    frame_n = 512
    frames = [pack_batch(bench_msgs[i:i + frame_n])
              for i in range(0, n_bench, frame_n)]
    frames_per_call = max(1, batch // frame_n)

    t0 = time.perf_counter()
    alerts = 0
    for start in range(0, len(frames), frames_per_call):
        out, _n_msgs, _n_lines = det.process_frames(
            frames[start:start + frames_per_call])
        alerts += sum(o is not None for o in out)
    alerts += sum(o is not None for o in det.flush())
    elapsed = time.perf_counter() - t0
    lines_per_s = n_bench / elapsed

    # p50 single-message latency (lone message through the same path; flush
    # forces the device readback the pipelined path would overlap)
    lat = []
    single = make_messages(64, anomaly_rate=0.0, seed=2)
    for msg in single:
        t = time.perf_counter()
        det.process_frames([msg])
        det.flush()
        lat.append(time.perf_counter() - t)
    p50_ms = float(np.median(lat) * 1000.0)

    payload = {
        "metric": "audit_log_lines_per_sec_through_detector",
        "value": round(lines_per_s, 1),
        "unit": "lines/s",
        "vs_baseline": round(lines_per_s / TARGET_LINES_PER_S, 3),
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "p50_ms": round(p50_ms, 4),
        "alerts": alerts,
        "n": n_bench,
        "elapsed_s": round(elapsed, 3),
    }
    if OPENLOOP_ENABLED:
        payload["open_loop"] = run_open_loop(det, lines_per_s)
    return payload


def run_open_loop(det, closed_loop_lps: float) -> dict:
    """Open-loop phase: bursts arrive on a wall-clock schedule, whether or
    not the detector kept up — queueing and padding become visible instead
    of being absorbed by the caller's pacing. The adaptive coalescer is
    enabled for this phase only (the closed-loop headline stays on the
    legacy dispatch path), and its scheduler counters are the result."""
    # the arrival machinery is the loadgen package's OpenLoopSchedule — the
    # same immutable wall-clock schedule scripts/soak.py drives the full
    # pipeline with, here replayed against the in-process detector
    from detectmateservice_tpu.loadgen.generator import OpenLoopSchedule

    rate = OPENLOOP_RATE or max(1000.0, 0.6 * closed_loop_lps)
    burst = max(1, OPENLOOP_BURST)
    total = max(burst, int(min(rate * OPENLOOP_SECONDS, 2_000_000)))
    msgs = make_messages(min(total, 65536), anomaly_rate=0.01, seed=3)

    det.config.batch_deadline_ms = OPENLOOP_DEADLINE_MS
    det.config.batch_target_occupancy = 0.9
    before = det.batching_stats()
    tick_s = max(0.0005, (det.drain_poll_ms or 5) / 1000.0)
    alerts = sent = i = 0
    sched = OpenLoopSchedule(rate, burst, clock=time.perf_counter)
    t0 = sched.t0
    try:
        while sent < total:
            now = time.perf_counter()
            if now < sched.deadline(i):
                # the engine's short-poll tick stand-in: deadline releases
                # and ready readbacks drain between arrivals
                alerts += sum(o is not None for o in det.drain_ready())
                time.sleep(min(sched.deadline(i) - now, tick_s))
                continue
            base = sent % len(msgs)
            chunk = msgs[base:base + burst]
            if len(chunk) < burst:
                chunk = chunk + msgs[:burst - len(chunk)]
            alerts += sum(o is not None for o in det.process_batch(chunk))
            sent += burst
            i += 1
            if sched.lag_s(i) > 2.0:
                # hopelessly behind: skip ahead on the fixed schedule
                # (open loop, not a death spiral — skipped bursts are
                # offered-but-unsourced load, visible as achieved < offered)
                i = int((sched.clock() - sched.t0) / sched.interval_s)
        alerts += sum(o is not None for o in det.flush())
        elapsed = time.perf_counter() - t0
        after = det.batching_stats()
    finally:
        det.config.batch_deadline_ms = 0.0  # leave the detector as found
    d_n = after["dispatches"] - before["dispatches"]
    d_occ = after["occupancy_sum"] - before["occupancy_sum"]
    return {
        "arrival_rate_lines_per_s": round(rate, 1),
        "burst": burst,
        "deadline_ms": OPENLOOP_DEADLINE_MS,
        "n": sent,
        "elapsed_s": round(elapsed, 3),
        "achieved_lines_per_s": round(sent / max(elapsed, 1e-9), 1),
        "occupancy_mean": round(d_occ / d_n, 4) if d_n else None,
        "dispatches": d_n,
        "releases": after["releases"],
        "queue_wait_max_s": after["max_wait_s"],
        "queue_wait_mean_s": after["mean_wait_s"],
        "warm_buckets": after["warm_buckets"],
        "alerts": alerts,
    }


def main() -> int:
    from detectmateservice_tpu.utils.backend import (BackendUnavailable,
                                                     apply_platform_pin,
                                                     request_platform)

    request_platform("tpu")
    try:
        apply_platform_pin()
    except BackendUnavailable as exc:
        print(f"bench: no accelerator — {exc}", file=sys.stderr)
        return 1
    n_bench = int(sys.argv[1]) if len(sys.argv) > 1 else FULL_N
    print(json.dumps(run(n_bench)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
