"""A/B the upload/dispatch-overlap lever (`upload_workers`) on the in-process
detector contract: with dispatch inline, every device_put + jit call
serializes with host featurize on the engine thread.

Runs the same fused process_frames hot path as bench.py's run() at each
workers setting and prints one JSON line per setting plus a verdict line.
Honest-measurement notes carried over from bench.py: flush_final() joins the
host-bucket warm thread before timing; frames are packed outside the timed
loop (sender-side cost).

Usage:
    python scripts/bench_overlap.py [N] [--workers 0 1]
    JAX_PLATFORMS=cpu python scripts/bench_overlap.py 8192   # mechanics only
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import bench as B  # noqa: E402


def measure(n_bench: int, workers: int) -> dict:
    from detectmateservice_tpu.engine.framing import pack_batch

    n_train = B.BENCH_SCORER_CONFIG["data_use_training"]
    batch = B.BENCH_SCORER_CONFIG["max_batch"]
    import jax

    dtype = "float32" if jax.default_backend() == "cpu" else "auto"
    det = B.build_bench_detector(workers=workers, dtype=dtype)
    det.setup_io()
    import jax

    platform = jax.devices()[0].platform

    train_msgs = B.make_messages(n_train, anomaly_rate=0.0)
    for start in range(0, n_train, batch):
        det.process_batch(train_msgs[start:start + batch])
    det.flush()

    bench_msgs = B.make_messages(n_bench, anomaly_rate=0.01, seed=1)
    det.process_batch(bench_msgs[:batch])
    det.flush_final()

    frame_n = 512
    frames = [pack_batch(bench_msgs[i:i + frame_n])
              for i in range(0, n_bench, frame_n)]
    frames_per_call = max(1, batch // frame_n)

    t0 = time.perf_counter()
    alerts = 0
    for start in range(0, len(frames), frames_per_call):
        out, _m, _l = det.process_frames(frames[start:start + frames_per_call])
        alerts += sum(o is not None for o in out)
    alerts += sum(o is not None for o in det.flush())
    elapsed = time.perf_counter() - t0
    return {"upload_workers": workers, "platform": platform,
            "lines_per_s": round(n_bench / elapsed, 1), "alerts": alerts,
            "n": n_bench, "elapsed_s": round(elapsed, 3)}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("n", nargs="?", type=int, default=131072)
    ap.add_argument("--workers", type=int, nargs="+", default=[0, 1])
    args = ap.parse_args()

    results = [measure(args.n, w) for w in args.workers]
    for r in results:
        print(json.dumps(r), flush=True)
    if len(results) >= 2:
        base = results[0]["lines_per_s"]
        best = max(results[1:], key=lambda r: r["lines_per_s"])
        print(json.dumps({
            "verdict": "overlap_wins" if best["lines_per_s"] > base * 1.02
            else ("parity" if best["lines_per_s"] > base * 0.98
                  else "inline_wins"),
            "speedup": round(best["lines_per_s"] / max(base, 1e-9), 3),
            "alerts_match": all(r["alerts"] == results[0]["alerts"]
                                for r in results),
        }), flush=True)


if __name__ == "__main__":
    main()
