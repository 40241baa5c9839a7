"""Throughput sweep across the scorer model families (mlp / gru / logbert).

Measures the full detector contract per family — serialized ParserSchema in,
C featurize, batched jit scoring, alert bytes out — on whatever platform jax
picks (TPU when present). Complements bench.py (which reports the headline
mlp number): this records what switching `model:` costs, so the
signal-vs-FLOPs tradeoff documented in docs/library.md has measured numbers.

Usage: python scripts/bench_models.py [N]
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench as B  # noqa: E402  (message builder reuse)


def run_family(model: str, msgs, train, batch: int = 16384,
               **overrides) -> dict:
    from detectmateservice_tpu.library.detectors import JaxScorerDetector

    cfg = {
        "method_type": "jax_scorer", "auto_config": False, "model": model,
        "data_use_training": len(train), "train_epochs": 2, "async_fit": False,
        "seq_len": 32, "dim": 128, "max_batch": batch, "pipeline_depth": 8,
        "threshold_sigma": 6.0,
    }
    cfg.update(overrides)
    det = JaxScorerDetector(config={"detectors": {"JaxScorerDetector": cfg}})
    det.setup_io()
    det.process_batch(train)
    det.flush_final()
    det.process_batch(msgs[:batch])
    det.flush_final()  # warmup + join host warm thread (see bench.py)

    # measure the fused wire-frame production path (see bench.py): frames
    # packed outside the timed loop, 512 messages per frame
    from detectmateservice_tpu.engine.framing import pack_batch

    frames = [pack_batch(msgs[i:i + 512]) for i in range(0, len(msgs), 512)]
    per_call = max(1, batch // 512)
    n = len(msgs)
    t0 = time.perf_counter()
    alerts = 0
    for start in range(0, len(frames), per_call):
        out, _nm, _nl = det.process_frames(frames[start:start + per_call])
        alerts += sum(o is not None for o in out)
    alerts += sum(o is not None for o in det.flush())
    elapsed = time.perf_counter() - t0
    return {
        "model": model,
        "lines_per_s": round(n / elapsed, 1),
        "elapsed_s": round(elapsed, 3),
        "alerts": alerts,
        "n": n,
        **{k: v for k, v in overrides.items() if k == "score_vocab"},
    }


def main() -> None:
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 131072
    msgs = B.make_messages(n, anomaly_rate=0.01, seed=1)
    train = B.make_messages(2048, anomaly_rate=0.0)
    import jax

    platform = jax.devices()[0].platform
    results = []
    for model, overrides in (
        ("mlp", {}),
        ("gru", {"depth": 1}),
        ("gru", {"depth": 1, "score_vocab": 2048}),
        ("logbert", {"depth": 2, "heads": 4}),
        ("logbert", {"depth": 2, "heads": 4, "score_vocab": 2048}),
    ):
        res = run_family(model, msgs, train, **overrides)
        res["platform"] = platform
        results.append(res)
        print(json.dumps(res), flush=True)
    fastest = max(results, key=lambda r: r["lines_per_s"])
    print(f"# fastest: {fastest['model']} at {fastest['lines_per_s']:,.0f} "
          f"lines/s on {platform}", file=sys.stderr)


if __name__ == "__main__":
    main()
