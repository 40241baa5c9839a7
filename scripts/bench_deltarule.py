"""Gated-delta-rule microbench on the chip, and the ``moe_delta`` scoring
call per bucket.

Default: the delta rule's core (ops/deltarule.py: 16 key and 32 value heads
of 128, lines of 32 positions, one chunk a line) per row count — the kernel
``gated_delta`` as served from 256 rows beside the chunked form, each with
its share of the memory floor (q, k, v in and o out once in bfloat16, the
gates in float32, at 819 GB/s) — the chunked form's triangular inverse
alone, how far either parts from the position-by-position scan at 256 rows,
and the 4-tap convolution with SiLU over the 8,192 q, k, v channels.

``--calls`` times the whole ``moe_delta`` scoring call per bucket instead
(random weights at the benchmark configuration's shape) and the fit's
donated 32-row train step.

One JSON line per reading; run it ON the TPU:
    python scripts/bench_deltarule.py [--calls]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from scripts.bench_experts import build_scorer, timed  # noqa: E402

HK, HV, DK, SEQ, TAPS = 16, 32, 128, 32, 4
BYTES_PER_S = 819e9
ROWS = (32, 256, 512, 1024)     # the fit's step and the served buckets


def bench_core() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from detectmateservice_tpu.ops.deltarule import (gated_delta_rule,
                                                     unit_lower_inverse)
    from detectmateservice_tpu.ops.shortconv import causal_conv_silu

    def form(impl):
        return jax.jit(lambda q, k, v, g, b: gated_delta_rule(
            q, k, v, g, b, SEQ, impl=impl))

    core, fused, scan = form("chunked"), form("fused"), form("scan")
    for rows in ROWS:
        n = rows * SEQ
        keys = jax.random.split(jax.random.PRNGKey(rows), 6)
        q, k = (jax.random.normal(key, (n, HK, DK), jnp.bfloat16)
                for key in keys[:2])
        v = jax.random.normal(keys[2], (n, HV, DK), jnp.bfloat16)
        g = -jax.random.uniform(keys[3], (n, HV), jnp.float32, 0.0, 3.0)
        beta = jax.random.uniform(keys[4], (n, HV), jnp.float32)
        floor_ms = 1e3 * (2 * n * (2 * HK + 2 * HV) * DK
                          + 4 * n * 2 * HV) / BYTES_PER_S
        ms = timed(core, q, k, v, g, beta)
        a = jnp.tril(jax.random.normal(keys[5], (SEQ, SEQ, rows * HV),
                                       jnp.float32) * 0.1, -1)
        fused_ms = timed(fused, q, k, v, g, beta)
        line = {"core": "gated_delta_rule", "rows": rows,
                "floor_ms": floor_ms, "chunked_ms": ms,
                "chunked_share_of_floor": floor_ms / ms,
                "fused_ms": fused_ms,
                "fused_share_of_floor": floor_ms / fused_ms,
                "inverse_ms": timed(jax.jit(unit_lower_inverse), a)}
        if rows == 256:
            want = np.asarray(scan(q, k, v, g, beta))
            line.update(
                scan_ms=timed(scan, q, k, v, g, beta),
                chunked_max_abs_gap_to_scan=float(np.abs(
                    np.asarray(core(q, k, v, g, beta)) - want).max()),
                fused_max_abs_gap_to_scan=float(np.abs(
                    np.asarray(fused(q, k, v, g, beta)) - want).max()))
        print(json.dumps(line), flush=True)
    conv = jax.jit(causal_conv_silu, static_argnames=("seq",))
    for rows in ROWS[1::2]:
        n, width = rows * SEQ, (2 * HK + HV) * DK
        kx, kw = jax.random.split(jax.random.PRNGKey(rows))
        x = jax.random.normal(kx, (n, width), jnp.bfloat16)
        weight = jax.random.normal(kw, (width, TAPS), jnp.float32)
        floor_ms = 1e3 * 2 * 2 * n * width / BYTES_PER_S
        ms = timed(conv, x, weight, SEQ)
        print(json.dumps({"core": "causal_conv_silu", "rows": rows,
                          "floor_ms": floor_ms, "ms": ms,
                          "share_of_floor": floor_ms / ms}), flush=True)


def bench_calls() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    scorer, block = build_scorer("qwen3-next-80b-a3b-ep16")
    params, opt_state = jax.jit(scorer.init)(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    for rows in ROWS:
        tokens = rng.integers(3, block["vocab_size"],
                              size=(rows, block["seq_len"])).astype(np.uint16)
        tokens[:, 0] = 2
        tokens = jnp.asarray(tokens)
        t0 = time.perf_counter()
        scores, counts = scorer._score(params, tokens)
        jax.block_until_ready(scores)
        first_s = time.perf_counter() - t0
        ms = timed(scorer._score, params, tokens)
        print(json.dumps({"rows": rows, "call_ms": ms,
                          "first_call_s": first_s,
                          "counts": [int(c) for c in counts],
                          "finite": bool(np.isfinite(np.asarray(scores)).all()),
                          "attn_route": scorer.attn_routes.get(rows),
                          "delta_route": scorer.delta_routes.get(rows),
                          "head_route": scorer.head_routes.get(rows),
                          "expert_route": scorer.expert_routes.get(rows),
                          "lines_per_s": 1e3 * rows / ms}), flush=True)
    tokens = jnp.asarray(rng.integers(3, block["vocab_size"], size=(
        32, block["seq_len"])).astype(np.int32))
    key = jax.random.PRNGKey(1)
    t0 = time.perf_counter()
    params, opt_state, loss = scorer.train_step(params, opt_state, key,
                                                tokens, donate=True)
    jax.block_until_ready(loss)
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(5):
        params, opt_state, loss = scorer.train_step(params, opt_state, key,
                                                    tokens, donate=True)
    jax.block_until_ready(loss)
    stats = jax.devices()[0].memory_stats() or {}
    print(json.dumps({"train_step_ms": 1e3 * (time.perf_counter() - t0) / 5,
                      "first_step_s": first_s, "loss": float(loss),
                      "peak_bytes_in_use": stats.get("peak_bytes_in_use")}),
          flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--calls", action="store_true")
    args = ap.parse_args()
    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"bench_deltarule: no TPU (jax reports {device.platform!r}); "
                 "a millisecond here would not be the chip's")
    print(json.dumps({"device": str(device), "platform": device.platform}),
          flush=True)
    if args.calls:
        bench_calls()
    else:
        bench_core()


if __name__ == "__main__":
    main()
