"""Gated-short-convolution microbench on the chip, and the ``moe_conv``
scoring call per bucket.

Default: the operator's elementwise core (ops/shortconv.py) at the published
width (D 2048, 3 taps, S 32) per row count — the Pallas kernel
(``gated_conv``) against XLA's fusion of the plain form, each with its share
of the memory floor (B, C and x̃ in, the result out, once, in bfloat16, at
819 GB/s) and how far the two part — then grouped-query attention's core
(32 query heads, 8 key/value heads of 64; the grouped einsum) alone.

``--calls`` times the whole ``moe_conv`` scoring call per bucket instead
(random weights at the benchmark configuration's shape), with ``conv_impl:
xla`` and as ``auto`` routes it (the kernel from 256 rows), how far the two
calls' scores part, and the fit's donated 32-row train step.

One JSON line per reading; run it ON the TPU:
    python scripts/bench_shortconv.py [--calls]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from scripts.bench_experts import timed  # noqa: E402

D, TAPS, SEQ, HEADS, KV_HEADS = 2048, 3, 32, 32, 8
BYTES_PER_S = 819e9


def bench_core() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from detectmateservice_tpu.ops.attention import grouped_query_attention
    from detectmateservice_tpu.ops.shortconv import (gated_conv,
                                                     gated_conv_xla)

    plain = jax.jit(gated_conv_xla, static_argnames=("seq",))
    for rows in (256, 512, 1024):
        tokens = rows * SEQ
        kb, kw = jax.random.split(jax.random.PRNGKey(rows))
        bcx = jax.random.normal(kb, (tokens, 3 * D), jnp.bfloat16)
        weight = jax.random.normal(kw, (D, TAPS), jnp.float32)
        floor_ms = 1e3 * 2 * 4 * tokens * D / BYTES_PER_S
        fused_ms = timed(gated_conv, bcx, weight, SEQ)
        xla_ms = timed(plain, bcx, weight, SEQ)
        gap = (np.asarray(gated_conv(bcx, weight, SEQ), np.float32)
               - np.asarray(plain(bcx, weight, SEQ), np.float32))
        print(json.dumps({
            "core": "gated_conv", "rows": rows, "floor_ms": floor_ms,
            "fused_ms": fused_ms, "xla_ms": xla_ms,
            "fused_share_of_floor": floor_ms / fused_ms,
            "xla_share_of_floor": floor_ms / xla_ms,
            "max_abs_gap": float(np.abs(gap).max())}), flush=True)
    for rows in (256, 1024):
        keys = jax.random.split(jax.random.PRNGKey(rows), 3)
        q = jax.random.normal(keys[0], (rows * SEQ, D), jnp.bfloat16)
        k, v = (jax.random.normal(key, (rows * SEQ, D // 4), jnp.bfloat16)
                for key in keys[1:])
        mask = jnp.ones((rows, SEQ), bool)
        core = jax.jit(lambda q, k, v, m: grouped_query_attention(
            q, k, v, m, HEADS, KV_HEADS, 1e6))
        print(json.dumps({"core": "grouped_query einsum", "rows": rows,
                          "ms": timed(core, q, k, v, mask)}), flush=True)


def bench_calls() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib.manifest import read_json
    from detectmateservice_tpu.models.moe_conv import (MoEConvArch,
                                                       MoEConvConfig,
                                                       MoEConvScorer)

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    config = read_json(os.path.join(repo, "benchmark", "configs",
                                    "lfm2-24b-a2b-ep8.json"))
    (block,) = config["stages"]["detector"]["component"]["detectors"].values()
    plain, scorer = (MoEConvScorer(MoEConvConfig(
        arch=MoEConvArch.from_mapping(block["arch"]),
        vocab_size=block["vocab_size"], seq_len=block["seq_len"],
        conv_impl=impl)) for impl in ("xla", "auto"))
    params, opt_state = jax.jit(scorer.init)(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    for rows in (32, 256, 512, 1024):
        tokens = rng.integers(3, block["vocab_size"],
                              size=(rows, block["seq_len"])).astype(np.uint16)
        tokens[:, 0] = 2
        tokens = jnp.asarray(tokens)
        ms = timed(scorer._score, params, tokens)
        scores, counts = scorer._score(params, tokens)
        gap = np.asarray(plain._score(params, tokens)[0]) - np.asarray(scores)
        print(json.dumps({"rows": rows, "call_ms": ms,
                          "xla_conv_call_ms": timed(plain._score, params,
                                                    tokens),
                          "score_gap_max_nats": float(np.abs(gap).max()),
                          "counts": [int(c) for c in counts],
                          "attn_route": scorer.attn_routes.get(rows),
                          "conv_route": scorer.conv_routes.get(rows),
                          "head_route": scorer.head_routes.get(rows),
                          "lines_per_s": 1e3 * rows / ms}), flush=True)
    tokens = jnp.asarray(rng.integers(3, block["vocab_size"], size=(
        32, block["seq_len"])).astype(np.int32))
    key = jax.random.PRNGKey(1)
    params, opt_state, loss = scorer.train_step(params, opt_state, key,
                                                tokens, donate=True)
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(5):
        params, opt_state, loss = scorer.train_step(params, opt_state, key,
                                                    tokens, donate=True)
    jax.block_until_ready(loss)
    stats = jax.devices()[0].memory_stats() or {}
    print(json.dumps({"train_step_ms": 1e3 * (time.perf_counter() - t0) / 5,
                      "loss": float(loss),
                      "peak_bytes_in_use": stats.get("peak_bytes_in_use")}),
          flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--calls", action="store_true")
    args = ap.parse_args()
    import jax

    print(json.dumps({"device": str(jax.devices()[0]),
                      "platform": jax.devices()[0].platform}), flush=True)
    if args.calls:
        bench_calls()
    else:
        bench_core()


if __name__ == "__main__":
    main()
