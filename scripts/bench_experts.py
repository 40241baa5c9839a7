"""Expert-layer microbench on the chip: the routed experts' part of one
expert layer at the published widths (D 2048, experts 768 wide, 16 of 128
held, 6 a token), N tokens, under the router's own (even) routing and under
a router biased to send every token to held experts.

The layer is ops/experts.py as served: assignments sorted by expert, the
held ones first, walked in chunks under ``lax.cond``; grouped matmuls
(``jax.lax.ragged_dot``); scatter-add back to the tokens. What lost against
it on the v5e (a gather combine out of an [N*K, D] buffer; every held expert
over every token) is in PERF.md section 6, PR 27, with its numbers.

``--calls`` times the whole ``moe_mla`` scoring call per bucket instead
(random weights at the benchmark configuration's shape), with ``attn_impl:
einsum`` and as ``auto`` routes it (latent attention's two-width kernel from
256 rows), and how far the two calls' scores part.

One JSON line per reading; run it ON the TPU:
    python scripts/bench_experts.py [--tokens 32768] [--calls]
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

D, M, HELD, ROUTER, K = 2048, 768, 16, 128, 6


def timed(fn, *args, reps: int = 5) -> float:
    import jax

    jax.block_until_ready(fn(*args))
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        out.append(time.perf_counter() - t0)
    return 1e3 * statistics.median(out)


def bench_layer(tokens: int) -> None:
    import jax
    import jax.numpy as jnp

    from detectmateservice_tpu.ops import experts as ops

    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(keys[0], (tokens, D), jnp.bfloat16)
    router = 0.02 * jax.random.normal(keys[1], (D, ROUTER), jnp.float32)
    gate, up = (0.02 * jax.random.normal(k, (HELD, D, M), jnp.bfloat16)
                for k in keys[2:4])
    down = 0.02 * jax.random.normal(keys[4], (HELD, M, D), jnp.bfloat16)
    valid = jnp.ones((tokens,), bool)
    chunk = ops.chunk_rows_for(tokens, K)
    for name, bias_held in (("even", 0.0), ("all_held", 50.0)):
        bias = jnp.zeros((ROUTER,)).at[:HELD].set(bias_held)
        route = jax.jit(lambda x: ops.route(
            x, router, bias, valid, top_k=K, norm_topk_prob=True,
            scaling=2.448))
        routing = route(x)
        served = jax.jit(lambda x, r: ops.routed_experts(
            x, r, gate, up, down)[0])
        held = int(ops.held_counts(routing.experts, 0, HELD).sum())
        print(json.dumps({"routing": name, "tokens": tokens,
                          "held_assignments": held, "chunk_rows": chunk,
                          "route_ms": timed(route, x),
                          "ms": timed(served, x, routing),
                          "least_ms_at_peak": 1e3 * held * 3 * D * M * 2 / 197e12}),
              flush=True)


def bench_calls() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib.manifest import read_json
    from detectmateservice_tpu.models.moe_mla import (MoEMLAArch,
                                                      MoEMLAConfig,
                                                      MoEMLAScorer)

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    config = read_json(os.path.join(repo, "benchmark", "configs",
                                    "kanana2-30b-a3b-ep8.json"))
    (block,) = config["stages"]["detector"]["component"]["detectors"].values()
    einsum, scorer = (MoEMLAScorer(MoEMLAConfig(
        arch=MoEMLAArch.from_mapping(block["arch"]),
        vocab_size=block["vocab_size"], seq_len=block["seq_len"],
        attn_impl=impl)) for impl in ("einsum", "auto"))
    params = jax.jit(lambda k: scorer.init(k)[0])(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    for rows in (32, 256, 512, 1024):
        tokens = rng.integers(3, block["vocab_size"],
                              size=(rows, block["seq_len"])).astype(np.uint16)
        tokens[:, 0] = 2
        tokens = jnp.asarray(tokens)
        ms = timed(scorer._score, params, tokens)
        scores, counts = scorer._score(params, tokens)
        gap = np.asarray(einsum._score(params, tokens)[0]) - np.asarray(scores)
        print(json.dumps({"rows": rows, "call_ms": ms,
                          "einsum_call_ms": timed(einsum._score, params,
                                                  tokens),
                          "score_gap_max_nats": float(np.abs(gap).max()),
                          "score_gap_rms_nats": float(np.sqrt(
                              (gap ** 2).mean())),
                          "counts": [int(c) for c in counts],
                          "attn_route": scorer.attn_routes.get(rows),
                          "head_route": scorer.head_routes.get(rows),
                          "lines_per_s": 1e3 * rows / ms}), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tokens", type=int, default=32768)
    ap.add_argument("--calls", action="store_true")
    args = ap.parse_args()
    import jax

    print(json.dumps({"device": str(jax.devices()[0]),
                      "platform": jax.devices()[0].platform}), flush=True)
    if args.calls:
        bench_calls()
    else:
        bench_layer(args.tokens)


if __name__ == "__main__":
    main()
