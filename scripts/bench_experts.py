"""Expert-layer microbench on the chip: the routed experts' part of one
expert layer at a benchmark configuration's published widths (``--config``:
``benchmark/configs/kanana2-30b-a3b-ep8.json`` — D 2048, experts 768 wide,
16 of 128 held, 6 a token — or ``lfm2-24b-a2b-ep8.json`` — 1,536 wide, 8 of
64 held, 4 a token), N tokens, under the router's own (even) routing and
under a router biased to send every token to held experts.

The layer is ops/experts.py as served: assignments sorted by expert, the
held ones first, walked in chunks under ``lax.cond``; grouped matmuls
(``jax.lax.ragged_dot``); then the way back to the tokens, by either form
(``--ways``): ``segment_sum`` (each chunk's rows re-ordered by token, one
row gather, the kernel ``segment_sum_add``: what ONE TPU runs, served
and in the fit) and ``scatter_add`` (the colliding scatter it replaced
there; what the CPU and a mesh run). Each line gives the whole routed
part's milliseconds and, beside it, the combine of one full live chunk
alone. What lost against
the sorted walk on the v5e (a gather combine out of an [N*K, D] buffer;
every held expert over every token: PR 27) and against the kernel (K - 1
shifted adds over the token-ordered chunk, then an N-row gather through
each token's first row, or a unique sorted scatter of the head rows: PR 32)
is in PERF.md section 6 with its numbers.

``--calls`` times the whole scoring call of the configuration per bucket
instead (random weights at its shape) as the TPU routes it, and the fit's
32-row train step; the scatter-add's whole call is the parent commit's
(232.44 / 202.14 ms at 1024 rows where the kernel's read 214.30 / 182.10:
PERF.md section 6, PR 32).

One JSON line per reading; needs the TPU and exits without one:
    python scripts/bench_experts.py [--config lfm2-24b-a2b-ep8]
        [--tokens 32768] [--calls]
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CONFIGS = ("kanana2-30b-a3b-ep8", "lfm2-24b-a2b-ep8")
WAYS = ("scatter_add", "segment_sum")


def timed(fn, *args, reps: int = 5) -> float:
    import jax

    jax.block_until_ready(fn(*args))
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        out.append(time.perf_counter() - t0)
    return 1e3 * statistics.median(out)


def build_scorer(config: str):
    """The scorer of ``benchmark/configs/<config>.json`` as the detector
    builds it (``scorer_families.FAMILIES``), and the detector's block."""
    from benchmark.lib.manifest import read_json
    from detectmateservice_tpu.library.detectors.scorer_families import \
        FAMILIES

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    (block,) = read_json(os.path.join(
        repo, "benchmark", "configs", config + ".json"))[
            "stages"]["detector"]["component"]["detectors"].values()
    cfg = types.SimpleNamespace(**{
        "score_topk": 0, "attn_impl": "auto", "head_impl": "auto", **block})
    return FAMILIES[block["model"]].build(cfg, {}), block


def bench_layer(config: str, tokens: int, ways) -> None:
    import jax
    import jax.numpy as jnp

    from detectmateservice_tpu.ops import experts as ops

    scorer, _ = build_scorer(config)
    arch = scorer.config.arch
    spec, d = arch.expert_spec, arch.hidden_size
    m, held, k = spec.width, spec.held, spec.top_k
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(keys[0], (tokens, d), jnp.bfloat16)
    router = 0.02 * jax.random.normal(keys[1], (d, spec.router_experts),
                                      jnp.float32)
    gate, up = (0.02 * jax.random.normal(key, (held, d, m), jnp.bfloat16)
                for key in keys[2:4])
    down = 0.02 * jax.random.normal(keys[4], (held, m, d), jnp.bfloat16)
    valid = jnp.ones((tokens,), bool)
    chunk = ops.chunk_rows_for(tokens, k)
    y = jax.random.normal(keys[5], (chunk, d), jnp.float32)

    @jax.jit
    def sorted_both_ways(routing):
        plan = ops.dispatch(routing, 0, held)
        return plan, ops.token_order(plan, tokens, chunk)

    for name, bias_held in (("even", 0.0), ("all_held", 50.0)):
        bias = jnp.zeros((spec.router_experts,)).at[:held].set(bias_held)
        route = jax.jit(lambda x: ops.route(
            x, router, bias, valid, top_k=k,
            norm_topk_prob=spec.norm_topk_prob, scaling=spec.scaling,
            scoring_func=spec.scoring_func, norm_eps=spec.norm_eps))
        routing = route(x)
        n_held = int(ops.held_counts(routing.experts, 0, held).sum())
        live = -(-n_held // chunk)
        plan, back = sorted_both_ways(routing)
        line = {"config": config, "routing": name, "tokens": tokens,
                "held_assignments": n_held, "chunk_rows": chunk,
                "live_chunks": live, "route_ms": timed(route, x),
                "least_ms_at_peak": 1e3 * n_held * 3 * d * m * 2 / 197e12}
        for way in ways:
            served = jax.jit(lambda x, r, gate, up, down, way=way:
                             ops.routed_experts(x, r, gate, up, down,
                                                combine=way)[0])
            # the combine of one full live chunk alone, the accumulator
            # given up to it as the walk's carry is
            alone = jax.jit(lambda acc, y, plan, back, way=way: ops.way_back(
                way, acc, y, plan, back, jnp.int32(0), tokens),
                donate_argnums=0)
            acc = alone(jnp.zeros((tokens, d), jnp.float32), y, plan, back)
            jax.block_until_ready(acc)
            t0 = time.perf_counter()
            for _ in range(10):
                acc = alone(acc, y, plan, back)
            jax.block_until_ready(acc)
            combine_ms = 1e2 * (time.perf_counter() - t0)
            del acc
            line[way] = {"ms": timed(served, x, routing, gate, up, down),
                         "combine_ms_a_live_chunk": combine_ms}
        print(json.dumps(line), flush=True)


def bench_calls(config: str) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    scorer, block = build_scorer(config)
    params, opt_state = jax.jit(scorer.init)(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    for rows in (32, 256, 512, 1024):
        tokens = rng.integers(3, block["vocab_size"],
                              size=(rows, block["seq_len"])).astype(np.uint16)
        tokens[:, 0] = 2
        tokens = jnp.asarray(tokens)
        ms = timed(scorer._score, params, tokens)
        _, counts = scorer._score(params, tokens)
        print(json.dumps({"config": config, "rows": rows, "call_ms": ms,
                          "counts": [int(c) for c in counts],
                          "expert_route": scorer.expert_routes.get(rows),
                          "attn_route": scorer.attn_routes.get(rows),
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
    print(json.dumps({"config": config,
                      "train_step_ms": 1e3 * (time.perf_counter() - t0) / 5,
                      "loss": float(loss)}), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", choices=CONFIGS, default=CONFIGS[0])
    ap.add_argument("--tokens", type=int, default=32768)
    ap.add_argument("--calls", action="store_true")
    ap.add_argument("--ways", default=",".join(WAYS),
                    help="the layer bench's combines, forced by name")
    args = ap.parse_args()
    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"bench_experts: no TPU (jax reports {device.platform!r}); "
                 "a millisecond here would not be the chip's")
    print(json.dumps({"device": str(device), "platform": device.platform}),
          flush=True)
    if args.calls:
        bench_calls(args.config)
    else:
        bench_layer(args.config, args.tokens, args.ways.split(","))


if __name__ == "__main__":
    main()
