#!/usr/bin/env python
"""Benchmark the pallas attention kernels against the einsum path.

Run on TPU. ``python scripts/bench_flash.py`` times the flash kernel at long
sequences (informs FLASH_MIN_SEQ in ops/attention.py).

``--short`` times the short-sequence kernel (ops/shortattn.py) at the
scorers' shape — H 4, S 32, D 64, rows 256 ... 32768 — against the einsum
route as ``logbert``'s block ran it (q / k / v split, head-major transposes,
``dot_product_attention``, transpose back): ms a layer's core, and the share
of the memory floor (q, k, v read and the output written once in bfloat16:
2.15 GB = 2.6 ms at 32768 rows and 819 GB/s). Each time is the slope between
a short and a long chain of data-dependent calls inside one jit, so dispatch
and fetch cancel (scripts/bench_scorehead.py's protocol).

``--latent`` times latent attention's causal two-width kernel
(``short_latent_attention``: 32 heads of 128 ‖ 64, values 128, S 32) against
``latent_einsum`` — the head-major copies, rope's slices and concatenations
and the einsum core it replaced — at the sparse-expert scorer's 256-, 512-
and 1024-row buckets: ms a layer's rope and core, and the share of the
memory floor (q, k_nope, v and k_rope read, the output written once: 1.21 GB
= 1.5 ms at 1024 rows).

``--buckets`` times the whole ``LogBERTScorer.score`` call at the flagship
shape per power-of-two bucket, ``attn_impl: einsum`` against ``short``, with
the largest score difference: the table ``attention_route``'s row threshold
is set from (PERF.md section 6, PR 28).
"""
from __future__ import annotations

import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax
import jax.numpy as jnp
import numpy as np


def main() -> None:
    from detectmateservice_tpu.ops.attention import dot_product_attention
    from detectmateservice_tpu.ops.flash import flash_attention

    rng = np.random.default_rng(0)
    print(f"device: {jax.devices()[0]}")
    for (b, h, s, d) in [(8, 4, 128, 64), (8, 4, 512, 64), (4, 4, 1024, 64),
                         (4, 4, 2048, 64), (2, 4, 4096, 64)]:
        q = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.bfloat16)
        k = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.bfloat16)
        v = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.bfloat16)
        mask = jnp.asarray(rng.random((b, s)) > 0.1)

        einsum_fn = jax.jit(lambda q, k, v, m:
                            dot_product_attention(q, k, v, m[:, None, None, :]))
        flash_fn = jax.jit(lambda q, k, v, m: flash_attention(q, k, v, m))

        ref = jax.block_until_ready(einsum_fn(q, k, v, mask))
        out = jax.block_until_ready(flash_fn(q, k, v, mask))
        err = float(jnp.abs(ref.astype(jnp.float32)
                            - out.astype(jnp.float32)).max())

        def timeit(fn, n=20):
            jax.block_until_ready(fn(q, k, v, mask))
            t0 = time.perf_counter()
            for _ in range(n):
                r = fn(q, k, v, mask)
            jax.block_until_ready(r)
            return (time.perf_counter() - t0) / n * 1e3

        te, tf = timeit(einsum_fn), timeit(flash_fn)
        print(f"B{b} H{h} S{s} D{d}: einsum {te:7.3f} ms  flash {tf:7.3f} ms  "
              f"speedup {te / tf:4.2f}x  max_err {err:.3e}")


_SHORT_CHAIN = 4
_HBM_BYTES_PER_S = 819e9   # TPU v5e (benchmark/peaks.json)


def _chained(fn, k):
    """``k`` data-dependent calls of ``fn(qkv, mask)`` in one program: each
    call's mask depends on a probe of the previous result, so none is
    dropped or merged, and the probe is one element."""
    def run(qkv, mask):
        def body(_, carry):
            mask, acc = carry
            probe = fn(qkv, mask)[0, 0, 0].astype(jnp.float32)
            return mask | (probe > 1e30), acc + probe
        return jax.lax.fori_loop(0, k, body, (mask, jnp.float32(0.0)))[1]
    return jax.jit(run)


def _slope_ms(fn, qkv, mask, chain, repeats=5):
    def timed(k):
        run = _chained(fn, k)
        float(run(qkv, mask))
        ts = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            float(run(qkv, mask))
            ts.append((time.perf_counter() - t0) * 1e3)
        return statistics.median(ts)
    return (timed(chain) - timed(_SHORT_CHAIN)) / (chain - _SHORT_CHAIN)


def bench_short() -> None:
    """One JSON line per row count: the einsum route against the kernel."""
    from detectmateservice_tpu.ops.shortattn import (einsum_route,
                                                     short_attention)

    device = jax.devices()[0]
    on_tpu = device.platform == "tpu"
    heads, s, d = 4, 32, 64
    rng = np.random.default_rng(0)
    rows = 256
    while rows <= (32768 if on_tpu else 256):
        qkv = jnp.asarray(rng.standard_normal((rows, s, 3 * heads * d)),
                          jnp.bfloat16)
        lengths = rng.integers(1, s + 1, rows)
        mask = jnp.asarray(np.arange(s)[None] < lengths[:, None])
        routes = {
            "einsum": lambda x, m: einsum_route(x, m, heads),
            "short": lambda x, m: short_attention(x, m, heads, None,
                                                  not on_tpu),
        }
        ref = routes["einsum"](qkv.astype(jnp.float32), mask)
        floor_ms = rows * s * heads * d * 2 * 4 / _HBM_BYTES_PER_S * 1e3
        out = {"rows": rows, "device": device.device_kind,
               "floor_ms": round(floor_ms, 4)}
        chain = 36 if rows <= 4096 else 12
        for name, fn in routes.items():
            got = jax.jit(fn)(qkv, mask).astype(jnp.float32)
            out[f"{name}_max_err"] = round(float(jnp.abs(got - ref).max()), 5)
            ms = _slope_ms(fn, qkv, mask, chain)
            out[f"{name}_ms"] = round(ms, 4)
            out[f"{name}_floor_share"] = round(100 * floor_ms / ms, 1)
        out["speedup"] = round(out["einsum_ms"] / out["short_ms"], 2)
        print(json.dumps(out), flush=True)
        rows *= 2


def bench_latent() -> None:
    """One JSON line per row count: ``latent_einsum`` against the kernel
    (median of ten calls; a call is milliseconds, dispatch a tenth of
    one)."""
    from detectmateservice_tpu.ops.attention import latent_einsum
    from detectmateservice_tpu.ops.shortattn import short_latent_attention

    device = jax.devices()[0]
    on_tpu = device.platform == "tpu"
    heads, nope, rope, dv, s, theta = 32, 128, 64, 128, 32, 1e6
    rng = np.random.default_rng(0)

    def median_ms(fn, *args):
        jax.block_until_ready(fn(*args))
        ts = []
        for _ in range(10):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            ts.append((time.perf_counter() - t0) * 1e3)
        return statistics.median(ts)

    for rows in ((256, 512, 1024) if on_tpu else (8,)):
        n = rows * s
        q, kv, k_rope = (
            jnp.asarray(rng.standard_normal((n, w)), jnp.bfloat16)
            for w in (heads * (nope + rope), heads * (nope + dv), rope))
        lengths = rng.integers(1, s + 1, rows)
        mask = jnp.asarray(np.arange(s)[None] < lengths[:, None])
        einsum = jax.jit(lambda q, kv, kr, m: latent_einsum(
            q, kv, kr, m, heads, nope, theta, True))
        short = jax.jit(lambda q, kv, kr, m: short_latent_attention(
            q, kv, kr, m, heads, nope, theta, True, None, not on_tpu))
        floor_ms = 2 * n * (heads * (2 * nope + rope + 2 * dv) + rope
                            ) / _HBM_BYTES_PER_S * 1e3
        ref = einsum(*(x.astype(jnp.float32) for x in (q, kv, k_rope)), mask)
        out = {"rows": rows, "device": device.device_kind,
               "floor_ms": round(floor_ms, 4)}
        for name, fn in (("einsum", einsum), ("short", short)):
            got = fn(q, kv, k_rope, mask).astype(jnp.float32)
            out[f"{name}_max_err"] = round(float(jnp.abs(got - ref).max()), 5)
            ms = median_ms(fn, q, kv, k_rope, mask)
            out[f"{name}_ms"] = round(ms, 4)
            out[f"{name}_floor_share"] = round(100 * floor_ms / ms, 1)
        out["speedup"] = round(out["einsum_ms"] / out["short_ms"], 2)
        print(json.dumps(out), flush=True)


def bench_buckets() -> None:
    """One JSON line per bucket: median ms of the whole scoring call with
    the einsum attention and with the short kernel (the fused head in
    both), how far the scores part, and what ``auto`` takes."""
    from detectmateservice_tpu.models.logbert import (LogBERTConfig,
                                                      LogBERTScorer)

    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"--buckets times the chip; jax reports {device.platform!r}")
    einsum = LogBERTScorer(LogBERTConfig(attn_impl="einsum"))
    short = LogBERTScorer(LogBERTConfig(attn_impl="short"))
    auto = LogBERTScorer(LogBERTConfig())
    params = jax.device_put(einsum.init(jax.random.PRNGKey(0))[0], device)
    cfg = einsum.config
    rng = np.random.default_rng(0)

    def median_ms(scorer, tokens, repeats):
        jax.block_until_ready(scorer.score(params, tokens))
        ts = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(scorer.score(params, tokens))
            ts.append((time.perf_counter() - t0) * 1e3)
        return statistics.median(ts)

    rows = 32
    while rows <= 32768:
        tokens = rng.integers(1, cfg.vocab_size, (rows, cfg.seq_len))
        lengths = rng.integers(4, cfg.seq_len + 1, rows)
        tokens[np.arange(cfg.seq_len)[None] >= lengths[:, None]] = 0  # PAD
        tokens = jax.device_put(tokens.astype(np.uint16), device)
        repeats = 5 if rows >= 8192 else 20
        out = {"bucket": rows, "device": device.device_kind,
               "einsum_ms": round(median_ms(einsum, tokens, repeats), 3),
               "short_ms": round(median_ms(short, tokens, repeats), 3)}
        out["speedup"] = round(out["einsum_ms"] / out["short_ms"], 2)
        out["max_abs_score_diff"] = float(np.max(np.abs(
            np.asarray(einsum.score(params, tokens))
            - np.asarray(short.score(params, tokens)))))
        jax.eval_shape(auto._score_impl, params, tokens)
        out["auto"] = auto.attn_routes[rows]
        print(json.dumps(out), flush=True)
        rows *= 2


if __name__ == "__main__":
    if "--short" in sys.argv[1:]:
        bench_short()
    elif "--latent" in sys.argv[1:]:
        bench_latent()
    elif "--buckets" in sys.argv[1:]:
        bench_buckets()
    else:
        main()
