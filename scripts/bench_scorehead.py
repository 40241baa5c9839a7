"""Scoring-head microbench: XLA einsum+logsumexp vs the fused Pallas
online-logsumexp kernel (ops/scorehead.py), and the whole scoring call per
bucket with either head.

Kernel rows: N = B·S rows of hidden state against C embeddings — the
served exact head (32768 rows x 32 positions against V = 32768, D = 256),
one chunk of the einsum route, and the candidate-path shapes. The XLA
path materializes the [N, C] logits between matmul and reduce; the kernel
keeps them in VMEM — on a chip the delta is HBM traffic, so run this ON
TPU. ``models/base.py::head_route`` holds what was decided from it
(PERF.md section 6, PR 25; ROADMAP D5).

Measurement protocol: the harness (a) chains CHAIN data-dependent
evaluations inside one jit (the k-th call consumes a perturbation derived
from the (k-1)-th result, so XLA cannot CSE or reorder them), (b) fetches
the chained scalar with ``float()`` inside the timed region, and (c) reports
the SLOPE between a short and a long chain — per-op time with the
per-call dispatch and fetch floor cancelled:
``(T(chain) - T(4)) / (chain - 4)``.

``--buckets`` instead times ``LogBERTScorer.score`` at the flagship shape
(dim 256, depth 4, V = 32768, S = 32) for every power-of-two bucket from 32
to 32768 rows, ``head_impl: einsum`` against ``pallas`` in one process,
with the largest score difference between the two: the table the ``auto``
rule is set from.

Usage: python scripts/bench_scorehead.py [chain]
       python scripts/bench_scorehead.py --buckets
       JAX_PLATFORMS=cpu python scripts/bench_scorehead.py  # interpret-mode smoke
"""
from __future__ import annotations

import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


_SHORT_CHAIN = 4


def bench_buckets() -> None:
    """One JSON line per bucket: median ms of the whole scoring call with
    the einsum head and with the fused head, and how far the scores part."""
    import jax
    import numpy as np

    from detectmateservice_tpu.models.logbert import (LogBERTConfig,
                                                      LogBERTScorer)

    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"--buckets times the chip; jax reports {device.platform!r}")
    einsum = LogBERTScorer(LogBERTConfig(head_impl="einsum"))
    fused = LogBERTScorer(LogBERTConfig(head_impl="pallas"))
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
            ts.append((time.perf_counter() - t0) * 1000)
        return statistics.median(ts)

    rows = 32
    while rows <= 32768:
        tokens = jax.device_put(rng.integers(
            1, cfg.vocab_size, (rows, cfg.seq_len)).astype(np.uint16), device)
        repeats = 5 if rows >= 8192 else 20
        out = {"bucket": rows, "device": device.device_kind,
               "einsum_ms": round(median_ms(einsum, tokens, repeats), 3),
               "pallas_ms": round(median_ms(fused, tokens, repeats), 3)}
        out["speedup"] = round(out["einsum_ms"] / out["pallas_ms"], 2)
        out["max_abs_score_diff"] = float(np.max(np.abs(
            np.asarray(einsum.score(params, tokens))
            - np.asarray(fused.score(params, tokens)))))
        jax.eval_shape(auto._score_impl, params, tokens)
        out["auto"] = auto.head_routes[rows]
        print(json.dumps(out), flush=True)
        rows *= 2


def main() -> None:
    if "--buckets" in sys.argv[1:]:
        bench_buckets()
        return
    chain = int(sys.argv[1]) if len(sys.argv) > 1 else 36
    if chain <= _SHORT_CHAIN:
        sys.exit(f"chain must exceed {_SHORT_CHAIN} (the short-chain "
                 f"baseline the slope subtracts); got {chain}")
    import jax
    import jax.numpy as jnp
    import numpy as np

    from detectmateservice_tpu.ops.scorehead import candidate_lse

    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"
    rng = np.random.default_rng(0)
    # each row's XLA baseline is the SHIPPED einsum route for that path
    # (models/base.py): the candidate head computes bf16 logits + the
    # low-precision lse; the exact head computes fp32 logits +
    # jax.nn.logsumexp — A/B'ing pallas against anything else would decide
    # the auto route on numbers head_impl: auto never produces
    shapes = [
        # (label, N, C, D, baseline) — N = B*S for the shipped batch shapes
        # the served exact head, whole: 32768 rows x 32 positions. The XLA
        # side maps over 32768-row chunks, the einsum route's own schedule
        ("exact-head served 32768 x 32 rows, V=32768, D=256", 32768 * 32,
         32768, 256, "exact"),
        ("logbert-16k x 32, C=2048, D=256", 16384 * 32, 2048, 256, "candidate"),
        ("gru-16k x 32, C=2048, D=128", 16384 * 32, 2048, 128, "candidate"),
        # one S-chunk of the shipped exact path (the chunk budget caps
        # [rows, V] fp32 at 1 GB, models/base.py _CHUNK_ELEMENT_BUDGET):
        # the baseline here IS the per-chunk compute the einsum route runs
        ("exact-head chunk 8192 rows, V=32768, D=256", 8192, 32768, 256,
         "exact"),
        ("small (CPU-safe)", 4096, 512, 128, "candidate"),
    ] if on_tpu else [("small (CPU-safe)", 4096, 512, 128, "candidate")]

    def xla_lse_candidate(h, e):
        logits = jax.lax.dot_general(
            h, e, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.bfloat16)
        m = jnp.max(logits, axis=-1, keepdims=True)
        s = jnp.sum(jnp.exp(logits - m), axis=-1, dtype=jnp.float32)
        return jnp.log(s) + m[..., 0].astype(jnp.float32)

    def xla_lse_exact(h, e):
        def one_chunk(h_c):
            logits = jax.lax.dot_general(
                h_c, e, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            return jax.nn.logsumexp(logits, axis=-1)

        # the einsum route scans chunks of at most 2**28 logits
        # (models/base.py _CHUNK_ELEMENT_BUDGET)
        n = h.shape[0]
        chunk = max(1, (1 << 28) // e.shape[0])
        if n <= chunk:
            return one_chunk(h)
        return jax.lax.map(one_chunk, h.reshape(n // chunk, chunk, -1)
                           ).reshape(n)

    def chained(single, k):
        """k data-dependent evals of ``single`` in one jitted program:
        each iteration perturbs h by a scalar derived from the previous
        result, so the compiler must run all k matmul+lse passes."""
        def run(h, e):
            def body(_, carry):
                eps, acc = carry
                out = single(h + eps, e)
                # tiny, value-dependent perturbation: keeps the numerics
                # intact (|eps| ~ 1e-6) while defeating CSE
                return ((jnp.mean(out) * 1e-9).astype(jnp.bfloat16),
                        acc + out[0])
            return jax.lax.fori_loop(
                0, k, body, (jnp.bfloat16(0.0), jnp.float32(0.0)))[1]
        return jax.jit(run)

    def timed_ms(fn, h, e, repeats: int = 5) -> float:
        """Median wall ms with the value FETCHED inside the timed region
        (block_until_ready alone may not execute on this backend)."""
        float(fn(h, e))  # compile + first fetch
        ts = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            float(fn(h, e))
            ts.append((time.perf_counter() - t0) * 1000)
        return statistics.median(ts)

    short = _SHORT_CHAIN

    def pal_single(h, e):
        return candidate_lse(h, e, interpret=not on_tpu)

    full_chain = chain
    for label, n, c, d, baseline in shapes:
        # 0.1-0.4 s an op at the served shape: a short long-chain is enough
        chain = min(full_chain, 12) if n * c >= 1 << 34 else full_chain
        h = jnp.asarray(rng.normal(size=(n, d)), jnp.bfloat16)
        e = jnp.asarray(rng.normal(size=(c, d)) * d ** -0.5, jnp.bfloat16)
        # ONE definition per path, shared by parity check and timing — the
        # two must measure the same program
        xla_single = xla_lse_exact if baseline == "exact" else xla_lse_candidate
        # parity first — a fast wrong kernel is worthless. The XLA side
        # exps in bf16, the kernel in fp32, so ~0.15 of drift is the two
        # approximations disagreeing; past 0.3 the kernel is WRONG and the
        # speedup must not be reported as actionable.
        err = float(jnp.max(jnp.abs(jax.jit(xla_single)(h, e)
                                    - jax.jit(pal_single)(h, e))))
        parity_ok = err < 0.3
        out = {"shape": label, "n": n, "c": c, "d": d, "chain": chain,
               "platform": platform, "max_abs_err": round(err, 5),
               "parity": "ok" if parity_ok else "FAIL"}
        slope_ok = True
        for name, single in (("xla_ms", xla_single), ("pallas_ms", pal_single)):
            t_short = timed_ms(chained(single, short), h, e)
            t_long = timed_ms(chained(single, chain), h, e)
            # slope protocol sanity: a noisy median-of-5 can yield
            # t_long < t_short, and the resulting negative ms/op would
            # print a sign-flipped "speedup" as if it were valid
            if t_long <= t_short:
                slope_ok = False
            out[name] = round((t_long - t_short) / (chain - short), 3)
        if not slope_ok:
            out["slope"] = "unreliable"
        if parity_ok and slope_ok:
            out["speedup"] = round(out["xla_ms"] / max(out["pallas_ms"], 1e-9), 2)
        print(json.dumps(out), flush=True)
        if not parity_ok:
            print(f"# PARITY FAIL on {label}: do NOT act on the timing above",
                  file=sys.stderr)


if __name__ == "__main__":
    main()
