"""Compile the Pallas kernels on the attached TPU and compare each with its
``jax.numpy`` reference at the shapes the scorers use.

One process, ``interpret=False`` throughout; fails without a TPU. Each check
also asserts that the lowered program holds a Mosaic custom call, so a route
that quietly fell back to XLA or to the interpreter cannot pass.

* ``ops/scorehead.candidate_lse`` at the served exact-head shape
  (N = 32768·32 = 1,048,576 rows, D = 256, V = 32768; the reference runs in
  the 32768-row chunks the einsum head uses), at half of it (16384·32) and
  at ``mlp``'s (N = 16384, D = 128, V = 32768), each with the kernel's own
  time (``kernel_ms``, median of three calls);
* ``ops/shortattn.short_attention`` at the served shape (32768 rows, S 32,
  4 heads of 64, bf16) and at 300 rows of S 16, PAD masks with a fully
  padded line, against ``dot_product_attention`` in float32;
* ``ops/shortattn.short_latent_attention`` (latent attention's causal,
  two-width form) at the sparse-expert scorer's served shape (1024 rows, S
  32, 32 heads of 128 ‖ 64, values 128, bf16) and at 300 rows of S 16,
  against ``ops/attention.latent_einsum`` in float32;
* ``ops/shortconv.gated_conv`` (the gated short convolution's elementwise
  core) at the served shape (1024 rows, S 32, D 2048, 3 taps, bf16) and at
  300 rows of S 16, against ``gated_conv_xla`` in float32;
* ``ops/deltarule.gated_delta`` (the gated delta rule's closed form, one
  chunk a line) at the served shape (256, 512 and 1024 rows, S 32, 16 key
  and 32 value heads of 128, bf16 operands), an all-PAD line and a PAD tail
  among them, against the chunked form (bf16 operands) and the
  position-by-position scan (float32), reading q | k | v in place from one
  array as the served layer hands them over and as three arrays;
* ``ops/experts.segment_sum_add`` (the routed experts' way back to the
  tokens) at the served shape (32768 tokens, a chunk of 16384 rows, D
  2048) and at the fit's (1024 tokens, 4096 rows), with runs of unrouted
  tokens — whole blocks of tokens without a row, one run on a row block's
  edge — NaN in the rows past the live ones and in the memory freed before
  the call, without an accumulator (every block written) and with one
  (updated in place), against ``numpy.add.at`` in float64;
* ``ops/flash.flash_attention`` forward at S = T = 2048 and 8192, D = 64,
  bf16, with a key mask;
* the flash backward kernels (dq; dk+dv) at the same shapes.

Prints one JSON line per check and a final summary line; the full record
goes to ``chiprun_out/chip_kernels.json``. Exit code 1 if any check failed.

Usage: python scripts/chip_kernels.py [WORD]   (only the checks whose name
holds WORD, e.g. ``short``)
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

OUT_PATH = os.path.join("chiprun_out", "chip_kernels.json")


def _compiled(fn, *args):
    """Lower ``fn`` for ``args``, require a Mosaic kernel in the program,
    compile it and return (executable, compile seconds)."""
    import jax

    lowered = jax.jit(fn).lower(*args)
    if "tpu_custom_call" not in lowered.as_text():
        raise AssertionError("lowered program holds no Mosaic custom call")
    t0 = time.perf_counter()
    exe = lowered.compile()
    return exe, time.perf_counter() - t0


def _kernel_ms(exe, *args) -> float:
    """The middle of three timed calls of a compiled executable, in ms."""
    import jax

    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(exe(*args))
        times.append((time.perf_counter() - t0) * 1000)
    return round(sorted(times)[1], 3)


def check_candidate_lse(n: int, d: int, v: int, ref_chunk: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from detectmateservice_tpu.ops.scorehead import candidate_lse

    kh, ke = jax.random.split(jax.random.PRNGKey(n + d))
    hidden = jax.random.normal(kh, (n, d), jnp.float32).astype(jnp.bfloat16)
    emb = (jax.random.normal(ke, (v, d), jnp.float32) * d ** -0.5
           ).astype(jnp.bfloat16)
    exe, compile_s = _compiled(
        lambda h, e: candidate_lse(h, e, interpret=False), hidden, emb)
    got = np.asarray(exe(hidden, emb))
    kernel_ms = _kernel_ms(exe, hidden, emb)

    @jax.jit
    def ref_chunked(h, e):
        def one(h_c):
            logits = jnp.einsum("nd,vd->nv", h_c, e,
                                preferred_element_type=jnp.float32)
            return jax.nn.logsumexp(logits, axis=-1)

        return jax.lax.map(one, h.reshape(n // ref_chunk, ref_chunk, d)
                           ).reshape(n)

    want = np.asarray(ref_chunked(hidden, emb))
    err = float(np.max(np.abs(got - want)))
    return {"compile_s": round(compile_s, 2), "max_abs_err": err,
            "kernel_ms": kernel_ms,
            "finite": bool(np.isfinite(got).all()), "ok": err < 2e-2}


def check_short_attention(rows: int, s: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from detectmateservice_tpu.ops.shortattn import (einsum_route,
                                                     short_attention)

    heads, d = 4, 64
    kq, kl = jax.random.split(jax.random.PRNGKey(rows + s))
    qkv = jax.random.normal(kq, (rows, s, 3 * heads * d), jnp.float32
                            ).astype(jnp.bfloat16)
    lengths = jax.random.randint(kl, (rows,), 1, s + 1).at[0].set(0)
    key_mask = jnp.arange(s)[None, :] < lengths[:, None]
    exe, compile_s = _compiled(
        lambda x, m: short_attention(x, m, heads, None, False), qkv, key_mask)
    got = np.asarray(exe(qkv, key_mask), np.float32)
    want = np.asarray(jax.jit(lambda x, m: einsum_route(
        x.astype(jnp.float32), m, heads))(qkv, key_mask))
    err = float(np.max(np.abs(got - want)))
    return {"compile_s": round(compile_s, 2), "max_abs_err": err,
            "finite": bool(np.isfinite(got).all()), "ok": err < 3e-2}


def check_short_latent_attention(rows: int, s: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from detectmateservice_tpu.ops.attention import latent_einsum
    from detectmateservice_tpu.ops.shortattn import short_latent_attention

    heads, nope, rope, dv, theta = 32, 128, 64, 128, 1e6
    keys = jax.random.split(jax.random.PRNGKey(rows + s), 4)
    q, kv, k_rope = (
        jax.random.normal(key, (rows * s, width), jnp.float32
                          ).astype(jnp.bfloat16)
        for key, width in zip(keys, (heads * (nope + rope),
                                     heads * (nope + dv), rope)))
    lengths = jax.random.randint(keys[3], (rows,), 1, s + 1).at[0].set(0)
    key_mask = jnp.arange(s)[None, :] < lengths[:, None]
    exe, compile_s = _compiled(
        lambda q, kv, kr, m: short_latent_attention(
            q, kv, kr, m, heads, nope, theta, True, None, False),
        q, kv, k_rope, key_mask)
    got = np.asarray(exe(q, kv, k_rope, key_mask), np.float32)
    want = np.asarray(jax.jit(lambda q, kv, kr, m: latent_einsum(
        q.astype(jnp.float32), kv.astype(jnp.float32),
        kr.astype(jnp.float32), m, heads, nope, theta, True))(
        q, kv, k_rope, key_mask))
    err = float(np.max(np.abs(got - want)))
    # the turned rope parts are rounded to bfloat16 on the kernel's side
    # only: logits of magnitude ~14 move by ~0.05
    return {"compile_s": round(compile_s, 2), "max_abs_err": err,
            "finite": bool(np.isfinite(got).all()), "ok": err < 6e-2}


def check_gated_conv(rows: int, s: int, d: int = 2048) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from detectmateservice_tpu.ops.shortconv import (gated_conv,
                                                     gated_conv_xla)

    kb, kw = jax.random.split(jax.random.PRNGKey(rows + s))
    bcx = jax.random.normal(kb, (rows * s, 3 * d), jnp.float32
                            ).astype(jnp.bfloat16)
    weight = jax.random.normal(kw, (d, 3), jnp.float32)
    exe, compile_s = _compiled(lambda b, w: gated_conv(b, w, s), bcx, weight)
    got = np.asarray(exe(bcx, weight), np.float32)
    want = np.asarray(jax.jit(lambda b, w: gated_conv_xla(
        b.astype(jnp.float32), w, s))(bcx, weight))
    err = float(np.max(np.abs(got - want) / (1.0 + np.abs(want))))
    return {"compile_s": round(compile_s, 2), "max_rel_err": err,
            "finite": bool(np.isfinite(got).all()), "ok": err < 1e-2}


def check_gated_delta(rows: int, s: int = 32) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from detectmateservice_tpu.ops.deltarule import (Heads, gated_delta,
                                                     gated_delta_rule)

    heads = Heads(16, 32, 128, 128)
    n = rows * s
    km, kg, kb = jax.random.split(jax.random.PRNGKey(rows + s), 3)
    mixed = jax.random.normal(km, (n, 2 * 2048 + 4096), jnp.float32)
    # a PAD tail in line 1, line 2 all PAD: zero q, k and v
    mixed = mixed.at[s + s // 2:3 * s].set(0.0).astype(jnp.bfloat16)
    g = -jax.random.uniform(kg, (n, 32), jnp.float32, 0.0, 3.0)
    beta = jax.random.uniform(kb, (n, 32), jnp.float32)
    exe, compile_s = _compiled(
        lambda m, g, b: gated_delta((m,), g, b, heads, s), mixed, g, beta)
    got = np.asarray(exe(mixed, g, beta))
    apart, _ = _compiled(lambda m, g, b: gated_delta(
        heads.split(m), g, b, heads, s), mixed, g, beta)
    kernel_ms = _kernel_ms(exe, mixed, g, beta)

    def plain(impl, dtype):
        return np.asarray(jax.jit(lambda m, g, b: gated_delta_rule(
            *heads.split(m), g, b, s, impl=impl, dtype=dtype))(mixed, g, beta))

    chunked, scan = plain("chunked", jnp.bfloat16), plain("scan", jnp.float32)
    out = {"compile_s": round(compile_s, 2),
           "kernel_ms": kernel_ms,
           "max_abs_gap_to_chunked": float(np.abs(got - chunked).max()),
           "max_abs_gap_to_scan": float(np.abs(got - scan).max()),
           "chunked_max_abs_gap_to_scan": float(np.abs(chunked - scan).max()),
           "in_place_equals_apart": bool(
               (got == np.asarray(apart(mixed, g, beta))).all()),
           "pad_rows_zero": bool((got[s + s // 2:3 * s] == 0.0).all()),
           "scale": float(np.abs(scan).max()),
           "finite": bool(np.isfinite(got).all())}
    # bfloat16 operands of k kᵀ, q kᵀ and the last product on either side:
    # the kernel may part from the scan no further than the chunked form
    # does, with room for the order of the sums
    out["ok"] = (out["finite"] and out["pad_rows_zero"]
                 and out["in_place_equals_apart"]
                 and out["max_abs_gap_to_scan"]
                 < 2.0 * out["chunked_max_abs_gap_to_scan"] + 1e-4)
    return out


def check_segment_sum(tokens: int, chunk: int, d: int = 2048) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from detectmateservice_tpu.ops.experts import segment_sum_add

    rng = np.random.default_rng(tokens + chunk)
    live = chunk - 300
    # unrouted: tokens 128..639 (rows 127 and 128 stand five blocks of
    # tokens apart), the last block and, where there is room, a run inside
    routed = np.ones(tokens, bool)
    routed[128:640] = routed[tokens - 128:] = False
    if tokens >= 4096:
        routed[tokens // 2:tokens // 2 + 400] = False
    token = np.full(chunk, tokens, np.int32)
    token[:128] = np.sort(rng.integers(0, 128, 128))
    token[128:live] = np.sort(rng.choice(np.flatnonzero(routed)[128:],
                                         live - 128))
    y = rng.normal(size=(chunk, d)).astype(np.float32)
    weight = rng.uniform(0.1, 1.0, chunk).astype(np.float32)
    acc = rng.normal(size=(tokens, d)).astype(np.float32)
    want = np.zeros((tokens, d))
    np.add.at(want, token[:live], y[:live].astype(np.float64)
              * weight[:live, None])
    y[live:] = np.nan
    args = (jnp.asarray(y), jnp.asarray(token), jnp.asarray(weight),
            jnp.int32(live))
    out = {"ok": True}
    for name, first in (("no_accumulator", ()), ("accumulator", (acc,))):
        exe, compile_s = _compiled(
            lambda *a: segment_sum_add(a[0] if first else None,
                                       *a[len(first):], tokens),
            *map(jnp.asarray, first), *args)
        # what an unwritten block would read
        jax.block_until_ready(jnp.full((tokens, d), jnp.nan, jnp.float32))
        got = np.asarray(exe(*map(jnp.asarray, first), *args), np.float64)
        err = float(np.nanmax(np.abs(got - want - (acc if first else 0.0))))
        finite = bool(np.isfinite(got).all())
        out[name] = {"compile_s": round(compile_s, 2), "max_abs_err": err,
                     "finite": finite,
                     "unrouted_rows_nonzero": int(
                         (got[~routed] != (acc[~routed] if first else 0.0)
                          ).any(-1).sum())}
        out["ok"] &= finite and err < 2e-5 and not out[name][
            "unrouted_rows_nonzero"]
    return out


def _flash_inputs(s: int):
    import jax
    import jax.numpy as jnp

    b, h, d = 1, 4, 64
    kq, kk, kv, kw = jax.random.split(jax.random.PRNGKey(s), 4)
    q, k, v = (jax.random.normal(r, (b, h, s, d), jnp.float32
                                 ).astype(jnp.bfloat16) for r in (kq, kk, kv))
    # the last eighth of the keys is padding
    key_mask = jnp.arange(s)[None, :] < (s - s // 8)
    w = jax.random.normal(kw, (b, h, s, d), jnp.float32)
    return q, k, v, key_mask, w


def check_flash_forward(s: int) -> dict:
    import numpy as np

    from detectmateservice_tpu.ops.flash import (_reference_attention,
                                                 flash_attention)

    q, k, v, key_mask, _ = _flash_inputs(s)
    exe, compile_s = _compiled(
        lambda q, k, v, m: flash_attention(q, k, v, m, interpret=False),
        q, k, v, key_mask)
    got = np.asarray(exe(q, k, v, key_mask), np.float32)
    want = np.asarray(_reference_attention(q, k, v, key_mask), np.float32)
    err = float(np.max(np.abs(got - want)))
    return {"compile_s": round(compile_s, 2), "max_abs_err": err,
            "finite": bool(np.isfinite(got).all()), "ok": err < 3e-2}


def check_flash_backward(s: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from detectmateservice_tpu.ops.flash import (_reference_attention,
                                                 flash_attention)

    q, k, v, key_mask, w = _flash_inputs(s)

    def loss(attn):
        return lambda q, k, v: jnp.sum(
            attn(q, k, v).astype(jnp.float32) * w)

    flash_grad = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, key_mask, interpret=False)), argnums=(0, 1, 2))
    ref_grad = jax.jit(jax.grad(loss(lambda q, k, v: _reference_attention(
        q, k, v, key_mask)), argnums=(0, 1, 2)))
    exe, compile_s = _compiled(flash_grad, q, k, v)
    got = [np.asarray(g, np.float32) for g in exe(q, k, v)]
    want = [np.asarray(g, np.float32) for g in ref_grad(q, k, v)]
    # bf16 gradients of an O(sqrt(S))-magnitude sum: compare relative to
    # the reference's own scale
    errs = {name: float(np.max(np.abs(g - r)) / max(1e-6, np.max(np.abs(r))))
            for name, g, r in zip(("dq", "dk", "dv"), got, want)}
    return {"compile_s": round(compile_s, 2), "max_rel_err": errs,
            "finite": all(bool(np.isfinite(g).all()) for g in got),
            "ok": max(errs.values()) < 5e-2}


CHECKS = [
    ("candidate_lse logbert served N=1048576 D=256 V=32768",
     lambda: check_candidate_lse(32768 * 32, 256, 32768, 32768)),
    ("candidate_lse logbert N=524288 D=256 V=32768",
     lambda: check_candidate_lse(16384 * 32, 256, 32768, 16384)),
    ("candidate_lse mlp N=16384 D=128 V=32768",
     lambda: check_candidate_lse(16384, 128, 32768, 16384)),
    ("short_attention logbert served rows=32768 S=32 H=4 D=64",
     lambda: check_short_attention(32768, 32)),
    ("short_attention rows=300 S=16 H=4 D=64",
     lambda: check_short_attention(300, 16)),
    ("short_latent_attention moe_mla served rows=1024 S=32 H=32 128|64 v128",
     lambda: check_short_latent_attention(1024, 32)),
    ("short_latent_attention rows=300 S=16 H=32 128|64 v128",
     lambda: check_short_latent_attention(300, 16)),
    ("gated_conv moe_conv served rows=1024 S=32 D=2048 K=3",
     lambda: check_gated_conv(1024, 32)),
    ("gated_conv rows=300 S=16 D=256 K=3",
     lambda: check_gated_conv(300, 16, 256)),
    ("gated_delta moe_delta served rows=1024 S=32 Hk=16 Hv=32 D=128",
     lambda: check_gated_delta(1024)),
    ("gated_delta rows=512 S=32 Hk=16 Hv=32 D=128",
     lambda: check_gated_delta(512)),
    ("gated_delta rows=256 S=32 Hk=16 Hv=32 D=128",
     lambda: check_gated_delta(256)),
    ("segment_sum_add served tokens=32768 chunk=16384 D=2048",
     lambda: check_segment_sum(32768, 16384)),
    ("segment_sum_add fit tokens=1024 chunk=4096 D=2048",
     lambda: check_segment_sum(1024, 4096)),
    ("flash forward S=2048", lambda: check_flash_forward(2048)),
    ("flash forward S=8192", lambda: check_flash_forward(8192)),
    ("flash backward S=2048", lambda: check_flash_backward(2048)),
    ("flash backward S=8192", lambda: check_flash_backward(8192)),
]


def main() -> int:
    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"chip_kernels: no TPU (jax reports {device.platform!r}); "
              "the kernels compile for the chip only", file=sys.stderr)
        return 1
    results = []
    word = sys.argv[1] if len(sys.argv) > 1 else ""
    for name, check in CHECKS:
        if word not in name:
            continue
        entry = {"check": name}
        try:
            entry.update(check())
        except Exception as exc:  # noqa: BLE001 — the compiler's message IS the result
            entry.update(ok=False, error=f"{type(exc).__name__}: {exc}"[:4000])
        results.append(entry)
        print(json.dumps(entry), flush=True)
    summary = {
        "ok": all(r["ok"] for r in results),
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": len(jax.devices())},
        "jax": jax.__version__,
        "checks": results,
    }
    os.makedirs(os.path.dirname(OUT_PATH), exist_ok=True)
    with open(OUT_PATH, "w", encoding="utf-8") as fh:
        json.dump(summary, fh, indent=1)
    print(json.dumps({k: summary[k] for k in ("ok", "device", "jax")}))
    return 0 if summary["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
