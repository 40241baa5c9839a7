"""Fused flash attention as a Pallas TPU kernel.

The promise at ops/attention.py:5 made real: a single fused kernel computing
softmax(QK^T/sqrt(d)) V with the online-softmax recurrence, so the [S, T]
logits matrix never materializes in HBM — the working set per grid step is
one (block_q x d) query tile, one (block_k x d) key/value tile, and the
(block_q x d) fp32 accumulator in VMEM.

When it matters: long-context scoring (SURVEY §5.7 analog — multi-line log
windows, stack traces, transaction sessions tokenized to thousands of
tokens). At the flagship scorer's default seq_len=32 the whole attention fits
in one MXU tile and XLA's fused einsum is already optimal — so
``attention()`` in ops/attention.py routes: seq < FLASH_MIN_SEQ stays on the
einsum path, longer sequences take this kernel. Its speed against the
einsum path (scripts/bench_flash.py) is not measured on the attached chip;
the einsum path's [B,H,S,S] fp32 logits are 1 GB per batch-head at S=8192,
against the kernel's O(S·block_k) VMEM working set.

Training-grade: the backward is two more fused kernels (dq; dk+dv) that
recompute probability tiles from (q, k, saved per-row logsumexp) — the
FlashAttention backward recurrence — so gradients also never materialize
the [S, T] logits, and long-context *training* keeps the same memory
profile as scoring. The scoring path skips the lse output entirely (no
extra HBM write when no grad is pending).

Layout choices, TPU-first:
* grid = (B*H, S/block_q, T/block_k) with the k dimension innermost and
  "arbitrary" semantics (sequential accumulation), q/batch dims parallel;
* fp32 accumulator + running (max, sum) live in VMEM scratch across the
  k-steps; the output tile is written once, on the last k-step;
* PAD-key masking arrives as an additive fp32 bias [B, T] (0 or -1e30) so
  the kernel needs no boolean plumbing and padding to block multiples is
  masking-correct by construction;
* blocks default to 128x128 — the MXU tile — with fp32 accumulation via
  ``preferred_element_type`` on both matmuls.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 512
_NEG_BIG = -1e30


def _flash_kernel(bias_ref, q_ref, k_ref, v_ref, o_ref, *rest,
                  scale: float, want_lse: bool):
    """One (batch*head, q-block, k-block) grid step of online softmax.
    ``want_lse`` (backward pass pending) adds a second output carrying the
    per-row logsumexp; the scoring path skips the write entirely."""
    if want_lse:
        lse_ref, acc_ref, m_ref, l_ref = rest
    else:
        (acc_ref, m_ref, l_ref), lse_ref = rest, None
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_BIG)
        l_ref[:] = jnp.zeros_like(l_ref)

    q = q_ref[0]                                   # [bq, d]
    k = k_ref[0]                                   # [bk, d]
    v = v_ref[0]                                   # [bk, d]
    s = jax.lax.dot_general(                       # [bq, bk] fp32
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    ) * scale
    s = s + bias_ref[0]                            # [1, bk]: PAD keys -> -1e30

    m_prev = m_ref[:, :1]                          # [bq, 1]
    l_prev = l_ref[:, :1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    correction = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                         # [bq, bk]
    l_new = l_prev * correction + p.sum(axis=-1, keepdims=True)
    # p casts down to the value dtype (bf16 on the hot path) so BOTH matmuls
    # run the MXU at native width; accumulation stays fp32 throughout
    acc_ref[:] = acc_ref[:] * correction + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(kb == pl.num_programs(2) - 1)
    def _finalize():
        # l >= 1 always: every row has at least the -1e30-biased exp terms
        # summed with max subtracted, so a fully-masked row divides by the
        # number of keys, producing ~0 output rather than NaN
        l_safe = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        if lse_ref is not None:
            # per-row logsumexp L = m + log(l): the backward's softmax
            # denominator — saving it is what lets the bwd kernels
            # recompute p = exp(s - L) in one pass, no online recurrence
            lse_ref[0] = jnp.broadcast_to(m_ref[:, :1] + jnp.log(l_safe),
                                          lse_ref.shape[1:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def flash_attention(
    q: jax.Array,                     # [B, H, S, D]
    k: jax.Array,                     # [B, H, T, D]
    v: jax.Array,                     # [B, H, T, D]
    key_mask: Optional[jax.Array] = None,   # [B, T] bool; True = attend
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    """Fused attention; numerically matches ``dot_product_attention`` with a
    broadcast key mask (the scorer's use). S/T pad up to block multiples
    internally; D must be an MXU-friendly multiple of 8 (it is 64 for every
    shipped config).

    Differentiable end to end in the fused regime: the forward saves the
    per-row logsumexp, and the backward (``custom_vjp``) runs two more
    Pallas kernels (dq; dk+dv) that recompute the probability tiles from
    (q, k, lse) — so neither direction ever materializes the [S, T]
    logits in HBM and long-context *training* keeps the O(S·block)
    memory profile. Gradients match the einsum formulation's (pinned in
    tests/test_flash.py)."""
    out, _ = _flash_forward(q, k, v, key_mask, block_q, block_k, interpret,
                            want_lse=False)
    return out


def _pad_inputs(q, k, v, key_mask, block_q, block_k):
    """Shared fwd/bwd padding: S/T up to block multiples, PAD keys as an
    additive fp32 bias. Returns the padded operands + the shapes."""
    b, h, s, d = q.shape
    t = k.shape[2]
    block_q = min(block_q, max(s, 8))
    block_k = min(block_k, max(t, 8))
    s_pad = -(-s // block_q) * block_q
    t_pad = -(-t // block_k) * block_k
    if key_mask is None:
        key_mask = jnp.ones((b, t), dtype=bool)
    if t_pad != t:
        key_mask = jnp.pad(key_mask, ((0, 0), (0, t_pad - t)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, t_pad - t), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, t_pad - t), (0, 0)))
    if s_pad != s:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, s_pad - s), (0, 0)))
    # [B, 1, Tp]: the singleton middle dim satisfies the TPU block-shape rule
    # (last two block dims must divide (8, 128) or equal the array dims)
    bias = jnp.where(key_mask, 0.0, _NEG_BIG).astype(jnp.float32)[:, None, :]
    return q, k, v, bias, block_q, block_k, s_pad, t_pad


def _flash_forward(q, k, v, key_mask, block_q, block_k, interpret,
                   want_lse: bool):
    """Run the fused forward; returns (out [B,H,S,D], lse [BH,Sp,128] or
    None). The lse output exists only when a backward is pending — the
    scoring path skips its HBM write."""
    b, h, s, d = q.shape
    q, k, v, bias, block_q, block_k, s_pad, t_pad = _pad_inputs(
        q, k, v, key_mask, block_q, block_k)
    qr = q.reshape(b * h, s_pad, d)
    kr = k.reshape(b * h, t_pad, d)
    vr = v.reshape(b * h, t_pad, d)
    grid = (b * h, s_pad // block_q, t_pad // block_k)

    out_specs = [pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0))]
    out_shape = [jax.ShapeDtypeStruct((b * h, s_pad, d), q.dtype)]
    if want_lse:
        out_specs.append(
            pl.BlockSpec((1, block_q, 128), lambda bh, qi, ki: (bh, qi, 0)))
        out_shape.append(
            jax.ShapeDtypeStruct((b * h, s_pad, 128), jnp.float32))

    result = pl.pallas_call(
        functools.partial(_flash_kernel, scale=d ** -0.5, want_lse=want_lse),
        grid=grid,
        in_specs=[
            # bias indexes by batch (= bh // h), broadcast over heads/q
            pl.BlockSpec((1, 1, block_k), lambda bh, qi, ki: (bh // h, 0, ki)),
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),    # accumulator
            pltpu.VMEM((block_q, 128), jnp.float32),  # running max
            pltpu.VMEM((block_q, 128), jnp.float32),  # running sum
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(bias, qr, kr, vr)
    out, lse = (result if want_lse else (result[0], None))

    out = out.reshape(b, h, s_pad, d)
    return (out[:, :, :s] if s_pad != s else out), lse


def _dq_kernel(bias_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, dq_acc, *, scale: float):
    """dQ: grid (BH, S/bq, T/bk), k innermost; dq accumulates across k."""
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = s + bias_ref[0]
    p = jnp.exp(s - lse_ref[0][:, :1])             # [bq, bk] via saved L
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta_ref[0][:, :1])            # [bq, bk]
    dq_acc[:] += jax.lax.dot_general(
        ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) * scale

    @pl.when(kb == pl.num_programs(2) - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_kernel(bias_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, scale: float):
    """dK/dV: grid (BH, T/bk, S/bq), q innermost; both accumulate across q."""
    qb = pl.program_id(2)

    @pl.when(qb == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = s + bias_ref[0]
    p = jnp.exp(s - lse_ref[0][:, :1])             # [bq, bk]
    dv_acc[:] += jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)        # pᵀ · dO  → [bk, d]
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta_ref[0][:, :1])
    dk_acc[:] += jax.lax.dot_general(
        ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # dsᵀ · Q → [bk, d]

    @pl.when(qb == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _reference_attention(q, k, v, key_mask):
    """The einsum formulation the kernel matches — the fwd/grad parity
    oracle in tests. There is deliberately no einsum fallback inside this
    module — the route decision lives in ops/attention.py."""
    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * (d ** -0.5)
    if key_mask is not None:
        s = s + jnp.where(key_mask, 0.0, _NEG_BIG)[:, None, None, :]
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _flash_fwd(q, k, v, key_mask, block_q, block_k, interpret):
    out, lse = _flash_forward(q, k, v, key_mask, block_q, block_k, interpret,
                              want_lse=True)
    return out, (q, k, v, key_mask, out, lse)


def _flash_bwd(block_q, block_k, interpret, residuals, g):
    q, k, v, key_mask, out, lse = residuals
    b, h, s, d = q.shape
    t = k.shape[2]
    # delta_i = Σ_d dO·O per row — the softmax-jacobian rowsum, computed
    # once outside the kernels (an [S, D] elementwise + reduce, cheap)
    delta = jnp.einsum("bhsd,bhsd->bhs", g.astype(jnp.float32),
                       out.astype(jnp.float32))
    qp, kp, vp, bias, bq, bk, s_pad, t_pad = _pad_inputs(
        q, k, v, key_mask, block_q, block_k)
    dop = jnp.pad(g, ((0, 0), (0, 0), (0, s_pad - s), (0, 0)))
    deltap = jnp.pad(delta, ((0, 0), (0, 0), (0, s_pad - s)))
    deltar = jnp.broadcast_to(
        deltap.reshape(b * h, s_pad, 1), (b * h, s_pad, 128))
    qr = qp.reshape(b * h, s_pad, d)
    kr = kp.reshape(b * h, t_pad, d)
    vr = vp.reshape(b * h, t_pad, d)
    dor = dop.reshape(b * h, s_pad, d)
    scale = d ** -0.5

    q_spec = pl.BlockSpec((1, bq, d), lambda bh_, i, j: (bh_, i, 0))
    k_spec = pl.BlockSpec((1, bk, d), lambda bh_, i, j: (bh_, j, 0))
    row_spec = pl.BlockSpec((1, bq, 128), lambda bh_, i, j: (bh_, i, 0))
    bias_spec = pl.BlockSpec((1, 1, bk), lambda bh_, i, j: (bh_ // h, 0, j))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale),
        grid=(b * h, s_pad // bq, t_pad // bk),
        in_specs=[bias_spec, q_spec, k_spec, k_spec, q_spec, row_spec,
                  row_spec],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh_, i, j: (bh_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s_pad, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(bias, qr, kr, vr, dor, lse, deltar)

    # dkv grid swaps the outer block dim to k; index maps flip accordingly
    q_spec2 = pl.BlockSpec((1, bq, d), lambda bh_, i, j: (bh_, j, 0))
    k_spec2 = pl.BlockSpec((1, bk, d), lambda bh_, i, j: (bh_, i, 0))
    row_spec2 = pl.BlockSpec((1, bq, 128), lambda bh_, i, j: (bh_, j, 0))
    bias_spec2 = pl.BlockSpec((1, 1, bk), lambda bh_, i, j: (bh_ // h, 0, i))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale),
        grid=(b * h, t_pad // bk, s_pad // bq),
        in_specs=[bias_spec2, q_spec2, k_spec2, k_spec2, q_spec2, row_spec2,
                  row_spec2],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda bh_, i, j: (bh_, i, 0)),
            pl.BlockSpec((1, bk, d), lambda bh_, i, j: (bh_, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, t_pad, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, t_pad, d), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(bias, qr, kr, vr, dor, lse, deltar)

    dq = dq.reshape(b, h, s_pad, d)[:, :, :s]
    dk = dk.reshape(b, h, t_pad, d)[:, :, :t]
    dv = dv.reshape(b, h, t_pad, d)[:, :, :t]
    return dq, dk, dv, None


flash_attention.defvjp(_flash_fwd, _flash_bwd)
