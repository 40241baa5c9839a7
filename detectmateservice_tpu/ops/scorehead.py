"""Fused candidate-vocab scoring head as a Pallas TPU kernel.

The sequence families' detect-path bottleneck is the scoring head: for
every token position, logits against the candidate subset ``emb_c`` and a
logsumexp over them (models/base.py ``_token_nlls_candidate``). On
the XLA path the ``[N, C]`` logits tensor materializes between the matmul
and the reduce — at N = B·S = 512k, C = 2048 that is 2 GB of HBM traffic
written and read back per batch.

This kernel fuses both: grid (N/block_n, C/block_c) with the C dimension
innermost and "arbitrary" (sequential) semantics, an online (max, sum)
recurrence in VMEM scratch — the same shape as ops/flash.py's softmax
recurrence, minus the value matmul. The logits tile lives only in VMEM;
HBM sees the ``[N, D]`` hidden states once (the hidden block index does
not change across the inner C steps, so Pallas keeps the tile resident),
the ``[C, D]`` candidate embeddings once per N block, and a ``[N]``-sized
output.

Correctness is pinned against the jnp reference in interpret mode on CPU
(tests/test_scorehead.py); routing lives behind the scorer's
``head_impl`` knob. On the attached chip the kernel compiles and matches
the reference at the flagship and mlp head shapes
(scripts/chip_kernels.py, CHANGES.md PR 21); its speed against the XLA
einsum route is not measured there (scripts/bench_scorehead.py, ROADMAP
D5), so ``head_impl: auto`` keeps einsum. On the EXACT full-vocab head the
kernel deletes the [rows, V] chunk materialization (the HBM high-water of
the exact path).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_N = 256
DEFAULT_BLOCK_C = 512
_NEG_BIG = -1e30


def _lse_kernel(bias_ref, h_ref, e_ref, o_ref, m_ref, l_ref):
    """One (n-block, c-block) grid step of the online logsumexp."""
    cb = pl.program_id(1)

    @pl.when(cb == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_BIG)
        l_ref[:] = jnp.zeros_like(l_ref)

    h = h_ref[:]                                   # [bn, d]
    e = e_ref[:]                                   # [bc, d]
    s = jax.lax.dot_general(                       # [bn, bc] fp32
        h, e, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    )
    s = s + bias_ref[:]                            # [1, bc]: C-pad rows → -inf
    m_prev = m_ref[:, :1]                          # [bn, 1]
    l_prev = l_ref[:, :1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    l_new = (l_prev * jnp.exp(m_prev - m_new)
             + jnp.exp(s - m_new).sum(axis=-1, keepdims=True))
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(cb == pl.num_programs(1) - 1)
    def _finalize():
        # l >= 1 whenever at least one candidate exists (max subtracted),
        # so the log is finite for every real row
        o_ref[:] = jnp.broadcast_to(
            jnp.log(jnp.maximum(l_ref[:, :1], 1e-30)) + m_ref[:, :1],
            o_ref.shape)


def candidate_lse(hidden: jax.Array, emb_c: jax.Array,
                  block_n: int = DEFAULT_BLOCK_N,
                  block_c: int = DEFAULT_BLOCK_C,
                  interpret: bool = False) -> jax.Array:
    """``logsumexp(hidden @ emb_c.T, axis=-1)`` without materializing the
    ``[N, C]`` logits in HBM.

    ``hidden``: [N, D] (any float dtype; the matmul accumulates fp32),
    ``emb_c``: [C, D]. Returns fp32 [N]. Both N and C pad internally to
    block multiples — padded C rows are masked out with an additive -inf
    bias (the flash-kernel pattern), so arbitrary vocab/candidate sizes
    keep full-width blocks instead of degrading to divisor-sized ones.
    """
    with jax.named_scope("lse_pallas"):
        return _candidate_lse(hidden, emb_c, block_n, block_c, interpret)


def _candidate_lse(hidden: jax.Array, emb_c: jax.Array, block_n: int,
                   block_c: int, interpret: bool) -> jax.Array:
    n, d = hidden.shape
    c = emb_c.shape[0]
    block_n = min(block_n, max(n, 8))
    block_c = min(block_c, max(c, 128))
    n_pad = -(-n // block_n) * block_n
    c_pad = -(-c // block_c) * block_c
    if n_pad != n:
        hidden = jnp.pad(hidden, ((0, n_pad - n), (0, 0)))
    if c_pad != c:
        emb_c = jnp.pad(emb_c, ((0, c_pad - c), (0, 0)))
    bias = jnp.where(jnp.arange(c_pad) < c, 0.0, _NEG_BIG
                     ).astype(jnp.float32)[None, :]

    grid = (n_pad // block_n, c_pad // block_c)
    out = pl.pallas_call(
        _lse_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_c), lambda ni, ci: (0, ci)),
            pl.BlockSpec((block_n, d), lambda ni, ci: (ni, 0)),
            pl.BlockSpec((block_c, d), lambda ni, ci: (ci, 0)),
        ],
        # [bn, 128] lane-width tile; column 0 carries the result
        out_specs=pl.BlockSpec((block_n, 128), lambda ni, ci: (ni, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, 128), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((block_n, 128), jnp.float32),  # running max
            pltpu.VMEM((block_n, 128), jnp.float32),  # running sum
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(bias, hidden, emb_c)
    return out[:n, 0]
