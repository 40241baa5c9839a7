"""Fused scoring head as a Pallas TPU kernel: ``logsumexp(hidden @ emb.T)``
with the logits living only in VMEM.

The sequence families' detect-path bottleneck is the scoring head: for
every token position, logits against the vocabulary (or a candidate subset
of it) and a logsumexp over them (models/base.py). On the XLA path the
``[N, V]`` float32 logits materialize between the matmul and the reduce:
at the served flagship shape (32768 rows x 32 positions, V = 32768) that is
a 4 GiB chunk written to HBM and read back 32 times a call, 387.5 ms on a
v5e where the matmuls need 89 ms (my chip run, PR 25; PERF.md section 6).

This kernel fuses both. Grid (N/block_n, V/block_v), the V dimension
innermost and "arbitrary" (sequential). The logits tile is computed
**vocabulary-major**, ``emb_tile @ hidden_tile^T -> [rows of V, columns of
N]``, so that

* the max and the sum of the online recurrence run *down the rows* — plain
  element-wise VPU work between vregs, no cross-lane reduction — against a
  running ``[8, block_n]`` (max, sum) state in VMEM scratch that is folded
  to one row only when an N block finishes;
* state and output are lane-dense: the result is one ``[1, N]`` float32 row
  (4 bytes a row of HBM, where a ``[N, 128]`` lane-padded column cost 512);
* neither operand is transposed or copied on the way in: the contraction
  is over the last dimension of both (the flash kernels' ``q @ k^T`` form).

Each grid step walks its ``[block_v, block_n]`` tile in unrolled sub-tiles
of ``_SUB_V`` rows, so the scheduler overlaps one sub-tile's matmul with
the previous one's exponentials. HBM sees the hidden states once, the
embedding once per N block, and the output row.

Tiles come from the shapes alone (:func:`tile_sizes`): 2048 x 2048 with
256-row sub-tiles at D = 256, where one grid step is ~2 GFLOP (about 11 us
of MXU time at peak) against ~0.35 us of step overhead. Measured on the
attached v5e at N = 1,048,576, D = 256, V = 32768 (bfloat16 in, float32
accumulate): 100.9 ms, 88% of the matmul's least time, against 387.5 ms
for the chunked einsum + logsumexp and 342.7 ms with the 256 x 512
token-major tiles this file had; at N = 32768: 3.8 against 12.8 ms; at
N = 8192: 1.6 against 3.8 ms (my chip runs, PR 25; calls 1-2).

Correctness is pinned against the jnp reference in interpret mode on CPU
(tests/test_scorehead.py) and on the chip by scripts/chip_kernels.py;
which head takes this route is ``models/base.py::head_route``.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_BIG = -1e30
# rows of the logits tile one matmul + exp pass covers: 256 x 2048 float32
# is 2 MiB, small enough that the pass after it stays in flight beside the
# next matmul (512 was 7-9% slower on the chip; PERF.md section 6)
_SUB_V = 256
_MAX_BLOCK = 2048
# an operand block ([block, D]) stays under this, double-buffered twice over
_OPERAND_BLOCK_BYTES = 2 << 20


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def tile_sizes(n: int, v: int, d: int, itemsize: int = 2
               ) -> Tuple[int, int, int]:
    """(block_n, block_v, sub_v) for ``n`` rows of width ``d`` against ``v``
    embeddings: the widest tiles up to 2048 x 2048 whose operand blocks
    stay at 2 MiB, clipped to the (padded) problem."""
    cap = max(256, min(_MAX_BLOCK,
                       _OPERAND_BLOCK_BYTES // (d * itemsize) // 256 * 256))
    block_n = min(cap, _round_up(n, 128))
    sub_v = min(_SUB_V, _round_up(v, 16))
    block_v = min(cap, _round_up(v, sub_v))
    return block_n, block_v, sub_v


def _lse_kernel(e_ref, h_ref, o_ref, m_ref, l_ref, *, v_real: int,
                block_v: int, sub_v: int):
    """One (n-block, v-block) grid step of the online logsumexp."""
    vb = pl.program_id(1)
    last = pl.num_programs(1) - 1

    @pl.when(vb == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_BIG)
        l_ref[:] = jnp.zeros_like(l_ref)

    def accumulate(masked: bool) -> None:
        h = h_ref[:]                               # [bn, d]
        m_run = m_ref[:]                           # [8, bn]
        l_run = l_ref[:]
        bn = m_run.shape[1]
        for j in range(block_v // sub_v):
            s = jax.lax.dot_general(               # [sub_v, bn] fp32
                e_ref[j * sub_v:(j + 1) * sub_v, :], h,
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            if masked:                             # V-pad rows → -inf
                row = (vb * block_v + j * sub_v
                       + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0))
                s = jnp.where(row < v_real, s, _NEG_BIG)
            s = s.reshape(sub_v // 8, 8, bn)       # vreg rows: free
            m_new = jnp.maximum(m_run, jnp.max(s, axis=0))
            l_run = (l_run * jnp.exp(m_run - m_new)
                     + jnp.sum(jnp.exp(s - m_new[None]), axis=0))
            m_run = m_new
        m_ref[:] = m_run
        l_ref[:] = l_run

    if v_real % block_v == 0:
        accumulate(False)
    else:
        # only the last V block holds padding: the others skip the mask
        pl.when(vb < last)(lambda: accumulate(False))
        pl.when(vb == last)(lambda: accumulate(True))

    @pl.when(vb == last)
    def _finalize():
        # fold the 8 sublane-wise partial states; every one holds at least
        # one real logit, so the sum is >= 1 at the fold's max
        m = m_ref[:]
        top = jnp.max(m, axis=0, keepdims=True)
        total = jnp.sum(l_ref[:] * jnp.exp(m - top), axis=0, keepdims=True)
        o_ref[:] = jnp.log(total) + top


def candidate_lse(hidden: jax.Array, emb_c: jax.Array,
                  block_n: Optional[int] = None,
                  block_c: Optional[int] = None,
                  interpret: bool = False) -> jax.Array:
    """``logsumexp(hidden @ emb_c.T, axis=-1)`` without materializing the
    ``[N, C]`` logits in HBM.

    ``hidden``: [N, D], ``emb_c``: [C, D] (the full vocabulary on the exact
    head), multiplied in ``hidden``'s dtype with fp32 accumulation; the
    scorers hand both over in their compute dtype (bfloat16). Returns fp32
    [N]. N and C pad internally to block multiples — padded C rows are
    masked to -inf in the last C block, so arbitrary (even prime) vocab
    sizes keep full-width blocks. ``block_n`` / ``block_c`` override the
    shape-derived tiles (:func:`tile_sizes`); tests use them to put several
    blocks on a small problem.
    """
    with jax.named_scope("lse_pallas"):
        return _candidate_lse(hidden, emb_c, block_n, block_c, interpret)


def _candidate_lse(hidden: jax.Array, emb_c: jax.Array,
                   block_n: Optional[int], block_c: Optional[int],
                   interpret: bool) -> jax.Array:
    n, d = hidden.shape
    c = emb_c.shape[0]
    emb_c = emb_c.astype(hidden.dtype)
    itemsize = jnp.dtype(hidden.dtype).itemsize
    auto_n, auto_c, sub_v = tile_sizes(n, c, d, itemsize)
    block_n = min(_round_up(block_n or auto_n, 128), _round_up(n, 128))
    block_c = min(_round_up(block_c or auto_c, sub_v), _round_up(c, sub_v))
    n_pad = _round_up(n, block_n)
    c_pad = _round_up(c, block_c)
    if n_pad != n:
        hidden = jnp.pad(hidden, ((0, n_pad - n), (0, 0)))
    if c_pad != c:
        emb_c = jnp.pad(emb_c, ((0, c_pad - c), (0, 0)))
    # both operand blocks double-buffered, the sub-tile and the handful of
    # float32 temporaries of its max/exp/sum pass, twice over for headroom
    vmem = 2 * (2 * (block_n + block_c) * d * itemsize
                + 8 * sub_v * block_n * 4)
    out = pl.pallas_call(
        functools.partial(_lse_kernel, v_real=c, block_v=block_c,
                          sub_v=sub_v),
        grid=(n_pad // block_n, c_pad // block_c),
        in_specs=[
            pl.BlockSpec((block_c, d), lambda ni, ci: (ci, 0)),
            pl.BlockSpec((block_n, d), lambda ni, ci: (ni, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_n), lambda ni, ci: (0, ni)),
        out_shape=jax.ShapeDtypeStruct((1, n_pad), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((8, block_n), jnp.float32),  # running max
            pltpu.VMEM((8, block_n), jnp.float32),  # running sum
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=max(32 << 20, vmem),
        ),
        interpret=interpret,
    )(emb_c, hidden)
    return out[0, :n]
