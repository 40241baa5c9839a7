"""Fused self-attention for whole SHORT sequences as Pallas TPU kernels:
``softmax(QK^T / sqrt(d)) V`` for S = T <= 128 with nothing of shape
``[rows, heads, S, T]`` and no head-major copy of q, k, v in HBM — one form
for one q·k·v width (``short_attention``), one for latent attention's two
q·k widths, value width and causal mask (``short_latent_attention``).

ops/flash.py tiles *along* a long sequence; at the scorers' S = 32 it has
nothing to tile. What the einsum route costs there is shape, not
arithmetic: the float32 ``[rows, heads, 32, 32]`` logits are stored in
(8, 128) tiles, so their 0.54 GB at 32768 rows are 2.1 GB in HBM, written
and read several times a layer; the 64-wide heads pad the transposed q, k,
v twofold; and the ``[b, s, h, d] <-> [b, h, s, d]`` transposes are
materialised — 49.6 ms a layer on a v5e where the chip needs 5.5 (ledger,
PR 27; PERF.md section 6, PR 28).

This kernel takes the fused projection as the model writes it — ``qkv``
``[rows, S, 3 * heads * head_dim]``, heads side by side in the lane
dimension — and returns ``[rows, S, heads * head_dim]`` ready for the
output projection. Its three operand blocks are the three thirds of the
one array (same buffer, block index 0 / 1 / 2 along the lanes), so not
even the q / k / v split is copied.

A grid step owns a block of whole lines, token-major (``[rows * S, heads *
head_dim]``), and walks it a few lines at a time: batched ``S x D``
matmuls per (line, head), float32 logits ``[lines, S, S]`` that live in
vregs only. Heads narrower than a vreg's 128 lanes are taken a lane group
at a time (two 64-wide heads): the group's q is zeroed outside the head's
lanes and contracted over all 128 — the MXU is 128 deep either way — and
the head's lanes of ``p @ v`` are kept, so nothing is ever sliced or
shifted across lanes.

What lost on the chip (my chip runs, PR 28; calls 1 and 3; 32768 rows, 4
heads, S 32, D 64, memory floor 2.62 ms; this form 6.49-6.50 ms a layer's
core, 40% of the floor; the einsum route's split, transposes and core 23.3
ms): *packed tiles* — 128 / S lines' tokens sharing one 128 x 128 logits
tile under a block-diagonal mask, every matmul MXU-aligned at four times
the needed operations — 8.16 ms (0.074 against 0.058 ms at 256 rows), the
same from 1024 to 8192 tokens a grid step; *folded tiles* — the packed
tile's four diagonal blocks selected into one dense, key-major ``[S, 128]``
tile, so the softmax runs on a quarter of the vregs and reduces down the
sublanes, then unfolded for a transposed ``p @ v`` — 8.89-8.93 ms. All
three are bound inside the core, not by HBM; what the two tile forms save
in vector work they lose to 128-row matmuls that are three quarters
zeros.

**Latent attention's form** (``short_latent_attention``, PR 30; the
sparse-expert scorer, models/moe_mla.py): the same walk — whole lines, a
few a step, float32 logits in vregs — with what that core adds as static
parameters of a second kernel body that shares this one's softmax: a
**causal** mask beside the PAD mask; **two q·k operands**, a head's logits
being ``q_nope·k_nopeᵀ + rot(q_rope)·rot(k_rope)ᵀ`` scaled by ``(nope +
rope)^-0.5`` (the sum the 192-wide contraction computes, so nothing is
concatenated); ``k_rope`` ``[tokens, 64]`` **shared by the heads**; a
**value width of its own**; and **rotary positions turned inside**, on the
64-wide parts only, with ``attention.rotary``'s arithmetic (the pair swap a
±1 matmul, float32 products, cast to the operands' dtype). Operands are
token-major as the projections write them, each array's two parts as column
blocks of one buffer: ``q`` ``[tokens, 32·128 | 32·64]``, ``kv`` ``[tokens,
32·128 | 32·128]``. The grid is (blocks of lines, lane groups of rope
parts): a step owns two heads — 256 nope lanes of q, k and v and the 128
lanes of their two rope parts — and the two heads' rope contraction runs
over all 128 lanes with q zeroed outside the head's and ``k_rope`` written
twice side by side (a matmul with a 0 / 1 matrix, fused with its rotation
and kept in VMEM for the block's other heads), so nothing is shifted across
lanes. On the chip (my chip runs, PR 30; call 1; 1024 rows, 32 heads, S 32:
memory floor 1.48 ms a layer): **3.12 ms, 47% of the floor**, where
``attention.latent_einsum`` — head-major copies, rope's slices and
concatenations, ``k_rope`` broadcast to 32 heads, padded float32 logits —
takes 18.71 ms; 1.96 against 9.19 at 512 rows, 1.29 against 4.11 at 256.
``logbert``'s body is the one it had.

The arithmetic is ``ops/attention.py::dot_product_attention``'s: logits
from multiplies in the input dtype accumulated in float32, scaled in
float32; PAD keys filled with ``finfo(float32).min`` (a line whose every
key is PAD attends uniformly over its S keys, not NaN); float32 max / exp
/ sum; probabilities cast to the value dtype; ``p @ v`` accumulated in
float32. The one departure: ``e * (1 / sum)`` with an exact reciprocal of
the ``[S, 1]`` column where ``jax.nn.softmax`` divides the tile (at most
one float32 ulp, gone in the cast to bfloat16).

Differentiation (the boundary fit): ``jax.custom_vjp`` whose backward is
the vjp of the einsum route recomputed from the operands (``einsum_route``;
``attention.latent_einsum``) — exact, and the fit is 2048 lines a boot in
32-row steps that ``auto`` leaves on einsum anyway, where a backward kernel
would buy nothing.

Correctness is pinned against ``dot_product_attention`` in interpret mode
on CPU (tests/test_shortattn.py) and on the chip by
scripts/chip_kernels.py; which call takes this route is
``ops/attention.py::attention_route``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import (dot_product_attention, latent_einsum, merge_heads,
                        rotary_tables, split_heads)

# a vreg's lanes, the MXU's depth: the width of a lane group
LANES = 128
MAX_SEQ = 128
# tokens a grid step owns (1024 to 8192 read the same on the chip) and
# tokens one step of its walk takes: eight lines at S = 32
_BLOCK_TOKENS = 2048
_STEP_TOKENS = 256
_MASKED = float(jnp.finfo(jnp.float32).min)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def heads_per_lane_group(heads: int, head_dim: int) -> int:
    """How many heads the kernel takes at a time: as many as fit a vreg's
    128 lanes and divide ``heads`` (two at 64 wide; one from 128 up)."""
    per = max(1, LANES // head_dim)
    while heads % per:
        per -= 1
    return per


def _whole_lines(seq: int) -> bool:
    """Lines of whole (16, 128) bfloat16 tiles that divide a step of the
    walk (the interpreter takes any ``seq`` dividing 256)."""
    return 0 < seq <= MAX_SEQ and seq % 16 == 0 and _STEP_TOKENS % seq == 0


def fits(seq: int, heads: int, head_dim: int) -> bool:
    """Whether the compiled kernel takes the shape: whole lines, and lane
    groups of whole vregs."""
    return (_whole_lines(seq)
            and (heads_per_lane_group(heads, head_dim) * head_dim) % LANES == 0)


def fits_latent(seq: int, heads: int, nope: int, rope: int,
                value_dim: int) -> bool:
    """Whether the compiled two-width kernel takes the shape: whole lines,
    nope and value widths of whole lane groups (a head's part is then a
    column block), and rope parts that fill lane groups of whole vregs."""
    return (_whole_lines(seq) and nope % LANES == 0 and value_dim % LANES == 0
            and rope % 2 == 0
            and (heads_per_lane_group(heads, rope) * rope) % LANES == 0)


def _softmax_pv(logits, keep, v):
    """One (lines, head) tile: PAD (and future) keys filled, float32
    softmax over the keys, ``p @ v`` accumulated in float32."""
    logits = jnp.where(keep, logits, _MASKED)
    top = jnp.max(logits, axis=-1, keepdims=True)
    e = jnp.exp(logits - top)
    probs = e * (1.0 / jnp.sum(e, axis=-1, keepdims=True))
    return jnp.einsum("lst,ltd->lsd", probs.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)


def _blocks(n: int, block_tokens: Optional[int]) -> tuple:
    """(tokens a grid step owns, ``n`` padded to whole blocks). A block is
    whole steps, and its PAD mask ([lines, S] float32) whole 8-row tiles:
    8 * 256 tokens at the least."""
    unit = 8 * _STEP_TOKENS
    block = min(_round_up(block_tokens or _BLOCK_TOKENS, unit),
                _round_up(n, unit))
    return block, _round_up(n, block)


def _step_rows(t):
    return pl.ds(pl.multiple_of(t * _STEP_TOKENS, _STEP_TOKENS), _STEP_TOKENS)


def _short_kernel(valid_ref, q_ref, k_ref, v_ref, o_ref, *, seq: int,
                  head_dim: int, per: int):
    """One block of whole lines: a few lines a step, lane group by lane
    group, head by head."""
    block_tokens, width = q_ref.shape
    lanes = per * head_dim
    scale = head_dim ** -0.5
    lines = _STEP_TOKENS // seq
    head_of_lane = (jax.lax.broadcasted_iota(
        jnp.int32, (lines, seq, lanes), 2) // head_dim)

    def one_step(t, carry):
        rows = _step_rows(t)
        keep = valid_ref[pl.ds(pl.multiple_of(t * lines, lines), lines),
                         :][:, None, :] > 0.5                    # [L, 1, S]
        for group in range(width // lanes):
            cols = slice(group * lanes, (group + 1) * lanes)
            q, k, v = (ref[rows, cols].reshape(lines, seq, lanes)
                       for ref in (q_ref, k_ref, v_ref))
            out = None
            for head in range(per):
                mine = head_of_lane == head
                q_head = q if per == 1 else jnp.where(mine, q, 0)
                logits = jnp.einsum("lsd,ltd->lst", q_head, k,
                                    preferred_element_type=jnp.float32) * scale
                pv = _softmax_pv(logits, keep, v)
                out = pv if out is None else jnp.where(mine, pv, out)
            o_ref[rows, cols] = out.reshape(_STEP_TOKENS, lanes).astype(
                o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, block_tokens // _STEP_TOKENS, one_step, 0)


# jitted so that a model's layers share ONE trace of the kernel: tracing its
# body is Python work the detector pays at every bucket's first dispatch,
# inside set-up (four traces a bucket cost 1.4 s a warm-up burst: PERF.md
# section 6, PR 28)
@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def attn_short(qkv: jax.Array, key_mask: Optional[jax.Array], heads: int,
               block_tokens: Optional[int], interpret: bool) -> jax.Array:
    b, s, width = qkv.shape
    hd = width // 3
    head_dim = hd // heads
    if width != 3 * heads * head_dim or s > MAX_SEQ or _STEP_TOKENS % s:
        raise ValueError(
            f"short_attention takes qkv [rows, S, 3*heads*head_dim] with S "
            f"<= {MAX_SEQ} dividing {_STEP_TOKENS}; got {qkv.shape} at "
            f"heads={heads}")
    per = heads_per_lane_group(heads, head_dim)
    if not interpret and not fits(s, heads, head_dim):
        raise ValueError(
            f"short_attention compiles for S a multiple of 16 and lane "
            f"groups of whole vregs; S={s}, heads={heads} x "
            f"head_dim={head_dim} gives {per * head_dim} lanes a group "
            f"(attn_impl 'einsum' takes any shape)")
    n = b * s
    block, n_pad = _blocks(n, block_tokens)
    tokens = qkv.reshape(n, width)
    valid = (jnp.ones((n,), jnp.float32) if key_mask is None
             else key_mask.reshape(n).astype(jnp.float32))
    if n_pad != n:
        # padding lines are all PAD: uniform over zeros, sliced off below
        tokens = jnp.pad(tokens, ((0, n_pad - n), (0, 0)))
        valid = jnp.pad(valid, (0, n_pad - n))

    def third(which: int) -> pl.BlockSpec:
        return pl.BlockSpec((block, hd), lambda i: (i, which))

    itemsize = jnp.dtype(qkv.dtype).itemsize
    out = pl.pallas_call(
        functools.partial(_short_kernel, seq=s, head_dim=head_dim, per=per),
        grid=(n_pad // block,),
        in_specs=[pl.BlockSpec((block // s, s), lambda i: (i, 0)),
                  third(0), third(1), third(2)],
        out_specs=pl.BlockSpec((block, hd), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, hd), qkv.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            # four blocks double-buffered, and room for a step's temporaries
            vmem_limit_bytes=max(32 << 20, 12 * block * hd * itemsize)),
        interpret=interpret,
    )(valid.reshape(n_pad // s, s), tokens, tokens, tokens)
    return out[:n].reshape(b, s, hd)


def einsum_route(qkv: jax.Array, key_mask: Optional[jax.Array],
                 heads: int) -> jax.Array:
    """The same self-attention through ``dot_product_attention``, with the
    q / k / v split and the head-major transposes the kernel does without:
    what the kernel's backward differentiates."""
    mask = None if key_mask is None else key_mask[:, None, None, :]
    return merge_heads(dot_product_attention(*split_heads(qkv, heads), mask))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def short_attention(qkv: jax.Array,                  # [B, S, 3 * H * D]
                    key_mask: Optional[jax.Array],   # [B, S] bool; True = attend
                    heads: int,
                    block_tokens: Optional[int] = None,
                    interpret: bool = False) -> jax.Array:
    """Self-attention of whole short lines from the fused projection →
    ``[B, S, H * D]`` in ``qkv``'s dtype. ``block_tokens`` overrides the
    tokens a grid step owns (tests put several blocks on a small problem);
    ``interpret`` runs the Pallas interpreter (the CPU)."""
    return attn_short(qkv, key_mask, heads, block_tokens, interpret)


def _short_fwd(qkv, key_mask, heads, block_tokens, interpret):
    return (attn_short(qkv, key_mask, heads, block_tokens, interpret),
            (qkv, key_mask))


def _short_bwd(heads, block_tokens, interpret, residuals, g):
    qkv, key_mask = residuals
    _, pullback = jax.vjp(lambda x: einsum_route(x, key_mask, heads), qkv)
    return pullback(g)[0], None


short_attention.defvjp(_short_fwd, _short_bwd)


def _lane_matrices(width: int, lanes: int, dtype) -> tuple:
    """(copy, swap): the 0 / ±1 matrices ``[width, lanes]`` that write
    ``lanes // width`` copies of ``x`` side by side, as it is and with
    ``attention.rotary``'s pair swap (``out[2i] = -x[2i+1]``, ``out[2i+1]
    = x[2i]``). Built from iotas: a kernel captures no constant."""
    row = jax.lax.broadcasted_iota(jnp.int32, (width, lanes), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (width, lanes), 1) % width
    swap = (jnp.where((row % 2 == 0) & (col == row + 1), 1.0, 0.0)
            - jnp.where((row % 2 == 1) & (col == row - 1), 1.0, 0.0))
    return (col == row).astype(dtype), swap.astype(dtype)


def _latent_kernel(valid_ref, cos_ref, sin_ref, qn_ref, qr_ref, kn_ref,
                   kr_ref, v_ref, o_ref, kr_turned_ref, *, seq: int, per: int,
                   nope: int, rope: int, value_dim: int, causal: bool):
    """One block of whole lines and one lane group of rope parts (``per``
    heads): a few lines a step, head by head. The grid walks a block's
    heads innermost, so ``k_rope`` is turned once a block, at its first
    group, and kept in VMEM for the others."""
    block_tokens = qn_ref.shape[0]
    lanes = per * rope
    dtype = qr_ref.dtype
    scale = (nope + rope) ** -0.5
    lines = _STEP_TOKENS // seq
    steps = block_tokens // _STEP_TOKENS
    cos, sin = cos_ref[...], sin_ref[...]                # [step, lanes] f32

    @pl.when(pl.program_id(1) == 0)
    def _():
        # one k_rope for all heads: its copies side by side, one a head of
        # the lane group, each turned (two matmuls with 0 / ±1 matrices:
        # exact, and nothing is shifted across lanes)
        copy, swap = _lane_matrices(rope, lanes, dtype)

        def turn(t, carry):
            rows = _step_rows(t)
            k = kr_ref[rows, :]
            kr_turned_ref[rows, :] = (
                jnp.dot(k, copy, preferred_element_type=jnp.float32) * cos
                + jnp.dot(k, swap, preferred_element_type=jnp.float32) * sin
            ).astype(dtype)
            return carry

        jax.lax.fori_loop(0, steps, turn, 0)

    _, swap = _lane_matrices(lanes, lanes, dtype)
    head_of_lane = (jax.lax.broadcasted_iota(
        jnp.int32, (lines, seq, lanes), 2) // rope)
    lower = (jax.lax.broadcasted_iota(jnp.int32, (seq, seq), 0)
             >= jax.lax.broadcasted_iota(jnp.int32, (seq, seq), 1)
             ).astype(jnp.float32)[None]                        # [1, S, S]

    def one_step(t, carry):
        rows = _step_rows(t)
        keep = valid_ref[pl.ds(pl.multiple_of(t * lines, lines), lines),
                         :][:, None, :]                          # [L, 1, S]
        keep = (keep * lower if causal else keep) > 0.5
        q_rope = qr_ref[rows, :]
        q_rope = (q_rope.astype(jnp.float32) * cos
                  + jnp.dot(q_rope, swap,
                            preferred_element_type=jnp.float32) * sin
                  ).astype(dtype).reshape(lines, seq, lanes)
        k_rope = kr_turned_ref[rows, :].reshape(lines, seq, lanes)
        for head in range(per):
            q, k = (ref[rows, head * nope:(head + 1) * nope].reshape(
                lines, seq, nope) for ref in (qn_ref, kn_ref))
            cols = slice(head * value_dim, (head + 1) * value_dim)
            v = v_ref[rows, cols].reshape(lines, seq, value_dim)
            q_head = (q_rope if per == 1
                      else jnp.where(head_of_lane == head, q_rope, 0))
            logits = (jnp.einsum("lsd,ltd->lst", q, k,
                                 preferred_element_type=jnp.float32)
                      + jnp.einsum("lsd,ltd->lst", q_head, k_rope,
                                   preferred_element_type=jnp.float32)
                      ) * scale
            o_ref[rows, cols] = _softmax_pv(logits, keep, v).reshape(
                _STEP_TOKENS, value_dim).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, steps, one_step, 0)


# jitted for the reason attn_short is: six layers share one trace
@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8, 9))
def attn_short_latent(q: jax.Array, kv: jax.Array, k_rope: jax.Array,
                      key_mask: jax.Array, heads: int, nope: int,
                      theta: float, causal: bool,
                      block_tokens: Optional[int], interpret: bool
                      ) -> jax.Array:
    b, s = key_mask.shape
    n, rope = k_rope.shape
    value_dim = kv.shape[-1] // heads - nope
    per = heads_per_lane_group(heads, rope)
    lanes = per * rope
    if (n != b * s or q.shape != (n, heads * (nope + rope))
            or kv.shape != (n, heads * (nope + value_dim))
            or s > MAX_SEQ or _STEP_TOKENS % s
            or (heads * nope) % lanes or (heads * nope) % (per * value_dim)):
        raise ValueError(
            f"short_latent_attention takes q [rows*S, heads*nope + "
            f"heads*rope], kv [rows*S, heads*nope + heads*value], k_rope "
            f"[rows*S, rope] with S <= {MAX_SEQ} dividing {_STEP_TOKENS} "
            f"and the nope parts ending on a block of rope parts and of "
            f"values; got {q.shape}, {kv.shape}, {k_rope.shape} at "
            f"heads={heads}, nope={nope}, key_mask {key_mask.shape}")
    if not interpret and not fits_latent(s, heads, nope, rope, value_dim):
        raise ValueError(
            f"short_latent_attention compiles for S a multiple of 16, nope "
            f"and value widths of whole lane groups and rope parts filling "
            f"one; S={s}, heads={heads}, nope={nope}, rope={rope}, "
            f"value={value_dim} (attn_impl 'einsum' takes any shape)")
    block, n_pad = _blocks(n, block_tokens)
    valid = key_mask.reshape(n).astype(jnp.float32)
    if n_pad != n:
        # padding lines are all PAD: uniform over zeros, sliced off below
        q, kv, k_rope = (jnp.pad(x, ((0, n_pad - n), (0, 0)))
                         for x in (q, kv, k_rope))
        valid = jnp.pad(valid, (0, n_pad - n))
    # a step's rotary tables: its lines' positions down the rows, the lane
    # group's heads side by side
    cos, sin = (jnp.tile(x, (_STEP_TOKENS // s, per))
                for x in rotary_tables(s, rope, theta))

    def columns(width: int, first: int) -> pl.BlockSpec:
        """A lane group's block of ``width`` columns, counted from block
        ``first``: the operand's second part starts where its nope parts
        end."""
        return pl.BlockSpec((block, width), lambda i, j: (i, first + j))

    def whole(shape) -> pl.BlockSpec:
        return pl.BlockSpec(shape, lambda i, j: (0, 0))

    itemsize = jnp.dtype(q.dtype).itemsize
    out = pl.pallas_call(
        functools.partial(_latent_kernel, seq=s, per=per, nope=nope,
                          rope=rope, value_dim=value_dim, causal=causal),
        grid=(n_pad // block, heads // per),
        in_specs=[pl.BlockSpec((block // s, s), lambda i, j: (i, 0)),
                  whole((_STEP_TOKENS, lanes)), whole((_STEP_TOKENS, lanes)),
                  columns(per * nope, 0),
                  columns(lanes, heads * nope // lanes),
                  columns(per * nope, 0),
                  pl.BlockSpec((block, rope), lambda i, j: (i, 0)),
                  columns(per * value_dim, heads * nope // (per * value_dim))],
        out_specs=columns(per * value_dim, 0),
        out_shape=jax.ShapeDtypeStruct((n_pad, heads * value_dim), q.dtype),
        scratch_shapes=[pltpu.VMEM((block, lanes), q.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            # six blocks double-buffered, and room for a step's temporaries
            vmem_limit_bytes=max(32 << 20, 6 * block * per * (
                2 * nope + 2 * value_dim + 2 * rope) * itemsize)),
        interpret=interpret,
    )(valid.reshape(n_pad // s, s), cos, sin, q, q, kv, k_rope, kv)
    return out[:n]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def short_latent_attention(
        q: jax.Array,         # [B * S, H * nope + H * rope]
        kv: jax.Array,        # [B * S, H * nope + H * Dv]
        k_rope: jax.Array,    # [B * S, rope]
        key_mask: jax.Array,  # [B, S] bool; True = attend
        heads: int, nope: int, theta: float, causal: bool,
        block_tokens: Optional[int] = None,
        interpret: bool = False) -> jax.Array:
    """``ops/attention.py::latent_attention``'s kernel route → ``[B * S,
    H * Dv]`` in ``q``'s dtype; ``block_tokens`` and ``interpret`` as
    :func:`short_attention`."""
    return attn_short_latent(q, kv, k_rope, key_mask, heads, nope, theta,
                             causal, block_tokens, interpret)


def _latent_fwd(q, kv, k_rope, key_mask, *static):
    return (attn_short_latent(q, kv, k_rope, key_mask, *static),
            (q, kv, k_rope, key_mask))


def _latent_bwd(heads, nope, theta, causal, block_tokens, interpret,
                residuals, g):
    q, kv, k_rope, key_mask = residuals
    _, pullback = jax.vjp(
        lambda *operands: latent_einsum(*operands, key_mask, heads, nope,
                                        theta, causal), q, kv, k_rope)
    return (*pullback(g), None)


short_latent_attention.defvjp(_latent_fwd, _latent_bwd)
