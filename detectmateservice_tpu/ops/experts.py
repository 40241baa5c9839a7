"""The sparse-expert layer: route every token over ALL published experts,
compute the part the experts held on this chip give.

This is the layer expert parallelism needs (one chip of a group that shares
each layer holds ``held`` of the ``E`` routed experts): the router keeps its
published width and its experts per token, the weights normalise over all
chosen experts, and what the absent experts would have added is left out —
nothing here stands in for the other chips or for their exchange.

Contract:

* **No assignment is ever dropped.** There is no capacity factor: at any
  skew — every token on held experts, or none — each (token, expert)
  assignment that falls on a held expert is computed.
* **Shapes are fixed by the call's shape alone** (N tokens, K experts a
  token), so a compile bucket has one program whatever the routing.
* **Cost follows the held assignments**, not N·K and not held x N: the
  N·K assignments are sorted by expert with the held ones first (the
  weights ride the sort) and the sorted list is walked in chunks of fixed
  length, as far as the last held assignment and no further: the first
  chunk makes the accumulator, a ``scan`` over the rest skips the dead
  ones under ``lax.cond`` (a loop whose length follows the routing would
  have no reverse pass, and the fit takes the same walk). Inside a live
  chunk the three matmuls of the gated unit (the two of a non-gated
  one) are grouped matmuls
  (``jax.lax.ragged_dot``: on the TPU a native grouped-matmul call whose
  tiles follow the group sizes; on the CPU XLA's reference lowering).
  Under even routing 16 of 128 experts see 0.75 N assignments: two of
  the 12 chunks of N/2 rows.
* PAD positions (``valid`` false) are neither routed nor counted.

The way back from expert order to token order has two forms, told apart by
:func:`combine_route` from the platform and the call's shape:

* ``scatter_add`` — ``acc.at[token].add(w · y)``: a scatter whose indices
  collide (a token has up to K held assignments), which XLA lowers on the
  v5e to a loop over rows: 3.07 ms for a chunk of 16384 rows of 2048
  float32 beside a 0.41 ms mask-and-weight pass and 0.41 ms of zeros
  stored for the accumulator. What the CPU and a mesh run: its gradient
  is autodiff's, the gather ``g[token] · w``.
* ``segment_sum`` — a second permutation beside the first (``token_order``:
  each chunk's rows by token, one more sort of the size the layer already
  sorts, 0.13 ms), one row gather that brings a live chunk's ``y`` into
  token order (0.64 ms), and the kernel ``segment_sum_add`` (0.87 ms): a
  walk over blocks of 128 tokens, each with the one contiguous range of
  the chunk's rows that holds its addends; mask and weight on the VPU,
  the run sums as a ``[tokens, rows]`` 0/1 matrix on the MXU against ``w ·
  y`` split into three bfloat16 parts (exact products, float32
  accumulation: the float32 sum is kept), the accumulator's block read and
  written once, in place. The first chunk has no accumulator to read and
  writes every block of tokens, its own or not, so no zeros are stored
  first. What ONE TPU runs wherever the shapes tile, the fit's 32-row
  step (one chunk, no loop) included: ``custom_vjp`` over gather and
  kernel, whose backward is the scatter-add's own — the gather
  ``g[token] · w`` in expert order, no scatter in either direction.

Precision: router logits, scores, top-k and the weights in float32 with
``Precision.HIGHEST`` (the published code casts the hidden state to float32
for the router); expert matmuls in the compute dtype with float32
accumulation; a token's addends are weighted and summed in float32 on
either way back.
"""
from __future__ import annotations

import contextlib
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# a chunk of the sorted assignment list is half the call's token count, and
# the whole list where that would be under _MIN_CHUNK_ROWS. Half, because
# even routing over an eighth of the experts puts 0.75 N assignments on the
# chip: with N/4 (or any N/2^k) that load ends exactly on a chunk's edge,
# with N/2 it is two chunks until the load passes 1.33x even; and each live
# chunk reads and writes the accumulator once
_CHUNKS_PER_TOKEN_COUNT = 2
_MIN_CHUNK_ROWS = 2048

LANES = 128
WAYS_BACK = ("scatter_add", "segment_sum")
# a step of the kernel: a block of tokens (the accumulator's rows) by a
# block of the chunk's token-ordered rows, all columns
_TOKEN_BLOCK = 128
_ROW_BLOCK = 128
_LANE_SLAB = 512


class Routing(NamedTuple):
    experts: jax.Array     # [N, K] int32 ids over ALL experts; -1 = PAD token
    weights: jax.Array     # [N, K] float32, normalised over the K chosen


def route(x: jax.Array, router: jax.Array, bias: jax.Array,
          valid: jax.Array, *, top_k: int, norm_topk_prob: bool,
          scaling: float, scoring_func: str = "sigmoid",
          norm_eps: float = 1e-20, n_group: int = 1,
          topk_group: int = 1) -> Routing:
    """Score ``x`` [N, D] against ``router`` [D, E] in float32 and choose
    ``top_k`` of the E experts by ``score + bias`` (``bias`` is the
    selection-only correction buffer: it moves the choice, never the
    weight, and no gradient reaches it). ``valid`` [N] marks non-PAD
    tokens; a PAD token's experts are -1. ``norm_eps`` is what the
    published code adds to the chosen scores' sum before it divides (the
    sources differ: 1e-20, 1e-6). With ``n_group`` over 1 the choice is
    group-limited (:func:`keep_groups`): the experts of the ``topk_group``
    best of ``n_group`` groups stand, the others are set aside, before the
    ``top_k`` are taken; at one group nothing is added to the program."""
    # the grouped router's three steps carry scopes of their own; at one
    # group the operations keep the names the accepted cells' traces have
    scope = (jax.named_scope if n_group > 1
             else lambda name: contextlib.nullcontext())
    with scope("scores"):
        logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        if scoring_func == "sigmoid":
            scores = jax.nn.sigmoid(logits)
        elif scoring_func == "softmax":
            scores = jax.nn.softmax(logits, axis=-1)
        else:
            raise ValueError(f"unknown scoring_func {scoring_func!r}")
        choice = scores + jax.lax.stop_gradient(bias.astype(jnp.float32))
    if n_group > 1:
        with scope("groups"):
            choice = keep_groups(choice, n_group, topk_group)
    with scope("top_k"):
        _, experts = jax.lax.top_k(choice, top_k)
        # the chosen experts' scores by comparison, not by a gather of N*K
        # scalars (2.7 ms against 0.1 on the v5e at N = 32768)
        chosen = experts[..., None] == jnp.arange(scores.shape[-1])
        weights = jnp.where(chosen, scores[:, None, :], 0.0).sum(-1)
        if norm_topk_prob:
            weights = weights / (weights.sum(-1, keepdims=True) + norm_eps)
        weights = weights * scaling
        experts = jnp.where(valid[:, None], experts.astype(jnp.int32), -1)
    return Routing(experts, weights)


def keep_groups(choice: jax.Array, n_group: int, topk_group: int
                ) -> jax.Array:
    """Group-limited routing's first step: ``choice`` [N, E] float32 in
    ``n_group`` groups of ``E / n_group`` consecutive experts, a group's
    score the sum of its two largest entries; the ``topk_group`` best
    groups keep their entries, every other entry reads ``-inf`` and is
    never chosen (``topk_group · E / n_group`` entries stand, which has to
    cover ``top_k``: the caller's check)."""
    n, e = choice.shape
    if e % n_group or not 0 < topk_group <= n_group:
        raise ValueError(f"route: {e} experts in n_group {n_group} groups "
                         f"with topk_group {topk_group} kept do not divide")
    by_group = choice.reshape(n, n_group, e // n_group)
    group_score = jax.lax.top_k(by_group, min(2, e // n_group))[0].sum(-1)
    _, best = jax.lax.top_k(group_score, topk_group)            # [N, G']
    kept = (best[..., None] == jnp.arange(n_group)).any(-2)     # [N, G]
    return jnp.where(kept[..., None], by_group, -jnp.inf).reshape(n, e)


def chunk_rows_for(tokens: int, top_k: int) -> int:
    """Length of one chunk of the sorted assignment list for a call of
    ``tokens`` tokens: half the token count, the whole list for a small
    call (the fit's 32-row batches among them: one chunk, no loop)."""
    if (tokens % _CHUNKS_PER_TOKEN_COUNT == 0
            and tokens // _CHUNKS_PER_TOKEN_COUNT >= _MIN_CHUNK_ROWS):
        return tokens // _CHUNKS_PER_TOKEN_COUNT
    return tokens * top_k


def held_counts(experts: jax.Array, offset: int, held: int) -> jax.Array:
    """[held] int32: how many of the assignments ``experts`` [N, K] fall on
    each of the experts ``offset .. offset + held - 1``."""
    local = experts.reshape(-1) - offset
    return (local[:, None] == jnp.arange(held)[None, :]).sum(
        0, dtype=jnp.int32)


class Dispatch(NamedTuple):
    """One layer's sorted assignments (:func:`dispatch`)."""
    token_of: jax.Array    # [N·K] token of each assignment, sorted by expert
    weight_of: jax.Array   # [N·K] its weight
    starts: jax.Array      # [held] where each held expert's group starts
    ends: jax.Array        # [held] and ends
    counts: jax.Array      # [held] assignments per held expert


class TokenOrder(NamedTuple):
    """The sorted list's second permutation (:func:`token_order`): chunk
    by chunk, its rows by token."""
    rows: jax.Array        # [N·K] row of the sorted list at each place
    token: jax.Array       # [N·K] its token, ascending in a chunk; N = dead
    weight: jax.Array      # [N·K] its weight; 0 = dead


def dispatch(routing: Routing, offset: int, held: int) -> Dispatch:
    """The N·K assignments sorted by expert, the held ones first."""
    k = routing.experts.shape[1]
    local = routing.experts.reshape(-1) - offset
    on_held = (local >= 0) & (local < held)
    # the rest sort behind the held ones; the weights ride the sort (a
    # gather of N·K scalars costs ten times the sort on the v5e)
    _, order, weight_of = jax.lax.sort(
        (jnp.where(on_held, local, held),
         jnp.arange(local.shape[0], dtype=jnp.int32),
         routing.weights.reshape(-1)), num_keys=1, is_stable=True)
    counts = held_counts(routing.experts, offset, held)
    ends = jnp.cumsum(counts)
    return Dispatch(order // k, weight_of, ends - counts, ends, counts)


def token_order(plan: Dispatch, tokens: int, chunk: int) -> TokenOrder:
    """Within each chunk of ``chunk`` rows of the sorted list, the rows
    re-ordered by token — the sort by expert is a permutation, so is this
    — so that a token's addends of one chunk are adjacent. Rows past the
    last held assignment carry the token N and the weight 0 and sort behind
    a chunk's live rows. One sort of the size the layer already sorts; the
    weights ride it as numbers (their gradient goes by the first list:
    :func:`way_back`)."""
    row = jnp.arange(plan.token_of.shape[0], dtype=jnp.int32)
    live = row < plan.ends[-1]
    chunk_of = row // chunk
    key, rows, weight = jax.lax.sort(
        (chunk_of * (tokens + 1) + jnp.where(live, plan.token_of, tokens),
         row, jnp.where(live, jax.lax.stop_gradient(plan.weight_of), 0.0)),
        num_keys=1, is_stable=True)
    return TokenOrder(rows, key - chunk_of * (tokens + 1), weight)


# -- the way back: a live chunk's results from expert order to token order --

def _tiles(tokens: int, chunk: int, width: int) -> bool:
    """Whether the kernel's blocks divide the call: tokens and a chunk's
    rows in whole blocks, the width in whole lane groups."""
    return (tokens % _TOKEN_BLOCK == 0 and chunk % _ROW_BLOCK == 0
            and width % LANES == 0)


def combine_route(platform: str, tokens: int, chunk: int, width: int,
                  mesh_devices: int = 1) -> str:
    """``"segment_sum"`` or ``"scatter_add"`` for one traced call: the
    kernel on ONE TPU (GSPMD does not partition a Pallas call) wherever
    the shapes tile — the served buckets and the fit's 32-row step alike
    (its donated step 73.2 -> 71.6 ms and 75.0 -> 71.4 on the v5e, PERF.md
    section 6, PR 32) — else the scatter-add."""
    if (platform == "tpu" and mesh_devices == 1
            and _tiles(tokens, chunk, width)):
        return "segment_sum"
    return "scatter_add"


def segment_work(token: jax.Array, live: jax.Array, tokens: int,
                 whole: bool = False):
    """The kernel's list of steps for one chunk: every (block of tokens,
    block of the chunk's token-ordered rows) pair that holds a live row,
    in order — a merge of two ascending lists, so at most as many pairs as
    both have blocks. With ``whole`` every block of tokens has a step,
    rows or none: a row block's steps start behind the one before it and
    the last live one's run to the end, so the blocks of tokens between
    two row blocks, before the first and behind the last are walked too.
    → ``(token block [G], row block [G], flags [G]: 1 = the step is the
    first on its token block, 2 = it has rows to add)``."""
    chunk = token.shape[0]
    n_tb, n_rb = tokens // _TOKEN_BLOCK, chunk // _ROW_BLOCK
    steps = n_tb + n_rb
    last = token[jnp.maximum(live - 1, 0)]
    block = jnp.arange(n_rb, dtype=jnp.int32)
    alive = block * _ROW_BLOCK < live
    first_tb = token[::_ROW_BLOCK] // _TOKEN_BLOCK
    last_tb = jnp.minimum(token[_ROW_BLOCK - 1::_ROW_BLOCK],
                          last) // _TOKEN_BLOCK
    if whole:
        # block 0 is the last live one where none lives
        ends_at = jnp.maximum(-(-live // _ROW_BLOCK), 1) - 1
        alive = alive | (block == 0)
        behind = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                  last_tb[:-1] + 1])
        first_tb = jnp.minimum(first_tb, behind)
        last_tb = jnp.where(block == ends_at, n_tb - 1, last_tb)
    pairs = jnp.where(alive, last_tb - first_tb + 1, 0)
    ends = jnp.cumsum(pairs)
    total = ends[-1]
    step = jnp.arange(steps, dtype=jnp.int32)
    at = jnp.minimum(step, jnp.maximum(total - 1, 0))
    rb = jnp.minimum((ends[None, :] <= at[:, None]).sum(-1, dtype=jnp.int32),
                     n_rb - 1)
    tb = jnp.clip(first_tb[rb] + at - (ends - pairs)[rb], 0, n_tb - 1)
    new = jnp.concatenate([jnp.ones((1,), bool), tb[1:] != tb[:-1]])
    return tb, rb, new + 2 * (step < total)


def _segment_kernel(tb_ref, rb_ref, flag_ref, live_ref, tok_ref, w_ref,
                    y_ref, *refs, slab: int):
    out_ref = refs[-1]
    step = pl.program_id(0)
    flag = flag_ref[step]

    @pl.when(flag % 2 == 1)
    def _():
        # the accumulator's block, or zeros where there is none yet
        out_ref[...] = (refs[0][...] if len(refs) == 2
                        else jnp.zeros_like(out_ref))

    @pl.when(flag >= 2)
    def _():
        ids = tb_ref[step] * _TOKEN_BLOCK + jax.lax.broadcasted_iota(
            jnp.int32, (_TOKEN_BLOCK, _ROW_BLOCK), 0)
        # [tokens, rows] 0/1: exact in bfloat16, so a float32 addend
        # split into three bfloat16 parts is summed exactly on the MXU
        picks = jnp.where(tok_ref[...] == ids, 1.0, 0.0).astype(jnp.bfloat16)
        # a row past the chunk's live ones holds what the last group's
        # matmul made of another token: zero whatever it is (0 x NaN)
        dead = (rb_ref[step] * _ROW_BLOCK + jax.lax.broadcasted_iota(
            jnp.int32, (_ROW_BLOCK, 1), 0)) >= live_ref[0]
        weight = w_ref[...]
        for lo in range(0, y_ref.shape[1], slab):
            rest = jnp.where(dead, 0.0, y_ref[:, lo:lo + slab] * weight)
            total = out_ref[:, lo:lo + slab]
            for _ in range(3):
                part = rest.astype(jnp.bfloat16)
                total = total + jnp.dot(picks, part,
                                        preferred_element_type=jnp.float32)
                rest = rest - part.astype(jnp.float32)
            out_ref[:, lo:lo + slab] = total


@functools.partial(jax.jit, static_argnames=("tokens", "interpret"))
def segment_sum_add(acc: Optional[jax.Array], y: jax.Array, token: jax.Array,
                    weight: jax.Array, live: jax.Array, tokens: int,
                    interpret: bool = False) -> jax.Array:
    """``acc`` [N, D] float32 (``None``: zeros, never written or read)
    plus, for each of the ``tokens`` = N tokens, the weighted sum of its
    rows of ``y`` [chunk, D] float32, whose rows are in token order
    (``token`` [chunk] ascending over the first ``live`` rows, ``weight``
    [chunk]). The kernel: a walk over blocks of tokens, each with the one
    contiguous range of ``y`` that holds its rows; ``acc`` is updated in
    place, and without one every block is written. jitted, so that a
    stack's layers share one trace of its body (PERF.md section 6, PR 28)."""
    chunk, d = y.shape
    tb, rb, flags = segment_work(token, live, tokens, whole=acc is None)
    slab = _LANE_SLAB if d % _LANE_SLAB == 0 else LANES

    def rows(width) -> pl.BlockSpec:
        return pl.BlockSpec((_ROW_BLOCK, width),
                            lambda i, tb, rb, fl, live: (rb[i], 0))

    acc_block = pl.BlockSpec((_TOKEN_BLOCK, d),
                             lambda i, tb, rb, fl, live: (tb[i], 0))
    return pl.pallas_call(
        functools.partial(_segment_kernel, slab=slab),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(tb.shape[0],),
            in_specs=[pl.BlockSpec((None, 1, _ROW_BLOCK),
                                   lambda i, tb, rb, fl, live: (rb[i], 0, 0)),
                      rows(1), rows(d)] + [acc_block] * (acc is not None),
            out_specs=acc_block),
        out_shape=jax.ShapeDtypeStruct((tokens, d), jnp.float32),
        # acc is the eighth operand, after the four prefetched lists
        input_output_aliases={} if acc is None else {7: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # the rows' block and the accumulator's, in and out, double-
            # buffered, and room for a step's temporaries
            vmem_limit_bytes=8 * (_ROW_BLOCK + 2 * _TOKEN_BLOCK) * d
            + (8 << 20)),
        interpret=interpret, name="segment_sum_add",
    )(tb, rb, flags, live.reshape(1).astype(jnp.int32),
      token.reshape(chunk // _ROW_BLOCK, 1, _ROW_BLOCK),
      weight.reshape(chunk, 1), y, *(() if acc is None else (acc,)))


class _Lists(NamedTuple):
    """What :func:`_segment_sum` reads of one chunk beside ``y`` and the
    weights: nothing a gradient reaches."""
    token_of: jax.Array    # [chunk] token of each row, expert order
    place: jax.Array       # [chunk] row of the chunk at each token-ordered place
    token: jax.Array       # [chunk] its token (TokenOrder.token)
    weight: jax.Array      # [chunk] its weight (TokenOrder.weight)
    live: jax.Array        # [] rows of the chunk that are held assignments


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _segment_sum(acc, y, weight, lists: _Lists, tokens, interpret):
    """One row gather brings ``y`` [chunk, D] (expert order) into token
    order; mask, weight and sum are the kernel's. ``weight`` [chunk] is in
    expert order and is read by the backward pass alone."""
    return segment_sum_add(acc, y[lists.place], lists.token, lists.weight,
                           lists.live, tokens, interpret)


def _segment_fwd(acc, y, weight, lists, tokens, interpret):
    # whether there was an accumulator is kept as structure, not as a value
    return (_segment_sum.fun(acc, y, weight, lists, tokens, interpret),
            (None if acc is None else (), y, weight, lists))


def _segment_bwd(tokens, interpret, saved, grad):
    # exact, and the scatter-add's own: a sum's gradient is a gather by
    # token in expert order; rows past the live ones take zero
    had_acc, y, weight, lists = saved
    alive = (jnp.arange(y.shape[0], dtype=jnp.int32) < lists.live)[:, None]
    back = jnp.where(alive, grad[lists.token_of], 0.0)
    return (None if had_acc is None else grad, back * weight[:, None],
            (back * jnp.where(alive, y, 0.0)).sum(-1), None)


_segment_sum.defvjp(_segment_fwd, _segment_bwd)


def way_back(way: str, acc: Optional[jax.Array], y: jax.Array,
             plan: Dispatch, back: Optional[TokenOrder], lo: jax.Array,
             tokens: int, interpret: bool = False) -> jax.Array:
    """``acc`` [N, D] float32 (``None``: zeros) plus a chunk's results:
    ``y`` [chunk, D] float32 are rows ``lo .. lo + chunk - 1`` of the
    sorted list, each weighted (zero past the last held assignment) and
    added to its token's row of ``tokens`` = N. ``way`` is one of
    ``WAYS_BACK`` (:func:`combine_route`), ``back`` the list's
    :func:`token_order` where that is ``segment_sum``."""
    chunk = y.shape[0]
    live = jnp.clip(plan.ends[-1] - lo, 0, chunk)

    def rows(of: jax.Array) -> jax.Array:
        return jax.lax.dynamic_slice(of, (lo,), (chunk,))

    if way == "scatter_add":
        computed = jnp.arange(chunk, dtype=jnp.int32) < live
        y = jnp.where(computed[:, None], y, 0.0) * rows(
            plan.weight_of)[:, None]
        if acc is None:
            acc = jnp.zeros((tokens, y.shape[1]), jnp.float32)
        return acc.at[rows(plan.token_of)].add(y)
    return _segment_sum(
        acc, y, rows(plan.weight_of),
        _Lists(rows(plan.token_of), rows(back.rows) - lo, rows(back.token),
               rows(back.weight), live), tokens, interpret)


def routed_experts(x: jax.Array, routing: Routing, gate: Optional[jax.Array],
                   up: jax.Array, down: jax.Array, *, offset: int = 0,
                   chunk_rows: Optional[int] = None,
                   combine: str = "scatter_add",
                   platform: str = "") -> Tuple[jax.Array, jax.Array]:
    """Σ over a token's assignments on HELD experts of ``w · E(x)`` →
    ``([N, D] float32, [held] int32 assignments per held expert)``.

    ``gate``/``up`` [held, D, M] and ``down`` [held, M, D] are the held
    experts ``offset .. offset + held - 1`` of the gated unit
    ``down(silu(gate·x) ⊙ up·x)`` or, with no ``gate``, of the non-gated
    ``down(relu(up·x)²)``; ``x`` [N, D] is in the compute dtype (D the
    width the experts live at: the residual's, or a latent's).
    ``combine`` is the way back, one of ``WAYS_BACK`` (the caller asks
    :func:`combine_route`), ``platform`` where the call runs (the process
    default where empty)."""
    n, d = x.shape
    k = routing.experts.shape[1]
    held = up.shape[0]
    slots = n * k
    chunk = chunk_rows or chunk_rows_for(n, k)
    if slots % chunk:
        raise ValueError(f"chunk_rows {chunk} does not divide {slots} "
                         "assignment slots")
    if combine not in WAYS_BACK:
        raise ValueError(f"combine {combine!r}: expected one of {WAYS_BACK}")
    if combine == "segment_sum" and not _tiles(n, chunk, d):
        raise ValueError(
            f"combine 'segment_sum': {n} tokens and chunks of {chunk} rows "
            f"by {d} columns do not tile (blocks of {_TOKEN_BLOCK} tokens "
            f"and {_ROW_BLOCK} rows, columns in multiples of {LANES})")
    platform = platform or jax.default_backend()
    with jax.named_scope("dispatch"):
        plan = dispatch(routing, offset, held)
        back = (token_order(plan, n, chunk) if combine == "segment_sum"
                else None)
        n_held = plan.ends[-1]
    # cast once, not once a chunk
    gate, up, down = (None if w is None else w.astype(x.dtype)
                      for w in (gate, up, down))

    def one_chunk(acc: Optional[jax.Array], lo: jax.Array) -> jax.Array:
        with jax.named_scope("dispatch"):
            tok = jax.lax.dynamic_slice(plan.token_of, (lo,), (chunk,))
            sizes = (jnp.clip(plan.ends - lo, 0, chunk)
                     - jnp.clip(plan.starts - lo, 0, chunk)).astype(jnp.int32)
            # rows past the last held assignment join the last group: they
            # are computed and masked. A grouped matmul leaves rows outside
            # every group uninitialised on the TPU, and though the forward
            # pass masks them, 0 x NaN in the backward pass does not (a fit
            # on the chip came out NaN; on the CPU such rows read zero)
            sizes = sizes.at[-1].add(chunk - sizes.sum())
            xs = x[tok]
        with jax.named_scope("experts"):
            if gate is not None:
                g = jax.lax.ragged_dot(xs, gate, sizes,
                                       preferred_element_type=jnp.float32)
            u = jax.lax.ragged_dot(xs, up, sizes,
                                   preferred_element_type=jnp.float32)
            h = (jnp.square(jax.nn.relu(u)) if gate is None
                 else jax.nn.silu(g) * u).astype(x.dtype)
            y = jax.lax.ragged_dot(h, down, sizes,
                                   preferred_element_type=jnp.float32)
        with jax.named_scope("combine"):
            return way_back(combine, acc, y, plan, back, lo, n,
                            platform == "cpu")

    # the first chunk makes the accumulator (every row written once: no
    # zeros are stored first and read back), the walk adds the rest to it
    if chunk == slots:
        return one_chunk(None, jnp.int32(0)), plan.counts
    acc = jax.lax.cond(n_held > 0, lambda: one_chunk(None, jnp.int32(0)),
                       lambda: jnp.zeros((n, d), jnp.float32))

    def step(acc, lo):
        return jax.lax.cond(lo < n_held, one_chunk,
                            lambda a, _: a, acc, lo), None

    acc, _ = jax.lax.scan(step, acc,
                          jnp.arange(chunk, slots, chunk, dtype=jnp.int32))
    return acc, plan.counts
