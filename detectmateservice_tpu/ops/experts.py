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
  N·K assignments are sorted by expert with the held ones first and the
  sorted list is walked in chunks of fixed length, as far as the last held
  assignment and no further: a ``scan`` over the chunks that skips the
  dead ones under ``lax.cond`` (a loop whose length follows the routing
  would have no reverse pass, and the fit takes the same walk; each live
  chunk copies the float32 accumulator, 0.65 ms at N = 32768). Inside a
  live chunk the three matmuls of the gated unit are grouped matmuls
  (``jax.lax.ragged_dot``: on the TPU a native grouped-matmul call whose
  tiles follow the group sizes; on the CPU XLA's reference lowering).
  Under even routing 16 of 128 experts see 0.75 N assignments: two of
  the 12 chunks of N/2 rows.
* PAD positions (``valid`` false) are neither routed nor counted.

Precision: router logits, scores, top-k and the weights in float32 with
``Precision.HIGHEST`` (the published code casts the hidden state to float32
for the router); expert matmuls in the compute dtype with float32
accumulation; the combine accumulates in float32.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

# a chunk of the sorted assignment list is half the call's token count, and
# the whole list where that would be under _MIN_CHUNK_ROWS. Half, because
# even routing over an eighth of the experts puts 0.75 N assignments on the
# chip: with N/4 (or any N/2^k) that load ends exactly on a chunk's edge,
# with N/2 it is two chunks until the load passes 1.33x even; and each live
# chunk costs a copy of the accumulator
_CHUNKS_PER_TOKEN_COUNT = 2
_MIN_CHUNK_ROWS = 2048


class Routing(NamedTuple):
    experts: jax.Array     # [N, K] int32 ids over ALL experts; -1 = PAD token
    weights: jax.Array     # [N, K] float32, normalised over the K chosen


def route(x: jax.Array, router: jax.Array, bias: jax.Array,
          valid: jax.Array, *, top_k: int, norm_topk_prob: bool,
          scaling: float, scoring_func: str = "sigmoid",
          norm_eps: float = 1e-20) -> Routing:
    """Score ``x`` [N, D] against ``router`` [D, E] in float32 and choose
    ``top_k`` of the E experts by ``score + bias`` (``bias`` is the
    selection-only correction buffer: it moves the choice, never the
    weight, and no gradient reaches it). ``valid`` [N] marks non-PAD
    tokens; a PAD token's experts are -1. ``norm_eps`` is what the
    published code adds to the chosen scores' sum before it divides (the
    sources differ: 1e-20, 1e-6)."""
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if scoring_func == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    elif scoring_func == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"unknown scoring_func {scoring_func!r}")
    choice = scores + jax.lax.stop_gradient(bias.astype(jnp.float32))
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


def sorted_assignments(routing: Routing, offset: int, held: int):
    """The N·K assignments sorted by expert, the held ones first:
    ``(token_of [N·K], weight_of [N·K], starts [held], ends [held], counts
    [held])`` — the token and weight of each sorted assignment and where
    each held expert's group starts and ends in the list."""
    k = routing.experts.shape[1]
    local = routing.experts.reshape(-1) - offset
    on_held = (local >= 0) & (local < held)
    # the rest sort behind the held ones
    order = jnp.argsort(jnp.where(on_held, local, held),
                        stable=True).astype(jnp.int32)
    counts = held_counts(routing.experts, offset, held)
    ends = jnp.cumsum(counts)
    return (order // k, routing.weights.reshape(-1)[order], ends - counts,
            ends, counts)


def routed_experts(x: jax.Array, routing: Routing, gate: jax.Array,
                   up: jax.Array, down: jax.Array, *, offset: int = 0,
                   chunk_rows: Optional[int] = None,
                   ) -> Tuple[jax.Array, jax.Array]:
    """Σ over a token's assignments on HELD experts of ``w · E(x)`` →
    ``([N, D] float32, [held] int32 assignments per held expert)``.

    ``gate``/``up`` [held, D, M] and ``down`` [held, M, D] are the held
    experts ``offset .. offset + held - 1`` of the gated unit
    ``down(silu(gate·x) ⊙ up·x)``; ``x`` [N, D] is in the compute dtype."""
    n, d = x.shape
    k = routing.experts.shape[1]
    held = gate.shape[0]
    slots = n * k
    chunk = chunk_rows or chunk_rows_for(n, k)
    if slots % chunk:
        raise ValueError(f"chunk_rows {chunk} does not divide {slots} "
                         "assignment slots")
    with jax.named_scope("dispatch"):
        token_of, weight_of, starts, ends, counts = sorted_assignments(
            routing, offset, held)
        n_held = ends[-1]
    # cast once, not once a chunk
    gate, up, down = (w.astype(x.dtype) for w in (gate, up, down))

    def one_chunk(acc: jax.Array, lo: jax.Array) -> jax.Array:
        with jax.named_scope("dispatch"):
            tok = jax.lax.dynamic_slice(token_of, (lo,), (chunk,))
            wts = jax.lax.dynamic_slice(weight_of, (lo,), (chunk,))
            computed = lo + jnp.arange(chunk, dtype=jnp.int32) < n_held
            sizes = (jnp.clip(ends - lo, 0, chunk)
                     - jnp.clip(starts - lo, 0, chunk)).astype(jnp.int32)
            # rows past the last held assignment join the last group: they
            # are computed and masked. A grouped matmul leaves rows outside
            # every group uninitialised on the TPU, and though the forward
            # pass masks them, 0 x NaN in the backward pass does not (a fit
            # on the chip came out NaN; on the CPU such rows read zero)
            sizes = sizes.at[-1].add(chunk - sizes.sum())
            xs = x[tok]
        with jax.named_scope("experts"):
            g = jax.lax.ragged_dot(xs, gate, sizes,
                                   preferred_element_type=jnp.float32)
            u = jax.lax.ragged_dot(xs, up, sizes,
                                   preferred_element_type=jnp.float32)
            h = (jax.nn.silu(g) * u).astype(x.dtype)
            y = jax.lax.ragged_dot(h, down, sizes,
                                   preferred_element_type=jnp.float32)
        with jax.named_scope("combine"):
            y = jnp.where(computed[:, None], y, 0.0) * wts[:, None]
            return acc.at[tok].add(y)

    acc = jnp.zeros((n, d), jnp.float32)
    if chunk == slots:
        return one_chunk(acc, jnp.int32(0)), counts

    def step(acc, lo):
        return jax.lax.cond(lo < n_held, one_chunk,
                            lambda a, _: a, acc, lo), None

    acc, _ = jax.lax.scan(step, acc,
                          jnp.arange(0, slots, chunk, dtype=jnp.int32))
    return acc, counts
