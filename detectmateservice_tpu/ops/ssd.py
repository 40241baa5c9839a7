"""The selective state-space operation over a line's positions, token-major.

A state-space mixer (models/moe_ssm.py) keeps, per head, a ``[P, N]`` state
that every position decays and writes to, and reads:

    S_t = exp(Δ_t A) · S_{t-1} + Δ_t · x_t B_tᵀ     (A < 0, Δ_t > 0)
    o_t = S_t C_t + D · x_t

with ``S_0 = 0`` at a line's first position: a diagonal decay a head and no
correction of what the state already holds (ops/deltarule.py has one, and a
triangular inverse for it; this has neither). ``x`` is ``[P]`` a head, ``B``
and ``C`` are ``[N]`` a *group* of heads — head ``h`` reads group ``h //
(H / G)`` — and ``A``, ``D`` are scalars a head. Operands are token-major
(``[B·S, heads, width]``); a line never reads its neighbours.

One form, the chunked closed form in ``jax.numpy``: positions in chunks of
``chunk``, and with ``a_t = Δ_t A``, ``cum_t = Σ_{s<=t} a_s`` inside a chunk
and the entering state ``S``:

    o_t = e^{cum_t} S C_t + Σ_{s<=t} e^{cum_t − cum_s} (C_t·B_s) Δ_s x_s + D x_t
    S  ← e^{cum_C} S + Σ_s e^{cum_C − cum_s} Δ_s x_s B_sᵀ

``C Bᵀ`` is computed once a group and the decay mask once a head; a scan
over chunks carries the state. Where a line is one chunk (the served shape:
32 positions under the published ``chunk_size`` 128) there is no entering
state and no scan — three products and a mask. Differentiable; the CPU, the
TPU, a mesh and the fit's step take the same form. The recurrence position by
position is the benchmark's reference (benchmark/reference/moe_ssm.py), which
the tests hold this to.

Precision: Δ, the decays and their cumulative sums, the mask and the state in
float32; ``C Bᵀ``, the masked scores' product with ``x`` and the state's
products take operands in ``dtype`` (bfloat16 as served) with float32
accumulation.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp


def state_space_scan(x: jax.Array, b: jax.Array, c: jax.Array, dt: jax.Array,
                     a: jax.Array, d: jax.Array, seq: int, chunk: int = 128,
                     dtype: Any = jnp.bfloat16) -> jax.Array:
    """``x`` [N, H, P], ``b``, ``c`` [N, G, S] (G dividing H), ``dt`` [N, H]
    (Δ > 0, after its softplus), ``a`` [H] (A < 0) and ``d`` [H] over ``N =
    lines·seq`` tokens in lines of ``seq`` → ``o`` [N, H, P] float32.
    ``chunk`` is cut to the line where the line is shorter and has to divide
    it."""
    n, h, p = x.shape
    g = b.shape[1]
    if h % g:
        raise ValueError(f"state-space scan: {g} groups do not divide {h} "
                         "heads")
    chunk = min(chunk, seq)
    if n % seq or seq % chunk:
        raise ValueError(f"state-space scan: chunks of {chunk} in lines of "
                         f"{seq} do not divide {n} tokens")
    lines, nc, rep = n // seq, seq // chunk, h // g

    def chunks(t: jax.Array) -> jax.Array:
        return t.reshape(lines, nc, chunk, *t.shape[1:])

    xc, bc, cc = (chunks(t.astype(dtype)) for t in (x, b, c))
    # by head, positions last: [L, nc, H, C]
    dth = jnp.moveaxis(chunks(dt.astype(jnp.float32)), 3, 2)
    cum = jnp.cumsum(dth * a.astype(jnp.float32)[:, None], axis=-1)
    with jax.named_scope("scores"):
        cb = jnp.einsum("lntgs,lnugs->lngtu", cc, bc,
                        preferred_element_type=jnp.float32)
        # e^{cum_t - cum_u} for u <= t (the minimum keeps what the mask
        # drops finite), times Δ_u
        lower = jnp.tril(jnp.ones((chunk, chunk), bool))
        decay = jnp.where(lower, jnp.exp(jnp.minimum(
            cum[..., :, None] - cum[..., None, :], 0.0)), 0.0)
        scores = (jnp.repeat(cb, rep, axis=2) * decay * dth[..., None, :])
    with jax.named_scope("values"):
        out = jnp.einsum("lnhtu,lnuhp->lnthp", scores.astype(dtype), xc,
                         preferred_element_type=jnp.float32)
    if nc > 1:
        with jax.named_scope("state"):
            out = out + _entering(xc, bc, cc, dth, cum, rep, dtype)
    out = out.reshape(n, h, p)
    return out + d.astype(jnp.float32)[:, None] * x.astype(jnp.float32)


def _entering(xc, bc, cc, dth, cum, rep: int, dtype) -> jax.Array:
    """What the state a chunk enters with adds to its positions, ``e^{cum_t}
    S C_t``: each chunk's own contribution to the state, a scan over chunks
    that carries it, and the read. ``xc`` [L, nc, C, H, P], ``bc``, ``cc``
    [L, nc, C, G, S], ``dth``, ``cum`` [L, nc, H, C] → [L, nc, C, H, P]."""
    lines, nc, chunk, h, p = xc.shape
    g, s = bc.shape[3], bc.shape[4]
    last = cum[..., -1]                                       # [L, nc, H]
    # e^{cum_C - cum_u} Δ_u x_u, then its product with B_u over the chunk
    carried = jnp.moveaxis(jnp.exp(last[..., None] - cum) * dth, 2, 3)
    written = (carried[..., None] * xc.astype(jnp.float32)).astype(dtype)
    adds = jnp.einsum(
        "lnugrp,lnugs->lngrps", written.reshape(lines, nc, chunk, g, rep, p),
        bc, preferred_element_type=jnp.float32).reshape(lines, nc, h, p, s)

    def step(state, xs):
        add, decay = xs
        return state * decay[..., None, None] + add, state

    _, entering = jax.lax.scan(
        step, jnp.zeros((lines, h, p, s), jnp.float32),
        (jnp.moveaxis(adds, 1, 0), jnp.moveaxis(jnp.exp(last), 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)               # [L, nc, H, P, S]
    read = jnp.einsum(
        "lngrps,lntgs->lntgrp",
        entering.astype(dtype).reshape(lines, nc, g, rep, p, s), cc,
        preferred_element_type=jnp.float32).reshape(lines, nc, chunk, h, p)
    return read * jnp.moveaxis(jnp.exp(cum), 2, 3)[..., None]
