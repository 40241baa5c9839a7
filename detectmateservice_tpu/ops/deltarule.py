"""The gated delta rule over a line's positions, token-major.

A linear-attention layer (models/moe_delta.py) keeps, per value head, a
``[Dk, Dv]`` state that every position decays, corrects and reads:

    S' = exp(g_t) · S_{t-1}                 (g_t <= 0: the gate's log decay)
    u_t = β_t · (v_t − S'ᵀ k_t)             (the delta rule: what k_t still
    S_t = S' + k_t u_tᵀ                      predicts wrongly is written)
    o_t = S_tᵀ q_t

with ``S_0 = 0`` at a line's first position — the first operation in
``ops/`` that carries state over positions. ``q`` and ``k`` are L2-normalised
per head here (``q`` also scaled by ``Dk^-0.5``); value head ``h`` reads key
head ``h // (Hv / Hk)``. Operands are token-major (``[B·S, heads, width]``,
as the stacks run since PR 28); a line never reads its neighbours.

Two forms, told apart by :func:`delta_route`:

* ``scan`` — the recurrence as written, a ``lax.scan`` over positions with
  the state in float32. What the tests hold the other form to; its reverse
  pass keeps a state per position (67 MB a step and layer at 32 rows of 32
  value heads), so nothing served or fitted takes it.
* ``chunked`` — positions in chunks of ``chunk``; inside a chunk the
  recurrence is solved in closed form, between chunks the state is carried
  by a scan over chunks. With ``γ_t = Σ_{s<=t} g_s`` inside the chunk and
  the entering state ``S``:

      A[t, s] = β_t · exp(γ_t − γ_s) · (k_t·k_s)          s < t, else 0
      T = (I + A)^-1                       (unit lower-triangular)
      u = T(β ⊙ v) − T(β ⊙ e^γ ⊙ k) S
      o = (e^γ ⊙ q) S + ((q kᵀ) ⊙ e^{γ_t − γ_s}, s <= t) u
      S ← e^{γ_C} S + (e^{γ_C − γ} ⊙ k)ᵀ u

  At the served shape (lines of 32 positions, one chunk) there is no
  entering state and no scan. The inverse is forward substitution over the
  chunk's rows with the (line, chunk, head) index on the lanes — ``[C, C,
  B·H]`` float32 — because a ``[.., 32, 32]`` float32 matrix per head pads
  its rows fourfold on the TPU and a loop over them would walk the padding
  31 times; the matmuls around it take their batch dimensions first, as
  the MXU wants them. Both forms are differentiable; the fit's 32-row step
  takes the chunked one.

Precision: gates, decays, L2 norms, the inverse, ``T``'s products and the
state in float32 (``Precision.HIGHEST`` where a float32 matmul would
otherwise run in one bfloat16 pass); ``k kᵀ``, ``q kᵀ`` and the product
with ``u`` take operands in ``dtype`` (bfloat16 as served) with float32
accumulation.
"""
from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp

from .attention import current_placement

_HIGHEST = jax.lax.Precision.HIGHEST
IMPLS = ("auto", "chunked", "scan")


def delta_route(impl: str, seq: int, chunk: int) -> str:
    """``"chunked <C>"`` or ``"scan"`` for one traced call. ``impl`` other
    than ``"auto"`` forces; ``auto`` is the chunked form everywhere (the
    scan's reverse pass does not fit beside the fit's parameters), with the
    chunk cut to the line where the line is shorter."""
    if impl not in IMPLS:
        raise ValueError(f"delta impl {impl!r}: expected one of {list(IMPLS)}")
    if impl == "scan":
        return "scan"
    chunk = min(chunk, seq)
    if seq % chunk:
        raise ValueError(f"delta rule: chunks of {chunk} do not divide a "
                         f"line's {seq} positions")
    return f"chunked {chunk}"


def l2_normalise(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + eps)


def gated_delta_rule(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                     beta: jax.Array, seq: int, chunk: int = 32,
                     impl: str = "auto", dtype: Any = jnp.bfloat16
                     ) -> jax.Array:
    """``q``, ``k`` [N, Hk, Dk], ``v`` [N, Hv, Dv], ``g`` (log decay, <= 0)
    and ``beta`` [N, Hv] over ``N = B·seq`` tokens in lines of ``seq`` →
    ``o`` [N, Hv, Dv] float32. ``q`` and ``k`` arrive unnormalised.
    ``chunk`` is a static argument of the operation, not a key of any
    configuration."""
    route = delta_route(impl, seq, chunk)
    records = current_placement().delta_routes
    if records is not None:
        records[q.shape[0] // seq] = route
    n, hk, dk = q.shape
    hv = v.shape[1]
    lines = n // seq
    q = l2_normalise(q) * dk ** -0.5
    k = l2_normalise(k)

    def by_line(x: jax.Array) -> jax.Array:
        return x.astype(jnp.float32).reshape(lines, seq, *x.shape[1:])

    operands = tuple(by_line(x) for x in (q, k, v, g, beta))
    if route == "scan":
        with jax.named_scope("delta_scan"):
            out = _scan(*operands)
    else:
        with jax.named_scope("delta_chunked"):
            out = _chunked(*operands, min(chunk, seq), dtype)
    return out.reshape(n, hv, v.shape[2])


def _scan(q, k, v, g, beta) -> jax.Array:
    """The recurrence, position by position: ``q``, ``k`` [B, S, Hk, Dk]
    (normalised), ``v`` [B, S, Hv, Dv], ``g``, ``beta`` [B, S, Hv]."""
    b, _, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    q, k = (jnp.repeat(x, hv // hk, axis=2) for x in (q, k))

    def step(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        state = state * jnp.exp(g_t)[..., None, None]
        seen = jnp.einsum("bhkv,bhk->bhv", state, k_t, precision=_HIGHEST)
        u_t = b_t[..., None] * (v_t - seen)
        state = state + k_t[..., :, None] * u_t[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t,
                                 precision=_HIGHEST)

    positions_first = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    _, out = jax.lax.scan(step, jnp.zeros((b, hv, dk, dv), jnp.float32),
                          positions_first)
    return jnp.moveaxis(out, 0, 1)


@jax.custom_vjp
def unit_lower_inverse(a: jax.Array) -> jax.Array:
    """``(I + A)^-1`` for ``a`` [C, C, L]: L strictly lower-triangular
    matrices with their index on the lanes (whatever ``a`` holds on or
    above the diagonal is taken as zero), by forward substitution
    (:func:`_forward_substitution`): fused multiply-adds over ``[., L]``
    rows, no padding walked. Its reverse pass is the inverse's own, ``Ā =
    −Tᵀ T̄ Tᵀ`` below the diagonal: two products, not the loop's transpose
    (which took the CPU's compiler two minutes at test sizes)."""
    return _forward_substitution(a)


def _rows_product(x: jax.Array, y: jax.Array) -> jax.Array:
    """``x @ y`` for matrices whose index is on the trailing axes (``[s, s,
    ...]``): s fused multiply-adds on the VPU, no transpose to a batch-first
    layout and back for matrices of 8 or 16 rows."""
    return sum(x[:, k, None] * y[k, None] for k in range(x.shape[1]))


# rows of the diagonal blocks that plain forward substitution inverts
_INVERSE_BLOCK = 8


# jitted, so that a stack's delta layers share one trace of the loop
@jax.jit
def _forward_substitution(a: jax.Array) -> jax.Array:
    """Blocked: the diagonal blocks of ``_INVERSE_BLOCK`` rows side by side
    through one forward substitution (row t of an inverse is ``e_t − Σ_{j<t}
    A[t, j] · row_j``), then pairs of inverted blocks merged, ``[[T1, 0],
    [−T2 A21 T1, T2]]``, until one is left — 76 fused multiply-adds at 32
    rows where the row-by-row walk has 496, the same arithmetic and a sixth
    of the program for XLA to compile."""
    c, lanes = a.shape[0], a.shape[2]
    blocks = c // _INVERSE_BLOCK
    # pairs merge, so a power of two of whole blocks, or one block of all
    b = (_INVERSE_BLOCK if c % _INVERSE_BLOCK == 0
         and blocks & (blocks - 1) == 0 else c)
    starts = range(0, c, b)
    diag = jnp.stack([a[i:i + b, i:i + b] for i in starts], axis=2)
    eye = jnp.eye(b, dtype=a.dtype)[:, :, None, None]
    rows = [jnp.broadcast_to(eye[0], diag.shape[1:])]
    for t in range(1, b):
        rows.append(eye[t] - sum(diag[t, j][None] * rows[j]
                                 for j in range(t)))
    inv = jnp.stack(rows)                                  # [b, b, c / b, L]
    size = b
    while size < c:
        first, second = inv[:, :, 0::2], inv[:, :, 1::2]
        below = jnp.stack([a[i + size:i + 2 * size, i:i + size]
                           for i in range(0, c, 2 * size)], axis=2)
        corner = -_rows_product(_rows_product(second, below), first)
        inv = jnp.concatenate([
            jnp.concatenate([first, jnp.zeros_like(first)], axis=1),
            jnp.concatenate([corner, second], axis=1)], axis=0)
        size *= 2
    return inv.reshape(c, c, lanes)


def _inverse_fwd(a):
    t_inv = _forward_substitution(a)
    return t_inv, t_inv


def _inverse_bwd(t_inv, grad):
    c = t_inv.shape[0]
    back = -jnp.einsum("jil,jkl,mkl->iml", t_inv, grad, t_inv,
                       precision=_HIGHEST)
    return (jnp.where(jnp.tril(jnp.ones((c, c), bool), -1)[:, :, None],
                      back, 0.0),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def _chunked(q, k, v, g, beta, chunk: int, dtype) -> jax.Array:
    """The closed form a chunk, the state carried between chunks (the
    module's docstring). Shapes as :func:`_scan`."""
    b, s, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    nc, c, rep = s // chunk, chunk, hv // hk

    def chunks(x: jax.Array) -> jax.Array:
        return x.reshape(b, nc, c, *x.shape[2:])

    q, k, v, g, beta = (chunks(x) for x in (q, k, v, g, beta))
    gamma = jnp.cumsum(g, axis=2)                             # [B, nc, C, Hv]
    lower = jnp.tril(jnp.ones((c, c), bool))
    # exp(γ_t − γ_s) for s <= t, index on the lanes: [C, C, B, nc, Hv]
    gl = jnp.moveaxis(gamma, 2, 0)
    decay = jnp.where(lower[:, :, None, None, None],
                      jnp.exp(jnp.minimum(gl[:, None] - gl[None, :], 0.0)),
                      0.0)
    ql, kl = q.astype(dtype), k.astype(dtype)
    kk = jnp.einsum("bnthd,bnshd->tsbnh", kl, kl,
                    preferred_element_type=jnp.float32)
    qk = jnp.einsum("bnthd,bnshd->tsbnh", ql, kl,
                    preferred_element_type=jnp.float32)
    # a key head's products serve its value heads
    kk, qk = (jnp.repeat(x, rep, axis=-1) for x in (kk, qk))
    strict = jnp.tril(jnp.ones((c, c), bool), -1)[:, :, None, None, None]
    a = jnp.where(strict, jnp.moveaxis(beta, 2, 0)[:, None] * decay * kk, 0.0)
    with jax.named_scope("solve"):
        t_inv = unit_lower_inverse(a.reshape(c, c, -1)).reshape(a.shape)
    t_inv = jnp.moveaxis(t_inv, (0, 1), (3, 4))               # [B, nc, Hv, C, C]
    attn = jnp.moveaxis(qk * decay, (0, 1), (3, 4))
    u_v = jnp.einsum("bnhts,bnshd->bnthd", t_inv, beta[..., None] * v,
                     precision=_HIGHEST)
    if nc == 1:
        out = jnp.einsum("bnhts,bnshd->bnthd", attn.astype(dtype),
                         u_v.astype(dtype),
                         preferred_element_type=jnp.float32)
        return out.reshape(b, s, hv, dv)

    # what the entering state adds: its products stay in float32
    k_v, q_v = (jnp.repeat(x, rep, axis=3) for x in (k, q))   # [B, nc, C, Hv, Dk]
    grow = jnp.exp(gamma)[..., None]
    w = jnp.einsum("bnhts,bnshk->bnthk", t_inv, beta[..., None] * grow * k_v,
                   precision=_HIGHEST)
    q_in = q_v * grow
    to_end = jnp.exp(gamma[:, :, -1:] - gamma)[..., None] * k_v
    end = jnp.exp(gamma[:, :, -1])                            # [B, nc, Hv]

    def step(state, xs):
        u_c, w_c, q_c, attn_c, k_c, end_c = xs
        u = u_c - jnp.einsum("bthk,bhkv->bthv", w_c, state,
                             precision=_HIGHEST)
        out = (jnp.einsum("bthk,bhkv->bthv", q_c, state, precision=_HIGHEST)
               + jnp.einsum("bhts,bshd->bthd", attn_c.astype(dtype),
                            u.astype(dtype),
                            preferred_element_type=jnp.float32))
        state = (state * end_c[..., None, None]
                 + jnp.einsum("bthk,bthv->bhkv", k_c, u, precision=_HIGHEST))
        return state, out

    chunks_first = tuple(jnp.moveaxis(x, 1, 0)
                         for x in (u_v, w, q_in, attn, to_end, end))
    _, out = jax.lax.scan(step, jnp.zeros((b, hv, dk, dv), jnp.float32),
                          chunks_first)
    return jnp.moveaxis(out, 0, 1).reshape(b, s, hv, dv)


def delta_gates(a: jax.Array, b: jax.Array, a_log: jax.Array,
                dt_bias: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """``(g, β)`` [N, Hv] float32 from the gate projections ``a``, ``b``
    [N, Hv]: ``g = −exp(A_log) · softplus(a + dt_bias)``, ``β =
    sigmoid(b)``."""
    g = -jnp.exp(a_log.astype(jnp.float32)) * jax.nn.softplus(
        a.astype(jnp.float32) + dt_bias.astype(jnp.float32))
    return g, jax.nn.sigmoid(b.astype(jnp.float32))
