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

One algorithm in two forms, and the closed form written twice; told apart by
:func:`delta_route` from what the call can observe:

* ``scan`` — the recurrence as written, a ``lax.scan`` over positions with
  the state in float32. What the tests hold the other forms to; its reverse
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

  As ``jax.numpy``: the inverse is forward substitution over the chunk's
  rows with the (line, chunk, head) index on the lanes — ``[C, C, B·H]``
  float32 — because a ``[.., 32, 32]`` float32 matrix per head pads its
  rows fourfold on the TPU and a loop over them would walk the padding 31
  times; the matmuls around it take their batch dimensions first, as the
  MXU wants them, and XLA copies between the two layouts. Differentiable,
  and what the CPU, a mesh, a line of several chunks and the fit's 32-row
  step take.
* ``fused`` — the same closed form where a line is one chunk (no entering
  state, no scan: the served shape, lines of 32 positions) as one Pallas
  kernel, :func:`gated_delta`: a block of lines' ``q``, ``k``, ``v`` read
  once in the dtype they arrive in and in place (no slice of ``q | k | v``,
  no float32 copy), the gates read once, ``o`` written once, and the norms,
  ``γ``, ``k kᵀ``, ``q kᵀ``, ``A``, the inverse and both products with
  nothing between them leaving VMEM — no padded ``[32, 32, ·]``
  intermediate and no copy between layouts reaches HBM. What ``auto`` takes
  on one TPU from ``FUSED_MIN_ROWS`` rows where the shapes tile
  (:func:`fits`). Its reverse pass is the chunked form's, recomputed from
  the saved operands.

Precision, the same in the chunked form and the kernel: gates, decays, L2
norms, the inverse, ``T``'s products and the state in float32
(``Precision.HIGHEST`` where a float32 matmul would otherwise run in one
bfloat16 pass; the kernel's inverse is float32 multiply-adds on the VPU and
its ``T diag(β) v`` splits ``T diag(β)`` into three bfloat16 parts against
``v`` as it arrived, which gives the float32 product whole); ``k kᵀ``,
``q kᵀ`` and the product with ``u`` take operands in ``dtype`` (bfloat16
as served) with float32 accumulation.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import current_placement

_HIGHEST = jax.lax.Precision.HIGHEST
IMPLS = ("auto", "chunked", "scan", "fused")
LANES = 128
# rows from which ``auto`` takes the kernel on one TPU: the smallest served
# bucket; the fit's 32-row step keeps the chunked form, whose backward it
# needs anyway
FUSED_MIN_ROWS = 256


def delta_route(impl: str, seq: int, chunk: int, platform: str = "",
                rows: int = 0, key_dim: int = 0, value_dim: int = 0,
                mesh_devices: int = 1, rep: int = 1) -> str:
    """``"chunked <C>"``, ``"scan"`` or ``"fused"`` for one traced call.
    ``impl`` other than ``"auto"`` forces. ``auto`` takes the kernel on ONE
    TPU (GSPMD does not partition a Pallas call) from ``FUSED_MIN_ROWS``
    rows where a line is one chunk and the shapes tile (:func:`fits`), and
    the chunked form everywhere else — the CPU, a mesh, a line of several
    chunks, the fit's 32-row step (the scan's reverse pass does not fit
    beside the fit's parameters) — with the chunk cut to the line where
    the line is shorter."""
    if impl not in IMPLS:
        raise ValueError(f"delta impl {impl!r}: expected one of {list(IMPLS)}")
    if impl in ("scan", "fused"):
        return impl
    chunk = min(chunk, seq)
    if seq % chunk:
        raise ValueError(f"delta rule: chunks of {chunk} do not divide a "
                         f"line's {seq} positions")
    if (impl == "auto" and platform == "tpu" and mesh_devices == 1
            and rows >= FUSED_MIN_ROWS and chunk == seq
            and fits(seq, key_dim, value_dim, rep)):
        return "fused"
    return f"chunked {chunk}"


def fits(seq: int, key_dim: int, value_dim: int, rep: int = 1) -> bool:
    """What the kernel tiles: head widths in whole lane groups, lines in
    whole 8-row sublane tiles that fill a 128-token tile, and ``rep`` value
    heads a key head that divide a lane group's 128 (value head, tile)
    units into eight tiles each or more."""
    return (key_dim > 0 and key_dim % LANES == 0 and value_dim > 0
            and value_dim % LANES == 0 and seq % 8 == 0 and LANES % seq == 0
            and 0 < rep <= LANES // 8 and LANES % rep == 0)


def l2_normalise(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + eps)


def gated_delta_rule(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                     beta: jax.Array, seq: int, chunk: int = 32,
                     impl: str = "auto", dtype: Any = jnp.bfloat16,
                     platform: str = "",
                     mixed: Optional[jax.Array] = None) -> jax.Array:
    """``q``, ``k`` [N, Hk, Dk], ``v`` [N, Hv, Dv], ``g`` (log decay, <= 0)
    and ``beta`` [N, Hv] over ``N = B·seq`` tokens in lines of ``seq`` →
    ``o`` [N, Hv, Dv] float32. ``q`` and ``k`` arrive unnormalised.
    ``chunk`` is a static argument of the operation, not a key of any
    configuration; ``platform`` is where the call is placed (the default
    backend when empty). ``mixed`` is the ``[N, 2·Hk·Dk + Hv·Dv]`` array
    ``q | k | v`` were sliced from, where the caller has it: the kernel
    then reads its column blocks in place, and no slice is copied for it."""
    placed = current_placement()
    platform = platform or jax.default_backend()
    rows = q.shape[0] // seq
    heads = Heads(q.shape[1], v.shape[1], q.shape[2], v.shape[2])
    route = delta_route(impl, seq, chunk, platform, rows, heads.dk, heads.dv,
                        placed.mesh_devices, heads.rep)
    if placed.delta_routes is not None:
        placed.delta_routes[rows] = route
    if route == "fused":
        if chunk < seq or not fits(seq, heads.dk, heads.dv, heads.rep):
            raise ValueError(
                f"delta impl 'fused': lines of {seq} positions in chunks of "
                f"{chunk} with {heads.rep} value heads of {heads.dv} a key "
                f"head of {heads.dk} do not tile (one chunk a line, 8 | seq "
                f"| {LANES}, head widths in multiples of {LANES}, value "
                f"heads a key head a power of two up to {LANES // 8})")
        # in place where v starts on a whole block of a key head's values
        in_place = (mixed is not None and (2 * heads.hk * heads.dk)
                    % (heads.rep * heads.dv) == 0)
        with jax.named_scope("delta_fused"):
            return _fused((mixed,) if in_place else (q, k, v), g, beta, heads,
                          seq, dtype, platform != "tpu")
    return _plain(q, k, v, g, beta, seq, chunk, dtype, route)


class Heads(NamedTuple):
    """Key heads, value heads and their widths."""
    hk: int
    hv: int
    dk: int
    dv: int

    @property
    def rep(self) -> int:
        return self.hv // self.hk

    def split(self, mixed: jax.Array) -> Tuple[jax.Array, ...]:
        """``q``, ``k``, ``v`` by head out of ``q | k | v`` [N, ·]."""
        n, key_w = mixed.shape[0], self.hk * self.dk
        return (mixed[:, :key_w].reshape(n, self.hk, self.dk),
                mixed[:, key_w:2 * key_w].reshape(n, self.hk, self.dk),
                mixed[:, 2 * key_w:].reshape(n, self.hv, self.dv))


def _plain(q, k, v, g, beta, seq: int, chunk: int, dtype, route: str
           ) -> jax.Array:
    """The two ``jax.numpy`` forms over unnormalised token-major operands."""
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


# -- the kernel ---------------------------------------------------------------

def _kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, kk_ref, qk_ref,
            gamma_ref, beta_ref, a_ref, w_ref, t_ref, p_ref, *, seq: int,
            rep: int, dv: int, dtype):
    """One grid step: a block of whole 128-token tiles of one key head and
    its ``rep`` value heads; a tile holds ``128 / seq`` lines. Four phases,
    everything between them in VMEM:

    1. a tile at a time on the MXU: ``k kᵀ`` and ``q kᵀ`` of the 128 tokens
       in one product, the lines' diagonal ``[seq, seq]`` blocks kept side
       by side along the lanes (``[seq, (line, s)]``; the products between
       different lines are the price of whole MXU tiles);
    2. row t of every tile's block gathered and transposed, so that a
       (value head, tile) unit owns a lane: ``A`` and the decay-masked
       ``q kᵀ`` as ``[t, (line, s), unit]``, the gates, the decays and the
       masks elementwise with nothing padded;
    3. ``(I + A)^-1`` by forward substitution over rows, in place (row t of
       the inverse is ``e_t − Σ_{j<t} A[t, j] · row_j``): float32
       multiply-adds over whole vregs of units, no MXU pass;
    4. back to a row a sublane, and a tile at a time on the MXU again:
       ``u = T diag(β) v`` and ``o = (q kᵀ ⊙ decay) u`` per value head, the
       blocks spread block-diagonally over the tile's 128 tokens."""
    tiles = q_ref.shape[0] // LANES
    dk = q_ref.shape[1]
    lines, units = LANES // seq, rep * tiles
    line_of_lane = jax.lax.broadcasted_iota(jnp.int32, (seq, LANES), 1) // seq
    one_line = (jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0) // seq
                == jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
                // seq)

    def fold(x):
        # [128, 128] → [seq, (line, s)]: each line's diagonal block
        out = x[:seq]
        for i in range(1, lines):
            out = jnp.where(line_of_lane == i, x[i * seq:(i + 1) * seq], out)
        return out

    def unfold(x):
        # and back: block-diagonal over the tile's lines
        return jnp.where(one_line, jnp.concatenate([x] * lines, axis=0),
                         jnp.zeros((), x.dtype))

    def over_tiles(body):
        # a few tiles an iteration: independent work for the scheduler to
        # lay over each other's MXU latency
        unroll = next(u for u in (4, 2, 1) if tiles % u == 0)

        def step(i, carry):
            for r in range(unroll):
                body(i * unroll + r)
            return carry

        jax.lax.fori_loop(0, tiles // unroll, step, 0)

    def tile_rows(c):
        return pl.ds(pl.multiple_of(c * LANES, LANES), LANES)

    def block_rows(index):
        return pl.ds(pl.multiple_of(index * seq, seq), seq)

    def products(c):
        k = l2_normalise(k_ref[tile_rows(c), :]).astype(dtype)
        q = (l2_normalise(q_ref[tile_rows(c), :]) * dk ** -0.5).astype(dtype)
        both = jax.lax.dot_general(
            jnp.concatenate([k, q], axis=0), k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        kk_ref[block_rows(c), :] = fold(both[:LANES])
        qk_ref[block_rows(c), :] = fold(both[LANES:])

    over_tiles(products)

    # the gates, unit j · tiles + c a row: γ the running sum of g inside
    # each line along the lanes, then a unit a lane
    gamma = jnp.concatenate([g_ref[j] for j in range(rep)], axis=0)
    place = jax.lax.broadcasted_iota(jnp.int32, gamma.shape, 1) % seq
    shift = 1
    while shift < seq:
        gamma = gamma + jnp.where(place >= shift,
                                  pltpu.roll(gamma, shift, 1), 0.0)
        shift *= 2
    gamma_ref[...] = gamma.T                             # [(line, t), unit]
    beta_ref[...] = jnp.concatenate([b_ref[j] for j in range(rep)], axis=0).T
    s_at = jax.lax.broadcasted_iota(jnp.int32, (seq, units), 0)

    def lanes_last(t, carry):
        def rows_t(ref):
            row = ref[pl.ds(t, tiles, stride=seq), :]    # row t, every tile
            return jnp.concatenate([row] * rep, axis=0).T

        kk, qk = rows_t(kk_ref), rows_t(qk_ref)          # [(line, s), unit]
        for i in range(lines):
            at, here = slice(i * seq, (i + 1) * seq), pl.ds(i * seq + t, 1)
            decay = jnp.exp(jnp.minimum(
                gamma_ref[here, :] - gamma_ref[at, :], 0.0))
            a_ref[t, at, :] = jnp.where(
                s_at < t, beta_ref[here, :] * decay * kk[at], 0.0)
            w_ref[t, at, :] = jnp.where(s_at <= t, decay * qk[at], 0.0)
        return carry

    jax.lax.fori_loop(0, seq, lanes_last, 0)

    def solve(t, carry):
        def term(j, acc):
            return tuple(
                acc[i] + a_ref[t, pl.ds(i * seq + j, 1), :]
                * a_ref[j, i * seq:(i + 1) * seq, :] for i in range(lines))

        acc = jax.lax.fori_loop(0, t, term, tuple(
            jnp.zeros((seq, units), jnp.float32) for _ in range(lines)))
        for i in range(lines):
            a_ref[t, i * seq:(i + 1) * seq, :] = jnp.where(
                s_at == t, 1.0, 0.0) - acc[i]
        return carry

    jax.lax.fori_loop(0, seq, solve, 0)

    def rows_last(t, carry):
        # T diag(β): its product with v then takes v as it arrived
        t_ref[pl.ds(t, units, stride=seq), :] = (a_ref[t] * beta_ref[...]).T
        p_ref[pl.ds(t, units, stride=seq), :] = w_ref[t].T
        return carry

    jax.lax.fori_loop(0, seq, rows_last, 0)

    def apply(c):
        for j in range(rep):
            at, cols = block_rows(j * tiles + c), slice(j * dv, (j + 1) * dv)
            t_beta, v = t_ref[at, :], v_ref[tile_rows(c), cols]
            if v.dtype == jnp.bfloat16:
                # three bfloat16 parts hold a float32, and v is bfloat16 as
                # it is: three MXU passes give the float32 product whole
                # (Precision.HIGHEST would split v too, in six)
                high = t_beta.astype(jnp.bfloat16)
                rest = t_beta - high.astype(jnp.float32)
                mid = rest.astype(jnp.bfloat16)
                low = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
                parts = jnp.dot(
                    jnp.concatenate([unfold(x) for x in (high, mid, low)],
                                    axis=0), v,
                    preferred_element_type=jnp.float32)
                u = parts[:LANES] + parts[LANES:2 * LANES] + parts[2 * LANES:]
            else:
                u = jnp.dot(unfold(t_beta), v.astype(jnp.float32),
                            precision=_HIGHEST,
                            preferred_element_type=jnp.float32)
            o_ref[tile_rows(c), cols] = jnp.dot(
                unfold(p_ref[at, :].astype(dtype)), u.astype(dtype),
                preferred_element_type=jnp.float32)

    over_tiles(apply)


def _block_tiles(tiles: int, rep: int, interpret: bool = False) -> int:
    """Tiles a grid step owns: a (value head, tile) unit a lane, so a whole
    lane group of them, in whole sublane tiles; the interpreter, which tiles
    nothing, is not made to walk the empty ones of a short call."""
    block = LANES // rep
    return min(block, tiles) if interpret else block


@functools.partial(jax.jit, static_argnames=(
    "heads", "seq", "dtype", "interpret"))
def gated_delta(operands: Tuple[jax.Array, ...], g: jax.Array,
                beta: jax.Array, heads: Heads, seq: int,
                dtype: Any = jnp.bfloat16, interpret: bool = False
                ) -> jax.Array:
    """The kernel: the closed form of one chunk a line. ``operands`` is
    ``(q, k, v)`` as :func:`gated_delta_rule` takes them, or ``(mixed,)``,
    the one array that holds ``q | k | v`` side by side, read in place.
    jitted, so that a stack's layers share one trace of its body (PERF.md
    section 6, PR 28). Lines that do not fill the last block of tiles are
    followed by empty ones."""
    hk, hv, dk, dv = heads
    rep = heads.rep
    n = g.shape[0]
    block = _block_tiles(-(-n // LANES), rep, interpret)
    pad = -n % (block * LANES)
    tiles = (n + pad) // LANES

    def whole_blocks(x: jax.Array) -> jax.Array:
        return jnp.pad(x.reshape(n, -1), ((0, pad), (0, 0))) if pad else (
            x.reshape(n, -1))

    if len(operands) == 1:
        q = k = v = whole_blocks(operands[0])
        k_at, v_at = hk, 2 * hk * dk // (rep * dv)
    else:
        q, k, v = (whole_blocks(x) for x in operands)
        k_at = v_at = 0

    def by_tile(x: jax.Array) -> jax.Array:
        # [N, Hv] → [Hk, rep, tiles, 128]: a tile's tokens along the lanes
        return whole_blocks(x.astype(jnp.float32)).T.reshape(
            hk, rep, tiles, LANES)

    gates = pl.BlockSpec((None, rep, block, LANES), lambda i, h: (h, 0, i, 0))
    units = block * rep
    out = pl.pallas_call(
        functools.partial(_kernel, seq=seq, rep=rep, dv=dv, dtype=dtype),
        grid=(tiles // block, hk),
        in_specs=[pl.BlockSpec((block * LANES, dk), lambda i, h: (i, h)),
                  pl.BlockSpec((block * LANES, dk),
                               lambda i, h: (i, k_at + h)),
                  pl.BlockSpec((block * LANES, rep * dv),
                               lambda i, h: (i, v_at + h)),
                  gates, gates],
        out_specs=pl.BlockSpec((block * LANES, rep * dv),
                               lambda i, h: (i, h)),
        out_shape=jax.ShapeDtypeStruct((n + pad, hv * dv), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((block * seq, LANES), jnp.float32),       # k kᵀ
            pltpu.VMEM((block * seq, LANES), jnp.float32),       # q kᵀ
            pltpu.VMEM((LANES, units), jnp.float32),             # γ
            pltpu.VMEM((LANES, units), jnp.float32),             # β
            pltpu.VMEM((seq, LANES, units), jnp.float32),        # A, then T
            pltpu.VMEM((seq, LANES, units), jnp.float32),        # q kᵀ ⊙ decay
            pltpu.VMEM((units * seq, LANES), jnp.float32),       # T diag(β)
            pltpu.VMEM((units * seq, LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=100 << 20),
        interpret=interpret, name="gated_delta",
    )(q, k, v, by_tile(g), by_tile(beta))
    return out[:n].reshape(n, hv, dv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _fused(operands, g, beta, heads, seq, dtype, interpret):
    return gated_delta(operands, g, beta, heads, seq, dtype, interpret)


def _fused_fwd(operands, g, beta, heads, seq, dtype, interpret):
    return (gated_delta(operands, g, beta, heads, seq, dtype, interpret),
            (operands, g, beta))


def _fused_bwd(heads, seq, dtype, interpret, saved, grad):
    # exact: the chunked form recomputed from the operands. The fit's 32-row
    # step takes that form anyway; a backward kernel would buy nothing
    def chunked(operands, g, beta):
        q, k, v = heads.split(*operands) if len(operands) == 1 else operands
        return _plain(q, k, v, g, beta, seq, seq, dtype, f"chunked {seq}")

    return jax.vjp(chunked, *saved)[1](grad)


_fused.defvjp(_fused_fwd, _fused_bwd)


# -- the delta rule whose decay is a vector a head ----------------------------
#
# Kimi Delta Attention (models/moe_kda.py): the state's rows decay each at
# a rate of their own,
#
#     S' = Diag(exp(g_t)) · S_{t-1}           (g_t in R^Dk, lower_bound < g < 0)
#     u_t = β_t · (v_t − S'ᵀ k_t);   S_t = S' + k_t u_tᵀ;   o_t = S_tᵀ q_t
#
# so the closed form has no scalar ``exp(γ_t − γ_s)`` to mask ``k kᵀ`` with:
# the decay sits inside the contraction, ``M[t, s] = Σ_c k_tc k_sc
# exp(γ_tc − γ_sc)``, with ``γ`` the gates' running sum over the chunk. As a
# matmul of ``k ⊙ e^γ`` with ``k ⊙ e^−γ`` it overflows inside one served
# line (32 positions at a gate of −5 are e^160; float32 ends at e^88.7).
# The gate's bound is what makes it computable: the chunk's rows are taken
# in sub-blocks of ``KDA_BLOCK`` positions, sub-block j about a reference
# point of its own, ``r_j = γ`` just before its first position,
#
#     M[t, s] = (k_t ⊙ e^{γ_t − r_j}) · (k_s ⊙ e^{r_j − γ_s})     t in j, s <= t
#
# where the left exponent lies in ``[−KDA_BLOCK · |lower_bound|, 0]`` and
# the right one in ``[0, KDA_BLOCK · |lower_bound|]`` for the positions of
# j itself — 40 at the published −5 and 8 positions, e^±40 = 2.4e17 and
# its inverse — and is <= 0 for those of earlier sub-blocks (γ falls).
# Eight positions and not sixteen: 16 keep the exponents under 80 and
# overflow nothing, but at the bound the left factor of a sub-block's last
# row is then e^−80 = 1.8e-35, and a lane of ``k`` under 6e-4 leaves
# float32's normal range with it (2e-4 of error on one position in five
# seeded cases); within ±40 both factors keep twenty decades of room. A
# reference point at a sub-block's middle would do the same for 16
# positions, but it lies in the future of the sub-block's first rows: their
# results would follow a later token's gate in the last digit, and a line's
# positions are causal to the bit here as in the scan.
# The rest is the scalar form's: ``A = β_t M[t, s]`` below the diagonal, ``T =
# (I + A)^-1`` (:func:`unit_lower_inverse`), ``u = T(β ⊙ v) − T(β ⊙ e^γ ⊙ k)
# S``, ``o = (e^γ ⊙ q) S + (M_qk, s <= t) u``, ``S ← e^{γ_C} ⊙ S + (e^{γ_C −
# γ} ⊙ k)ᵀ u`` with every exponent of the entering state's terms <= 0.
# Precision as the scalar form's: gates, decays, their running sums, the
# inverse, T's products and the state in float32, the two ``M`` products
# and the product with ``u`` on operands in ``dtype`` with float32
# accumulation. No kernel: PERF.md sets the scope ``layer<i>/kda/core``
# against ``benchmark/flops/moe_kda.py::kda_core_ops_and_bytes``.

KDA_IMPLS = ("auto", "chunked", "scan")
# positions a sub-block of the closed form
KDA_BLOCK = 8
# the largest exponent either factor of a sub-block's products may reach,
# a sub-block at the bound: e^40 = 2.4e17, twenty decades inside float32's
# range on either side
_KDA_MAX_EXPONENT = 40.0


def kda_route(impl: str, seq: int, chunk: int,
              lower_bound: float = -5.0) -> str:
    """``"kda chunked <C>/<B>"`` (chunks of C positions in sub-blocks of B)
    or ``"kda scan"`` for one traced call; ``auto`` is the chunked form
    everywhere (there is no kernel), the chunk cut to the line where the
    line is shorter. A bound under which a sub-block's exponent could pass
    ``_KDA_MAX_EXPONENT`` is refused by name."""
    if impl not in KDA_IMPLS:
        raise ValueError(f"kda impl {impl!r}: expected one of "
                         f"{list(KDA_IMPLS)}")
    if impl == "scan":
        return "kda scan"
    chunk, block = _kda_blocks(seq, chunk)
    if seq % chunk or chunk % block:
        raise ValueError(f"kda: chunks of {chunk} positions in sub-blocks "
                         f"of {block} do not divide a line's {seq}")
    if not -_KDA_MAX_EXPONENT <= lower_bound * block <= 0:
        raise ValueError(
            f"kda lower_bound {lower_bound}: a sub-block's {block} "
            f"positions at that gate pass e^{_KDA_MAX_EXPONENT:g}, which "
            "the closed form's float32 products do not hold")
    return f"kda chunked {chunk}/{block}"


def _kda_blocks(seq: int, chunk: int) -> Tuple[int, int]:
    """(positions a chunk, positions a sub-block): the chunk cut to the
    line, the sub-block to the chunk."""
    chunk = min(chunk, seq)
    return chunk, min(KDA_BLOCK, chunk)


def kda_gates(f: jax.Array, b: jax.Array, a_log: jax.Array,
              dt_bias: jax.Array, lower_bound: float
              ) -> Tuple[jax.Array, jax.Array]:
    """``(g [N, H, Dk], β [N, H])`` float32 from the decay projection ``f``
    [N, H, Dk] and ``b`` [N, H]: the lower-bound gate ``g = lower_bound ·
    sigmoid(exp(A_log_h) · (f + dt_bias))`` in ``(lower_bound, 0)``
    (``A_log`` [H], ``dt_bias`` [H, Dk]), ``β = sigmoid(b)``."""
    rate = jnp.exp(a_log.astype(jnp.float32))[:, None]
    g = lower_bound * jax.nn.sigmoid(
        rate * (f.astype(jnp.float32) + dt_bias.astype(jnp.float32)))
    return g, jax.nn.sigmoid(b.astype(jnp.float32))


def kda_delta_rule(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                   beta: jax.Array, seq: int, chunk: int = 32,
                   impl: str = "auto", dtype: Any = jnp.bfloat16,
                   lower_bound: float = -5.0) -> jax.Array:
    """``q``, ``k`` and the log decay ``g`` (``lower_bound <= g <= 0``) [N,
    H, Dk], ``v`` [N, H, Dv] and ``beta`` [N, H] over ``N = B·seq`` tokens
    in lines of ``seq`` → ``o`` [N, H, Dv] float32. ``q`` and ``k`` arrive
    unnormalised (:func:`l2_normalise`, ``q`` also times ``Dk^-0.5``); every
    head has its own keys. ``chunk`` and ``lower_bound`` are static: the
    bound is the published ``kda_lower_bound`` the gates were made with."""
    placed = current_placement()
    route = kda_route(impl, seq, chunk, lower_bound)
    n, h, dk = q.shape
    lines = n // seq
    if placed.delta_routes is not None:
        placed.delta_routes[lines] = route
    q = l2_normalise(q) * dk ** -0.5
    k = l2_normalise(k)

    def by_line(x: jax.Array) -> jax.Array:
        return x.astype(jnp.float32).reshape(lines, seq, *x.shape[1:])

    operands = tuple(by_line(x) for x in (q, k, v, g, beta))
    if route == "kda scan":
        with jax.named_scope("kda_scan"):
            out = _kda_scan(*operands)
    else:
        with jax.named_scope("kda_chunked"):
            out = _kda_chunked(*operands, *_kda_blocks(seq, chunk), dtype)
    return out.reshape(n, h, v.shape[2])


def _kda_scan(q, k, v, g, beta) -> jax.Array:
    """The recurrence, position by position: ``q``, ``k`` (normalised) and
    ``g`` [B, S, H, Dk], ``v`` [B, S, H, Dv], ``beta`` [B, S, H]."""
    b, _, h, dk = q.shape

    def step(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        state = state * jnp.exp(g_t)[..., None]
        seen = jnp.einsum("bhkv,bhk->bhv", state, k_t, precision=_HIGHEST)
        u_t = b_t[..., None] * (v_t - seen)
        state = state + k_t[..., :, None] * u_t[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t,
                                 precision=_HIGHEST)

    positions_first = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    _, out = jax.lax.scan(step, jnp.zeros((b, h, dk, v.shape[3]),
                                          jnp.float32), positions_first)
    return jnp.moveaxis(out, 0, 1)


def _kda_chunked(q, k, v, g, beta, chunk: int, block: int, dtype
                 ) -> jax.Array:
    """The closed form a chunk in sub-blocks of ``block`` positions, the
    state carried between chunks (the section's comment). Shapes as
    :func:`_kda_scan`."""
    b, s, h, dk = q.shape
    nc, c, nb = s // chunk, chunk, chunk // block
    exact = _HIGHEST if jnp.dtype(dtype) == jnp.float32 else None

    def chunks(x: jax.Array) -> jax.Array:
        return x.reshape(b, nc, c, *x.shape[2:])

    q, k, v, g, beta = (chunks(x) for x in (q, k, v, g, beta))
    gamma = jnp.cumsum(g, axis=2)                         # [B, nc, C, H, Dk]
    # r_j: γ just before sub-block j's first position (0 before the chunk)
    ref = jnp.pad(gamma, ((0, 0), (0, 0), (1, 0), (0, 0), (0, 0)))[
        :, :, :c:block, None]                          # [B, nc, nb, 1, H, Dk]

    def by_block(x: jax.Array) -> jax.Array:
        return x.reshape(b, nc, nb, block, h, dk)

    # a chunk's positions as rows of their sub-block, about its reference
    # point: exponents <= 0
    shrink = jnp.exp(by_block(gamma) - ref)

    def rows(x: jax.Array) -> jax.Array:
        return (by_block(x) * shrink).astype(dtype)

    # and as columns against sub-block j's rows: r_j − γ_s is <= 0 in the
    # sub-blocks before j and within a sub-block at the bound in j; the
    # sub-blocks behind j lie above the diagonal and read exponent 0 — set
    # before exp, so that nothing masked is infinite on the way back either
    seen = (jnp.arange(c) // block)[None, :] <= jnp.arange(nb)[:, None]
    cols = (k[:, :, None] * jnp.exp(jnp.where(
        seen[:, :, None, None], ref - gamma[:, :, None], 0.0))).astype(dtype)
    kk, qk = (jnp.einsum("bnjthd,bnjshd->jtsbnh", rows(x), cols,
                         precision=exact, preferred_element_type=jnp.float32
                         ).reshape(c, c, b, nc, h) for x in (k, q))
    lower = jnp.tril(jnp.ones((c, c), bool))[:, :, None, None, None]
    strict = jnp.tril(jnp.ones((c, c), bool), -1)[:, :, None, None, None]
    a = jnp.where(strict, jnp.moveaxis(beta, 2, 0)[:, None] * kk, 0.0)
    with jax.named_scope("solve"):
        t_inv = unit_lower_inverse(a.reshape(c, c, -1)).reshape(a.shape)
    t_inv = jnp.moveaxis(t_inv, (0, 1), (3, 4))            # [B, nc, H, C, C]
    attn = jnp.moveaxis(jnp.where(lower, qk, 0.0), (0, 1), (3, 4))
    u_v = jnp.einsum("bnhts,bnshd->bnthd", t_inv, beta[..., None] * v,
                     precision=_HIGHEST)

    def read(scores: jax.Array, u: jax.Array, index: str) -> jax.Array:
        return jnp.einsum(index, scores.astype(dtype), u.astype(dtype),
                          precision=exact, preferred_element_type=jnp.float32)

    if nc == 1:
        return read(attn, u_v, "bnhts,bnshd->bnthd").reshape(b, s, h, -1)

    # what the entering state adds: its products stay in float32, and every
    # exponent here is <= 0
    grow = jnp.exp(gamma)
    w = jnp.einsum("bnhts,bnshk->bnthk", t_inv, beta[..., None] * grow * k,
                   precision=_HIGHEST)
    to_end = jnp.exp(gamma[:, :, -1:] - gamma) * k
    end = jnp.exp(gamma[:, :, -1])                            # [B, nc, H, Dk]

    def step(state, xs):
        u_c, w_c, q_c, attn_c, k_c, end_c = xs
        u = u_c - jnp.einsum("bthk,bhkv->bthv", w_c, state,
                             precision=_HIGHEST)
        out = (jnp.einsum("bthk,bhkv->bthv", q_c, state, precision=_HIGHEST)
               + read(attn_c, u, "bhts,bshd->bthd"))
        state = (state * end_c[..., None]
                 + jnp.einsum("bthk,bthv->bhkv", k_c, u, precision=_HIGHEST))
        return state, out

    chunks_first = tuple(jnp.moveaxis(x, 1, 0)
                         for x in (u_v, w, q * grow, attn, to_end, end))
    _, out = jax.lax.scan(step, jnp.zeros((b, h, dk, v.shape[-1]),
                                          jnp.float32), chunks_first)
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, -1)
