"""Attention ops for the scorer models.

TPU-first: batched, bfloat16-friendly einsum attention the MXU tiles well,
with a numerically stable blockwise variant that is the building block for
ring attention (parallel/ring.py), the fused pallas kernel for long
sequences (ops/flash.py) and the one for whole short sequences
(ops/shortattn.py). ``attention()`` (head-major q, k, v),
``self_attention()`` (the fused projection, as the models write it) and
``latent_attention()`` (latent attention's two q·k widths and value width,
token-major, as its projections write them) and
``grouped_query_attention()`` (fewer key/value heads than query heads,
causal, rotary over the whole head or its first lanes; token-major) route
between them by :func:`attention_route`: from ``FLASH_MIN_SEQ`` up the flash kernel avoids
materializing the [S, T] logits in HBM; for S = T <= 128 on one TPU the
short kernels keep the ``[rows, heads, S, T]`` float32 logits in VMEM and
take q, k, v where the projections wrote them.

That second route replaces a belief this file used to state — that below
``FLASH_MIN_SEQ`` "the whole score matrix fits one MXU tile and XLA's fused
einsum has nothing for a kernel to save". The trace refuted it: at the
served shape (32768 rows, 4 heads, S 32, D 64) the einsum route's
``layer<i>/attn`` scopes took 198.5 ms of a 332.9 ms scoring call, 49.6 ms
a layer, of which the three arithmetic operations were 22 ms and the rest
(8, 128)-tile padding of the 32-wide float32 logits, twofold lane padding
of the 64-wide head-major q, k, v, and materialised transposes (ledger, PR
27); the chip needs 5.5 ms a layer. What the kernel takes: PERF.md section
6, PR 28.

Latent attention (models/moe_mla.py) had the same disease in another form
and kept einsum until PR 30, because its core is causal and its keys
(128 ‖ 64) and values (128) differ in width: 32 heads reshaped to
``[b, s, 32, 192]`` and transposed head-major, sliced at 128, the 64-wide
part turned and concatenated back, one ``k_rope`` for all heads broadcast
to 32 and concatenated onto ``k_nope`` — 41 ms a 1024-row call for rope's
slices and concatenations and 46 ms for the einsum core over six layers,
where the traffic needs 8.9 ms (my chip runs, PR 27; call 5).
:func:`latent_attention` takes the operands in their two widths and never
builds the 192-wide heads; its einsum form is what the CPU, a mesh and the
fit's 32-row step run, and what the kernel's backward differentiates.
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

# from here up the kernel avoids the [S, S] fp32 logits (1 GB per batch-head
# at S=8192); below it the einsum path stays. The crossover is not measured
# on the attached chip (scripts/bench_flash.py, ROADMAP D10)
FLASH_MIN_SEQ = 2048

# rows from which ``auto`` takes the short kernel on a TPU: the smallest
# bucket the served path warms. On the attached v5e the whole ``logbert``
# scoring call is 1.19x faster there (2.25 against 2.69 ms), 1.27x at 512
# and 1024 rows, 1.62x at 2048, 1.81x at 4096 and 1.79x at 32768 (186.6
# against 334.2 ms). Under it the kernel is 3-11% ahead too (0.95 against
# 0.98 ms at 32 rows, 1.58 against 1.76 at 128), a tenth of a millisecond
# no line waits for; the boundary fit's 32-row train step keeps the route
# it has always compiled (scripts/bench_flash.py --buckets; my chip runs,
# PR 28, call 3; PERF.md section 6)
SHORT_MIN_ROWS = 256

class Placement(NamedTuple):
    """What surrounds a scorer's tracing: how many devices the executor
    spreads the call over, and the scorer's records (rows -> implementation)
    the resolved routes are written to. Tracing-time only, like the ring
    context below."""
    mesh_devices: int = 1
    attn_routes: Optional[Dict[int, str]] = None
    conv_routes: Optional[Dict[int, str]] = None
    delta_routes: Optional[Dict[int, str]] = None


_PLACEMENT: contextvars.ContextVar = contextvars.ContextVar(
    "dm_attention_placement", default=Placement())


@contextlib.contextmanager
def placement(mesh_devices: int, routes: Optional[Dict[int, str]] = None,
              conv_routes: Optional[Dict[int, str]] = None,
              delta_routes: Optional[Dict[int, str]] = None):
    """Tell the attention calls (and the short convolutions,
    ops/shortconv.py, and the delta rule, ops/deltarule.py) traced under
    this scope where they run (models/base.py wraps every model
    application in it)."""
    token = _PLACEMENT.set(Placement(mesh_devices, routes, conv_routes,
                                     delta_routes))
    try:
        yield
    finally:
        _PLACEMENT.reset(token)


def current_placement() -> Placement:
    """The :class:`Placement` a call is traced under; one device and no
    record outside any."""
    return _PLACEMENT.get()


def attention_route(impl: str, platform: str, s: int, t: int, heads: int,
                    head_dim: int, value_dim: int, causal: bool, rows: int,
                    mesh_devices: int = 1, rope_dim: int = 0,
                    kv_heads: int = 0) -> str:
    """Which implementation computes one traced attention call.

    ``impl`` is the model's ``attn_impl``; any name but ``"auto"`` forces.
    ``"auto"`` decides from what the call can observe — the platform it is
    placed on, query and key lengths, heads, the q·k and value widths
    (``rope_dim`` of ``head_dim`` is the second, position-turned q·k
    operand of a :func:`latent_attention` call; 0 = one operand; ``kv_heads``
    under ``heads`` is a :func:`grouped_query_attention` call, each key /
    value head serving ``heads / kv_heads`` query heads; 0 = as many as
    ``heads``), whether it is causal, its rows, and how many devices the
    executor spread it over:

    * on a TPU from ``FLASH_MIN_SEQ`` keys up: ``"flash"``, as before;
    * on ONE TPU, whole short self-attention — S = T <= 128 in whole
      16-row tiles — from ``SHORT_MIN_ROWS`` rows: ``"short"``
      (ops/shortattn.py: logits stay in VMEM, no head-major copies), in
      the form the call's widths name: one q·k width that is the value
      width too, in whole-vreg lane groups, no causal mask (``logbert``);
      or two q·k widths and a value width of whole lane groups, the
      second q·k width dividing one, causal or not (latent attention).
      The third form — causal, one width, fewer key/value heads than
      query heads — has no kernel body yet and takes the grouped einsum,
      which reads each key/value head in place for its query heads;
    * everything else ``"einsum"``: the CPU (tier-1 tests, the host twin —
      the kernel would run in the Pallas interpreter), a mesh of more than
      one device (GSPMD does not partition a Pallas call), head-major
      calls with a causal mask or a value width of their own
      (:func:`attention`), widths off the lane groups, fewer rows (the
      fit's 32-row step).
    """
    if impl != "auto":
        return impl
    if platform != "tpu":
        return "einsum"
    if t >= FLASH_MIN_SEQ:
        return "flash"
    if kv_heads and kv_heads != heads:
        return "einsum"
    if mesh_devices == 1 and s == t and rows >= SHORT_MIN_ROWS:
        from .shortattn import fits, fits_latent

        if rope_dim:
            if fits_latent(s, heads, head_dim - rope_dim, rope_dim,
                           value_dim):
                return "short"
        elif (not causal and value_dim == head_dim
                and fits(s, heads, head_dim)):
            return "short"
    return "einsum"


def _resolve(impl: str, platform: Optional[str], q_shape, t: int,
             value_dim: int, causal: bool, record: bool = True,
             rope_dim: int = 0, kv_heads: int = 0) -> tuple:
    """(implementation, platform) for a call with head-major query shape
    ``q_shape``, recorded where a scorer listens."""
    rows, heads, s, head_dim = q_shape
    if platform is None:
        platform = jax.default_backend()
    placed = _PLACEMENT.get()
    impl = attention_route(impl, platform, s, t, heads, head_dim, value_dim,
                           causal, rows, placed.mesh_devices, rope_dim,
                           kv_heads)
    if record and placed.attn_routes is not None:
        placed.attn_routes[rows] = impl
    return impl, platform


def self_attention_route(rows: int, s: int, heads: int, head_dim: int,
                         impl: str = "auto",
                         platform: Optional[str] = None) -> str:
    """The route :func:`self_attention` takes for this shape under the
    current placement, without a call: a model asks before it decides how
    to lay out its activations (models/logbert.py)."""
    return _resolve(impl, platform, (rows, heads, s, head_dim), s, head_dim,
                    False, record=False)[0]


# (mesh, batch_axis, seq_axis) for impl="ring" — set by the execution layer
# (parallel.ShardedScorer) around tracing so the *model* stays mesh-agnostic:
# the same LogBERT module scores single-device, dp×tp, or sequence-parallel
# purely by who wraps the call
_RING_CTX: contextvars.ContextVar = contextvars.ContextVar(
    "dm_ring_attention_ctx", default=None)


@contextlib.contextmanager
def ring_context(mesh, batch_axis: Optional[str] = None, axis_name: str = "seq"):
    """Make ``impl="ring"`` resolvable inside model code traced under this
    scope. Tracing-time only — compiled executables keep the mesh baked in."""
    token = _RING_CTX.set((mesh, batch_axis, axis_name))
    try:
        yield
    finally:
        _RING_CTX.reset(token)


def attention(
    q: jax.Array,  # [B, H, S, D]
    k: jax.Array,  # [B, H, T, D]
    v: jax.Array,  # [B, H, T, Dv]; Dv may differ from D (einsum route)
    key_mask: Optional[jax.Array] = None,  # [B, T] bool; True = attend
    impl: str = "auto",
    platform: Optional[str] = None,
    causal: bool = False,
) -> jax.Array:
    """Route to the right attention implementation.

    ``impl``: "auto" (:func:`attention_route`: flash on a TPU for long
    sequences, the short kernel on one TPU for whole short ones, einsum
    otherwise), "einsum", "flash", "short", "blockwise", or "ring"
    (sequence-parallel exact attention over the mesh provided via
    ``ring_context``). A caller that holds the fused projection calls
    :func:`self_attention` instead: from this head-major layout the short
    kernel pays the transposes back. The mask here is
    the scorer's PAD-key form ([B, T]); einsum/blockwise broadcast it, ring
    uses it as per-shard key validity.

    ``platform`` is the platform of the device the computation is placed on
    (the scorers pass the one their executor resolved); None = the process
    default backend. The flash kernel compiles for ``tpu`` and runs in
    interpret mode on ``cpu`` — and only there.

    ``causal`` adds the lower-triangular mask (query s sees keys t <= s;
    S must equal T). From this head-major layout only the einsum route has
    it, as it alone takes a value width other than the q·k width: flash,
    short, blockwise and ring refuse either by name rather than compute
    something else (latent attention's causal kernel takes its operands
    token-major: :func:`latent_attention`)."""
    impl, platform = _resolve(impl, platform, q.shape, k.shape[2],
                              v.shape[-1], causal)
    if impl != "einsum" and (causal or v.shape[-1] != q.shape[-1]):
        raise ValueError(
            f"attention impl={impl!r} has no causal mask and one head width "
            "for q·k and v; only 'einsum' computes causal attention or a "
            f"value width ({v.shape[-1]}) other than q·k's ({q.shape[-1]})")
    with jax.named_scope(f"attn_{impl}"):
        return _attention(q, k, v, key_mask, impl, platform, causal)


def self_attention(
    qkv: jax.Array,  # [B, S, 3 * heads * head_dim]: q | k | v, heads side by side
    heads: int,
    key_mask: Optional[jax.Array] = None,  # [B, S] bool; True = attend
    impl: str = "auto",
    platform: Optional[str] = None,
) -> jax.Array:
    """Self-attention from the fused q·k·v projection's own layout →
    ``[B, S, heads * head_dim]``, ready for the output projection.

    Same routes and ``impl`` / ``platform`` as :func:`attention`. The short
    kernel reads ``qkv`` as it lies; every other route gets the q / k / v
    split and the ``[b, s, h, d] <-> [b, h, s, d]`` transposes it needs."""
    b, s, width = qkv.shape
    head_dim = width // 3 // heads
    impl, platform = _resolve(impl, platform, (b, heads, s, head_dim), s,
                              head_dim, False)
    with jax.named_scope(f"attn_{impl}"):
        if impl == "short":
            from .shortattn import short_attention

            return short_attention(qkv, key_mask, heads, None,
                                   platform == "cpu")
        q, k, v = split_heads(qkv, heads)
        return merge_heads(_attention(q, k, v, key_mask, impl, platform))


def rotary_tables(s: int, r: int, theta: float,
                  interleaved: bool = True) -> tuple:
    """(cos, sin) ``[S, R]`` float32 of rotary positions 0..S-1 over R
    lanes: interleaved, lanes ``2i`` and ``2i+1`` share ``pos ·
    theta^(-2i/R)``; in the rotate-half form lanes ``i`` and ``i + R/2``
    do."""
    inv = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    if interleaved:
        return (jnp.repeat(jnp.cos(angle), 2, axis=-1),
                jnp.repeat(jnp.sin(angle), 2, axis=-1))
    return (jnp.tile(jnp.cos(angle), 2), jnp.tile(jnp.sin(angle), 2))


def rotary(x: jax.Array, theta: float, interleaved: bool = True,
           heads_inside: bool = False,
           rotary_dim: Optional[int] = None) -> jax.Array:
    """Rotary positions over the last axis of ``x`` [..., S, R] (or, with
    ``heads_inside``, [..., S, H, R]: positions before the heads, as a
    token-major projection reshapes). Pairs
    interleaved: ``x[2i], x[2i+1]`` turn by ``pos · theta^(-2i/R)`` and stay
    where they are (the published code moves the pairs' halves apart; q and
    k share either layout, and only q·k is read). Rotate-half
    (``interleaved`` false): the pair is ``x[i], x[i + R/2]``. The pair swap
    is a matmul with a constant ±1 matrix — exact, and on the MXU — because
    a reshape to ``[..., R/2, 2]`` costs a relayout of the whole tensor on
    the TPU (7 ms a layer at 32768 tokens against 1). Angles and products
    in float32. ``rotary_dim`` under R turns the first ``rotary_dim`` lanes
    only (a partial rotary factor): the tables read cos 1 and sin 0 on the
    rest and the swap matrix is zero there, so no lane is sliced off and
    put back."""
    r = x.shape[-1]
    turned_r = r if rotary_dim is None else rotary_dim
    cos, sin = rotary_tables(x.shape[-3 if heads_inside else -2], turned_r,
                             theta, interleaved)
    if turned_r < r:
        cos = jnp.pad(cos, ((0, 0), (0, r - turned_r)), constant_values=1.0)
        sin = jnp.pad(sin, ((0, 0), (0, r - turned_r)))
    if heads_inside:
        cos, sin = cos[:, None, :], sin[:, None, :]
    first, second = ((np.arange(0, turned_r, 2), np.arange(1, turned_r, 2))
                     if interleaved else
                     (np.arange(turned_r // 2),
                      np.arange(turned_r // 2, turned_r)))
    swap = np.zeros((r, r), np.float32)
    swap[second, first] = -1.0      # out[first] = -x[second]
    swap[first, second] = 1.0       # out[second] = x[first]
    turned = jnp.dot(x, jnp.asarray(swap, x.dtype),
                     preferred_element_type=jnp.float32)
    return x.astype(jnp.float32) * cos + turned * sin


def latent_attention(
    q: jax.Array,        # [B * S, H * nope + H * rope]: every head's nope part, then every head's rope part
    kv: jax.Array,       # [B * S, H * nope + H * Dv]: every head's k_nope, then every head's values
    k_rope: jax.Array,   # [B * S, rope]: one for all heads, not yet turned
    key_mask: jax.Array,  # [B, S] bool; True = attend
    heads: int,
    nope: int,
    theta: float,
    impl: str = "auto",
    platform: Optional[str] = None,
    causal: bool = False,
) -> jax.Array:
    """Latent attention's core from its projections' own, token-major
    layout → ``[B * S, H * Dv]``, ready for the output projection.

    A head's logits are ``q_nope·k_nopeᵀ + rot(q_rope)·rot(k_rope)ᵀ``
    scaled by ``(nope + rope)^-0.5`` — the sum the ``nope + rope``-wide
    contraction computes, so the two parts are never concatenated — with
    rotary positions (:func:`rotary`, base ``theta``) on the rope parts
    only, turned in float32 and cast to the operands' dtype. ``impl`` /
    ``platform`` / ``causal`` as :func:`attention`; the routes here are
    ``short`` (ops/shortattn.py's two-width kernel: nothing head-major, no
    ``k_rope`` broadcast over heads and no float32 logits reach HBM) and
    ``einsum`` (:func:`latent_einsum`)."""
    b, s = key_mask.shape
    rope = k_rope.shape[-1]
    value_dim = kv.shape[-1] // heads - nope
    impl, platform = _resolve(impl, platform, (b, heads, s, nope + rope), s,
                              value_dim, causal, rope_dim=rope)
    with jax.named_scope(f"attn_{impl}"):
        if impl == "short":
            from .shortattn import short_latent_attention

            return short_latent_attention(q, kv, k_rope, key_mask, heads,
                                          nope, theta, causal, None,
                                          platform == "cpu")
        if impl != "einsum":
            raise ValueError(
                f"attention impl={impl!r} does not compute latent "
                "attention (two q·k widths, a value width of its own): "
                "'short' and 'einsum' do")
        return latent_einsum(q, kv, k_rope, key_mask, heads, nope, theta,
                             causal)


def grouped_query_attention(
    q: jax.Array,        # [B * S, H * D]: the query heads side by side
    k: jax.Array,        # [B * S, G * D]: the key heads, G dividing H
    v: jax.Array,        # [B * S, G * D]
    key_mask: jax.Array,  # [B, S] bool; True = attend
    heads: int,
    kv_heads: int,
    theta: float,
    impl: str = "auto",
    platform: Optional[str] = None,
    causal: bool = True,
    rotary_dim: Optional[int] = None,
) -> jax.Array:
    """Grouped-query self-attention's core from its projections' own,
    token-major layout → ``[B * S, H * D]``, ready for the output
    projection: key/value head ``g`` serves the query heads ``g·H/G ..
    (g+1)·H/G − 1``; rotary positions (base ``theta``) over the whole head
    — or its first ``rotary_dim`` lanes, a partial rotary factor; none where
    that is 0 (a stack whose other mixers carry the positions) — in the
    rotate-half form on q and k, turned in float32 and cast back;
    ``softmax(q·kᵀ/√D + causal and PAD mask)·v``.

    The one route is the grouped einsum (:func:`attention_route` answers
    ``einsum`` for fewer key/value heads than query heads): the query
    heads are viewed ``[B, S, G, H/G, D]`` and contracted against ``[B, S,
    G, D]`` keys and values, so no key/value head is repeated in HBM, and
    the rotation is :func:`rotary`'s ±1 matmul, so no ``[..., D/2, 2]``
    pair reshape is built. A forced kernel is refused by name."""
    b, s = key_mask.shape
    d = q.shape[-1] // heads
    impl, _ = _resolve(impl, platform, (b, heads, s, d), s, d, causal,
                       kv_heads=kv_heads)
    if impl != "einsum":
        raise ValueError(
            f"attention impl={impl!r} does not compute grouped-query "
            f"attention ({kv_heads} key/value heads for {heads} query "
            "heads): 'einsum' does")
    group = heads // kv_heads
    with jax.named_scope("attn_einsum"):
        q = q.reshape(b, s, heads, d)
        k = k.reshape(b, s, kv_heads, d)
        v = v.reshape(b, s, kv_heads, d)
        if rotary_dim != 0:
            with jax.named_scope("rope"):
                q = rotary(q, theta, False, True, rotary_dim).astype(q.dtype)
                k = rotary(k, theta, False, True, rotary_dim).astype(k.dtype)
        q = q.reshape(b, s, kv_heads, group, d)
        logits = jnp.einsum("bsgrd,btgd->bgrst", q, k,
                            preferred_element_type=jnp.float32) * d ** -0.5
        mask = key_mask[:, None, None, None, :]
        if causal:
            mask = mask & jnp.tril(jnp.ones((s, s), bool))
        logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bgrst,btgd->bsgrd", probs.astype(v.dtype), v)
        return out.reshape(b * s, heads * d)


def sigmoid_gate(out: jax.Array, gate: jax.Array) -> jax.Array:
    """An attention core's output gate: ``out ⊙ sigmoid(gate)``, the
    product in float32, in ``out``'s dtype. ``gate`` [N, H] beside ``out``
    [N, H · Dv] is head-wise: one value a head, over all its lanes."""
    if gate.shape != out.shape:
        heads = gate.shape[-1]
        return (out.astype(jnp.float32).reshape(-1, heads,
                                                out.shape[-1] // heads)
                * jax.nn.sigmoid(gate.astype(jnp.float32))[..., None]
                ).astype(out.dtype).reshape(out.shape)
    return (out.astype(jnp.float32)
            * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(out.dtype)


def latent_head_norms(
    q: jax.Array,        # [N, H * (nope + rope)]: head by head, a head's nope part then its rope part
    kv: jax.Array,       # [N, H * (nope + Dv)]: head by head, a head's k_nope then its values
    k_rope: jax.Array,   # [N, rope]: one for all heads
    q_scale: jax.Array,  # [nope + rope]: the query norm's weight
    k_scale: jax.Array,  # [nope + rope]: the key norm's
    eps: float,
    heads: int,
    nope: int,
) -> tuple:
    """RMSNorm over each head's whole ``nope + rope``-wide query and key,
    before any rotation → ``(q, k)`` [N, H, nope + rope] in ``q``'s dtype
    (statistics and scaling in float32). The key's norm reads the shared
    ``k_rope`` beside the head's own ``k_nope``, so behind it every head
    has a rope part of its own: what :func:`per_head_latent_attention`
    takes and :func:`latent_attention` (one ``k_rope`` for all heads) does
    not."""
    n, rope = q.shape[0], k_rope.shape[-1]

    def normed(x: jax.Array, scale: jax.Array) -> jax.Array:
        x = x.astype(jnp.float32)
        return (x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps)
                * scale.astype(jnp.float32)).astype(q.dtype)

    k = jnp.concatenate(
        [kv.reshape(n, heads, -1)[..., :nope],
         jnp.broadcast_to(k_rope[:, None, :], (n, heads, rope))], axis=-1)
    return normed(q.reshape(n, heads, nope + rope), q_scale), normed(k, k_scale)


def per_head_latent_attention(
    q: jax.Array,        # [B * S, H, nope + rope]
    k: jax.Array,        # [B * S, H, nope + rope]: every head's rope part its own
    v: jax.Array,        # [B * S, H, Dv]
    key_mask: jax.Array,  # [B, S] bool; True = attend
    nope: int,
    theta: float,
    impl: str = "auto",
    platform: Optional[str] = None,
    causal: bool = True,
) -> jax.Array:
    """Latent attention's core where the keys' rope parts differ by head
    (:func:`latent_head_norms`) → ``[B * S, H * Dv]``: rotary positions
    (interleaved, base ``theta``; angles and products in float32) on the
    lanes from ``nope`` up of q and k, ``softmax(q·kᵀ/√(nope + rope) +
    causal and PAD mask)·v``. The one route is the einsum over whole heads
    (:func:`dot_product_attention`): :func:`latent_attention`'s kernel
    reads one ``k_rope`` for all heads. A forced kernel is refused by
    name."""
    b, s = key_mask.shape
    heads = q.shape[1]
    impl, _ = _resolve(impl, platform, (b, heads, s, q.shape[-1]), s,
                       v.shape[-1], causal)
    if impl != "einsum":
        raise ValueError(
            f"attention impl={impl!r} does not compute latent attention "
            "behind per-head key norms (every head's rope part is its "
            "own): 'einsum' does")

    def head_major(x: jax.Array) -> jax.Array:
        return x.reshape(b, s, heads, -1).transpose(0, 2, 1, 3)

    def turned(x: jax.Array) -> jax.Array:
        x = x.reshape(b, s, heads, -1)
        with jax.named_scope("rope"):
            rope = rotary(x[..., nope:], theta, heads_inside=True)
        return head_major(jnp.concatenate(
            [x[..., :nope], rope.astype(x.dtype)], axis=-1))

    with jax.named_scope("attn_einsum"):
        mask = key_mask[:, None, None, :]
        if causal:
            mask = mask & jnp.tril(jnp.ones((s, s), bool))[None, None]
        out = dot_product_attention(turned(q), turned(k), head_major(v),
                                    mask)
        return merge_heads(out).reshape(b * s, -1)


def latent_einsum(q: jax.Array, kv: jax.Array, k_rope: jax.Array,
                  key_mask: jax.Array, heads: int, nope: int, theta: float,
                  causal: bool) -> jax.Array:
    """:func:`latent_attention` through ``dot_product_attention`` on
    ``nope + rope``-wide heads: head-major copies, the rope parts turned
    and concatenated back, ``k_rope`` broadcast over the heads — what the
    kernel does without, and what its backward differentiates."""
    b, s = key_mask.shape
    rope = k_rope.shape[-1]

    def head_major(x: jax.Array) -> jax.Array:
        return x.reshape(b, s, heads, -1).transpose(0, 2, 1, 3)

    q_nope, q_rope = (head_major(x) for x in
                      jnp.split(q, [heads * nope], axis=-1))
    k_nope, v = (head_major(x) for x in
                 jnp.split(kv, [heads * nope], axis=-1))
    with jax.named_scope("rope"):
        q_rope = rotary(q_rope, theta).astype(q.dtype)
        k_rope = rotary(k_rope.reshape(b, 1, s, rope), theta).astype(q.dtype)
    mask = key_mask[:, None, None, :]
    if causal:
        mask = mask & jnp.tril(jnp.ones((s, s), bool))[None, None]
    out = dot_product_attention(
        jnp.concatenate([q_nope, q_rope], axis=-1),
        jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (b, heads, s, rope))], axis=-1),
        v, mask)
    return merge_heads(out).reshape(b * s, -1)


def split_heads(qkv: jax.Array, heads: int) -> tuple:
    """The fused projection ``[B, S, 3 * H * D]`` → head-major q, k, v
    ``[B, H, S, D]``: the copies every route but the short kernel needs."""
    b, s, width = qkv.shape
    return tuple(part.reshape(b, s, heads, width // 3 // heads)
                 .transpose(0, 2, 1, 3)
                 for part in jnp.split(qkv, 3, axis=-1))


def merge_heads(out: jax.Array) -> jax.Array:
    """Head-major ``[B, H, S, D]`` → ``[B, S, H * D]`` for the projection."""
    b, h, s, d = out.shape
    return out.transpose(0, 2, 1, 3).reshape(b, s, h * d)


def _attention(q, k, v, key_mask, impl: str, platform: str,
               causal: bool = False) -> jax.Array:
    if impl == "short":
        if q.shape != k.shape:
            raise ValueError(
                "attention impl='short' is self-attention over whole short "
                f"sequences: q {q.shape} and k {k.shape} differ")
        from .shortattn import short_attention

        b, h, s, d = q.shape
        qkv = jnp.concatenate([merge_heads(part) for part in (q, k, v)],
                              axis=-1)
        out = short_attention(qkv, key_mask, h, None, platform == "cpu")
        return out.reshape(b, s, h, d).transpose(0, 2, 1, 3)
    if impl == "ring":
        ctx = _RING_CTX.get()
        if ctx is None:
            raise ValueError(
                "attention impl='ring' needs a sequence mesh: run the model "
                "through parallel.ShardedScorer with a 'seq' mesh axis (or "
                "wrap the call in ops.attention.ring_context)")
        mesh, batch_axis, axis_name = ctx
        from ..parallel.ring import ring_attention

        return ring_attention(q, k, v, mesh, kv_valid=key_mask,
                              axis_name=axis_name, batch_axis=batch_axis)
    if impl == "flash":
        from .flash import flash_attention

        # interpret mode keeps a forced flash config runnable (and its
        # numerics testable) when placed on the CPU — slow, but not a crash
        return flash_attention(q, k, v, key_mask,
                               interpret=platform == "cpu")
    mask = None if key_mask is None else key_mask[:, None, None, :]
    if impl == "blockwise":
        return blockwise_attention(q, k, v, mask=mask)
    if causal:
        s = q.shape[2]
        lower = jnp.tril(jnp.ones((s, s), bool))[None, None]
        mask = lower if mask is None else mask & lower
    return dot_product_attention(q, k, v, mask)


def dot_product_attention(
    q: jax.Array,  # [B, H, S, D]
    k: jax.Array,  # [B, H, T, D]
    v: jax.Array,  # [B, H, T, Dv]
    mask: Optional[jax.Array] = None,  # broadcastable to [B, H, S, T]; True = attend
) -> jax.Array:
    """Standard softmax attention; accumulates in fp32 regardless of input
    dtype. The scale is the q·k width's; the value width is its own."""
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bhsd,bhtd->bhst", q, k, preferred_element_type=jnp.float32)
    logits = logits * scale
    if mask is not None:
        logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhst,bhtd->bhsd", probs.astype(v.dtype), v)


def blockwise_attention_step(
    q: jax.Array,            # [B, H, S, D]
    k_block: jax.Array,      # [B, H, Tb, D]
    v_block: jax.Array,      # [B, H, Tb, D]
    acc: jax.Array,          # [B, H, S, D] fp32 running numerator
    row_max: jax.Array,      # [B, H, S] fp32 running max
    row_sum: jax.Array,      # [B, H, S] fp32 running denominator
    mask_block: Optional[jax.Array] = None,  # [B, H, S, Tb]
):
    """One streaming-softmax update against a block of keys/values.

    The online-softmax recurrence (flash-attention style): callers scan this
    over key/value blocks — locally for long sequences, or over ppermute'd
    shards for ring attention — and finish with ``acc / row_sum``.
    """
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bhsd,bhtd->bhst", q, k_block,
                        preferred_element_type=jnp.float32) * scale
    if mask_block is not None:
        logits = jnp.where(mask_block, logits, jnp.finfo(jnp.float32).min)
    block_max = jnp.max(logits, axis=-1)                      # [B,H,S]
    new_max = jnp.maximum(row_max, block_max)
    correction = jnp.exp(row_max - new_max)
    probs = jnp.exp(logits - new_max[..., None])              # [B,H,S,Tb]
    new_sum = row_sum * correction + probs.sum(axis=-1)
    new_acc = acc * correction[..., None] + jnp.einsum(
        "bhst,bhtd->bhsd", probs, v_block.astype(jnp.float32)
    )
    return new_acc, new_max, new_sum


def blockwise_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    block_size: int = 128,
    mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Full attention computed in key blocks via ``lax.scan`` — O(S·Tb) memory.

    Matches ``dot_product_attention`` numerically (fp32 accumulation); used for
    long-context scoring where the [S, T] logits matrix would blow VMEM/HBM.
    """
    b, h, s, d = q.shape
    t = k.shape[2]
    if t % block_size != 0:
        raise ValueError(f"key length {t} not divisible by block size {block_size}")
    n_blocks = t // block_size
    k_blocks = k.reshape(b, h, n_blocks, block_size, d).transpose(2, 0, 1, 3, 4)
    v_blocks = v.reshape(b, h, n_blocks, block_size, d).transpose(2, 0, 1, 3, 4)
    if mask is not None:
        mask = jnp.broadcast_to(mask, (b, h, s, t))
        mask_blocks = mask.reshape(b, h, s, n_blocks, block_size).transpose(3, 0, 1, 2, 4)
    else:
        mask_blocks = jnp.ones((n_blocks, b, h, s, block_size), dtype=bool)

    init = (
        jnp.zeros((b, h, s, d), jnp.float32),
        jnp.full((b, h, s), jnp.finfo(jnp.float32).min, jnp.float32),
        jnp.zeros((b, h, s), jnp.float32),
    )

    def step(carry, blocks):
        k_b, v_b, m_b = blocks
        acc, row_max, row_sum = carry
        return blockwise_attention_step(q, k_b, v_b, acc, row_max, row_sum, m_b), None

    (acc, _, row_sum), _ = jax.lax.scan(step, init, (k_blocks, v_blocks, mask_blocks))
    # defensive guard matching ring.py; row_sum stays ≥ 1 even for fully
    # masked rows (masked logits are finfo.min, not -inf, so probs = 1)
    return (acc / jnp.maximum(row_sum[..., None], 1e-30)).astype(q.dtype)
