"""Attention ops for the scorer models.

TPU-first: batched, bfloat16-friendly einsum attention the MXU tiles well,
with a numerically stable blockwise variant that is the building block for
ring attention (parallel/ring.py), and the fused pallas kernel (ops/flash.py)
for long sequences. ``attention()`` routes between them: below
``FLASH_MIN_SEQ`` the whole score matrix fits one MXU tile and XLA's fused
einsum has nothing for a kernel to save,
above it the pallas kernel avoids materializing the [S, T] logits in HBM.
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Optional

import jax
import jax.numpy as jnp

# from here up the kernel avoids the [S, S] fp32 logits (1 GB per batch-head
# at S=8192); below it the einsum path stays. The crossover is not measured
# on the attached chip (scripts/bench_flash.py, ROADMAP D10)
FLASH_MIN_SEQ = 2048

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

    ``impl``: "auto" (flash on TPU for long sequences, einsum otherwise),
    "einsum", "flash", "blockwise", or "ring" (sequence-parallel exact
    attention over the mesh provided via ``ring_context``). The mask here is
    the scorer's PAD-key form ([B, T]); einsum/blockwise broadcast it, ring
    uses it as per-shard key validity.

    ``platform`` is the platform of the device the computation is placed on
    (the scorers pass the one their executor resolved); None = the process
    default backend. The flash kernel compiles for ``tpu`` and runs in
    interpret mode on ``cpu`` — and only there.

    ``causal`` adds the lower-triangular mask (query s sees keys t <= s;
    S must equal T). Only the einsum route has it, as it alone takes a
    value width other than the q·k width: flash, blockwise and ring refuse
    either by name rather than compute something else."""
    t = k.shape[2]
    if platform is None:
        platform = jax.default_backend()
    if impl == "auto":
        impl = ("flash" if platform == "tpu" and t >= FLASH_MIN_SEQ
                else "einsum")
    if impl != "einsum" and (causal or v.shape[-1] != q.shape[-1]):
        raise ValueError(
            f"attention impl={impl!r} has no causal mask and one head width "
            "for q·k and v; only 'einsum' computes causal attention or a "
            f"value width ({v.shape[-1]}) other than q·k's ({q.shape[-1]})")
    with jax.named_scope(f"attn_{impl}"):
        return _attention(q, k, v, key_mask, impl, platform, causal)


def _attention(q, k, v, key_mask, impl: str, platform: str,
               causal: bool = False) -> jax.Array:
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
