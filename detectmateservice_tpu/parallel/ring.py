"""Ring attention: sequence-parallel exact attention over a device mesh.

First-class long-context support (task requirement; the reference has no
attention at all, SURVEY.md §5.7). Each device holds a ``[B, H, S/n, D]``
shard of the sequence; key/value shards rotate around the ring with
``lax.ppermute`` while every device folds each arriving block into a
streaming-softmax accumulator (ops/attention.blockwise_attention_step). After
``n`` hops every query shard has attended to the full sequence — exact
attention, O(S/n) memory per device, and the permute traffic rides ICI
neighbor links.

Run under ``shard_map`` over the ``seq`` axis of a mesh (tests use the
8-device virtual CPU mesh).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.attention import blockwise_attention_step


def _ring_attention_shard(q, k, v, kv_valid, axis_name: str,
                          vary_axes: tuple = (), n: int = 1):
    """Per-device body. q/k/v: [B, H, Sl, D] local shards; kv_valid: [B, Sl]
    bool validity (PAD masking) for the local key shard. ``n`` is the ring
    size (the mesh axis size — static, passed by ring_attention).

    The hop loop is ``lax.scan`` (not fori_loop) so the whole ring is
    reverse-mode differentiable — ppermute's transpose is the inverted
    permutation — which is what lets the flagship *training* step run under a
    sequence-parallel mesh, not just inference."""
    b, h, s_local, d = q.shape

    # mark the accumulators as device-varying over every manually-mapped
    # mesh axis (ring axis + optional batch axis) so the scan carry type
    # matches (shard_map's varying-manual-axes check)
    vary = lambda t: jax.lax.pcast(t, vary_axes or (axis_name,), to="varying")
    acc = vary(jnp.zeros((b, h, s_local, d), jnp.float32))
    row_max = vary(jnp.full((b, h, s_local), jnp.finfo(jnp.float32).min, jnp.float32))
    row_sum = vary(jnp.zeros((b, h, s_local), jnp.float32))
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(carry, _):
        acc, row_max, row_sum, k_blk, v_blk, valid_blk = carry
        mask = jnp.broadcast_to(valid_blk[:, None, None, :], (b, h, s_local, s_local))
        acc, row_max, row_sum = blockwise_attention_step(
            q, k_blk, v_blk, acc, row_max, row_sum, mask
        )
        # rotate kv one hop around the ring (neighbor ICI traffic)
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        valid_blk = jax.lax.ppermute(valid_blk, axis_name, perm)
        return (acc, row_max, row_sum, k_blk, v_blk, valid_blk), None

    (acc, row_max, row_sum, *_), _ = jax.lax.scan(
        body, (acc, row_max, row_sum, k, v, kv_valid), None, length=n
    )
    return (acc / jnp.maximum(row_sum[..., None], 1e-30)).astype(q.dtype)


def ring_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    mesh: Mesh,
    kv_valid: Optional[jax.Array] = None,
    axis_name: str = "seq",
    batch_axis: Optional[str] = None,
) -> jax.Array:
    """Exact attention with q/k/v sharded on the sequence dim of ``mesh``.

    q/k/v: [B, H, S, D] global; S must divide by mesh.shape[axis_name].
    kv_valid: optional [B, S] bool (False = PAD key, excluded everywhere).
    ``batch_axis`` names a mesh axis to shard the batch dim over as well
    (dp×sp: each data-replica row runs its own independent ring).
    """
    if kv_valid is None:
        kv_valid = jnp.ones((q.shape[0], q.shape[2]), dtype=bool)
    spec_qkv = P(batch_axis, None, axis_name, None)
    spec_valid = P(batch_axis, axis_name)
    vary_axes = (axis_name,) + ((batch_axis,) if batch_axis else ())
    fn = jax.shard_map(
        partial(_ring_attention_shard, axis_name=axis_name,
                vary_axes=vary_axes, n=int(mesh.shape[axis_name])),
        mesh=mesh,
        in_specs=(spec_qkv, spec_qkv, spec_qkv, spec_valid),
        out_specs=spec_qkv,
    )
    return fn(q, k, v, kv_valid)
