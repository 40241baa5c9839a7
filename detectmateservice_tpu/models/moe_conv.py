"""Gated-short-convolution, grouped-query, sparse-expert causal LM scorer
(flax): a stack whose layers differ in *kind* by a published list
(``layer_types``) — most mix positions with a gated short convolution, every
few with grouped-query attention — over a dense gated feed-forward in the
leading layers and routed experts, with no shared expert, in the rest.
Named by mechanism, not by any one model.

Fifth scorer family (mlp → gru → logbert → moe_mla → moe_conv). Like
``gru`` and ``moe_mla`` it is a causal next-token language model over a
line's hashed tokens, scored by the (top-k) mean next-token NLL at every
position through the exact full-vocabulary head (models/base.py); its head
is TIED to the embedding.

Shape: one mapping, ``arch``, carries the model's published ``config.json``
keys under their published names (:class:`MoEConvArch`), plus what a
chip's share of an expert-parallel deployment needs — ``router_experts``
(the published expert count the router scores over; ``num_experts`` is then
how many this chip HOLDS) and ``expert_offset`` (the first one held).
``vocab_size`` and ``seq_len`` stay the scorer's own keys.

Layer equations (x the block's input, float32; RMSNorm ``norm_eps`` before
each sub-layer, the residual after):

* every layer: ``x ← x + Op(norm_operator(x))``, then ``x ← x +
  FF(norm_ffn(x))``; ``Op`` by ``layer_types[i]``.
* ``conv``: ``[B | C | x̃] = W_in·y`` (D → 3·D), ``u = B ⊙ x̃``, ``v[t] =
  Σ_j w[:, j] ⊙ u[t − (K−1) + j]`` (depthwise over positions, ``K =
  conv_L_cache`` taps, zeros left of the line's first position), ``Op =
  W_out·(C ⊙ v)``. No bias. Causal by construction; PAD lies right of a
  line's tokens and is never read by a valid position
  (ops/shortconv.py: token-major, never across a line's edge).
* ``full_attention``: ``q, k, v = W_qkv·y`` with ``num_attention_heads``
  query heads and ``num_key_value_heads`` key/value heads of ``hidden_size /
  num_attention_heads``; RMSNorm on q and on k per head; rotary positions
  over the whole head in the rotate-half form; each key/value head serves
  ``heads / kv_heads`` consecutive query heads; ``softmax(q·kᵀ/√d + causal
  and PAD mask)·v`` → ``W_o`` (ops/attention.py::grouped_query_attention).
* feed-forward, layers below ``num_dense_layers``: ``W_2(silu(W_1·y) ⊙
  W_3·y)`` at ``intermediate_size``; from there the expert layer
  (models/blocks.py, ops/experts.py): router over all ``router_experts`` in
  float32, sigmoid scores, ``num_experts_per_tok`` chosen by score +
  ``expert_bias``, weights = the chosen scores over their sum (+ 1e-6) ×
  ``routed_scaling_factor``; the held experts' part of ``Σ w_i·E_i(y)`` at
  ``moe_intermediate_size``. Nothing else is added: no shared expert.
* final RMSNorm (the published ``embedding_norm``), head = the embedding.

Departures from the published code, each shared with the reference
(benchmark/reference/moe_conv.py): the shift-right causal contract
(position t is predicted from the tokens before t; input 0 is CLS's own
embedding; rotary position t is the input's place), nothing cached and
nothing decoded (the convolution's ``conv_L_cache``-deep state and the
key/value cache are never built: every position is scored in one pass);
q, k and v come from one fused projection (the published three side by
side); ``expert_bias`` is zeros, gets no gradient and no balance update;
a share's router is not trained (models/blocks.py).

Precision: multiplies in the compute dtype (bfloat16) with float32
accumulation; residual stream, RMSNorm statistics (the per-head ones
too), rotary angles, the gates' and the convolution's products and sums,
router (logits to weights), softmax and the head's logsumexp in float32.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.attention import grouped_query_attention
from ..ops.shortconv import gated_short_conv
from .blocks import (ExpertLMScorer, ExpertSpec, arch_keys, causal_stack,
                     check_share, dense, expert_layer, gated_unit, rms_norm)

LAYER_KINDS = ("conv", "full_attention")
# published keys this family reads but implements one value of
_ONE_VALUE = {"conv_bias": False, "use_expert_bias": True}
# published keys that say nothing this family needs
_UNREAD = ("model_type", "max_position_embeddings", "vocab_size")


@dataclasses.dataclass(frozen=True)
class MoEConvArch:
    """The ``arch`` mapping, typed. Field names are the published
    ``config.json`` keys (``rope_theta`` is ``rope_parameters``'); the last
    two place this chip's share."""
    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    conv_L_cache: int
    intermediate_size: int
    moe_intermediate_size: int
    num_experts_per_tok: int
    num_dense_layers: int
    norm_topk_prob: bool
    routed_scaling_factor: float
    norm_eps: float
    rope_theta: float
    num_hidden_layers: int
    layer_types: Tuple[str, ...]
    num_experts: int               # experts HELD here
    router_experts: int            # experts the router scores over
    expert_offset: int = 0         # first held expert

    @classmethod
    def from_mapping(cls, arch: Mapping[str, Any]) -> "MoEConvArch":
        """Typed ``arch`` or a ValueError that names what is wrong: a key
        this family does not know, a missing one, or a published setting it
        cannot compute."""
        arch = dict(arch)
        arch.setdefault("router_experts", arch.get("num_experts"))
        rope = dict(arch.pop("rope_parameters", None) or {})
        if rope.pop("rope_type", "default") != "default":
            raise ValueError("arch.rope_parameters.rope_type: the moe_conv "
                             "scorer computes only rope_type = 'default'")
        arch["rope_theta"] = rope.pop("rope_theta", None)
        if rope:
            raise ValueError("arch.rope_parameters: unknown key(s) "
                             f"{sorted(rope)}")
        arch = arch_keys(cls, arch, _ONE_VALUE, _UNREAD, "moe_conv")
        arch["layer_types"] = tuple(arch["layer_types"])
        out = cls(**arch)
        if len(out.layer_types) != out.num_hidden_layers:
            raise ValueError(
                f"arch.layer_types names {len(out.layer_types)} layers, "
                f"num_hidden_layers is {out.num_hidden_layers}")
        strange = sorted(set(out.layer_types) - set(LAYER_KINDS))
        if strange:
            raise ValueError(f"arch.layer_types: unknown kind(s) {strange}; "
                             f"expected {list(LAYER_KINDS)}")
        if not 0 <= out.num_dense_layers <= out.num_hidden_layers:
            raise ValueError("arch.num_dense_layers must lie in "
                             "0..num_hidden_layers")
        if (out.hidden_size % out.num_attention_heads
                or out.num_attention_heads % out.num_key_value_heads
                or out.head_dim % 2):
            raise ValueError(
                "arch: num_attention_heads must divide hidden_size into even "
                "heads, and num_key_value_heads must divide it")
        check_share(out.expert_spec)
        if out.conv_L_cache < 1:
            raise ValueError("arch.conv_L_cache must be at least 1")
        return out

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def expert_spec(self) -> ExpertSpec:
        return ExpertSpec(
            width=self.moe_intermediate_size, held=self.num_experts,
            router_experts=self.router_experts, offset=self.expert_offset,
            top_k=self.num_experts_per_tok,
            norm_topk_prob=self.norm_topk_prob,
            scaling=self.routed_scaling_factor, norm_eps=1e-6)


@dataclasses.dataclass(frozen=True)
class MoEConvConfig:
    arch: MoEConvArch
    vocab_size: int = 32768
    seq_len: int = 32
    dtype: Any = jnp.bfloat16
    learning_rate: float = 1e-4
    initializer_range: float = 0.02
    score_topk: int = 0
    # "auto" | "einsum" (ops/attention.py::attention_route: fewer key/value
    # heads than query heads take the grouped einsum everywhere)
    attn_impl: str = "auto"
    # "auto" | "xla" | "fused" (ops/shortconv.py::conv_route)
    conv_impl: str = "auto"
    head_impl: str = "auto"
    platform: str = ""


class Block(nn.Module):
    config: MoEConvConfig
    # position in the stack: decides the operator (layer_types) and the
    # feed-forward (dense below num_dense_layers), names the device scopes
    layer: int = 0

    @nn.compact
    def __call__(self, x: jax.Array, key_mask: jax.Array, valid: jax.Array
                 ) -> Tuple[jax.Array, jax.Array]:
        """``x`` [B·S, D] float32, token-major; ``key_mask`` and ``valid``
        [B, S] → (x', [3] int32 routing counts)."""
        cfg, a = self.config, self.config.arch
        y = rms_norm(x, self.param("operator_norm", nn.initializers.ones,
                                   (a.hidden_size,)),
                     a.norm_eps).astype(cfg.dtype)
        if a.layer_types[self.layer] == "conv":
            with jax.named_scope(f"layer{self.layer}/conv"):
                x = x + self._conv(y, key_mask.shape[1])
        else:
            with jax.named_scope(f"layer{self.layer}/attn"):
                x = x + self._attention(y, key_mask)
        y = rms_norm(x, self.param("ffn_norm", nn.initializers.ones,
                                   (a.hidden_size,)), a.norm_eps)
        if self.layer < a.num_dense_layers:
            with jax.named_scope(f"layer{self.layer}/ffn"):
                out = gated_unit(y.astype(cfg.dtype), a.intermediate_size,
                                 a.hidden_size, cfg)
            return x + out.astype(jnp.float32), jnp.zeros((3,), jnp.int32)
        with jax.named_scope(f"layer{self.layer}/moe"):
            out, counts = expert_layer(self, y, valid, a.expert_spec, cfg)
        return x + out, counts

    def _conv(self, y: jax.Array, seq: int) -> jax.Array:
        cfg, a = self.config, self.config.arch
        with jax.named_scope("in_proj"):
            bcx = dense(3 * a.hidden_size, cfg, "in_proj")(y)
        with jax.named_scope("gate_conv"):
            mixed = gated_short_conv(
                bcx, self.param(
                    "conv_weight",
                    nn.initializers.normal(cfg.initializer_range),
                    (a.hidden_size, a.conv_L_cache)),
                seq, impl=cfg.conv_impl, platform=cfg.platform)
        with jax.named_scope("out_proj"):
            return dense(a.hidden_size, cfg, "out_proj")(mixed).astype(
                jnp.float32)

    def _attention(self, y: jax.Array, key_mask: jax.Array) -> jax.Array:
        cfg, a = self.config, self.config.arch
        h, g, d = a.num_attention_heads, a.num_key_value_heads, a.head_dim

        def head_norm(x: jax.Array, name: str) -> jax.Array:
            scale = self.param(name, nn.initializers.ones, (d,))
            return rms_norm(x.reshape(x.shape[0], -1, d), scale,
                            a.norm_eps).astype(cfg.dtype).reshape(x.shape)

        with jax.named_scope("qkv"):
            qkv = dense((h + 2 * g) * d, cfg, "qkv_proj")(y)
        with jax.named_scope("qk_norm"):
            q = head_norm(qkv[:, :h * d], "q_norm")
            k = head_norm(qkv[:, h * d:(h + g) * d], "k_norm")
        with jax.named_scope("core"):
            out = grouped_query_attention(
                q, k, qkv[:, (h + g) * d:], key_mask, h, g, a.rope_theta,
                impl=cfg.attn_impl, platform=cfg.platform or None)
        with jax.named_scope("out_proj"):
            return dense(a.hidden_size, cfg, "out_proj")(out).astype(
                jnp.float32)


class MoEConvLM(nn.Module):
    config: MoEConvConfig

    def setup(self) -> None:
        cfg, a = self.config, self.config.arch
        self.tok_embed = nn.Embed(
            cfg.vocab_size, a.hidden_size, dtype=cfg.dtype,
            embedding_init=nn.initializers.normal(cfg.initializer_range))
        self.layers = [Block(cfg, layer=i)
                       for i in range(a.num_hidden_layers)]
        self.final_norm = self.param("final_norm", nn.initializers.ones,
                                     (a.hidden_size,))

    def hidden_and_counts(self, tokens: jax.Array
                          ) -> Tuple[jax.Array, jax.Array]:
        """[B, S] int32 → ([B, S, D] float32 causal hidden states, [3]
        int32 routing counts of the call: models/blocks.py)."""
        return causal_stack(tokens, self.tok_embed, self.layers,
                            self.final_norm, self.config.arch.norm_eps)

    def hidden(self, tokens: jax.Array) -> jax.Array:
        return self.hidden_and_counts(tokens)[0]

    def __call__(self, tokens: jax.Array) -> jax.Array:
        """[B, S, V] float32 next-token logits (head tied to the embedding;
        compute-dtype multiplies, float32 accumulation): the fit's path."""
        cfg = self.config
        return jnp.einsum("bsd,vd->bsv", self.hidden(tokens).astype(cfg.dtype),
                          self.tok_embed.embedding.astype(cfg.dtype),
                          preferred_element_type=jnp.float32)


class MoEConvScorer(ExpertLMScorer):
    """Causal sparse-expert LM scorer over gated short convolutions and
    grouped-query attention; scoring call, routing counts and train step
    are :class:`~.blocks.ExpertLMScorer`'s, the head is the tied
    embedding (``SequenceScorerBase._head_matrix``)."""

    name = "moe_conv"

    def _build_model(self) -> MoEConvLM:
        return MoEConvLM(self.config)
