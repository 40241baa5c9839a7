"""Sparse-expert, latent-attention causal LM scorer (flax): the block of
today's open mixture-of-experts language models — multi-head latent
attention (MLA), a leading dense gated feed-forward, then expert layers with
a sigmoid-scored router, shared experts and routed experts — as a log
scorer. Named by mechanism, not by any one model.

Fourth scorer family (mlp → gru → logbert → moe_mla). Like ``gru`` it is a
causal next-token language model over a line's hashed tokens and its score
is the (top-k) mean next-token NLL at every position through the exact
full-vocabulary head (models/base.py); unlike ``gru`` its blocks are a
transformer's and its head is UNTIED (``lm_head``, its own [V, D] matrix).

Shape: one mapping, ``arch``, carries the model's published ``config.json``
keys under their published names (:class:`MoEMLAArch`), plus what a chip's
share of an expert-parallel deployment needs — ``router_experts`` (the
published expert count the router scores over), ``n_routed_experts`` (how
many of them this chip holds) and ``expert_offset`` (the first one held).
``vocab_size`` and ``seq_len`` stay the scorer's own keys: the hashing
tokenizer shares them.

Layer equations (x the block's input; RMSNorm before each sub-layer, the
residual after, the residual stream in float32):

* attention (MLA, no query compression): ``q = x·W_q`` → per head
  ``q_nope ‖ q_rope``; ``x·W_kva`` → ``c = RMSNorm(first kv_lora_rank)``
  and ``k_rope`` (one for all heads); ``c·W_kvb`` → per head
  ``k_nope ‖ v``; rotary positions on ``q_rope`` and ``k_rope``
  (interleaved pairs); ``softmax(q·kᵀ/√(nope+rope) + causal and PAD
  mask)·v`` → ``W_o``. The activations run token-major (``[B·S, ·]``)
  and the two projections write every head's first part, then every
  head's second (:class:`HeadSplitDense`: a column view of the stored
  kernel), so that the core (ops/attention.py::latent_attention) reads
  the 128-wide and 64-wide parts as column blocks and never builds a
  192-wide head.
* dense layers (the first ``first_k_dense_replace``):
  ``W_down(silu(W_gate·x) ⊙ W_up·x)`` at ``intermediate_size``.
* expert layers: ops/experts.py — router over all ``router_experts`` in
  float32, ``num_experts_per_tok`` chosen by score + correction bias
  (among the experts of the ``topk_group`` best of ``n_group`` groups
  where the published router limits the choice so: 1 / 1 is no limit),
  weights normalised over the chosen and scaled; the held experts' part of
  ``Σ w_i·E_i(x)`` plus the shared experts (one gated unit at
  ``n_shared_experts × moe_intermediate_size``).
* final RMSNorm, ``lm_head``.

Causal contract (SequenceScorerBase's, ``gru``'s): position t's state is
computed from the tokens before t — the input at step t is token t-1, the
input at step 0 is ``CLS_ID``'s own embedding (every line starts with CLS,
so no parameter is added) — and the per-position NLLs line up 1:1 with the
input tokens. Nothing is cached and nothing is decoded: every position is
scored in one pass.

Precision: multiplies in the compute dtype (bfloat16) with float32
accumulation; residual stream, RMSNorm statistics, rotary angles, router
(logits to weights), softmax and the head's logsumexp in float32.
``e_score_correction_bias`` (``router_bias``) is zeros: no gradient reaches
it and the fit does not move it (the published training's balance update
lies outside any config key), so a checkpoint's bias is read as it is.
Where the chip holds a share of the experts the router's matrix gets no
gradient either: trained against the held experts' part of the result alone
it would drift towards them. A random router over log lines — whose tokens
are mostly the template's, the same at each position of every line — is
uneven: on the chip the busiest held expert took 4 times the mean and the
held share of the assignments read 8-9% where even routing gives 12.5
(PERF.md, section 5).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.attention import latent_attention
from .blocks import (ExpertLMScorer, ExpertSpec, arch_keys, causal_stack,
                     check_share, dense, expert_layer, gated_unit, rms_norm)

# published keys this family reads but implements one value of: a config
# that says otherwise is refused by name instead of being run as something
# else
_ONE_VALUE = {
    "q_lora_rank": None, "rope_scaling": None,
    "moe_layer_freq": 1, "attention_bias": False,
    "tie_word_embeddings": False, "hidden_act": "silu",
    "rope_interleave": True,
}
# published keys that say nothing this family needs (or repeat what other
# keys say); accepted so that a config.json can be passed as it is
_UNREAD = ("model_type", "head_dim", "qk_head_dim", "num_key_value_heads",
           "max_position_embeddings", "topk_method", "vocab_size")


@dataclasses.dataclass(frozen=True)
class MoEMLAArch:
    """The ``arch`` mapping, typed. Field names are the published
    ``config.json`` keys; the last two place this chip's share."""
    hidden_size: int
    num_attention_heads: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    kv_lora_rank: int
    intermediate_size: int
    moe_intermediate_size: int
    n_shared_experts: int
    num_experts_per_tok: int
    first_k_dense_replace: int
    norm_topk_prob: bool
    routed_scaling_factor: float
    scoring_func: str
    rope_theta: float
    rms_norm_eps: float
    num_hidden_layers: int
    n_routed_experts: int          # experts HELD here
    router_experts: int            # experts the router scores over
    expert_offset: int = 0         # first held expert
    # group-limited routing (ops/experts.py keep_groups); 1 / 1: none
    n_group: int = 1
    topk_group: int = 1

    @classmethod
    def from_mapping(cls, arch: Mapping[str, Any]) -> "MoEMLAArch":
        """Typed ``arch`` or a ValueError that names what is wrong: a key
        this family does not know, a missing one, or a published setting it
        cannot compute yet."""
        arch = dict(arch)
        arch.setdefault("router_experts", arch.get("n_routed_experts"))
        out = cls(**arch_keys(cls, arch, _ONE_VALUE, _UNREAD, "moe_mla"))
        if not 0 < out.first_k_dense_replace <= out.num_hidden_layers:
            raise ValueError("arch.first_k_dense_replace must lie in "
                             "1..num_hidden_layers")
        check_share(out.expert_spec)
        if out.scoring_func not in ("sigmoid", "softmax"):
            raise ValueError(f"arch.scoring_func {out.scoring_func!r}: "
                             "expected 'sigmoid' or 'softmax'")
        if out.qk_rope_head_dim % 2:
            raise ValueError("arch.qk_rope_head_dim must be even")
        return out

    @property
    def expert_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def expert_spec(self) -> ExpertSpec:
        return ExpertSpec(
            width=self.moe_intermediate_size, held=self.n_routed_experts,
            router_experts=self.router_experts, offset=self.expert_offset,
            top_k=self.num_experts_per_tok,
            norm_topk_prob=self.norm_topk_prob,
            scaling=self.routed_scaling_factor,
            scoring_func=self.scoring_func, shared=self.n_shared_experts,
            n_group=self.n_group, topk_group=self.topk_group)


@dataclasses.dataclass(frozen=True)
class MoEMLAConfig:
    arch: MoEMLAArch
    vocab_size: int = 32768
    seq_len: int = 32
    dtype: Any = jnp.bfloat16
    learning_rate: float = 1e-4
    initializer_range: float = 0.02
    score_topk: int = 0
    # "auto" (ops/attention.py::attention_route: the two-width kernel of
    # ops/shortattn.py on one TPU from 256 rows where the widths fill lane
    # groups, einsum everywhere else) | "einsum" | "short"
    attn_impl: str = "auto"
    head_impl: str = "auto"
    platform: str = ""


class HeadSplitDense(nn.Module):
    """``nn.Dense`` without bias over a kernel stored head by head —
    column ``h * (split + rest) + i`` is head ``h``'s ``i``-th — that writes
    every head's first ``split`` columns side by side, then every head's
    rest: ``[N, heads * split | heads * rest]``. The regrouping is a column
    view of the kernel taken once a call (25 MB for ``q_proj``, 17 MB for
    ``kv_up``, against 400 MB of activations a layer); the parameter's
    name, shape and stored layout are ``nn.Dense``'s."""
    features: int
    heads: int
    split: int
    config: MoEMLAConfig

    @nn.compact
    def __call__(self, y: jax.Array) -> jax.Array:
        cfg, d = self.config, y.shape[-1]
        by_head = self.param(
            "kernel", nn.initializers.normal(cfg.initializer_range),
            (d, self.features)).astype(cfg.dtype).reshape(d, self.heads, -1)
        return jnp.dot(y.astype(cfg.dtype), jnp.concatenate(
            [by_head[..., :self.split].reshape(d, -1),
             by_head[..., self.split:].reshape(d, -1)], axis=-1))


class Block(nn.Module):
    config: MoEMLAConfig
    # position in the stack: decides the layer's type (dense below
    # first_k_dense_replace, expert from there) and names the device scopes
    layer: int = 0

    @nn.compact
    def __call__(self, x: jax.Array, key_mask: jax.Array, valid: jax.Array
                 ) -> Tuple[jax.Array, jax.Array]:
        """``x`` [B·S, D] float32, token-major; ``key_mask`` and ``valid``
        [B, S] → (x', [3] int32 routing counts)."""
        cfg, a = self.config, self.config.arch
        with jax.named_scope(f"layer{self.layer}/attn"):
            x = x + self._attention(x, key_mask)
        y = rms_norm(x, self.param("ffn_norm", nn.initializers.ones,
                                   (a.hidden_size,)), a.rms_norm_eps)
        if self.layer < a.first_k_dense_replace:
            with jax.named_scope(f"layer{self.layer}/ffn"):
                out = gated_unit(y.astype(cfg.dtype), a.intermediate_size,
                                 a.hidden_size, cfg)
            return x + out.astype(jnp.float32), jnp.zeros((3,), jnp.int32)
        with jax.named_scope(f"layer{self.layer}/moe"):
            out, counts = expert_layer(self, y, valid, a.expert_spec, cfg)
        return x + out, counts

    def _attention(self, x: jax.Array, key_mask: jax.Array) -> jax.Array:
        cfg, a = self.config, self.config.arch
        h, nope, rope = (a.num_attention_heads, a.qk_nope_head_dim,
                         a.qk_rope_head_dim)
        y = rms_norm(x, self.param("attn_norm", nn.initializers.ones,
                                   (a.hidden_size,)),
                     a.rms_norm_eps).astype(cfg.dtype)
        with jax.named_scope("q_proj"):
            q = HeadSplitDense(h * (nope + rope), h, nope, cfg,
                               name="q_proj")(y)
        with jax.named_scope("kv_down"):
            kva = dense(a.kv_lora_rank + rope, cfg, "kv_down")(y)
            c = rms_norm(kva[..., :a.kv_lora_rank],
                         self.param("kv_norm", nn.initializers.ones,
                                    (a.kv_lora_rank,)),
                         a.rms_norm_eps).astype(cfg.dtype)
            k_rope = kva[..., a.kv_lora_rank:]                 # [B·S, R]
        with jax.named_scope("kv_up"):
            kv = HeadSplitDense(h * (nope + a.v_head_dim), h, nope, cfg,
                                name="kv_up")(c)
        with jax.named_scope("core"):
            out = latent_attention(q, kv, k_rope, key_mask, h, nope,
                                   a.rope_theta, impl=cfg.attn_impl,
                                   platform=cfg.platform or None,
                                   causal=True)
        with jax.named_scope("out_proj"):
            return dense(a.hidden_size, cfg, "out_proj")(out).astype(
                jnp.float32)


class MoEMLALM(nn.Module):
    config: MoEMLAConfig

    def setup(self) -> None:
        cfg, a = self.config, self.config.arch
        init = nn.initializers.normal(cfg.initializer_range)
        self.tok_embed = nn.Embed(cfg.vocab_size, a.hidden_size,
                                  dtype=cfg.dtype, embedding_init=init)
        self.layers = [Block(cfg, layer=i)
                       for i in range(a.num_hidden_layers)]
        self.final_norm = self.param("final_norm", nn.initializers.ones,
                                     (a.hidden_size,))
        self.lm_head = self.param("lm_head", init,
                                  (cfg.vocab_size, a.hidden_size))

    def hidden_and_counts(self, tokens: jax.Array
                          ) -> Tuple[jax.Array, jax.Array]:
        """[B, S] int32 → ([B, S, D] float32 causal hidden states, [3]
        int32 routing counts of the call: assignments of non-PAD positions
        over all experts, those that fell on held experts, and the busiest
        held expert's count summed over the expert layers)."""
        return causal_stack(tokens, self.tok_embed, self.layers,
                            self.final_norm, self.config.arch.rms_norm_eps)

    def hidden(self, tokens: jax.Array) -> jax.Array:
        return self.hidden_and_counts(tokens)[0]

    def __call__(self, tokens: jax.Array) -> jax.Array:
        """[B, S, V] float32 next-token logits (untied head; compute-dtype
        multiplies, float32 accumulation): the fit's path."""
        cfg = self.config
        return jnp.einsum("bsd,vd->bsv", self.hidden(tokens).astype(cfg.dtype),
                          self.lm_head.astype(cfg.dtype),
                          preferred_element_type=jnp.float32)


class MoEMLAScorer(ExpertLMScorer):
    """Causal sparse-expert LM scorer with latent attention and an untied
    head; scoring call, routing counts and train step are
    :class:`~.blocks.ExpertLMScorer`'s."""

    name = "moe_mla"

    def _build_model(self) -> MoEMLALM:
        return MoEMLALM(self.config)

    def _head_matrix(self, params) -> jax.Array:
        return params["params"]["lm_head"]
