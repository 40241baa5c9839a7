"""Gated-delta-rule, gated-attention, sparse-expert causal LM scorer (flax):
a stack whose layers differ in *kind* by a published rule — every
``full_attention_interval``-th mixes positions with gated grouped-query
attention, the others with the gated delta rule, a linear attention that
carries a ``[Dk, Dv]`` state per head over a line's positions — over routed
experts and one gated shared expert in every layer. Named by mechanism, not
by any one model.

Sixth scorer family (mlp → gru → logbert → moe_mla → moe_conv → moe_delta).
Like ``moe_mla`` and ``moe_conv`` it is a causal next-token language model
over a line's hashed tokens, scored by the (top-k) mean next-token NLL at
every position through the exact full-vocabulary head (models/base.py);
its head is untied (``lm_head``).

Shape: one mapping, ``arch``, carries the model's published ``config.json``
keys under their published names (:class:`MoEDeltaArch`), plus what a
chip's share of an expert-parallel deployment needs — ``router_experts``
(the published expert count the router scores over; ``num_experts`` is then
how many this chip HOLDS) and ``expert_offset`` (the first one held).
``vocab_size`` and ``seq_len`` stay the scorer's own keys.

Layer equations (x the block's input, float32; ``norm(x; w) = x ·
rsqrt(mean(x²) + rms_norm_eps) · (1 + w)``, w zeros at initialisation, for
a block's two norms, the final norm and the per-head norms of q and k):

* every layer: ``h = x + mixer_i(norm(x))``, ``y = h + moe(norm(h))``;
  ``mixer_i`` is full attention where ``(i + 1) % full_attention_interval
  == 0``, else the gated delta rule.
* gated delta rule (Hk key heads and Hv value heads of Dk = Dv; value head
  h reads key head ``h // (Hv / Hk)``): ``q | k | v | z = W_in·y``, ``b | a
  = W_ba·y``, no biases; ``concat(q, k, v)`` through a depthwise causal
  convolution of ``linear_conv_kernel_dim`` taps over positions (zero
  history at a line's start) and SiLU (ops/shortconv.py::causal_conv_silu);
  ``β = sigmoid(b)``, ``g = −exp(A_log) · softplus(a + dt_bias)`` per value
  head; q and k L2-normalised per head, q scaled by ``Dk^-0.5``; per value
  head ``S' = exp(g_t) S_{t−1}``, ``u_t = β_t (v_t − S'ᵀ k_t)``, ``S_t = S'
  + k_t u_tᵀ``, ``o_t = S_tᵀ q_t`` from ``S_0 = 0``
  (ops/deltarule.py::gated_delta_rule: the chunked closed form, as one
  Pallas kernel on the served buckets of one TPU, as ``jax.numpy``
  elsewhere); ``o ← w ⊙ o · rsqrt(mean(o²) + eps) ⊙ silu(z)`` per head (the
  plain norm, w ones); then ``W_out``.
* gated full attention (H query and G key/value heads of ``head_dim``):
  ``q | gate | k | v = W_qkv·y`` (each query head with a gate as wide);
  zero-centred RMSNorm on q and on k per head; rotary positions,
  rotate-half, on the first ``partial_rotary_factor · head_dim`` lanes;
  causal softmax at ``head_dim^-0.5``, each key/value head serving H / G
  query heads (ops/attention.py::grouped_query_attention); ``W_o(attn ⊙
  sigmoid(gate))``.
* experts (models/blocks.py, ops/experts.py): router over all
  ``router_experts`` in float32, softmax scores, ``num_experts_per_tok``
  chosen, weights = the chosen scores over their sum, no scaling and no
  selection bias; the held experts' part of ``Σ w_i·E_i(y)`` at
  ``moe_intermediate_size`` plus one shared gated unit at
  ``shared_expert_intermediate_size`` times ``sigmoid(y · w_s)``.
* final norm, untied head.

Departures from the published code, each shared with the reference
(benchmark/reference/moe_delta.py): the shift-right causal contract
(position t is predicted from the tokens before t; input 0 is CLS's own
embedding; rotary position t is the input's place), nothing cached and
nothing decoded (neither the convolution's nor the delta rule's state
outlives a line; no key/value cache: every position is scored in one
pass), no multi-token-prediction module, the fused projections' columns
in this repo's order (q | k | v | z by kind, not by key-head group;
q | gate | k | v), a share's router is not trained (models/blocks.py).

Precision: multiplies in the compute dtype (bfloat16) with float32
accumulation; residual stream, RMSNorm statistics (the per-head ones
too), rotary angles, the convolution's products and SiLU, the delta rule's
gates, decays, L2 norms, triangular inverse and state, the output gates,
router (logits to weights), the shared expert's gate, softmax and the
head's logsumexp in float32.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.attention import grouped_query_attention, sigmoid_gate
from ..ops.deltarule import delta_gates, gated_delta_rule
from ..ops.shortconv import causal_conv_silu
from .blocks import (ExpertLMScorer, ExpertSpec, arch_keys, causal_stack,
                     check_share, dense, expert_layer, rms_norm)

LAYER_KINDS = ("linear_attention", "full_attention")
# published keys this family reads but implements one value of
_ONE_VALUE = {"hidden_act": "silu", "decoder_sparse_step": 1,
              "mlp_only_layers": [], "rope_scaling": None,
              "use_sliding_window": False, "norm_topk_prob": True,
              "tie_word_embeddings": False}
# published keys that say nothing this family needs: with no dense layer
# (mlp_only_layers [], decoder_sparse_step 1) intermediate_size is unread
_UNREAD = ("model_type", "max_position_embeddings", "vocab_size",
           "intermediate_size")
# positions a chunk of the delta rule's closed form: a served line (32) is
# one chunk. A static argument of the operation, no key of any configuration
DELTA_CHUNK = 32


@dataclasses.dataclass(frozen=True)
class MoEDeltaArch:
    """The ``arch`` mapping, typed. Field names are the published
    ``config.json`` keys; the last two place this chip's share."""
    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    partial_rotary_factor: float
    rope_theta: float
    full_attention_interval: int
    linear_conv_kernel_dim: int
    linear_key_head_dim: int
    linear_value_head_dim: int
    linear_num_key_heads: int
    linear_num_value_heads: int
    moe_intermediate_size: int
    shared_expert_intermediate_size: int
    num_experts_per_tok: int
    rms_norm_eps: float
    num_hidden_layers: int
    num_experts: int               # experts HELD here
    router_experts: int            # experts the router scores over
    expert_offset: int = 0         # first held expert

    @classmethod
    def from_mapping(cls, arch: Mapping[str, Any]) -> "MoEDeltaArch":
        """Typed ``arch`` or a ValueError that names what is wrong: a key
        this family does not know, a missing one, or a published setting it
        cannot compute."""
        arch = dict(arch)
        arch.setdefault("router_experts", arch.get("num_experts"))
        out = cls(**arch_keys(cls, arch, _ONE_VALUE, _UNREAD, "moe_delta"))
        if out.full_attention_interval < 1:
            raise ValueError("arch.full_attention_interval must be at "
                             "least 1")
        if (out.num_attention_heads % out.num_key_value_heads
                or out.rotary_dim % 2 or not 0 < out.rotary_dim <=
                out.head_dim):
            raise ValueError(
                "arch: num_key_value_heads must divide num_attention_heads, "
                "and partial_rotary_factor x head_dim must be an even "
                "number of lanes within the head")
        if (out.linear_num_value_heads % out.linear_num_key_heads
                or out.linear_key_head_dim != out.linear_value_head_dim):
            raise ValueError(
                "arch: linear_num_key_heads must divide "
                "linear_num_value_heads, and the moe_delta scorer computes "
                "only linear_key_head_dim = linear_value_head_dim")
        if out.shared_expert_intermediate_size % out.moe_intermediate_size:
            raise ValueError(
                "arch.shared_expert_intermediate_size must be a multiple "
                "of moe_intermediate_size")
        check_share(out.expert_spec)
        if out.linear_conv_kernel_dim < 1:
            raise ValueError("arch.linear_conv_kernel_dim must be at "
                             "least 1")
        return out

    @property
    def layer_types(self) -> Tuple[str, ...]:
        """The published rule: every ``full_attention_interval``-th layer
        is full attention."""
        return tuple(
            LAYER_KINDS[(i + 1) % self.full_attention_interval == 0]
            for i in range(self.num_hidden_layers))

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def expert_spec(self) -> ExpertSpec:
        return ExpertSpec(
            width=self.moe_intermediate_size, held=self.num_experts,
            router_experts=self.router_experts, offset=self.expert_offset,
            top_k=self.num_experts_per_tok, norm_topk_prob=True,
            scaling=1.0, scoring_func="softmax",
            shared=(self.shared_expert_intermediate_size
                    // self.moe_intermediate_size),
            norm_eps=0.0, shared_gate=True)


@dataclasses.dataclass(frozen=True)
class MoEDeltaConfig:
    arch: MoEDeltaArch
    vocab_size: int = 32768
    seq_len: int = 32
    dtype: Any = jnp.bfloat16
    learning_rate: float = 1e-4
    initializer_range: float = 0.02
    score_topk: int = 0
    # "auto" | "einsum" (ops/attention.py::attention_route: fewer key/value
    # heads than query heads take the grouped einsum everywhere)
    attn_impl: str = "auto"
    # "auto" | "fused" | "chunked" | "scan" (ops/deltarule.py::delta_route:
    # auto = the kernel on one TPU from 256 rows, else the chunked form)
    delta_impl: str = "auto"
    head_impl: str = "auto"
    platform: str = ""


def _a_log_init(key, shape, dtype=jnp.float32):
    """``log A``, ``A ~ U(0, 16)`` (kept off zero, whose log is not
    finite)."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1e-3, 16.0))


class Block(nn.Module):
    config: MoEDeltaConfig
    # position in the stack: decides the mixer, names the device scopes
    layer: int = 0

    @nn.compact
    def __call__(self, x: jax.Array, key_mask: jax.Array, valid: jax.Array
                 ) -> Tuple[jax.Array, jax.Array]:
        """``x`` [B·S, D] float32, token-major; ``key_mask`` and ``valid``
        [B, S] → (x', [3] int32 routing counts)."""
        cfg, a = self.config, self.config.arch
        y = rms_norm(x, 1.0 + self.param("input_norm", nn.initializers.zeros,
                                         (a.hidden_size,)),
                     a.rms_norm_eps).astype(cfg.dtype)
        if a.layer_types[self.layer] == "linear_attention":
            with jax.named_scope(f"layer{self.layer}/delta"):
                x = x + self._delta(y, key_mask.shape[1])
        else:
            with jax.named_scope(f"layer{self.layer}/attn"):
                x = x + self._attention(y, key_mask)
        y = rms_norm(x, 1.0 + self.param("post_norm", nn.initializers.zeros,
                                         (a.hidden_size,)), a.rms_norm_eps)
        with jax.named_scope(f"layer{self.layer}/moe"):
            out, counts = expert_layer(self, y, valid, a.expert_spec, cfg)
        return x + out, counts

    def _delta(self, y: jax.Array, seq: int) -> jax.Array:
        cfg, a = self.config, self.config.arch
        hk, hv = a.linear_num_key_heads, a.linear_num_value_heads
        dk, dv = a.linear_key_head_dim, a.linear_value_head_dim
        key_w, value_w = hk * dk, hv * dv
        mixed_w = 2 * key_w + value_w                          # q | k | v
        n = y.shape[0]
        with jax.named_scope("in_proj"):
            qkvz = dense(mixed_w + value_w, cfg, "in_proj")(y)
            ba = dense(2 * hv, cfg, "ba_proj")(y)
        with jax.named_scope("conv"):
            qkv = causal_conv_silu(
                qkvz[:, :mixed_w],
                self.param("conv_weight",
                           nn.initializers.normal(cfg.initializer_range),
                           (mixed_w, a.linear_conv_kernel_dim)), seq)
        with jax.named_scope("gates"):
            g, beta = delta_gates(
                ba[:, hv:], ba[:, :hv],
                self.param("A_log", _a_log_init, (hv,)),
                self.param("dt_bias", nn.initializers.ones, (hv,)))
        with jax.named_scope("core"):
            out = gated_delta_rule(
                qkv[:, :key_w].reshape(n, hk, dk),
                qkv[:, key_w:2 * key_w].reshape(n, hk, dk),
                qkv[:, 2 * key_w:].reshape(n, hv, dv), g, beta, seq,
                chunk=DELTA_CHUNK, impl=cfg.delta_impl, dtype=cfg.dtype,
                platform=cfg.platform, mixed=qkv)
        with jax.named_scope("norm_gate"):
            z = qkvz[:, mixed_w:].reshape(n, hv, dv).astype(jnp.float32)
            out = rms_norm(out, self.param("out_norm", nn.initializers.ones,
                                           (dv,)), a.rms_norm_eps)
            out = (out * nn.silu(z)).astype(cfg.dtype).reshape(n, value_w)
        with jax.named_scope("out_proj"):
            return dense(a.hidden_size, cfg, "out_proj")(out).astype(
                jnp.float32)

    def _attention(self, y: jax.Array, key_mask: jax.Array) -> jax.Array:
        cfg, a = self.config, self.config.arch
        h, g, d = a.num_attention_heads, a.num_key_value_heads, a.head_dim

        def head_norm(x: jax.Array, name: str) -> jax.Array:
            scale = 1.0 + self.param(name, nn.initializers.zeros, (d,))
            return rms_norm(x.reshape(x.shape[0], -1, d), scale,
                            a.rms_norm_eps).astype(cfg.dtype).reshape(x.shape)

        with jax.named_scope("qkv"):
            qkv = dense((2 * h + 2 * g) * d, cfg, "qkv_proj")(y)
        with jax.named_scope("qk_norm"):
            q = head_norm(qkv[:, :h * d], "q_norm")
            k = head_norm(qkv[:, 2 * h * d:(2 * h + g) * d], "k_norm")
        with jax.named_scope("core"):
            out = grouped_query_attention(
                q, k, qkv[:, (2 * h + g) * d:], key_mask, h, g, a.rope_theta,
                impl=cfg.attn_impl, platform=cfg.platform or None,
                rotary_dim=a.rotary_dim)
        with jax.named_scope("gate"):
            out = sigmoid_gate(out, qkv[:, h * d:2 * h * d])
        with jax.named_scope("out_proj"):
            return dense(a.hidden_size, cfg, "out_proj")(out).astype(
                jnp.float32)


class MoEDeltaLM(nn.Module):
    config: MoEDeltaConfig

    def setup(self) -> None:
        cfg, a = self.config, self.config.arch
        init = nn.initializers.normal(cfg.initializer_range)
        self.tok_embed = nn.Embed(cfg.vocab_size, a.hidden_size,
                                  dtype=cfg.dtype, embedding_init=init)
        self.layers = [Block(cfg, layer=i)
                       for i in range(a.num_hidden_layers)]
        self.final_norm = self.param("final_norm", nn.initializers.zeros,
                                     (a.hidden_size,))
        self.lm_head = self.param("lm_head", init,
                                  (cfg.vocab_size, a.hidden_size))

    def hidden_and_counts(self, tokens: jax.Array
                          ) -> Tuple[jax.Array, jax.Array]:
        """[B, S] int32 → ([B, S, D] float32 causal hidden states, [3]
        int32 routing counts of the call: models/blocks.py)."""
        return causal_stack(tokens, self.tok_embed, self.layers,
                            1.0 + self.final_norm,
                            self.config.arch.rms_norm_eps)

    def hidden(self, tokens: jax.Array) -> jax.Array:
        return self.hidden_and_counts(tokens)[0]

    def __call__(self, tokens: jax.Array) -> jax.Array:
        """[B, S, V] float32 next-token logits (untied head; compute-dtype
        multiplies, float32 accumulation): the fit's path."""
        cfg = self.config
        return jnp.einsum("bsd,vd->bsv", self.hidden(tokens).astype(cfg.dtype),
                          self.lm_head.astype(cfg.dtype),
                          preferred_element_type=jnp.float32)


class MoEDeltaScorer(ExpertLMScorer):
    """Causal sparse-expert LM scorer over the gated delta rule and gated
    grouped-query attention, with an untied head; scoring call, routing
    counts and train step are :class:`~.blocks.ExpertLMScorer`'s."""

    name = "moe_delta"

    def _build_model(self) -> MoEDeltaLM:
        return MoEDeltaLM(self.config)

    def _head_matrix(self, params) -> jax.Array:
        return params["params"]["lm_head"]
