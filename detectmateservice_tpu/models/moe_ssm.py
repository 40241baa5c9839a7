"""State-space, grouped-query-attention, latent-sparse-expert causal LM scorer
(flax): a stack whose every layer is ONE sub-layer, its kind read off a
published pattern string — a Mamba-2 state-space mixer (``M``), causal
grouped-query attention without rotary positions (``*``) or an expert layer
(``E``) whose routed experts are non-gated ``relu²`` units living in a
latent narrower than the residual, beside one shared unit at the residual's
width. Named by mechanism, not by any one model.

Seventh scorer family (mlp → gru → logbert → moe_mla → moe_conv → moe_delta →
moe_ssm). Like the other expert families it is a causal next-token language
model over a line's hashed tokens, scored by the (top-k) mean next-token NLL
at every position through the exact full-vocabulary head (models/base.py);
its head is untied (``lm_head``).

Shape: one mapping, ``arch``, carries the model's published ``config.json``
keys under their published names (:class:`MoESSMArch`), plus what a chip's
share of a deployment needs. The keys that count heads, groups and experts
give what THIS chip holds; the share says of what:

* ``router_experts`` / ``expert_offset`` — the published expert count the
  router scores over and the first expert held (``n_routed_experts`` is
  then how many are held), as in the other expert families;
* ``tensor_parallel`` / ``tensor_rank`` — how many chips share each mixer,
  and which of them this is: ``mamba_num_heads``, ``n_groups``,
  ``num_attention_heads`` and ``num_key_value_heads`` are this chip's
  part, the published counts ``tensor_parallel`` times as many (but the
  key/value heads where there are fewer of them than chips: each chip then
  holds one whole). A share counts heads and never cuts a width: the
  shared unit is held whole at ``moe_shared_expert_intermediate_size`` on
  every chip, like the router and the latent's two projections.
  :meth:`MoESSMArch.share_of` derives the mapping from a published
  ``config.json`` and refuses a share that does not divide.

A tensor share needs no other code: the chip computes its heads' addend of
``W_out·o`` and of ``W_o·attn``, its held experts' addend of the latent sum
through the whole ``W_lat_out``, and the shared unit as every chip of the
group computes it (counted once where the addends meet); those partial
results go on to the next layer. Nothing stands in for the absent chips or
their exchange.

Layer equations (x the block's input, float32; ``norm(x; w) = w ⊙ x ·
rsqrt(mean(x²) + layer_norm_epsilon)``, w ones at initialisation, for every
block's norm and the final norm): ``x ← x + mixer_i(norm_i(x))``, ``mixer_i``
by ``hybrid_override_pattern[i]``:

* ``M``, state space (H = ``mamba_num_heads`` heads of P =
  ``mamba_head_dim``, G = ``n_groups`` groups of state N =
  ``ssm_state_size``; head h reads group ``h // (H / G)``): ``z | xBC | dt =
  W_in·y`` (H·P | H·P + 2·G·N | H columns, no bias); ``xBC ←
  silu(conv_K(xBC) + b_conv)``, depthwise causal over positions, zero
  history at a line's start (ops/shortconv.py::causal_conv_silu); ``x | B |
  C = xBC``; ``Δ_t = softplus(dt_t + dt_bias)``, ``A = −exp(A_log)`` per
  head; per head ``S_t = exp(Δ_t A) S_{t−1} + Δ_t x_t B_tᵀ`` from ``S_0 =
  0``, ``o_t = S_t C_t + D x_t`` (ops/ssd.py: the chunked closed form, one
  chunk a served line); ``o ← w ⊙ rms(o ⊙ silu(z))`` over each group's
  ``H·P / G`` channels (the gate before the norm); then ``W_out``.
* ``*``, attention: H query and G key/value heads of ``head_dim``, ``q | k |
  v = W_qkv·y``, no bias, no rotary positions (the state-space layers carry
  them), causal softmax at ``head_dim^-0.5``, each key/value head serving
  H / G query heads (ops/attention.py::grouped_query_attention); ``W_o``.
* ``E``, experts (models/blocks.py, ops/experts.py): ``s = sigmoid(W_r·y)``
  in float32 over all ``router_experts``; ``num_experts_per_tok`` chosen by
  ``s + bias`` (zeros, selection only; ``n_group 1``: no grouping); ``w =
  s_chosen / (Σ s_chosen + 1e-20) · routed_scaling_factor``; ``l =
  W_lat_in·y`` (``moe_latent_size``); the held experts' part of ``r = Σ w_i ·
  W_down,i · relu(W_up,i·l)²`` at ``moe_intermediate_size``; ``out =
  W_lat_out·r + W_sdown·relu(W_sup·y)²``. No bias anywhere.
* final norm, untied head.

Departures from the published code, each shared with the reference
(benchmark/reference/moe_ssm.py): the shift-right causal contract (position
t is predicted from the tokens before t; input 0 is CLS's own embedding),
nothing cached and nothing decoded (neither the convolution's nor the
state-space layer's state outlives a line; no key/value cache), no
multi-token-prediction module, the float32 residual stream (the published
``residual_in_fp32`` is false), attention's three projections fused (q | k
| v), a share's router is not trained (models/blocks.py).

Precision: multiplies in the compute dtype (bfloat16) with float32
accumulation; residual stream, RMSNorm statistics (the gated group norm's
too), the convolution's products, bias and SiLU, Δ, the decays, their
cumulative sums and the state, the output gate, router (logits to weights),
the experts' weighted sum, softmax and the head's logsumexp in float32.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Mapping, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.attention import grouped_query_attention
from ..ops.shortconv import causal_conv_silu
from ..ops.ssd import state_space_scan
from .blocks import (ExpertLMScorer, ExpertSpec, arch_keys, causal_stack,
                     check_share, dense, expert_layer, rms_norm)

# the pattern's letters
LAYER_KINDS = {"M": "ssm", "*": "attn", "E": "moe"}
# published keys this family reads but implements one value of
_ONE_VALUE = {"attention_bias": False, "mamba_proj_bias": False,
              "mlp_bias": False, "use_bias": False, "use_conv_bias": True,
              "mamba_hidden_act": "silu", "mlp_hidden_act": "relu2",
              "n_group": 1, "topk_group": 1, "n_shared_experts": 1,
              "norm_topk_prob": True, "tie_word_embeddings": False,
              "sliding_window": None}
# published keys that say nothing this family needs: no layer of the pattern
# is a dense feed-forward (intermediate_size), attention applies no rotary
# positions, the multi-token-prediction module is no part of a scoring pass,
# the residual stream is float32 here whatever residual_in_fp32 says, expand
# restates heads x head_dim, and the rest name kernels, caches and
# initialisers of the published code
_UNREAD = ("model_type", "max_position_embeddings", "vocab_size",
           "intermediate_size", "expand", "rope_theta",
           "partial_rotary_factor", "num_logits_to_keep",
           "num_nextn_predict_layers", "mtp_hybrid_override_pattern",
           "moe_shared_expert_overlap", "rescale_prenorm_residual",
           "residual_in_fp32", "use_mamba_kernels", "time_step_limit")
# the published counts a tensor share divides evenly
_SHARED_COUNTS = ("mamba_num_heads", "n_groups", "num_attention_heads")


@dataclasses.dataclass(frozen=True)
class MoESSMArch:
    """The ``arch`` mapping, typed. Field names are the published
    ``config.json`` keys; the last four place this chip's share."""
    hidden_size: int
    num_hidden_layers: int
    hybrid_override_pattern: str
    mamba_num_heads: int           # state-space heads HELD here
    mamba_head_dim: int
    n_groups: int                  # B/C groups held here
    ssm_state_size: int
    conv_kernel: int
    chunk_size: int
    num_attention_heads: int       # query heads held here
    num_key_value_heads: int       # key/value heads held here
    head_dim: int
    n_routed_experts: int          # experts HELD here
    num_experts_per_tok: int
    moe_intermediate_size: int
    moe_latent_size: int
    moe_shared_expert_intermediate_size: int   # the shared unit, whole
    routed_scaling_factor: float
    layer_norm_epsilon: float
    router_experts: int            # experts the router scores over
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    expert_offset: int = 0         # first held expert
    tensor_parallel: int = 1       # chips that share a mixer
    tensor_rank: int = 0           # which of them this is

    @classmethod
    def from_mapping(cls, arch: Mapping[str, Any]) -> "MoESSMArch":
        """Typed ``arch`` or a ValueError that names what is wrong: a key
        this family does not know, a missing one, or a published setting it
        cannot compute."""
        arch = dict(arch)
        arch.setdefault("router_experts", arch.get("n_routed_experts"))
        eps = arch.pop("norm_eps", None)
        out = cls(**arch_keys(cls, arch, _ONE_VALUE, _UNREAD, "moe_ssm"))
        if eps is not None and eps != out.layer_norm_epsilon:
            raise ValueError("arch.norm_eps: the moe_ssm scorer computes "
                             "one epsilon, layer_norm_epsilon")
        pattern = out.hybrid_override_pattern
        if (len(pattern) != out.num_hidden_layers
                or set(pattern) - set(LAYER_KINDS)):
            raise ValueError(
                "arch.hybrid_override_pattern must hold num_hidden_layers "
                f"letters of {sorted(LAYER_KINDS)} (M state space, * "
                "attention, E experts; a dense feed-forward layer '-' is "
                f"not computed): {pattern!r}")
        if (out.mamba_num_heads % out.n_groups or out.n_groups < 1
                or out.conv_kernel < 1 or out.chunk_size < 1):
            raise ValueError(
                "arch: n_groups must divide mamba_num_heads, and "
                "conv_kernel and chunk_size be at least 1")
        if (out.num_key_value_heads < 1
                or out.num_attention_heads % out.num_key_value_heads):
            raise ValueError("arch: num_key_value_heads must divide "
                             "num_attention_heads")
        if not 0 <= out.tensor_rank < out.tensor_parallel:
            raise ValueError(
                f"arch.tensor_rank {out.tensor_rank} is none of "
                f"tensor_parallel {out.tensor_parallel} chips")
        check_share(out.expert_spec)
        return out

    @classmethod
    def share_of(cls, published: Mapping[str, Any], *, tensor_parallel: int,
                 tensor_rank: int = 0, experts_held: int = 0,
                 expert_offset: int = 0, num_hidden_layers: int = 0
                 ) -> dict:
        """The ``arch`` mapping of one chip's share of a published
        ``config.json``: ``tensor_parallel`` chips share each mixer
        (state-space heads with their B/C groups, query heads: each has to
        divide; the shared unit stays whole), ``experts_held``
        routed experts from ``expert_offset`` lie here (all of them where
        0), and the first ``num_hidden_layers`` layers of the pattern (all
        where 0). Fewer key/value heads than chips: each chip holds one
        whole, chip r head ``r // (tensor_parallel / heads)``."""
        arch, tp = dict(published), tensor_parallel
        for key in _SHARED_COUNTS:
            if arch[key] % tp:
                raise ValueError(f"arch.{key} {arch[key]} does not divide "
                                 f"over tensor_parallel {tp} chips")
            arch[key] //= tp
        kv = arch["num_key_value_heads"]
        if kv % tp and tp % kv:
            raise ValueError(f"arch.num_key_value_heads {kv} neither "
                             f"divides over nor into tensor_parallel {tp}")
        arch["num_key_value_heads"] = max(kv // tp, 1)
        layers = num_hidden_layers or arch["num_hidden_layers"]
        arch.update(
            tensor_parallel=tp, tensor_rank=tensor_rank,
            router_experts=arch["n_routed_experts"],
            n_routed_experts=experts_held or arch["n_routed_experts"],
            expert_offset=expert_offset, num_hidden_layers=layers,
            hybrid_override_pattern=arch["hybrid_override_pattern"][:layers])
        return arch

    @property
    def layer_types(self) -> Tuple[str, ...]:
        return tuple(LAYER_KINDS[letter]
                     for letter in self.hybrid_override_pattern)

    @property
    def ssm_inner(self) -> int:
        """The state-space mixer's channels here: heads x head width."""
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def expert_spec(self) -> ExpertSpec:
        return ExpertSpec(
            width=self.moe_intermediate_size, held=self.n_routed_experts,
            router_experts=self.router_experts, offset=self.expert_offset,
            top_k=self.num_experts_per_tok, norm_topk_prob=True,
            scaling=float(self.routed_scaling_factor),
            scoring_func="sigmoid", shared=1, norm_eps=1e-20, gated=False,
            latent=self.moe_latent_size,
            shared_width=self.moe_shared_expert_intermediate_size)


@dataclasses.dataclass(frozen=True)
class MoESSMConfig:
    arch: MoESSMArch
    vocab_size: int = 32768
    seq_len: int = 32
    dtype: Any = jnp.bfloat16
    learning_rate: float = 1e-4
    initializer_range: float = 0.02
    score_topk: int = 0
    # "auto" | "einsum" (ops/attention.py::attention_route: fewer key/value
    # heads than query heads take the grouped einsum everywhere)
    attn_impl: str = "auto"
    head_impl: str = "auto"
    platform: str = ""


def _a_log_init(key, shape, dtype=jnp.float32):
    """``log A``, ``A = 1 .. heads`` as published (a share holds the first
    of them)."""
    del key
    return jnp.log(jnp.arange(1, shape[0] + 1, dtype=dtype))


def _dt_bias_init(arch: MoESSMArch):
    """The published initialiser: Δ log-uniform over ``[time_step_min,
    time_step_max]``, kept over ``time_step_floor``, through the inverse of
    softplus."""
    lo, hi = math.log(arch.time_step_min), math.log(arch.time_step_max)

    def init(key, shape, dtype=jnp.float32):
        dt = jnp.maximum(jnp.exp(jax.random.uniform(key, shape, dtype, lo,
                                                    hi)),
                         arch.time_step_floor)
        return dt + jnp.log(-jnp.expm1(-dt))
    return init


class Block(nn.Module):
    config: MoESSMConfig
    # position in the stack: decides the kind, names the device scopes
    layer: int = 0

    @nn.compact
    def __call__(self, x: jax.Array, key_mask: jax.Array, valid: jax.Array
                 ) -> Tuple[jax.Array, jax.Array]:
        """``x`` [B·S, D] float32, token-major; ``key_mask`` and ``valid``
        [B, S] → (x', [3] int32 routing counts: zeros but for an expert
        layer)."""
        cfg, a = self.config, self.config.arch
        kind = a.layer_types[self.layer]
        y = rms_norm(x, self.param("norm", nn.initializers.ones,
                                   (a.hidden_size,)), a.layer_norm_epsilon)
        counts = jnp.zeros((3,), jnp.int32)
        with jax.named_scope(f"layer{self.layer}/{kind}"):
            if kind == "ssm":
                out = self._state_space(y.astype(cfg.dtype),
                                        key_mask.shape[1])
            elif kind == "attn":
                out = self._attention(y.astype(cfg.dtype), key_mask)
            else:
                out, counts = expert_layer(self, y, valid, a.expert_spec, cfg)
        return x + out, counts

    def _state_space(self, y: jax.Array, seq: int) -> jax.Array:
        cfg, a = self.config, self.config.arch
        h, p, g, s = (a.mamba_num_heads, a.mamba_head_dim, a.n_groups,
                      a.ssm_state_size)
        inner, conv_w = a.ssm_inner, a.ssm_inner + 2 * g * s
        n = y.shape[0]
        with jax.named_scope("in_proj"):
            zxbcdt = dense(inner + conv_w + h, cfg, "in_proj")(y)
        with jax.named_scope("conv"):
            init = nn.initializers.normal(cfg.initializer_range)
            xbc = causal_conv_silu(
                zxbcdt[:, inner:inner + conv_w],
                self.param("conv_weight", init, (conv_w, a.conv_kernel)), seq,
                self.param("conv_bias", nn.initializers.zeros, (conv_w,)))
        with jax.named_scope("gates"):
            dt = jax.nn.softplus(
                zxbcdt[:, inner + conv_w:].astype(jnp.float32)
                + self.param("dt_bias", _dt_bias_init(a), (h,)))
            decay = -jnp.exp(self.param("A_log", _a_log_init, (h,)))
        with jax.named_scope("core"):
            out = state_space_scan(
                xbc[:, :inner].reshape(n, h, p),
                xbc[:, inner:inner + g * s].reshape(n, g, s),
                xbc[:, inner + g * s:].reshape(n, g, s), dt, decay,
                self.param("D", nn.initializers.ones, (h,)), seq,
                chunk=a.chunk_size, dtype=cfg.dtype)
        with jax.named_scope("norm_gate"):
            # the gate first, then the norm over each group's channels
            z = zxbcdt[:, :inner].astype(jnp.float32)
            out = (out.reshape(n, inner) * nn.silu(z)).reshape(
                n, g, inner // g)
            out = rms_norm(out, self.param(
                "out_norm", nn.initializers.ones, (inner,)).reshape(
                    g, inner // g), a.layer_norm_epsilon)
            out = out.astype(cfg.dtype).reshape(n, inner)
        with jax.named_scope("out_proj"):
            return dense(a.hidden_size, cfg, "out_proj")(out).astype(
                jnp.float32)

    def _attention(self, y: jax.Array, key_mask: jax.Array) -> jax.Array:
        cfg, a = self.config, self.config.arch
        h, g, d = a.num_attention_heads, a.num_key_value_heads, a.head_dim
        with jax.named_scope("qkv"):
            qkv = dense((h + 2 * g) * d, cfg, "qkv_proj")(y)
        with jax.named_scope("core"):
            out = grouped_query_attention(
                qkv[:, :h * d], qkv[:, h * d:(h + g) * d],
                qkv[:, (h + g) * d:], key_mask, h, g, 0.0,
                impl=cfg.attn_impl, platform=cfg.platform or None,
                rotary_dim=0)
        with jax.named_scope("out_proj"):
            return dense(a.hidden_size, cfg, "out_proj")(out).astype(
                jnp.float32)


class MoESSMLM(nn.Module):
    config: MoESSMConfig

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
        int32 routing counts of the call: models/blocks.py)."""
        return causal_stack(tokens, self.tok_embed, self.layers,
                            self.final_norm,
                            self.config.arch.layer_norm_epsilon)

    def hidden(self, tokens: jax.Array) -> jax.Array:
        return self.hidden_and_counts(tokens)[0]

    def __call__(self, tokens: jax.Array) -> jax.Array:
        """[B, S, V] float32 next-token logits (untied head; compute-dtype
        multiplies, float32 accumulation): the fit's path."""
        cfg = self.config
        return jnp.einsum("bsd,vd->bsv", self.hidden(tokens).astype(cfg.dtype),
                          self.lm_head.astype(cfg.dtype),
                          preferred_element_type=jnp.float32)


class MoESSMScorer(ExpertLMScorer):
    """Causal LM scorer over state-space mixers, grouped-query attention
    and latent sparse experts, with an untied head; scoring call, routing
    counts and train step are :class:`~.blocks.ExpertLMScorer`'s."""

    name = "moe_ssm"

    def _build_model(self) -> MoESSMLM:
        return MoESSMLM(self.config)

    def _head_matrix(self, params) -> jax.Array:
        return params["params"]["lm_head"]
