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
  float32, ``num_experts_per_tok`` chosen by score + correction bias,
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
from typing import Any, Dict, Mapping, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax

from ..ops import experts as expert_ops
from ..ops.attention import latent_attention
from .base import SequenceScorerBase, reduce_nlls
from .gru import causal_lm_loss
from .tokenizer import CLS_ID, PAD_ID

# published keys this family reads but implements one value of: a config
# that says otherwise is refused by name instead of being run as something
# else
_ONE_VALUE = {
    "q_lora_rank": None, "rope_scaling": None, "n_group": 1,
    "topk_group": 1, "moe_layer_freq": 1, "attention_bias": False,
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

    @classmethod
    def from_mapping(cls, arch: Mapping[str, Any]) -> "MoEMLAArch":
        """Typed ``arch`` or a ValueError that names what is wrong: a key
        this family does not know, a missing one, or a published setting it
        cannot compute yet."""
        arch = dict(arch)
        arch.setdefault("router_experts", arch.get("n_routed_experts"))
        for key, only in _ONE_VALUE.items():
            if key in arch and arch.pop(key) != only:
                raise ValueError(
                    f"arch.{key}: the moe_mla scorer computes only "
                    f"{key} = {only!r}")
        for key in _UNREAD:
            arch.pop(key, None)
        fields = {f.name for f in dataclasses.fields(cls)}
        unknown = sorted(set(arch) - fields)
        if unknown:
            raise ValueError(f"arch: unknown key(s) {unknown}")
        missing = sorted(f.name for f in dataclasses.fields(cls)
                         if f.default is dataclasses.MISSING
                         and arch.get(f.name) is None)
        if missing:
            raise ValueError(f"arch: missing key(s) {missing}")
        out = cls(**arch)
        if not 0 < out.first_k_dense_replace <= out.num_hidden_layers:
            raise ValueError("arch.first_k_dense_replace must lie in "
                             "1..num_hidden_layers")
        if not (0 <= out.expert_offset and out.n_routed_experts > 0
                and out.expert_offset + out.n_routed_experts
                <= out.router_experts):
            raise ValueError(
                f"arch: held experts {out.expert_offset}.."
                f"{out.expert_offset + out.n_routed_experts - 1} do not lie "
                f"within the router's {out.router_experts}")
        if out.num_experts_per_tok > out.router_experts:
            raise ValueError("arch.num_experts_per_tok exceeds "
                             "router_experts")
        if out.scoring_func not in ("sigmoid", "softmax"):
            raise ValueError(f"arch.scoring_func {out.scoring_func!r}: "
                             "expected 'sigmoid' or 'softmax'")
        if out.qk_rope_head_dim % 2:
            raise ValueError("arch.qk_rope_head_dim must be even")
        return out

    @property
    def expert_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace


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


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """float32 in, float32 out: statistics and scaling in float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _dense(features: int, cfg: MoEMLAConfig, name: str) -> nn.Dense:
    return nn.Dense(features, use_bias=False, dtype=cfg.dtype, name=name,
                    kernel_init=nn.initializers.normal(cfg.initializer_range))


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
                out = self._gated(y.astype(cfg.dtype), a.intermediate_size,
                                  "")
            return x + out.astype(jnp.float32), jnp.zeros((3,), jnp.int32)
        with jax.named_scope(f"layer{self.layer}/moe"):
            out, counts = self._experts(y, valid)
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
            kva = _dense(a.kv_lora_rank + rope, cfg, "kv_down")(y)
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
            return _dense(a.hidden_size, cfg, "out_proj")(out).astype(
                jnp.float32)

    def _gated(self, y: jax.Array, width: int, prefix: str) -> jax.Array:
        """``W_down(silu(W_gate·y) ⊙ W_up·y)`` at ``width``."""
        cfg, a = self.config, self.config.arch
        gate = _dense(width, cfg, prefix + "gate_proj")(y)
        up = _dense(width, cfg, prefix + "up_proj")(y)
        return _dense(a.hidden_size, cfg, prefix + "down_proj")(
            nn.silu(gate) * up)

    def _experts(self, y: jax.Array, valid: jax.Array
                 ) -> Tuple[jax.Array, jax.Array]:
        cfg, a = self.config, self.config.arch
        d = y.shape[-1]
        init = nn.initializers.normal(cfg.initializer_range)
        m, held = a.moe_intermediate_size, a.n_routed_experts
        valid = valid.reshape(-1)
        router = self.param("router", init, (d, a.router_experts))
        if held < a.router_experts:
            # a share's fit sees only the held experts' part of the result,
            # so its gradient pulls the router towards them (at a tiny size
            # a boundary fit moved 25% of the assignments on the held
            # experts to 83%): the router of a share is not trained here
            router = jax.lax.stop_gradient(router)
        with jax.named_scope("router"):
            routing = expert_ops.route(
                y, router,
                self.param("router_bias", nn.initializers.zeros,
                           (a.router_experts,)),
                valid, top_k=a.num_experts_per_tok,
                norm_topk_prob=a.norm_topk_prob,
                scaling=a.routed_scaling_factor,
                scoring_func=a.scoring_func)
        routed, per_expert = expert_ops.routed_experts(
            y.astype(cfg.dtype), routing,
            self.param("experts_gate", init, (held, d, m)),
            self.param("experts_up", init, (held, d, m)),
            self.param("experts_down", init, (held, m, d)),
            offset=a.expert_offset)
        with jax.named_scope("shared"):
            shared = self._gated(y.astype(cfg.dtype),
                                 a.n_shared_experts * m, "shared_")
        with jax.named_scope("combine"):
            out = routed + shared.astype(jnp.float32)
        counts = jnp.stack([
            valid.sum(dtype=jnp.int32) * a.num_experts_per_tok,
            per_expert.sum(dtype=jnp.int32), per_expert.max()])
        return out, counts


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
        with jax.named_scope("embed"):
            # teacher-forced shift-right: the input at step t is token t-1,
            # at step 0 CLS's own embedding
            inputs = jnp.concatenate(
                [jnp.full_like(tokens[:, :1], CLS_ID), tokens[:, :-1]],
                axis=1)
            # token-major from here on: a [B·S, ·] array has one layout on
            # the TPU, a [B, S, ·] one is laid out sequence-major and
            # copied before every kernel (PERF.md section 6, PR 28)
            x = self.tok_embed(inputs).astype(jnp.float32).reshape(
                -1, self.config.arch.hidden_size)
        key_mask, valid = inputs != PAD_ID, tokens != PAD_ID
        counts = jnp.zeros((3,), jnp.int32)
        for block in self.layers:
            x, layer_counts = block(x, key_mask, valid)
            counts = counts + layer_counts
        return (rms_norm(x, self.final_norm, self.config.arch.rms_norm_eps
                         ).reshape(*tokens.shape, -1), counts)

    def hidden(self, tokens: jax.Array) -> jax.Array:
        return self.hidden_and_counts(tokens)[0]

    def __call__(self, tokens: jax.Array) -> jax.Array:
        """[B, S, V] float32 next-token logits (untied head; compute-dtype
        multiplies, float32 accumulation): the fit's path."""
        cfg = self.config
        return jnp.einsum("bsd,vd->bsv", self.hidden(tokens).astype(cfg.dtype),
                          self.lm_head.astype(cfg.dtype),
                          preferred_element_type=jnp.float32)


class MoEMLAScorer(SequenceScorerBase):
    """Causal sparse-expert LM scorer. The scoring call returns the
    routing counts beside the scores (``score_aux``): one [3] int32 array
    from the same executable, so the detector's counters ride the scores'
    readback."""

    name = "moe_mla"
    score_aux = True

    def __init__(self, config: MoEMLAConfig):
        super().__init__(config)
        # which expert path each traced executable took, by batch rows
        # (GET /admin/xla -> buckets.expert_route)
        self.expert_routes: Dict[int, str] = {}

    def _build_model(self) -> MoEMLALM:
        return MoEMLALM(self.config)

    def _head_matrix(self, params) -> jax.Array:
        return params["params"]["lm_head"]

    def _score_impl(self, params, tokens: jax.Array):
        tokens = tokens.astype(jnp.int32)
        dtype = self.config.dtype
        hidden, counts = self._apply(params, tokens,
                                          method="hidden_and_counts")
        b, s = tokens.shape
        a = self.config.arch
        k = a.num_experts_per_tok
        self.expert_routes[b] = (
            f"sorted ragged_dot, {a.n_routed_experts} of "
            f"{a.router_experts} experts from {a.expert_offset}, chunks of "
            f"{expert_ops.chunk_rows_for(b * s, k)} of {b * s * k} slots")
        with jax.named_scope("head/nll"):
            nlls = self._exact_head(
                hidden.astype(dtype),
                self._head_matrix(params).astype(dtype), tokens)
        mask = (tokens != PAD_ID).astype(jnp.float32)
        return reduce_nlls(nlls, mask, self.config.score_topk), counts

    def _train_impl(self, params, opt_state, rng, tokens):
        del rng  # teacher forcing is deterministic
        tokens = tokens.astype(jnp.int32)

        def loss_fn(p):
            return causal_lm_loss(self._apply(p, tokens), tokens)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = self.optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss
