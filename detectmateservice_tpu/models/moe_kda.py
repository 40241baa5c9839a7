"""Vector-decay delta rule, gated latent attention, group-routed sparse-expert
causal LM scorer (flax): a stack whose layers differ in *kind* by a published
rule — every ``layer_group_size``-th mixes positions with multi-head latent
attention behind per-head query/key norms and a head-wise output gate, the
others with the delta rule whose decay is a vector a head (Kimi Delta
Attention: a ``[Dk, Dv]`` state per head whose rows each decay at a rate of
their own, the rate held above a published lower bound) — over a leading
dense gated unit, then routed experts chosen group-first (the best
``topk_group`` of ``n_group`` groups of experts, then the best experts
among them) beside one shared expert. Named by mechanism, not by any one
model.

Eighth scorer family (mlp → gru → logbert → moe_mla → moe_conv → moe_delta →
moe_ssm → moe_kda). Like the other expert families it is a causal next-token
language model over a line's hashed tokens, scored by the (top-k) mean
next-token NLL at every position through the exact full-vocabulary head
(models/base.py); its head is untied (``lm_head``).

Shape: one mapping, ``arch``, carries the model's published ``config.json``
keys under their published names (:class:`MoEKDAArch`), plus what a chip's
share of a deployment needs. The keys that count heads and experts give
what THIS chip holds; the share says of what:

* ``router_experts`` / ``expert_offset`` — the published expert count the
  router scores over and the first expert held (``num_experts`` is then how
  many are held), as in the other expert families;
* ``tensor_parallel`` / ``tensor_rank`` — how many chips share each mixer,
  and which of them this is: ``num_attention_heads`` (and
  ``num_key_value_heads``, the same count) are this chip's heads of both
  kinds of mixer, the published count ``tensor_parallel`` times as many.
  A share counts heads and never cuts a width: ``kv_down`` and its norm,
  the router, the shared expert and the dense layer's unit are held whole
  on every chip. :meth:`MoEKDAArch.share_of` derives the mapping from a
  published ``config.json`` and refuses a count that does not divide.

A tensor share needs no other code: both mixers are sums over heads with
nothing shared between heads but replicated projections (the delta rule's
output norm is a head's own: ``group_norm_size 1``), so the chip computes
its heads' addend of ``W_o·[…]``, its held experts' addend of the routed
sum, and what every chip of the group computes alike (the shared expert,
the dense unit: counted once where the addends meet); those partial results
go on to the next layer. Nothing stands in for the absent chips or their
exchange.

Layer equations (x the block's input, float32; ``norm(x; w) = w ⊙ x ·
rsqrt(mean(x²) + rms_norm_eps)``, w ones at initialisation, before each
sub-layer, the residual after; H heads HELD, d = ``head_dim``): ``h = x +
mixer_i(norm(x))``, ``y = h + ffn_i(norm(h))``; ``mixer_i`` is latent
attention where ``(i + 1) % layer_group_size == 0``, else the delta rule;
``ffn_i`` is the dense unit for ``i < first_k_dense_replace``, else the
expert layer.

* delta rule, a vector of decays a head (``kda``): ``q | k | v | f | z =
  W_in·y`` (five blocks of H·d columns; ``f`` one full matrix:
  ``no_kda_lora``), ``b = W_b·y`` [H], no bias; ``q, k, v ←
  silu(conv_K(q | k | v))``, depthwise causal over positions with
  ``short_conv_kernel_size`` taps, zero history at a line's start
  (ops/shortconv.py::causal_conv_silu; ``linear_silu``); per head ``q ←
  q/‖q‖ · d^-1/2``, ``k ← k/‖k‖``; the decay, under the lower-bound gate
  (``kda_safe_gate``): ``g = kda_lower_bound · sigmoid(exp(A_log_h) · (f +
  dt_bias))`` in ``(kda_lower_bound, 0)``, a value a head and lane; ``β =
  sigmoid(b)``; per head from ``S_0 = 0`` at a line's first position ``S'
  = Diag(e^{g_t}) S_{t−1}``, ``u_t = β_t (v_t − S'ᵀ k_t)``, ``S_t = S' +
  k_t u_tᵀ``, ``o_t = S_tᵀ q_t`` (ops/deltarule.py::kda_delta_rule: the
  chunked closed form in sub-blocks of 8 positions, one chunk a served
  line); ``W_o [norm_head(o_t; w_o) ⊙ sigmoid(z)]``, the norm over each
  head's d lanes with one weight of d. No rotary positions here.
* latent attention (``attn``): as ``models/moe_mla.py`` (``q = W_q·y``, per
  head ``q_nope ‖ q_rope``; ``W_kva·y → c = norm(first kv_lora_rank; w_c),
  k_rope``; ``W_kvb·c →`` per head ``k_nope ‖ v``; interleaved rotary at
  ``rope_theta`` on the rope lanes; causal softmax at ``(nope +
  rope)^-1/2``) with two additions: ``norm`` over each head's whole ``nope
  + rope``-wide query and key before the rotation (``use_qk_norm``: the
  key's reads the shared ``k_rope`` beside the head's ``k_nope``;
  ops/attention.py::latent_head_norms), and a head-wise output gate
  ``o_h ← o_h · sigmoid(W_gate·y)_h``, ``W_gate`` [D, H]
  (``gated_attention_proj_granularity_type head_wise``), before ``W_o``.
* dense unit: ``W_down(silu(W_gate·y) ⊙ W_up·y)`` at ``intermediate_size``.
* expert layer (models/blocks.py, ops/experts.py): ``s = sigmoid(W_r·y)``
  over all ``router_experts`` in float32; ``c = s + bias`` (zeros,
  selection only, no gradient); the experts in ``n_group`` groups of
  consecutive ones, a group's score the sum of its two largest ``c``; the
  ``topk_group`` best groups kept, ``c`` of the others set aside; the
  ``num_experts_per_tok`` largest ``c`` among the kept; ``w = s_chosen /
  (Σ s_chosen + 1e-20) · routed_scaling_factor``; the held experts' part
  of ``Σ w_i E_i(y)`` (gated units at ``moe_intermediate_size``) plus the
  shared expert (one gated unit at ``moe_shared_expert_intermediate_size``).
* final norm, untied head.

Departures from the published description, each shared with the reference
(benchmark/reference/moe_kda.py): the shift-right causal contract (position
t is predicted from the tokens before t; input 0 is CLS's own embedding),
nothing cached and nothing decoded (neither the convolution's nor the delta
rule's state outlives a line; no key/value cache), no multi-token-prediction
layer (the published ``mtp_loss_scaling_factor`` is 0), no clamp in the
gated units (the published limit lists are 0 for every layer kept; a
non-zero entry is refused by name), the float32 residual stream, the delta
rule's five projections as one matrix ordered by kind, a share's router not
trained (models/blocks.py). Three readings are the family's conventions
and no key of the published file: the gate's form under ``kda_safe_gate``,
where ``use_qk_norm`` sits in latent attention, and ``group_norm_size 1``
as a norm a head — the benchmark's configuration lists them under
``assumed``.

The scoring programs walk a run of consecutive layers of one kind as one
``lax.scan`` of a block over the run's stacked leaves
(:meth:`MoEKDALM._walk`); the fit's step and the reference walk the layers
one by one. Same arithmetic a layer either way.

Precision: multiplies in the compute dtype (bfloat16) with float32
accumulation; residual stream, RMSNorm statistics (the per-head ones too),
rotary angles, the convolution's products and SiLU, the delta rule's gates,
decays, their running sums, L2 norms, triangular inverse and state, the
output gates, router (logits to weights), softmax and the head's logsumexp
in float32.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.attention import (latent_head_norms, per_head_latent_attention,
                             sigmoid_gate)
from ..ops.deltarule import kda_delta_rule, kda_gates, kda_route
from ..ops.shortconv import causal_conv_silu
from .blocks import (ExpertLMScorer, ExpertSpec, arch_keys, causal_stack,
                     check_share, dense, expert_layer, gated_unit, rms_norm)
from .moe_delta import _a_log_init

LAYER_KINDS = ("kda", "attn")
# published keys this family reads but implements one value of
_ONE_VALUE = {
    "hidden_act": "silu", "use_bias": False, "use_qkv_bias": False,
    "tie_word_embeddings": False, "norm_topk_prob": True,
    "num_shared_experts": 1, "moe_router_enable_expert_bias": True,
    "score_function": "sigmoid", "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "rope_interleave": True,
    "rope_scaling": None, "q_lora_rank": None, "use_qk_norm": True,
    "gated_attention_proj_granularity_type": "head_wise",
    "kda_safe_gate": True, "linear_silu": True, "no_kda_lora": True,
    "use_kda_lora": False, "group_norm_size": 1, "use_nGPT": False,
    "value_norm": False, "up_proj_norm": False, "scale_router_input": False,
    "use_mla_nope": False, "mtp_use_kda": False,
    "num_kv_heads_for_linear_attn": 0,
}
# published keys that say nothing this family needs: the multi-token-
# prediction layer is no part of a scoring pass, qk_head_dim, rotary_dim and
# partial_rotary_factor restate the two q·k widths, and the rest are the
# published code's position limit, window switch and auxiliary loss
_UNREAD = ("model_type", "max_position_embeddings", "vocab_size",
           "max_window_layers", "mtp_loss_scaling_factor",
           "num_nextn_predict_layers", "seq_aux", "qk_head_dim",
           "rotary_dim", "partial_rotary_factor")
# a gated unit's clamp, one entry a layer: this family computes no clamp
_LIMIT_LISTS = ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list")
# positions a chunk of the delta rule's closed form: a served line (32) is
# one chunk. A static argument of the operation, no key of any configuration
KDA_CHUNK = 32


@dataclasses.dataclass(frozen=True)
class MoEKDAArch:
    """The ``arch`` mapping, typed. Field names are the published
    ``config.json`` keys; the last four place this chip's share."""
    hidden_size: int
    num_hidden_layers: int
    layer_group_size: int
    first_k_dense_replace: int
    num_attention_heads: int       # heads HELD here, of both mixers
    head_dim: int
    short_conv_kernel_size: int
    kda_lower_bound: float
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    kv_lora_rank: int
    rope_theta: float
    intermediate_size: int
    moe_intermediate_size: int
    moe_shared_expert_intermediate_size: int
    num_experts: int               # experts HELD here
    num_experts_per_tok: int
    n_group: int
    topk_group: int
    routed_scaling_factor: float
    rms_norm_eps: float
    router_experts: int            # experts the router scores over
    expert_offset: int = 0         # first held expert
    tensor_parallel: int = 1       # chips that share a mixer
    tensor_rank: int = 0           # which of them this is

    @classmethod
    def from_mapping(cls, arch: Mapping[str, Any]) -> "MoEKDAArch":
        """Typed ``arch`` or a ValueError that names what is wrong: a key
        this family does not know, a missing one, or a published setting it
        cannot compute."""
        arch = dict(arch)
        arch.setdefault("router_experts", arch.get("num_experts"))
        layers = arch.get("num_hidden_layers")
        for key in _LIMIT_LISTS:
            limits = arch.pop(key, None)
            if limits is not None and (any(limits) or len(limits) != layers):
                raise ValueError(
                    f"arch.{key}: the moe_kda scorer clamps no gated unit "
                    f"and takes one 0 a layer ({layers}): {limits!r}")
        kv_heads = arch.pop("num_key_value_heads", None)
        out = cls(**arch_keys(cls, arch, _ONE_VALUE, _UNREAD, "moe_kda"))
        if kv_heads is not None and kv_heads != out.num_attention_heads:
            raise ValueError(
                "arch.num_key_value_heads: the moe_kda scorer computes "
                "as many key/value heads as query heads (latent attention "
                "and the delta rule have no grouped keys)")
        if out.layer_group_size < 1:
            raise ValueError("arch.layer_group_size must be at least 1")
        if not 0 <= out.first_k_dense_replace <= out.num_hidden_layers:
            raise ValueError("arch.first_k_dense_replace must lie in "
                             "0..num_hidden_layers")
        if out.qk_rope_head_dim % 2 or out.qk_rope_head_dim < 2:
            raise ValueError("arch.qk_rope_head_dim must be even")
        if out.short_conv_kernel_size < 1:
            raise ValueError("arch.short_conv_kernel_size must be at "
                             "least 1")
        if not out.kda_lower_bound < 0:
            raise ValueError("arch.kda_lower_bound must be negative: the "
                             "log decay lies in (kda_lower_bound, 0)")
        # and no further down than the closed form's sub-blocks hold
        kda_route("auto", KDA_CHUNK, KDA_CHUNK, out.kda_lower_bound)
        if not 0 <= out.tensor_rank < out.tensor_parallel:
            raise ValueError(
                f"arch.tensor_rank {out.tensor_rank} is none of "
                f"tensor_parallel {out.tensor_parallel} chips")
        check_share(out.expert_spec)
        return out

    @classmethod
    def share_of(cls, published: Mapping[str, Any], *, tensor_parallel: int,
                 tensor_rank: int = 0, experts_held: int = 0,
                 expert_offset: int = 0, num_hidden_layers: int = 0,
                 first_k_dense_replace: int = -1) -> dict:
        """The ``arch`` mapping of one chip's share of a published
        ``config.json``: ``tensor_parallel`` chips share each mixer's heads
        (the count has to divide; every width stays whole), ``experts_held``
        routed experts from ``expert_offset`` lie here (all of them where
        0), and the first ``num_hidden_layers`` layers of the published
        rule (all where 0) of which the first ``first_k_dense_replace``
        are dense (the published count where negative)."""
        arch, tp = dict(published), tensor_parallel
        for key in ("num_attention_heads", "num_key_value_heads"):
            if key in arch:
                if arch[key] % tp:
                    raise ValueError(
                        f"arch.{key} {arch[key]} does not divide over "
                        f"tensor_parallel {tp} chips")
                arch[key] //= tp
        layers = num_hidden_layers or arch["num_hidden_layers"]
        for key in _LIMIT_LISTS:
            if key in arch:
                arch[key] = list(arch[key][:layers])
        if first_k_dense_replace >= 0:
            arch["first_k_dense_replace"] = first_k_dense_replace
        arch.update(
            tensor_parallel=tp, tensor_rank=tensor_rank,
            router_experts=arch["num_experts"],
            num_experts=experts_held or arch["num_experts"],
            expert_offset=expert_offset, num_hidden_layers=layers)
        return arch

    @property
    def layer_types(self) -> Tuple[str, ...]:
        """The published rule: every ``layer_group_size``-th layer is
        latent attention."""
        return tuple(LAYER_KINDS[(i + 1) % self.layer_group_size == 0]
                     for i in range(self.num_hidden_layers))

    @property
    def expert_spec(self) -> ExpertSpec:
        return ExpertSpec(
            width=self.moe_intermediate_size, held=self.num_experts,
            router_experts=self.router_experts, offset=self.expert_offset,
            top_k=self.num_experts_per_tok, norm_topk_prob=True,
            scaling=float(self.routed_scaling_factor),
            scoring_func="sigmoid", shared=1, norm_eps=1e-20,
            shared_width=self.moe_shared_expert_intermediate_size,
            n_group=self.n_group, topk_group=self.topk_group)


@dataclasses.dataclass(frozen=True)
class MoEKDAConfig:
    arch: MoEKDAArch
    vocab_size: int = 32768
    seq_len: int = 32
    dtype: Any = jnp.bfloat16
    learning_rate: float = 1e-4
    initializer_range: float = 0.02
    score_topk: int = 0
    # "auto" | "einsum" (ops/attention.py::per_head_latent_attention:
    # behind the key's norm every head has a rope part of its own, which
    # the einsum computes everywhere)
    attn_impl: str = "auto"
    # "auto" | "chunked" | "scan" (ops/deltarule.py::kda_route)
    kda_impl: str = "auto"
    head_impl: str = "auto"
    platform: str = ""


class Block(nn.Module):
    config: MoEKDAConfig
    # position in the stack: decides the mixer and the feed-forward's kind,
    # names the device scopes
    layer: int = 0

    @nn.compact
    def __call__(self, x: jax.Array, key_mask: jax.Array, valid: jax.Array
                 ) -> Tuple[jax.Array, jax.Array]:
        """``x`` [B·S, D] float32, token-major; ``key_mask`` and ``valid``
        [B, S] → (x', [3] int32 routing counts: zeros in a dense layer)."""
        cfg, a = self.config, self.config.arch
        kind = a.layer_types[self.layer]
        y = rms_norm(x, self.param("input_norm", nn.initializers.ones,
                                   (a.hidden_size,)),
                     a.rms_norm_eps).astype(cfg.dtype)
        with jax.named_scope(f"layer{self.layer}/{kind}"):
            x = x + (self._kda(y, key_mask.shape[1]) if kind == "kda"
                     else self._attention(y, key_mask))
        y = rms_norm(x, self.param("post_norm", nn.initializers.ones,
                                   (a.hidden_size,)), a.rms_norm_eps)
        if self.layer < a.first_k_dense_replace:
            with jax.named_scope(f"layer{self.layer}/ffn"):
                out = gated_unit(y.astype(cfg.dtype), a.intermediate_size,
                                 a.hidden_size, cfg)
            return x + out.astype(jnp.float32), jnp.zeros((3,), jnp.int32)
        with jax.named_scope(f"layer{self.layer}/moe"):
            out, counts = expert_layer(self, y, valid, a.expert_spec, cfg)
        return x + out, counts

    def _kda(self, y: jax.Array, seq: int) -> jax.Array:
        cfg, a = self.config, self.config.arch
        h, d = a.num_attention_heads, a.head_dim
        width, n = h * d, y.shape[0]
        with jax.named_scope("in_proj"):
            qkvfz = dense(5 * width, cfg, "in_proj")(y)    # q | k | v | f | z
            b = dense(h, cfg, "b_proj")(y)
        with jax.named_scope("conv"):
            qkv = causal_conv_silu(
                qkvfz[:, :3 * width],
                self.param("conv_weight",
                           nn.initializers.normal(cfg.initializer_range),
                           (3 * width, a.short_conv_kernel_size)), seq)
        with jax.named_scope("gates"):
            g, beta = kda_gates(
                qkvfz[:, 3 * width:4 * width].reshape(n, h, d), b,
                self.param("A_log", _a_log_init, (h,)),
                self.param("dt_bias", nn.initializers.ones, (h, d)),
                a.kda_lower_bound)
        with jax.named_scope("core"):
            q, k, v = (qkv[:, i * width:(i + 1) * width].reshape(n, h, d)
                       for i in range(3))
            out = kda_delta_rule(q, k, v, g, beta, seq, chunk=KDA_CHUNK,
                                 impl=cfg.kda_impl, dtype=cfg.dtype,
                                 lower_bound=a.kda_lower_bound)
        with jax.named_scope("norm_gate"):
            out = rms_norm(out, self.param("out_norm", nn.initializers.ones,
                                           (d,)), a.rms_norm_eps)
            out = sigmoid_gate(out.reshape(n, width),
                               qkvfz[:, 4 * width:]).astype(cfg.dtype)
        with jax.named_scope("out_proj"):
            return dense(a.hidden_size, cfg, "out_proj")(out).astype(
                jnp.float32)

    def _attention(self, y: jax.Array, key_mask: jax.Array) -> jax.Array:
        cfg, a = self.config, self.config.arch
        h, nope, rope = (a.num_attention_heads, a.qk_nope_head_dim,
                         a.qk_rope_head_dim)

        def weight(name: str, width: int) -> jax.Array:
            return self.param(name, nn.initializers.ones, (width,))

        with jax.named_scope("q_proj"):
            q = dense(h * (nope + rope), cfg, "q_proj")(y)
        with jax.named_scope("kv_down"):
            kva = dense(a.kv_lora_rank + rope, cfg, "kv_down")(y)
            c = rms_norm(kva[..., :a.kv_lora_rank],
                         weight("kv_norm", a.kv_lora_rank),
                         a.rms_norm_eps).astype(cfg.dtype)
        with jax.named_scope("kv_up"):
            kv = dense(h * (nope + a.v_head_dim), cfg, "kv_up")(c)
        with jax.named_scope("qk_norm"):
            q, k = latent_head_norms(
                q, kv, kva[..., a.kv_lora_rank:],
                weight("q_norm", nope + rope), weight("k_norm", nope + rope),
                a.rms_norm_eps, h, nope)
        with jax.named_scope("core"):
            out = per_head_latent_attention(
                q, k, kv.reshape(kv.shape[0], h, -1)[..., nope:], key_mask,
                nope, a.rope_theta, impl=cfg.attn_impl,
                platform=cfg.platform or None)
        with jax.named_scope("gate"):
            out = sigmoid_gate(out, dense(h, cfg, "attn_gate")(y))
        with jax.named_scope("out_proj"):
            return dense(a.hidden_size, cfg, "out_proj")(out).astype(
                jnp.float32)


class MoEKDALM(nn.Module):
    config: MoEKDAConfig

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

    def hidden_and_counts(self, tokens: jax.Array, scan_runs: bool = True
                          ) -> Tuple[jax.Array, jax.Array]:
        """[B, S] int32 → ([B, S, D] float32 causal hidden states, [3]
        int32 routing counts of the call: models/blocks.py)."""
        return causal_stack(tokens, self.tok_embed,
                            self._walk() if scan_runs else self.layers,
                            self.final_norm, self.config.arch.rms_norm_eps)

    def _walk(self) -> list:
        """The stack as ``causal_stack`` walks it: a layer alone is its
        ``Block``; a run of consecutive layers of one kind (the same mixer
        over the same feed-forward: the published rule makes runs of
        ``layer_group_size − 1``) is ONE ``lax.scan`` of a ``Block`` over
        the run's leaves, stacked at the call — so a compiled program
        holds a run's body once, not once a layer. The parameters keep
        their per-layer names (``layers_<i>``: the checkpoint's, the
        reference's); the stack costs one copy of the run's leaves a call
        (1.1 GB at the published widths, 3 ms of the chip's bandwidth);
        the run's device scopes all read ``layer<first>``. Why: four
        unrolled copies of the delta-rule and expert layers made the
        configuration's seven programs 205 MB of compile-cache entries,
        over the 192 MiB the chip tool's machine keeps, and every run
        compiled them anew (PERF.md section 6, PR 44). The scoring
        programs only: the fit's step (``__call__``) walks the layers one
        by one, because a scan's reverse pass keeps the stacked leaves and
        their gradient beside the step's 16 bytes a parameter (6.28 GB of
        temporaries for 2.95 by XLA's buffer assignment)."""
        if self.is_initializing():
            return self.layers          # each layer makes its own leaves
        a = self.config.arch
        kind = [(a.layer_types[i], i < a.first_k_dense_replace)
                for i in range(a.num_hidden_layers)]
        walk, first = [], 0
        while first < len(kind):
            stop = first + 1
            while stop < len(kind) and kind[stop] == kind[first]:
                stop += 1
            walk.append(self.layers[first] if stop == first + 1
                        else self._run(first, stop))
            first = stop
        return walk

    def _run(self, first: int, stop: int):
        leaves = [self.variables["params"][f"layers_{i}"]
                  for i in range(first, stop)]
        block = Block(self.config, layer=first, parent=None)   # unbound

        def run(x: jax.Array, key_mask: jax.Array, valid: jax.Array
                ) -> Tuple[jax.Array, jax.Array]:
            stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                             *leaves)
            x, counts = jax.lax.scan(
                lambda x, layer: block.apply({"params": layer}, x, key_mask,
                                             valid), x, stacked)
            return x, counts.sum(0)
        return run

    def hidden(self, tokens: jax.Array) -> jax.Array:
        return self.hidden_and_counts(tokens)[0]

    def __call__(self, tokens: jax.Array) -> jax.Array:
        """[B, S, V] float32 next-token logits (untied head; compute-dtype
        multiplies, float32 accumulation): the fit's path."""
        cfg = self.config
        hidden, _ = self.hidden_and_counts(tokens, scan_runs=False)
        return jnp.einsum("bsd,vd->bsv", hidden.astype(cfg.dtype),
                          self.lm_head.astype(cfg.dtype),
                          preferred_element_type=jnp.float32)


class MoEKDAScorer(ExpertLMScorer):
    """Causal sparse-expert LM scorer over the vector-decay delta rule and
    gated latent attention, with an untied head; scoring call, routing
    counts and train step are :class:`~.blocks.ExpertLMScorer`'s."""

    name = "moe_kda"

    def _build_model(self) -> MoEKDALM:
        return MoEKDALM(self.config)

    def _head_matrix(self, params) -> jax.Array:
        return params["params"]["lm_head"]
