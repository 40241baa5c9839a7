"""What the sparse-expert scorer families share between their blocks:
the ``arch`` mapping's checks, RMSNorm, the gated feed-forward unit, the
expert layer, the walk over a causal stack and the scorer around it. One home, so
that ``moe_mla`` (latent attention) and ``moe_conv`` (gated short
convolutions and grouped-query attention) call the same code and differ
only in how they mix positions.

The functions create their parameters in the flax module that calls them
(``nn.Dense`` children by name; ``mod.param`` on the module handed in), so
a block's parameter names, shapes and device scopes are the caller's: a
``moe_mla`` checkpoint reads as it did when these were methods of its
``Block``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Mapping, NamedTuple, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax

from ..ops import experts as expert_ops
from ..ops.attention import current_placement
from .base import SequenceScorerBase, reduce_nlls
from .gru import causal_lm_loss
from .tokenizer import CLS_ID, PAD_ID


class ExpertSpec(NamedTuple):
    """An expert layer's shape, under names of its own: the families'
    published keys differ (``n_routed_experts`` / ``num_experts``)."""
    width: int              # a routed expert's gated unit (moe_intermediate_size)
    held: int               # routed experts held on this chip
    router_experts: int     # experts the router scores over
    offset: int             # first held expert
    top_k: int              # experts a token
    norm_topk_prob: bool
    scaling: float          # routed_scaling_factor
    scoring_func: str = "sigmoid"
    shared: int = 0         # shared experts: one gated unit at shared x width
    norm_eps: float = 1e-20  # the weights' normalisation: w / (sum + eps)
    # the shared unit times sigmoid(y · w_s), a per-token gate ([D, 1])
    shared_gate: bool = False
    # False: every unit, routed and shared, is the non-gated
    # down(relu(up·y)²), with no gate projection
    gated: bool = True
    # the routed experts live in a latent this wide, between two projections
    # the layer owns (D -> latent before them, latent -> D behind their
    # weighted sum); 0: at the residual's width. The router and the shared
    # unit read the residual either way
    latent: int = 0
    # the shared unit's width where it is none of the routed experts';
    # 0: shared x width
    shared_width: int = 0
    # group-limited routing: the router's experts in n_group groups, of
    # which a token's topk_group best stand for its choice (ops/experts.py
    # keep_groups); 1 / 1: none
    n_group: int = 1
    topk_group: int = 1


def arch_keys(cls: type, arch: Mapping[str, Any], one_value: Mapping[str, Any],
              unread: Tuple[str, ...], family: str) -> Dict[str, Any]:
    """The keys of an ``arch`` mapping that the typed form ``cls`` (a
    dataclass of published ``config.json`` keys) takes, or a ValueError that
    names what is wrong: a published setting the family computes one value
    of (``one_value``) set to another, a key it does not know, a missing
    one. ``unread`` keys say nothing the family needs and are dropped, so
    that a ``config.json`` can be passed as it is."""
    arch = dict(arch)
    for key, only in one_value.items():
        if key in arch and arch.pop(key) != only:
            raise ValueError(f"arch.{key}: the {family} scorer computes "
                             f"only {key} = {only!r}")
    for key in unread:
        arch.pop(key, None)
    fields = dataclasses.fields(cls)
    unknown = sorted(set(arch) - {f.name for f in fields})
    if unknown:
        raise ValueError(f"arch: unknown key(s) {unknown}")
    missing = sorted(f.name for f in fields
                     if f.default is dataclasses.MISSING
                     and arch.get(f.name) is None)
    if missing:
        raise ValueError(f"arch: missing key(s) {missing}")
    return arch


def check_share(spec: ExpertSpec) -> None:
    """ValueError unless the held experts lie within the router's and a
    token's choices do not outnumber them."""
    if not (0 <= spec.offset and spec.held > 0
            and spec.offset + spec.held <= spec.router_experts):
        raise ValueError(
            f"arch: held experts {spec.offset}.."
            f"{spec.offset + spec.held - 1} do not lie within the router's "
            f"{spec.router_experts}")
    if spec.top_k > spec.router_experts:
        raise ValueError("arch.num_experts_per_tok exceeds router_experts")
    if (spec.n_group < 1 or spec.router_experts % spec.n_group
            or not 0 < spec.topk_group <= spec.n_group):
        raise ValueError(
            f"arch.n_group {spec.n_group} must divide the router's "
            f"{spec.router_experts} experts and topk_group "
            f"{spec.topk_group} lie in 1..n_group")
    if (spec.n_group > 1 and spec.top_k
            > spec.topk_group * (spec.router_experts // spec.n_group)):
        raise ValueError(
            f"arch.num_experts_per_tok {spec.top_k} exceeds the experts of "
            f"topk_group {spec.topk_group} of n_group {spec.n_group} groups")


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """float32 in, float32 out: statistics and scaling in float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def dense(features: int, cfg: Any, name: str) -> nn.Dense:
    """``nn.Dense`` without bias in ``cfg.dtype``, normal(``cfg.
    initializer_range``)."""
    return nn.Dense(features, use_bias=False, dtype=cfg.dtype, name=name,
                    kernel_init=nn.initializers.normal(cfg.initializer_range))


def gated_unit(y: jax.Array, width: int, out_features: int, cfg: Any,
               prefix: str = "") -> jax.Array:
    """``W_down(silu(W_gate·y) ⊙ W_up·y)`` at ``width``; the three
    projections are children ``<prefix>gate_proj`` / ``up_proj`` /
    ``down_proj`` of the calling module."""
    gate = dense(width, cfg, prefix + "gate_proj")(y)
    up = dense(width, cfg, prefix + "up_proj")(y)
    return dense(out_features, cfg, prefix + "down_proj")(nn.silu(gate) * up)


def relu2_unit(y: jax.Array, width: int, out_features: int, cfg: Any,
               prefix: str = "") -> jax.Array:
    """``W_down(relu(W_up·y)²)`` at ``width``, the non-gated unit: children
    ``<prefix>up_proj`` / ``down_proj`` of the calling module."""
    up = dense(width, cfg, prefix + "up_proj")(y)
    return dense(out_features, cfg, prefix + "down_proj")(
        jnp.square(nn.relu(up)))


# jitted, so that a stack's expert layers share one trace and one lowering
# of the routed part (a scoring program's text is a third shorter, and the
# warm-up traces it once a bucket, not once a layer); what the trace reads
# of its surroundings — the platform, the way back — is a static argument
_routed_experts = jax.jit(
    expert_ops.routed_experts,
    static_argnames=("offset", "chunk_rows", "combine", "platform"))


def expert_walk(tokens: int, width: int, spec: ExpertSpec, platform: str,
                mesh_devices: int) -> Tuple[int, str]:
    """``(rows a chunk of the sorted list, the way back to the tokens)``
    of one traced call of ``tokens`` tokens by ``width`` columns of
    residual (the routed experts' own width is the spec's latent where it
    has one): what an expert layer runs and what ``expert_routes``
    records."""
    chunk = expert_ops.chunk_rows_for(tokens, spec.top_k)
    return chunk, expert_ops.combine_route(
        platform, tokens, chunk, spec.latent or width, mesh_devices)


def expert_layer(mod: nn.Module, y: jax.Array, valid: jax.Array,
                 spec: ExpertSpec, cfg: Any) -> Tuple[jax.Array, jax.Array]:
    """The held experts' part of ``Σ w_i·E_i(y)`` (ops/experts.py) plus the
    shared experts where the model has them → ``([N, D] float32, [3] int32
    routing counts: assignments of non-PAD tokens over all experts, those on
    held experts, the busiest held expert's)``. ``y`` [N, D] float32 (the
    router reads it so), ``valid`` marks non-PAD tokens. Parameters:
    ``router`` [D, E], ``router_bias`` [E] (zeros; selection only, no
    gradient), ``experts_gate`` / ``experts_up`` [held, D, M],
    ``experts_down`` [held, M, D] and, with shared experts,
    ``shared_{gate,up,down}_proj`` (and ``shared_gate`` [D, 1] where the
    spec sets it: float32, the router's precision). A non-gated spec has
    no ``experts_gate`` and no ``shared_gate_proj``; with a latent the
    experts' D is the latent's width and ``latent_in`` / ``latent_out``
    (scopes of the same names) lie before and behind them."""
    d = y.shape[-1]
    init = nn.initializers.normal(cfg.initializer_range)
    m, held = spec.width, spec.held
    # the width the routed experts live at
    lat = spec.latent or d
    valid = valid.reshape(-1)
    router = mod.param("router", init, (d, spec.router_experts))
    if held < spec.router_experts:
        # a share's fit sees only the held experts' part of the result,
        # so its gradient pulls the router towards them (at a tiny size
        # a boundary fit moved 25% of the assignments on the held
        # experts to 83%): the router of a share is not trained here
        router = jax.lax.stop_gradient(router)
    with jax.named_scope("router"):
        routing = expert_ops.route(
            y, router,
            mod.param("router_bias", nn.initializers.zeros,
                      (spec.router_experts,)),
            valid, top_k=spec.top_k, norm_topk_prob=spec.norm_topk_prob,
            scaling=spec.scaling, scoring_func=spec.scoring_func,
            norm_eps=spec.norm_eps, n_group=spec.n_group,
            topk_group=spec.topk_group)
    chunk, combine = expert_walk(y.shape[0], d, spec, cfg.platform,
                                 current_placement().mesh_devices)
    routed_in = y.astype(cfg.dtype)
    if spec.latent:
        with jax.named_scope("latent_in"):
            routed_in = dense(lat, cfg, "latent_in")(routed_in)
    out, per_expert = _routed_experts(
        routed_in, routing,
        (mod.param("experts_gate", init, (held, lat, m)) if spec.gated
         else None),
        mod.param("experts_up", init, (held, lat, m)),
        mod.param("experts_down", init, (held, m, lat)),
        offset=spec.offset, chunk_rows=chunk, combine=combine,
        platform=cfg.platform)
    if spec.latent:
        with jax.named_scope("latent_out"):
            out = dense(d, cfg, "latent_out")(out.astype(cfg.dtype)).astype(
                jnp.float32)
    if spec.shared:
        with jax.named_scope("shared"):
            shared = (gated_unit if spec.gated else relu2_unit)(
                y.astype(cfg.dtype), spec.shared_width or spec.shared * m, d,
                cfg, "shared_")
        if spec.shared_gate:
            with jax.named_scope("shared_gate"):
                shared = shared.astype(jnp.float32) * jax.nn.sigmoid(jnp.dot(
                    y, mod.param("shared_gate", init, (d, 1)),
                    precision=jax.lax.Precision.HIGHEST))
        with jax.named_scope("combine"):
            out = out + shared.astype(jnp.float32)
    counts = jnp.stack([
        valid.sum(dtype=jnp.int32) * spec.top_k,
        per_expert.sum(dtype=jnp.int32), per_expert.max()])
    return out, counts


def causal_stack(tokens: jax.Array, embed: nn.Embed, blocks, final_norm:
                 jax.Array, eps: float) -> Tuple[jax.Array, jax.Array]:
    """[B, S] int32 → ([B, S, D] float32 causal hidden states, [3] int32
    routing counts of the call, summed over the expert layers). Each block
    maps ``(x [B·S, D] float32, key_mask [B, S], valid [B, S])`` to ``(x',
    [3] counts)``."""
    with jax.named_scope("embed"):
        # teacher-forced shift-right: the input at step t is token t-1,
        # at step 0 CLS's own embedding
        inputs = jnp.concatenate(
            [jnp.full_like(tokens[:, :1], CLS_ID), tokens[:, :-1]], axis=1)
        # token-major from here on: a [B·S, ·] array has one layout on
        # the TPU, a [B, S, ·] one is laid out sequence-major and
        # copied before every kernel (PERF.md section 6, PR 28)
        x = embed(inputs).astype(jnp.float32).reshape(
            -1, final_norm.shape[-1])
    key_mask, valid = inputs != PAD_ID, tokens != PAD_ID
    counts = jnp.zeros((3,), jnp.int32)
    for block in blocks:
        x, layer_counts = block(x, key_mask, valid)
        counts = counts + layer_counts
    return rms_norm(x, final_norm, eps).reshape(*tokens.shape, -1), counts


class ExpertLMScorer(SequenceScorerBase):
    """Causal sparse-expert LM scorer: the scoring call returns the routing
    counts beside the scores (``score_aux``) — one [3] int32 array from the
    same executable, so the detector's counters ride the scores' readback.
    The model gives ``hidden_and_counts(tokens)`` and next-token logits
    from ``__call__``; ``config.arch.expert_spec`` is its expert layers'
    shape."""

    score_aux = True

    def __init__(self, config: Any):
        super().__init__(config)
        # which expert path each traced executable took, by batch rows
        # (GET /admin/xla -> buckets.expert_route)
        self.expert_routes: Dict[int, str] = {}

    def _score_impl(self, params, tokens: jax.Array):
        tokens = tokens.astype(jnp.int32)
        dtype = self.config.dtype
        hidden, counts = self._apply(params, tokens,
                                     method="hidden_and_counts")
        b, s = tokens.shape
        spec = self.config.arch.expert_spec
        chunk, combine = expert_walk(b * s, hidden.shape[-1], spec,
                                     self.config.platform, self.mesh_devices)
        self.expert_routes[b] = (
            f"sorted ragged_dot, {spec.held} of {spec.router_experts} "
            f"experts from {spec.offset}, chunks of {chunk} of "
            f"{b * s * spec.top_k} slots, combine {combine}")
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
