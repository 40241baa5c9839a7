"""The gated-short-convolution, grouped-query, sparse-expert scorer
(models/moe_conv.py, models/blocks.py, ops/shortconv.py, ops/attention.py's
grouped-query form) at a tiny size on the CPU, held to the benchmark's plain
reference (benchmark/reference/moe_conv.py, which imports nothing of models/
or ops/): scores and per-position NLLs in float32 and bfloat16, the fit, the
share test without a shared expert, the grouped einsum against repeated
heads, the attention route's table with the third form, ``arch``'s refusals,
causality, the tied head, the routing counters, and the whole detector life
(fit, threshold, checkpoint, restore)."""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.reference import moe_conv as reference  # noqa: E402
from detectmateservice_tpu.library.common.core import LibraryError  # noqa: E402
from detectmateservice_tpu.library.detectors import JaxScorerDetector  # noqa: E402
from detectmateservice_tpu.models import blocks  # noqa: E402
from detectmateservice_tpu.models.moe_conv import (  # noqa: E402
    MoEConvArch, MoEConvConfig, MoEConvScorer)
from detectmateservice_tpu.ops import experts as expert_ops  # noqa: E402
from detectmateservice_tpu.ops.attention import (  # noqa: E402
    attention_route, dot_product_attention, grouped_query_attention, rotary)

VOCAB, SEQ = 64, 16
ARCH = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    conv_L_cache=3, conv_bias=False, intermediate_size=96,
    moe_intermediate_size=48, num_experts_per_tok=2, num_dense_layers=1,
    norm_topk_prob=True, routed_scaling_factor=1, use_expert_bias=True,
    norm_eps=1e-5, rope_parameters={"rope_theta": 1e6,
                                    "rope_type": "default"},
    num_hidden_layers=4,
    layer_types=["conv", "full_attention", "conv", "conv"],
    num_experts=8, router_experts=8, expert_offset=0)
SHARE = dict(num_experts=4, expert_offset=2)


def arch_with(**changes):
    return dict(ARCH, **changes)


def make_scorer(arch=None, dtype=jnp.float32, init=0.3, seed=0, **config):
    """A seeded scorer; ``init`` is wide so that the blocks, not the
    embedding, decide the scores at this size."""
    scorer = MoEConvScorer(MoEConvConfig(
        arch=MoEConvArch.from_mapping(arch or ARCH), vocab_size=VOCAB,
        seq_len=SEQ, dtype=dtype, initializer_range=init, **config))
    params, opt_state = scorer.init(jax.random.PRNGKey(seed))
    return scorer, params, opt_state


def make_tokens(rows=8, seed=0):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(3, VOCAB, size=(rows, SEQ)).astype(np.int32)
    tokens[:, 0] = 2                      # CLS
    tokens[3, 9:] = 0                     # short lines
    tokens[5, 4:] = 0
    tokens[rows - 1, :] = 0               # a padding row
    return tokens


def as_numpy(tree):
    return jax.tree_util.tree_map(np.asarray, tree)


# -- scorer against the reference ------------------------------------------

@pytest.mark.parametrize("dtype,nll_tol,score_tol", [
    (jnp.float32, 5e-5, 1e-5),
    # bfloat16 multiplies through four layers at init 0.1: a position's NLL
    # is off by under 0.01 nats at the median and by tenths where a token's
    # second expert changed (routing is discontinuous), a line's score by
    # hundredths; the float8_e4m3fn control's scores are off by 0.1 and
    # more. The tolerances lie between
    (jnp.bfloat16, 0.03, 0.05),
])
def test_scorer_matches_reference(dtype, nll_tol, score_tol):
    arch = arch_with(**SHARE)
    scorer, params, _ = make_scorer(arch, dtype, init=0.1)
    tokens = make_tokens()
    scores, _ = scorer._score(params, tokens)
    nlls = scorer._token_nlls(params, tokens)
    want_nlls = reference.token_nlls(as_numpy(params), tokens, arch)
    want = reference.score(as_numpy(params), tokens, {"arch": arch})
    gaps = np.abs(np.asarray(nlls - want_nlls))[tokens != 0]
    assert (gaps.max() if dtype == jnp.float32
            else np.median(gaps)) < nll_tol
    assert float(np.abs(np.asarray(scores) - want).max()) < score_tol
    assert float(jnp.abs(nlls[-1]).max()) == 0.0       # the padding row
    assert np.allclose(np.asarray(scorer.score(params, tokens)),
                       np.asarray(scores))
    assert scorer.attn_routes == {8: "einsum"}
    assert scorer.conv_routes == {8: "xla"}


def test_the_kernels_route_scores_what_xlas_does():
    """``conv_impl: fused`` (the Pallas kernel in the interpreter) at a
    width that tiles, against ``xla`` on the same parameters."""
    arch = arch_with(hidden_size=128, **SHARE)
    plain, params, _ = make_scorer(arch, init=0.1, conv_impl="xla",
                                   platform="cpu")
    fused = MoEConvScorer(dataclasses.replace(plain.config,
                                              conv_impl="fused"))
    tokens = make_tokens()
    (a, counts_a), (b, counts_b) = (plain._score(params, tokens),
                                    fused._score(params, tokens))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(counts_a), np.asarray(counts_b))
    assert fused.conv_routes == {8: "fused"}
    want = reference.score(as_numpy(params), tokens, {"arch": arch})
    assert float(np.abs(np.asarray(b) - want).max()) < 1e-5


def test_reference_lower_control_changes_the_scores():
    _, params, _ = make_scorer(init=0.1)
    tokens = make_tokens()
    plain = reference.score(as_numpy(params), tokens, {"arch": ARCH})
    again = reference.score(as_numpy(params), tokens, {"arch": ARCH},
                            block_rows=4)
    lowered = reference.score(as_numpy(params), tokens, {"arch": ARCH},
                              lower=jnp.float8_e4m3fn)
    assert np.allclose(plain, again, atol=1e-5)       # blocks change nothing
    assert np.abs(plain - lowered)[:-1].max() > 1e-3


# -- the fit ---------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_fit_lowers_the_loss_and_stays_finite(dtype):
    scorer, params, opt_state = make_scorer(arch_with(**SHARE), dtype,
                                            init=0.1)
    # the fit's learning rate is sized for published widths; at 64 wide a
    # few steps need a larger one to show
    import optax
    scorer.optimizer = optax.adamw(3e-3)
    opt_state = scorer.optimizer.init(params)
    scorer._train_donating = jax.jit(scorer._train_impl,
                                     donate_argnums=(0, 1))
    tokens = make_tokens(rows=32, seed=1)
    losses = []
    for step in range(6):
        params, opt_state, loss = scorer.train_step(
            params, opt_state, jax.random.PRNGKey(step), tokens, donate=True)
        losses.append(float(loss))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0] - 0.05
    assert all(bool(jnp.isfinite(leaf).all())
               for leaf in jax.tree_util.tree_leaves(params))
    # the selection bias is a buffer; a share's router is not trained
    for layer in ("layers_1", "layers_2", "layers_3"):
        assert float(jnp.abs(
            params["params"][layer]["router_bias"]).max()) == 0


def test_a_shares_router_is_not_trained_and_the_taps_are():
    share, sp, so = make_scorer(arch_with(**SHARE), init=0.1)
    tokens = make_tokens()
    sn, _, _ = share.train_step(sp, so, jax.random.PRNGKey(1), tokens)
    drift = jnp.abs(sn["params"]["layers_1"]["router"]
                    - sp["params"]["layers_1"]["router"]).max()
    assert float(drift) < 1e-7       # AdamW's decay alone touches it
    for layer, leaf in (("layers_0", "conv_weight"), ("layers_1", "q_norm"),
                        ("layers_2", "experts_up"),
                        ("layers_3", "conv_weight")):
        assert float(jnp.abs(sn["params"][layer][leaf]
                             - sp["params"][layer][leaf]).max()) > 1e-6, leaf
    whole, wp, wo = make_scorer(init=0.1)
    wn, _, _ = whole.train_step(wp, wo, jax.random.PRNGKey(1), tokens)
    assert float(jnp.abs(wn["params"]["layers_1"]["router"]
                         - wp["params"]["layers_1"]["router"]).max()) > 1e-6


# -- the share, without a shared expert -------------------------------------

def test_eight_shares_add_up_to_the_uncut_references_layer():
    """The parts of an expert layer's result that the eight shares give
    add up to what the uncut reference gives for the whole layer; nothing
    is computed by every chip alike (no shared expert), so nothing is
    counted once."""
    rng = np.random.default_rng(1)
    n, d, m, e_all, k = 96, 32, 24, 16, 4
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(d, e_all)) * 0.5, jnp.float32)
    gate, up = (jnp.asarray(rng.normal(size=(e_all, d, m)) * 0.2,
                            jnp.float32) for _ in range(2))
    down = jnp.asarray(rng.normal(size=(e_all, m, d)) * 0.2, jnp.float32)
    valid = jnp.asarray(rng.random(n) > 0.1)
    routing = expert_ops.route(x, router, jnp.zeros(e_all), valid, top_k=k,
                               norm_topk_prob=True, scaling=1.0,
                               norm_eps=1e-6)
    parts, counts = [], []
    for offset in range(0, e_all, 2):                  # eight shares of two
        part, c = expert_ops.routed_experts(
            x, routing, gate[offset:offset + 2], up[offset:offset + 2],
            down[offset:offset + 2], offset=offset)
        parts.append(part)
        counts.append(c)
    assert int(np.concatenate(counts).sum()) == int(valid.sum()) * k
    arch = {"num_experts_per_tok": k, "norm_topk_prob": True,
            "routed_scaling_factor": 1}
    with jax.default_matmul_precision("highest"):
        chosen, w = reference.routing(x, router, jnp.zeros(e_all), arch)
        uncut = jnp.zeros((n, d))
        for e in range(e_all):
            w_e = (w * (chosen == e)).sum(-1)
            uncut += w_e[:, None] * (
                (jax.nn.silu(x @ gate[e]) * (x @ up[e])) @ down[e])
    uncut = jnp.where(valid[:, None], uncut, 0.0)
    assert np.allclose(sum(parts), uncut, atol=1e-4)
    # the published epsilon: the four weights sum to 1 / (1 + 1e-6 / sum)
    assert np.allclose(routing.weights.sum(-1), 1.0, atol=1e-5)
    assert float(routing.weights.sum(-1).max()) < 1.0


def test_the_normalisations_epsilon_is_the_callers():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(16, 4)), jnp.float32)
    kw = dict(top_k=2, norm_topk_prob=True, scaling=1.0)
    default = expert_ops.route(x, router, jnp.zeros(4), jnp.ones(8, bool),
                               **kw)
    large = expert_ops.route(x, router, jnp.zeros(4), jnp.ones(8, bool),
                             norm_eps=1.0, **kw)
    assert np.allclose(default.weights.sum(-1), 1.0, atol=1e-6)
    assert float(large.weights.sum(-1).max()) < 0.7


def test_no_shared_expert_builds_no_shared_unit():
    """``shared 0`` asks for no zero-width matmul: the layer has no
    ``shared_*`` parameter, in this family and in ``moe_mla`` alike."""
    from detectmateservice_tpu.models.moe_mla import (
        MoEMLAArch, MoEMLAConfig, MoEMLAScorer)
    from tests.test_moe_mla import ARCH as MLA_ARCH

    _, params, _ = make_scorer()
    names = set(params["params"]["layers_1"])
    assert not [name for name in names if name.startswith("shared")]
    assert {"router", "router_bias", "experts_gate", "experts_up",
            "experts_down"} <= names
    mla = MoEMLAScorer(MoEMLAConfig(
        arch=MoEMLAArch.from_mapping(dict(MLA_ARCH, n_shared_experts=0)),
        vocab_size=VOCAB, seq_len=SEQ, dtype=jnp.float32))
    mla_params, _ = mla.init(jax.random.PRNGKey(0))
    assert not [name for name in mla_params["params"]["layers_1"]
                if name.startswith("shared")]
    scores, counts = mla._score(mla_params, make_tokens())
    assert np.isfinite(np.asarray(scores)).all() and int(counts[0]) > 0
    with_shared = MoEMLAScorer(MoEMLAConfig(
        arch=MoEMLAArch.from_mapping(MLA_ARCH), vocab_size=VOCAB,
        seq_len=SEQ, dtype=jnp.float32))
    assert "shared_gate_proj" in with_shared.init(
        jax.random.PRNGKey(0))[0]["params"]["layers_1"]


def test_the_families_share_one_expert_layer_and_one_norm():
    """``moe_mla`` and ``moe_conv`` call models/blocks.py; no copy of the
    expert layer, the gated unit or RMSNorm is left in either."""
    from detectmateservice_tpu.models import moe_conv, moe_mla

    for family, scorer in ((moe_mla, moe_mla.MoEMLAScorer),
                           (moe_conv, moe_conv.MoEConvScorer)):
        assert family.expert_layer is blocks.expert_layer
        assert family.gated_unit is blocks.gated_unit
        assert family.rms_norm is blocks.rms_norm
        assert issubclass(scorer, blocks.ExpertLMScorer)
    found = subprocess.run(
        ["grep", "-rn", "def _experts\\|def _gated\\|def rms_norm",
         os.path.join(REPO, "detectmateservice_tpu")],
        capture_output=True, text=True).stdout.splitlines()
    assert [line.split(":")[0][len(REPO) + 1:] for line in found] == [
        "detectmateservice_tpu/models/blocks.py"]


# -- grouped-query attention -------------------------------------------------

def _gqa_operands(seed=4, b=3, s=8, h=8, g=2, d=16):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b * s, h * d)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(b * s, g * d)), jnp.float32)
            for _ in range(2))
    mask = jnp.asarray(rng.random((b, s)) > 0.2).at[:, 0].set(True)
    return q, k, v, mask, (b, s, h, g, d)


def test_the_grouped_einsum_equals_repeated_heads():
    q, k, v, mask, (b, s, h, g, d) = _gqa_operands()
    out = grouped_query_attention(q, k, v, mask, h, g, 1e4, platform="cpu")
    assert out.shape == (b * s, h * d)

    def head_major(x, n):
        return x.reshape(b, s, n, d).transpose(0, 2, 1, 3)

    qh = rotary(head_major(q, h), 1e4, interleaved=False)
    kh = rotary(head_major(k, g), 1e4, interleaved=False)
    # every key/value head repeated for its H / G consecutive query heads
    kh, vh = (jnp.repeat(x, h // g, axis=1)
              for x in (kh, head_major(v, g)))
    see = mask[:, None, None, :] & jnp.tril(jnp.ones((s, s), bool))
    want = dot_product_attention(qh, kh, vh, see)
    want = want.transpose(0, 2, 1, 3).reshape(b * s, h * d)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)
    # one key/value head a query head is plain multi-head attention
    full = grouped_query_attention(q, jnp.tile(k, (1, h // g)),
                                   jnp.tile(v, (1, h // g)), mask, h, h, 1e4,
                                   platform="cpu")
    assert full.shape == out.shape


def test_rotate_half_is_the_references_rotation():
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(2, 8, 3, 16)), jnp.float32)  # [B,S,H,D]
    turned = rotary(x, 1e6, interleaved=False, heads_inside=True)
    np.testing.assert_allclose(np.asarray(turned),
                               np.asarray(reference._rotate_half(x, 1e6)),
                               atol=1e-5)
    # position 0 is not turned; the interleaved form differs
    np.testing.assert_allclose(np.asarray(turned[:, 0]), np.asarray(x[:, 0]),
                               atol=1e-6)
    other = rotary(x.transpose(0, 2, 1, 3), 1e6).transpose(0, 2, 1, 3)
    assert float(jnp.abs(other - turned).max()) > 1e-2


def test_a_forced_kernel_is_refused_by_name_for_grouped_heads():
    q, k, v, mask, (b, s, h, g, d) = _gqa_operands()
    for impl in ("short", "flash"):
        with pytest.raises(ValueError, match="grouped-query"):
            grouped_query_attention(q, k, v, mask, h, g, 1e4, impl=impl,
                                    platform="cpu")


@pytest.mark.parametrize("call,want", [
    # logbert's form: one width, no mask, as many key/value heads as heads
    (dict(heads=4, head_dim=64, value_dim=64, causal=False), "short"),
    # latent attention's: two q·k widths, a value width of its own, causal
    (dict(heads=32, head_dim=192, value_dim=128, causal=True, rope_dim=64),
     "short"),
    # the third form: causal, one width, 8 key/value heads for 32 query heads
    (dict(heads=32, head_dim=64, value_dim=64, causal=True, kv_heads=8),
     "einsum"),
    # as many key/value heads as query heads is not the third form; causal
    # without a second width still has no kernel body
    (dict(heads=32, head_dim=64, value_dim=64, causal=True, kv_heads=32),
     "einsum"),
    (dict(heads=4, head_dim=64, value_dim=64, causal=False, kv_heads=4),
     "short"),
])
def test_the_attention_routes_table_with_the_third_form(call, want):
    def route(platform="tpu", rows=1024, mesh=1, impl="auto"):
        return attention_route(impl, platform, 32, 32, rows=rows,
                               mesh_devices=mesh, **call)

    assert route() == want
    assert route(platform="cpu") == "einsum"
    assert route(mesh=4) == "einsum"
    assert route(rows=32) == "einsum"          # the fit's step
    assert route(impl="einsum") == "einsum"


def test_the_third_form_is_told_by_head_counts_not_by_a_name():
    """A traced 1024-row program at head counts 32 / 8 records ``einsum``
    on one TPU, on the CPU and on a mesh; nothing is lowered."""
    arch = arch_with(hidden_size=256, num_attention_heads=32,
                     num_key_value_heads=8, num_hidden_layers=3,
                     layer_types=["conv", "full_attention", "conv"])

    def routes(platform, mesh_devices=1):
        scorer = MoEConvScorer(MoEConvConfig(
            arch=MoEConvArch.from_mapping(arch), vocab_size=VOCAB,
            seq_len=32, platform=platform, head_impl="einsum",
            conv_impl="xla"))
        scorer.mesh_devices = mesh_devices
        params = jax.eval_shape(lambda: scorer.init(jax.random.PRNGKey(0))[0])
        for rows in (32, 1024):
            jax.eval_shape(scorer._score_impl, params,
                           jax.ShapeDtypeStruct((rows, 32), jnp.uint16))
        return scorer.attn_routes

    assert routes("tpu") == routes("cpu") == routes("tpu", 4) == {
        32: "einsum", 1024: "einsum"}


# -- the contracts of the family ---------------------------------------------

def test_causal_a_change_at_t_leaves_earlier_nlls_untouched():
    scorer, params, _ = make_scorer(init=0.2)
    tokens = make_tokens()
    t = 6
    changed = tokens.copy()
    changed[0, t] = (changed[0, t] + 7) % (VOCAB - 3) + 3
    before = np.asarray(scorer._token_nlls(params, tokens))
    after = np.asarray(scorer._token_nlls(params, changed))
    assert np.allclose(before[0, :t], after[0, :t], atol=1e-6)
    assert abs(before[0, t] - after[0, t]) > 1e-4       # its own target
    assert np.abs(before[0, t + 1:] - after[0, t + 1:]).max() > 1e-4
    assert np.allclose(before[1:], after[1:], atol=1e-6)


def test_a_stack_of_convolutions_alone_sees_as_far_as_its_taps_reach():
    """Without attention a change at t reaches the NLLs up to t + layers x
    (K - 1) + 1 and no further (the input is shifted right by one)."""
    arch = arch_with(num_hidden_layers=2, layer_types=["conv", "conv"],
                     num_dense_layers=2)
    scorer, params, _ = make_scorer(arch, init=0.2)
    tokens = make_tokens()
    t = 3
    changed = tokens.copy()
    changed[0, t] = (changed[0, t] + 7) % (VOCAB - 3) + 3
    before = np.asarray(scorer._token_nlls(params, tokens))
    after = np.asarray(scorer._token_nlls(params, changed))
    moved = np.flatnonzero(np.abs(before[0] - after[0]) > 1e-7)
    assert moved.min() == t and moved.max() == t + 1 + 2 * 2
    assert np.allclose(before[1:], after[1:], atol=1e-7)


def test_the_head_is_tied_to_the_embedding():
    scorer, params, _ = make_scorer(init=0.2)
    assert "lm_head" not in params["params"]
    tokens = make_tokens()
    # a token that is never an INPUT (only the last position's target)
    # still moves the scores through its embedding row: the head is tied
    tokens[:, -1] = np.where(tokens[:, -1] != 0, VOCAB - 1, 0)
    tokens[:, :-1] = np.where(tokens[:, :-1] == VOCAB - 1, 5,
                              tokens[:, :-1])
    base = np.asarray(scorer.score(params, tokens))
    p = params["params"]
    emb = p["tok_embed"]["embedding"].at[VOCAB - 1].mul(3.0)
    changed = {"params": dict(p, tok_embed={"embedding": emb})}
    assert np.abs(np.asarray(scorer.score(changed, tokens))
                  - base)[:-1].max() > 1e-3


def test_counters_match_the_references_routing():
    arch = arch_with(**SHARE)
    scorer, params, _ = make_scorer(arch, init=0.2)
    tokens = make_tokens()
    _, counts = scorer._score(params, tokens)
    _, chosen = reference.token_nlls(as_numpy(params), tokens, arch,
                                     with_routing=True)
    chosen = np.asarray(chosen)                     # [layers, N, S, K]
    assert chosen.shape[0] == 3                     # layers 1-3 hold experts
    held = (chosen >= 2) & (chosen < 6)
    busiest = sum(max(int((layer == e).sum()) for e in range(2, 6))
                  for layer in chosen)
    assert [int(c) for c in counts] == [int((chosen >= 0).sum()),
                                        int(held.sum()), busiest]
    assert int(counts[0]) == int((tokens != 0).sum()) * 2 * 3


@pytest.mark.parametrize("change,named", [
    ({"conv_bias": True}, "conv_bias"),
    ({"use_expert_bias": False}, "use_expert_bias"),
    ({"rope_parameters": {"rope_theta": 1e6, "rope_type": "yarn"}},
     "rope_type"),
    ({"rope_parameters": {"rope_theta": 1e6, "factor": 8}}, "factor"),
    ({"rope_parameters": None}, "rope_theta"),
    ({"bogus": 1}, "bogus"),
    ({"layer_types": ["conv", "full_attention", "conv"]}, "layer_types"),
    ({"layer_types": ["conv", "sliding_attention", "conv", "conv"]},
     "sliding_attention"),
    ({"expert_offset": 6, "num_experts": 4}, "held experts"),
    ({"num_key_value_heads": 3}, "num_key_value_heads"),
    ({"conv_L_cache": None}, "conv_L_cache"),
    ({"num_dense_layers": 5}, "num_dense_layers"),
    ({"n_shared_experts": 1}, "n_shared_experts"),
])
def test_arch_refuses_by_name(change, named):
    with pytest.raises(ValueError, match=named):
        MoEConvArch.from_mapping(arch_with(**change))


def test_arch_takes_a_published_config_as_it_is():
    published = dict(ARCH, model_type="lfm2_moe",
                     max_position_embeddings=128000, vocab_size=VOCAB)
    published.pop("router_experts")
    published.pop("expert_offset")
    arch = MoEConvArch.from_mapping(published)
    assert arch.router_experts == arch.num_experts == 8
    assert arch.rope_theta == 1e6 and arch.head_dim == 16
    assert arch.layer_types == ("conv", "full_attention", "conv", "conv")
    spec = arch.expert_spec
    assert (spec.shared, spec.norm_eps, spec.scoring_func, spec.top_k) == (
        0, 1e-6, "sigmoid", 2)


# -- through JaxScorerDetector ----------------------------------------------

def detector_config(**overrides):
    base = {
        "method_type": "jax_scorer", "auto_config": False,
        "model": "moe_conv", "arch": arch_with(**SHARE),
        "vocab_size": 256, "seq_len": SEQ, "dtype": "float32",
        "data_use_training": 32, "train_epochs": 2, "min_train_steps": 8,
        "train_batch_size": 8, "max_batch": 32, "pipeline_depth": 2,
        "host_score_max_batch": 0, "async_fit": False,
    }
    base.update(overrides)
    return {"detectors": {"JaxScorerDetector": base}}


def _msgs(n, salt=""):
    from detectmateservice_tpu.schemas import ParserSchema

    return [ParserSchema(
        EventID=1, template="user <*> logged in from <*>",
        variables=[f"u{i % 8}{salt}", f"10.0.0.{i % 16}"], logID=str(i),
        logFormatVariables={"Time": "1700000000"}).serialize()
        for i in range(n)]


def _sample(det, name):
    from prometheus_client import REGISTRY

    return REGISTRY.get_sample_value(name, det._obs_labels()) or 0.0


def test_detector_life_fit_checkpoint_restore_and_counters(tmp_path):
    det = JaxScorerDetector(config=detector_config())
    assert det.process_batch(_msgs(32)) == []
    det.flush_final()
    assert det._fitted and np.isfinite(det._threshold)
    names = ("detector_moe_assignments_total",
             "detector_moe_held_assignments_total",
             "detector_moe_busiest_expert_assignments_total")
    before = [_sample(det, n) for n in names]
    batch = _msgs(24, salt="x")
    det.process_batch(batch)
    det.flush_final()
    tokens, ok = det._featurize_raw_batch(batch)
    assert ok.all()
    padded = np.concatenate([tokens, np.zeros((8, SEQ), np.int32)])
    _, chosen = reference.token_nlls(
        as_numpy(det._exec.params), padded, det.config.arch, with_routing=True)
    chosen = np.asarray(chosen)
    held = (chosen >= 2) & (chosen < 6)
    want = [int((chosen >= 0).sum()), int(held.sum()),
            sum(max(int((layer == e).sum()) for e in range(2, 6))
                for layer in chosen)]
    assert [_sample(det, n) - b for n, b in zip(names, before)] == want
    state = det._bucket_state()
    assert "ragged_dot, 4 of 8 experts from 2" in state["expert_route"]["32"]
    assert state["attn_route"]["32"] == "einsum"
    assert state["conv_route"]["32"] == "xla"
    assert state["head_route"]["32"] == "einsum"
    info = det.device_info()
    assert info["scorer"]["model"] == "moe_conv"
    assert info["scorer"]["arch"]["conv_L_cache"] == 3
    assert info["host_twin"]["state"] == "off"
    scores = det.score_tokens(tokens)
    want_scores = reference.score(as_numpy(det._exec.params), tokens,
                                  {"arch": det.config.arch})
    assert np.abs(scores - want_scores).max() < 1e-4
    det.save_checkpoint(str(tmp_path / "ckpt"))
    fresh = JaxScorerDetector(config=detector_config())
    fresh.load_checkpoint(str(tmp_path / "ckpt"))
    assert fresh._fitted
    assert fresh._threshold == pytest.approx(det._threshold)
    assert np.allclose(fresh.score_tokens(tokens), scores, atol=1e-6)


@pytest.mark.parametrize("overrides,named", [
    ({"mesh_shape": {"data": 2}}, "mesh_shape"),
    ({"dtype": "int8w"}, "int8w"),
    ({"score_vocab": 16}, "score_vocab"),
    ({"attn_impl": "short"}, "attn_impl"),
    ({"attn_impl": "flash"}, "attn_impl"),
    ({"arch": None}, "arch"),
    ({"host_score_max_batch": 8}, None),       # admitted: the twin stays off
])
def test_detector_refuses_at_validation_by_name(overrides, named):
    if named is None:
        det = JaxScorerDetector(config=detector_config(**overrides))
        assert not det._host_scoring_possible()
        return
    with pytest.raises(LibraryError, match=named):
        JaxScorerDetector(config=detector_config(**overrides))


def test_other_families_refuse_an_arch_and_unknown_models_are_named():
    from tests.test_jax_scorer import scorer_config

    with pytest.raises(LibraryError, match="arch"):
        JaxScorerDetector(config=scorer_config(arch=ARCH))
    with pytest.raises(LibraryError, match="moe_conv"):
        JaxScorerDetector(config=scorer_config(model="nope"))


def test_a_bad_arch_fails_at_build_before_any_trace():
    det = JaxScorerDetector(config=detector_config(
        arch=arch_with(conv_bias=True)))
    with pytest.raises(LibraryError, match="conv_bias"):
        det._ensure_scorer()


def test_no_branch_on_a_models_name_outside_the_families_table():
    """``jax_scorer.py`` and ``device_executor.py`` name no family in code:
    the table (scorer_families.py) is the one place."""
    import re

    for name in ("jax_scorer.py", "device_executor.py"):
        path = os.path.join(REPO, "detectmateservice_tpu", "library",
                            "detectors", name)
        with open(path, encoding="utf-8") as fh:
            code = [line.split("#", 1)[0] for line in fh
                    if not line.lstrip().startswith("#")]
        named = [line for line in code
                 if re.search(r"[\"'](moe_conv|moe_mla)[\"']", line)]
        assert not named, named
