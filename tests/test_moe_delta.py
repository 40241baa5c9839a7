"""The gated-delta-rule, gated-attention, sparse-expert scorer
(models/moe_delta.py, models/blocks.py, ops/deltarule.py, ops/shortconv.py's
ungated convolution, ops/attention.py's gated, partly rotated grouped-query
form) at a tiny size on the CPU, held to the benchmark's plain reference
(benchmark/reference/moe_delta.py, which imports nothing of models/ or ops/
and runs the recurrence as a scan): scores and per-position NLLs in float32
and bfloat16, either mixer alone, the chunk length, the fit, the share test
with the gated shared expert counted once, ``arch``'s refusals, causality,
the untied head, the routing counters, and the whole detector life (fit,
threshold, checkpoint, restore)."""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.reference import moe_delta as reference  # noqa: E402
from detectmateservice_tpu.library.common.core import LibraryError  # noqa: E402
from detectmateservice_tpu.library.detectors import JaxScorerDetector  # noqa: E402
from detectmateservice_tpu.models import blocks, moe_delta  # noqa: E402
from detectmateservice_tpu.models.moe_delta import (  # noqa: E402
    MoEDeltaArch, MoEDeltaConfig, MoEDeltaScorer)
from detectmateservice_tpu.ops import experts as expert_ops  # noqa: E402

VOCAB, SEQ = 64, 32
ARCH = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    head_dim=32, partial_rotary_factor=0.25, rope_theta=1e7,
    full_attention_interval=4, linear_conv_kernel_dim=4,
    linear_key_head_dim=16, linear_value_head_dim=16, linear_num_key_heads=2,
    linear_num_value_heads=4, moe_intermediate_size=48,
    shared_expert_intermediate_size=48, num_experts_per_tok=3,
    rms_norm_eps=1e-6, num_hidden_layers=4, num_experts=16,
    router_experts=16, expert_offset=0)
SHARE = dict(num_experts=4, expert_offset=4)
# what a published config.json carries beside the keys the family reads
PUBLISHED_EXTRAS = dict(
    hidden_act="silu", decoder_sparse_step=1, mlp_only_layers=[],
    rope_scaling=None, use_sliding_window=False, norm_topk_prob=True,
    tie_word_embeddings=False, model_type="qwen3_next",
    intermediate_size=160, max_position_embeddings=262144, vocab_size=VOCAB)


def arch_with(**changes):
    return dict(ARCH, **changes)


def make_scorer(arch=None, dtype=jnp.float32, init=0.1, seed=0, **config):
    """A seeded scorer; ``init`` is wide so that the blocks, not the
    embedding, decide the scores at this size."""
    scorer = MoEDeltaScorer(MoEDeltaConfig(
        arch=MoEDeltaArch.from_mapping(arch or ARCH), vocab_size=VOCAB,
        seq_len=SEQ, dtype=dtype, initializer_range=init, **config))
    params, opt_state = scorer.init(jax.random.PRNGKey(seed))
    return scorer, params, opt_state


def make_tokens(rows=8, seed=0):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(3, VOCAB, size=(rows, SEQ)).astype(np.int32)
    tokens[:, 0] = 2                      # CLS
    tokens[3, 19:] = 0                    # PAD tails
    tokens[5, 4:] = 0
    tokens[rows - 1, :] = 0               # an all-PAD line
    return tokens


def as_numpy(tree):
    return jax.tree_util.tree_map(np.asarray, tree)


# -- scorer against the reference ------------------------------------------

@pytest.mark.parametrize("dtype,nll_tol,score_tol", [
    (jnp.float32, 1e-4, 2e-5),
    # bfloat16 multiplies through four layers at init 0.1: a position's NLL
    # is off by under 0.01 nats at the median and by tenths where a token's
    # third expert changed (routing is discontinuous), a line's score by
    # hundredths; the float8_e4m3fn control's scores are off by 0.1 and
    # more. The tolerances lie between
    (jnp.bfloat16, 0.03, 0.06),
])
def test_scorer_matches_reference(dtype, nll_tol, score_tol):
    arch = arch_with(**SHARE)
    scorer, params, _ = make_scorer(arch, dtype)
    tokens = make_tokens()
    scores, _ = scorer._score(params, tokens)
    nlls = scorer._token_nlls(params, tokens)
    want_nlls = reference.token_nlls(as_numpy(params), tokens, arch)
    want = reference.score(as_numpy(params), tokens, {"arch": arch})
    gaps = np.abs(np.asarray(nlls - want_nlls))[tokens != 0]
    assert (gaps.max() if dtype == jnp.float32
            else np.median(gaps)) < nll_tol
    assert float(np.abs(np.asarray(scores) - want).max()) < score_tol
    assert float(jnp.abs(nlls[-1]).max()) == 0.0       # the all-PAD line
    assert np.isfinite(np.asarray(scores)).all()
    assert np.allclose(np.asarray(scorer.score(params, tokens)),
                       np.asarray(scores))
    assert scorer.attn_routes == {8: "einsum"}
    assert scorer.delta_routes == {8: "chunked 32"}
    assert scorer.conv_routes == {}          # the ungated form has one route


@pytest.mark.parametrize("interval,layers,kinds", [
    (1, 2, ("full_attention",) * 2),         # gated attention alone
    (5, 3, ("linear_attention",) * 3),       # the delta rule alone
    (2, 4, ("linear_attention", "full_attention") * 2),
])
def test_either_mixer_alone_matches_the_reference(interval, layers, kinds):
    arch = arch_with(full_attention_interval=interval,
                     num_hidden_layers=layers, **SHARE)
    assert MoEDeltaArch.from_mapping(arch).layer_types == kinds
    scorer, params, _ = make_scorer(arch, init=0.2)
    tokens = make_tokens()
    nlls = scorer._token_nlls(params, tokens)
    want = reference.token_nlls(as_numpy(params), tokens, arch)
    assert float(jnp.abs(nlls - want).max()) < 2e-4
    names = set(params["params"]["layers_0"])
    assert ("qkv_proj" in names) == (kinds[0] == "full_attention")
    assert ("A_log" in names) == (kinds[0] == "linear_attention")


@pytest.mark.parametrize("chunk", [8, 16])
def test_the_chunk_length_moves_no_score(chunk, monkeypatch):
    """A 32-long line in 8- and 16-long chunks, the state carried between
    them, through the whole scorer: the scan's scores to float32's error."""
    arch = arch_with(**SHARE)
    scorer, params, _ = make_scorer(arch, init=0.2)
    tokens = make_tokens()
    want = reference.score(as_numpy(params), tokens, {"arch": arch})
    monkeypatch.setattr(moe_delta, "DELTA_CHUNK", chunk)
    chunked = MoEDeltaScorer(scorer.config)
    scores, _ = chunked._score(params, tokens)
    assert chunked.delta_routes == {8: f"chunked {chunk}"}
    assert float(np.abs(np.asarray(scores) - want).max()) < 2e-5
    scanned = MoEDeltaScorer(dataclasses.replace(scorer.config,
                                                 delta_impl="scan"))
    assert float(np.abs(np.asarray(scanned._score(params, tokens)[0])
                        - want).max()) < 2e-5
    assert scanned.delta_routes == {8: "scan"}


# delta-rule heads in whole lane groups, as the kernel tiles them
WIDE = dict(linear_key_head_dim=128, linear_value_head_dim=128,
            linear_num_key_heads=1, linear_num_value_heads=2)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 0.06)])
def test_the_kernel_moves_no_score(dtype, tol):
    """``delta_impl: fused`` (the Pallas interpreter here) through the whole
    scorer, reading q | k | v in place from the convolution's output: the
    reference's scores, and the chunked form's to the same tolerance."""
    arch = arch_with(**SHARE, **WIDE)
    scorer, params, _ = make_scorer(arch, dtype, init=0.2)
    tokens = make_tokens()
    want = reference.score(as_numpy(params), tokens, {"arch": arch})
    fused = MoEDeltaScorer(dataclasses.replace(scorer.config,
                                               delta_impl="fused"))
    scores, _ = fused._score(params, tokens)
    assert fused.delta_routes == {8: "fused"}
    assert float(np.abs(np.asarray(scores) - want).max()) < tol
    assert np.isfinite(np.asarray(scores)).all()
    plain, _ = scorer._score(params, tokens)
    assert scorer.delta_routes == {8: "chunked 32"}
    assert float(np.abs(np.asarray(scores) - np.asarray(plain)).max()) < tol


def test_the_scorer_records_the_delta_route_by_bucket():
    """What ``auto`` resolves to where the scorer is placed on one TPU,
    read from a trace alone (nothing is lowered or run): the kernel from
    256 rows, the chunked form for the fit's 32-row step, on a mesh and
    where the heads do not fill a lane group."""
    def traced(scorer, params, opt_state, rows=(32, 256, 512, 1024)):
        for n in rows:
            jax.eval_shape(scorer._score_impl, params,
                           jnp.zeros((n, SEQ), jnp.uint16))
        return scorer.delta_routes

    scorer, params, opt_state = make_scorer(arch_with(**SHARE, **WIDE),
                                            platform="tpu")
    assert traced(scorer, params, opt_state) == {
        32: "chunked 32", 256: "fused", 512: "fused", 1024: "fused"}
    scorer.delta_routes.clear()
    jax.eval_shape(scorer._train_impl, params, opt_state,
                   jax.random.PRNGKey(0), jnp.zeros((32, SEQ), jnp.int32))
    assert scorer.delta_routes == {32: "chunked 32"}
    scorer.delta_routes.clear()
    scorer.mesh_devices = 4
    assert traced(scorer, params, opt_state, (256,)) == {256: "chunked 32"}
    narrow, params, opt_state = make_scorer(arch_with(**SHARE),
                                            platform="tpu")
    assert traced(narrow, params, opt_state, (256,)) == {256: "chunked 32"}
    host, params, opt_state = make_scorer(arch_with(**SHARE, **WIDE),
                                          platform="cpu")
    assert traced(host, params, opt_state, (256,)) == {256: "chunked 32"}


def test_reference_lower_control_changes_the_scores():
    _, params, _ = make_scorer()
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
    scorer, params, opt_state = make_scorer(arch_with(**SHARE), dtype)
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
    assert scorer.delta_routes == {32: "chunked 32"}    # the fit's form


def test_a_shares_router_is_not_trained_and_the_mixers_are():
    share, sp, so = make_scorer(arch_with(**SHARE))
    tokens = make_tokens()
    sn, _, _ = share.train_step(sp, so, jax.random.PRNGKey(1), tokens)
    drift = jnp.abs(sn["params"]["layers_1"]["router"]
                    - sp["params"]["layers_1"]["router"]).max()
    assert float(drift) < 1e-7       # AdamW's decay alone touches it
    for layer, leaf in (("layers_0", "conv_weight"), ("layers_0", "A_log"),
                        ("layers_1", "dt_bias"), ("layers_2", "out_norm"),
                        ("layers_2", "shared_gate"), ("layers_3", "q_norm"),
                        ("layers_3", "experts_up"), ("layers_0", "input_norm")):
        assert float(jnp.abs(sn["params"][layer][leaf]
                             - sp["params"][layer][leaf]).max()) > 1e-7, leaf
    for layer in ("layers_0", "layers_3"):
        assert float(jnp.abs(sn["params"][layer]["router_bias"]).max()) == 0
    whole, wp, wo = make_scorer()
    wn, _, _ = whole.train_step(wp, wo, jax.random.PRNGKey(1), tokens)
    assert float(jnp.abs(wn["params"]["layers_1"]["router"]
                         - wp["params"]["layers_1"]["router"]).max()) > 1e-6


def test_the_norms_are_zero_centred_but_the_delta_rules_output_norm():
    _, params, _ = make_scorer()
    p = params["params"]
    for name in ("input_norm", "post_norm"):
        assert float(jnp.abs(p["layers_0"][name]).max()) == 0.0
    assert float(jnp.abs(p["final_norm"]).max()) == 0.0
    for name in ("q_norm", "k_norm"):
        assert float(jnp.abs(p["layers_3"][name]).max()) == 0.0
    assert float(jnp.abs(p["layers_0"]["out_norm"] - 1.0).max()) == 0.0
    assert float(jnp.abs(p["layers_0"]["dt_bias"] - 1.0).max()) == 0.0
    a = np.exp(np.asarray(p["layers_0"]["A_log"]))
    assert (a > 0).all() and (a <= 16).all()
    # a weight of w scales by 1 + w: doubling it is not doubling the norm
    x = jnp.asarray(np.random.default_rng(0).normal(size=(3, 8)), jnp.float32)
    w = jnp.full((8,), 0.5)
    np.testing.assert_allclose(
        np.asarray(blocks.rms_norm(x, 1.0 + w, 1e-6)),
        1.5 * np.asarray(blocks.rms_norm(x, jnp.ones(8), 1e-6)), rtol=1e-6)


# -- the share, with the gated shared expert counted once -------------------

def test_sixteen_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """The guide's share test: the routed parts that sixteen shares give,
    plus what every chip computes alike — the shared expert times its
    per-token gate — counted once, equal the uncut reference layer."""
    rng = np.random.default_rng(1)
    n, d, m, e_all, k = 96, 32, 24, 32, 5
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(d, e_all)) * 0.5, jnp.float32)
    gate, up = (jnp.asarray(rng.normal(size=(e_all, d, m)) * 0.2,
                            jnp.float32) for _ in range(2))
    down = jnp.asarray(rng.normal(size=(e_all, m, d)) * 0.2, jnp.float32)
    s_gate, s_up = (jnp.asarray(rng.normal(size=(d, m)) * 0.2, jnp.float32)
                    for _ in range(2))
    s_down = jnp.asarray(rng.normal(size=(m, d)) * 0.2, jnp.float32)
    w_s = jnp.asarray(rng.normal(size=(d, 1)), jnp.float32)
    valid = jnp.asarray(rng.random(n) > 0.1)
    routing = expert_ops.route(x, router, jnp.zeros(e_all), valid, top_k=k,
                               norm_topk_prob=True, scaling=1.0,
                               scoring_func="softmax", norm_eps=0.0)
    parts, counts = [], []
    for offset in range(0, e_all, 2):                 # sixteen shares of two
        part, c = expert_ops.routed_experts(
            x, routing, gate[offset:offset + 2], up[offset:offset + 2],
            down[offset:offset + 2], offset=offset)
        parts.append(part)
        counts.append(c)
    assert len(parts) == 16
    assert int(np.concatenate(counts).sum()) == int(valid.sum()) * k
    np.testing.assert_allclose(np.asarray(routing.weights.sum(-1)), 1.0,
                               atol=1e-6)

    def unit(y, g, u, dn):
        return (jax.nn.silu(y @ g) * (y @ u)) @ dn

    with jax.default_matmul_precision("highest"):
        chosen, w = reference.routing(x, router,
                                      {"num_experts_per_tok": k})
        routed = jnp.zeros((n, d))
        for e in range(e_all):
            w_e = (w * (chosen == e)).sum(-1)
            routed += w_e[:, None] * unit(x, gate[e], up[e], down[e])
        shared = jax.nn.sigmoid(x @ w_s) * unit(x, s_gate, s_up, s_down)
    routed = jnp.where(valid[:, None], routed, 0.0)
    assert np.allclose(sum(parts), routed, atol=1e-4)
    # the whole layer through the program's expert_layer, all experts held:
    # the routed part and the gated shared expert, once
    import flax.linen as nn

    spec = blocks.ExpertSpec(width=m, held=e_all, router_experts=e_all,
                             offset=0, top_k=k, norm_topk_prob=True,
                             scaling=1.0, scoring_func="softmax", shared=1,
                             norm_eps=0.0, shared_gate=True)

    class Layer(nn.Module):
        @nn.compact
        def __call__(self, y, ok):
            cfg = MoEDeltaConfig(arch=None, dtype=jnp.float32, platform="cpu")
            return blocks.expert_layer(self, y, ok, spec, cfg)

    params = {"params": {
        "router": router, "router_bias": jnp.zeros(e_all),
        "experts_gate": gate, "experts_up": up, "experts_down": down,
        "shared_gate_proj": {"kernel": s_gate},
        "shared_up_proj": {"kernel": s_up},
        "shared_down_proj": {"kernel": s_down}, "shared_gate": w_s}}
    with jax.default_matmul_precision("highest"):
        out, layer_counts = Layer().apply(params, x, valid)
    assert np.allclose(out, sum(parts) + shared, atol=1e-4)
    assert np.allclose(out, routed + shared, atol=1e-4)
    assert int(layer_counts[0]) == int(layer_counts[1]) == int(
        valid.sum()) * k
    # without the gate the spec adds the bare shared unit, as moe_mla's
    bare = spec._replace(shared_gate=False)

    class Bare(nn.Module):
        @nn.compact
        def __call__(self, y, ok):
            cfg = MoEDeltaConfig(arch=None, dtype=jnp.float32, platform="cpu")
            return blocks.expert_layer(self, y, ok, bare, cfg)

    ungated = dict(params["params"])
    ungated.pop("shared_gate")
    with jax.default_matmul_precision("highest"):
        out_bare, _ = Bare().apply({"params": ungated}, x, valid)
    assert np.allclose(out_bare, routed + unit(x, s_gate, s_up, s_down),
                       atol=1e-4)


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
    # the state carries it to every later position of the line
    assert (np.abs(before[0, t + 1:] - after[0, t + 1:]) > 1e-7).all()
    assert np.allclose(before[1:], after[1:], atol=1e-6)


def test_the_delta_rule_alone_carries_a_change_to_the_lines_end():
    """Without attention a change at t still reaches the line's last NLL:
    the state is carried over positions (and decays on the way), where a
    stack of short convolutions sees as far as its taps reach
    (tests/test_moe_conv.py) — here t + 1 + 3 for one layer of four taps."""
    arch = arch_with(full_attention_interval=5, num_hidden_layers=1)
    scorer, params, _ = make_scorer(arch, init=0.3)
    tokens = make_tokens()
    t = 3
    changed = tokens.copy()
    changed[0, t] = (changed[0, t] + 7) % (VOCAB - 3) + 3
    before = np.asarray(scorer._token_nlls(params, tokens))
    after = np.asarray(scorer._token_nlls(params, changed))
    moved = np.flatnonzero(np.abs(before[0] - after[0]) > 1e-7)
    assert moved.min() == t and moved.max() == SEQ - 1
    assert (moved > t + 1 + 3).sum() >= 3
    assert np.allclose(before[1:], after[1:], atol=1e-7)


def test_the_head_is_untied():
    scorer, params, _ = make_scorer(init=0.2)
    assert params["params"]["lm_head"].shape == (VOCAB, 64)
    tokens = make_tokens()
    base = np.asarray(scorer.score(params, tokens))
    p = params["params"]
    changed = {"params": dict(p, lm_head=p["lm_head"] * 1.5)}
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
    assert chosen.shape[0] == 4                     # every layer has experts
    held = (chosen >= 4) & (chosen < 8)
    busiest = sum(max(int((layer == e).sum()) for e in range(4, 8))
                  for layer in chosen)
    assert [int(c) for c in counts] == [int((chosen >= 0).sum()),
                                        int(held.sum()), busiest]
    assert int(counts[0]) == int((tokens != 0).sum()) * 3 * 4


@pytest.mark.parametrize("change,named", [
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"decoder_sparse_step": 2}, "decoder_sparse_step"),
    ({"mlp_only_layers": [0]}, "mlp_only_layers"),
    ({"rope_scaling": {"type": "yarn", "factor": 4}}, "rope_scaling"),
    ({"use_sliding_window": True}, "use_sliding_window"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"bogus": 1}, "bogus"),
    ({"layer_types": ["linear_attention"] * 4}, "layer_types"),
    ({"head_dim": None}, "head_dim"),
    ({"expert_offset": 14, "num_experts": 4}, "held experts"),
    ({"num_key_value_heads": 3}, "num_key_value_heads"),
    ({"partial_rotary_factor": 0.1}, "partial_rotary_factor"),
    ({"linear_num_key_heads": 3}, "linear_num_key_heads"),
    ({"linear_value_head_dim": 32}, "linear_value_head_dim"),
    ({"shared_expert_intermediate_size": 50},
     "shared_expert_intermediate_size"),
    ({"full_attention_interval": 0}, "full_attention_interval"),
    ({"linear_conv_kernel_dim": 0}, "linear_conv_kernel_dim"),
    ({"num_experts_per_tok": 17}, "num_experts_per_tok"),
])
def test_arch_refuses_by_name(change, named):
    with pytest.raises(ValueError, match=named):
        MoEDeltaArch.from_mapping(arch_with(**change))


def test_arch_takes_a_published_config_as_it_is():
    published = dict(ARCH, **PUBLISHED_EXTRAS)
    published.pop("router_experts")
    published.pop("expert_offset")
    arch = MoEDeltaArch.from_mapping(published)
    assert arch.router_experts == arch.num_experts == 16
    assert arch.rope_theta == 1e7 and arch.rotary_dim == 8
    assert arch.layer_types == ("linear_attention",) * 3 + (
        "full_attention",)
    spec = arch.expert_spec
    assert (spec.shared, spec.shared_gate, spec.norm_eps, spec.scoring_func,
            spec.top_k, spec.scaling) == (1, True, 0.0, "softmax", 3, 1.0)


def test_the_family_calls_the_shared_blocks():
    assert moe_delta.expert_layer is blocks.expert_layer
    assert moe_delta.rms_norm is blocks.rms_norm
    assert moe_delta.causal_stack is blocks.causal_stack
    assert issubclass(MoEDeltaScorer, blocks.ExpertLMScorer)
    # a spec that does not set the gate builds no such parameter
    from tests.test_moe_mla import ARCH as MLA_ARCH
    from detectmateservice_tpu.models.moe_mla import MoEMLAArch

    assert not MoEMLAArch.from_mapping(MLA_ARCH).expert_spec.shared_gate


# -- through JaxScorerDetector ----------------------------------------------

def detector_config(**overrides):
    base = {
        "method_type": "jax_scorer", "auto_config": False,
        "model": "moe_delta", "arch": arch_with(**SHARE),
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


def test_detector_life_fit_threshold_checkpoint_restore_and_counters(
        tmp_path):
    det = JaxScorerDetector(config=detector_config())
    train = _msgs(32)
    assert det.process_batch(train) == []
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
    held = (chosen >= 4) & (chosen < 8)
    want = [int((chosen >= 0).sum()), int(held.sum()),
            sum(max(int((layer == e).sum()) for e in range(4, 8))
                for layer in chosen)]
    assert [_sample(det, n) - b for n, b in zip(names, before)] == want
    state = det._bucket_state()
    assert "ragged_dot, 4 of 16 experts from 4" in state["expert_route"]["32"]
    assert state["attn_route"]["32"] == "einsum"
    assert state["delta_route"]["32"] == "chunked 32"
    assert state["conv_route"] == {}
    assert state["head_route"]["32"] == "einsum"
    info = det.device_info()
    assert info["scorer"]["model"] == "moe_delta"
    assert info["scorer"]["arch"]["full_attention_interval"] == 4
    assert info["host_twin"]["state"] == "off"
    scores = det.score_tokens(tokens)
    want_scores = reference.score(as_numpy(det._exec.params), tokens,
                                  {"arch": det.config.arch})
    assert np.abs(scores - want_scores).max() < 1e-4
    # the fitted threshold is what the reference's scores of the training
    # lines give under the detector's rule (mean + threshold_sigma x std)
    train_tokens, _ = det._featurize_raw_batch(train)
    ref_train = reference.score(as_numpy(det._exec.params), train_tokens,
                                {"arch": det.config.arch})
    own_train = det.score_tokens(train_tokens)
    assert np.abs(own_train - ref_train).max() < 1e-4
    assert det._threshold == pytest.approx(
        ref_train.mean() + det.config.threshold_sigma * ref_train.std(),
        abs=1e-3)
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


def test_other_families_refuse_an_arch_and_unknown_models_name_this_one():
    from tests.test_jax_scorer import scorer_config

    with pytest.raises(LibraryError, match="moe_delta"):
        JaxScorerDetector(config=scorer_config(arch=ARCH))
    with pytest.raises(LibraryError, match="moe_delta"):
        JaxScorerDetector(config=scorer_config(model="nope"))


def test_a_bad_arch_fails_at_build_before_any_trace():
    det = JaxScorerDetector(config=detector_config(
        arch=arch_with(hidden_act="gelu")))
    with pytest.raises(LibraryError, match="hidden_act"):
        det._ensure_scorer()


def test_no_branch_on_the_familys_name_outside_the_families_table():
    import re

    for name in ("jax_scorer.py", "device_executor.py"):
        path = os.path.join(REPO, "detectmateservice_tpu", "library",
                            "detectors", name)
        with open(path, encoding="utf-8") as fh:
            code = [line.split("#", 1)[0] for line in fh
                    if not line.lstrip().startswith("#")]
        named = [line for line in code
                 if re.search(r"[\"']moe_delta[\"']", line)]
        assert not named, named
