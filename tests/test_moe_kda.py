"""The vector-decay delta rule, gated latent attention, group-routed
sparse-expert scorer (models/moe_kda.py over ops/deltarule.py's
``kda_delta_rule``, ops/attention.py's per-head norms and head-wise gate,
ops/experts.py's grouped choice and models/blocks.py's expert layer) at a tiny
size on the CPU, held to the benchmark's plain reference
(benchmark/reference/moe_kda.py, which imports nothing of models/ or ops/ and
runs the recurrence as a scan, attention as a dense softmax and the grouped
choice by sorting): scores and per-position NLLs in float32 and bfloat16,
every kind of layer alone, the chunk length, the fit, **the shares add up**
(four tensor shares times the expert groups of one delta-rule, one
latent-attention and one expert layer, what every chip computes alike counted
once, against the uncut reference layer), ``arch``'s refusals and
``share_of`` against the catalog's row, causality, the untied head, the
routing counters, and the whole detector life (fit, threshold, checkpoint,
restore)."""
import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.reference import moe_kda as reference  # noqa: E402
from detectmateservice_tpu.library.common.core import LibraryError  # noqa: E402
from detectmateservice_tpu.library.detectors import JaxScorerDetector  # noqa: E402
from detectmateservice_tpu.models import blocks, moe_kda  # noqa: E402
from detectmateservice_tpu.models.moe_kda import (  # noqa: E402
    Block, MoEKDAArch, MoEKDAConfig, MoEKDAScorer)

VOCAB, SEQ = 64, 32
ARCH = dict(
    hidden_size=64, num_hidden_layers=4, layer_group_size=3,
    first_k_dense_replace=1, num_attention_heads=4, head_dim=16,
    short_conv_kernel_size=4, kda_lower_bound=-5, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=24, rope_theta=6000000,
    intermediate_size=96, moe_intermediate_size=24,
    moe_shared_expert_intermediate_size=40, num_experts=16,
    num_experts_per_tok=3, n_group=4, topk_group=2,
    routed_scaling_factor=2.5, rms_norm_eps=1e-6, router_experts=16,
    expert_offset=0)
SHARE = dict(num_experts=4, expert_offset=4)
KINDS = [("kda", "ffn"), ("kda", "moe"), ("attn", "moe"), ("kda", "moe")]
# what a published config.json carries beside the keys the family reads
PUBLISHED_EXTRAS = dict(
    hidden_act="silu", use_bias=False, use_qkv_bias=False,
    tie_word_embeddings=False, norm_topk_prob=True, num_shared_experts=1,
    moe_router_enable_expert_bias=True, score_function="sigmoid",
    scoring_func="sigmoid", topk_method="noaux_tc", rope_interleave=True,
    rope_scaling=None, q_lora_rank=None, use_qk_norm=True,
    gated_attention_proj_granularity_type="head_wise", kda_safe_gate=True,
    linear_silu=True, no_kda_lora=True, use_kda_lora=False,
    group_norm_size=1, use_nGPT=False, value_norm=False, up_proj_norm=False,
    scale_router_input=False, use_mla_nope=False, mtp_use_kda=False,
    num_kv_heads_for_linear_attn=0, num_key_value_heads=4,
    expert_swiglu_limit_list=[0, 0, 0, 0],
    share_expert_swiglu_limit_list=[0, 0, 0, 0], model_type="bailing_hybrid",
    max_position_embeddings=262144, vocab_size=VOCAB, max_window_layers=20,
    mtp_loss_scaling_factor=0, num_nextn_predict_layers=1, seq_aux=True,
    qk_head_dim=24, rotary_dim=8, partial_rotary_factor=0.5)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def arch_with(**changes):
    return dict(ARCH, **changes)


def make_scorer(arch=None, dtype=jnp.float32, init=0.1, seed=0, **config):
    """A seeded scorer; ``init`` is wide so that the blocks, not the
    embedding, decide the scores at this size."""
    scorer = MoEKDAScorer(MoEKDAConfig(
        arch=MoEKDAArch.from_mapping(arch or ARCH), vocab_size=VOCAB,
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
    # is off by under 0.01 nats at the median and by more where a token's
    # third expert, or a group's place among the kept, changed (routing is
    # discontinuous), a full line's score by under 0.035 over three seeds
    # and the three-token line's by 0.089 on this one (one such token is a
    # third of its score). The float8 control's lines read 0.03-0.14 and
    # its worst 0.11-0.28: the limits lie between
    (jnp.bfloat16, 0.03, 0.1),
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
    line_gaps = np.abs(np.asarray(scores) - want)
    assert float(line_gaps.max()) < score_tol
    assert float(line_gaps[(tokens != 0).sum(-1) >= 16].max()) < 0.4 * score_tol
    assert float(jnp.abs(nlls[-1]).max()) == 0.0       # the all-PAD line
    assert np.isfinite(np.asarray(scores)).all()
    assert np.allclose(np.asarray(scorer.score(params, tokens)),
                       np.asarray(scores))
    assert scorer.attn_routes == {8: "einsum"}
    assert scorer.delta_routes == {8: "kda chunked 32/8"}
    assert scorer.conv_routes == {}


@pytest.mark.parametrize("group,dense,kinds,leaf", [
    (5, 0, [("kda", "moe")] * 2, "A_log"),          # the delta rule alone
    (1, 0, [("attn", "moe")] * 2, "attn_gate"),     # latent attention alone
    (1, 2, [("attn", "ffn")] * 2, "gate_proj"),     # over dense units
    (2, 1, [("kda", "ffn"), ("attn", "moe")], "dt_bias"),
])
def test_every_kind_of_layer_alone_matches_the_reference(group, dense, kinds,
                                                         leaf):
    arch = arch_with(layer_group_size=group, first_k_dense_replace=dense,
                     num_hidden_layers=2, **SHARE)
    assert reference.kinds(arch) == kinds
    assert MoEKDAArch.from_mapping(arch).layer_types == tuple(
        mixer for mixer, _ in kinds)
    scorer, params, _ = make_scorer(arch, init=0.2)
    tokens = make_tokens()
    nlls = scorer._token_nlls(params, tokens)
    want = reference.token_nlls(as_numpy(params), tokens, arch)
    assert float(jnp.abs(nlls - want).max()) < 3e-4
    names = set(params["params"]["layers_0"])
    assert leaf in names and {"input_norm", "post_norm"} < names
    assert ("out_norm" in names) == (kinds[0][0] == "kda")
    assert ("kv_norm" in names) == (kinds[0][0] == "attn")
    assert ("router" in names) == (kinds[0][1] == "moe")
    assert ("down_proj" in names) == (kinds[0][1] == "ffn")


@pytest.mark.parametrize("chunk", [8, 16])
def test_the_chunk_length_moves_no_score(chunk, monkeypatch):
    """A 32-long line in 8- and 16-long chunks, the state carried between
    them, through the whole scorer: the scan's scores to float32's error.
    (The chunk is a static argument of the operation, no key of ``arch``.)"""
    arch = arch_with(**SHARE)
    scorer, params, _ = make_scorer(arch, init=0.2)
    tokens = make_tokens()
    want = reference.score(as_numpy(params), tokens, {"arch": arch})
    whole, _ = scorer._score(params, tokens)
    assert float(np.abs(np.asarray(whole) - want).max()) < 2e-5
    monkeypatch.setattr(moe_kda, "KDA_CHUNK", chunk)
    chunked = MoEKDAScorer(scorer.config)
    scores, _ = chunked._score(params, tokens)
    assert chunked.delta_routes == {8: f"kda chunked {chunk}/8"}
    assert float(np.abs(np.asarray(scores) - want).max()) < 2e-5
    scan = MoEKDAScorer(dataclasses.replace(scorer.config, kda_impl="scan"))
    scores, _ = scan._score(params, tokens)
    assert scan.delta_routes == {8: "kda scan"}
    assert float(np.abs(np.asarray(scores) - want).max()) < 2e-5


def test_a_run_of_like_layers_is_one_scan_and_moves_no_score():
    """Six layers under ``layer_group_size 6``: layers 1-4 are one kind (the
    delta rule over experts) and the scoring call scans one block over
    their stacked leaves (``MoEKDALM._walk``); the fit's path walks them
    one by one. Same scores, same counts, the reference's; the leaves keep
    their per-layer names."""
    arch = arch_with(layer_group_size=6, num_hidden_layers=6, **SHARE)
    scorer, params, _ = make_scorer(arch, init=0.2)
    assert {f"layers_{i}" for i in range(6)} < set(params["params"])
    tokens = make_tokens()
    scanned, counts = scorer._score(params, tokens)
    want = reference.score(as_numpy(params), tokens, {"arch": arch})
    assert float(np.abs(np.asarray(scanned) - want).max()) < 2e-5
    text = jax.jit(scorer._score_impl).lower(params, tokens).as_text()
    assert text.count("stablehlo.while") >= 1
    walked, walked_counts = scorer.model.apply(
        params, jnp.asarray(tokens), False, method="hidden_and_counts")
    run, run_counts = scorer.model.apply(
        params, jnp.asarray(tokens), method="hidden_and_counts")
    assert float(jnp.abs(walked - run).max()) < 1e-4
    assert (np.asarray(walked_counts) == np.asarray(run_counts)).all()
    assert (np.asarray(counts) == np.asarray(run_counts)).all()
    # the fit's logits come from the layers one by one: no loop in the step
    step = jax.jit(scorer._train_impl).lower(
        params, scorer.optimizer.init(params), jax.random.PRNGKey(0),
        jnp.asarray(tokens)).as_text()
    assert "stablehlo.while" not in step


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


def test_a_shares_router_is_not_trained_and_the_mixers_are():
    share, sp, so = make_scorer(arch_with(**SHARE))
    tokens = make_tokens()
    sn, _, _ = share.train_step(sp, so, jax.random.PRNGKey(1), tokens)
    drift = jnp.abs(sn["params"]["layers_1"]["router"]
                    - sp["params"]["layers_1"]["router"]).max()
    assert float(drift) < 1e-7       # AdamW's decay alone touches it
    for layer, leaf in (("layers_0", "conv_weight"), ("layers_0", "A_log"),
                        ("layers_0", "dt_bias"), ("layers_0", "out_norm"),
                        ("layers_0", "input_norm"), ("layers_0", "post_norm"),
                        ("layers_1", "experts_up"),
                        ("layers_1", "experts_down"),
                        ("layers_2", "kv_norm"), ("layers_2", "q_norm"),
                        ("layers_2", "k_norm")):
        assert float(jnp.abs(sn["params"][layer][leaf]
                             - sp["params"][layer][leaf]).max()) > 1e-7, leaf
    for layer, leaf in (("layers_0", "in_proj"), ("layers_0", "b_proj"),
                        ("layers_0", "out_proj"), ("layers_0", "down_proj"),
                        ("layers_1", "shared_up_proj"),
                        ("layers_2", "q_proj"), ("layers_2", "kv_down"),
                        ("layers_2", "kv_up"), ("layers_2", "attn_gate"),
                        ("layers_2", "out_proj")):
        assert float(jnp.abs(sn["params"][layer][leaf]["kernel"]
                             - sp["params"][layer][leaf]["kernel"]
                             ).max()) > 1e-7, leaf
    for layer in ("layers_1", "layers_2", "layers_3"):
        assert float(jnp.abs(sn["params"][layer]["router_bias"]).max()) == 0
    whole, wp, wo = make_scorer()
    wn, _, _ = whole.train_step(wp, wo, jax.random.PRNGKey(1), tokens)
    assert float(jnp.abs(wn["params"]["layers_1"]["router"]
                         - wp["params"]["layers_1"]["router"]).max()) > 1e-6


def test_the_initialisers_are_the_stated_ones():
    _, params, _ = make_scorer()
    p = params["params"]
    for layer in ("layers_0", "layers_1", "layers_2", "layers_3"):
        for norm in ("input_norm", "post_norm"):
            assert float(jnp.abs(p[layer][norm] - 1.0).max()) == 0.0
    assert float(jnp.abs(p["final_norm"] - 1.0).max()) == 0.0
    mixer = p["layers_0"]
    assert float(jnp.abs(mixer["out_norm"] - 1.0).max()) == 0.0
    assert mixer["out_norm"].shape == (16,)          # a head's own norm
    assert float(jnp.abs(mixer["dt_bias"] - 1.0).max()) == 0.0
    assert mixer["dt_bias"].shape == (4, 16) and mixer["A_log"].shape == (4,)
    rates = np.exp(np.asarray(mixer["A_log"]))
    assert (rates >= 1e-3).all() and (rates <= 16.0).all()
    assert set(mixer) == {
        "input_norm", "post_norm", "in_proj", "b_proj", "conv_weight",
        "A_log", "dt_bias", "out_norm", "out_proj", "gate_proj", "up_proj",
        "down_proj"}
    assert mixer["in_proj"]["kernel"].shape == (64, 5 * 64)
    assert mixer["b_proj"]["kernel"].shape == (64, 4)
    assert mixer["conv_weight"].shape == (3 * 64, 4)
    assert mixer["down_proj"]["kernel"].shape == (96, 64)
    attn = p["layers_2"]
    assert set(attn) == {
        "input_norm", "post_norm", "q_proj", "kv_down", "kv_norm", "kv_up",
        "q_norm", "k_norm", "attn_gate", "out_proj", "router", "router_bias",
        "experts_gate", "experts_up", "experts_down", "shared_gate_proj",
        "shared_up_proj", "shared_down_proj"}
    assert attn["q_proj"]["kernel"].shape == (64, 4 * 24)
    assert attn["kv_down"]["kernel"].shape == (64, 24 + 8)
    assert attn["kv_up"]["kernel"].shape == (24, 4 * 32)
    assert attn["attn_gate"]["kernel"].shape == (64, 4)
    assert attn["q_norm"].shape == attn["k_norm"].shape == (24,)
    assert attn["experts_up"].shape == (16, 64, 24)
    assert attn["shared_up_proj"]["kernel"].shape == (64, 40)
    assert float(jnp.abs(attn["router_bias"]).max()) == 0.0


# -- the shares add up ---------------------------------------------------------

# an uncut layer of every kind at a size four chips divide: 8 heads of both
# mixers, 32 experts in 4 groups over the 8 chips of two tensor groups
WHOLE = dict(
    hidden_size=32, num_hidden_layers=1, layer_group_size=6,
    first_k_dense_replace=0, num_attention_heads=8, num_key_value_heads=8,
    head_dim=4, short_conv_kernel_size=4, kda_lower_bound=-5,
    qk_nope_head_dim=4, qk_rope_head_dim=2, v_head_dim=4, kv_lora_rank=12,
    rope_theta=6000000, intermediate_size=24, moe_intermediate_size=12,
    moe_shared_expert_intermediate_size=16, num_experts=32,
    num_experts_per_tok=5, n_group=4, topk_group=2,
    routed_scaling_factor=2.5, rms_norm_eps=1e-6)
TP, EXPERT_GROUPS = 4, 2


def _whole_layer(kind, seed):
    """Seeded leaves of one uncut layer, by the checkpoint's names."""
    rng = np.random.default_rng(seed)
    a = WHOLE
    d, h, hd = a["hidden_size"], a["num_attention_heads"], a["head_dim"]
    nope, rope, dv, rank = (a["qk_nope_head_dim"], a["qk_rope_head_dim"],
                            a["v_head_dim"], a["kv_lora_rank"])
    nrm = lambda *shape: rng.normal(size=shape).astype(np.float32) * 0.3  # noqa: E731
    lay = {"input_norm": 1 + nrm(d), "post_norm": 1 + nrm(d)}
    if kind == "kda":
        lay.update(
            in_proj={"kernel": nrm(d, 5 * h * hd)},
            b_proj={"kernel": nrm(d, h)}, conv_weight=nrm(3 * h * hd, 4),
            A_log=np.log(rng.uniform(0.1, 4.0, size=h)).astype(np.float32),
            dt_bias=1 + nrm(h, hd), out_norm=1 + nrm(hd),
            out_proj={"kernel": nrm(h * hd, d)})
    elif kind == "attn":
        lay.update(
            q_proj={"kernel": nrm(d, h * (nope + rope))},
            kv_down={"kernel": nrm(d, rank + rope)}, kv_norm=1 + nrm(rank),
            kv_up={"kernel": nrm(rank, h * (nope + dv))},
            q_norm=1 + nrm(nope + rope), k_norm=1 + nrm(nope + rope),
            attn_gate={"kernel": nrm(d, h)},
            out_proj={"kernel": nrm(h * dv, d)})
    else:
        e_all, m, ms = (a["num_experts"], a["moe_intermediate_size"],
                        a["moe_shared_expert_intermediate_size"])
        lay.update(router=nrm(d, e_all) * 3,
                   router_bias=np.zeros(e_all, np.float32),
                   experts_gate=nrm(e_all, d, m), experts_up=nrm(e_all, d, m),
                   experts_down=nrm(e_all, m, d),
                   shared_gate_proj={"kernel": nrm(d, ms)},
                   shared_up_proj={"kernel": nrm(d, ms)},
                   shared_down_proj={"kernel": nrm(ms, d)})
    return lay


def _share_of_layer(kind, lay, rank, held=None, offset=0):
    """The leaves chip ``rank`` of the tensor group holds of the uncut
    ``lay``: its heads' columns of the input projections and rows of the
    output projection, the experts ``offset .. offset + held - 1``; what
    every chip holds alike (norms, kv_down and its norm, the head norms'
    weights, the router, the shared expert) whole."""
    a = WHOLE
    h, hd = a["num_attention_heads"], a["head_dim"]
    mine = slice(rank * h // TP, (rank + 1) * h // TP)

    def heads(t, axis, per_head, blocks=1):
        """This chip's heads of ``t``'s ``axis``, which holds ``blocks``
        blocks of ``h`` heads of ``per_head`` lanes."""
        shape = t.shape[:axis] + (blocks, h, per_head) + t.shape[axis + 1:]
        cut = np.take(t.reshape(shape), np.arange(mine.start, mine.stop),
                      axis=axis + 1)
        return cut.reshape(t.shape[:axis] + (-1,) + t.shape[axis + 1:])

    if kind == "kda":
        return dict(
            lay, in_proj={"kernel": heads(lay["in_proj"]["kernel"], 1, hd, 5)},
            b_proj={"kernel": lay["b_proj"]["kernel"][:, mine]},
            conv_weight=heads(lay["conv_weight"], 0, hd, 3),
            A_log=lay["A_log"][mine], dt_bias=lay["dt_bias"][mine],
            out_proj={"kernel": heads(lay["out_proj"]["kernel"], 0, hd)})
    if kind == "attn":
        nope, rope, dv = (a["qk_nope_head_dim"], a["qk_rope_head_dim"],
                          a["v_head_dim"])
        return dict(
            lay,
            q_proj={"kernel": heads(lay["q_proj"]["kernel"], 1, nope + rope)},
            kv_up={"kernel": heads(lay["kv_up"]["kernel"], 1, nope + dv)},
            attn_gate={"kernel": lay["attn_gate"]["kernel"][:, mine]},
            out_proj={"kernel": heads(lay["out_proj"]["kernel"], 0, dv)})
    held_experts = slice(offset, offset + held)
    return dict(lay, **{name: lay[name][held_experts] for name in (
        "experts_gate", "experts_up", "experts_down")})


def _block_addends(arch, lay, x, tokens):
    """What the program's Block of this share adds to the residual: (the
    mixer's addend, the feed-forward's for the mixer's output as its
    input). The block is built with the leaves of both sub-layers; a test
    reads the one it is about."""
    cfg = MoEKDAConfig(arch=MoEKDAArch.from_mapping(arch), vocab_size=VOCAB,
                       seq_len=x.shape[1], dtype=jnp.float32,
                       platform="cpu")
    flat = x.reshape(-1, x.shape[-1])
    with jax.default_matmul_precision("highest"):
        out, _ = Block(cfg, layer=0).apply(
            {"params": lay}, flat, tokens != 0, tokens != 0)
    return np.asarray(out - flat).reshape(x.shape)


def _zero_ffn(lay):
    """``lay`` with an expert layer that adds nothing (no held expert's and
    no shared expert's down projection), so that a block's addend is its
    mixer's alone."""
    moe = _whole_layer("moe", seed=1)
    moe["experts_down"] = moe["experts_down"][:1] * 0
    moe["experts_gate"], moe["experts_up"] = (moe["experts_gate"][:1],
                                              moe["experts_up"][:1])
    moe["shared_down_proj"] = {"kernel": moe["shared_down_proj"]["kernel"] * 0}
    return dict({k: v for k, v in moe.items()
                 if k not in ("input_norm", "post_norm")}, **lay)


@pytest.mark.parametrize("kind", ["kda", "attn", "moe"])
def test_the_shares_add_up_to_the_uncut_reference_layer(kind):
    """The guide's share test for the tensor share: the addends of the four
    chips of a tensor group (and, in an expert layer, of the eight chips of
    two such groups, every chip its own four experts) with what every chip
    computes alike counted once — the shared expert, which every one of
    the eight holds whole; ``kv_down``, the router — equal the uncut
    reference's layer."""
    rng = np.random.default_rng(3)
    lines, seq = 3, 16
    tokens = rng.integers(3, VOCAB, size=(lines, seq)).astype(np.int32)
    tokens[1, 11:] = 0
    d = WHOLE["hidden_size"]
    x = rng.normal(size=(lines, seq, d)).astype(np.float32)
    lay = _whole_layer(kind, seed=7)
    group = 1 if kind == "attn" else 6
    whole_arch = dict(WHOLE, layer_group_size=group, router_experts=32)
    inp_seen = (tokens != 0)[:, None, None, :] & np.tril(
        np.ones((seq, seq), bool))[None, None]
    norm = "post_norm" if kind == "moe" else "input_norm"
    with jax.default_matmul_precision("highest"):
        y = reference._norm(jnp.asarray(x), jnp.asarray(lay[norm]), 1e-6)
        want, _ = reference.mixer(lay, kind, y, whole_arch,
                                  jnp.asarray(inp_seen))
    want = np.asarray(want)
    keep = (tokens != 0)[..., None]

    def addend(arch, leaves):
        """The sub-layer's addend through the program's Block: a mixer's
        over an expert layer that adds nothing; an expert layer's behind a
        mixer that adds nothing (a delta rule with a zero output
        projection), so that its input is ``x`` itself."""
        if kind == "moe":
            mixer = _whole_layer("kda", seed=2)
            h = arch["num_attention_heads"] * arch["head_dim"]
            mixer = {
                "in_proj": {"kernel": mixer["in_proj"]["kernel"][:, :5 * h]},
                "b_proj": {"kernel": mixer["b_proj"]["kernel"][
                    :, :arch["num_attention_heads"]]},
                "conv_weight": mixer["conv_weight"][:3 * h],
                "A_log": mixer["A_log"][:arch["num_attention_heads"]],
                "dt_bias": mixer["dt_bias"][:arch["num_attention_heads"]],
                "out_norm": mixer["out_norm"],
                "out_proj": {"kernel": np.zeros((h, d), np.float32)}}
            return _block_addends(arch, dict(mixer, **leaves), x,
                                  tokens) * keep
        return _block_addends(dict(arch, num_experts=1, expert_offset=0),
                              _zero_ffn(leaves), x, tokens)

    if kind == "moe":
        want = want * keep                        # PAD tokens are not routed
    # the uncut layer through the program, one chip holding all of it
    uncut = addend(whole_arch, lay)
    np.testing.assert_allclose(uncut, want, rtol=2e-4, atol=2e-4)
    total = np.zeros_like(want)
    held = WHOLE["num_experts"] // (TP * EXPERT_GROUPS)
    for expert_group in range(EXPERT_GROUPS if kind == "moe" else 1):
        for rank in range(TP):
            offset = (expert_group * TP + rank) * held
            arch = MoEKDAArch.share_of(
                dict(WHOLE, layer_group_size=group), tensor_parallel=TP,
                tensor_rank=rank, experts_held=held, expert_offset=offset)
            assert (arch["num_attention_heads"], arch["num_key_value_heads"],
                    arch["kv_lora_rank"], arch["intermediate_size"],
                    arch["moe_shared_expert_intermediate_size"],
                    arch["num_experts"], arch["router_experts"]) == (
                2, 2, 12, 24, 16, held, 32)
            share = _share_of_layer(kind, lay, rank, held, offset)
            part = addend(arch, share)
            if kind == "moe" and (expert_group, rank) != (0, 0):
                # the shared expert was counted with the first chip: every
                # chip computes it alike
                alone = dict(share, experts_down=share["experts_down"] * 0)
                part = part - addend(arch, alone)
            total += part
    np.testing.assert_allclose(total, want, rtol=3e-4, atol=3e-4)
    # no share alone is the layer
    assert np.abs(part - want).max() > 1e-2


def test_share_of_is_the_catalogs_row_cut_and_refuses_what_does_not_divide():
    if not os.path.exists(CATALOG):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(CATALOG, encoding="utf-8") as fh:
        rows = [json.loads(line) for line in fh if line.strip()]
    (row,) = [r for r in rows if r["name"] == "Ling-3.0-flash"]
    published = row["config"]
    # the published file as it is names layers whose gated units clamp
    # (4 from layer 35, 5 from 34): refused by name, not run without
    with pytest.raises(ValueError, match="expert_swiglu_limit_list"):
        MoEKDAArch.from_mapping(published)
    # without the clamp: 42 layers, 32 heads, every expert held
    published = dict(published, expert_swiglu_limit_list=[0] * 42,
                     share_expert_swiglu_limit_list=[0] * 42)
    whole = MoEKDAArch.from_mapping(published)
    assert (whole.num_hidden_layers, whole.num_attention_heads,
            whole.num_experts, whole.router_experts, whole.tensor_parallel,
            whole.first_k_dense_replace) == (42, 32, 512, 512, 1, 2)
    assert whole.layer_types.count("attn") == 7
    assert [i for i, kind in enumerate(whole.layer_types)
            if kind == "attn"] == [5, 11, 17, 23, 29, 35, 41]
    share = MoEKDAArch.share_of(
        published, tensor_parallel=4, tensor_rank=0, experts_held=8,
        expert_offset=0, num_hidden_layers=7, first_k_dense_replace=1)
    typed = MoEKDAArch.from_mapping(share)
    assert (typed.num_hidden_layers, typed.first_k_dense_replace,
            typed.num_attention_heads, typed.num_experts,
            typed.router_experts, typed.expert_offset, typed.tensor_parallel,
            typed.tensor_rank) == (7, 1, 8, 8, 512, 0, 4, 0)
    assert typed.layer_types == ("kda",) * 5 + ("attn", "kda")
    assert share["expert_swiglu_limit_list"] == [0] * 7
    # every width is the published one
    for key in ("hidden_size", "head_dim", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "kv_lora_rank",
                "intermediate_size", "moe_intermediate_size",
                "moe_shared_expert_intermediate_size", "num_experts_per_tok",
                "n_group", "topk_group"):
        assert share[key] == published[key], key
    # the layers the clamp is published for are refused by name, not run
    with pytest.raises(ValueError, match="expert_swiglu_limit_list"):
        MoEKDAArch.from_mapping(MoEKDAArch.share_of(
            dict(published, expert_swiglu_limit_list=[
                0] * 6 + [4] + [0] * 35), tensor_parallel=4,
            num_hidden_layers=7))
    for key, count in (("num_attention_heads", 30),
                       ("num_key_value_heads", 30)):
        with pytest.raises(ValueError, match=f"{key} {count} does not"):
            MoEKDAArch.share_of(dict(published, **{key: count}),
                                tensor_parallel=4)
    with pytest.raises(ValueError, match="held experts"):
        MoEKDAArch.from_mapping(MoEKDAArch.share_of(
            published, tensor_parallel=4, experts_held=8, expert_offset=508))
    one = MoEKDAArch.share_of(published, tensor_parallel=1)
    assert MoEKDAArch.from_mapping(one) == whole
    # a share counts heads and cuts no width
    assert MoEKDAArch.share_of(
        dict(published, moe_shared_expert_intermediate_size=20),
        tensor_parallel=4)["moe_shared_expert_intermediate_size"] == 20


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
    # the state and attention carry it to every later position of the line
    assert (np.abs(before[0, t + 1:] - after[0, t + 1:]) > 1e-7).all()
    assert np.allclose(before[1:], after[1:], atol=1e-6)


def test_the_delta_rule_alone_carries_a_change_past_the_convolutions_taps():
    """Without attention a change at t still reaches positions the four
    taps do not: the state is carried over positions (and decays on the
    way: at this initialisation most lanes sit near the bound and forget
    within a few positions, some hold on)."""
    arch = arch_with(layer_group_size=5, num_hidden_layers=1,
                     first_k_dense_replace=1)
    scorer, params, _ = make_scorer(arch, init=0.3)
    tokens = make_tokens()
    t = 3
    changed = tokens.copy()
    changed[0, t] = (changed[0, t] + 7) % (VOCAB - 3) + 3
    before = np.asarray(scorer._token_nlls(params, tokens))
    after = np.asarray(scorer._token_nlls(params, changed))
    moved = np.flatnonzero(np.abs(before[0] - after[0]) > 1e-7)
    assert (before[0, :t] == after[0, :t]).all()         # causal to the bit
    assert moved.min() == t and moved.max() > t + 1 + 3
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
    chosen = np.asarray(chosen)                     # [E layers, N, S, K]
    assert chosen.shape[0] == 3                     # three of the four layers
    held = (chosen >= 4) & (chosen < 8)
    busiest = sum(max(int((layer == e).sum()) for e in range(4, 8))
                  for layer in chosen)
    assert [int(c) for c in counts] == [int((chosen >= 0).sum()),
                                        int(held.sum()), busiest]
    assert int(counts[0]) == int((tokens != 0).sum()) * 3 * 3
    # a token's experts lie in at most topk_group of the groups of four
    real = chosen[chosen[..., 0] >= 0]
    assert max(len(set(row // 4)) for row in real) <= 2


@pytest.mark.parametrize("change,named", [
    ({"use_nGPT": True}, "use_nGPT"),
    ({"value_norm": True}, "value_norm"),
    ({"up_proj_norm": True}, "up_proj_norm"),
    ({"scale_router_input": True}, "scale_router_input"),
    ({"use_mla_nope": True}, "use_mla_nope"),
    ({"use_kda_lora": True}, "use_kda_lora"),
    ({"no_kda_lora": False}, "no_kda_lora"),
    ({"mtp_use_kda": True}, "mtp_use_kda"),
    ({"rope_scaling": {"type": "yarn"}}, "rope_scaling"),
    ({"q_lora_rank": 1536}, "q_lora_rank"),
    ({"num_kv_heads_for_linear_attn": 2}, "num_kv_heads_for_linear_attn"),
    ({"kda_safe_gate": False}, "kda_safe_gate"),
    ({"linear_silu": False}, "linear_silu"),
    ({"group_norm_size": 4}, "group_norm_size"),
    ({"use_qk_norm": False}, "use_qk_norm"),
    ({"gated_attention_proj_granularity_type": "element_wise"},
     "gated_attention_proj_granularity_type"),
    ({"rope_interleave": False}, "rope_interleave"),
    ({"score_function": "softmax"}, "score_function"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"num_shared_experts": 2}, "num_shared_experts"),
    ({"moe_router_enable_expert_bias": False},
     "moe_router_enable_expert_bias"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"use_bias": True}, "use_bias"),
    ({"use_qkv_bias": True}, "use_qkv_bias"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"expert_swiglu_limit_list": [0, 0, 4, 0]}, "expert_swiglu_limit_list"),
    ({"share_expert_swiglu_limit_list": [0, 5, 0, 0]},
     "share_expert_swiglu_limit_list"),
    ({"expert_swiglu_limit_list": [0, 0]}, "expert_swiglu_limit_list"),
    ({"num_key_value_heads": 2}, "num_key_value_heads"),
    ({"bogus": 1}, "bogus"),
    ({"head_dim": None}, "head_dim"),
    ({"layer_group_size": 0}, "layer_group_size"),
    ({"first_k_dense_replace": 5}, "first_k_dense_replace"),
    ({"qk_rope_head_dim": 7}, "qk_rope_head_dim"),
    ({"short_conv_kernel_size": 0}, "short_conv_kernel_size"),
    ({"kda_lower_bound": 0.5}, "kda_lower_bound"),
    ({"kda_lower_bound": -5.5}, "lower_bound -5.5"),
    ({"n_group": 3}, "n_group"),
    ({"topk_group": 5}, "topk_group"),
    ({"n_group": 8, "topk_group": 1}, "num_experts_per_tok"),
    ({"expert_offset": 14, "num_experts": 4}, "held experts"),
    ({"num_experts_per_tok": 17}, "num_experts_per_tok"),
    ({"tensor_parallel": 4, "tensor_rank": 4}, "tensor_rank"),
    ({"tensor_rank": -1}, "tensor_rank"),
])
def test_arch_refuses_by_name(change, named):
    with pytest.raises(ValueError, match=named):
        MoEKDAArch.from_mapping(arch_with(**change))


def test_a_bound_the_closed_form_cannot_hold_is_refused_at_build():
    """``kda_lower_bound`` is a published key; what a sub-block's eight
    positions at a gate further down would do to float32 is refused by
    name when the scorer is built, before anything is traced."""
    for bound in (-5, -1, -0.5):
        MoEKDAArch.from_mapping(arch_with(kda_lower_bound=bound))
    det = JaxScorerDetector(config=detector_config(
        arch=arch_with(kda_lower_bound=-8, **SHARE)))
    with pytest.raises(LibraryError, match="lower_bound -8"):
        det._ensure_scorer()


def test_arch_takes_a_published_config_as_it_is():
    published = dict(ARCH, **PUBLISHED_EXTRAS)
    published.pop("router_experts")
    published.pop("expert_offset")
    arch = MoEKDAArch.from_mapping(published)
    assert arch.router_experts == arch.num_experts == 16
    assert (arch.tensor_parallel, arch.tensor_rank) == (1, 0)
    assert arch.layer_types == ("kda", "kda", "attn", "kda")
    assert reference.kinds(ARCH) == KINDS
    spec = arch.expert_spec
    assert (spec.shared, spec.shared_width, spec.shared_gate, spec.gated,
            spec.latent, spec.norm_eps, spec.scoring_func, spec.top_k,
            spec.scaling, spec.width, spec.n_group, spec.topk_group) == (
        1, 40, False, True, 0, 1e-20, "sigmoid", 3, 2.5, 24, 4, 2)
    # and the share of it, by the keys the detector is given
    share = MoEKDAArch.from_mapping(MoEKDAArch.share_of(
        published, tensor_parallel=2, tensor_rank=1, experts_held=4,
        expert_offset=8, num_hidden_layers=3, first_k_dense_replace=0))
    assert (share.num_attention_heads, share.num_experts,
            share.router_experts, share.expert_offset,
            share.num_hidden_layers, share.first_k_dense_replace,
            share.moe_shared_expert_intermediate_size, share.tensor_parallel,
            share.tensor_rank) == (2, 4, 16, 8, 3, 0, 40, 2, 1)


def test_the_family_calls_the_shared_blocks_and_operations():
    assert moe_kda.expert_layer is blocks.expert_layer
    assert moe_kda.rms_norm is blocks.rms_norm
    assert moe_kda.gated_unit is blocks.gated_unit
    assert moe_kda.causal_stack is blocks.causal_stack
    assert issubclass(MoEKDAScorer, blocks.ExpertLMScorer)
    from detectmateservice_tpu.models import moe_delta
    from detectmateservice_tpu.ops import deltarule, shortconv

    assert moe_kda.causal_conv_silu is shortconv.causal_conv_silu
    assert moe_kda._a_log_init is moe_delta._a_log_init
    assert moe_kda.kda_delta_rule is deltarule.kda_delta_rule
    # the other families' specs route without groups
    from tests.test_moe_delta import ARCH as DELTA_ARCH
    from tests.test_moe_mla import ARCH as MLA_ARCH
    from detectmateservice_tpu.models.moe_delta import MoEDeltaArch
    from detectmateservice_tpu.models.moe_mla import MoEMLAArch

    for spec in (MoEMLAArch.from_mapping(MLA_ARCH).expert_spec,
                 MoEDeltaArch.from_mapping(DELTA_ARCH).expert_spec):
        assert (spec.n_group, spec.topk_group) == (1, 1)
    # and latent attention's family takes the groups where a config has them
    grouped = MoEMLAArch.from_mapping(dict(MLA_ARCH, n_group=4, topk_group=2))
    assert (grouped.expert_spec.n_group,
            grouped.expert_spec.topk_group) == (4, 2)


def test_moe_mla_routes_group_first_where_its_config_says_so():
    """``n_group`` / ``topk_group`` left ``moe_mla``'s one-value keys: a
    latent-attention model whose published router limits the choice to
    groups runs, held to the ``moe_mla`` reference's experts by the counts
    (a token's experts lie in at most ``topk_group`` groups)."""
    from tests.test_moe_mla import ARCH as MLA_ARCH
    from detectmateservice_tpu.models.moe_mla import (
        MoEMLAArch, MoEMLAConfig, MoEMLAScorer)

    def counts(**groups):
        scorer = MoEMLAScorer(MoEMLAConfig(
            arch=MoEMLAArch.from_mapping(dict(MLA_ARCH, **groups)),
            vocab_size=VOCAB, seq_len=SEQ, dtype=jnp.float32,
            initializer_range=0.2))
        params, _ = scorer.init(jax.random.PRNGKey(0))
        return scorer._score(params, make_tokens())

    plain, plain_counts = counts()
    one, one_counts = counts(n_group=1, topk_group=1)
    assert (np.asarray(plain) == np.asarray(one)).all()
    assert (np.asarray(plain_counts) == np.asarray(one_counts)).all()
    grouped, grouped_counts = counts(n_group=4, topk_group=1)
    assert np.isfinite(np.asarray(grouped)).all()
    assert int(grouped_counts[0]) == int(plain_counts[0])
    assert np.abs(np.asarray(grouped) - np.asarray(plain))[:-1].max() > 1e-4


# -- through JaxScorerDetector ----------------------------------------------

def detector_config(**overrides):
    base = {
        "method_type": "jax_scorer", "auto_config": False,
        "model": "moe_kda", "arch": arch_with(**SHARE),
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
    assert state["delta_route"]["32"] == "kda chunked 32/8"
    assert state["conv_route"] == {}
    assert state["head_route"]["32"] == "einsum"
    info = det.device_info()
    assert info["scorer"]["model"] == "moe_kda"
    assert info["scorer"]["arch"]["layer_group_size"] == 3
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

    with pytest.raises(LibraryError, match="moe_kda"):
        JaxScorerDetector(config=scorer_config(arch=ARCH))
    with pytest.raises(LibraryError, match="moe_kda"):
        JaxScorerDetector(config=scorer_config(model="nope"))


def test_a_bad_arch_fails_at_build_before_any_trace():
    det = JaxScorerDetector(config=detector_config(
        arch=arch_with(use_nGPT=True)))
    with pytest.raises(LibraryError, match="use_nGPT"):
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
                 if re.search(r"[\"']moe_kda[\"']", line)]
        assert not named, named
