"""The state-space, grouped-query-attention, latent-sparse-expert scorer
(models/moe_ssm.py, models/blocks.py's latent and non-gated expert layer,
ops/ssd.py, ops/shortconv.py's biased convolution, ops/attention.py's
grouped-query form without rotary positions) at a tiny size on the CPU, held
to the benchmark's plain reference (benchmark/reference/moe_ssm.py, which
imports nothing of models/ or ops/ and runs the recurrence as a scan):
scores and per-position NLLs in float32 and bfloat16, every kind of layer
alone, the chunk length, the fit, **the shares add up** (eight tensor shares
times the expert shares of one M, one * and one E layer, what every chip
computes alike counted once, against the uncut reference layer), ``arch``'s
refusals and ``share_of``, causality, the untied head, the routing counters,
and the whole detector life (fit, threshold, checkpoint, restore)."""
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

from benchmark.reference import moe_ssm as reference  # noqa: E402
from detectmateservice_tpu.library.common.core import LibraryError  # noqa: E402
from detectmateservice_tpu.library.detectors import JaxScorerDetector  # noqa: E402
from detectmateservice_tpu.models import blocks, moe_ssm  # noqa: E402
from detectmateservice_tpu.models.moe_ssm import (  # noqa: E402
    Block, MoESSMArch, MoESSMConfig, MoESSMScorer)

VOCAB, SEQ = 64, 32
ARCH = dict(
    hidden_size=64, num_hidden_layers=4, hybrid_override_pattern="ME*E",
    mamba_num_heads=4, mamba_head_dim=16, n_groups=2, ssm_state_size=8,
    conv_kernel=4, chunk_size=128, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, n_routed_experts=16,
    num_experts_per_tok=3, moe_intermediate_size=48, moe_latent_size=32,
    moe_shared_expert_intermediate_size=40, routed_scaling_factor=2.5,
    layer_norm_epsilon=1e-5, router_experts=16, expert_offset=0)
SHARE = dict(n_routed_experts=4, expert_offset=4)
# what a published config.json carries beside the keys the family reads
PUBLISHED_EXTRAS = dict(
    attention_bias=False, mamba_proj_bias=False, mlp_bias=False,
    use_bias=False, use_conv_bias=True, mamba_hidden_act="silu",
    mlp_hidden_act="relu2", n_group=1, topk_group=1, n_shared_experts=1,
    norm_topk_prob=True, tie_word_embeddings=False, sliding_window=None,
    norm_eps=1e-5, model_type="nemotron_h", max_position_embeddings=262144,
    vocab_size=VOCAB, intermediate_size=48, expand=2, rope_theta=10000,
    partial_rotary_factor=1, num_logits_to_keep=1,
    num_nextn_predict_layers=1, mtp_hybrid_override_pattern="*E",
    moe_shared_expert_overlap=False, rescale_prenorm_residual=True,
    residual_in_fp32=False, use_mamba_kernels=True, time_step_floor=1e-4,
    time_step_max=0.1, time_step_min=0.001)


def arch_with(**changes):
    return dict(ARCH, **changes)


def make_scorer(arch=None, dtype=jnp.float32, init=0.1, seed=0, **config):
    """A seeded scorer; ``init`` is wide so that the blocks, not the
    embedding, decide the scores at this size."""
    scorer = MoESSMScorer(MoESSMConfig(
        arch=MoESSMArch.from_mapping(arch or ARCH), vocab_size=VOCAB,
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
    # third expert changed (routing is discontinuous), a line's score by
    # hundredths. The tolerances lie between that and the float8 control's
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
    assert scorer.conv_routes == {} and scorer.delta_routes == {}


@pytest.mark.parametrize("pattern,kinds,leaf", [
    ("MM", ("ssm",) * 2, "A_log"),               # the state space alone
    ("**", ("attn",) * 2, "qkv_proj"),           # attention alone
    ("EE", ("moe",) * 2, "latent_in"),           # the experts alone
    ("MEMEMEM*EME", ("ssm", "moe") * 3 + ("ssm", "attn", "moe", "ssm",
                                          "moe"), "conv_bias"),
])
def test_every_kind_of_layer_alone_matches_the_reference(pattern, kinds,
                                                         leaf):
    arch = arch_with(hybrid_override_pattern=pattern,
                     num_hidden_layers=len(pattern), **SHARE)
    assert MoESSMArch.from_mapping(arch).layer_types == kinds
    scorer, params, _ = make_scorer(arch, init=0.2)
    tokens = make_tokens()
    nlls = scorer._token_nlls(params, tokens)
    want = reference.token_nlls(as_numpy(params), tokens, arch)
    assert float(jnp.abs(nlls - want).max()) < 3e-4
    assert leaf in params["params"]["layers_0"]
    # one sub-layer a layer: a single norm, and only its kind's leaves
    names = set(params["params"]["layers_0"])
    assert "norm" in names
    assert ("router" in names) == (kinds[0] == "moe")
    assert ("out_norm" in names) == (kinds[0] == "ssm")


@pytest.mark.parametrize("chunk", [8, 16])
def test_the_chunk_length_moves_no_score(chunk):
    """A 32-long line in 8- and 16-long chunks, the state carried between
    them, through the whole scorer: the scan's scores to float32's error."""
    arch = arch_with(**SHARE)
    scorer, params, _ = make_scorer(arch, init=0.2)
    tokens = make_tokens()
    want = reference.score(as_numpy(params), tokens, {"arch": arch})
    chunked = MoESSMScorer(dataclasses.replace(
        scorer.config, arch=MoESSMArch.from_mapping(
            arch_with(chunk_size=chunk, **SHARE))))
    scores, _ = chunked._score(params, tokens)
    assert float(np.abs(np.asarray(scores) - want).max()) < 2e-5
    whole, _ = scorer._score(params, tokens)
    assert float(np.abs(np.asarray(whole) - want).max()) < 2e-5


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
    for layer, leaf in (("layers_0", "conv_weight"), ("layers_0", "conv_bias"),
                        ("layers_0", "A_log"), ("layers_0", "dt_bias"),
                        ("layers_0", "D"), ("layers_0", "out_norm"),
                        ("layers_0", "norm"), ("layers_1", "experts_up"),
                        ("layers_1", "experts_down"), ("layers_3", "norm")):
        assert float(jnp.abs(sn["params"][layer][leaf]
                             - sp["params"][layer][leaf]).max()) > 1e-7, leaf
    for layer, leaf in (("layers_0", "in_proj"), ("layers_0", "out_proj"),
                        ("layers_1", "latent_in"), ("layers_1", "latent_out"),
                        ("layers_1", "shared_up_proj"),
                        ("layers_2", "qkv_proj"), ("layers_2", "out_proj")):
        assert float(jnp.abs(sn["params"][layer][leaf]["kernel"]
                             - sp["params"][layer][leaf]["kernel"]
                             ).max()) > 1e-7, leaf
    for layer in ("layers_1", "layers_3"):
        assert float(jnp.abs(sn["params"][layer]["router_bias"]).max()) == 0
    whole, wp, wo = make_scorer()
    wn, _, _ = whole.train_step(wp, wo, jax.random.PRNGKey(1), tokens)
    assert float(jnp.abs(wn["params"]["layers_1"]["router"]
                         - wp["params"]["layers_1"]["router"]).max()) > 1e-6


def test_the_initialisers_are_the_published_ones():
    _, params, _ = make_scorer()
    p = params["params"]
    for layer in ("layers_0", "layers_1", "layers_2", "layers_3"):
        assert float(jnp.abs(p[layer]["norm"] - 1.0).max()) == 0.0
    assert float(jnp.abs(p["final_norm"] - 1.0).max()) == 0.0
    mixer = p["layers_0"]
    assert float(jnp.abs(mixer["out_norm"] - 1.0).max()) == 0.0
    assert float(jnp.abs(mixer["D"] - 1.0).max()) == 0.0
    assert float(jnp.abs(mixer["conv_bias"]).max()) == 0.0
    np.testing.assert_allclose(np.exp(np.asarray(mixer["A_log"])),
                               [1, 2, 3, 4], rtol=1e-6)
    # dt_bias: the inverse softplus of a time step in [0.001, 0.1]
    dt = np.asarray(jax.nn.softplus(mixer["dt_bias"]))
    assert (dt >= 0.001 - 1e-6).all() and (dt <= 0.1 + 1e-6).all()
    assert set(p["layers_1"]) == {
        "norm", "router", "router_bias", "latent_in", "latent_out",
        "experts_up", "experts_down", "shared_up_proj", "shared_down_proj"}
    assert p["layers_1"]["experts_up"].shape == (16, 32, 48)
    assert p["layers_1"]["experts_down"].shape == (16, 48, 32)
    assert p["layers_1"]["shared_up_proj"]["kernel"].shape == (64, 40)
    assert set(p["layers_2"]) == {"norm", "qkv_proj", "out_proj"}
    assert p["layers_0"]["in_proj"]["kernel"].shape == (
        64, 64 + (64 + 2 * 2 * 8) + 4)
    assert p["layers_0"]["conv_weight"].shape == (96, 4)


# -- the shares add up ---------------------------------------------------------

# an uncut layer of every kind at a size eight chips divide: 16 state-space
# heads in 8 groups, 8 query heads over 2 key/value heads, a shared unit of
# 16 columns, 32 experts over the 16 chips of two tensor groups
WHOLE = dict(
    hidden_size=32, num_hidden_layers=1, hybrid_override_pattern="M",
    mamba_num_heads=16, mamba_head_dim=4, n_groups=8, ssm_state_size=4,
    conv_kernel=4, chunk_size=128, num_attention_heads=8,
    num_key_value_heads=2, head_dim=8, n_routed_experts=32,
    num_experts_per_tok=5, moe_intermediate_size=12, moe_latent_size=16,
    moe_shared_expert_intermediate_size=16, routed_scaling_factor=5,
    layer_norm_epsilon=1e-5)
TP, EXPERT_GROUPS = 8, 2


def _columns(block, rank, of=TP):
    """The ``rank``-th of ``of`` equal slices of a block of columns."""
    width = block // of
    return slice(rank * width, (rank + 1) * width)


def _whole_layer(letter, seed):
    """Seeded leaves of one uncut layer, by the checkpoint's names."""
    rng = np.random.default_rng(seed)
    a = WHOLE
    d = a["hidden_size"]
    nrm = lambda *shape: rng.normal(size=shape).astype(np.float32) * 0.3  # noqa: E731
    lay = {"norm": 1 + nrm(d)}
    if letter == "M":
        h, g, ns = a["mamba_num_heads"], a["n_groups"], a["ssm_state_size"]
        inner = h * a["mamba_head_dim"]
        lay.update(
            in_proj={"kernel": nrm(d, 2 * inner + 2 * g * ns + h)},
            conv_weight=nrm(inner + 2 * g * ns, 4),
            conv_bias=nrm(inner + 2 * g * ns), dt_bias=nrm(h),
            A_log=np.log(np.arange(1, h + 1)).astype(np.float32),
            D=1 + nrm(h), out_norm=1 + nrm(inner),
            out_proj={"kernel": nrm(inner, d)})
    elif letter == "*":
        heads, groups, hd = (a["num_attention_heads"],
                             a["num_key_value_heads"], a["head_dim"])
        lay.update(qkv_proj={"kernel": nrm(d, (heads + 2 * groups) * hd)},
                   out_proj={"kernel": nrm(heads * hd, d)})
    else:
        e_all, lat, m = (a["n_routed_experts"], a["moe_latent_size"],
                         a["moe_intermediate_size"])
        ms = a["moe_shared_expert_intermediate_size"]
        lay.update(router=nrm(d, e_all) * 3,
                   router_bias=np.zeros(e_all, np.float32),
                   latent_in={"kernel": nrm(d, lat)},
                   latent_out={"kernel": nrm(lat, d)},
                   experts_up=nrm(e_all, lat, m),
                   experts_down=nrm(e_all, m, lat),
                   shared_up_proj={"kernel": nrm(d, ms)},
                   shared_down_proj={"kernel": nrm(ms, d)})
    return lay


def _share_of_layer(letter, lay, rank, held=None, offset=0):
    """The leaves chip ``rank`` of the tensor group holds of the uncut
    ``lay``: its heads' columns of the input projections and rows of the
    output projection, its groups, the experts ``offset .. offset + held -
    1``; what every chip holds alike (norms, router, the latent's
    projections, the shared unit) whole."""
    a = WHOLE
    if letter == "M":
        h, g, ns = a["mamba_num_heads"], a["n_groups"], a["ssm_state_size"]
        inner, state = h * a["mamba_head_dim"], g * ns
        mine, groups, heads = (_columns(inner, rank), _columns(state, rank),
                               _columns(h, rank))

        def blocks_of(t, axis, *parts):
            """This chip's columns of each block ``(block's start, its
            slice within the block)`` of ``t``, side by side."""
            return np.concatenate([np.take(
                t, np.arange(lo + cols.start, lo + cols.stop), axis=axis)
                for lo, cols in parts], axis=axis)

        conv_parts = ((0, mine), (inner, groups), (inner + state, groups))
        return {
            "norm": lay["norm"],
            # z | x | B | C | dt
            "in_proj": {"kernel": blocks_of(
                lay["in_proj"]["kernel"], 1, (0, mine), (inner, mine),
                (2 * inner, groups), (2 * inner + state, groups),
                (2 * inner + 2 * state, heads))},
            # x | B | C
            "conv_weight": blocks_of(lay["conv_weight"], 0, *conv_parts),
            "conv_bias": blocks_of(lay["conv_bias"], 0, *conv_parts),
            "dt_bias": lay["dt_bias"][heads], "A_log": lay["A_log"][heads],
            "D": lay["D"][heads], "out_norm": lay["out_norm"][mine],
            "out_proj": {"kernel": lay["out_proj"]["kernel"][mine]}}
    if letter == "*":
        heads, groups, hd = (a["num_attention_heads"],
                             a["num_key_value_heads"], a["head_dim"])
        q = _columns(heads * hd, rank)
        # fewer key/value heads than chips: chip r holds head r // (8 / 2)
        kv_head = rank // (TP // groups)
        kv = slice(kv_head * hd, (kv_head + 1) * hd)
        w = lay["qkv_proj"]["kernel"]
        return {
            "norm": lay["norm"],
            "qkv_proj": {"kernel": np.concatenate(
                [w[:, q], w[:, heads * hd:][:, kv],
                 w[:, (heads + groups) * hd:][:, kv]], axis=1)},
            "out_proj": {"kernel": lay["out_proj"]["kernel"][q]}}
    held_experts = slice(offset, offset + held)
    return dict(lay, experts_up=lay["experts_up"][held_experts],
                experts_down=lay["experts_down"][held_experts])


def _block_addend(letter, arch, lay, x, tokens):
    """What the program's Block of this share adds to the residual."""
    cfg = MoESSMConfig(arch=MoESSMArch.from_mapping(arch), vocab_size=VOCAB,
                       seq_len=x.shape[1], dtype=jnp.float32,
                       platform="cpu")
    flat = x.reshape(-1, x.shape[-1])
    with jax.default_matmul_precision("highest"):
        out, counts = Block(cfg, layer=0).apply(
            {"params": lay}, flat, tokens != 0, tokens != 0)
    return np.asarray(out - flat).reshape(x.shape), counts


@pytest.mark.parametrize("letter", ["M", "*", "E"])
def test_the_shares_add_up_to_the_uncut_reference_layer(letter):
    """The guide's share test for the tensor share: the addends of the
    eight chips of a tensor group (and, in an expert layer, of the sixteen
    chips of two such groups, every chip its own two experts) with what
    every chip computes alike counted once — the shared unit, which every
    one of the sixteen holds whole; the mixers over the two groups — equal
    the uncut reference's layer."""
    rng = np.random.default_rng(3)
    lines, seq = 3, 16
    tokens = rng.integers(3, VOCAB, size=(lines, seq)).astype(np.int32)
    tokens[1, 11:] = 0
    x = rng.normal(size=(lines, seq, WHOLE["hidden_size"])).astype(np.float32)
    lay = _whole_layer(letter, seed=7)
    whole_arch = dict(WHOLE, hybrid_override_pattern=letter,
                      router_experts=32)
    inp_seen = (tokens != 0)[:, None, None, :] & np.tril(
        np.ones((seq, seq), bool))[None, None]
    with jax.default_matmul_precision("highest"):
        y = reference._norm(jnp.asarray(x), jnp.asarray(lay["norm"]), 1e-5)
        want, _ = reference.mixer(lay, letter, y, whole_arch,
                                  jnp.asarray(inp_seen))
    want = np.asarray(want)
    # the uncut layer through the program, one chip holding all of it
    uncut, _ = _block_addend(letter, whole_arch, lay, x, tokens)
    keep = (tokens != 0)[..., None]
    if letter == "E":
        want, uncut = want * keep, uncut * keep   # PAD tokens are not routed
    np.testing.assert_allclose(uncut, want, rtol=2e-4, atol=2e-4)
    total = np.zeros_like(want)
    held = WHOLE["n_routed_experts"] // (TP * EXPERT_GROUPS)
    for group in range(EXPERT_GROUPS if letter == "E" else 1):
        for rank in range(TP):
            offset = (group * TP + rank) * held
            arch = MoESSMArch.share_of(
                dict(WHOLE, hybrid_override_pattern=letter),
                tensor_parallel=TP, tensor_rank=rank, experts_held=held,
                expert_offset=offset)
            assert (arch["mamba_num_heads"], arch["n_groups"],
                    arch["num_attention_heads"], arch["num_key_value_heads"],
                    arch["moe_shared_expert_intermediate_size"],
                    arch["n_routed_experts"], arch["router_experts"]) == (
                2, 1, 1, 1, 16, held, 32)
            share = _share_of_layer(letter, lay, rank, held, offset)
            part, counts = _block_addend(letter, arch, share, x, tokens)
            if letter == "E" and (group, rank) != (0, 0):
                # the shared unit was counted with the first chip: every
                # chip computes it alike
                alone = dict(share, experts_up=share["experts_up"] * 0,
                             experts_down=share["experts_down"] * 0)
                part = part - _block_addend(letter, arch, alone, x,
                                            tokens)[0]
            total += part * keep if letter == "E" else part
    np.testing.assert_allclose(total, want, rtol=3e-4, atol=3e-4)
    # no share alone is the layer
    assert np.abs(part - want).max() > 1e-2


def test_a_share_that_does_not_divide_is_refused_by_name():
    published = dict(WHOLE)
    for key, count in (("mamba_num_heads", 12), ("n_groups", 4),
                       ("num_attention_heads", 12)):
        with pytest.raises(ValueError, match=f"{key} {count} does not"):
            MoESSMArch.share_of(dict(published, **{key: count}),
                                tensor_parallel=TP)
    with pytest.raises(ValueError, match="num_key_value_heads"):
        MoESSMArch.share_of(dict(published, num_key_value_heads=3,
                                 num_attention_heads=24,
                                 mamba_num_heads=16, n_groups=8),
                            tensor_parallel=2)
    # a share counts heads and cuts no width: the shared unit stays whole
    assert MoESSMArch.share_of(
        dict(published, moe_shared_expert_intermediate_size=20),
        tensor_parallel=TP)["moe_shared_expert_intermediate_size"] == 20
    # key/value heads divide over the chips where there are enough of them
    two = MoESSMArch.share_of(published, tensor_parallel=2, tensor_rank=1)
    assert (two["num_key_value_heads"], two["num_attention_heads"],
            two["n_groups"], two["tensor_rank"]) == (1, 4, 4, 1)
    one = MoESSMArch.share_of(published, tensor_parallel=1)
    assert MoESSMArch.from_mapping(one) == MoESSMArch.from_mapping(
        dict(WHOLE, router_experts=32))


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


def test_the_state_space_alone_carries_a_change_to_the_lines_end():
    """Without attention a change at t still reaches the line's last NLL:
    the state is carried over positions (and decays on the way), where the
    convolution alone sees as far as its four taps reach."""
    arch = arch_with(hybrid_override_pattern="M", num_hidden_layers=1)
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
    chosen = np.asarray(chosen)                     # [E layers, N, S, K]
    assert chosen.shape[0] == 2                     # two of the four layers
    held = (chosen >= 4) & (chosen < 8)
    busiest = sum(max(int((layer == e).sum()) for e in range(4, 8))
                  for layer in chosen)
    assert [int(c) for c in counts] == [int((chosen >= 0).sum()),
                                        int(held.sum()), busiest]
    assert int(counts[0]) == int((tokens != 0).sum()) * 3 * 2


@pytest.mark.parametrize("change,named", [
    ({"mlp_hidden_act": "silu"}, "mlp_hidden_act"),
    ({"mamba_hidden_act": "gelu"}, "mamba_hidden_act"),
    ({"use_conv_bias": False}, "use_conv_bias"),
    ({"attention_bias": True}, "attention_bias"),
    ({"mamba_proj_bias": True}, "mamba_proj_bias"),
    ({"mlp_bias": True}, "mlp_bias"),
    ({"n_group": 2}, "n_group"),
    ({"topk_group": 2}, "topk_group"),
    ({"n_shared_experts": 2}, "n_shared_experts"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"sliding_window": 128}, "sliding_window"),
    ({"norm_eps": 1e-6}, "norm_eps"),
    ({"bogus": 1}, "bogus"),
    ({"head_dim": None}, "head_dim"),
    ({"hybrid_override_pattern": "ME*"}, "hybrid_override_pattern"),
    ({"hybrid_override_pattern": "ME-E"}, "hybrid_override_pattern"),
    ({"n_groups": 3}, "n_groups"),
    ({"conv_kernel": 0}, "conv_kernel"),
    ({"chunk_size": 0}, "chunk_size"),
    ({"num_key_value_heads": 3}, "num_key_value_heads"),
    ({"expert_offset": 14, "n_routed_experts": 4}, "held experts"),
    ({"num_experts_per_tok": 17}, "num_experts_per_tok"),
    ({"tensor_parallel": 8, "tensor_rank": 8}, "tensor_rank"),
    ({"tensor_rank": -1}, "tensor_rank"),
])
def test_arch_refuses_by_name(change, named):
    with pytest.raises(ValueError, match=named):
        MoESSMArch.from_mapping(arch_with(**change))


def test_arch_takes_a_published_config_as_it_is():
    published = dict(ARCH, **PUBLISHED_EXTRAS)
    published.pop("router_experts")
    published.pop("expert_offset")
    arch = MoESSMArch.from_mapping(published)
    assert arch.router_experts == arch.n_routed_experts == 16
    assert (arch.tensor_parallel, arch.tensor_rank) == (1, 0)
    assert arch.layer_types == ("ssm", "moe", "attn", "moe")
    assert arch.ssm_inner == 64
    spec = arch.expert_spec
    assert (spec.shared, spec.shared_width, spec.shared_gate, spec.gated,
            spec.latent, spec.norm_eps, spec.scoring_func, spec.top_k,
            spec.scaling, spec.width) == (1, 40, False, False, 32, 1e-20,
                                          "sigmoid", 3, 2.5, 48)
    # and the share of it, by the keys the detector is given
    share = MoESSMArch.from_mapping(MoESSMArch.share_of(
        published, tensor_parallel=2, tensor_rank=1, experts_held=4,
        expert_offset=8, num_hidden_layers=2))
    assert (share.mamba_num_heads, share.n_groups, share.num_attention_heads,
            share.num_key_value_heads,
            share.moe_shared_expert_intermediate_size, share.n_routed_experts,
            share.router_experts, share.expert_offset,
            share.hybrid_override_pattern, share.tensor_parallel,
            share.tensor_rank) == (2, 1, 2, 1, 40, 4, 16, 8, "ME", 2, 1)


def test_the_family_calls_the_shared_blocks():
    assert moe_ssm.expert_layer is blocks.expert_layer
    assert moe_ssm.rms_norm is blocks.rms_norm
    assert moe_ssm.causal_stack is blocks.causal_stack
    assert issubclass(MoESSMScorer, blocks.ExpertLMScorer)
    # the other families' specs stay gated, at the residual's width
    from tests.test_moe_delta import ARCH as DELTA_ARCH
    from tests.test_moe_mla import ARCH as MLA_ARCH
    from detectmateservice_tpu.models.moe_delta import MoEDeltaArch
    from detectmateservice_tpu.models.moe_mla import MoEMLAArch

    for spec in (MoEMLAArch.from_mapping(MLA_ARCH).expert_spec,
                 MoEDeltaArch.from_mapping(DELTA_ARCH).expert_spec):
        assert spec.gated and not spec.latent and not spec.shared_width


# -- through JaxScorerDetector ----------------------------------------------

def detector_config(**overrides):
    base = {
        "method_type": "jax_scorer", "auto_config": False,
        "model": "moe_ssm", "arch": arch_with(**SHARE),
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
    assert state["delta_route"] == {} and state["conv_route"] == {}
    assert state["head_route"]["32"] == "einsum"
    info = det.device_info()
    assert info["scorer"]["model"] == "moe_ssm"
    assert info["scorer"]["arch"]["hybrid_override_pattern"] == "ME*E"
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

    with pytest.raises(LibraryError, match="moe_ssm"):
        JaxScorerDetector(config=scorer_config(arch=ARCH))
    with pytest.raises(LibraryError, match="moe_ssm"):
        JaxScorerDetector(config=scorer_config(model="nope"))


def test_a_bad_arch_fails_at_build_before_any_trace():
    det = JaxScorerDetector(config=detector_config(
        arch=arch_with(mlp_hidden_act="silu")))
    with pytest.raises(LibraryError, match="mlp_hidden_act"):
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
                 if re.search(r"[\"']moe_ssm[\"']", line)]
        assert not named, named
