"""The sparse-expert, latent-attention scorer (models/moe_mla.py,
ops/experts.py) at a tiny size on the CPU, held to the benchmark's plain
reference (benchmark/reference/moe_mla.py, which imports nothing of models/
or ops/): scores and per-position NLLs, the share test, no dropped
assignment at any skew, causality, the untied head, the routing counters,
and the whole detector life (fit, threshold, checkpoint, restore)."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.reference import moe_mla as reference  # noqa: E402
from detectmateservice_tpu.library.common.core import LibraryError  # noqa: E402
from detectmateservice_tpu.library.detectors import JaxScorerDetector  # noqa: E402
from detectmateservice_tpu.models.moe_mla import (  # noqa: E402
    MoEMLAArch, MoEMLAConfig, MoEMLAScorer)
from detectmateservice_tpu.ops import experts as expert_ops  # noqa: E402
from detectmateservice_tpu.ops.attention import attention  # noqa: E402

VOCAB, SEQ = 64, 16
ARCH = dict(
    hidden_size=64, num_attention_heads=4, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32, q_lora_rank=None,
    intermediate_size=96, moe_intermediate_size=48, n_shared_experts=1,
    num_experts_per_tok=2, first_k_dense_replace=1, norm_topk_prob=True,
    routed_scaling_factor=2.448, scoring_func="sigmoid", rope_theta=1e6,
    rope_interleave=True, rms_norm_eps=1e-6, num_hidden_layers=3,
    n_routed_experts=8, router_experts=8, expert_offset=0)


def arch_with(**changes):
    return dict(ARCH, **changes)


def make_scorer(arch=None, dtype=jnp.float32, init=0.3, seed=0):
    """A seeded scorer; ``init`` is wide so that the blocks, not the
    embedding, decide the scores at this size."""
    scorer = MoEMLAScorer(MoEMLAConfig(
        arch=MoEMLAArch.from_mapping(arch or ARCH), vocab_size=VOCAB,
        seq_len=SEQ, dtype=dtype, initializer_range=init))
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
    # bfloat16 multiplies through three layers at init 0.1, four seeds
    # here: a position's NLL is off by 0.005-0.010 nats at the median and
    # by 0.3-0.5 where a token's second expert changed (routing is
    # discontinuous), a line's score by 0.020-0.037; the float8_e4m3fn
    # control's scores are off by 0.062-0.175. The tolerances lie between
    (jnp.bfloat16, 0.03, 0.05),
])
def test_scorer_matches_reference(dtype, nll_tol, score_tol):
    arch = arch_with(n_routed_experts=4, expert_offset=2)
    scorer, params, _ = make_scorer(arch, dtype, init=0.1)
    tokens = make_tokens()
    scores, _ = scorer._score(params, tokens)
    nlls = scorer._token_nlls(params, tokens)
    want_nlls = reference.token_nlls(as_numpy(params), tokens, arch)
    want = reference.score(as_numpy(params), tokens, {"arch": arch})
    gaps = np.abs(np.asarray(nlls - want_nlls))[tokens != 0]
    # float32: every position; bfloat16: the median position (a re-routed
    # token is far off, and the line's score below bounds what that costs)
    assert (gaps.max() if dtype == jnp.float32
            else np.median(gaps)) < nll_tol
    assert float(np.abs(np.asarray(scores) - want).max()) < score_tol
    assert float(jnp.abs(nlls[-1]).max()) == 0.0       # the padding row
    assert np.allclose(np.asarray(scorer.score(params, tokens)),
                       np.asarray(scores))


def test_reference_lower_control_changes_the_scores():
    scorer, params, _ = make_scorer(init=0.1)
    tokens = make_tokens()
    plain = reference.score(as_numpy(params), tokens, {"arch": ARCH})
    lowered = reference.score(as_numpy(params), tokens, {"arch": ARCH},
                              lower=jnp.float8_e4m3fn)
    assert np.abs(plain - lowered)[:-1].max() > 1e-3


# -- the share --------------------------------------------------------------

def test_shares_add_up_to_the_uncut_layer():
    """The parts of an expert layer's routed result that all shares give
    add up to what the uncut layer gives; the shared expert, which every
    chip computes alike, is outside ``routed_experts`` and counted once."""
    rng = np.random.default_rng(1)
    n, d, m, e_all, k = 96, 32, 24, 8, 3
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(d, e_all)) * 0.5, jnp.float32)
    gate, up = (jnp.asarray(rng.normal(size=(e_all, d, m)) * 0.2,
                            jnp.float32) for _ in range(2))
    down = jnp.asarray(rng.normal(size=(e_all, m, d)) * 0.2, jnp.float32)
    valid = jnp.asarray(rng.random(n) > 0.1)
    routing = expert_ops.route(x, router, jnp.zeros(e_all), valid, top_k=k,
                               norm_topk_prob=True, scaling=2.448)
    whole, whole_counts = expert_ops.routed_experts(x, routing, gate, up,
                                                    down)
    parts, counts = [], []
    for offset in range(0, e_all, 2):                  # four shares of two
        part, c = expert_ops.routed_experts(
            x, routing, gate[offset:offset + 2], up[offset:offset + 2],
            down[offset:offset + 2], offset=offset)
        parts.append(part)
        counts.append(c)
    assert np.allclose(sum(parts), whole, atol=1e-4)
    assert np.array_equal(np.concatenate(counts), whole_counts)
    assert int(whole_counts.sum()) == int(valid.sum()) * k

    # the uncut REFERENCE's layer: its own routing, every expert densely,
    # and the shared expert — which each share's chip computes alike and
    # which is therefore added once to the sum of the shares' parts
    def unit(y, g, u, dn):
        return (jax.nn.silu(y @ g) * (y @ u)) @ dn

    shared = [jnp.asarray(rng.normal(size=shape) * 0.2, jnp.float32)
              for shape in ((d, m), (d, m), (m, d))]
    with jax.default_matmul_precision("highest"):
        chosen, w = reference.routing(
            x, router, jnp.zeros(e_all),
            {"scoring_func": "sigmoid", "num_experts_per_tok": k,
             "norm_topk_prob": True, "routed_scaling_factor": 2.448})
        uncut = unit(x, *shared)
        for e in range(e_all):
            w_e = (w * (chosen == e)).sum(-1)
            uncut += w_e[:, None] * unit(x, gate[e], up[e], down[e])
    uncut = jnp.where(valid[:, None], uncut, unit(x, *shared))
    assert np.allclose(sum(parts) + unit(x, *shared), uncut, atol=1e-4)
    assert np.allclose(np.asarray(whole)[~np.asarray(valid)], 0.0)


# -- no dropped assignment, at any skew --------------------------------------

@pytest.mark.parametrize("bias_held,expect", [(+50.0, "all"), (-50.0, "none")])
@pytest.mark.parametrize("chunk_rows", [None, 32, 16])
def test_no_assignment_dropped_under_skew(bias_held, expect, chunk_rows):
    """A router biased to send every token to held experts computes all
    N*K assignments (six times the even share); one biased away computes
    none, and the result is exactly zero. Chunked and unchunked alike."""
    rng = np.random.default_rng(2)
    n, d, m, e_all, held, k, offset = 64, 32, 24, 16, 4, 2, 4
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(d, e_all)) * 0.3, jnp.float32)
    bias = np.zeros(e_all, np.float32)
    bias[offset:offset + held] = bias_held
    gate, up = (jnp.asarray(rng.normal(size=(held, d, m)) * 0.2,
                            jnp.float32) for _ in range(2))
    down = jnp.asarray(rng.normal(size=(held, m, d)) * 0.2, jnp.float32)
    valid = jnp.ones(n, bool)
    routing = expert_ops.route(x, router, jnp.asarray(bias), valid, top_k=k,
                               norm_topk_prob=True, scaling=1.0)
    out, counts = expert_ops.routed_experts(
        x, routing, gate, up, down, offset=offset, chunk_rows=chunk_rows)
    dense = jnp.zeros((n, d))
    for e in range(held):
        w_e = (routing.weights * (routing.experts == offset + e)).sum(-1)
        dense += w_e[:, None] * (
            (jax.nn.silu(x @ gate[e]) * (x @ up[e])) @ down[e])
    assert np.allclose(out, dense, atol=1e-4)
    if expect == "all":
        assert int(counts.sum()) == n * k
        # the bias moves the choice, never the weight
        assert np.allclose(routing.weights.sum(-1), 1.0, atol=1e-5)
    else:
        assert int(counts.sum()) == 0
        assert float(jnp.abs(out).max()) == 0.0


def test_chunked_walk_equals_one_chunk():
    rng = np.random.default_rng(3)
    n, d, m, e_all, k = 128, 32, 24, 8, 2
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(d, e_all)), jnp.float32)
    gate, up = (jnp.asarray(rng.normal(size=(4, d, m)) * 0.2, jnp.float32)
                for _ in range(2))
    down = jnp.asarray(rng.normal(size=(4, m, d)) * 0.2, jnp.float32)
    routing = expert_ops.route(x, router, jnp.zeros(e_all),
                               jnp.ones(n, bool), top_k=k,
                               norm_topk_prob=True, scaling=2.0)
    one, c1 = expert_ops.routed_experts(x, routing, gate, up, down, offset=2)
    many, c2 = expert_ops.routed_experts(x, routing, gate, up, down,
                                         offset=2, chunk_rows=16)
    assert np.allclose(one, many, atol=1e-5)
    assert np.array_equal(c1, c2)
    # the scan walk has a gradient, and it is the one-chunk walk's
    def gate_grad(rows):
        return jax.grad(lambda g: expert_ops.routed_experts(
            x, routing, g, up, down, offset=2, chunk_rows=rows
        )[0].sum())(gate)

    assert np.allclose(gate_grad(None), gate_grad(16), atol=1e-4)
    # half the token count; one chunk for a small call (the fit's batches)
    assert expert_ops.chunk_rows_for(32768, 6) == 16384
    assert expert_ops.chunk_rows_for(8192, 6) == 4096
    assert expert_ops.chunk_rows_for(1024, 6) == 6144


def test_no_row_is_left_outside_every_group(monkeypatch):
    """On the TPU a grouped matmul leaves rows outside every group
    uninitialised, forward and backward (a fit on the chip came out NaN:
    0 x NaN in the backward pass is NaN). So every row of a chunk belongs
    to a group — the dead ones to the last, computed and masked — under
    even routing and with nothing held; a call that leaves a row out is
    poisoned here."""
    real = jax.lax.ragged_dot

    def poisoned(lhs, rhs, group_sizes, **kw):
        out = real(lhs, rhs, group_sizes, **kw)
        # every row in a group, or the whole result is poison
        return jnp.where(group_sizes.sum() == lhs.shape[0], out, jnp.nan)

    monkeypatch.setattr(expert_ops.jax.lax, "ragged_dot", poisoned)
    rng = np.random.default_rng(5)
    n, d, m, e_all, k = 64, 32, 24, 16, 2
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(d, e_all)) * 0.3, jnp.float32)
    gate, up = (jnp.asarray(rng.normal(size=(4, d, m)) * 0.2, jnp.float32)
                for _ in range(2))
    down = jnp.asarray(rng.normal(size=(4, m, d)) * 0.2, jnp.float32)
    for bias_held in (0.0, -50.0):
        bias = jnp.zeros(e_all).at[4:8].set(bias_held)

        def loss(x, router, gate):
            routing = expert_ops.route(
                x, router, bias, jnp.ones(n, bool), top_k=k,
                norm_topk_prob=True, scaling=2.0)
            out, _ = expert_ops.routed_experts(x, routing, gate, up, down,
                                               offset=4, chunk_rows=32)
            return (out ** 2).sum()

        value, grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(
            x, router, gate)
        assert np.isfinite(float(value))
        assert all(bool(jnp.isfinite(g).all()) for g in grads)


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


def test_untied_head_is_used():
    scorer, params, _ = make_scorer(init=0.2)
    tokens = make_tokens()
    base = np.asarray(scorer.score(params, tokens))
    p = params["params"]
    head_changed = {"params": dict(p, lm_head=p["lm_head"] * 1.5)}
    assert np.abs(np.asarray(scorer.score(head_changed, tokens))
                  - base)[:-1].max() > 1e-3
    # the embedding of a token that is never an INPUT (only the last
    # position's target) does not reach the scores: the head is not tied
    tokens[:, -1] = np.where(tokens[:, -1] != 0, VOCAB - 1, 0)
    tokens[:, :-1] = np.where(tokens[:, :-1] == VOCAB - 1, 5,
                              tokens[:, :-1])
    base = np.asarray(scorer.score(params, tokens))
    emb = p["tok_embed"]["embedding"].at[VOCAB - 1].mul(3.0)
    emb_changed = {"params": dict(p, tok_embed={"embedding": emb})}
    assert np.allclose(np.asarray(scorer.score(emb_changed, tokens)), base,
                       atol=1e-6)


def test_counters_match_the_references_routing():
    arch = arch_with(n_routed_experts=4, expert_offset=2)
    scorer, params, _ = make_scorer(arch, init=0.2)
    tokens = make_tokens()
    _, counts = scorer._score(params, tokens)
    _, chosen = reference.token_nlls(as_numpy(params), tokens, arch,
                                     with_routing=True)
    chosen = np.asarray(chosen)                     # [layers, N, S, K]
    held = (chosen >= 2) & (chosen < 6)
    busiest = sum(max(int((layer == e).sum()) for e in range(2, 6))
                  for layer in chosen)
    assert [int(c) for c in counts] == [int((chosen >= 0).sum()),
                                        int(held.sum()), busiest]
    assert int(counts[0]) == int((tokens != 0).sum()) * 2 * 2


def test_the_fit_leaves_the_selection_bias_at_zero():
    """``e_score_correction_bias`` is a buffer: zeros at the start, no
    gradient reaches it and the fit has no balance update, so it is zeros
    after the fit too — while the parameters around it do move."""
    arch = arch_with(n_routed_experts=4, expert_offset=2)
    scorer, params, opt_state = make_scorer(arch, init=0.3, seed=3)
    tokens = make_tokens(rows=32, seed=1)
    gate0 = np.asarray(params["params"]["layers_1"]["experts_gate"])
    for step in range(3):
        params, opt_state, _ = scorer.train_step(
            params, opt_state, jax.random.PRNGKey(step), tokens)
    for layer in ("layers_1", "layers_2"):
        assert float(jnp.abs(
            params["params"][layer]["router_bias"]).max()) == 0
    assert not np.allclose(
        gate0, np.asarray(params["params"]["layers_1"]["experts_gate"]))


def test_the_fit_donates_and_a_shares_router_is_not_trained():
    scorer, params, opt_state = make_scorer(init=0.1)
    tokens = make_tokens()
    kept, kept_opt, first = scorer.train_step(
        params, opt_state, jax.random.PRNGKey(1), tokens)
    assert float(jnp.abs(params["params"]["lm_head"]).max()) > 0  # alive
    new, _, second = scorer.train_step(
        kept, kept_opt, jax.random.PRNGKey(2), tokens, donate=True)
    assert float(second) < float(first)
    # every expert held: the router is trained
    assert float(jnp.abs(new["params"]["layers_1"]["router"]
                         - params["params"]["layers_1"]["router"]).max()) > 1e-5
    # a share: its router gets no gradient (AdamW's decay alone touches it)
    share, sp, so = make_scorer(arch_with(n_routed_experts=4,
                                          expert_offset=2), init=0.1)
    sn, _, _ = share.train_step(sp, so, jax.random.PRNGKey(1), tokens)
    drift = jnp.abs(sn["params"]["layers_1"]["router"]
                    - sp["params"]["layers_1"]["router"]).max()
    assert float(drift) < 1e-7
    assert float(jnp.abs(sn["params"]["layers_1"]["experts_up"]
                         - sp["params"]["layers_1"]["experts_up"]).max()) > 1e-5


@pytest.mark.parametrize("change,named", [
    ({"q_lora_rank": 64}, "q_lora_rank"),
    # group-limited routing runs since PR 44 (tests/test_kda.py holds it to
    # a sort); what is refused is a grouping that does not divide
    ({"n_group": 3}, "n_group"),
    ({"n_group": 2, "topk_group": 3}, "topk_group"),
    ({"n_group": 4, "topk_group": 1, "num_experts_per_tok": 3},
     "num_experts_per_tok"),
    ({"bogus": 1}, "bogus"),
    ({"expert_offset": 6}, "held experts"),
    ({"kv_lora_rank": None}, "kv_lora_rank"),
])
def test_arch_refuses_by_name(change, named):
    with pytest.raises(ValueError, match=named):
        MoEMLAArch.from_mapping(arch_with(**change))


def test_arch_takes_a_published_config_as_it_is():
    published = dict(
        ARCH, model_type="deepseek_v3", head_dim=64, qk_head_dim=24,
        num_key_value_heads=4, max_position_embeddings=32768,
        topk_method="noaux_tc", n_group=1, topk_group=1, moe_layer_freq=1,
        attention_bias=False, tie_word_embeddings=False, hidden_act="silu",
        rope_scaling=None, vocab_size=VOCAB)
    published.pop("router_experts")
    arch = MoEMLAArch.from_mapping(published)
    assert arch.router_experts == arch.n_routed_experts == 8
    assert arch.expert_layers == 2


# -- ops/attention: causal, and a value width of its own ---------------------

def test_attention_causal_and_value_width():
    rng = np.random.default_rng(4)
    b, h, s = 2, 3, 8
    q, k = (jnp.asarray(rng.normal(size=(b, h, s, 12)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.normal(size=(b, h, s, 5)), jnp.float32)
    mask = jnp.asarray(rng.random((b, s)) > 0.2).at[:, 0].set(True)
    out = attention(q, k, v, key_mask=mask, impl="auto", platform="cpu",
                    causal=True)
    assert out.shape == (b, h, s, 5)
    logits = jnp.einsum("bhsd,bhtd->bhst", q, k) / np.sqrt(12)
    see = mask[:, None, None, :] & jnp.tril(jnp.ones((s, s), bool))
    want = jax.nn.softmax(jnp.where(see, logits, -1e30), -1) @ v
    assert np.allclose(out, want, atol=1e-5)
    for impl in ("flash", "blockwise", "ring"):
        with pytest.raises(ValueError, match="causal"):
            attention(q, k, q, key_mask=mask, impl=impl, platform="cpu",
                      causal=True)
    with pytest.raises(ValueError, match="value width"):
        attention(q, k, v, impl="blockwise", platform="cpu")


# -- the attention route (ops/attention.py::latent_attention) ---------------

@pytest.mark.parametrize("dtype,rms_limit,max_limit", [
    # the benchmark's own limits for this family (its file's ``check``):
    # score_gap_rms_nats 0.0065, score_gap_max_nats 0.10
    (jnp.bfloat16, 0.0065, 0.10),
    (jnp.float32, 1e-5, 1e-4),
])
def test_scores_agree_between_the_forced_kernel_and_einsum(dtype, rms_limit,
                                                           max_limit):
    """``attn_impl: short`` (the two-width kernel, here in the Pallas
    interpreter) against ``einsum`` on the same parameters: scores, the
    routing counts and the recorded route."""
    import dataclasses

    einsum, params, _ = make_scorer(dtype=dtype, init=0.1)
    einsum.config = dataclasses.replace(einsum.config, attn_impl="einsum")
    short = MoEMLAScorer(dataclasses.replace(einsum.config,
                                             attn_impl="short"))
    tokens = jnp.asarray(make_tokens(rows=24, seed=3))
    (a, counts_a), (b, counts_b) = (einsum._score(params, tokens),
                                    short._score(params, tokens))
    gap = np.asarray(a, np.float64) - np.asarray(b, np.float64)
    assert np.isfinite(np.asarray(b)).all()
    assert np.sqrt((gap ** 2).mean()) <= rms_limit
    assert np.abs(gap).max() <= max_limit
    if dtype == jnp.float32:
        np.testing.assert_array_equal(np.asarray(counts_a),
                                      np.asarray(counts_b))
    assert einsum.attn_routes == {24: "einsum"}
    assert short.attn_routes == {24: "short"}


def test_one_train_step_through_the_kernels_vjp_agrees():
    """The fit differentiates through ``short_latent_attention``'s custom
    vjp where the kernel is forced: loss and updated parameters against
    the einsum route's."""
    import dataclasses

    import optax

    einsum, params, _ = make_scorer(init=0.1)
    short = MoEMLAScorer(dataclasses.replace(einsum.config,
                                             attn_impl="short"))
    einsum.optimizer = short.optimizer = optax.sgd(0.1)
    opt = einsum.optimizer.init(params)
    tokens, rng = jnp.asarray(make_tokens(rows=8)), jax.random.PRNGKey(1)
    p_e, _, loss_e = einsum.train_step(params, opt, rng, tokens)
    p_s, _, loss_s = short.train_step(params, opt, rng, tokens)
    np.testing.assert_allclose(float(loss_s), float(loss_e), rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(p_e),
                    jax.tree_util.tree_leaves(p_s)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-5)


def test_published_widths_take_the_kernel_from_256_rows_on_one_tpu():
    """32 heads of 128 ‖ 64 with values 128: ``auto`` records ``short``
    for the served 256-, 512- and 1024-row programs and ``einsum`` for the
    fit's 32-row step; on the CPU, or on a mesh, ``einsum`` for all.
    Traced only (``eval_shape``): nothing is lowered for a chip that is not
    here."""
    arch = arch_with(hidden_size=256, num_attention_heads=32,
                     qk_nope_head_dim=128, qk_rope_head_dim=64,
                     v_head_dim=128, num_hidden_layers=2)

    def routes(platform, mesh_devices=1):
        scorer = MoEMLAScorer(MoEMLAConfig(
            arch=MoEMLAArch.from_mapping(arch), vocab_size=VOCAB,
            seq_len=32, platform=platform, head_impl="einsum"))
        scorer.mesh_devices = mesh_devices
        params = jax.eval_shape(lambda: scorer.init(jax.random.PRNGKey(0))[0])
        for rows in (32, 256, 512, 1024):
            jax.eval_shape(scorer._score_impl, params,
                           jax.ShapeDtypeStruct((rows, 32), jnp.uint16))
        return scorer.attn_routes

    assert routes("tpu") == {32: "einsum", 256: "short", 512: "short",
                             1024: "short"}
    assert set(routes("cpu").values()) == {"einsum"}
    assert set(routes("tpu", mesh_devices=4).values()) == {"einsum"}


def test_the_projections_keep_nn_denses_parameters():
    """``HeadSplitDense`` regroups columns of a kernel whose name, shape
    and stored layout are ``nn.Dense``'s: column ``h * 24 + i`` of
    ``q_proj`` is head h's i-th, nope first (the reference and every
    checkpoint read it so)."""
    scorer, params, _ = make_scorer()
    attn = params["params"]["layers_0"]
    assert attn["q_proj"]["kernel"].shape == (64, 4 * (16 + 8))
    assert attn["kv_up"]["kernel"].shape == (32, 4 * (16 + 16))
    assert set(attn["q_proj"]) == set(attn["kv_up"]) == {"kernel"}


def test_detector_admits_the_kernel_by_name():
    det = JaxScorerDetector(config=detector_config(attn_impl="short"))
    assert det.config.attn_impl == "short"


# -- through JaxScorerDetector ----------------------------------------------

def detector_config(**overrides):
    base = {
        "method_type": "jax_scorer", "auto_config": False,
        "model": "moe_mla", "arch": arch_with(n_routed_experts=4,
                                              expert_offset=2),
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
    assert "ragged_dot" in state["expert_route"]["32"]
    assert det.device_info()["scorer"]["arch"]["kv_lora_rank"] == 32
    assert det.device_info()["host_twin"]["state"] == "off"
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


def test_every_detector_exports_the_moe_counters_and_only_experts_move_them():
    from prometheus_client import REGISTRY

    from tests.test_jax_scorer import normal_msgs, scorer_config

    det = JaxScorerDetector(config=scorer_config(host_score_max_batch=0))
    det._ensure_scorer()
    name = "detector_moe_assignments_total"
    before = REGISTRY.get_sample_value(name, det._obs_labels())
    assert before is not None          # exported from scorer set-up on
    det.process_batch(normal_msgs(32))
    det.flush_final()
    det.process_batch(normal_msgs(16, salt="x"))
    det.flush_final()
    assert REGISTRY.get_sample_value(name, det._obs_labels()) == before


@pytest.mark.parametrize("overrides,named", [
    ({"mesh_shape": {"data": 2}}, "mesh_shape"),
    ({"dtype": "int8w"}, "int8w"),
    ({"score_vocab": 16}, "score_vocab"),
    ({"attn_impl": "flash"}, "attn_impl"),
    ({"arch": None}, "arch"),
])
def test_detector_refuses_at_validation_by_name(overrides, named):
    with pytest.raises(LibraryError, match=named):
        JaxScorerDetector(config=detector_config(**overrides))


def test_other_families_refuse_an_arch_and_unknown_models_are_named():
    from tests.test_jax_scorer import scorer_config

    with pytest.raises(LibraryError, match="arch"):
        JaxScorerDetector(config=scorer_config(arch=ARCH))
    with pytest.raises(LibraryError, match="moe_mla"):
        JaxScorerDetector(config=scorer_config(model="nope"))


def test_a_bad_arch_fails_at_build_before_any_trace():
    det = JaxScorerDetector(config=detector_config(
        arch=arch_with(q_lora_rank=64)))
    with pytest.raises(LibraryError, match="q_lora_rank"):
        det._ensure_scorer()
