"""The operations the ``moe_kda`` family adds (PR 44), on the CPU at small
sizes: the vector-decay delta rule (ops/deltarule.py::kda_delta_rule) — its
chunked closed form in sub-blocks against the position-by-position scan at
8-, 16- and 32-long lines and with an entering state, **with every gate at
the bound for all 32 positions** (where sub-blocks of 16 positions, or none,
leave float32), causal to the bit, its gradient, the gate's range, the route;
group-limited routing (ops/experts.py::route) against a sort-based choice,
and at one group against the router of before, to the bit; the per-head
norms and the head-wise gate around latent attention (ops/attention.py);
and that the lowered scoring programs of the four families this PR's edits
of ``ops/`` pass through are text-equal to what the untouched functions
trace."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from detectmateservice_tpu.ops import attention as attention_ops
from detectmateservice_tpu.ops import experts as expert_ops
from detectmateservice_tpu.ops.attention import (latent_attention,
                                                 latent_head_norms,
                                                 per_head_latent_attention,
                                                 placement, sigmoid_gate)
from detectmateservice_tpu.ops.deltarule import (KDA_BLOCK, kda_delta_rule,
                                                 kda_gates, kda_route)
from detectmateservice_tpu.ops.experts import keep_groups, route

H, D = 2, 8
BOUND = -5.0


def operands(lines=3, seq=32, seed=0, gate="random", h=H, d=D):
    """Seeded q, k, v, g, beta; ``gate`` "bound" holds every lane of every
    position at the published lower bound, "mixed" half of them."""
    rng = np.random.default_rng(seed)
    n = lines * seq
    q, k, v = (rng.normal(size=(n, h, d)) for _ in range(3))
    g = BOUND * rng.uniform(size=(n, h, d))
    if gate == "bound":
        g[:] = BOUND
    elif gate == "mixed":
        g[:, :, ::2] = BOUND
    beta = rng.uniform(size=(n, h))
    return tuple(jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta))


def run(args, seq, chunk=32, impl="chunked", dtype=jnp.float32):
    return kda_delta_rule(*args, seq, chunk=chunk, impl=impl, dtype=dtype,
                          lower_bound=BOUND)


# -- the closed form against the scan -----------------------------------------

@pytest.mark.parametrize("seq,chunk", [
    (8, 32), (16, 32), (32, 32),        # a line is one chunk, cut to it
    (32, 16), (32, 8), (64, 32),        # an entering state between chunks
])
@pytest.mark.parametrize("gate", ["random", "mixed", "bound"])
def test_the_closed_form_is_the_scan(seq, chunk, gate):
    """Finite and equal to the scan to float32's rounding — at the bound
    too, where 32 positions of −5 are e^−160 between a line's ends and a
    sub-block of 16 would be e^−80: sub-blocks of 8 about their own start
    keep either factor within e^±40. Over seeds, because what sub-blocks of
    16 lost (2e-4 on one position of a line in five seeded cases: a lane of
    k under 6e-4 leaving float32's normal range with e^−80) showed on some
    seeds only."""
    for seed in range(6):
        args = operands(seq=seq, seed=seed, gate=gate)
        want = run(args, seq, impl="scan")
        got = run(args, seq, chunk=chunk)
        assert bool(jnp.isfinite(got).all())
        assert float(jnp.abs(got - want).max()) < 5e-6, seed


def test_it_is_the_recurrence_written_out_in_numpy():
    lines, seq = 2, 32
    q, k, v, g, beta = (np.asarray(x, np.float64)
                        for x in operands(lines, seq, seed=3, gate="mixed"))
    q = q / np.sqrt((q * q).sum(-1, keepdims=True) + 1e-6) * D ** -0.5
    k = k / np.sqrt((k * k).sum(-1, keepdims=True) + 1e-6)
    want = np.zeros((lines * seq, H, D))
    for line in range(lines):
        state = np.zeros((H, D, D))
        for t in range(line * seq, (line + 1) * seq):
            state = state * np.exp(g[t])[..., None]
            u = beta[t][:, None] * (v[t] - np.einsum("hkv,hk->hv", state,
                                                     k[t]))
            state = state + k[t][:, :, None] * u[:, None, :]
            want[t] = np.einsum("hkv,hk->hv", state, q[t])
    args = operands(lines, seq, seed=3, gate="mixed")
    for impl in ("scan", "chunked"):
        got = np.asarray(run(args, seq, impl=impl))
        assert np.abs(got - want).max() < 2e-6, impl


@pytest.mark.parametrize("chunk,gate", [(32, "random"), (16, "random"),
                                        (32, "bound")])
def test_the_gradient_is_the_scans(chunk, gate):
    """Every operand's gradient, the gates' among them (through the
    exponentials about the reference points), finite at the bound too."""
    seq = 32
    args = operands(2, seq, seed=5, gate=gate)
    weights = jnp.asarray(np.random.default_rng(9).normal(
        size=(2 * seq, H, D)), jnp.float32)

    def loss(impl, chunk):
        return lambda *a: (kda_delta_rule(
            *a, seq, chunk=chunk, impl=impl, dtype=jnp.float32,
            lower_bound=BOUND) * weights).sum()

    want = jax.grad(loss("scan", 32), argnums=range(5))(*args)
    got = jax.grad(loss("chunked", chunk), argnums=range(5))(*args)
    for name, a, b in zip("qkvgb", got, want):
        assert bool(jnp.isfinite(a).all()), name
        assert float(jnp.abs(a - b).max()) < 2e-5, name


def test_a_lines_result_does_not_depend_on_its_neighbours():
    seq = 32
    args = operands(3, seq, seed=2)
    alone = tuple(x[seq:2 * seq] for x in args)
    assert float(jnp.abs(run(args, seq)[seq:2 * seq]
                         - run(alone, seq)).max()) < 1e-6


def test_an_all_pad_line_stays_finite_and_zero():
    """A PAD line's q, k and v are zero rows of the projections' output
    only up to the convolution; what reaches the core may be anything but
    has to stay finite, and zero values give zero."""
    seq = 32
    q, k, v, g, beta = operands(2, seq, seed=1, gate="bound")
    zero = jnp.zeros_like(v).at[:seq].set(v[:seq])
    out = run((q.at[seq:].set(0.0), k.at[seq:].set(0.0), zero, g, beta), seq)
    assert bool(jnp.isfinite(out).all())
    assert float(jnp.abs(out[seq:]).max()) == 0.0


def test_bfloat16_operands_stay_near_the_float32_core():
    seq = 32
    for gate in ("random", "bound"):
        args = operands(4, seq, seed=4, gate=gate)
        want = run(args, seq, impl="scan")
        got = run(args, seq, dtype=jnp.bfloat16)
        assert bool(jnp.isfinite(got).all())
        assert float(jnp.abs(got - want).max()) < 0.02 * float(
            jnp.abs(want).max())


def test_the_gate_lies_within_its_bound():
    rng = np.random.default_rng(0)
    n = 64
    f = jnp.asarray(rng.normal(size=(n, H, D)) * 30, jnp.float32)
    b = jnp.asarray(rng.normal(size=(n, H)), jnp.float32)
    a_log = jnp.log(jnp.asarray([0.001, 16.0]))
    g, beta = kda_gates(f, b, a_log, jnp.ones((H, D)), BOUND)
    assert g.shape == (n, H, D) and beta.shape == (n, H)
    assert float(g.min()) >= BOUND and float(g.max()) <= 0.0
    assert float(g[:, 1].min()) == BOUND          # saturated: AT the bound
    with np.errstate(over="ignore"):
        want = BOUND / (1 + np.exp(-np.exp(np.asarray(a_log))[:, None]
                                   * (np.asarray(f) + 1.0)))
    np.testing.assert_allclose(np.asarray(g), want, rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(beta),
                               1 / (1 + np.exp(-np.asarray(b))), rtol=1e-6)


def test_the_route_is_recorded_and_refused_by_name():
    assert kda_route("auto", 32, 32) == f"kda chunked 32/{KDA_BLOCK}"
    assert kda_route("chunked", 8, 32) == "kda chunked 8/8"
    assert kda_route("auto", 64, 32) == "kda chunked 32/8"
    assert kda_route("scan", 32, 32) == "kda scan"
    with pytest.raises(ValueError, match="kda impl 'fused'"):
        kda_route("fused", 32, 32)
    with pytest.raises(ValueError, match="do not divide"):
        kda_route("auto", 48, 32)
    with pytest.raises(ValueError, match="do not divide"):
        kda_route("auto", 20, 20)
    # a bound under which a sub-block's 8 positions pass e^40
    with pytest.raises(ValueError, match="lower_bound -6"):
        kda_route("auto", 32, 32, lower_bound=-6.0)
    assert kda_route("auto", 4, 32, lower_bound=-10.0) == "kda chunked 4/4"
    routes = {}
    with placement(1, delta_routes=routes):
        run(operands(3, 32), 32)
        run(operands(2, 16), 16, impl="scan")
    assert routes == {3: "kda chunked 32/8", 2: "kda scan"}


# -- group-limited routing ----------------------------------------------------

def sorted_choice(scores, bias, n_group, topk_group, top_k):
    """The grouped choice by sorting, in numpy: a group's score is the sum
    of its two largest entries; the experts of the best groups stand."""
    c = scores + bias
    n, e = c.shape
    by_group = c.reshape(n, n_group, e // n_group)
    group_score = np.sort(by_group, -1)[..., -2:].sum(-1)
    best = np.argsort(-group_score, -1, kind="stable")[:, :topk_group]
    standing = np.zeros((n, n_group), bool)
    np.put_along_axis(standing, best, True, axis=1)
    masked = np.where(np.repeat(standing, e // n_group, axis=1), c, -np.inf)
    return np.argsort(-masked, -1, kind="stable")[:, :top_k]


@pytest.mark.parametrize("experts,n_group,topk_group,top_k", [
    (32, 4, 2, 3), (64, 8, 4, 8), (16, 2, 1, 4), (24, 3, 3, 5)])
def test_the_grouped_choice_is_the_sorted_one(experts, n_group, topk_group,
                                              top_k):
    rng = np.random.default_rng(experts)
    x = jnp.asarray(rng.normal(size=(96, 12)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(12, experts)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(experts,)) * 0.1, jnp.float32)
    valid = jnp.asarray(rng.uniform(size=(96,)) > 0.1)
    out = route(x, router, bias, valid, top_k=top_k, norm_topk_prob=True,
                scaling=2.5, n_group=n_group, topk_group=topk_group)
    scores = 1 / (1 + np.exp(-np.asarray(x, np.float64)
                             @ np.asarray(router, np.float64)))
    want = sorted_choice(scores, np.asarray(bias, np.float64), n_group,
                         topk_group, top_k)
    got = np.asarray(out.experts)
    ok = np.asarray(valid)
    assert (got[~ok] == -1).all()
    assert (np.sort(got[ok], -1) == np.sort(want[ok], -1)).all()
    # no chosen expert lies outside the kept groups, and never more groups
    groups = got[ok] // (experts // n_group)
    assert max(len(set(row)) for row in groups) <= topk_group
    # the weights are the scores (never the bias), normalised and scaled
    w = np.take_along_axis(scores, got.clip(0), axis=1)
    w = w / w.sum(-1, keepdims=True) * 2.5
    np.testing.assert_allclose(np.asarray(out.weights)[ok], w[ok], rtol=2e-5)
    if topk_group == n_group:      # every group kept: the ungrouped choice
        plain = route(x, router, bias, valid, top_k=top_k,
                      norm_topk_prob=True, scaling=2.5)
        assert (np.asarray(plain.experts) == got).all()


def test_keep_groups_sets_the_other_groups_aside_and_refuses_by_name():
    choice = jnp.asarray([[0.9, 0.1, 0.5, 0.5, 0.2, 0.95, 0.3, 0.3]])
    kept = np.asarray(keep_groups(choice, 4, 2))
    # group scores 1.0, 1.0, 1.15, 0.6: groups 2 and 0 (the tie to the lower)
    assert np.isneginf(kept[0, 2:4]).all() and np.isneginf(kept[0, 6:]).all()
    assert (kept[0, [0, 1, 4, 5]] == np.asarray(choice)[0, [0, 1, 4, 5]]).all()
    with pytest.raises(ValueError, match="n_group 3"):
        keep_groups(choice, 3, 1)
    with pytest.raises(ValueError, match="topk_group 5"):
        keep_groups(choice, 4, 5)


def _route_of_before(x, router, bias, valid, *, top_k, norm_topk_prob,
                     scaling, scoring_func="sigmoid", norm_eps=1e-20):
    """ops/experts.py::route as the parent commit (4d26220) has it, kept
    here word for word: what one group has to trace."""
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if scoring_func == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    elif scoring_func == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"unknown scoring_func {scoring_func!r}")
    choice = scores + jax.lax.stop_gradient(bias.astype(jnp.float32))
    _, experts = jax.lax.top_k(choice, top_k)
    chosen = experts[..., None] == jnp.arange(scores.shape[-1])
    weights = jnp.where(chosen, scores[:, None, :], 0.0).sum(-1)
    if norm_topk_prob:
        weights = weights / (weights.sum(-1, keepdims=True) + norm_eps)
    weights = weights * scaling
    experts = jnp.where(valid[:, None], experts.astype(jnp.int32), -1)
    return expert_ops.Routing(experts, weights)


def _sigmoid_gate_of_before(out, gate):
    """ops/attention.py::sigmoid_gate as the parent commit has it."""
    return (out.astype(jnp.float32)
            * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(out.dtype)


@pytest.mark.parametrize("scoring_func", ["sigmoid", "softmax"])
def test_one_group_is_the_router_of_before_to_the_bit(scoring_func):
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(128, 16)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(16, 32)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(32,)) * 0.05, jnp.float32)
    valid = jnp.asarray(rng.uniform(size=(128,)) > 0.2)
    kw = dict(top_k=4, norm_topk_prob=True, scaling=2.5,
              scoring_func=scoring_func, norm_eps=1e-6)
    got = route(x, router, bias, valid, n_group=1, topk_group=1, **kw)
    want = _route_of_before(x, router, bias, valid, **kw)
    assert (np.asarray(got.experts) == np.asarray(want.experts)).all()
    assert (np.asarray(got.weights) == np.asarray(want.weights)).all()
    # and the same program: no operation more, none fewer, none renamed
    now = jax.jit(lambda *a: route(*a, **kw)).lower(x, router, bias, valid)
    before = jax.jit(lambda *a: _route_of_before(*a, **kw)).lower(
        x, router, bias, valid)
    assert now.as_text() == before.as_text()


# -- the lowered programs of the families that stand ---------------------------

def _tiny_scorer(family):
    if family == "moe_mla":
        from tests.test_moe_mla import ARCH
        from detectmateservice_tpu.models.moe_mla import (
            MoEMLAArch as Arch, MoEMLAConfig as Config, MoEMLAScorer as Scorer)
    elif family == "moe_conv":
        from tests.test_moe_conv import ARCH
        from detectmateservice_tpu.models.moe_conv import (
            MoEConvArch as Arch, MoEConvConfig as Config,
            MoEConvScorer as Scorer)
    elif family == "moe_delta":
        from tests.test_moe_delta import ARCH
        from detectmateservice_tpu.models.moe_delta import (
            MoEDeltaArch as Arch, MoEDeltaConfig as Config,
            MoEDeltaScorer as Scorer)
    else:
        from tests.test_moe_ssm import ARCH
        from detectmateservice_tpu.models.moe_ssm import (
            MoESSMArch as Arch, MoESSMConfig as Config,
            MoESSMScorer as Scorer)
    return Scorer(Config(arch=Arch.from_mapping(ARCH), vocab_size=64,
                         seq_len=32))


@pytest.mark.parametrize("family", ["moe_mla", "moe_conv", "moe_delta",
                                    "moe_ssm"])
def test_the_standing_families_lower_to_the_program_of_before(family,
                                                              monkeypatch):
    """PR 44 edits two functions of ``ops/`` that the accepted cells'
    programs pass through — ``experts.route`` (group-limited routing) and
    ``attention.sigmoid_gate`` (a head-wise gate) — and adds fields to
    ``ExpertSpec``. With the parent commit's functions put back in their
    place, each family's scoring program and train step lower to the same
    text, operation for operation: its scores stay the parent's, seed by seed
    (a kanana2 run read 0.10116 against 0.1 in PR 43 after a change had
    moved the initialiser's draws)."""
    def lowered():
        scorer = _tiny_scorer(family)
        params, opt_state = jax.eval_shape(
            lambda: scorer.init(jax.random.PRNGKey(0)))
        tokens = jax.ShapeDtypeStruct((8, 32), jnp.int32)
        return (jax.jit(scorer._score_impl).lower(params, tokens).as_text(),
                jax.jit(scorer._train_impl).lower(
                    params, opt_state, jax.ShapeDtypeStruct((2,), jnp.uint32),
                    tokens).as_text())

    now = lowered()
    monkeypatch.setattr(
        expert_ops, "route",
        lambda *a, n_group=1, topk_group=1, **kw: _route_of_before(*a, **kw))
    monkeypatch.setattr(attention_ops, "sigmoid_gate",
                        _sigmoid_gate_of_before)
    import detectmateservice_tpu.models.moe_delta as moe_delta
    monkeypatch.setattr(moe_delta, "sigmoid_gate", _sigmoid_gate_of_before)
    before = lowered()

    for a, b in zip(now, before):
        assert a == b


# -- per-head norms and the head-wise gate around latent attention -------------

def test_the_headwise_gate_is_one_value_a_head():
    rng = np.random.default_rng(1)
    out = jnp.asarray(rng.normal(size=(6, 3 * 4)), jnp.bfloat16)
    gate = jnp.asarray(rng.normal(size=(6, 3)), jnp.float32)
    got = sigmoid_gate(out, gate)
    assert got.dtype == jnp.bfloat16 and got.shape == out.shape
    want = sigmoid_gate(out, jnp.repeat(gate, 4, axis=-1))
    assert (np.asarray(got, np.float32) == np.asarray(want, np.float32)).all()
    # a gate of the output's own shape goes the way it always went
    full = jnp.asarray(rng.normal(size=(6, 12)), jnp.float32)
    assert (np.asarray(sigmoid_gate(out, full), np.float32) == np.asarray(
        _sigmoid_gate_of_before(out, full), np.float32)).all()


def test_the_head_norms_read_the_shared_rope_key_with_each_heads_own():
    rng = np.random.default_rng(2)
    n, heads, nope, rope, dv = 10, 3, 8, 4, 6
    q = jnp.asarray(rng.normal(size=(n, heads * (nope + rope))), jnp.float32)
    kv = jnp.asarray(rng.normal(size=(n, heads * (nope + dv))), jnp.float32)
    k_rope = jnp.asarray(rng.normal(size=(n, rope)), jnp.float32)
    q_scale, k_scale = (jnp.asarray(1 + 0.3 * rng.normal(size=(nope + rope,)),
                                    jnp.float32) for _ in range(2))
    got_q, got_k = latent_head_norms(q, kv, k_rope, q_scale, k_scale, 1e-6,
                                     heads, nope)
    assert got_q.shape == got_k.shape == (n, heads, nope + rope)
    for h in range(heads):
        whole = np.concatenate([
            np.asarray(kv).reshape(n, heads, -1)[:, h, :nope],
            np.asarray(k_rope)], -1)
        want = whole / np.sqrt((whole ** 2).mean(-1, keepdims=True) + 1e-6)
        np.testing.assert_allclose(np.asarray(got_k[:, h]),
                                   want * np.asarray(k_scale), rtol=2e-5)
    # behind the norm the rope parts differ by head
    assert float(jnp.abs(got_k[:, 0, nope:] - got_k[:, 1, nope:]).max()) > 1e-3


def test_per_head_attention_is_latent_attention_where_the_rope_key_is_shared():
    """With every head's rope part the same, the per-head core computes
    what ``latent_attention`` (``kanana2``'s, unchanged) computes from its
    own layout: the two differ only in who may hold a rope key."""
    rng = np.random.default_rng(3)
    b, s, heads, nope, rope, dv = 2, 8, 3, 8, 4, 6
    n = b * s
    q = rng.normal(size=(n, heads, nope + rope)).astype(np.float32)
    k_nope = rng.normal(size=(n, heads, nope)).astype(np.float32)
    k_rope = rng.normal(size=(n, rope)).astype(np.float32)
    v = rng.normal(size=(n, heads, dv)).astype(np.float32)
    mask = np.ones((b, s), bool)
    mask[1, 5:] = False
    k = np.concatenate([k_nope, np.broadcast_to(k_rope[:, None],
                                                (n, heads, rope))], -1)
    got = per_head_latent_attention(jnp.asarray(q), jnp.asarray(k),
                                    jnp.asarray(v), jnp.asarray(mask), nope,
                                    10000.0)
    want = latent_attention(
        jnp.asarray(np.concatenate([q[..., :nope].reshape(n, -1),
                                    q[..., nope:].reshape(n, -1)], -1)),
        jnp.asarray(np.concatenate([k_nope.reshape(n, -1),
                                    v.reshape(n, -1)], -1)),
        jnp.asarray(k_rope), jnp.asarray(mask), heads, nope, 10000.0,
        impl="einsum", causal=True)
    keep = mask.reshape(-1)
    np.testing.assert_allclose(np.asarray(got)[keep], np.asarray(want)[keep],
                               rtol=2e-5, atol=2e-6)
    with pytest.raises(ValueError, match="per-head key norms"):
        per_head_latent_attention(jnp.asarray(q), jnp.asarray(k),
                                  jnp.asarray(v), jnp.asarray(mask), nope,
                                  10000.0, impl="short")
