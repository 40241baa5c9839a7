"""The selective state-space operation (ops/ssd.py) at tiny sizes on the CPU:
the chunked closed form against the recurrence position by position (the
benchmark reference's scan, itself held to a numpy float64 loop in
tests/benchmark_tests/test_bench_moe_ssm.py) with one chunk a line and with
8- and 16-long chunks so that the entering state works, groups of heads, its
gradient, lines that never read their neighbours, and the refusals; with it
what the new family asks of the operations it shares: the short
convolution's optional bias (ops/shortconv.py), the non-gated ``relu²``
expert unit (ops/experts.py), attention without rotary positions
(ops/attention.py) and the router's choice of 22."""
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
from detectmateservice_tpu.ops import experts as expert_ops  # noqa: E402
from detectmateservice_tpu.ops.attention import (  # noqa: E402
    grouped_query_attention)
from detectmateservice_tpu.ops.shortconv import (  # noqa: E402
    causal_conv_silu, causal_taps)
from detectmateservice_tpu.ops.ssd import state_space_scan  # noqa: E402

LINES, SEQ, H, G, P, NS = 3, 32, 4, 2, 8, 6


def operands(seed=0, lines=LINES, seq=SEQ, h=H, g=G):
    rng = np.random.default_rng(seed)
    n = lines * seq
    return dict(
        x=jnp.asarray(rng.normal(size=(n, h, P)), jnp.float32),
        b=jnp.asarray(rng.normal(size=(n, g, NS)), jnp.float32),
        c=jnp.asarray(rng.normal(size=(n, g, NS)), jnp.float32),
        dt=jnp.asarray(rng.uniform(0.01, 1.0, size=(n, h)), jnp.float32),
        a=-jnp.asarray(rng.uniform(0.5, 8.0, size=(h,)), jnp.float32),
        d=jnp.asarray(rng.normal(size=(h,)), jnp.float32))


def step_by_step(ops, seq=SEQ, cast=lambda t: t):
    """The recurrence through the reference's scan, by line, a group's B and
    C repeated for its heads."""
    n, h, _ = ops["x"].shape
    g = ops["b"].shape[1]

    def lines(t):
        return t.reshape(n // seq, seq, *t.shape[1:])

    b, c = (jnp.repeat(lines(ops[k]), h // g, axis=2) for k in ("b", "c"))
    out = reference.state_space(lines(ops["x"]), b, c, lines(ops["dt"]),
                                ops["a"], ops["d"], cast)
    return out.reshape(n, h, P)


def closed_form(ops, chunk, dtype=jnp.float32, seq=SEQ):
    return state_space_scan(ops["x"], ops["b"], ops["c"], ops["dt"],
                            ops["a"], ops["d"], seq, chunk=chunk, dtype=dtype)


# -- the closed form against the recurrence -----------------------------------

@pytest.mark.parametrize("chunk", [8, 16, 32, 128])
def test_the_closed_form_is_the_recurrence(chunk):
    """One chunk a line (32, and 128 cut to the line) and several with the
    state carried between them (8, 16): float32's error."""
    ops = operands()
    want = np.asarray(step_by_step(ops))
    got = np.asarray(closed_form(ops, chunk))
    assert got.shape == (LINES * SEQ, H, P) and got.dtype == np.float32
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("chunk", [8, 32])
def test_bfloat16_operands_stay_near_and_float8_does_not(chunk):
    """Products in bfloat16 with float32 accumulation: a hundredth of the
    result's size; the reference's float8 control is ten times further."""
    ops = operands(1)
    want = np.asarray(step_by_step(ops))
    got = np.asarray(closed_form(ops, chunk, jnp.bfloat16))
    scale = np.abs(want).mean()
    assert np.abs(got - want).mean() < 0.02 * scale
    low = lambda t: t.astype(jnp.float8_e4m3fn).astype(jnp.float32)  # noqa: E731
    control = np.asarray(step_by_step(ops, cast=low))
    assert np.abs(control - want).mean() > 3 * np.abs(got - want).mean()


@pytest.mark.parametrize("h,g", [(4, 1), (4, 2), (4, 4), (6, 3)])
def test_a_head_reads_its_groups_b_and_c(h, g):
    ops = operands(2, h=h, g=g)
    want = np.asarray(step_by_step(ops))
    for chunk in (8, 32):
        np.testing.assert_allclose(np.asarray(closed_form(ops, chunk)), want,
                                   rtol=2e-4, atol=2e-4)
    # another group's B moves only that group's heads
    changed = dict(ops, b=ops["b"].at[:, 0].add(1.0))
    moved = np.abs(np.asarray(closed_form(changed, 32))
                   - np.asarray(closed_form(ops, 32))).max((0, 2))
    assert (moved[:h // g] > 1e-3).all()
    assert (moved[h // g:] == 0).all()


def test_the_state_decays_and_the_skip_adds_d_times_x():
    """With B = C = 0 the output is D x; with A very negative the state
    forgets and position t reads its own write alone: Δ (C·B) x + D x."""
    ops = operands(3)
    zero = dict(ops, b=jnp.zeros_like(ops["b"]))
    np.testing.assert_allclose(
        np.asarray(closed_form(zero, 16)),
        np.asarray(ops["d"][:, None] * ops["x"]), atol=1e-6)
    forgetful = dict(ops, a=jnp.full((H,), -200.0),
                     dt=jnp.ones_like(ops["dt"]))
    cb = jnp.einsum("ngs,ngs->ng", ops["c"], ops["b"])
    own = (jnp.repeat(cb, H // G, axis=1)[..., None] + ops["d"][:, None]
           ) * ops["x"]
    np.testing.assert_allclose(np.asarray(closed_form(forgetful, 8)),
                               np.asarray(own), rtol=1e-4, atol=1e-4)


def test_a_line_never_reads_its_neighbours_and_is_causal():
    ops = dict(operands(4), a=jnp.full((H,), -0.1))     # a slow decay
    base = np.asarray(closed_form(ops, 8))
    t = SEQ + 11                                    # line 1, position 11
    changed = dict(ops, x=ops["x"].at[t].add(1.0))
    after = np.asarray(closed_form(changed, 8))
    moved = np.flatnonzero(np.abs(after - base).max((1, 2)) > 1e-7)
    assert moved.min() == t and moved.max() == 2 * SEQ - 1
    assert len(moved) == SEQ - 11                   # to the line's end


def test_the_gradient_is_the_recurrences():
    ops = operands(5, lines=2)
    keys = ("x", "b", "c", "dt", "a", "d")

    def loss(fn, *args):
        return (fn(dict(zip(keys, args))) ** 2).sum()

    args = tuple(ops[k] for k in keys)
    want = jax.grad(lambda *a: loss(step_by_step, *a),
                    argnums=tuple(range(6)))(*args)
    for chunk in (8, 32):
        got = jax.grad(lambda *a: loss(lambda o: closed_form(o, chunk), *a),
                       argnums=tuple(range(6)))(*args)
        for key, g_got, g_want in zip(keys, got, want):
            assert np.isfinite(np.asarray(g_got)).all(), key
            np.testing.assert_allclose(
                np.asarray(g_got), np.asarray(g_want), rtol=2e-3,
                atol=2e-3 * float(np.abs(np.asarray(g_want)).max()),
                err_msg=key)


@pytest.mark.parametrize("change,named", [
    (dict(seq=24, chunk=16), "chunks of 16 in lines of 24"),
    (dict(seq=40, chunk=8), "do not divide 96 tokens"),
    (dict(g=3), "3 groups do not divide 4 heads"),
])
def test_shapes_that_do_not_divide_are_refused_by_name(change, named):
    ops = operands(0, g=change.get("g", G))
    with pytest.raises(ValueError, match=named):
        closed_form(ops, change.get("chunk", 8), seq=change.get("seq", SEQ))


def test_a_long_line_of_small_steps_stays_finite():
    """128 positions in one chunk at the published time steps (0.001 to
    0.1) and decays of 1 to 16: the mask's exponent is clipped at 0, so
    nothing above the diagonal overflows before it is dropped."""
    rng = np.random.default_rng(6)
    ops = operands(6, lines=2, seq=128)
    ops["dt"] = jnp.asarray(rng.uniform(0.001, 0.1, size=ops["dt"].shape),
                            jnp.float32)
    ops["a"] = -jnp.arange(1.0, H + 1) * 4
    got = np.asarray(closed_form(ops, 128, seq=128))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, np.asarray(step_by_step(ops, seq=128)),
                               rtol=5e-4, atol=5e-4)
    far = dict(ops, a=jnp.full((H,), -300.0), dt=jnp.ones_like(ops["dt"]))
    assert np.isfinite(np.asarray(closed_form(far, 128, seq=128))).all()


# -- the short convolution's bias ------------------------------------------------

def test_the_convolution_takes_a_bias_before_its_silu():
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(2 * 16, 12)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(12, 4)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(12,)), jnp.float32)
    want = jax.nn.silu(reference.short_conv(x.reshape(2, 16, 12), w) + bias)
    got = causal_conv_silu(x, w, 16, bias)
    np.testing.assert_allclose(np.asarray(got).reshape(2, 16, 12),
                               np.asarray(want), rtol=1e-5, atol=1e-5)
    # none for the callers that were there: the tap walk and SiLU alone
    plain = causal_conv_silu(x, w, 16)
    np.testing.assert_array_equal(
        np.asarray(plain), np.asarray(jax.nn.silu(causal_taps(x, w, 16))))
    np.testing.assert_array_equal(
        np.asarray(causal_conv_silu(x, w, 16, jnp.zeros(12))),
        np.asarray(plain))
    assert float(jnp.abs(got - plain).max()) > 1e-2
    low = causal_conv_silu(x.astype(jnp.bfloat16), w, 16, bias)
    assert low.dtype == jnp.bfloat16


def test_the_bias_takes_a_gradient():
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.normal(size=(16, 4)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 4)), jnp.float32)
    grad = jax.grad(lambda b: causal_conv_silu(x, w, 8, b).sum())(
        jnp.zeros(4))
    assert np.isfinite(np.asarray(grad)).all()
    assert float(jnp.abs(grad).min()) > 0


# -- the non-gated unit among the routed experts -------------------------------

@pytest.mark.parametrize("chunk_rows", [None, 64])
def test_routed_experts_without_a_gate_are_relu_squared_units(chunk_rows):
    """``gate=None``: ``down(relu(up·x)²)``, the held experts' part by the
    sorted walk against every expert densely."""
    rng = np.random.default_rng(9)
    n, d, m, e_all, k, held, offset = 64, 16, 24, 8, 3, 4, 2
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(d, e_all)), jnp.float32)
    up = jnp.asarray(rng.normal(size=(e_all, d, m)) * 0.3, jnp.float32)
    down = jnp.asarray(rng.normal(size=(e_all, m, d)) * 0.3, jnp.float32)
    valid = jnp.asarray(rng.random(n) > 0.1)
    routing = expert_ops.route(x, router, jnp.zeros(e_all), valid, top_k=k,
                               norm_topk_prob=True, scaling=2.5)
    out, counts = expert_ops.routed_experts(
        x, routing, None, up[offset:offset + held],
        down[offset:offset + held], offset=offset, chunk_rows=chunk_rows)
    want = jnp.zeros((n, d))
    for e in range(offset, offset + held):
        w_e = (routing.weights * (routing.experts == e)).sum(-1)
        want += w_e[:, None] * (jnp.square(jax.nn.relu(x @ up[e])) @ down[e])
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-4,
                               atol=1e-4)
    assert int(counts.sum()) == int(((routing.experts >= offset)
                                     & (routing.experts < offset + held)
                                     ).sum())
    # a gated unit of the same matrices is another function
    gated, _ = expert_ops.routed_experts(
        x, routing, up[offset:offset + held], up[offset:offset + held],
        down[offset:offset + held], offset=offset, chunk_rows=chunk_rows)
    assert float(jnp.abs(gated - out).max()) > 1e-2


def test_the_non_gated_walk_takes_a_gradient_in_every_operand():
    rng = np.random.default_rng(10)
    n, d, m = 32, 8, 12
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(d, 4)), jnp.float32)
    up = jnp.asarray(rng.normal(size=(2, d, m)) * 0.3, jnp.float32)
    down = jnp.asarray(rng.normal(size=(2, m, d)) * 0.3, jnp.float32)

    def loss(x, up, down):
        routing = expert_ops.route(x, router, jnp.zeros(4),
                                   jnp.ones(n, bool), top_k=2,
                                   norm_topk_prob=True, scaling=1.0)
        return expert_ops.routed_experts(x, routing, None, up, down,
                                         offset=1)[0].sum()

    grads = jax.grad(loss, argnums=(0, 1, 2))(x, up, down)
    for g in grads:
        assert np.isfinite(np.asarray(g)).all()
        assert float(jnp.abs(g).max()) > 0


def test_the_router_chooses_22_of_512_by_the_biased_score():
    """The published shape of the choice: sigmoid scores over 512, 22
    chosen by score + bias, weights the unbiased scores over their sum
    (+ 1e-20) times the scaling factor — the reference's, gathered."""
    rng = np.random.default_rng(11)
    n, d, e_all, k = 48, 32, 512, 22
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(d, e_all)) * 0.3, jnp.float32)
    bias = jnp.asarray(rng.normal(size=(e_all,)) * 0.05, jnp.float32)
    valid = jnp.ones(n, bool).at[5].set(False)
    routing = expert_ops.route(x, router, bias, valid, top_k=k,
                               norm_topk_prob=True, scaling=5.0)
    chosen, w = reference.routing(x, router, bias, {
        "num_experts_per_tok": k, "routed_scaling_factor": 5})
    assert routing.experts.shape == (n, k)
    np.testing.assert_array_equal(np.asarray(routing.experts[:5]),
                                  np.asarray(chosen[:5]))
    assert (np.asarray(routing.experts[5]) == -1).all()
    np.testing.assert_allclose(np.asarray(routing.weights),
                               np.asarray(w), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(routing.weights.sum(-1)), 5.0,
                               rtol=1e-5)


# -- attention without rotary positions ---------------------------------------------

def test_grouped_query_attention_without_rotary_positions():
    """``rotary_dim=0``: plain causal softmax attention, each key/value head
    serving its query heads; any other value turns q and k."""
    rng = np.random.default_rng(12)
    b, s, h, g, d = 2, 8, 4, 2, 16
    q = jnp.asarray(rng.normal(size=(b * s, h * d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b * s, g * d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b * s, g * d)), jnp.float32)
    mask = jnp.ones((b, s), bool).at[1, 6:].set(False)
    got = grouped_query_attention(q, k, v, mask, h, g, 0.0, impl="einsum",
                                  platform="cpu", rotary_dim=0)
    qh = q.reshape(b, s, h, d)
    kh = jnp.repeat(k.reshape(b, s, g, d), h // g, axis=2)
    vh = jnp.repeat(v.reshape(b, s, g, d), h // g, axis=2)
    logits = jnp.einsum("bshd,bthd->bhst", qh, kh) / np.sqrt(d)
    see = mask[:, None, None, :] & jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(see, logits, -1e30), axis=-1)
    want = jnp.einsum("bhst,bthd->bshd", probs, vh).reshape(b * s, h * d)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)
    turned = grouped_query_attention(q, k, v, mask, h, g, 10000.0,
                                     impl="einsum", platform="cpu")
    assert float(jnp.abs(turned - got).max()) > 1e-3
