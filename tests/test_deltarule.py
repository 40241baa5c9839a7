"""The gated delta rule (ops/deltarule.py) on the CPU at small sizes: the
chunked closed form against the position-by-position scan at three chunk
lengths over a 32-long line — forward and gradient, so the state carried
between chunks and the triangular inverse's own reverse pass are both
exercised — a line's independence of its neighbours, the inverse against
numpy's, the route's record and refusals; and what the family's other new
operations add beside it: the 4-tap convolution with SiLU against a plain
loop (ops/shortconv.py) and the partial rotation (ops/attention.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from detectmateservice_tpu.ops.attention import (grouped_query_attention,
                                                 placement, rotary)
from detectmateservice_tpu.ops.deltarule import (delta_gates, delta_route,
                                                 gated_delta_rule,
                                                 unit_lower_inverse)
from detectmateservice_tpu.ops.shortconv import (causal_conv_silu,
                                                 gated_conv_xla)

SEQ, HK, HV, D = 32, 2, 4, 16


def operands(lines=3, seed=0, seq=SEQ):
    """Seeded q, k, v, g, beta for ``lines`` lines; line 1's tail and all of
    the last line are what a PAD tail gives at its worst: zero keys, queries
    and values."""
    rng = np.random.default_rng(seed)
    n = lines * seq
    q, k = (rng.normal(size=(n, HK, D)) for _ in range(2))
    v = rng.normal(size=(n, HV, D))
    g = -rng.uniform(0.0, 2.0, size=(n, HV))
    beta = rng.uniform(0.0, 1.0, size=(n, HV))
    for x in (q, k, v):
        x[seq + seq // 2:2 * seq] = 0.0
        x[(lines - 1) * seq:] = 0.0
    return tuple(jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta))


def run(args, chunk=32, impl="chunked", dtype=jnp.float32, seq=SEQ):
    return gated_delta_rule(*args, seq, chunk=chunk, impl=impl, dtype=dtype)


@pytest.mark.parametrize("chunk", [8, 16, 32])
def test_the_chunked_form_is_the_scan_forward_and_backward(chunk):
    args = operands()
    want = run(args, impl="scan")
    got = run(args, chunk)
    assert got.shape == (3 * SEQ, HV, D)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    assert float(jnp.abs(want).max()) > 0.1

    def loss(impl, c):
        return lambda *a: (run(a, c, impl) ** 2).sum()

    want_grads = jax.grad(loss("scan", 32), argnums=(0, 1, 2, 3, 4))(*args)
    got_grads = jax.grad(loss("chunked", chunk), argnums=(0, 1, 2, 3, 4))(
        *args)
    for name, a, b in zip("qkvgb", got_grads, want_grads):
        scale = float(jnp.abs(b).max())
        assert scale > 0 and bool(jnp.isfinite(a).all()), name
        assert float(jnp.abs(a - b).max()) < 1e-5 * max(scale, 1.0), name


def test_an_all_pad_line_and_a_pad_tail_stay_finite_and_zero():
    args = operands()
    for out in (run(args, 8), run(args, impl="scan")):
        out = np.asarray(out).reshape(3, SEQ, HV, D)
        assert np.isfinite(out).all()
        assert np.abs(out[2]).max() == 0.0          # nothing written or read
        assert np.abs(out[1, SEQ // 2:]).max() == 0.0   # q = 0 reads nothing
        assert np.abs(out[1, :SEQ // 2]).max() > 0.01


@pytest.mark.parametrize("chunk", [8, 32])
def test_a_lines_result_does_not_depend_on_its_neighbours(chunk):
    args = operands(lines=4, seed=1)
    whole = np.asarray(run(args, chunk)).reshape(4, SEQ, HV, D)
    alone = np.asarray(run(tuple(x[SEQ:2 * SEQ] for x in args), chunk))
    np.testing.assert_allclose(whole[1], alone.reshape(SEQ, HV, D), atol=1e-6)
    other = operands(lines=4, seed=2)
    mixed = tuple(jnp.concatenate([o[:SEQ], a[SEQ:2 * SEQ], o[2 * SEQ:]])
                  for a, o in zip(args, other))
    np.testing.assert_allclose(
        np.asarray(run(mixed, chunk)).reshape(4, SEQ, HV, D)[1], whole[1],
        atol=1e-6)


def test_it_is_the_recurrence_written_out_in_numpy():
    """Decay, the delta correction, the write, the read — in float64, with
    the L2 norms and the query's scale."""
    q, k, v, g, beta = (np.asarray(x, np.float64) for x in operands(lines=1))
    out = np.asarray(run(operands(lines=1), 16)).reshape(SEQ, HV, D)
    norm = lambda x: x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)  # noqa: E731
    q, k = norm(q) / np.sqrt(D), norm(k)
    for h in range(HV):
        state = np.zeros((D, D))
        for t in range(SEQ):
            state = state * np.exp(g[t, h])
            u = beta[t, h] * (v[t, h] - state.T @ k[t, h // 2])
            state = state + np.outer(k[t, h // 2], u)
            np.testing.assert_allclose(out[t, h], state.T @ q[t, h // 2],
                                       atol=2e-6)


def test_bfloat16_operands_stay_near_the_float32_core():
    args = operands(seed=3)
    want = np.asarray(run(args, impl="scan"))
    got = np.asarray(run(args, dtype=jnp.bfloat16))
    assert 1e-5 < np.abs(got - want).max() < 0.02


@pytest.mark.parametrize("c", [4, 8, 12, 16, 32])
def test_the_inverse_is_numpys_blocked_or_not(c):
    rng = np.random.default_rng(c)
    lanes = 5
    a = np.tril(rng.normal(size=(lanes, c, c)), -1)
    # whatever lies on or above the diagonal is ignored
    noisy = a + np.triu(rng.normal(size=(lanes, c, c)))
    got = np.asarray(unit_lower_inverse(
        jnp.asarray(np.moveaxis(noisy, 0, -1), jnp.float32)))
    want = np.linalg.inv(np.eye(c) + a)
    np.testing.assert_allclose(np.moveaxis(got, -1, 0), want, atol=2e-4,
                               rtol=2e-4)


def test_the_inverses_reverse_pass_is_autodiffs_of_a_solve():
    rng = np.random.default_rng(7)
    c, lanes = 16, 3
    a = jnp.asarray(np.moveaxis(np.tril(
        rng.normal(size=(lanes, c, c)) * 0.3, -1), 0, -1), jnp.float32)
    w = jnp.asarray(rng.normal(size=(c, c, lanes)), jnp.float32)

    def by_solve(a):
        m = jnp.moveaxis(a, -1, 0)
        inv = jnp.linalg.inv(jnp.eye(c) + jnp.tril(m, -1))
        return (jnp.moveaxis(inv, 0, -1) * w).sum()

    got = jax.grad(lambda a: (unit_lower_inverse(a) * w).sum())(a)
    want = jax.grad(by_solve)(a)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-3,
                               rtol=1e-3)
    assert float(jnp.abs(jnp.triu(jnp.moveaxis(got, -1, 0))).max()) == 0.0


def test_the_route_is_recorded_and_refused_by_name():
    assert delta_route("auto", 32, 32) == "chunked 32"
    assert delta_route("auto", 16, 32) == "chunked 16"     # cut to the line
    assert delta_route("chunked", 32, 8) == "chunked 8"
    assert delta_route("scan", 32, 8) == "scan"
    with pytest.raises(ValueError, match="pallas"):
        delta_route("pallas", 32, 32)
    with pytest.raises(ValueError, match="do not divide"):
        delta_route("auto", 32, 12)
    routes = {}
    with placement(1, None, None, routes):
        run(operands(), 16)
        run(operands(lines=2), impl="scan")
    assert routes == {3: "chunked 16", 2: "scan"}


def test_the_gates_are_the_published_ones():
    rng = np.random.default_rng(4)
    a, b = (jnp.asarray(rng.normal(size=(6, HV)), jnp.float32)
            for _ in range(2))
    a_log = jnp.log(jnp.asarray([0.5, 1.0, 4.0, 16.0]))
    dt_bias = jnp.ones((HV,))
    g, beta = delta_gates(a, b, a_log, dt_bias)
    want = -np.exp(np.asarray(a_log)) * np.log1p(np.exp(np.asarray(a) + 1.0))
    np.testing.assert_allclose(np.asarray(g), want, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(beta),
                               1.0 / (1.0 + np.exp(-np.asarray(b))),
                               rtol=1e-5)
    assert float(g.max()) < 0.0


# -- the 4-tap convolution with SiLU ------------------------------------------

def test_the_convolution_is_a_plain_loop_and_never_crosses_a_line():
    rng = np.random.default_rng(5)
    seq, lines, width, taps = 8, 3, 6, 4
    x = rng.normal(size=(lines * seq, width)).astype(np.float32)
    w = rng.normal(size=(width, taps)).astype(np.float32)
    out = np.asarray(causal_conv_silu(jnp.asarray(x), jnp.asarray(w), seq))
    by_line = x.reshape(lines, seq, width)
    for t in range(seq):
        pre = sum(w[:, j] * by_line[:, t - (taps - 1) + j]
                  for j in range(taps) if t - (taps - 1) + j >= 0)
        np.testing.assert_allclose(
            out.reshape(lines, seq, width)[:, t], pre / (1.0 + np.exp(-pre)),
            rtol=1e-5, atol=1e-6)
    # a line's first position sees its own input alone
    first = x[seq] * w[:, taps - 1]
    np.testing.assert_allclose(out[seq], first / (1.0 + np.exp(-first)),
                               rtol=1e-5, atol=1e-6)
    assert causal_conv_silu(jnp.asarray(x, jnp.bfloat16), jnp.asarray(w),
                            seq).dtype == jnp.bfloat16


def test_the_gated_form_shares_the_tap_walk():
    """``gated_conv_xla`` is the same walk between two gates: with ones for
    both gates and three taps it is the ungated convolution."""
    rng = np.random.default_rng(6)
    seq, width = 8, 4
    x = jnp.asarray(rng.normal(size=(2 * seq, width)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(width, 3)), jnp.float32)
    ones = jnp.ones_like(x)
    gated = gated_conv_xla(jnp.concatenate([ones, ones, x], -1), w, seq)
    from detectmateservice_tpu.ops.shortconv import causal_taps

    np.testing.assert_allclose(np.asarray(gated),
                               np.asarray(causal_taps(x, w, seq)), atol=1e-6)


# -- the partial rotation -------------------------------------------------------

def test_partial_rotary_touches_the_first_lanes_only():
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.normal(size=(2, 8, 3, 256)), jnp.float32)  # [B,S,H,D]
    turned = rotary(x, 1e7, interleaved=False, heads_inside=True,
                    rotary_dim=64)
    np.testing.assert_array_equal(np.asarray(turned[..., 64:]),
                                  np.asarray(x[..., 64:]))
    whole = rotary(x[..., :64], 1e7, interleaved=False, heads_inside=True)
    np.testing.assert_allclose(np.asarray(turned[..., :64]),
                               np.asarray(whole), atol=1e-6)
    assert float(jnp.abs(turned[:, 1:, :, :64] - x[:, 1:, :, :64]).max()) > 0.1
    np.testing.assert_allclose(np.asarray(turned[:, 0]), np.asarray(x[:, 0]),
                               atol=1e-6)                  # position 0
    # the interleaved form keeps its pairs inside the first lanes too
    inter = rotary(x, 1e7, heads_inside=True, rotary_dim=64)
    np.testing.assert_array_equal(np.asarray(inter[..., 64:]),
                                  np.asarray(x[..., 64:]))


def test_grouped_query_attention_turns_only_the_rotary_width():
    rng = np.random.default_rng(9)
    b, s, h, g, d = 2, 8, 4, 2, 32
    q = jnp.asarray(rng.normal(size=(b * s, h * d)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(b * s, g * d)), jnp.float32)
            for _ in range(2))
    mask = jnp.ones((b, s), bool)
    whole = grouped_query_attention(q, k, v, mask, h, g, 1e7, platform="cpu")
    same = grouped_query_attention(q, k, v, mask, h, g, 1e7, platform="cpu",
                                   rotary_dim=d)
    part = grouped_query_attention(q, k, v, mask, h, g, 1e7, platform="cpu",
                                   rotary_dim=8)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(same), atol=1e-6)
    assert float(jnp.abs(part - whole).max()) > 1e-3
