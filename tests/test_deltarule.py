"""The gated delta rule (ops/deltarule.py) on the CPU at small sizes: the
chunked closed form against the position-by-position scan at three chunk
lengths over a 32-long line — forward and gradient, so the state carried
between chunks and the triangular inverse's own reverse pass are both
exercised — and the kernel ``gated_delta`` in the Pallas interpreter beside
it (heads of 128, as its tiles want them; forward, its ``custom_vjp``,
PAD lines, the published head counts, q | k | v read in place); a line's
independence of its neighbours and of the tile and block it falls in, the
inverse against numpy's, the route's record and refusals; and what the
family's other new
operations add beside it: the 4-tap convolution with SiLU against a plain
loop (ops/shortconv.py) and the partial rotation (ops/attention.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from detectmateservice_tpu.ops.attention import (grouped_query_attention,
                                                 placement, rotary)
from detectmateservice_tpu.ops.deltarule import (Heads, delta_gates,
                                                 delta_route,
                                                 gated_delta_rule,
                                                 unit_lower_inverse)
from detectmateservice_tpu.ops.shortconv import (causal_conv_silu,
                                                 gated_conv_xla)

SEQ, HK, HV, D = 32, 2, 4, 16
# a form of the operation as (impl, chunk, head width): the kernel wants
# heads in whole lane groups and runs in the Pallas interpreter here
CHUNKED_8, CHUNKED_32, SCAN = ("chunked", 8, D), ("chunked", 32, D), (
    "scan", 32, D)
FUSED = ("fused", 32, 128)


def operands(lines=3, seed=0, seq=SEQ, d=D, hk=HK, hv=HV):
    """Seeded q, k, v, g, beta for ``lines`` lines; line 1's tail and all of
    the last line are what a PAD tail gives at its worst: zero keys, queries
    and values."""
    rng = np.random.default_rng(seed)
    n = lines * seq
    q, k = (rng.normal(size=(n, hk, d)) for _ in range(2))
    v = rng.normal(size=(n, hv, d))
    g = -rng.uniform(0.0, 2.0, size=(n, hv))
    beta = rng.uniform(0.0, 1.0, size=(n, hv))
    for x in (q, k, v):
        x[seq + seq // 2:2 * seq] = 0.0
        x[(lines - 1) * seq:] = 0.0
    return tuple(jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta))


def run(args, chunk=32, impl="chunked", dtype=jnp.float32, seq=SEQ, **kw):
    return gated_delta_rule(*args, seq, chunk=chunk, impl=impl, dtype=dtype,
                            **kw)


@pytest.mark.parametrize("impl,chunk,d", [
    ("chunked", 8, D), ("chunked", 16, D), CHUNKED_32, FUSED])
def test_the_closed_forms_are_the_scan_forward_and_backward(impl, chunk, d):
    """The chunked form at three chunk lengths, and the kernel with its
    ``custom_vjp`` (whose backward is the chunked form's), against the
    position-by-position scan."""
    args = operands(d=d)
    want = run(args, impl="scan")
    got = run(args, chunk, impl)
    assert got.shape == (3 * SEQ, HV, d)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    assert float(jnp.abs(want).max()) > 0.04

    def loss(impl, c):
        return lambda *a: (run(a, c, impl) ** 2).sum()

    want_grads = jax.grad(loss("scan", 32), argnums=(0, 1, 2, 3, 4))(*args)
    got_grads = jax.grad(loss(impl, chunk), argnums=(0, 1, 2, 3, 4))(*args)
    for name, a, b in zip("qkvgb", got_grads, want_grads):
        scale = float(jnp.abs(b).max())
        assert scale > 0 and bool(jnp.isfinite(a).all()), name
        assert float(jnp.abs(a - b).max()) < 1e-5 * max(scale, 1.0), name


def test_the_kernels_backward_is_the_chunked_forms_gradient():
    args = operands(d=128, seed=5)
    w = jnp.asarray(np.random.default_rng(6).normal(size=(3 * SEQ, HV, 128)),
                    jnp.float32)

    def loss(impl):
        return lambda *a: (run(a, 32, impl) * w).sum()

    got = jax.grad(loss("fused"), argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(loss("chunked"), argnums=(0, 1, 2, 3, 4))(*args)
    for name, a, b in zip("qkvgb", got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6,
                                   err_msg=name)


@pytest.mark.parametrize("lines", [4, 5])
def test_the_kernel_at_the_published_head_counts(lines):
    """16 key and 32 value heads of 128, lines of 32: one whole 128-token
    tile, and five lines (three empty ones pad the second tile)."""
    args = operands(lines=lines, seed=7, d=128, hk=16, hv=32)
    got = np.asarray(run(args, impl="fused"))
    assert got.shape == (lines * SEQ, 32, 128)
    np.testing.assert_allclose(got, np.asarray(run(args, impl="scan")),
                               atol=2e-6)
    np.testing.assert_allclose(got, np.asarray(run(args)), atol=2e-6)


def test_the_kernel_reads_q_k_v_in_place_from_the_array_they_were_cut_from():
    q, k, v, g, beta = operands(lines=8, seed=8, d=128)
    mixed = jnp.concatenate([x.reshape(8 * SEQ, -1) for x in (q, k, v)], -1)
    apart = run((q, k, v, g, beta), impl="fused")
    in_place = run((q, k, v, g, beta), impl="fused", mixed=mixed)
    np.testing.assert_array_equal(np.asarray(in_place), np.asarray(apart))
    # the other forms take their slices and never look at it
    np.testing.assert_array_equal(
        np.asarray(run((q, k, v, g, beta), mixed=mixed * 0.0)),
        np.asarray(run((q, k, v, g, beta))))

    def loss(m):
        return (gated_delta_rule(*Heads(HK, HV, 128, 128).split(m), g, beta,
                                 SEQ, impl="fused", dtype=jnp.float32,
                                 mixed=m) ** 2).sum()

    def plain_loss(m):
        return (gated_delta_rule(*Heads(HK, HV, 128, 128).split(m), g, beta,
                                 SEQ, impl="chunked", dtype=jnp.float32
                                 ) ** 2).sum()

    np.testing.assert_allclose(np.asarray(jax.grad(loss)(mixed)),
                               np.asarray(jax.grad(plain_loss)(mixed)),
                               atol=1e-6)


@pytest.mark.parametrize("impl,chunk,d", [CHUNKED_8, SCAN, FUSED])
def test_an_all_pad_line_and_a_pad_tail_stay_finite_and_zero(impl, chunk, d):
    out = np.asarray(run(operands(d=d), chunk, impl)).reshape(3, SEQ, HV, d)
    assert np.isfinite(out).all()
    assert np.abs(out[2]).max() == 0.0          # nothing written or read
    assert np.abs(out[1, SEQ // 2:]).max() == 0.0   # q = 0 reads nothing
    assert np.abs(out[1, :SEQ // 2]).max() > 0.01


@pytest.mark.parametrize("impl,chunk,d", [CHUNKED_8, CHUNKED_32, FUSED])
def test_a_lines_result_does_not_depend_on_its_neighbours(impl, chunk, d):
    args = operands(lines=4, seed=1, d=d)
    whole = np.asarray(run(args, chunk, impl)).reshape(4, SEQ, HV, d)
    alone = np.asarray(run(tuple(x[SEQ:2 * SEQ] for x in args), chunk, impl))
    np.testing.assert_allclose(whole[1], alone.reshape(SEQ, HV, d), atol=1e-6)
    other = operands(lines=4, seed=2, d=d)
    mixed = tuple(jnp.concatenate([o[:SEQ], a[SEQ:2 * SEQ], o[2 * SEQ:]])
                  for a, o in zip(args, other))
    np.testing.assert_allclose(
        np.asarray(run(mixed, chunk, impl)).reshape(4, SEQ, HV, d)[1],
        whole[1], atol=1e-6)


def test_a_lines_result_does_not_depend_on_the_block_it_falls_in():
    """A grid step owns 128 (value head, tile) units, a lane each: 64 tiles
    of a key head with two value heads. 262 lines are 66 tiles, the last
    half empty: a whole block and one that the call's rows do not fill;
    their first 256 are one block alone, the other six two tiles of a short
    one."""
    from detectmateservice_tpu.ops.deltarule import _block_tiles

    assert [_block_tiles(t, 2) for t in (1, 64, 66, 256)] == [64] * 4
    assert [_block_tiles(t, 2, True) for t in (1, 2, 64, 66)] == [1, 2, 64, 64]
    assert _block_tiles(256, 1) == 128 and _block_tiles(256, 16) == 8
    args = operands(lines=262, seed=9, d=128, hk=1, hv=2)
    whole = np.asarray(run(args, impl="fused"))
    head = np.asarray(run(tuple(x[:256 * SEQ] for x in args), impl="fused"))
    tail = np.asarray(run(tuple(x[256 * SEQ:] for x in args), impl="fused"))
    np.testing.assert_allclose(whole[:256 * SEQ], head, atol=1e-6)
    np.testing.assert_allclose(whole[256 * SEQ:], tail, atol=1e-6)
    np.testing.assert_allclose(whole, np.asarray(run(args, impl="scan")),
                               atol=2e-6)


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


@pytest.mark.parametrize("impl,chunk,d", [CHUNKED_32, FUSED])
def test_bfloat16_operands_stay_near_the_float32_core(impl, chunk, d):
    args = operands(seed=3, d=d)
    want = np.asarray(run(args, impl="scan"))
    got = np.asarray(run(args, chunk, impl, dtype=jnp.bfloat16))
    assert 1e-5 < np.abs(got - want).max() < 0.02
    # as served: the operands arrive in bfloat16 and are read as they are
    served = tuple(x.astype(jnp.bfloat16) for x in args[:3]) + args[3:]
    got = np.asarray(run(served, chunk, impl, dtype=jnp.bfloat16))
    want = np.asarray(run(served, impl="scan"))
    assert got.dtype == np.float32
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


@pytest.mark.parametrize("platform,rows,seq,chunk,dk,dv,mesh,want", [
    ("tpu", 256, 32, 32, 128, 128, 1, "fused"),
    ("tpu", 1024, 32, 32, 128, 128, 1, "fused"),
    ("tpu", 1024, 16, 32, 256, 128, 1, "fused"),     # the chunk cut to the line
    ("cpu", 1024, 32, 32, 128, 128, 1, "chunked 32"),
    ("tpu", 1024, 32, 32, 128, 128, 4, "chunked 32"),    # a mesh
    ("tpu", 32, 32, 32, 128, 128, 1, "chunked 32"),      # the fit's step
    ("tpu", 1024, 64, 32, 128, 128, 1, "chunked 32"),    # an entering state
    ("tpu", 1024, 32, 32, 64, 64, 1, "chunked 32"),      # half a lane group
    ("tpu", 1024, 32, 32, 128, 192, 1, "chunked 32"),
    ("tpu", 1024, 24, 32, 128, 128, 1, "chunked 24"),    # 24 does not fill 128
    ("tpu", 1024, 4, 32, 128, 128, 1, "chunked 4"),      # half a sublane tile
])
def test_auto_takes_the_kernel_where_it_can_see_that_it_fits(
        platform, rows, seq, chunk, dk, dv, mesh, want):
    assert delta_route("auto", seq, chunk, platform, rows, dk, dv,
                       mesh) == want
    # a name forces, whatever the call looks like
    assert delta_route("fused", seq, chunk, platform, rows, dk, dv,
                       mesh) == "fused"
    assert delta_route("chunked", seq, chunk, platform, rows, dk, dv,
                       mesh) == f"chunked {min(seq, chunk)}"
    assert delta_route("scan", seq, chunk, platform, rows, dk, dv,
                       mesh) == "scan"
    # value heads a key head: a power of two that leaves each 8 tiles
    for rep, fused in ((2, True), (16, True), (3, False), (32, False)):
        assert (delta_route("auto", seq, chunk, platform, rows, dk, dv,
                            mesh, rep) == "fused") == (fused
                                                       and want == "fused")


def test_the_route_is_recorded_and_refused_by_name():
    assert delta_route("auto", 32, 32) == "chunked 32"
    assert delta_route("auto", 16, 32) == "chunked 16"     # cut to the line
    assert delta_route("chunked", 32, 8) == "chunked 8"
    assert delta_route("scan", 32, 8) == "scan"
    assert delta_route("fused", 32, 32) == "fused"
    with pytest.raises(ValueError, match="pallas"):
        delta_route("pallas", 32, 32)
    with pytest.raises(ValueError, match="do not divide"):
        delta_route("auto", 32, 12)
    routes = {}
    with placement(1, None, None, routes):
        run(operands(), 16)
        run(operands(lines=2), impl="scan")
        run(operands(lines=4, d=128), impl="fused")
        run(operands(lines=5, d=128), impl="auto", platform="cpu")
    assert routes == {3: "chunked 16", 2: "scan", 4: "fused",
                      5: "chunked 32"}
    # forced where it cannot tile: refused, with what would
    for args, kw in ((operands(), {}), (operands(d=128), {"chunk": 16})):
        with pytest.raises(ValueError, match="do not tile"):
            run(args, impl="fused", **kw)


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
