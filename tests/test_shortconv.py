"""The gated short convolution's core (ops/shortconv.py) on the CPU: both
forms — XLA's and the Pallas kernel in the interpreter — against
``jax.lax.conv_general_dilated`` on ``[lines, S, D]``; causal, blind to PAD
to the right, and **never reading across a line's edge** in the token-major
layout; the backward pass; the route's table."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from detectmateservice_tpu.ops import shortconv
from detectmateservice_tpu.ops.attention import placement

LINES, SEQ, WIDTH, TAPS = 6, 16, 128, 3
IMPLS = ("xla", "fused")


def operands(seed=0, lines=LINES, seq=SEQ, width=WIDTH, taps=TAPS,
             dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    bcx = jnp.asarray(rng.normal(size=(lines * seq, 3 * width)), dtype)
    weight = jnp.asarray(rng.normal(size=(width, taps)), jnp.float32)
    return bcx, weight


def run(impl, bcx, weight, seq=SEQ):
    return shortconv.gated_short_conv(bcx, weight, seq, impl=impl,
                                      platform="cpu")


def by_lax(bcx, weight, seq):
    """``C ⊙ conv(B ⊙ x̃)`` through XLA's own convolution: depthwise
    (``feature_group_count`` = channels), left-padded by K - 1."""
    width, taps = weight.shape
    b, c, x = (part.reshape(-1, seq, width) for part in
               jnp.split(bcx.astype(jnp.float32), 3, -1))
    v = jax.lax.conv_general_dilated(
        b * x, weight.T[:, None, :],          # [K, 1, D]: WIO
        window_strides=(1,), padding=[(taps - 1, 0)],
        dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=width,
        precision=jax.lax.Precision.HIGHEST)
    return (c * v).reshape(-1, width)


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("taps", [1, 2, 3, 4])
def test_equals_xlas_depthwise_convolution(impl, taps):
    bcx, weight = operands(taps=taps)
    np.testing.assert_allclose(np.asarray(run(impl, bcx, weight)),
                               np.asarray(by_lax(bcx, weight, SEQ)),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("impl", IMPLS)
def test_bfloat16_in_and_out_float32_inside(impl):
    bcx, weight = operands(dtype=jnp.bfloat16)
    out = run(impl, bcx, weight)
    assert out.dtype == jnp.bfloat16 and out.shape == (LINES * SEQ, WIDTH)
    want = by_lax(bcx, weight, SEQ)             # float32 from the same bf16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want), rtol=1e-2, atol=1e-2)


@pytest.mark.parametrize("impl", IMPLS)
def test_causal_a_later_token_moves_no_earlier_output(impl):
    bcx, weight = operands()
    t = 2 * SEQ + 7                              # line 2, place 7
    changed = bcx.at[t].add(3.0)
    before, after = (np.asarray(run(impl, x, weight)) for x in (bcx, changed))
    moved = np.flatnonzero(np.abs(before - after).max(-1) > 0)
    # its own place and the K - 1 after it, in its own line
    assert moved.tolist() == [t, t + 1, t + 2]


@pytest.mark.parametrize("impl", IMPLS)
def test_blind_to_pad_right_of_a_lines_tokens(impl):
    """PAD lies right of a line's tokens: whatever the projection wrote at
    those positions, no valid position's output reads it."""
    bcx, weight = operands()
    valid = 9                                    # places 0..8 of each line
    place = np.arange(LINES * SEQ) % SEQ
    noisy = jnp.where((place >= valid)[:, None], bcx * 50.0 + 7.0, bcx)
    before, after = (np.asarray(run(impl, x, weight)) for x in (bcx, noisy))
    np.testing.assert_array_equal(before[place < valid], after[place < valid])


@pytest.mark.parametrize("impl", IMPLS)
def test_never_reads_across_a_lines_edge(impl):
    """Token-major, a shift over positions is a shift over rows: the first
    K - 1 places of a line would read the last of the line before it. Each
    line's output equals the line computed alone, however large its
    neighbours' values."""
    bcx, weight = operands()
    loud = bcx.at[:SEQ].multiply(1e4).at[2 * SEQ:3 * SEQ].multiply(-1e4)
    out = np.asarray(run(impl, loud, weight))
    for line in range(LINES):
        rows = slice(line * SEQ, (line + 1) * SEQ)
        alone = np.asarray(run("xla", loud[rows], weight))
        np.testing.assert_allclose(out[rows], alone, rtol=1e-4)
    # and the first place of a line is its own tap alone
    b, c, x = jnp.split(loud, 3, -1)
    first = np.asarray(c * b * x * weight[:, TAPS - 1])[::SEQ]
    np.testing.assert_allclose(out[::SEQ], first, rtol=1e-4)


def test_the_two_forms_agree_in_value_and_gradient():
    bcx, weight = operands(seed=3)

    def loss(impl):
        return lambda b, w: (run(impl, b, w) ** 2).sum()

    np.testing.assert_allclose(np.asarray(run("fused", bcx, weight)),
                               np.asarray(run("xla", bcx, weight)),
                               rtol=1e-6, atol=1e-6)
    fused, plain = (jax.grad(loss(impl), argnums=(0, 1))(bcx, weight)
                    for impl in IMPLS)
    for a, b in zip(fused, plain):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-5)
    # the gradient is the depthwise convolution's own
    by_conv = jax.grad(lambda b, w: (by_lax(b, w, SEQ) ** 2).sum(),
                       argnums=(0, 1))(bcx, weight)
    for a, b in zip(plain, by_conv):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3,
                                   atol=1e-3)


def test_the_kernels_blocks_hold_whole_lines():
    assert shortconv._block_tokens(32768, 32) == 512
    assert shortconv._block_tokens(96, 16) == 96
    assert shortconv._block_tokens(33 * 32, 32) == 11 * 32   # divides 33 lines
    for tokens, seq in ((32768, 32), (8192, 32), (96, 16), (33 * 32, 32)):
        block = shortconv._block_tokens(tokens, seq)
        assert block % seq == 0 and tokens % block == 0


@pytest.mark.parametrize("platform,tokens,mesh,width,want", [
    ("tpu", 1024 * 32, 1, 2048, "fused"),
    ("tpu", 256 * 32, 1, 2048, "fused"),
    ("tpu", 32 * 32, 1, 2048, "xla"),        # the fit's step
    ("tpu", 1024 * 32, 4, 2048, "xla"),      # a mesh
    ("cpu", 1024 * 32, 1, 2048, "xla"),
    ("tpu", 1024 * 32, 1, 2000, "xla"),      # channels off the lane groups
])
def test_the_routes_table(platform, tokens, mesh, width, want):
    assert shortconv.conv_route("auto", platform, tokens, 32, width, 3,
                                mesh) == want
    for forced in IMPLS:
        assert shortconv.conv_route(forced, platform, tokens, 32, width, 3,
                                    mesh) == forced


def test_the_route_is_recorded_and_a_forced_kernel_refuses_by_name():
    bcx, weight = operands()
    routes = {}
    with placement(1, None, routes):
        run("auto", bcx, weight)
        run("fused", jnp.concatenate([bcx, bcx]), weight)
    assert routes == {LINES: "xla", 2 * LINES: "fused"}
    narrow, taps = operands(width=64)
    with pytest.raises(ValueError, match="do not tile"):
        run("fused", narrow, taps)
    with pytest.raises(ValueError, match="expected 'auto'"):
        run("pallas", bcx, weight)
