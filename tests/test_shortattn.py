"""Short-sequence attention kernel (ops/shortattn.py): parity with
``dot_product_attention`` in interpret mode, its gradients against the
einsum route's, the route rule as one table, the ``attn_impl`` route through
a real scorer, and the engagement record. On-chip speed is
scripts/bench_flash.py's job (``--short``, ``--buckets``)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from detectmateservice_tpu.ops.attention import (
    attention, attention_route, dot_product_attention, latent_attention,
    latent_einsum, merge_heads, rotary, self_attention, split_heads)
from detectmateservice_tpu.ops.shortattn import (
    einsum_route, fits, fits_latent, heads_per_lane_group, short_attention,
    short_latent_attention)


def make_qkv(b, s, h, d, dtype=jnp.bfloat16, seed=0):
    """The fused projection and a PAD mask of random line lengths whose
    first line is all PAD and whose last is full."""
    rng = np.random.default_rng(seed + b + s)
    qkv = jnp.asarray(rng.standard_normal((b, s, 3 * h * d)), dtype)
    lengths = rng.integers(1, s + 1, b)
    lengths[0], lengths[-1] = 0, s
    return qkv, jnp.asarray(np.arange(s)[None] < lengths[:, None])


def reference(qkv, mask, heads):
    """``dot_product_attention`` on head-major float32 copies."""
    q, k, v = split_heads(qkv.astype(jnp.float32), heads)
    return merge_heads(dot_product_attention(
        q, k, v, None if mask is None else mask[:, None, None, :]))


class TestKernelParity:
    # the served shape's heads and widths; S 16 and S 128 (eight lines and
    # one line a tile); B off the block multiple and, with 1024-token
    # blocks, several grid steps with the last one part padding
    @pytest.mark.parametrize("b,s,h,d,block", [
        (24, 32, 4, 64, None),
        (70, 32, 4, 64, 1024),
        (37, 16, 4, 64, None),
        (5, 128, 4, 64, None),
        (9, 32, 2, 128, None),     # one head a lane group
        (12, 32, 2, 16, None),     # the test scorers' narrow heads
    ])
    def test_matches_dot_product_attention_bf16(self, b, s, h, d, block):
        qkv, mask = make_qkv(b, s, h, d)
        want = reference(qkv, mask, h)
        got = short_attention(qkv, mask, h, block, True)
        assert got.shape == (b, s, h * d) and got.dtype == jnp.bfloat16
        assert bool(jnp.isfinite(got.astype(jnp.float32)).all())
        # bfloat16's error on outputs of magnitude ~1 (2**-8 a rounding:
        # the probabilities' and the output's)
        assert float(jnp.abs(got.astype(jnp.float32) - want).max()) < 3e-2
        # and no further from float32 than the einsum route is
        einsum = einsum_route(qkv, mask, h).astype(jnp.float32)
        assert (float(jnp.abs(got.astype(jnp.float32) - want).max())
                <= 2 * float(jnp.abs(einsum - want).max()) + 1e-3)

    @pytest.mark.parametrize("b,s,h,d", [(24, 32, 4, 64), (9, 16, 2, 16)])
    def test_matches_in_float32(self, b, s, h, d):
        qkv, mask = make_qkv(b, s, h, d, jnp.float32)
        got = short_attention(qkv, mask, h, None, True)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(reference(qkv, mask, h)),
                                   rtol=1e-5, atol=1e-5)

    def test_a_fully_padded_line_attends_uniformly_over_its_own_keys(self):
        """``finfo(float32).min``'s meaning: the all-PAD line's output is the
        mean of ITS values (not NaN, not its tile neighbours')."""
        qkv, mask = make_qkv(8, 32, 4, 64, jnp.float32)
        got = short_attention(qkv, mask, 4, None, True)
        v = qkv[0, :, 512:]
        np.testing.assert_allclose(
            np.asarray(got[0]), np.broadcast_to(np.asarray(v.mean(0)),
                                                (32, 256)),
            rtol=1e-5, atol=1e-5)

    def test_no_mask(self):
        qkv, _ = make_qkv(8, 32, 4, 64, jnp.float32)
        np.testing.assert_allclose(
            np.asarray(short_attention(qkv, None, 4, None, True)),
            np.asarray(reference(qkv, None, 4)), rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("shape,heads", [((4, 24, 768), 4),
                                             ((4, 256, 768), 4),
                                             ((4, 32, 700), 4)])
    def test_refuses_what_it_cannot_pack(self, shape, heads):
        with pytest.raises(ValueError, match="short_attention"):
            short_attention(jnp.zeros(shape, jnp.bfloat16), None, heads,
                            None, True)

    @pytest.mark.parametrize("seq,heads,head_dim,per,want", [
        (32, 4, 64, 2, True), (16, 4, 64, 2, True), (128, 8, 128, 1, True),
        (32, 2, 128, 1, True), (32, 8, 32, 4, True),
        (32, 2, 16, 2, False),     # 32 lanes a group: interpret mode only
        (32, 3, 64, 1, False), (24, 4, 64, 2, False), (256, 4, 64, 2, False),
    ])
    def test_what_the_compiled_kernel_takes(self, seq, heads, head_dim, per,
                                            want):
        assert heads_per_lane_group(heads, head_dim) == per
        assert fits(seq, heads, head_dim) is want


class TestGradients:
    @pytest.mark.parametrize("b,s,h,d", [(24, 32, 4, 64), (9, 16, 2, 16)])
    def test_gradients_are_the_einsum_routes(self, b, s, h, d):
        """The backward is the einsum route's vjp recomputed from qkv: the
        gradient of q, k and v (the three thirds) against that route's
        own."""
        qkv, mask = make_qkv(b, s, h, d, jnp.float32)
        w = jnp.asarray(np.random.default_rng(1).standard_normal(
            (b, s, h * d)), jnp.float32)

        def loss(fn):
            return lambda x: jnp.sum(fn(x) * w)

        got = jax.grad(loss(lambda x: short_attention(x, mask, h, None,
                                                      True)))(qkv)
        want = jax.grad(loss(lambda x: einsum_route(x, mask, h)))(qkv)
        for name, g, r in zip("qkv", jnp.split(got, 3, -1),
                              jnp.split(want, 3, -1)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                       rtol=1e-6, atol=1e-6, err_msg=name)
        assert float(jnp.abs(got).max()) > 0


class TestRouteRule:
    """``attn_impl: auto`` reads the platform, the call's shape and the
    mesh's size, and nothing else (ops/attention.py)."""

    @pytest.mark.parametrize(
        "impl,platform,s,t,heads,d,dv,causal,rows,mesh,want", [
            # one TPU, whole short self-attention: the kernel from 256 rows
            ("auto", "tpu", 32, 32, 4, 64, 64, False, 32768, 1, "short"),
            ("auto", "tpu", 32, 32, 4, 64, 64, False, 1024, 1, "short"),
            ("auto", "tpu", 32, 32, 4, 64, 64, False, 256, 1, "short"),
            ("auto", "tpu", 16, 16, 4, 64, 64, False, 4096, 1, "short"),
            ("auto", "tpu", 128, 128, 8, 128, 128, False, 512, 1, "short"),
            # fewer rows than the smallest warm bucket: einsum
            ("auto", "tpu", 32, 32, 4, 64, 64, False, 128, 1, "einsum"),
            ("auto", "tpu", 32, 32, 4, 64, 64, False, 1, 1, "einsum"),
            # tier-1 tests and the host twin run on the CPU
            ("auto", "cpu", 32, 32, 4, 64, 64, False, 32768, 1, "einsum"),
            ("auto", "gpu", 32, 32, 4, 64, 64, False, 32768, 1, "einsum"),
            # a mesh of more than one device: GSPMD does not partition it
            ("auto", "tpu", 32, 32, 4, 64, 64, False, 32768, 4, "einsum"),
            ("auto", "tpu", 32, 32, 4, 64, 64, False, 32768, 2, "einsum"),
            # the sparse-expert scorer's core: causal, values 128 != 192
            ("auto", "tpu", 32, 32, 32, 192, 128, True, 1024, 1, "einsum"),
            ("auto", "tpu", 32, 32, 4, 64, 64, True, 1024, 1, "einsum"),
            ("auto", "tpu", 32, 32, 4, 64, 32, False, 1024, 1, "einsum"),
            # cross-attention, lengths that do not pack, lanes that do not
            ("auto", "tpu", 32, 64, 4, 64, 64, False, 1024, 1, "einsum"),
            ("auto", "tpu", 24, 24, 4, 64, 64, False, 1024, 1, "einsum"),
            ("auto", "tpu", 256, 256, 4, 64, 64, False, 1024, 1, "einsum"),
            ("auto", "tpu", 32, 32, 2, 16, 16, False, 1024, 1, "einsum"),
            ("auto", "tpu", 32, 32, 3, 64, 64, False, 1024, 1, "einsum"),
            # long sequences on a TPU: flash, as before (on a mesh too)
            ("auto", "tpu", 2048, 2048, 4, 64, 64, False, 8, 1, "flash"),
            ("auto", "tpu", 8192, 8192, 4, 64, 64, False, 2, 4, "flash"),
            ("auto", "cpu", 2048, 2048, 4, 64, 64, False, 8, 1, "einsum"),
            # the forcing values mean what they meant
            ("einsum", "tpu", 32, 32, 4, 64, 64, False, 32768, 1, "einsum"),
            ("short", "cpu", 32, 32, 2, 16, 16, False, 8, 4, "short"),
            ("flash", "cpu", 32, 32, 4, 64, 64, False, 8, 1, "flash"),
            ("ring", "tpu", 32, 32, 4, 64, 64, False, 8, 4, "ring"),
        ])
    def test_route(self, impl, platform, s, t, heads, d, dv, causal, rows,
                   mesh, want):
        assert attention_route(impl, platform, s, t, heads, d, dv, causal,
                               rows, mesh) == want

    @pytest.mark.parametrize(
        "impl,platform,s,heads,nope,rope,dv,causal,rows,mesh,want", [
            # latent attention at the published widths (32 heads of
            # 128 ‖ 64, values 128) on one TPU: the two-width kernel for
            # the served 256-, 512- and 1024-row programs, causal or not
            ("auto", "tpu", 32, 32, 128, 64, 128, True, 1024, 1, "short"),
            ("auto", "tpu", 32, 32, 128, 64, 128, True, 512, 1, "short"),
            ("auto", "tpu", 32, 32, 128, 64, 128, True, 256, 1, "short"),
            ("auto", "tpu", 16, 32, 128, 64, 128, True, 256, 1, "short"),
            ("auto", "tpu", 32, 32, 128, 64, 128, False, 1024, 1, "short"),
            ("auto", "tpu", 32, 4, 256, 128, 128, True, 256, 1, "short"),
            # the fit's 32-row step, the CPU, a mesh: einsum
            ("auto", "tpu", 32, 32, 128, 64, 128, True, 32, 1, "einsum"),
            ("auto", "tpu", 32, 32, 128, 64, 128, True, 255, 1, "einsum"),
            ("auto", "cpu", 32, 32, 128, 64, 128, True, 1024, 1, "einsum"),
            ("auto", "tpu", 32, 32, 128, 64, 128, True, 1024, 4, "einsum"),
            # widths off the lane groups (the test scorers'), lines that do
            # not pack, an odd group of rope parts
            ("auto", "tpu", 16, 4, 16, 8, 16, True, 256, 1, "einsum"),
            ("auto", "tpu", 32, 32, 64, 64, 128, True, 1024, 1, "einsum"),
            ("auto", "tpu", 32, 32, 128, 64, 64, True, 1024, 1, "einsum"),
            ("auto", "tpu", 24, 32, 128, 64, 128, True, 1024, 1, "einsum"),
            ("auto", "tpu", 32, 3, 128, 64, 128, True, 1024, 1, "einsum"),
            # forcing
            ("einsum", "tpu", 32, 32, 128, 64, 128, True, 1024, 1, "einsum"),
            ("short", "cpu", 16, 4, 16, 8, 16, True, 8, 1, "short"),
        ])
    def test_latent_route(self, impl, platform, s, heads, nope, rope, dv,
                          causal, rows, mesh, want):
        assert attention_route(impl, platform, s, s, heads, nope + rope, dv,
                               causal, rows, mesh, rope_dim=rope) == want

    @pytest.mark.parametrize("seq,heads,nope,rope,dv,per,want", [
        (32, 32, 128, 64, 128, 2, True), (16, 32, 128, 64, 128, 2, True),
        (128, 4, 256, 128, 128, 1, True), (32, 8, 128, 32, 256, 4, True),
        (16, 4, 16, 8, 16, 4, False),     # interpret mode only
        (32, 32, 64, 64, 128, 2, False), (32, 32, 128, 64, 64, 2, False),
        (32, 3, 128, 64, 128, 1, False), (24, 32, 128, 64, 128, 2, False),
    ])
    def test_what_the_compiled_latent_kernel_takes(self, seq, heads, nope,
                                                   rope, dv, per, want):
        assert heads_per_lane_group(heads, rope) == per
        assert fits_latent(seq, heads, nope, rope, dv) is want


def make_latent(b, s, h, nope, rope, dv, dtype=jnp.bfloat16, seed=0):
    """Latent attention's token-major operands as the projections write
    them — q ``[B·S, H·nope | H·rope]``, kv ``[B·S, H·nope | H·dv]``, one
    ``k_rope [B·S, rope]`` — and a PAD mask of random line lengths whose
    first line is all PAD and whose last is full."""
    rng = np.random.default_rng(seed + b + s)
    q, kv, k_rope = (jnp.asarray(rng.standard_normal((b * s, w)), dtype)
                     for w in (h * (nope + rope), h * (nope + dv), rope))
    lengths = rng.integers(1, s + 1, b)
    lengths[0], lengths[-1] = 0, s
    return q, kv, k_rope, jnp.asarray(np.arange(s)[None] < lengths[:, None])


def latent_reference(q, kv, k_rope, mask, h, nope, theta, causal=True):
    """``dot_product_attention`` on concatenated, head-major float32
    operands — written out here, apart from ``latent_einsum``."""
    b, s = mask.shape
    q, kv, k_rope = (x.astype(jnp.float32) for x in (q, kv, k_rope))

    def heads_of(x):
        return x.reshape(b, s, h, -1).transpose(0, 2, 1, 3)

    q_rope = rotary(heads_of(q[:, h * nope:]), theta)
    k_rot = rotary(k_rope.reshape(b, 1, s, -1), theta)
    keys = jnp.concatenate([heads_of(kv[:, :h * nope]),
                            jnp.broadcast_to(k_rot, q_rope.shape)], -1)
    allowed = mask[:, None, None, :]
    if causal:
        allowed = allowed & jnp.tril(jnp.ones((s, s), bool))
    out = dot_product_attention(
        jnp.concatenate([heads_of(q[:, :h * nope]), q_rope], -1), keys,
        heads_of(kv[:, h * nope:]), allowed)
    return merge_heads(out).reshape(b * s, -1)


THETA = 1e6


class TestLatentKernelParity:
    """The causal two-width route (``short_latent_attention``) against
    ``dot_product_attention`` on concatenated operands."""

    # the published heads and widths at S 32 and 16; a small shape off the
    # lane groups (the test scorers'); one head a rope group; B off the
    # block multiple and several grid steps with the last part padding
    @pytest.mark.parametrize("b,s,h,nope,rope,dv,block", [
        (9, 32, 32, 128, 64, 128, None),
        (11, 16, 32, 128, 64, 128, None),
        (70, 32, 4, 128, 64, 128, 1024),
        (12, 16, 4, 16, 8, 16, None),
        (9, 32, 2, 128, 128, 256, None),
    ])
    def test_matches_dot_product_attention_bf16(self, b, s, h, nope, rope,
                                                dv, block):
        q, kv, k_rope, mask = make_latent(b, s, h, nope, rope, dv)
        want = latent_reference(q, kv, k_rope, mask, h, nope, THETA)
        got = short_latent_attention(q, kv, k_rope, mask, h, nope, THETA,
                                     True, block, True)
        assert got.shape == (b * s, h * dv) and got.dtype == jnp.bfloat16
        assert bool(jnp.isfinite(got.astype(jnp.float32)).all())
        err = float(jnp.abs(got.astype(jnp.float32) - want).max())
        # bfloat16's error on outputs of magnitude ~1: the turned rope
        # parts', the probabilities' and the output's roundings
        assert err < 4e-2
        # and no further from float32 than the einsum route is
        einsum = latent_einsum(q, kv, k_rope, mask, h, nope, THETA,
                               True).astype(jnp.float32)
        assert err <= 2 * float(jnp.abs(einsum - want).max()) + 1e-3

    @pytest.mark.parametrize("b,s,h,nope,rope,dv,causal", [
        (9, 32, 32, 128, 64, 128, True),
        (9, 16, 32, 128, 64, 128, True),
        (12, 16, 4, 16, 8, 16, True),
        (12, 16, 4, 16, 8, 16, False),
        (9, 32, 4, 128, 64, 128, False),
    ])
    def test_matches_in_float32(self, b, s, h, nope, rope, dv, causal):
        q, kv, k_rope, mask = make_latent(b, s, h, nope, rope, dv,
                                          jnp.float32)
        got = short_latent_attention(q, kv, k_rope, mask, h, nope, THETA,
                                     causal, None, True)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(latent_reference(
                q, kv, k_rope, mask, h, nope, THETA, causal)),
            rtol=2e-5, atol=2e-5)

    def test_a_fully_padded_line_attends_uniformly_over_its_own_keys(self):
        """Every key PAD: ``finfo(float32).min`` everywhere, the causal
        mask with it, so every position's output is the mean of the line's
        S values — not NaN, not its tile neighbours'."""
        h, nope, dv, s = 4, 128, 128, 32
        q, kv, k_rope, mask = make_latent(8, s, h, nope, 64, dv, jnp.float32)
        got = short_latent_attention(q, kv, k_rope, mask, h, nope, THETA,
                                     True, None, True)
        v = kv[:s, h * nope:]
        np.testing.assert_allclose(
            np.asarray(got[:s]),
            np.broadcast_to(np.asarray(v.mean(0)), (s, h * dv)),
            rtol=1e-5, atol=1e-5)

    def test_causal_a_later_token_moves_no_earlier_output(self):
        q, kv, k_rope, mask = make_latent(8, 32, 4, 128, 64, 128,
                                          jnp.float32)
        mask = jnp.ones_like(mask)
        at = 20                                  # position within line 3
        row = 3 * 32 + at
        moved = [x.at[row].add(1.0) for x in (q, kv, k_rope)]
        a = short_latent_attention(q, kv, k_rope, mask, 4, 128, THETA, True,
                                   None, True).reshape(8, 32, -1)
        b = short_latent_attention(*moved, mask, 4, 128, THETA, True, None,
                                   True).reshape(8, 32, -1)
        np.testing.assert_array_equal(np.asarray(a[3, :at]),
                                      np.asarray(b[3, :at]))
        assert float(jnp.abs(a[3, at:] - b[3, at:]).max()) > 1e-3
        np.testing.assert_array_equal(np.asarray(a[:3]), np.asarray(b[:3]))

    @pytest.mark.parametrize("shapes,heads,nope", [
        (((4 * 24, 4 * 24), (4 * 24, 4 * 32), (4 * 24, 8)), 4, 16),  # S 24
        (((64, 4 * 24), (64, 4 * 32), (64, 8)), 4, 12),   # widths disagree
        (((64, 4 * 40), (64, 4 * 32), (64, 24)), 4, 16),  # nope off a block
    ])
    def test_refuses_what_it_cannot_pack(self, shapes, heads, nope):
        q, kv, k_rope = (jnp.zeros(shape, jnp.bfloat16) for shape in shapes)
        rows = q.shape[0] // (24 if q.shape[0] == 96 else 16)
        mask = jnp.ones((rows, q.shape[0] // rows), bool)
        with pytest.raises(ValueError, match="short_latent_attention"):
            short_latent_attention(q, kv, k_rope, mask, heads, nope, THETA,
                                   True, None, True)

    def test_the_compiled_kernel_refuses_narrow_widths_by_name(self):
        q, kv, k_rope, mask = make_latent(4, 16, 4, 16, 8, 16)
        with pytest.raises(ValueError, match="whole lane groups"):
            short_latent_attention(q, kv, k_rope, mask, 4, 16, THETA, True,
                                   None, False)


class TestLatentGradients:
    @pytest.mark.parametrize("b,s,h,nope,rope,dv", [
        (9, 32, 4, 128, 64, 128), (12, 16, 4, 16, 8, 16)])
    def test_gradients_are_the_einsum_routes(self, b, s, h, nope, rope, dv):
        """The backward is ``latent_einsum``'s vjp recomputed from the
        operands: the gradients of q, kv and k_rope against that route's
        own."""
        operands = make_latent(b, s, h, nope, rope, dv, jnp.float32)
        mask = operands[3]
        w = jnp.asarray(np.random.default_rng(1).standard_normal(
            (b * s, h * dv)), jnp.float32)

        def loss(fn):
            return lambda q, kv, k_rope: jnp.sum(fn(q, kv, k_rope) * w)

        got = jax.grad(loss(lambda *x: short_latent_attention(
            *x, mask, h, nope, THETA, True, None, True)),
            argnums=(0, 1, 2))(*operands[:3])
        want = jax.grad(loss(lambda *x: latent_einsum(
            *x, mask, h, nope, THETA, True)), argnums=(0, 1, 2))(
            *operands[:3])
        for name, g, r in zip(("q", "kv", "k_rope"), got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                       rtol=1e-6, atol=1e-6, err_msg=name)
            assert float(jnp.abs(g).max()) > 0


class TestLatentEntry:
    def test_forced_short_matches_einsum_and_both_are_recorded(self):
        from detectmateservice_tpu.ops.attention import placement

        q, kv, k_rope, mask = make_latent(16, 16, 4, 16, 8, 16, jnp.float32)
        routes = {}
        with placement(1, routes):
            a = latent_attention(q, kv, k_rope, mask, 4, 16, THETA,
                                 impl="einsum", causal=True)
            assert routes == {16: "einsum"}
            b = latent_attention(q, kv, k_rope, mask, 4, 16, THETA,
                                 impl="short", platform="cpu", causal=True)
            assert routes == {16: "short"}
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                                   atol=2e-5)

    @pytest.mark.parametrize("impl", ["flash", "ring", "blockwise"])
    def test_other_routes_refuse_latent_attention_by_name(self, impl):
        q, kv, k_rope, mask = make_latent(4, 16, 4, 16, 8, 16, jnp.float32)
        with pytest.raises(ValueError, match="latent"):
            latent_attention(q, kv, k_rope, mask, 4, 16, THETA, impl=impl,
                             platform="cpu", causal=True)


class TestAttentionEntries:
    def test_self_attention_forced_short_matches_einsum(self):
        qkv, mask = make_qkv(16, 32, 4, 64)
        a = self_attention(qkv, 4, mask, impl="einsum").astype(jnp.float32)
        b = self_attention(qkv, 4, mask, impl="short",
                           platform="cpu").astype(jnp.float32)
        assert float(jnp.abs(a - b).max()) < 3e-2

    def test_head_major_entry_takes_the_forced_kernel_too(self):
        """``attention()`` holds head-major q, k, v: forced ``short`` pays
        the transposes back and computes the same."""
        qkv, mask = make_qkv(16, 32, 4, 64, jnp.float32)
        q, k, v = split_heads(qkv, 4)
        want = dot_product_attention(q, k, v, mask[:, None, None, :])
        got = attention(q, k, v, key_mask=mask, impl="short", platform="cpu")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("kw,match", [
        (dict(causal=True), "causal"),
        (dict(wide_v=True), "value width"),
    ])
    def test_short_refuses_causal_and_a_value_width_by_name(self, kw, match):
        qkv, mask = make_qkv(4, 32, 4, 64, jnp.float32)
        q, k, v = split_heads(qkv, 4)
        if kw.pop("wide_v", False):
            v = jnp.concatenate([v, v], axis=-1)
        with pytest.raises(ValueError, match=match):
            attention(q, k, v, key_mask=mask, impl="short", platform="cpu",
                      **kw)


def _scorer(attn_impl, **kw):
    from detectmateservice_tpu.models.logbert import (LogBERTConfig,
                                                      LogBERTScorer)

    return LogBERTScorer(LogBERTConfig(
        vocab_size=512, dim=32, depth=2, heads=2, seq_len=16,
        attn_impl=attn_impl, **kw))


def _tokens(rows, seq=16, vocab=512, seed=3):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(2, vocab, (rows, seq))
    lengths = rng.integers(3, seq + 1, rows)
    tokens[np.arange(seq)[None] >= lengths[:, None]] = 0      # PAD
    return jnp.asarray(tokens, jnp.int32)


class TestScorerRoute:
    def test_scores_agree_between_the_forced_kernel_and_einsum(self):
        einsum, short = _scorer("einsum"), _scorer("short")
        params, _ = einsum.init(jax.random.PRNGKey(0))
        tokens = _tokens(48)
        a = np.asarray(einsum.score(params, tokens))
        b = np.asarray(short.score(params, tokens))
        assert np.isfinite(b).all()
        assert np.abs(a - b).max() < 0.05
        assert einsum.attn_routes == {48: "einsum"}
        assert short.attn_routes == {48: "short"}

    def test_one_train_step_agrees(self):
        """``_train_impl`` differentiates through the kernel's custom vjp:
        the loss and every updated parameter against the einsum route's."""
        import optax

        einsum, short = (_scorer("einsum", dtype=jnp.float32),
                         _scorer("short", dtype=jnp.float32))
        # a step linear in the gradient: adamw's first step is lr * sign(g),
        # and the key bias's gradient is zero but for rounding
        einsum.optimizer = short.optimizer = optax.sgd(0.1)
        params, _ = einsum.init(jax.random.PRNGKey(0))
        opt = einsum.optimizer.init(params)
        tokens, rng = _tokens(32), jax.random.PRNGKey(1)
        p_e, _, loss_e = einsum.train_step(params, opt, rng, tokens)
        p_s, _, loss_s = short.train_step(params, opt, rng, tokens)
        assert np.isfinite(float(loss_s))
        np.testing.assert_allclose(float(loss_s), float(loss_e), rtol=1e-5)
        for a, b in zip(jax.tree_util.tree_leaves(p_e),
                        jax.tree_util.tree_leaves(p_s)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)
        moved = [float(jnp.abs(a - b).max()) for a, b in zip(
            jax.tree_util.tree_leaves(params),
            jax.tree_util.tree_leaves(p_s))]
        assert max(moved) > 0

    def test_traced_call_takes_the_rule(self):
        """A flagship-width scorer placed on a TPU records ``short`` for a
        256-row call and ``einsum`` for a 64-row one; on the CPU, or on a
        four-device mesh, ``einsum`` for both. Traced only (``eval_shape``):
        nothing is lowered for a chip that is not here."""
        from detectmateservice_tpu.models.logbert import (LogBERTConfig,
                                                          LogBERTScorer)

        def routes(platform, mesh_devices=1):
            scorer = LogBERTScorer(LogBERTConfig(
                vocab_size=2048, dim=256, depth=1, heads=4, seq_len=32,
                platform=platform))
            scorer.mesh_devices = mesh_devices
            params = jax.eval_shape(lambda: scorer.init(
                jax.random.PRNGKey(0))[0])
            for rows in (64, 256):
                jax.eval_shape(scorer._score_impl, params,
                               jax.ShapeDtypeStruct((rows, 32), jnp.uint16))
            return scorer.attn_routes

        assert routes("tpu") == {64: "einsum", 256: "short"}
        assert routes("cpu") == {64: "einsum", 256: "einsum"}
        assert routes("tpu", mesh_devices=4) == {64: "einsum", 256: "einsum"}

    @pytest.mark.parametrize("platform", ["cpu", "tpu"])
    def test_the_sparse_expert_scorer_reads_einsum(self, platform):
        """Its core is causal with a value width of its own: forced to
        einsum by name, refused by the rule besides, and recorded."""
        from detectmateservice_tpu.models.moe_mla import (
            MoEMLAArch, MoEMLAConfig, MoEMLAScorer)

        scorer = MoEMLAScorer(MoEMLAConfig(
            arch=MoEMLAArch.from_mapping(dict(
                hidden_size=64, num_attention_heads=4, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32,
                q_lora_rank=None, intermediate_size=96,
                moe_intermediate_size=48, n_shared_experts=1,
                num_experts_per_tok=2, first_k_dense_replace=1,
                norm_topk_prob=True, routed_scaling_factor=2.448,
                scoring_func="sigmoid", rope_theta=1e6, rope_interleave=True,
                rms_norm_eps=1e-6, num_hidden_layers=2, n_routed_experts=8,
                router_experts=8, expert_offset=0)),
            vocab_size=64, seq_len=16, dtype=jnp.float32, platform=platform,
            head_impl="einsum"))
        params = jax.eval_shape(lambda: scorer.init(jax.random.PRNGKey(0))[0])
        jax.eval_shape(scorer._score_impl, params,
                       jax.ShapeDtypeStruct((256, 16), jnp.int32))
        assert scorer.attn_routes == {256: "einsum"}

    def test_detector_takes_short_in_the_list_and_keeps_it_off_the_twin(self):
        from detectmateservice_tpu.library.detectors import JaxScorerDetector
        from detectmateservice_tpu.library.detectors.scorer_families import (
            FAMILIES)

        det = JaxScorerDetector(config={"detectors": {"JaxScorerDetector": {
            "method_type": "jax_scorer", "auto_config": False,
            "model": "logbert", "attn_impl": "short",
        }}})
        assert det.config.attn_impl == "short"
        assert not FAMILIES["logbert"].host_twin(det.config)
        assert FAMILIES["logbert"].host_twin(det.config.model_copy(
            update={"attn_impl": "auto"}))


class TestEngagementRecord:
    def test_admin_xla_names_the_attention_of_each_traced_bucket(self):
        """``GET /admin/xla`` → ``buckets.attn_route`` beside
        ``head_route``: on the CPU every warm bucket reads ``einsum``; the
        same scorer placed on a TPU reads ``short`` from 256 rows."""
        from detectmateservice_tpu.engine import device_obs
        from detectmateservice_tpu.library.detectors import JaxScorerDetector

        det = JaxScorerDetector(config={"detectors": {"JaxScorerDetector": {
            "method_type": "jax_scorer", "auto_config": False,
            "model": "logbert", "vocab_size": 2048, "dim": 256, "depth": 1,
            "heads": 4, "seq_len": 32, "max_batch": 64,
            "data_use_training": 32, "async_fit": False,
        }}})
        det.setup_io()
        buckets = device_obs.get_ledger().snapshot()["buckets"]
        routes = buckets["attn_route"]
        assert routes and set(routes.values()) == {"einsum"}
        assert set(routes) == set(buckets["head_route"])

        on_tpu = type(det._scorer)(dataclasses.replace(
            det._scorer.config, platform="tpu"))
        params = jax.eval_shape(lambda: on_tpu.init(jax.random.PRNGKey(0))[0])
        for rows in (64, 512):
            jax.eval_shape(on_tpu._score_impl, params,
                           jax.ShapeDtypeStruct((rows, 32), jnp.uint16))
        det._scorer = on_tpu
        routes = device_obs.get_ledger().snapshot()["buckets"]["attn_route"]
        assert routes == {"64": "einsum", "512": "short"}
