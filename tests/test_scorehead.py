"""Fused candidate scoring-head kernel (ops/scorehead.py): parity with the
jnp logsumexp reference in interpret mode, and the head_impl route through
a real scorer. On-chip perf is scripts/bench_scorehead.py's job."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from detectmateservice_tpu.models.base import head_route
from detectmateservice_tpu.ops.scorehead import candidate_lse, tile_sizes


def _f32_lse(h, e):
    return jax.nn.logsumexp(
        h.astype(jnp.float32) @ e.astype(jnp.float32).T, axis=-1)


class TestCandidateLse:
    # the last cases: D = 256 with the shape-derived tiles (2048 x 2048,
    # 256-row sub-tiles): N a multiple of 29,696 rows' 128-row remainder
    # (29,696 = 14.5 x 2048, so the last N block is half padding), a prime
    # V (last V block masked), and V under one sub-tile
    @pytest.mark.parametrize("n,c,d", [(1000, 2048, 128), (256, 512, 64),
                                       (37, 64, 32), (8, 8, 8),
                                       (29696 // 8, 2053, 256),
                                       (2048 + 128, 4096 + 613, 256),
                                       (300, 131, 256)])
    def test_matches_reference(self, n, c, d):
        rng = np.random.default_rng(n + c + d)
        h = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
        e = jnp.asarray(rng.normal(size=(c, d)), jnp.float32) * d ** -0.5
        ref = jax.nn.logsumexp(h @ e.T, axis=-1)
        got = candidate_lse(h, e, interpret=True)
        assert got.shape == (n,)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-4)

    @pytest.mark.parametrize("n,c,d,blocks", [
        (512, 256, 64, None),
        # D = 256, several N and V blocks, N and V off the block multiples
        (1200, 1031, 256, (256, 512)),
        (29696 // 16, 2053, 256, (512, 1024)),
    ])
    def test_bf16_inputs_fp32_accumulation(self, n, c, d, blocks):
        rng = np.random.default_rng(0)
        h = jnp.asarray(rng.normal(size=(n, d)), jnp.bfloat16)
        e = jnp.asarray(rng.normal(size=(c, d)) * (64 / d) ** 0.5,
                        jnp.bfloat16)
        ref = _f32_lse(h, e)
        kw = {} if blocks is None else dict(block_n=blocks[0],
                                            block_c=blocks[1])
        got = candidate_lse(h, e, interpret=True, **kw)
        assert got.dtype == jnp.float32 and got.shape == (n,)
        # bf16 matmul inputs with fp32 accumulation: small drift allowed
        assert float(jnp.max(jnp.abs(got - ref))) < 0.1

    @pytest.mark.parametrize("shape", ["uniform", "bf16-blocks"])
    def test_extreme_values_stay_finite(self, shape):
        """Online max-subtraction must keep exp in range the way the
        two-pass reference does."""
        if shape == "uniform":
            h = jnp.full((16, 32), 50.0, jnp.float32)
            e = jnp.concatenate([jnp.full((8, 32), 2.0),
                                 jnp.full((8, 32), -2.0)])
            got = candidate_lse(h, e, interpret=True)
        else:
            # logits from -25,600 to +25,600, the largest in the LAST V
            # block and the smallest in the first, so the running max
            # rescales an already huge negative state; bfloat16, D = 256,
            # three V blocks (the last one masked) and two N blocks
            h = jnp.full((200, 256), 10.0, jnp.bfloat16)
            col = jnp.linspace(-10.0, 10.0, 613)[:, None]
            e = jnp.broadcast_to(col, (613, 256)).astype(jnp.bfloat16)
            got = candidate_lse(h, e, block_n=128, block_c=256,
                                interpret=True)
        ref = _f32_lse(h, e)
        assert bool(jnp.isfinite(got).all())
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-3)

    @pytest.mark.parametrize("c", [96, 1031, 613])
    def test_non_pow2_and_prime_candidate_counts(self, c):
        """C pads to a full block; the padded rows are masked to -inf in
        the last block — arbitrary (even prime) vocab sizes keep full-width
        blocks instead of degrading to divisor-sized ones."""
        rng = np.random.default_rng(c)
        h = jnp.asarray(rng.normal(size=(100, 16)), jnp.float32)
        e = jnp.asarray(rng.normal(size=(c, 16)), jnp.float32)
        ref = jax.nn.logsumexp(h @ e.T, axis=-1)
        got = candidate_lse(h, e, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-4)

    @pytest.mark.parametrize("n,v,d,itemsize,want", [
        # the served head: 32768 rows x 32 positions, D = 256, bfloat16
        (32768 * 32, 32768, 256, 2, (2048, 2048, 256)),
        (256 * 32, 32768, 256, 2, (2048, 2048, 256)),
        # mlp's head rows; a narrow problem clips to its padded size
        (16384, 32768, 128, 2, (2048, 2048, 256)),
        (300, 131, 256, 2, (384, 144, 144)),
        # wider rows / float32 shrink the tiles: operand blocks stay 2 MiB
        (1 << 20, 50000, 1024, 2, (1024, 1024, 256)),
        (1 << 20, 32768, 256, 4, (2048, 2048, 256)),
        (1 << 20, 32768, 4096, 2, (256, 256, 256)),
    ])
    def test_tiles_come_from_the_shape(self, n, v, d, itemsize, want):
        block_n, block_v, sub_v = tile_sizes(n, v, d, itemsize)
        assert (block_n, block_v, sub_v) == want
        assert block_n % 128 == 0 and sub_v % 8 == 0 and block_v % sub_v == 0


class TestAutoRule:
    """``head_impl: auto`` reads the platform, the head's kind, the call's
    shape and the mesh's size, and nothing else (models/base.py)."""

    V = 32768

    @pytest.mark.parametrize("impl,platform,exact,rows,v,mesh,want", [
        # one TPU, exact head: fused from 32 rows x 32 positions up
        ("auto", "tpu", True, 32768 * 32, V, 1, "pallas"),
        ("auto", "tpu", True, 8192 * 32, V, 1, "pallas"),
        ("auto", "tpu", True, 1024 * 32, V, 1, "pallas"),
        ("auto", "tpu", True, 256 * 32, V, 1, "pallas"),
        ("auto", "tpu", True, 32 * 32, V, 1, "pallas"),
        # under the smallest shape measured: einsum
        ("auto", "tpu", True, 16 * 32, V, 1, "einsum"),
        ("auto", "tpu", True, 1 * 32, V, 1, "einsum"),
        ("auto", "tpu", True, 128 * 32, 4096, 1, "einsum"),
        # a larger vocabulary reaches the shape with fewer rows
        ("auto", "tpu", True, 8 * 32, 131072, 1, "pallas"),
        # tier-1 tests and the host twin run on the CPU: nothing changes
        ("auto", "cpu", True, 32768 * 32, V, 1, "einsum"),
        ("auto", "cpu", True, 256 * 32, V, 1, "einsum"),
        ("auto", "gpu", True, 32768 * 32, V, 1, "einsum"),
        # a mesh of more than one device: GSPMD does not partition the call
        ("auto", "tpu", True, 32768 * 32, V, 4, "einsum"),
        ("auto", "tpu", True, 32768 * 32, V, 2, "einsum"),
        # the candidate head and mlp's head keep auto = einsum
        ("auto", "tpu", False, 16384 * 32, 2048, 1, "einsum"),
        ("auto", "tpu", False, 16384, V, 1, "einsum"),
        # the forcing values mean what they meant
        ("einsum", "tpu", True, 32768 * 32, V, 1, "einsum"),
        ("pallas", "cpu", True, 64, 512, 1, "pallas"),
        ("pallas", "tpu", False, 64, 512, 4, "pallas"),
    ])
    def test_route(self, impl, platform, exact, rows, v, mesh, want):
        assert head_route(impl, platform, exact, rows, v, mesh) == want

    @pytest.mark.parametrize("model", ["logbert", "gru"])
    def test_traced_exact_head_takes_the_rule(self, model):
        """A scorer placed on a TPU records ``pallas`` for a 256-row call
        and ``einsum`` for an 8-row one; the same scorer on the CPU, or on
        a four-device mesh, records ``einsum`` for both. Traced only
        (``eval_shape``): nothing is lowered for a chip that is not here."""
        if model == "logbert":
            from detectmateservice_tpu.models.logbert import (
                LogBERTConfig as Config, LogBERTScorer as Scorer)
            kw = dict(dim=32, depth=1, heads=2)
        else:
            from detectmateservice_tpu.models.gru import (
                GRUScorer as Scorer, GRUScorerConfig as Config)
            kw = dict(dim=32, depth=1)

        def routes(platform, mesh_devices=1):
            scorer = Scorer(Config(vocab_size=self.V, seq_len=32,
                                   platform=platform, **kw))
            scorer.mesh_devices = mesh_devices
            params = jax.eval_shape(lambda: scorer.init(
                jax.random.PRNGKey(0))[0])
            for rows in (8, 256):
                jax.eval_shape(scorer._score_impl, params,
                               jax.ShapeDtypeStruct((rows, 32), jnp.uint16))
            return scorer.head_routes

        assert routes("tpu") == {8: "einsum", 256: "pallas"}
        assert routes("cpu") == {8: "einsum", 256: "einsum"}
        assert routes("tpu", mesh_devices=4) == {8: "einsum", 256: "einsum"}

    def test_sharded_scorer_tells_the_scorer_its_mesh(self):
        from detectmateservice_tpu.models.gru import GRUScorer, GRUScorerConfig
        from detectmateservice_tpu.parallel.mesh import make_mesh
        from detectmateservice_tpu.parallel.sharded import ShardedScorer

        scorer = GRUScorer(GRUScorerConfig(vocab_size=512, dim=16, depth=1,
                                           seq_len=8))
        assert scorer.mesh_devices == 1
        n = len(jax.devices())
        ShardedScorer(scorer, mesh=make_mesh({"data": n}))
        assert scorer.mesh_devices == n


class TestHeadImplRoute:
    def test_gru_pallas_head_matches_einsum_head(self):
        from detectmateservice_tpu.models.gru import GRUScorer, GRUScorerConfig

        toks = jnp.asarray(np.random.default_rng(2).integers(
            1, 4000, (64, 16)), jnp.int32)
        base = dict(vocab_size=4096, dim=64, depth=1, seq_len=16,
                    score_vocab=512)
        s_e = GRUScorer(GRUScorerConfig(**base, head_impl="einsum"))
        s_p = GRUScorer(GRUScorerConfig(**base, head_impl="pallas"))
        params, _ = s_e.init(jax.random.PRNGKey(0))
        a = np.asarray(s_e.score(params, toks))
        b = np.asarray(s_p.score(params, toks))
        assert np.abs(a - b).max() < 0.05

    def test_detector_validates_head_impl(self):
        from detectmateservice_tpu.library.common.core import LibraryError
        from detectmateservice_tpu.library.detectors import JaxScorerDetector

        with pytest.raises(LibraryError, match="head_impl"):
            JaxScorerDetector(config={"detectors": {"JaxScorerDetector": {
                "method_type": "jax_scorer", "auto_config": False,
                "head_impl": "cuda",
            }}})


class TestMlpHeadPallasRoute:
    def test_mlp_pallas_head_matches_attend_head(self):
        """head_impl=pallas on the flagship mlp: fused lse + direct target
        dots must track the attend+log_softmax formulation (bf16 head vs
        fp32 attend → loose-but-bounded drift; fit/detect share the path
        so threshold units stay consistent)."""
        from detectmateservice_tpu.models.mlp import MLPScorer, MLPScorerConfig

        from detectmateservice_tpu.models.tokenizer import PAD_ID

        rng = np.random.default_rng(7)
        toks = rng.integers(1, 4000, (64, 16)).astype(np.int32)
        # ragged batch: half the rows end in PAD runs of varying length —
        # the masked-mean divisor and PAD zeroing must match across heads
        for i in range(0, 64, 2):
            toks[i, 16 - (i % 8 + 1):] = PAD_ID
        toks = jnp.asarray(toks)
        base = dict(vocab_size=4096, dim=32, seq_len=16)
        s_e = MLPScorer(MLPScorerConfig(**base))
        s_p = MLPScorer(MLPScorerConfig(**base, head_impl="pallas"))
        params, _ = s_e.init(jax.random.PRNGKey(0))
        # the setup() refactor must keep the original compact param layout
        # (checkpoint tree version 1 compatibility)
        assert sorted(params["params"].keys()) == [
            "Dense_0", "Dense_1", "tok_embed"]
        a = np.asarray(s_e.score(params, toks))
        b = np.asarray(s_p.score(params, toks))
        assert np.abs(a - b).max() < 0.05
        # the positional path (score_norm: position / normscore) routes
        # through the kernel too — per-token NLLs must agree incl. PAD zeros
        ne = np.asarray(s_e._token_nlls(params, toks))
        npl = np.asarray(s_p._token_nlls(params, toks))
        # per-token drift (bf16 head vs fp32 attend) is noisier than the
        # masked mean; thresholds live at sigma scale (~1.0), so 0.1 is
        # still an order of magnitude under anything calibration can see
        assert np.abs(ne - npl).max() < 0.1
        assert (npl[np.asarray(toks) == PAD_ID] == 0).all()


class TestHostTwinStaysEinsum:
    def test_host_twin_not_bound_to_pallas_head(self):
        """The sparse-traffic host twin must score through the einsum
        formulation even when the device head is pallas — interpret-mode
        kernels per lone message would destroy the <10 ms p50 contract."""
        import time

        from detectmateservice_tpu.library.detectors import JaxScorerDetector
        from detectmateservice_tpu.schemas import ParserSchema

        def msg(i, template="user <*> ok from <*>"):
            return ParserSchema(
                EventID=1, template=template,
                variables=[f"u{i % 4}", f"10.0.0.{i % 8}"], logID=str(i),
                logFormatVariables={}).serialize()

        det = JaxScorerDetector(config={"detectors": {"JaxScorerDetector": {
            "method_type": "jax_scorer", "auto_config": False, "model": "mlp",
            "data_use_training": 32, "train_epochs": 1, "min_train_steps": 20,
            "seq_len": 16, "dim": 32, "max_batch": 32, "async_fit": False,
            "vocab_size": 2048, "threshold_sigma": 4.0,
            "head_impl": "pallas",
        }}})
        det.setup_io()
        det.process_batch([msg(i) for i in range(32)])
        det.flush_final()
        assert det._cpu_device is not None
        det.process_batch([msg(90)])
        det.flush()  # warm the host-twin compile
        t0 = time.perf_counter()
        det.process_batch([msg(91)])
        det.flush()
        ms = (time.perf_counter() - t0) * 1000
        assert ms < 200, (
            f"lone-message host path took {ms:.0f} ms — the twin is likely "
            "running the interpret-mode pallas kernel")


class TestExactHeadPallasRoute:
    def test_exact_path_pallas_matches_einsum(self):
        """head_impl=pallas on the EXACT (score_vocab=0) path: fused lse +
        direct target dot must match the chunked einsum formulation."""
        from detectmateservice_tpu.models.gru import GRUScorer, GRUScorerConfig

        toks = jnp.asarray(np.random.default_rng(5).integers(
            1, 500, (32, 16)), jnp.int32)
        base = dict(vocab_size=512, dim=32, depth=1, seq_len=16)
        s_e = GRUScorer(GRUScorerConfig(**base, head_impl="einsum"))
        s_p = GRUScorer(GRUScorerConfig(**base, head_impl="pallas"))
        params, _ = s_e.init(jax.random.PRNGKey(0))
        a = np.asarray(s_e.score(params, toks))
        b = np.asarray(s_p.score(params, toks))
        assert np.abs(a - b).max() < 0.05, np.abs(a - b).max()


class TestEngagementRecord:
    def test_admin_xla_names_the_route_of_each_traced_bucket(self):
        """``GET /admin/xla`` → ``buckets.head_route``: the head every
        traced device executable took, keyed by its rows. On the CPU every
        warm bucket reads ``einsum``; a scorer placed on a TPU reads
        ``pallas`` from the shape the rule names (512 rows x 32 positions
        at V = 2048 is 2**25 logits)."""
        import dataclasses

        from detectmateservice_tpu.engine import device_obs
        from detectmateservice_tpu.library.detectors import JaxScorerDetector

        det = JaxScorerDetector(config={"detectors": {"JaxScorerDetector": {
            "method_type": "jax_scorer", "auto_config": False,
            "model": "logbert", "vocab_size": 2048, "dim": 32, "depth": 1,
            "heads": 2, "seq_len": 32, "max_batch": 64,
            "data_use_training": 32, "async_fit": False,
        }}})
        det.setup_io()
        routes = device_obs.get_ledger().snapshot()["buckets"]["head_route"]
        assert routes and set(routes.values()) == {"einsum"}
        assert all(rows.isdigit() for rows in routes)

        on_tpu = type(det._scorer)(dataclasses.replace(
            det._scorer.config, platform="tpu"))
        params = jax.eval_shape(lambda: on_tpu.init(jax.random.PRNGKey(0))[0])
        for rows in (64, 512):
            jax.eval_shape(on_tpu._score_impl, params,
                           jax.ShapeDtypeStruct((rows, 32), jnp.uint16))
        det._scorer = on_tpu
        routes = device_obs.get_ledger().snapshot()["buckets"]["head_route"]
        assert routes == {"64": "einsum", "512": "pallas"}
