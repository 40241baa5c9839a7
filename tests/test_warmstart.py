"""dmwarm — AOT warm-start serving, shared compile cache, int8 parity (PR 17).

Covers the warm-start contract end to end:

* setup_io AOT-compiles the warm bucket set (``lower().compile()`` kept in
  the device executor's table) BEFORE ``mark_warmup_complete``, so the first dispatch
  after boot records **zero** ledger compiles — the boot→ACTIVE honesty
  gate, with ``WarmupPendingCheck`` refusing ACTIVE while warm-up is in
  flight;
* ``warm_set_spec`` round-trips through the rollout manifest
  (``CheckpointStore.record``) and ``install_candidate`` pre-warms the
  UNION of the live warm set and the persisted spec — a promote on a
  restarted process warms what the recording boot warmed;
* a second PROCESS booting against the same ``compile_cache_dir`` shows
  persistent-cache ``hits > 0``, ``misses == 0`` and a lower warm-up wall
  time (driven through ``scripts/warmstart_smoke.py`` child boots, because
  ``enable_compilation_cache`` is deliberately once-per-process);
* ``dtype: int8w`` activates only behind the differential parity gate:
  zero alert-decision flips on the parity corpus, and a corrupted
  quantization is refused (float path stays live).
"""
import importlib.util
import os
import sys
import tempfile
from pathlib import Path

import numpy as np
import pytest

from detectmateservice_tpu.engine import device_obs
from detectmateservice_tpu.engine.health import PASS, UNHEALTHY
from detectmateservice_tpu.rollout import CheckpointStore
from detectmateservice_tpu.schemas import ParserSchema

REPO = Path(__file__).resolve().parent.parent


def msg(i: int) -> bytes:
    return ParserSchema(
        EventID=1, template="user <*> logged in from <*>",
        variables=[f"u{i % 8}", f"10.0.0.{i % 16}"], logID=str(i),
        logFormatVariables={"Time": "1700000000"},
    ).serialize()


def make_detector(**overrides):
    from detectmateservice_tpu.library.detectors import JaxScorerDetector

    base = {
        "method_type": "jax_scorer", "auto_config": False, "model": "mlp",
        "data_use_training": 32, "train_epochs": 1, "min_train_steps": 5,
        "seq_len": 16, "dim": 32, "max_batch": 32, "async_fit": False,
        "host_score_max_batch": 0, "score_threshold": -1e9,
    }
    base.update(overrides)
    det = JaxScorerDetector(config={"detectors": {"JaxScorerDetector": base}})
    det.setup_io()
    assert det.process_batch([msg(i) for i in range(32)]) == []
    det.flush_final()
    return det


def ledger_totals() -> dict:
    return device_obs.get_ledger().snapshot(limit=1)["totals"]


@pytest.fixture(scope="module")
def warm_detector():
    return make_detector()


# ---------------------------------------------------------------------------
# AOT warm-up: executables built at setup_io, dispatch is compile-free
# ---------------------------------------------------------------------------
class TestAotWarmStart:
    def test_warm_set_is_aot_compiled_at_boot(self, warm_detector):
        det = warm_detector
        assert det._device_warm, "setup_io left the warm bucket set empty"
        # every warm bucket owns a kept executable for the serving kind
        kept = det._exec.kept_programs()
        kinds = {kind for (kind, _, _) in kept}
        buckets = {rows for (_, rows, _) in kept}
        assert kinds & {"score", "normscore"}
        assert set(det._device_warm) <= buckets

    def test_warmup_complete_with_phase_timings(self, warm_detector):
        snap = device_obs.get_ledger().snapshot(limit=1)
        assert snap["warmup_complete"]
        phases = snap["warmup_phases"]
        assert "aot" in phases and phases["aot"] >= 0.0
        assert "device_put" in phases

    def test_first_dispatch_records_zero_compiles(self, warm_detector):
        det = warm_detector
        before = ledger_totals()
        tokens = np.zeros((det.config.max_batch, det.config.seq_len),
                          np.int32)
        scores = det.score_tokens(tokens)
        after = ledger_totals()
        assert scores.shape == (det.config.max_batch,)
        assert after["compiles"] == before["compiles"], (
            "dispatch on a warm bucket paid a compile — the AOT warm set "
            "did not cover the serving path")
        assert after["unexpected"] == before["unexpected"]

    def test_warm_set_spec_describes_live_warm_set(self, warm_detector):
        det = warm_detector
        spec = det.warm_set_spec()
        assert spec["buckets"] == sorted(int(b) for b in det._device_warm)
        assert spec["seq_len"] == det.config.seq_len
        assert spec["dtype"] == str(det.config.dtype)
        assert spec["score_norm"] == str(det.config.score_norm)

    def test_warmup_pending_check_refuses_active_mid_warmup(self):
        ledger = device_obs.CompileLedger()
        check = device_obs.WarmupPendingCheck(ledger, monitor=None)
        status, detail = check.evaluate(0.0)
        assert status == UNHEALTHY and "refusing ACTIVE" in detail
        ledger.mark_warmup_complete()
        status, _ = check.evaluate(0.0)
        assert status == PASS


# ---------------------------------------------------------------------------
# install_candidate pre-warms from the persisted manifest warm-set spec
# ---------------------------------------------------------------------------
class TestInstallPrewarm:
    def test_manifest_round_trips_warm_set_spec(self, warm_detector,
                                                tmp_path):
        spec = warm_detector.warm_set_spec()
        store = CheckpointStore(str(tmp_path / "store"), keep=4)
        store.record(3, meta={"warm_set": spec, "source": "test"})
        assert store.entry(3)["meta"]["warm_set"] == spec

    def test_install_candidate_prewarms_spec_buckets(self):
        det = make_detector(max_batch=64)
        extras = [b for b in (2, 4, 8, 16) if b not in det._device_warm]
        assert extras, "every candidate bucket already warm — widen ladder"
        spec = {"buckets": extras, "seq_len": det.config.seq_len,
                "dtype": str(det.config.dtype),
                "score_norm": str(det.config.score_norm)}
        rows = np.random.default_rng(5).integers(
            0, 100, size=(64, det.config.seq_len)).astype(np.int32)
        params, opt_state, _ = det.rollout_fine_tune(rows, seed=5)
        before = ledger_totals()["unexpected"]
        swap = det.install_candidate(params, opt_state, version=17,
                                     warm_set=spec)
        assert swap["swapped"]
        assert set(extras) <= set(swap["prewarmed_buckets"])
        assert set(extras) <= det._device_warm
        # the freshly-warmed bucket serves its exact shape compile-free
        compiles = ledger_totals()["compiles"]
        scores = det.score_tokens(
            np.zeros((extras[0], det.config.seq_len), np.int32))
        assert scores.shape == (extras[0],)
        assert ledger_totals()["compiles"] == compiles
        assert ledger_totals()["unexpected"] == before

    def test_stale_seq_len_spec_is_ignored(self, warm_detector):
        det = warm_detector
        live = sorted(det._device_warm)
        stale = {"buckets": [max(live) * 2], "seq_len": det.config.seq_len + 1}
        assert det._resolve_warm_set(stale) == live

    def test_malformed_spec_warms_live_set_only(self, warm_detector):
        det = warm_detector
        live = sorted(det._device_warm)
        assert det._resolve_warm_set({"buckets": "nope"}) == live
        assert det._resolve_warm_set(None) == live


# ---------------------------------------------------------------------------
# int8 weight-only quantized serving behind the differential parity gate
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def int8_detector():
    # real calibrated threshold (no -1e9 override): the parity gate must
    # judge decisions that can actually flip
    return make_detector(dtype="int8w", score_threshold=None,
                         threshold_sigma=4.0)


class TestInt8Parity:
    def test_int8_activates_with_zero_flips(self, int8_detector):
        rep = int8_detector._int8_report
        assert rep is not None and rep["activated"]
        assert rep["gated"], "parity corpus missing — gate never judged"
        assert rep["rows"] > 0
        assert rep["flips"] == 0 and rep["flip_ratio"] == 0.0
        assert rep["bytes"]["int8_bytes"] > 0

    def test_int8_decisions_match_float_path(self, int8_detector):
        det = int8_detector
        qparams = det._exec.qparams
        assert qparams is not None
        tokens = np.random.default_rng(11).integers(
            0, 100, size=(det.config.max_batch,
                          det.config.seq_len)).astype(np.int32)
        q_scores = det.score_tokens(tokens)
        det._exec.clear_quantized()
        try:
            f_scores = det.score_tokens(tokens)
        finally:
            det._exec.install_quantized(qparams)
        assert np.all(np.isfinite(q_scores))
        thr = det._threshold
        assert np.array_equal(q_scores > thr, f_scores > thr), (
            "quantized path flips alert decisions vs float")

    def test_parity_gate_refuses_corrupt_quantization(self, monkeypatch):
        det = make_detector(dtype="int8w", score_threshold=None,
                            threshold_sigma=4.0)
        assert det._int8_report["activated"]
        from detectmateservice_tpu.models import quant

        real_quantize = quant.quantize_tree

        def corrupt_quantize(params):
            import jax

            return real_quantize(
                jax.tree_util.tree_map(lambda x: x * 0.0, params))

        monkeypatch.setattr(quant, "quantize_tree", corrupt_quantize)
        rep = det._activate_int8(where="test")
        assert not rep["activated"]
        assert rep["flips"] > 0
        assert det._exec.qparams is None, "refused tree left installed"
        # float path keeps serving
        scores = det.score_tokens(
            np.zeros((det.config.max_batch, det.config.seq_len), np.int32))
        assert np.all(np.isfinite(scores))


# ---------------------------------------------------------------------------
# shared persistent compile cache across PROCESS boots
# ---------------------------------------------------------------------------
def _load_smoke_module():
    path = REPO / "scripts" / "warmstart_smoke.py"
    spec = importlib.util.spec_from_file_location("warmstart_smoke",
                                                  str(path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestSharedCompileCache:
    def test_second_boot_hits_shared_cache_and_is_faster(self):
        smoke = _load_smoke_module()
        cache_dir = tempfile.mkdtemp(prefix="dmwarm_test_")
        cold = smoke.run_boot(cache_dir)
        warm = smoke.run_boot(cache_dir)
        for tag, boot in (("cold", cold), ("warm", warm)):
            assert boot["armed_dir"], f"{tag} boot failed to arm the cache"
            assert boot["warmup_complete_before_dispatch"], tag
            assert boot["dispatch_compiles"] == 0, (tag, boot["ledger_ring"])
            assert boot["unexpected"] == 0, tag
        assert cold["cache"]["misses"] > 0, "cold boot populated nothing"
        assert warm["cache"]["hits"] > 0, warm["cache"]
        assert warm["cache"]["misses"] == 0, warm["cache"]
        assert warm["warmup_s"] < cold["warmup_s"], (
            f"shared cache bought no warm-up time: "
            f"{warm['warmup_s']}s vs {cold['warmup_s']}s")
