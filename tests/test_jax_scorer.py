"""JaxScorerDetector tests: training gate, pipelined batching, flush,
thresholding, checkpointing."""
import numpy as np
import pytest

from detectmateservice_tpu.library.detectors import JaxScorerDetector
from detectmateservice_tpu.schemas import DetectorSchema, ParserSchema


def scorer_config(**overrides):
    base = {
        "method_type": "jax_scorer", "auto_config": False, "model": "mlp",
        "data_use_training": 32, "train_epochs": 2, "threshold_sigma": 4.0,
        "seq_len": 16, "dim": 32, "max_batch": 32, "pipeline_depth": 2,
    }
    base.update(overrides)
    return {"detectors": {"JaxScorerDetector": base}}


def msg(template, variables, log_id="1"):
    return ParserSchema(EventID=1, template=template, variables=variables,
                        logID=log_id, logFormatVariables={"Time": "1700000000"}).serialize()


def normal_msgs(n, salt=""):
    return [msg("user <*> logged in from <*>", [f"u{i % 8}{salt}", f"10.0.0.{i % 16}"],
                log_id=str(i)) for i in range(n)]


def _tokens_for(det, raw_msgs):
    tokens, ok = det._featurize_raw_batch(raw_msgs)
    assert ok.all()
    return tokens, raw_msgs


@pytest.fixture()
def trained_detector():
    det = JaxScorerDetector(config=scorer_config())
    out = det.process_batch(normal_msgs(32))
    assert out == []  # training messages produce no output
    det.flush_final()  # async boundary fit: wait so tests see calibrated state
    return det


class TestTrainingPhase:
    def test_training_messages_filtered(self):
        det = JaxScorerDetector(config=scorer_config(data_use_training=16))
        assert det.process_batch(normal_msgs(10)) == []
        assert det._trained == 10
        assert not det._fitted

    def test_fit_at_boundary_calibrates_threshold(self, trained_detector):
        assert trained_detector._fitted
        assert trained_detector._threshold is not None
        assert np.isfinite(trained_detector._threshold)

    def test_explicit_threshold_respected(self):
        det = JaxScorerDetector(config=scorer_config(score_threshold=123.0))
        det.process_batch(normal_msgs(32))
        det.flush_final()
        assert det._threshold == 123.0


class TestDetection:
    def test_normal_traffic_no_alerts(self, trained_detector):
        out = trained_detector.process_batch(normal_msgs(32, salt=""))
        out += trained_detector.flush()
        assert all(o is None for o in out) or not out

    def test_anomaly_alerts_with_schema_fields(self, trained_detector):
        weird = [msg("segfault <*> exploit <*>", ["0xdead", "shellcode"], log_id="66")] * 8
        out = trained_detector.process_batch(weird)
        out += trained_detector.flush()
        alerts = [o for o in out if o is not None]
        assert alerts, "anomalous batch produced no alerts"
        alert = DetectorSchema.from_bytes(alerts[0])
        assert alert.detectorType == "jax_scorer"
        assert alert.detectorID == "JaxScorerDetector"
        assert list(alert.logIDs) == ["66"]
        assert alert.score > 0

    def test_batch_alert_full_field_parity_with_make_output(self, trained_detector):
        """The batch path builds alerts straight on pb2 for speed; EVERY
        field must match what the wrapper path (CoreDetector.make_output)
        would produce — this is the pin that lets the two stay one contract."""
        from detectmateservice_tpu.schemas import SCHEMA_VERSION

        raw = msg("segfault <*> exploit <*>", ["0xdead", "shellcode"], log_id="9")
        out = trained_detector.process_batch([raw])
        out += trained_detector.flush()
        alert = DetectorSchema.from_bytes([o for o in out if o is not None][0])
        ref = trained_detector.make_output(ParserSchema.from_bytes(raw))
        assert getattr(alert._msg, "__version__") == SCHEMA_VERSION
        assert alert.detectorID == ref.detectorID == "JaxScorerDetector"
        assert alert.detectorType == ref.detectorType == "jax_scorer"
        assert list(alert.logIDs) == list(ref.logIDs) == ["9"]
        # msg() carries Time=1700000000 -> the extract_timestamp chain
        assert list(alert.extractedTimestamps) == [1700000000]
        assert alert.description == ref.description
        assert alert.detectionTimestamp > 1_700_000_000
        assert alert.receivedTimestamp == alert.detectionTimestamp
        assert alert.score > 0
        obtain = dict(alert.alertsObtain)
        assert "JaxScorerDetector - score" in obtain
        assert "anomaly score" in obtain["JaxScorerDetector - score"]

    def test_small_batch_host_path_returns_immediately(self, trained_detector):
        # batches ≤ host_score_max_batch score on the CPU twin and come back
        # in the same call — the sparse-traffic latency contract
        assert trained_detector._host_params is not None
        weird = [msg("segfault <*> exploit <*>", ["0xdead", "shellcode"])] * 4
        immediate = trained_detector.process_batch(weird)
        assert len(trained_detector._inflight) == 0
        assert any(o is not None for o in immediate)

    def test_pipelining_defers_then_flush_drains(self):
        # with the host path off, results pipeline (deferred up to
        # pipeline_depth batches) and flush() drains them
        det = JaxScorerDetector(config=scorer_config(host_score_max_batch=0,
                                                     async_fit=False))
        det.process_batch(normal_msgs(32))
        det.flush_final()
        weird = [msg("segfault <*> exploit <*>", ["0xdead", "shellcode"])] * 4
        det._dispatch(*_tokens_for(det, weird))
        assert len(det._inflight) == 1
        drained = det.flush()
        assert len(det._inflight) == 0
        assert any(o is not None for o in drained)

    def test_host_and_device_paths_agree(self, trained_detector):
        # the CPU twin must reproduce the accelerator scores (same math,
        # modulo backend float differences)
        weird = [msg("segfault <*> exploit <*>", ["0xdead", "shellcode"])] * 4
        tokens, _ = trained_detector._featurize_raw_batch(weird)
        host = np.asarray(trained_detector._score_host(tokens))
        dev = trained_detector.score_tokens(tokens)
        np.testing.assert_allclose(host, dev, rtol=1e-3, atol=1e-3)

    def test_garbage_bytes_ignored(self, trained_detector):
        out = trained_detector.process_batch([b"\xff\xfe\x01garbage"])
        out += trained_detector.flush()
        assert all(o is None for o in out) or not out

    def test_single_message_detect_path(self, trained_detector):
        # per-message parity path via CoreDetector.process
        raw = msg("user <*> logged in from <*>", ["u1", "10.0.0.1"])
        assert trained_detector.process(raw) is None

    def test_logbert_model_variant(self):
        det = JaxScorerDetector(config=scorer_config(
            model="logbert", dim=32, depth=1, heads=2, data_use_training=32))
        det.process_batch(normal_msgs(32))
        det.flush_final()  # wait out the async boundary fit
        assert det._fitted
        out = det.process_batch(normal_msgs(8)) + det.flush()
        assert isinstance(out, list)


class TestInlineDispatch:
    """Every device batch is issued on the caller's thread and joins the
    in-flight queue in dispatch order."""

    def test_dispatch_order_preserved_across_batches(self):
        det = JaxScorerDetector(config=scorer_config(
            host_score_max_batch=0, async_fit=False, max_batch=8,
            pipeline_depth=8))
        det.process_batch(normal_msgs(32))
        det.flush_final()
        # several max_batch-sized dispatches, each with one anomaly whose
        # logID encodes the batch index — drain order must match
        for b in range(4):
            batch = normal_msgs(7, salt=str(b)) + [
                msg("segfault <*> exploit <*>", ["0xdead", str(b)],
                    log_id=f"batch-{b}")]
            det.process_batch(batch)
        out = det.flush_final()
        ids = [DetectorSchema.from_bytes(o).logIDs[0]
               for o in out if o is not None]
        batch_ids = [i for i in ids if i.startswith("batch-")]
        assert batch_ids == sorted(batch_ids), ids
        assert len(det._inflight) == 0


class TestCheckpoint:
    def test_roundtrip(self, trained_detector, tmp_path):
        trained_detector.save_checkpoint(str(tmp_path / "ckpt"))
        fresh = JaxScorerDetector(config=scorer_config())
        fresh.load_checkpoint(str(tmp_path / "ckpt"))
        assert fresh._fitted
        assert fresh._threshold == pytest.approx(trained_detector._threshold)
        # restored detector skips training and scores immediately
        out = fresh.process_batch(normal_msgs(8)) + fresh.flush()
        assert isinstance(out, list)


class TestCandidateIdPersistence:
    def test_candidate_ids_survive_restore_verbatim(self, tmp_path):
        """The score_vocab candidate subset is persisted in checkpoint meta
        and reused on restore — numpy's Generator bit-stream is not stable
        across numpy versions, so regenerating from the seed could silently
        shift the approximation under the fit-frozen threshold (advisor r3)."""
        import json

        import numpy as np

        det = JaxScorerDetector(config=scorer_config(
            model="gru", depth=1, data_use_training=32, score_vocab=64,
            vocab_size=512, async_fit=False))
        det.process_batch(normal_msgs(32))
        det.flush_final()
        assert det._fitted
        det.save_checkpoint(str(tmp_path / "ckpt"))
        meta = json.loads((tmp_path / "ckpt" / "meta.json").read_text())
        assert meta["cand_key"] == [512, 64]
        assert len(meta["cand_ids"]) == 64

        fresh = JaxScorerDetector(config=scorer_config(
            model="gru", depth=1, data_use_training=32, score_vocab=64,
            vocab_size=512, async_fit=False))
        fresh.load_checkpoint(str(tmp_path / "ckpt"))
        key, ids = fresh._scorer._cand_cache
        assert key == (512, 64)
        assert np.array_equal(ids, np.asarray(meta["cand_ids"], np.int32))


class TestConfigValidation:
    def test_unknown_attn_impl_fails_at_construction(self):
        """ops/attention's router silently falls through to einsum for
        unknown strings, so a typo must be caught at configure time."""
        from detectmateservice_tpu.library.common.core import LibraryError

        with pytest.raises(LibraryError, match="attn_impl"):
            JaxScorerDetector(config=scorer_config(model="logbert",
                                                   attn_impl="rign"))

    def test_flash_attn_disables_host_twin(self):
        """The pallas flash kernel is TPU-only; a flash-configured logbert
        must not build the CPU scoring twin it cannot compile."""
        det = JaxScorerDetector(config=scorer_config(
            model="logbert", depth=1, heads=2, attn_impl="flash",
            host_score_max_batch=8))
        assert not det._host_scoring_possible()

    def test_einsum_attn_keeps_host_twin(self):
        det = JaxScorerDetector(config=scorer_config(
            model="logbert", depth=1, heads=2, attn_impl="einsum",
            host_score_max_batch=8))
        det._ensure_scorer()
        assert det._cpu_device is not None


class TestCheckpointTreeVersion:
    def test_mismatched_tree_version_fails_with_clear_error(
            self, tmp_path, trained_detector):
        import json

        from detectmateservice_tpu.utils.checkpoint import CheckpointFormatError

        trained_detector.save_checkpoint(str(tmp_path / "ckpt"))
        meta_path = tmp_path / "ckpt" / "meta.json"
        meta = json.loads(meta_path.read_text())
        meta["tree_version"] = 99  # a layout this build does not know
        meta_path.write_text(json.dumps(meta))
        fresh = JaxScorerDetector(config=scorer_config())
        with pytest.raises(CheckpointFormatError, match="tree version"):
            fresh.load_checkpoint(str(tmp_path / "ckpt"))

    @pytest.mark.parametrize("stamp", ["absent", 2])
    def test_compatible_mlp_checkpoints_still_load(self, tmp_path,
                                                   trained_detector, stamp):
        """The setup() restructure did not touch mlp's param tree, so both a
        version-1 (no tree_version key) mlp checkpoint AND one stamped with
        the interim global v2 must keep restoring — the gate is a per-family
        compatibility SET, not a single number."""
        import json

        trained_detector.save_checkpoint(str(tmp_path / "ckpt"))
        meta_path = tmp_path / "ckpt" / "meta.json"
        meta = json.loads(meta_path.read_text())
        if stamp == "absent":
            meta.pop("tree_version")
        else:
            meta["tree_version"] = stamp
        meta_path.write_text(json.dumps(meta))
        fresh = JaxScorerDetector(config=scorer_config())
        fresh.load_checkpoint(str(tmp_path / "ckpt"))
        assert fresh._fitted


class TestSingleMessageTraining:
    def test_per_message_training_populates_buffer_and_alerts(self):
        # engine_batch_size=1 parity mode: every message goes through
        # CoreDetector.process → train() → fit at the boundary; the detector
        # must still learn and alert (regression: train() was a no-op, so the
        # threshold calibrated to inf and nothing ever alerted)
        det = JaxScorerDetector(config=scorer_config(data_use_training=16))
        for raw in normal_msgs(16):
            assert det.process(raw) is None
        assert len(det._train_buffer) == 16 or det._fitted
        weird = msg("segfault <*> exploit <*>", ["0xdead", "shellcode"], log_id="7")
        out = det.process(weird)
        assert det._fitted
        assert np.isfinite(det._threshold)
        assert out is not None, "single-message path never alerts"
        assert list(DetectorSchema.from_bytes(out).logIDs) == ["7"]


class TestCheckpointThreshold:
    def test_config_override_survives_restore(self, trained_detector, tmp_path):
        trained_detector.save_checkpoint(str(tmp_path / "ckpt"))
        fresh = JaxScorerDetector(config=scorer_config(score_threshold=123.0))
        fresh.load_checkpoint(str(tmp_path / "ckpt"))
        assert fresh._threshold == 123.0  # explicit override wins over checkpoint

    def test_missing_threshold_key_defaults_finite_semantics(self, trained_detector, tmp_path):
        import json
        trained_detector.save_checkpoint(str(tmp_path / "ckpt"))
        meta_path = tmp_path / "ckpt" / "meta.json"
        meta = json.loads(meta_path.read_text())
        meta.pop("threshold", None)
        meta_path.write_text(json.dumps(meta))
        fresh = JaxScorerDetector(config=scorer_config())
        fresh.load_checkpoint(str(tmp_path / "ckpt"))
        # no calibration available → comparable (inf) threshold, not None
        assert fresh._threshold == float("inf")
        out = fresh.process_batch(normal_msgs(4)) + fresh.flush()
        assert all(o is None for o in out) or not out


class TestMeshSharded:
    """mesh_shape routes the hot path through parallel.ShardedScorer: batches
    shard over the data axis of the virtual 8-device mesh (conftest), params
    per the model rules; XLA inserts the collectives (BASELINE config #5)."""

    def _mesh_detector(self, **overrides):
        overrides.setdefault("async_fit", False)
        return JaxScorerDetector(config=scorer_config(
            mesh_shape={"data": 8}, **overrides))

    def test_train_detect_over_mesh(self):
        det = self._mesh_detector()
        assert det.process_batch(normal_msgs(32)) == []
        assert det._exec.mesh_shape == {"data": 8}
        weird = [msg("segfault <*> exploit <*>", ["0xdead", f"x{i}"], log_id=str(100 + i))
                 for i in range(4)]
        out = det.process_batch(normal_msgs(8) + weird) + det.flush()
        alerts = [o for o in out if o is not None]
        assert alerts, "mesh-sharded detector never alerted on anomalies"
        ids = {i for a in alerts for i in DetectorSchema.from_bytes(a).logIDs}
        assert ids <= {str(100 + i) for i in range(4)}

    def test_results_match_single_device(self):
        # same seed → identical init params; inference-only scoring must agree
        # tightly (only XLA partitioning reduction order differs). Training
        # accumulates in shard order, so trained thresholds agree loosely.
        single = JaxScorerDetector(config=scorer_config())
        sharded = self._mesh_detector()
        probe = np.stack([single.featurize(ParserSchema.from_bytes(m))
                          for m in normal_msgs(8, salt="p")])
        np.testing.assert_allclose(single.score_tokens(probe),
                                   sharded.score_tokens(probe), rtol=1e-4)
        train = normal_msgs(32)
        single.process_batch(train)
        sharded.process_batch(train)
        assert sharded._threshold == pytest.approx(single._threshold, rel=5e-2)

    def test_checkpoint_roundtrip_over_mesh(self, tmp_path):
        det = self._mesh_detector()
        det.process_batch(normal_msgs(32))
        det.save_checkpoint(str(tmp_path / "ckpt"))
        fresh = self._mesh_detector()
        fresh.load_checkpoint(str(tmp_path / "ckpt"))
        assert fresh._fitted
        assert fresh._threshold == pytest.approx(det._threshold)
        probe = np.stack([det.featurize(ParserSchema.from_bytes(m))
                          for m in normal_msgs(4, salt="c")])
        np.testing.assert_allclose(det.score_tokens(probe),
                                   fresh.score_tokens(probe), rtol=1e-5)

    def test_checkpoint_is_topology_portable(self, tmp_path):
        """A checkpoint is a deployment artifact, not a topology pin: state
        trained on an 8-way mesh must restore on a single device (scale-in)
        and vice versa (scale-out), scoring identically — the param VALUES
        are the contract, mesh placement is per-process."""
        mesh_det = self._mesh_detector()
        mesh_det.process_batch(normal_msgs(32))
        mesh_det.save_checkpoint(str(tmp_path / "m2s"))

        single = JaxScorerDetector(config=scorer_config(async_fit=False))
        single.load_checkpoint(str(tmp_path / "m2s"))
        assert single._fitted
        assert single._threshold == pytest.approx(mesh_det._threshold)
        probe = np.stack([single.featurize(ParserSchema.from_bytes(m))
                          for m in normal_msgs(4, salt="x")])
        np.testing.assert_allclose(mesh_det.score_tokens(probe),
                                   single.score_tokens(probe), rtol=1e-4)

        # scale-out: the single-device-saved state onto a fresh mesh
        single.save_checkpoint(str(tmp_path / "s2m"))
        remeshed = self._mesh_detector()
        remeshed.load_checkpoint(str(tmp_path / "s2m"))
        assert remeshed._threshold == pytest.approx(single._threshold)
        np.testing.assert_allclose(remeshed.score_tokens(probe),
                                   single.score_tokens(probe), rtol=1e-4)

    def test_logbert_tensor_parallel_mesh(self):
        # dp×tp mesh: logbert params shard over "model" per the Megatron
        # rules; a tiny under-trained model is noisy, so assert the pipeline
        # contract (runs, in-order, list out) rather than alert quality
        det = JaxScorerDetector(config=scorer_config(
            model="logbert", mesh_shape={"data": 4, "model": 2},
            dim=32, depth=1, seq_len=16, threshold_sigma=8.0, async_fit=False))
        assert det.process_batch(normal_msgs(32)) == []
        assert det._exec.mesh_shape == {"data": 4, "model": 2}
        out = det.process_batch(normal_msgs(8)) + det.flush()
        assert isinstance(out, list)


def noisy_msg(stable, noise, log_id="1"):
    # one low-entropy field (comm) + one high-entropy field (pid)
    return msg("pid=<*> comm=<*> exe=<*>", [noise, stable, f"/usr/bin/{stable}"],
               log_id=log_id)


class TestPositionNorm:
    """score_norm=position: per-position z-scores calibrated on held-out
    training traffic — noisy fields self-suppress, low-entropy fields flag
    unseen values (models/logbert.py positional_z_max)."""

    def _config(self, **overrides):
        # sync fit: these tests assert calibration state right at the boundary
        return scorer_config(score_norm="position", data_use_training=96,
                             threshold_sigma=5.0, seq_len=16, async_fit=False,
                             **overrides)

    def _train_msgs(self, n, start=0):
        comms = ["cron", "sshd", "systemd", "bash"]
        return [noisy_msg(comms[i % 4], str(3000 + i * 17), log_id=str(start + i))
                for i in range(n)]

    def test_noisy_field_suppressed_stable_field_flagged(self):
        det = JaxScorerDetector(config=self._config())
        assert det.process_batch(self._train_msgs(96)) == []
        assert det._norm_mu is not None and det._norm_sigma is not None
        # fresh pids (noise) on known comms: no alerts
        out = det.process_batch(self._train_msgs(32, start=500)) + det.flush()
        assert [o for o in out if o is not None] == []
        # unseen comm (low-entropy field): alert
        bad = [noisy_msg("xmrig", "4242", log_id="999")]
        out = det.process_batch(self._train_msgs(7, start=600) + bad) + det.flush()
        alerts = [o for o in out if o is not None]
        assert len(alerts) == 1
        assert list(DetectorSchema.from_bytes(alerts[0]).logIDs) == ["999"]

    def test_checkpoint_preserves_calibration(self, tmp_path):
        det = JaxScorerDetector(config=self._config())
        det.process_batch(self._train_msgs(96))
        det.save_checkpoint(str(tmp_path / "ckpt"))
        fresh = JaxScorerDetector(config=self._config())
        fresh.load_checkpoint(str(tmp_path / "ckpt"))
        np.testing.assert_allclose(fresh._norm_mu, det._norm_mu, rtol=1e-6)
        np.testing.assert_allclose(fresh._norm_sigma, det._norm_sigma, rtol=1e-6)
        bad = [noisy_msg("xmrig", "77", log_id="7")]
        out = fresh.process_batch(self._train_msgs(7, start=700) + bad) + fresh.flush()
        assert len([o for o in out if o is not None]) == 1

    def test_position_norm_over_mesh(self):
        det = JaxScorerDetector(config=self._config(mesh_shape={"data": 8}))
        assert det.process_batch(self._train_msgs(96)) == []
        bad = [noisy_msg("nc", "88", log_id="888")]
        out = det.process_batch(self._train_msgs(7, start=800) + bad) + det.flush()
        alerts = [o for o in out if o is not None]
        assert len(alerts) == 1
        assert list(DetectorSchema.from_bytes(alerts[0]).logIDs) == ["888"]


class TestAsyncFit:
    """async_fit runs the train→detect boundary fit off-thread: the engine
    keeps draining input, mid-fit messages buffer in order, and the backlog
    dispatches when the fit lands (flush waits so nothing is lost at stop)."""

    def _slow_fit_detector(self, delay=0.4, **overrides):
        det = JaxScorerDetector(config=scorer_config(**overrides))
        real_fit = det.fit

        def slow_fit():
            import time
            time.sleep(delay)
            return real_fit()

        det.fit = slow_fit
        return det

    def test_mid_fit_messages_buffer_then_alert(self):
        det = self._slow_fit_detector()
        assert det.process_batch(normal_msgs(32)) == []    # boundary: fit starts
        assert det._fit_thread is not None and det._fit_thread.is_alive()
        weird = [msg("segfault <*> exploit <*>", ["0xdead", "x"], log_id="55")] * 4
        out = det.process_batch(normal_msgs(4) + weird)
        assert out == []                                   # buffered, fit running
        assert len(det._pending) == 8
        # idle-time flush must NOT block on the running fit (engine calls it
        # on every 100ms lull); stop-time flush_final waits and drains
        assert det.flush() == []
        drained = det.flush_final()
        assert det._fit_thread is None and det._pending == []
        assert det._fitted
        alerts = [o for o in drained if o is not None]
        assert alerts and all(
            set(DetectorSchema.from_bytes(a).logIDs) == {"55"} for a in alerts)

    def test_backlog_dispatches_on_next_batch_in_order(self):
        det = self._slow_fit_detector(delay=0.2, pipeline_depth=0)
        det.process_batch(normal_msgs(32))
        det.process_batch([msg("segfault <*> exploit <*>", ["0xdead", "a"],
                               log_id="71")])
        det._fit_thread.join()  # deterministic: fit lands in the background
        out = det.process_batch([msg("segfault <*> exploit <*>", ["0xdead", "b"],
                                     log_id="72")])
        out += det.flush()
        ids = [list(DetectorSchema.from_bytes(o).logIDs)[0]
               for o in out if o is not None]
        assert ids == ["71", "72"]  # backlog first, then the new message

    def test_sync_mode_unchanged(self):
        det = JaxScorerDetector(config=scorer_config(async_fit=False))
        assert det.process_batch(normal_msgs(32)) == []
        assert det._fit_thread is None
        assert det._fitted


class TestProcessFrames:
    """Fused wire-frame hot path: process_frames must produce exactly the
    alerts process_batch does, including across the training boundary, with
    packed, single, mixed, and corrupt frames."""

    def _mk(self, **overrides):
        return JaxScorerDetector(config=scorer_config(
            async_fit=False, **overrides))

    def test_steady_state_parity_with_process_batch(self):
        from detectmateservice_tpu.engine.framing import pack_batch

        det_a, det_b = self._mk(), self._mk()
        train = normal_msgs(32)
        det_a.process_batch(train)
        outs_b, n_b, lines_b = det_b.process_frames([pack_batch(train)])
        assert n_b == 32 and outs_b == []
        det_a.flush_final(), det_b.flush_final()
        normal = normal_msgs(16, salt="")
        anomaly = msg("ERROR <*> segfault at <*> code <*>",
                      ["kernel-panic", "0xdeadbeef", "0x7f"], log_id="evil")
        stream = normal[:7] + [anomaly] + normal[7:]
        outs_a = det_a.process_batch(stream) + det_a.flush()
        # mixed framing: packed chunk, bare message, packed remainder
        frames = [pack_batch(stream[:5])] + stream[5:6] + [pack_batch(stream[6:])]
        outs_f, n, n_lines = det_b.process_frames(frames)
        outs_f += det_b.flush()
        assert n == len(stream)
        alerts_a = [DetectorSchema.from_bytes(o) for o in outs_a if o]
        alerts_f = [DetectorSchema.from_bytes(o) for o in outs_f if o]
        assert len(alerts_a) == len(alerts_f) == 1
        assert alerts_a[0].logIDs == alerts_f[0].logIDs
        assert alerts_a[0].score == pytest.approx(alerts_f[0].score, rel=1e-5)

    def test_training_phase_via_frames(self):
        from detectmateservice_tpu.engine.framing import pack_batch

        det = self._mk(data_use_training=32)
        outs, n, _ = det.process_frames([pack_batch(normal_msgs(32))])
        assert n == 32 and outs == []          # all buffered for training
        det.flush_final()
        assert det._fitted
        anomaly = msg("ERROR <*> segfault at <*> code <*>",
                      ["boom", "0xff", "1"], log_id="evil")
        outs, n, _ = det.process_frames([anomaly])
        outs += det.flush()
        assert n == 1
        assert any(o for o in outs)

    def test_corrupt_frame_counted_not_fatal(self):
        from detectmateservice_tpu.engine import metrics as m
        from detectmateservice_tpu.engine.framing import pack_batch

        det = self._mk(data_use_training=4)
        det.process_frames([pack_batch(normal_msgs(4))])
        det.flush_final()
        counter = m.PROCESSING_ERRORS().labels(
            component_type=det.config.method_type, component_id=det.name)
        before = counter._value.get()
        corrupt = b"\xd7DM\x01\xff\xff\xff\xff\xff\xff\xff\xff\xff\xff"
        outs, n, _ = det.process_frames([corrupt, normal_msgs(1)[0]])
        assert n == 1                           # corrupt frame contributed 0
        assert counter._value.get() == before + 1

    def test_empty_packed_messages_filtered(self):
        from detectmateservice_tpu.engine.framing import pack_batch

        det = self._mk(data_use_training=4)
        det.process_frames([pack_batch(normal_msgs(4))])
        det.flush_final()
        frame = pack_batch([b"", normal_msgs(1)[0], b""])
        outs, n, _ = det.process_frames([frame])
        assert n == 1                           # empties silently dropped


class TestLongSequenceConfig:
    """Long-context configs (SURVEY §5.7) through the FULL detector
    contract — multi-line log windows tokenized to hundreds of positions.
    The op-level kernels are covered in test_flash/test_parallel; this
    pins the detector plumbing (tokenizer seq_len, chunked NLL, bucketing,
    calibration) at a sequence length far past the flagship 32."""

    def test_logbert_seq256_train_detect(self):
        det = JaxScorerDetector(config=scorer_config(
            model="logbert", depth=1, heads=2, dim=32, seq_len=256,
            vocab_size=2048, data_use_training=16, max_batch=16,
            train_epochs=1, min_train_steps=10, async_fit=False,
            threshold_sigma=4.0))
        # long synthetic lines: many variables -> many tokens per line
        def long_msg(tag, i):
            return msg("proc <*> " + "arg <*> " * 40,
                       [f"{tag}{i % 3}"] + [f"v{j % 7}" for j in range(40)],
                       log_id=f"{tag}{i}")
        det.process_batch([long_msg("n", i) for i in range(16)])
        det.flush_final()
        assert det._fitted
        weird = msg("segfault <*> " + "exploit <*> " * 40,
                    ["0xdead"] + [f"x{j}" for j in range(40)], log_id="evil")
        out = det.process_batch([long_msg("n", 99), weird]) + det.flush()
        alerts = [o for o in out if o is not None]
        ids = {i for a in alerts for i in DetectorSchema.from_bytes(a).logIDs}
        assert "evil" in ids
