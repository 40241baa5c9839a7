"""dmroll — online learning + zero-downtime rollout (rollout/, PR 10).

Covers the subsystem contract end to end:

* sampler bounds + determinism (injected clock, seeded RNG — no flake);
* checkpoint crash-atomicity: an injected crash mid-save can never leave a
  corrupt "latest" that ``load_scorer_state`` trusts, and the versioned
  store's keep-N rotation never prunes the live/pinned/newest entries;
* shadow divergence math + the three-valued promotion gate;
* the pre-warm-then-swap zero-recompile contract against the real XLA
  ledger (fine-tune → shadow → promote → hot-swap with the dispatch path
  still scoring, ``scorer_xla_recompiles_unexpected_total`` frozen);
* promotion/holdback through the RolloutManager incl. the structured
  ``model_canary_holdback`` event and pin/rollback verbs;
* the rolling fleet deploy over the router admin plane (drain → promote →
  undrain per replica; one replica rejecting the checkpoint rolls the
  whole tier back).
"""
import io
import json
import urllib.error
from pathlib import Path

import numpy as np
import pytest

from detectmateservice_tpu.rollout import (
    CheckpointStore,
    RolloutError,
    RolloutManager,
    ShadowEvaluator,
    StoreError,
    TrafficSampler,
)
from detectmateservice_tpu.schemas import ParserSchema, schemas_pb2 as pb
from detectmateservice_tpu.settings import ServiceSettings


def msg(i: int) -> bytes:
    return ParserSchema(
        EventID=1, template="user <*> logged in from <*>",
        variables=[f"u{i % 8}", f"10.0.0.{i % 16}"], logID=str(i),
        logFormatVariables={"Time": "1700000000"},
    ).serialize()


# ---------------------------------------------------------------------------
# sampler: bounds + determinism (injected clock)
# ---------------------------------------------------------------------------
class TestTrafficSampler:
    def test_capacity_bounds_memory(self):
        sampler = TrafficSampler(capacity=64, ratio=1.0, seed=3)
        for start in range(0, 4096, 128):
            sampler.offer_rows(np.arange(start, start + 128,
                                         dtype=np.int32).reshape(128, 1))
        assert len(sampler) == 64
        snap = sampler.snapshot()
        assert snap.shape == (64, 1)
        stats = sampler.stats()
        assert stats["rows_offered"] == 4096
        assert stats["rows_sampled"] == 4096  # ratio 1.0 filters nothing

    def test_deterministic_for_seed_and_offer_order(self):
        def fill(seed):
            s = TrafficSampler(capacity=32, ratio=0.5, seed=seed)
            for start in range(0, 1024, 64):
                s.offer_rows(np.arange(start, start + 64,
                                       dtype=np.int32).reshape(64, 1))
            return s.snapshot()

        assert np.array_equal(fill(7), fill(7))
        assert not np.array_equal(fill(7), fill(8))

    def test_ratio_thins_the_stream(self):
        sampler = TrafficSampler(capacity=100000, ratio=0.25, seed=1)
        sampler.offer_rows(np.zeros((10000, 2), np.int32))
        assert 0.2 < sampler.stats()["rows_sampled"] / 10000 < 0.3

    def test_injected_clock_drives_offer_age(self):
        now = [100.0]
        sampler = TrafficSampler(capacity=8, ratio=1.0,
                                 clock=lambda: now[0])
        assert sampler.last_offer_age() is None
        sampler.offer_rows(np.zeros((2, 2), np.int32))
        now[0] = 107.5
        assert sampler.last_offer_age() == pytest.approx(7.5)

    def test_rejects_bad_parameters(self):
        with pytest.raises(ValueError):
            TrafficSampler(capacity=0, ratio=0.5)
        with pytest.raises(ValueError):
            TrafficSampler(capacity=8, ratio=0.0)
        with pytest.raises(ValueError):
            TrafficSampler(capacity=8, ratio=1.5)


# ---------------------------------------------------------------------------
# shadow divergence math + promotion gate
# ---------------------------------------------------------------------------
class TestShadowEvaluator:
    def test_divergence_math_is_exact(self):
        ev = ShadowEvaluator(threshold=1.0, min_samples=4,
                             max_mean_delta=0.5, max_flip_ratio=0.25)
        delta = ev.observe(np.array([0.0, 2.0, 0.5, 1.5]),
                           np.array([0.1, 1.8, 1.2, 1.4]))
        assert delta == pytest.approx([0.1, 0.2, 0.7, 0.1])
        assert ev.samples == 4
        assert ev.mean_delta == pytest.approx(0.275)
        assert ev.delta_max == pytest.approx(0.7)
        # flips: 0.5 vs 1.2 crosses the 1.0 threshold; the rest agree
        assert ev.flips == 1
        assert ev.flip_ratio == pytest.approx(0.25)

    def test_gate_waits_then_promotes(self):
        ev = ShadowEvaluator(threshold=10.0, min_samples=8,
                             max_mean_delta=0.5, max_flip_ratio=0.01)
        ev.observe(np.zeros(4), np.full(4, 0.1))
        assert ev.verdict() == "wait"
        ev.observe(np.zeros(4), np.full(4, 0.1))
        assert ev.verdict() == "promote"

    def test_gate_holds_on_mean_delta(self):
        ev = ShadowEvaluator(threshold=10.0, min_samples=2,
                             max_mean_delta=0.5, max_flip_ratio=1.0)
        ev.observe(np.zeros(4), np.full(4, 2.0))
        assert ev.verdict() == "hold"

    def test_gate_holds_on_flip_ratio(self):
        ev = ShadowEvaluator(threshold=1.0, min_samples=2,
                             max_mean_delta=10.0, max_flip_ratio=0.1)
        # tiny deltas, but every row flips the alert decision
        ev.observe(np.full(4, 0.95), np.full(4, 1.05))
        assert ev.verdict() == "hold"
        assert ev.stats()["verdict"] == "hold"

    def test_shape_mismatch_rejected(self):
        ev = ShadowEvaluator(threshold=1.0, min_samples=1,
                             max_mean_delta=1.0, max_flip_ratio=1.0)
        with pytest.raises(ValueError):
            ev.observe(np.zeros(3), np.zeros(4))


# ---------------------------------------------------------------------------
# versioned store: rotation, keep-N, pin, manifest atomicity
# ---------------------------------------------------------------------------
class TestCheckpointStore:
    def test_record_live_history_and_rollback_target(self, tmp_path):
        store = CheckpointStore(tmp_path / "s", keep=10)
        for v in (1, 2):
            store.version_dir(v).mkdir()
            store.record(v, {"tag": f"v{v}"})
        store.set_live(1)
        store.set_live(2)
        assert store.live_version() == 2
        assert store.previous_live() == 1
        statuses = {e["version"]: e["status"] for e in store.history()}
        assert statuses == {1: "superseded", 2: "live"}

    def test_keep_n_prunes_oldest_but_never_live_pinned_newest(self, tmp_path):
        store = CheckpointStore(tmp_path / "s", keep=2)
        for v in range(1, 6):
            store.version_dir(v).mkdir()
            (store.version_dir(v) / "blob").write_text("x")
            if v == 1:
                store.record(v, {})
                store.set_live(1)
                store.pin(1)
            else:
                store.record(v, {})
        versions = [e["version"] for e in store.manifest()["entries"]]
        # live+pinned v1 and newest v5 survive; the window squeezed the rest
        assert 1 in versions and 5 in versions
        assert not store.version_dir(2).exists()
        assert store.version_dir(1).exists()
        assert store.version_dir(5).exists()

    def test_pin_unknown_version_fails(self, tmp_path):
        store = CheckpointStore(tmp_path / "s")
        with pytest.raises(StoreError):
            store.pin(99)

    def test_manifest_commit_is_atomic(self, tmp_path, monkeypatch):
        store = CheckpointStore(tmp_path / "s", keep=4)
        store.version_dir(1).mkdir()
        store.record(1, {"ok": True})
        before = (store.root / "MANIFEST.json").read_text()

        import detectmateservice_tpu.utils.checkpoint as ckpt

        def crash(tmp, final):
            raise OSError("injected crash before the rename commit")

        monkeypatch.setattr(ckpt.os, "replace", crash)
        store.version_dir(2).mkdir()
        with pytest.raises(OSError):
            store.record(2, {"ok": False})
        monkeypatch.undo()
        # the manifest on disk is byte-identical: the torn write never
        # reached the commit point
        assert (store.root / "MANIFEST.json").read_text() == before
        assert [e["version"] for e in store.history()] == [1]


# ---------------------------------------------------------------------------
# checkpoint crash-atomicity (utils/checkpoint.py)
# ---------------------------------------------------------------------------
class TestCheckpointCrashAtomicity:
    def test_crash_mid_save_preserves_previous_generation(self, tmp_path,
                                                          monkeypatch):
        from detectmateservice_tpu.utils import checkpoint as ckpt

        directory = str(tmp_path / "ck")
        params_v1 = {"w": np.full(4, 1.0, np.float32)}
        opt_v1 = {"m": np.zeros(4, np.float32)}
        ckpt.save_scorer_state(directory, params_v1, opt_v1,
                               {"generation": 1})

        # crash AFTER the new data dirs are written but BEFORE the meta
        # commit — the window the old in-place layout corrupted
        real_commit = ckpt.write_json_atomic

        def crash(path, doc):
            raise OSError("injected crash before meta commit")

        monkeypatch.setattr(ckpt, "write_json_atomic", crash)
        with pytest.raises(OSError):
            ckpt.save_scorer_state(directory,
                                   {"w": np.full(4, 2.0, np.float32)},
                                   opt_v1, {"generation": 2})
        monkeypatch.setattr(ckpt, "write_json_atomic", real_commit)

        params, _opt, meta = ckpt.load_scorer_state(
            directory, {"w": np.zeros(4, np.float32)},
            {"m": np.zeros(4, np.float32)})
        assert meta["generation"] == 1
        assert np.array_equal(np.asarray(params["w"]), params_v1["w"])

        # a later successful save commits generation 3 and prunes the
        # crashed generation's orphan dirs
        ckpt.save_scorer_state(directory,
                               {"w": np.full(4, 3.0, np.float32)},
                               opt_v1, {"generation": 3})
        params, _opt, meta = ckpt.load_scorer_state(
            directory, {"w": np.zeros(4, np.float32)},
            {"m": np.zeros(4, np.float32)})
        assert meta["generation"] == 3
        assert np.asarray(params["w"])[0] == 3.0
        nonce = meta["data_nonce"]
        stray = [p.name for p in Path(directory).glob("params.*")
                 if not p.name.endswith(nonce)]
        assert stray == []

    def test_legacy_bare_layout_still_loads(self, tmp_path):
        """A pre-PR-10 checkpoint (no data_nonce, bare params/opt_state
        dirs) must keep restoring."""
        from detectmateservice_tpu.utils import checkpoint as ckpt

        directory = tmp_path / "legacy"
        directory.mkdir()
        ckptr = ckpt._checkpointer()
        ckptr.save(directory / "params", {"w": np.full(2, 5.0, np.float32)},
                   force=True)
        ckptr.save(directory / "opt_state", {"m": np.zeros(2, np.float32)},
                   force=True)
        ckptr.wait_until_finished()
        (directory / "meta.json").write_text(
            json.dumps({"tree_version": 1, "generation": 0}))
        params, _opt, meta = ckpt.load_scorer_state(
            str(directory), {"w": np.zeros(2, np.float32)},
            {"m": np.zeros(2, np.float32)})
        assert np.asarray(params["w"])[0] == 5.0
        assert "data_nonce" not in meta


# ---------------------------------------------------------------------------
# detector + manager: fine-tune, zero-recompile swap, gate, verbs
# ---------------------------------------------------------------------------
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


@pytest.fixture(scope="module")
def fitted_detector():
    return make_detector()


def rollout_settings(tmp_path, **overrides) -> ServiceSettings:
    base = dict(
        component_type="core", component_name="rollout-test", http_port=0,
        rollout_enabled=True, rollout_dir=str(tmp_path / "store"),
        rollout_interval_s=3600.0, rollout_sample_ratio=1.0,
        rollout_sample_capacity=256, rollout_min_fit_rows=16,
        rollout_min_shadow_samples=16, rollout_shadow_timeout_s=30.0,
        rollout_max_mean_delta=5.0, rollout_max_flip_ratio=0.1,
        rollout_keep_checkpoints=4)
    base.update(overrides)
    return ServiceSettings(**base)


class EventSink:
    def __init__(self):
        self.events = []

    def emit_event(self, event, level=None):
        self.events.append(event)
        return event

    def kinds(self):
        return [e.get("kind") for e in self.events]


def make_manager(det, tmp_path, monkeypatch=None, **overrides):
    sink = EventSink()
    mgr = RolloutManager(
        det, rollout_settings(tmp_path, **overrides),
        labels={"component_type": "test",
                "component_id": f"rollout-{tmp_path.name}"},
        monitor=sink)
    return mgr, sink


def feed(det, base, n=64):
    for start in range(0, n, 16):
        det.process_batch([msg(base + start + i) for i in range(16)])
    det.flush()


def unexpected_total():
    from detectmateservice_tpu.engine import device_obs

    return device_obs.get_ledger().snapshot(limit=1)["totals"]["unexpected"]


class TestDetectorRollout:
    def test_fine_tune_leaves_live_params_untouched(self, fitted_detector):
        import jax

        det = fitted_detector
        live_leaf = np.array(jax.tree_util.tree_leaves(det._exec.params)[0])
        rows = np.random.default_rng(0).integers(
            0, 100, size=(64, det.config.seq_len)).astype(np.int32)
        params, opt_state, info = det.rollout_fine_tune(rows, epochs=2,
                                                        seed=1)
        assert info["steps"] >= 2 and np.isfinite(info["loss"])
        assert np.array_equal(
            live_leaf, np.array(jax.tree_util.tree_leaves(det._exec.params)[0]))
        cand_leaf = np.array(jax.tree_util.tree_leaves(params)[0])
        assert not np.array_equal(live_leaf, cand_leaf)

    def test_prewarm_then_swap_is_recompile_free(self, fitted_detector):
        det = fitted_detector
        rows = np.random.default_rng(1).integers(
            0, 100, size=(48, det.config.seq_len)).astype(np.int32)
        before = unexpected_total()
        params, opt_state, _ = det.rollout_fine_tune(rows, seed=2)
        swap = det.install_candidate(params, opt_state, version=41)
        assert swap["swapped"] and swap["prewarmed_buckets"]
        assert det.model_version() == 41
        # the dispatch path keeps scoring the new params without a compile
        outs = [o for o in det.process_batch(
            [msg(900 + i) for i in range(16)]) if o is not None]
        outs += [o for o in det.flush() if o is not None]
        assert outs, "no alerts flowed after the swap"
        assert unexpected_total() == before

    def test_shadow_scores_match_live_for_identical_params(
            self, fitted_detector):
        det = fitted_detector
        rows = np.random.default_rng(2).integers(
            0, 100, size=(20, det.config.seq_len)).astype(np.int32)
        live = det.rollout_scores(None, rows)
        same = det.rollout_scores(det._exec.params, rows)
        assert np.allclose(live, same)
        assert live.shape == (20,)


class TestRolloutManager:
    def test_cycle_promotes_through_the_gate(self, tmp_path):
        det = make_detector()
        mgr, sink = make_manager(det, tmp_path)
        try:
            feed(det, 1000)
            before = unexpected_total()
            info = mgr.run_cycle(reason="test", block=True)
            outcome = info["outcome"]
            assert outcome["result"] == "promoted", info
            assert mgr.store.live_version() == outcome["version"]
            assert det.model_version() == outcome["version"]
            assert unexpected_total() == before
            assert "model_promoted" in sink.kinds()
            status = mgr.status()
            assert status["live_version"] == outcome["version"]
            assert status["sampler"]["rows_offered"] > 0
        finally:
            mgr.stop()

    def test_broken_candidate_holds_back_with_event(self, tmp_path):
        import jax

        det = make_detector()
        mgr, sink = make_manager(det, tmp_path)
        try:
            feed(det, 2000)
            broken = jax.tree_util.tree_map(lambda a: a * 10.0, det._exec.params)
            version = mgr.inject_candidate(broken, det._exec.opt_state,
                                           tag="broken", min_samples=8)
            outcome = None
            for _ in range(20):
                outcome = mgr.shadow_tick()
                if outcome is not None:
                    break
            assert outcome is not None and outcome["result"] == "holdback"
            assert "model_canary_holdback" in sink.kinds()
            entry = mgr.store.entry(version)
            assert entry["status"] == "holdback"
            assert entry["meta"]["divergence"]["mean_abs_delta"] > 1.0
            # the live model was never touched
            assert det.model_version() == 0
            assert mgr.store.live_version() is None
        finally:
            mgr.stop()

    def test_promote_by_version_and_rollback(self, tmp_path):
        det = make_detector()
        mgr, sink = make_manager(det, tmp_path)
        try:
            feed(det, 3000)
            v1 = mgr.run_cycle(block=True)["outcome"]["version"]
            feed(det, 3200)
            v2 = mgr.run_cycle(block=True)["outcome"]["version"]
            assert (v1, v2) == (1, 2)
            assert mgr.store.live_version() == 2
            out = mgr.rollback()
            assert out["result"] == "rolled_back" and out["version"] == 1
            assert det.model_version() == 1
            assert mgr.store.live_version() == 1
            # promote back up by number off the store
            out = mgr.promote(version=2)
            assert out["result"] == "promoted" and det.model_version() == 2
            assert "model_rolled_back" in sink.kinds()
        finally:
            mgr.stop()

    def test_pin_suspends_cycles(self, tmp_path):
        det = make_detector()
        mgr, _sink = make_manager(det, tmp_path)
        try:
            feed(det, 4000)
            v1 = mgr.run_cycle(block=True)["outcome"]["version"]
            mgr.pin(v1)
            info = mgr.run_cycle(reason="test")
            assert "pinned" in info["skipped"]
            mgr.unpin()
            feed(det, 4200)
            assert mgr.run_cycle(block=True)["outcome"]["version"] == 2
        finally:
            mgr.stop()

    def test_rollback_without_history_fails(self, tmp_path):
        det = make_detector()
        mgr, _sink = make_manager(det, tmp_path)
        try:
            with pytest.raises(RolloutError):
                mgr.rollback()
            with pytest.raises(RolloutError):
                mgr.promote()            # nothing shadowing
        finally:
            mgr.stop()


# ---------------------------------------------------------------------------
# rolling fleet deploy over the router admin plane
# ---------------------------------------------------------------------------
class StubReplicaClient:
    def __init__(self, state):
        self.state = state

    def model_action(self, action, version=None, block=False):
        self.state["calls"].append((self.state["addr"], action, version))
        if action == "promote":
            if self.state.get("reject"):
                raise urllib.error.HTTPError(
                    "http://x", 400, "tree-version mismatch", {},
                    io.BytesIO(b"{}"))
            self.state["prev"] = self.state["live"]
            self.state["live"] = version
            return {"result": "promoted", "version": version}
        if action == "rollback":
            self.state["live"] = self.state.get("prev")
            return {"result": "rolled_back"}
        raise AssertionError(f"unexpected action {action}")

    def model_status(self):
        return {"live_version": self.state["live"]}


class StubRouterClient:
    def __init__(self, fleet, log):
        self.fleet = fleet
        self.log = log

    def replicas(self):
        return {"replicas": [
            {"addr": s["addr"], "admin_url": s["admin"], "state": s["state"]}
            for s in self.fleet]}

    def _find(self, addr):
        return next(s for s in self.fleet if s["addr"] == addr)

    def replica_drain(self, addr):
        self.log.append(("drain", addr))
        self._find(addr)["state"] = "drained"

    def replica_undrain(self, addr):
        self.log.append(("undrain", addr))
        self._find(addr)["state"] = "active"


def make_fleet(n, reject=()):
    log = []
    fleet = []
    for i in range(n):
        fleet.append({"addr": f"inproc://rep-{i}",
                      "admin": f"http://admin-{i}", "state": "active",
                      "live": 0, "calls": log, "reject": i in reject})
    return fleet, log


def fleet_factory(fleet, log):
    def factory(url):
        if url == "http://router":
            return StubRouterClient(fleet, log)
        for s in fleet:
            if s["admin"] == url:
                return StubReplicaClient(s)
        raise AssertionError(f"unknown url {url}")
    return factory


class TestRollingDeploy:
    def test_rolls_every_replica_drain_promote_undrain(self):
        from detectmateservice_tpu.client import rolling_deploy

        fleet, log = make_fleet(3)
        printed = []
        rc = rolling_deploy("http://router", 7,
                            client_factory=fleet_factory(fleet, log),
                            timeout_s=5, poll_s=0, sleep=lambda s: None,
                            out=printed.append)
        assert rc == 0
        assert all(s["live"] == 7 for s in fleet)
        assert all(s["state"] == "active" for s in fleet)
        # strict per-replica ordering: drain → promote → undrain, one
        # replica at a time (the stub records both verb streams into one
        # shared log, so interleaving is fully observable)
        assert log == [("drain", "inproc://rep-0"),
                       ("inproc://rep-0", "promote", 7),
                       ("undrain", "inproc://rep-0"),
                       ("drain", "inproc://rep-1"),
                       ("inproc://rep-1", "promote", 7),
                       ("undrain", "inproc://rep-1"),
                       ("drain", "inproc://rep-2"),
                       ("inproc://rep-2", "promote", 7),
                       ("undrain", "inproc://rep-2")]

    def test_rejecting_replica_rolls_the_tier_back(self):
        from detectmateservice_tpu.client import rolling_deploy

        fleet, log = make_fleet(3, reject={1})
        printed = []
        rc = rolling_deploy("http://router", 7,
                            client_factory=fleet_factory(fleet, log),
                            timeout_s=5, poll_s=0, sleep=lambda s: None,
                            out=printed.append)
        assert rc == 1
        # replica 0 was promoted then rolled back; replica 1 rejected;
        # replica 2 was never touched
        assert fleet[0]["live"] == 0
        assert fleet[2]["live"] == 0
        actions = [c for c in fleet[0]["calls"]]
        assert ("inproc://rep-0", "promote", 7) in actions
        assert ("inproc://rep-0", "rollback", None) in actions
        assert ("inproc://rep-1", "promote", 7) in actions
        assert not any(a[0] == "inproc://rep-2" for a in actions)
        # the failed replica was undrained so the tier keeps its capacity
        assert ("undrain", "inproc://rep-1") in log

    def test_replicas_without_admin_urls_refused(self):
        from detectmateservice_tpu.client import rolling_deploy

        fleet, log = make_fleet(1)
        fleet[0]["admin"] = None
        rc = rolling_deploy("http://router", 1,
                            client_factory=fleet_factory(fleet, log),
                            sleep=lambda s: None, out=lambda s: None)
        assert rc == 2


# ---------------------------------------------------------------------------
# settings + admin plumbing
# ---------------------------------------------------------------------------
class TestRolloutPlumbing:
    def test_rollout_requires_dir(self):
        with pytest.raises(SystemExit):
            # from_yaml-style failure is SystemExit; direct construction
            # raises pydantic's ValidationError — accept either
            try:
                ServiceSettings(rollout_enabled=True)
            except Exception as exc:
                raise SystemExit(str(exc)) from exc

    def test_admin_model_404_without_rollout(self):
        from detectmateservice_tpu.web.router import _model, _model_control

        class Stub:
            rollout = None

        assert _model(Stub(), {}, None).status == 404
        assert _model_control(Stub(), {}, {"action": "promote"}).status == 404

    def test_admin_model_unknown_action_rejected(self, tmp_path):
        from detectmateservice_tpu.web.router import _model_control

        class Stub:
            rollout = object()   # present but never reached

        with pytest.raises(ValueError):
            _model_control(Stub(), {}, {"action": "explode"})
