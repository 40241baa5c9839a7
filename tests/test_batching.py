"""Adaptive continuous batching: the deadline-aware coalescer between the
engine and the device (library/detectors/jax_scorer.py _BatchCoalescer).

Covers the scheduler contract end to end:

* pure coalescer mechanics (injected clock — no flake): FIFO take across
  segment boundaries, per-row deadline clocks surviving splits, the
  release-early-as-the-deadline-approaches rule;
* detector-level coalescing: rows held across ``process_batch`` calls,
  in-order delivery under ``pipeline_depth`` backpressure, deadline- and
  target-occupancy releases, flush-everything on teardown — with ZERO
  unexpected XLA recompiles across coalescing, early release, bucket
  retirement, and resurrection (the few-compiled-shapes contract);
* bucket retirement policy: underused buckets leave the active set, their
  rows pad up, persistent best-fit pressure resurrects via an expected
  pre-warm, and ``GET /admin/xla``'s bucket state reports the live sets;
* engine↔scorer deferred-output plumbing: the engine honors a processor's
  ``drain_poll_ms`` hint, drains held rows on short-poll ticks, and
  ``flush_final`` drains everything at stop.
"""
import time

import numpy as np
import pytest

from detectmateservice_tpu.engine import Engine, InprocQueueSocketFactory
from detectmateservice_tpu.engine import device_obs
from detectmateservice_tpu.library.detectors import JaxScorerDetector
from detectmateservice_tpu.library.detectors.jax_scorer import (
    _BatchCoalescer,
    _ChainRaws,
)
from detectmateservice_tpu.schemas import ParserSchema, schemas_pb2 as pb
from detectmateservice_tpu.settings import ServiceSettings

from conftest import wait_until


def msg(i: int) -> bytes:
    return ParserSchema(
        EventID=1, template="user <*> logged in from <*>",
        variables=[f"u{i % 8}", f"10.0.0.{i % 16}"], logID=str(i),
        logFormatVariables={"Time": "1700000000"},
    ).serialize()


def alert_log_ids(outs) -> list:
    ids = []
    for o in outs:
        if o is None:
            continue
        d = pb.DetectorSchema()
        d.ParseFromString(o)
        ids.append(int(d.logIDs[0]))
    return ids


def coalescing_detector(**overrides) -> JaxScorerDetector:
    """Small, fast-compiling scorer with coalescing on and — unless
    overridden — an always-alert threshold so output order is observable
    per message."""
    base = {
        "method_type": "jax_scorer", "auto_config": False, "model": "mlp",
        "data_use_training": 32, "train_epochs": 1, "min_train_steps": 5,
        "seq_len": 16, "dim": 32, "max_batch": 32, "pipeline_depth": 2,
        "async_fit": False, "host_score_max_batch": 0,
        "batch_deadline_ms": 60.0, "batch_target_occupancy": 0.9,
        "score_threshold": -1e9,
    }
    base.update(overrides)
    det = JaxScorerDetector(config={"detectors": {"JaxScorerDetector": base}})
    det.setup_io()
    assert det.process_batch([msg(i) for i in range(32)]) == []
    det.flush_final()
    return det


# ---------------------------------------------------------------------------
# pure mechanics (injected clock, no jax)
# ---------------------------------------------------------------------------
class TestChainRaws:
    def test_indexes_across_segments(self):
        chain = _ChainRaws([[b"a", b"b"], [b"c"], [b"d", b"e"]])
        assert len(chain) == 5
        assert [chain[i] for i in range(5)] == [b"a", b"b", b"c", b"d", b"e"]
        assert chain[-1] == b"e"
        with pytest.raises(IndexError):
            chain[5]

    def test_slices_stay_lazy_and_correct(self):
        chain = _ChainRaws([[b"a", b"b"], [b"c"], [b"d", b"e"]])
        sub = chain[1:4]  # the dispatch chunking idiom
        assert isinstance(sub, _ChainRaws)
        assert [sub[i] for i in range(len(sub))] == [b"b", b"c", b"d"]
        assert [b for b in (chain[0:0])[0:0]._segs] == []


class TestCoalescerMechanics:
    def _rows(self, ids):
        tokens = np.asarray(ids, np.int32).reshape(-1, 1)
        return tokens, [str(i).encode() for i in ids]

    def test_take_preserves_fifo_across_segments(self):
        co = _BatchCoalescer(deadline_s=1.0, target_occupancy=0.9)
        co.add(*self._rows([1, 2, 3]), now=10.0)
        co.add(*self._rows([4, 5]), now=11.0)
        assert len(co) == 5
        tokens, raws, t_oldest = co.take(4)
        assert t_oldest == 10.0
        assert tokens[:, 0].tolist() == [1, 2, 3, 4]
        assert [raws[i] for i in range(4)] == [b"1", b"2", b"3", b"4"]
        assert len(co) == 1

    def test_split_segment_keeps_its_arrival_stamp(self):
        # the deadline clock is per ROW: splitting a call's rows across two
        # releases must not reset the remainder's age
        co = _BatchCoalescer(deadline_s=1.0, target_occupancy=0.9)
        co.add(*self._rows([1, 2, 3]), now=10.0)
        co.take(2)
        assert co.oldest_age(now=10.5) == pytest.approx(0.5)
        tokens, raws, t_oldest = co.take(1)
        assert t_oldest == 10.0 and tokens[0, 0] == 3 and raws[0] == b"3"

    def test_due_releases_one_tick_early(self):
        # the release rule: due once the oldest row's age reaches 75% of
        # the budget, so deadline + one drain tick (deadline/4) bounds the
        # worst-case wait at ~the budget itself
        co = _BatchCoalescer(deadline_s=0.100, target_occupancy=0.9)
        co.add(*self._rows([1]), now=0.0)
        assert not co.due(now=0.074)
        assert co.due(now=0.0751)  # 75% of the budget (float-epsilon past)
        assert co.due(now=5.0)

    def test_empty_coalescer_is_never_due(self):
        co = _BatchCoalescer(deadline_s=0.1, target_occupancy=0.9)
        assert not co.due(now=100.0)
        assert co.oldest_age(now=100.0) == 0.0

    def test_release_accounting(self):
        co = _BatchCoalescer(deadline_s=0.1, target_occupancy=0.9)
        co.note_release("deadline", 0.08)
        co.note_release("full", 0.01)
        assert co.releases == {"full": 1, "deadline": 1, "flush": 0}
        assert co.max_wait_s == pytest.approx(0.08)
        assert co.wait_sum_s == pytest.approx(0.09)


class TestRowWeightedHold:
    """``detector_row_hold_seconds_total`` / ``detector_rows_released_total``:
    the hold of the MEAN row, where ``detector_queue_wait_seconds`` observes
    the oldest row's."""

    def _rows(self, ids):
        tokens = np.asarray(ids, np.int32).reshape(-1, 1)
        return tokens, [str(i).encode() for i in ids]

    def test_scripted_sequence_sums_rows_times_wait(self):
        co = _BatchCoalescer(deadline_s=10.0, target_occupancy=0.9)
        co.add(*self._rows([1, 2, 3]), now=10.0)
        co.add(*self._rows([4, 5]), now=11.0)
        co.add(*self._rows([6, 7, 8, 9]), now=12.5)
        # release at 13.0: three rows held 3 s, two 2 s, one of four 0.5 s
        co.take(6, now=13.0)
        assert co.row_hold_s == 3 * 3.0 + 2 * 2.0 + 1 * 0.5
        assert co.rows_out == 6
        # the remainder keeps ITS stamp (12.5) across the second release
        co.add(*self._rows([10]), now=14.0)
        co.take(4, now=15.0)
        assert co.row_hold_s == 13.5 + 3 * 2.5 + 1 * 1.0
        assert co.rows_out == 10 and len(co) == 0
        # the oldest row's wait, by comparison, was 3.0 and 2.5

    def test_take_without_a_clock_counts_nothing(self):
        co = _BatchCoalescer(deadline_s=1.0, target_occupancy=0.9)
        co.add(*self._rows([1, 2]), now=1.0)
        co.take(2)
        assert co.row_hold_s == 0.0 and co.rows_out == 0

    def test_fair_share_release_weights_each_tenant_by_its_own_stamp(self):
        co = _BatchCoalescer(deadline_s=1.0, target_occupancy=0.9)
        co.add(*self._rows([1, 2, 3, 4]), now=0.0, tenant="a")
        co.add(*self._rows([5, 6, 7, 8]), now=1.0, tenant="b")
        co.take(4, now=2.0)              # two rows of each tenant
        assert co.row_hold_s == 2 * 2.0 + 2 * 1.0

    def test_counters_tick_at_release_by_reason(self):
        from prometheus_client import REGISTRY

        det = coalescing_detector(batch_deadline_ms=10_000.0)
        labels = det._obs_labels()

        def sample(name, **extra):
            return REGISTRY.get_sample_value(name, dict(labels, **extra)) or 0.0

        hold0 = sample("detector_row_hold_seconds_total")
        full0 = sample("detector_rows_released_total", reason="full")
        flush0 = sample("detector_rows_released_total", reason="flush")
        co0 = det._get_coalescer().row_hold_s
        det.process_batch([msg(i) for i in range(10)])
        time.sleep(0.03)
        det.process_batch([msg(i) for i in range(10, 30)])   # 30 >= 29: full
        assert sample("detector_rows_released_total",
                      reason="full") == full0 + 30
        held = sample("detector_row_hold_seconds_total") - hold0
        assert held == pytest.approx(det._coalescer.row_hold_s - co0)
        assert held >= 10 * 0.03 - 1e-3          # ten rows waited >= 30 ms
        det.process_batch([msg(99)])
        det.flush()
        assert sample("detector_rows_released_total",
                      reason="flush") == flush0 + 1
        assert det.batching_stats()["mean_row_hold_s"] > 0.0


class TestScoredRowsCounter:
    """``detector_device_lines_total`` / ``_batches_total`` count rows and
    batches the DEVICE path scored, when their scores are host-readable —
    not arrivals at the coalescer, and not the host twin's rows."""

    def _sample(self, det, name):
        from prometheus_client import REGISTRY

        labels = dict(det._obs_labels(), device=det._exec.label)
        return REGISTRY.get_sample_value(name, labels) or 0.0

    def test_ticks_at_drain_not_on_arrival(self):
        det = coalescing_detector(batch_deadline_ms=10_000.0)
        lines0 = self._sample(det, "detector_device_lines_total")
        batches0 = self._sample(det, "detector_device_batches_total")
        det.process_batch([msg(i) for i in range(20)])
        # held by the coalescer: arrived, not scored
        assert self._sample(det, "detector_device_lines_total") == lines0
        outs = det.flush()                   # release + drain
        assert len(alert_log_ids(outs)) == 20
        assert self._sample(det, "detector_device_lines_total") == lines0 + 20
        assert self._sample(
            det, "detector_device_batches_total") == batches0 + 1

    def test_host_twin_rows_do_not_count(self):
        det = coalescing_detector(host_score_max_batch=8,
                                  batch_deadline_ms=0.0)
        det.flush_final()                    # the twin's buckets are warm
        assert det._host_twin_state == "ready"
        lines0 = self._sample(det, "detector_device_lines_total")
        det.process_batch([msg(i) for i in range(4)])       # host twin
        det.flush()
        assert self._sample(det, "detector_device_lines_total") == lines0
        det.process_batch([msg(i) for i in range(16)])      # device
        det.flush()
        assert self._sample(det, "detector_device_lines_total") == lines0 + 16
        paths = [span["path"] for span in
                 device_obs.get_ledger().snapshot()["batches"][-2:]]
        assert paths == ["host", "device"]


class TestIdleAccountOnTheDispatchPath:
    def test_idle_time_between_two_batches_is_fill_then_host(self):
        """Device idle from the first batch's drain to the second call
        issued: the coalescer held rows short of its target (fill) until
        the target was reached, the rest is the release itself (host)."""
        det = coalescing_detector(batch_deadline_ms=10_000.0)
        det.process_batch([msg(i) for i in range(32)])       # full release
        t0 = time.monotonic()
        det.flush()                                          # seen readable
        clock = det._idle_clock
        assert clock.idle
        base = dict(clock.seconds)
        det.process_batch([msg(i) for i in range(5)])        # held: fill
        time.sleep(0.05)
        det.process_batch([msg(i) for i in range(5, 32)])    # target reached
        assert not clock.idle                                # call issued
        det.flush()
        wall = time.monotonic() - t0
        grown = {c: clock.seconds[c] - base[c] for c in clock.CAUSES}
        assert grown["fill"] >= 0.05 - 2e-3
        assert grown["host"] > 0.0                  # release → call issued
        # no_rows: from the drain to the first rows nothing was held
        assert sum(grown.values()) <= wall


# ---------------------------------------------------------------------------
# detector-level coalescing (CPU scorer; the acceptance behaviors)
# ---------------------------------------------------------------------------
class TestCoalescedDispatch:
    def test_rows_held_across_calls_then_deadline_release_in_order(self):
        det = coalescing_detector()
        unexpected0 = device_obs.get_ledger().snapshot()["totals"]["unexpected"]
        held = det.process_batch([msg(100), msg(101)])
        held += det.process_batch([msg(102)])
        # fewer ready results than inputs: the coalescer holds all three
        assert held == [] and len(det._inflight) == 0
        assert det.pending_count() == 1  # engine short-poll signal
        deadline_s = det.config.batch_deadline_ms / 1000.0
        tick_s = det.drain_poll_ms / 1000.0
        outs = []
        t0 = time.monotonic()
        while len(det._coalescer) and time.monotonic() - t0 < 5 * deadline_s:
            outs.extend(det.drain_ready())
            time.sleep(tick_s)
        outs.extend(det.flush())
        stats = det.batching_stats()
        assert stats["releases"]["deadline"] == 1
        # the acceptance bound: oldest-row wait <= deadline + one dispatch
        # interval (plus scheduler-jitter slack for a loaded CI box)
        assert stats["max_wait_s"] <= deadline_s + tick_s + 0.25
        assert alert_log_ids(outs) == [100, 101, 102]
        assert device_obs.get_ledger().snapshot()["totals"]["unexpected"] \
            == unexpected0

    def test_target_occupancy_triggers_full_release(self):
        det = coalescing_detector()
        # 70 rows vs max_batch 32 @ target 0.9 (=> release while held >= 29):
        # two full 32-chunks go immediately, 6 rows stay held for the deadline
        out = det.process_batch([msg(200 + i) for i in range(70)])
        stats = det.batching_stats()
        assert stats["releases"]["full"] == 2
        assert stats["held_rows"] == 6
        out += det.flush()
        assert alert_log_ids(out) == list(range(200, 270))
        # two full 32-chunks (occ 1.0) + the 6-row flush tail in bucket 8
        # (occ 0.75): mean stays at the >= 0.9 heavy-load target
        stats = det.batching_stats()
        assert stats["occupancy_mean"] >= 0.9

    def test_flush_releases_everything_on_teardown(self):
        det = coalescing_detector()
        assert det.process_batch([msg(300), msg(301)]) == []
        assert len(det._coalescer) == 2
        outs = det.flush_final()
        assert len(det._coalescer) == 0 and len(det._inflight) == 0
        assert det.batching_stats()["releases"]["flush"] >= 1
        assert alert_log_ids(outs) == [300, 301]

    def test_order_preserved_under_pipeline_depth_backpressure(self):
        det = coalescing_detector(pipeline_depth=1, batch_deadline_ms=30.0)
        outs = []
        for start in range(0, 320, 20):  # ragged calls, mid-bucket sizes
            outs.extend(det.process_batch(
                [msg(1000 + start + j) for j in range(20)]))
        outs.extend(det.flush())
        assert alert_log_ids(outs) == list(range(1000, 1320))

    def test_queue_wait_includes_coalescer_hold(self):
        det = coalescing_detector()
        det.process_batch([msg(1)])
        time.sleep(0.02)
        det.flush()
        span = device_obs.get_ledger().snapshot()["batches"][-1]
        assert span["release"] == "flush"
        assert span["queue_wait_s"] >= 0.02 - 1e-3

    def test_default_config_keeps_legacy_dispatch(self):
        det = coalescing_detector(batch_deadline_ms=0.0)
        assert det._get_coalescer() is None
        det.process_batch([msg(1), msg(2)])
        # no coalescer: the call dispatched immediately (results in flight
        # or already drained — never held)
        assert det._coalescer is None or len(det._coalescer) == 0
        assert alert_log_ids(det.flush()) == [1, 2]

    def test_runtime_disable_flushes_held_rows(self):
        det = coalescing_detector()
        assert det.process_batch([msg(7)]) == []
        det.config.batch_deadline_ms = 0.0
        det.apply_config()
        outs = det.drain_ready() + det.flush()
        assert alert_log_ids(outs) == [7]
        assert det.batching_stats()["releases"]["flush"] >= 1


# ---------------------------------------------------------------------------
# bucket retirement / resurrection
# ---------------------------------------------------------------------------
class TestBucketRetirement:
    def _retiring_detector(self):
        return coalescing_detector(bucket_retire_interval_s=60.0,
                                   bucket_retire_min_dispatches=2)

    def test_underused_buckets_retire_and_largest_survives(self):
        det = self._retiring_detector()
        # bucket 4 used once (below the floor), bucket 32 used repeatedly
        det.process_batch([msg(i) for i in range(3)])
        det.flush()
        for _ in range(3):
            det.process_batch([msg(i) for i in range(32)])
            det.flush()
        det._retire_sweep(time.monotonic())
        stats = det.batching_stats()
        assert 4 in stats["retired_buckets"]
        assert 32 in stats["warm_buckets"]  # the pad-up backstop never goes
        # /admin/xla's document carries the live sets
        buckets = device_obs.get_ledger().snapshot()["buckets"]
        assert buckets["retired"] == stats["retired_buckets"]
        assert buckets["coalescing"] is True

    def test_retired_bucket_pads_up_without_recompiling(self):
        det = self._retiring_detector()
        unexpected0 = device_obs.get_ledger().snapshot()["totals"]["unexpected"]
        det.process_batch([msg(i) for i in range(3)])   # warms bucket 4
        det.flush()
        det._retire_sweep(time.monotonic())
        assert 4 in det.batching_stats()["retired_buckets"]
        det.process_batch([msg(i) for i in range(3)])   # would best-fit 4
        det.flush()
        span = device_obs.get_ledger().snapshot()["batches"][-1]
        assert span["real"] == 3 and span["bucket"] > 4  # padded up
        assert device_obs.get_ledger().snapshot()["totals"]["unexpected"] \
            == unexpected0

    def test_persistent_pressure_resurrects_via_expected_prewarm(self):
        det = self._retiring_detector()
        ledger = device_obs.get_ledger()
        unexpected0 = ledger.snapshot()["totals"]["unexpected"]
        det.process_batch([msg(i) for i in range(3)])
        det.flush()
        det._retire_sweep(time.monotonic())
        assert 4 in det._retired_buckets
        # keep hitting the retired bucket's best fit: after
        # bucket_retire_min_dispatches pad-ups it resurrects
        for _ in range(4):
            det.process_batch([msg(i) for i in range(3)])
            det.flush()
        stats = det.batching_stats()
        assert 4 in stats["warm_buckets"]
        assert 4 not in stats["retired_buckets"]
        snap = ledger.snapshot()
        assert snap["totals"]["unexpected"] == unexpected0
        # the resurrection compile (if XLA re-compiled at all) attributed
        # to the expected bucket_warm context, never the dispatch path
        warm_events = [e for e in snap["compiles"]
                       if e["where"] == "bucket_warm"]
        assert all(not e["unexpected"] for e in warm_events)


# ---------------------------------------------------------------------------
# engine ↔ scorer deferred-output plumbing (fake processor, real engine)
# ---------------------------------------------------------------------------
class HoldingProcessor:
    """Models the coalescer's engine-visible contract: process_batch holds
    rows; drain_ready releases them (upper-cased) after a hold count of
    short-poll ticks; flush/flush_final release everything."""

    drain_poll_ms = 17

    def __init__(self, ticks_to_release: int = 2):
        self.held = []
        self.ticks = 0
        self.ticks_to_release = ticks_to_release
        self.flush_final_called = False

    def process(self, data):  # engine Processor contract
        return data.upper()

    def process_batch(self, batch):
        self.held.extend(batch)
        return []

    def pending_count(self):
        return len(self.held)

    def drain_ready(self):
        self.ticks += 1
        if self.ticks < self.ticks_to_release:
            return []
        out, self.held = [d.upper() for d in self.held], []
        return out

    def flush(self):
        out, self.held = [d.upper() for d in self.held], []
        return out

    def flush_final(self):
        self.flush_final_called = True
        return self.flush()


def batch_settings(addr: str, **overrides) -> ServiceSettings:
    base = dict(component_type="core", engine_addr=addr, out_addr=[],
                engine_batch_size=8, engine_batch_timeout_ms=5.0,
                engine_recv_timeout=50, log_to_file=False)
    base.update(overrides)
    return ServiceSettings(**base)


class TestEngineDeferredOutputs:
    def test_engine_honors_drain_poll_hint_and_drains_held_rows(self,
                                                                inproc_factory):
        proc = HoldingProcessor(ticks_to_release=4)
        engine = Engine(batch_settings("inproc://coal1"), proc,
                        inproc_factory)
        client = inproc_factory.create_output("inproc://coal1")
        client.recv_timeout = 2000
        try:
            engine.start()
            client.send(b"held-row")
            # while results are pending the engine must poll at the
            # processor's drain_poll_ms hint, not the 5 ms default
            assert wait_until(
                lambda: engine._pair_sock.recv_timeout == proc.drain_poll_ms,
                2.0)
            # the reply arrives via drain_ready short-poll ticks — within
            # ~ticks_to_release * drain_poll_ms, far inside the idle lull
            assert client.recv() == b"HELD-ROW"
        finally:
            engine.stop()
            client.close()

    def test_stop_flush_final_drains_held_rows(self, inproc_factory):
        proc = HoldingProcessor(ticks_to_release=10**9)  # never self-release
        engine = Engine(batch_settings("inproc://coal2"), proc,
                        inproc_factory)
        client = inproc_factory.create_output("inproc://coal2")
        client.recv_timeout = 2000
        try:
            engine.start()
            client.send(b"stuck-row")
            assert wait_until(lambda: proc.held, 2.0)
            engine.stop()
            assert proc.flush_final_called
            assert proc.held == []
            assert client.recv() == b"STUCK-ROW"
        finally:
            client.close()

    def test_default_short_poll_without_hint(self, inproc_factory):
        class NoHint(HoldingProcessor):
            drain_poll_ms = None

        engine = Engine(batch_settings("inproc://coal3"),
                        NoHint(ticks_to_release=1), inproc_factory)
        client = inproc_factory.create_output("inproc://coal3")
        client.recv_timeout = 2000
        try:
            engine.start()
            client.send(b"x")
            assert client.recv() == b"X"
        finally:
            engine.stop()
            client.close()
