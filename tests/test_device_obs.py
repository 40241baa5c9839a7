"""Device-side observability (engine/device_obs.py + the admin/profiler
surface): the XLA compile ledger attributes compiles, flags unexpected
recompiles after warm-up, exports HBM gauges only where the backend reports
memory stats, and the on-demand profiler capture is concurrency-guarded and
disk-bounded.

The Service-level class is the acceptance path: a real jax_scorer detector
warms up on CPU, an injected dispatch on an unwarmed bucket triggers a REAL
XLA compile, and the flag propagates end to end — counter, structured event
on /admin/events, xla_recompile_storm degradation on /admin/health?deep=1,
and a ledger entry on /admin/xla.
"""
import io
import json
import time
import urllib.error
import urllib.request
import zipfile

import numpy as np
import pytest
from prometheus_client import REGISTRY

from detectmateservice_tpu.core import Service
from detectmateservice_tpu.engine import device_obs
from detectmateservice_tpu.engine.device_obs import (
    CompileLedger,
    RecompileStormCheck,
)
from detectmateservice_tpu.engine.health import EventLog, HealthMonitor
from detectmateservice_tpu.settings import ServiceSettings

LABELS = {"component_type": "test_obs", "component_id": "obs-1"}


def http_json(port, path, method="GET"):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", method=method,
        data=b"" if method == "POST" else None)
    try:
        with urllib.request.urlopen(req, timeout=10) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as exc:
        return exc.code, json.loads(exc.read())


def http_raw(port, path):
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=10) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as exc:
        return exc.code, exc.read()


def make_monitor(events=None):
    return HealthMonitor(dict(LABELS), events=events)


# ---------------------------------------------------------------------------
# ledger unit behavior (injected records — no jax compiles needed)
# ---------------------------------------------------------------------------
class TestCompileLedger:
    def test_warmup_compiles_are_recorded_but_never_flagged(self):
        ledger = CompileLedger()
        ledger.bind(labels=LABELS)
        event = ledger.record_compile(0.5, bucket=8, backend="cpu",
                                      where="warmup", expected=True)
        assert event["phase"] == "warmup"
        assert event["unexpected"] is False
        snap = ledger.snapshot()
        assert snap["warmup_complete"] is False
        assert snap["totals"] == {"compiles": 1, "seconds": 0.5,
                                  "unexpected": 0}
        assert snap["compiles"][0]["bucket"] == "8"

    def test_dispatch_compile_after_warmup_is_flagged_and_emitted(self):
        events = EventLog()
        monitor = make_monitor(events)
        ledger = CompileLedger()
        ledger.bind(labels=LABELS, monitor=monitor)
        ledger.mark_warmup_complete()
        before = REGISTRY.get_sample_value(
            "scorer_xla_recompiles_unexpected_total", LABELS) or 0.0
        event = ledger.record_compile(1.25, bucket=64, backend="cpu",
                                      where="dispatch", expected=False)
        assert event["unexpected"] is True and event["phase"] == "runtime"
        after = REGISTRY.get_sample_value(
            "scorer_xla_recompiles_unexpected_total", LABELS)
        assert after == before + 1
        ring = events.snapshot()["events"]
        recompiles = [e for e in ring if e.get("kind") == "unexpected_recompile"]
        assert recompiles and recompiles[-1]["bucket"] == "64"
        # the bound monitor's storm check degrades while the event is recent
        status, detail = RecompileStormCheck(ledger, monitor).evaluate(0.0)
        assert status == "degraded" and "unexpected XLA recompile" in detail

    def test_external_compiles_are_recorded_but_not_flagged(self):
        """A compile with no ledger context (another library jitting in the
        same process) lands in the ring as 'external' and can never trip
        the storm detector — no co-tenant false alarms."""
        ledger = CompileLedger()
        ledger.bind(labels=LABELS)
        ledger.mark_warmup_complete()
        event = ledger.record_compile(0.2)
        assert event["where"] == "external"
        assert event["unexpected"] is False
        assert ledger.unexpected_in_window() == 0

    def test_expected_flag_is_inherited_through_nested_contexts(self):
        """The sharded scorer's inner context must not launder the dispatch
        path's expected=False back to the default."""
        ledger = CompileLedger()
        ledger.bind(labels=LABELS)
        ledger.mark_warmup_complete()
        with ledger.context(bucket=32, where="dispatch", expected=False):
            with ledger.context(bucket=64, backend="mesh", where="sharded"):
                event = ledger.record_compile(0.1)
        assert event["unexpected"] is True
        assert event["bucket"] == "64" and event["where"] == "sharded"
        # and an expected outer context stays expected through nesting
        with ledger.context(where="fit", expected=True):
            with ledger.context(bucket=16, where="sharded"):
                event = ledger.record_compile(0.1)
        assert event["unexpected"] is False

    def test_ring_and_span_log_are_bounded(self):
        ledger = CompileLedger(max_events=4, max_spans=3)
        ledger.bind(labels=LABELS)
        for i in range(10):
            ledger.record_compile(0.01, bucket=i, backend="cpu",
                                  where="warmup")
            ledger.record_span(8, 5, "device", 0.0, 0.01)
        snap = ledger.snapshot()
        assert len(snap["compiles"]) == 4
        assert len(snap["batches"]) == 3
        assert snap["totals"]["compiles"] == 10  # totals keep counting
        assert snap["compiles"][-1]["bucket"] == "9"

    def test_storm_check_passes_for_a_no_longer_bound_monitor(self):
        """Tests/processes build several Services; a storm can only be
        blamed on the service the ledger is currently bound to."""
        ledger = CompileLedger()
        old_monitor = make_monitor()
        ledger.bind(labels=LABELS, monitor=old_monitor)
        old_check = RecompileStormCheck(ledger, old_monitor)
        ledger.mark_warmup_complete()
        ledger.record_compile(1.0, bucket=8, where="dispatch", expected=False)
        assert old_check.evaluate(0.0)[0] == "degraded"
        new_monitor = make_monitor()
        ledger.bind(monitor=new_monitor)
        assert old_check.evaluate(0.0)[0] == "pass"
        # re-binding clears the storm window: a storm that predates the new
        # service's binding is not blamed on it (the ring keeps the history)
        new_check = RecompileStormCheck(ledger, new_monitor)
        assert new_check.evaluate(0.0)[0] == "pass"
        ledger.record_compile(1.0, bucket=8, where="dispatch", expected=False)
        assert new_check.evaluate(0.0)[0] == "degraded"

    def test_emit_events_off_still_counts_but_stays_silent(self):
        events = EventLog()
        monitor = make_monitor(events)
        ledger = CompileLedger()
        ledger.bind(labels=LABELS, monitor=monitor, emit_events=False)
        ledger.mark_warmup_complete()
        event = ledger.record_compile(0.3, bucket=8, where="dispatch",
                                      expected=False)
        assert event["unexpected"] is True
        assert not [e for e in events.snapshot()["events"]
                    if e.get("kind") == "unexpected_recompile"]


# ---------------------------------------------------------------------------
# the jax.monitoring listener with REAL compiles (CPU)
# ---------------------------------------------------------------------------
class TestListenerWithRealCompiles:
    def test_real_jit_compiles_attribute_through_contexts(self):
        import jax
        import jax.numpy as jnp

        ledger = CompileLedger()
        ledger.bind(labels=LABELS)
        assert device_obs.install_listener()
        previous = device_obs.activate(ledger)
        try:
            fn = jax.jit(lambda x: x * 3 + 1)
            with ledger.context(bucket=8, backend="cpu", where="warmup",
                                expected=True):
                fn(jnp.ones((8, 4))).block_until_ready()
            snap = ledger.snapshot()
            assert snap["totals"]["compiles"] >= 1
            assert any(e["bucket"] == "8" and e["where"] == "warmup"
                       and e["seconds"] > 0 for e in snap["compiles"])
            ledger.mark_warmup_complete()
            with ledger.context(bucket=16, backend="cpu", where="dispatch",
                                expected=False):
                fn(jnp.ones((16, 4))).block_until_ready()  # new shape: compiles
            snap = ledger.snapshot()
            flagged = [e for e in snap["compiles"] if e["unexpected"]]
            assert flagged and flagged[-1]["bucket"] == "16"
            assert ledger.unexpected_in_window() >= 1
        finally:
            device_obs.activate(previous)


# ---------------------------------------------------------------------------
# HBM gauges
# ---------------------------------------------------------------------------
class TestHbmGauges:
    def test_cpu_backend_exports_nothing(self):
        """CPU devices return memory_stats() None — the guarded path — so no
        device_hbm_bytes child may appear."""
        labels = {"component_type": "hbm_cpu", "component_id": "none"}
        assert device_obs.export_hbm_gauges(labels) == 0
        assert REGISTRY.get_sample_value(
            "device_hbm_bytes",
            dict(labels, device="TFRT_CPU_0", kind="in_use")) is None

    def test_stats_backed_device_exports_scrape_time_gauges(self, monkeypatch):
        import jax

        stats = {"bytes_in_use": 1024, "bytes_limit": 4096}

        class FakeDevice:
            def memory_stats(self):
                return dict(stats)

            def __str__(self):
                return "FAKE_TPU_0"

        monkeypatch.setattr(jax, "local_devices", lambda: [FakeDevice()])
        labels = {"component_type": "hbm_fake", "component_id": "fake-1"}
        assert device_obs.export_hbm_gauges(labels) == 1
        in_use = REGISTRY.get_sample_value(
            "device_hbm_bytes", dict(labels, device="FAKE_TPU_0", kind="in_use"))
        limit = REGISTRY.get_sample_value(
            "device_hbm_bytes", dict(labels, device="FAKE_TPU_0", kind="limit"))
        assert (in_use, limit) == (1024.0, 4096.0)
        stats["bytes_in_use"] = 2048  # refreshed at scrape time, not export time
        assert REGISTRY.get_sample_value(
            "device_hbm_bytes",
            dict(labels, device="FAKE_TPU_0", kind="in_use")) == 2048.0


# ---------------------------------------------------------------------------
# batch telemetry math (no jax needed)
# ---------------------------------------------------------------------------
class TestBatchTelemetry:
    def _detector(self):
        from detectmateservice_tpu.library.detectors import JaxScorerDetector

        return JaxScorerDetector(config={"detectors": {"JaxScorerDetector": {
            "method_type": "jax_scorer", "auto_config": False,
            "vocab_size": 256, "seq_len": 8, "dim": 8}}})

    def test_occupancy_math_on_ragged_batches(self):
        from detectmateservice_tpu.library.detectors.jax_scorer import (
            _InflightSlot,
        )

        det = self._detector()
        labels = dict(det._obs_labels(), path="device")

        def sample(name):
            return REGISTRY.get_sample_value(name, labels) or 0.0

        occ_sum0, occ_cnt0 = (sample("detector_batch_occupancy_sum"),
                              sample("detector_batch_occupancy_count"))
        for real, bucket in ((5, 8), (8, 8), (1, 16)):
            slot = _InflightSlot([], real, bucket=bucket, path="device")
            slot.t_start = slot.t_enqueue + 0.25
            det._observe_batch(slot, device_s=0.5)
        assert sample("detector_batch_occupancy_count") == occ_cnt0 + 3
        assert sample("detector_batch_occupancy_sum") == pytest.approx(
            occ_sum0 + 5 / 8 + 1.0 + 1 / 16)
        # queue wait observed the enqueue→start gap
        assert (REGISTRY.get_sample_value(
            "detector_queue_wait_seconds_sum", labels) or 0.0) >= 0.75 - 1e-6
        # bucket selection counted per (bucket, path)
        assert REGISTRY.get_sample_value(
            "detector_bucket_selected_total",
            dict(det._obs_labels(), bucket="8", path="device")) >= 2

    def test_span_records_trace_link_fields(self):
        ledger = CompileLedger()
        ledger.record_span(16, 9, "device", 0.001, 0.02, trace_id="abcd" * 4)
        span = ledger.snapshot()["batches"][-1]
        assert span["occupancy"] == pytest.approx(9 / 16)
        assert span["trace_id"] == "abcd" * 4
        assert span["path"] == "device"


# ---------------------------------------------------------------------------
# spans, the ring's stamps and the idle account (no jax needed)
# ---------------------------------------------------------------------------
class TestSpanRing:
    def test_ring_keeps_4096_spans_with_offsets_in_order(self):
        ledger = CompileLedger()
        for i in range(device_obs.MAX_SPANS + 10):
            t = 100.0 + i
            seq = ledger.next_batch_seq()
            entry = ledger.record_span(
                8, 5, "device", 0.0, 0.01, release="full", seq=seq,
                stamps={"oldest_arrival": t - 0.4, "release": t,
                        "pickup": t + 0.001, "call_issued": t + 0.003,
                        "readable": t + 0.6})
            ledger.note_sent(entry, 0.62)
        spans = ledger.snapshot()["batches"]
        assert len(spans) == device_obs.MAX_SPANS == 4096
        assert [s["seq"] for s in spans] == list(range(11, 4096 + 11))
        for span in (spans[0], spans[-1]):
            offsets = [span["offsets_s"][name]
                       for name in device_obs.SPAN_STAMPS]
            assert offsets == sorted(offsets)
            assert offsets == pytest.approx(
                [-0.4, 0.0, 0.001, 0.003, 0.6, 0.62])

    def test_a_span_without_stamps_still_files_under_its_own_seq(self):
        ledger = CompileLedger()
        ledger.record_span(8, 5, "host", 0.0, 0.01)
        held = ledger.next_batch_seq()           # allotted at release
        ledger.record_span(8, 8, "host", 0.0, 0.01)
        ledger.record_span(16, 9, "device", 0.0, 0.02, seq=held)
        spans = ledger.snapshot()["batches"]
        assert [s["seq"] for s in spans] == [1, 3, held]
        assert spans[0]["offsets_s"] == {}

    def test_limit_zero_serves_no_ring_entries(self):
        ledger = CompileLedger()
        ledger.record_span(8, 5, "device", 0.0, 0.01)
        snap = ledger.snapshot(limit=0)
        assert snap["batches"] == [] and snap["compiles"] == []
        assert len(ledger.snapshot(limit=1)["batches"]) == 1

    def test_span_is_a_shared_noop_where_nothing_is_armed(self, monkeypatch):
        monkeypatch.setattr(device_obs, "_ANNOTATION", None)
        monkeypatch.setattr(device_obs, "_PHASE_CHILDREN", {})
        assert device_obs.span("dm.send", results=3) is device_obs.NULL_SPAN
        with device_obs.span("dm.recv_wait"):
            pass

    def test_phase_spans_feed_their_counters(self, monkeypatch):
        labels = {"component_type": "test_obs", "component_id": "phase-1"}
        monkeypatch.setattr(device_obs, "_PHASE_CHILDREN", {})
        device_obs.arm_spans(labels)

        def sample(name, phase):
            return REGISTRY.get_sample_value(
                name, dict(labels, phase=phase))

        for phase in device_obs.PHASE_SPANS.values():
            assert sample("detector_phase_total", phase) is not None
        count0 = sample("detector_phase_total", "upload")
        seconds0 = sample("detector_phase_seconds_total", "upload")
        with device_obs.span("dm.upload", batch=1, bucket=8, rows=5,
                             release="full"):
            pass
        with device_obs.span("dm.call", batch=1, bucket=8, rows=5,
                             release="full"):
            pass                                 # annotation only
        assert sample("detector_phase_total", "upload") == count0 + 1
        assert sample("detector_phase_seconds_total", "upload") >= seconds0
        assert set(device_obs.PHASE_SPANS.values()) == {
            "upload", "readback", "alert_build"}


class TestDeviceIdleClock:
    """The three causes sum to the host-known idle time — last batch seen
    readable to next call issued — and never exceed wall time; on a fake
    clock."""

    def test_fill_then_host_then_issue(self):
        clock = device_obs.DeviceIdleClock()
        clock.idle_from(100.0)                   # batch k seen readable
        clock.advance(100.2, None)               # nothing held: no_rows
        clock.advance(100.5, release_at=101.0)   # rows held, due at 101.0
        clock.advance(101.3, release_at=101.0)   # the pump came 0.3 late
        assert clock.busy_from(101.3, release_at=101.0) is True
        clock.issued(0.004)                      # release → call issued
        assert clock.seconds == pytest.approx(
            {"no_rows": 0.2, "fill": 0.3 + 0.5, "host": 0.3 + 0.004})
        assert sum(clock.seconds.values()) == pytest.approx(1.304)
        assert not clock.idle

    def test_busy_device_accrues_nothing(self):
        clock = device_obs.DeviceIdleClock()
        clock.advance(5.0, None)
        assert clock.busy_from(6.0, None) is False
        assert sum(clock.seconds.values()) == 0.0

    def test_target_reached_counts_as_host_from_the_mark(self):
        clock = device_obs.DeviceIdleClock()
        clock.idle_from(10.0)
        clock.advance(10.5, release_at=float("-inf"))
        assert clock.seconds == {"fill": 0.0, "no_rows": 0.0, "host": 0.5}

    def test_random_walk_sums_to_the_idle_time_and_stays_under_wall(self):
        import random

        rng = random.Random(7)
        clock = device_obs.DeviceIdleClock()
        now, idle_time, idle_since = 50.0, 0.0, None
        wall0 = now
        for _ in range(2000):
            now += rng.random() * 0.01
            release_at = rng.choice(
                [None, float("-inf"), now - 0.005, now + 0.02])
            roll = rng.random()
            if clock.idle and roll < 0.15:
                assert clock.busy_from(now, release_at)
                issue = rng.random() * 0.002
                clock.issued(issue)
                now += issue
                idle_time += now - idle_since
                idle_since = None
            elif not clock.idle and roll < 0.3:
                clock.idle_from(now)
                idle_since = now
            else:
                clock.advance(now, release_at)
        if idle_since is not None:
            clock.advance(now, None)
            idle_time += now - idle_since
        assert sum(clock.seconds.values()) == pytest.approx(idle_time)
        assert sum(clock.seconds.values()) <= now - wall0
        assert all(v >= 0.0 for v in clock.seconds.values())

    def test_children_tick_with_the_account(self):
        class Child:
            def __init__(self):
                self.total = 0.0

            def inc(self, amount):
                self.total += amount

        children = {c: Child() for c in device_obs.DeviceIdleClock.CAUSES}
        clock = device_obs.DeviceIdleClock(children)
        clock.idle_from(1.0)
        clock.advance(2.0, None)
        clock.busy_from(2.5, release_at=2.25)
        assert {c: ch.total for c, ch in children.items()} == clock.seconds
        assert clock.seconds == {"no_rows": 1.0, "fill": 0.25, "host": 0.25}


# ---------------------------------------------------------------------------
# the spans on a real scorer (CPU): counters from boot, one batch's events
# ---------------------------------------------------------------------------
def _parser_msg(i: int) -> bytes:
    from detectmateservice_tpu.schemas import ParserSchema

    return ParserSchema(
        EventID=1, template="user <*> logged in from <*>",
        variables=[f"u{i % 8}", f"10.0.0.{i % 16}"], logID=str(i),
        logFormatVariables={"Time": "1700000000"}).serialize()


def _span_detector(name: str, **overrides):
    from detectmateservice_tpu.library.detectors import JaxScorerDetector

    base = {
        "method_type": "jax_scorer", "auto_config": False, "model": "mlp",
        "data_use_training": 32, "train_epochs": 1, "min_train_steps": 5,
        "seq_len": 16, "dim": 32, "max_batch": 32, "pipeline_depth": 2,
        "async_fit": False, "host_score_max_batch": 0,
        "batch_deadline_ms": 10_000.0, "batch_target_occupancy": 0.9,
        "score_threshold": -1e9,
    }
    base.update(overrides)
    return JaxScorerDetector(name=name,
                             config={"detectors": {name: base}})


class TestBoundaryCountersFromBoot:
    def test_every_child_reads_zero_before_the_first_batch(self):
        """``benchmark/lib/layers.py`` drops a metric whose series is absent
        from the scrape: every phase / cause / reason child must be exported
        as 0 from scorer set-up on."""
        from prometheus_client import generate_latest

        det = _span_detector("zero-from-boot")
        det.setup_io()
        text = generate_latest(REGISTRY).decode()
        ident = 'component_id="zero-from-boot",component_type="jax_scorer"'

        def line(series, extra=""):
            wanted = "{" + ",".join(sorted(
                (ident + ("," + extra if extra else "")).split(","))) + "}"
            return f"{series}{wanted} 0.0"

        for phase in ("upload", "readback", "alert_build"):
            assert line("detector_phase_seconds_total",
                        f'phase="{phase}"') in text
            assert line("detector_phase_total", f'phase="{phase}"') in text
        for cause in ("fill", "no_rows", "host"):
            assert line("detector_device_idle_seconds_total",
                        f'cause="{cause}"') in text
        for reason in ("full", "deadline", "flush"):
            assert line("detector_rows_released_total",
                        f'reason="{reason}"') in text
        assert line("detector_row_hold_seconds_total") in text
        device = f'device="{det._exec.label}"'
        assert line("detector_device_lines_total", device) in text
        assert line("detector_device_batches_total", device) in text
        det.flush_final()


class TestBatchSpansInACapture:
    """A ``ProfileManager`` capture (Python tracer off) over a few batches
    holds the ``dm.*`` host events, and those of one device batch share its
    ``batch`` id — the one its ring entry is filed under."""

    def test_one_batch_carries_one_id_through_every_span(self, tmp_path):
        import glob

        import jax

        from detectmateservice_tpu.utils.profiling import ProfileManager

        det = _span_detector("span-capture")
        det.setup_io()
        assert det.process_batch([_parser_msg(i) for i in range(32)]) == []
        det.flush_final()                        # fitted
        manager = ProfileManager()
        manager.start(str(tmp_path / "profiles"), 1.0)
        outs, deadline = [], time.monotonic() + 0.7
        k = 1000
        while time.monotonic() < deadline:
            outs.extend(det.process_batch(
                [_parser_msg(k + j) for j in range(32)]))   # full release
            k += 32
            time.sleep(0.02)
        outs.extend(det.flush())
        assert manager.wait(60)
        det.flush_final()
        assert manager.status()["last"]["state"] == "done"
        assert outs

        (path,) = glob.glob(str(tmp_path / "profiles" / "**" / "*.xplane.pb"),
                            recursive=True)
        events = {}                              # name -> {batch: stats}
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            for line in plane.lines:
                for event in line.events:
                    if event.name.startswith("dm."):
                        stats = {k: v for k, v in event.stats}
                        events.setdefault(event.name, {})[
                            stats.get("batch")] = stats
        # the python tracer is off and the annotations still land
        for name in ("dm.featurize", "dm.release", "dm.upload", "dm.call",
                     "dm.readback", "dm.alert_build"):
            assert name in events, sorted(events)
        shared = (set(events["dm.upload"]) & set(events["dm.call"])
                  & set(events["dm.readback"])
                  & set(events["dm.alert_build"])
                  & set(events["dm.release"]))
        assert shared, "no batch carries every span"
        batch = sorted(shared)[0]
        stats = events["dm.call"][batch]
        assert stats["bucket"] == 32 and stats["rows"] == 32
        assert stats["release"] == "full"
        ring = {s["seq"]: s for s in
                device_obs.get_ledger().snapshot()["batches"]}
        assert ring[batch]["real"] == 32 and ring[batch]["release"] == "full"
        offsets = ring[batch]["offsets_s"]
        assert (offsets["oldest_arrival"] <= offsets["release"] == 0.0
                <= offsets["pickup"] <= offsets["call_issued"]
                <= offsets["readable"] <= offsets["sent"])


class TestDeviceScopes:
    def test_logbert_program_names_its_layers_and_head(self):
        """``jax.named_scope`` is metadata: the scopes are in the lowered
        program's debug info and nowhere in the text the persistent cache's
        key is made from."""
        import jax
        import jax.numpy as jnp

        from detectmateservice_tpu.models.logbert import (
            LogBERTConfig,
            LogBERTScorer,
        )

        # chunked head (the scan body) at a small size
        scorer = LogBERTScorer(LogBERTConfig(vocab_size=512, dim=32, depth=2,
                                             heads=2, seq_len=16))
        scorer._CHUNK_ELEMENT_BUDGET = 64 * 8 * 512
        params, _ = scorer.init(jax.random.PRNGKey(0))
        lowered = jax.jit(scorer._score_impl).lower(
            params, jnp.zeros((64, 16), jnp.uint16))
        named = lowered.as_text(debug_info=True)
        for scope in ("embed", "layer0/attn", "layer0/ffn", "layer1/attn",
                      "head/nll", "head/nll/while"):
            assert scope in named, scope
        plain = lowered.as_text()
        assert "head/nll" not in plain and "layer0" not in plain


# ---------------------------------------------------------------------------
# the acceptance path: a real scorer service on CPU, end to end
# ---------------------------------------------------------------------------
class TestScorerServiceEndToEnd:
    @pytest.fixture()
    def service(self, run_service, inproc_factory):
        svc = Service(
            ServiceSettings(component_type="core", component_name="devobs",
                            engine_addr="inproc://devobs", http_port=0,
                            log_to_file=False, log_to_console=False,
                            watchdog_enabled=False),
            socket_factory=inproc_factory)
        return run_service(svc)

    def test_warmup_then_injected_recompile_end_to_end(self, service):
        """warm-up → injected recompile → RecompileStorm-eligible health
        event → /admin/xla ledger entry, all in-process on CPU."""
        from detectmateservice_tpu.library.detectors import JaxScorerDetector

        # the ledger is process-wide: clear residue from earlier tests in
        # this pytest session so the ring/warm state below is THIS test's
        device_obs.get_ledger().reset()
        det = JaxScorerDetector(config={"detectors": {"JaxScorerDetector": {
            "method_type": "jax_scorer", "auto_config": False,
            "model": "mlp", "vocab_size": 256, "seq_len": 8, "dim": 8,
            "data_use_training": 8, "train_batch_size": 8, "max_batch": 16,
            "host_score_max_batch": 0,  # all dispatches ride the device path
        }}})
        det.health_monitor = service.health
        det.setup_io()
        ledger = device_obs.get_ledger()
        assert ledger.warmup_complete
        snap = ledger.snapshot()
        assert snap["totals"]["compiles"] >= 2  # warm set compiled for real
        assert all(not e["unexpected"] for e in snap["compiles"])

        # cold-bucket dispatch: bucket 4 is NOT in the warm set {1, 8, 16}.
        # Since the replica-tier round this is PLANNED warm-set growth —
        # the dispatch path pre-warms the bucket under an expected
        # bucket_warm context (a real XLA compile, but never a page): a
        # tier splitting traffic must not recompile-page every replica
        # whose natural batch size the setup warm-up didn't see.
        unexpected_before = snap["totals"]["unexpected"]
        tokens = np.zeros((3, 8), np.int32)
        det._dispatch(tokens, [b"a", b"b", b"c"])
        det.flush()

        snap = ledger.snapshot()
        assert snap["totals"]["unexpected"] == unexpected_before
        warm_growth = [e for e in snap["compiles"]
                       if e["bucket"] == "4" and e["where"] == "bucket_warm"]
        assert warm_growth and not warm_growth[-1]["unexpected"]

        # a TRUE unexpected recompile — a compile of a bucket the scorer
        # believes warm (cache invalidation, the storm class) — drives the
        # event/health/alert plumbing end to end via the ledger's
        # injection seam (the same seam scripts/soak.py's `recompile`
        # scenario uses)
        ledger.record_compile(0.2, bucket=4, backend="cpu",
                              where="dispatch", expected=False)
        snap = ledger.snapshot()
        assert snap["totals"]["unexpected"] == unexpected_before + 1
        flagged = [e for e in snap["compiles"] if e["unexpected"]]
        assert flagged and flagged[-1]["bucket"] == "4"
        assert flagged[-1]["where"] in ("dispatch", "sharded")

        port = service.web_server.port
        # 1. the ledger entry on GET /admin/xla
        code, body = http_json(port, "/admin/xla")
        assert code == 200 and body["warmup_complete"] is True
        assert [e for e in body["compiles"] if e["unexpected"]]
        assert body["batches"], "device-batch spans must be recorded"
        span = body["batches"][-1]
        assert span["bucket"] == 4 and span["real"] == 3
        assert span["occupancy"] == pytest.approx(0.75)

        # 2. the structured health event on GET /admin/events
        code, events = http_json(port, "/admin/events")
        assert code == 200
        recompiles = [e for e in events["events"]
                      if e.get("kind") == "unexpected_recompile"]
        assert recompiles and recompiles[-1]["bucket"] == "4"

        # 3. the RecompileStorm-eligible state on deep health
        code, health = http_json(port, "/admin/health?deep=1")
        assert code == 503 and health["state"] == "degraded"
        failing = {c["name"]: c["status"] for c in health["checks"]
                   if c["status"] != "pass"}
        assert failing == {"xla_recompile_storm": "degraded"}

        # 4. the batch telemetry moved for the device path
        labels = dict(det._obs_labels(), path="device")
        assert REGISTRY.get_sample_value(
            "detector_batch_occupancy_count", labels) >= 1


# ---------------------------------------------------------------------------
# on-demand profiler capture via the admin plane
# ---------------------------------------------------------------------------
class TestProfileAdmin:
    @pytest.fixture()
    def service(self, run_service, inproc_factory, tmp_path):
        svc = Service(
            ServiceSettings(component_type="core", component_name="prof",
                            engine_addr="inproc://prof", http_port=0,
                            log_to_file=False, log_to_console=False,
                            watchdog_enabled=False,
                            profile_dir=str(tmp_path / "profiles"),
                            profile_max_captures=2),
            socket_factory=inproc_factory)
        return run_service(svc)

    def test_capture_happy_path_second_rejected_and_bounded(self, service,
                                                            tmp_path):
        from detectmateservice_tpu.utils.profiling import PROFILER

        port = service.web_server.port
        code, body = http_raw(port, "/admin/profile/latest")
        assert code == 404  # nothing captured yet

        code, body = http_json(port, "/admin/profile?seconds=0.2",
                               method="POST")
        assert code == 200 and body["detail"] == "capture started"
        # concurrency guard: one capture per process
        code2, body2 = http_json(port, "/admin/profile?seconds=0.2",
                                 method="POST")
        assert code2 == 409 and "already running" in body2["detail"]
        assert PROFILER.wait(30)

        code, status = http_json(port, "/admin/profile")
        assert code == 200 and status["running"] is False
        assert status["last"]["state"] == "done"

        code, data = http_raw(port, "/admin/profile/latest")
        assert code == 200
        archive = zipfile.ZipFile(io.BytesIO(data))
        assert archive.namelist(), "capture artifact must not be empty"

        # artifact bound: profile_max_captures=2 keeps only the newest two
        for _ in range(2):
            code, _body = http_json(port, "/admin/profile?seconds=0.1",
                                    method="POST")
            assert code == 200
            assert PROFILER.wait(30)
        capture_dirs = sorted(
            p.name for p in (tmp_path / "profiles").iterdir()
            if p.name.startswith("capture-"))
        assert capture_dirs == ["capture-0002", "capture-0003"]

    def test_invalid_seconds_is_a_client_error(self, service):
        port = service.web_server.port
        code, body = http_json(port, "/admin/profile?seconds=0", method="POST")
        assert code == 400 and "seconds" in body["detail"]
        code, body = http_json(port, "/admin/profile?seconds=bogus",
                               method="POST")
        assert code == 400

    def test_client_profile_subcommand_downloads_artifact(self, service,
                                                          tmp_path):
        from detectmateservice_tpu.client import main as client_main

        out = tmp_path / "artifact.zip"
        rc = client_main([
            "--url", f"http://127.0.0.1:{service.web_server.port}",
            "profile", "--seconds", "0.2", "--wait", "-o", str(out)])
        assert rc == 0
        assert zipfile.ZipFile(out).namelist()

    def test_client_xla_subcommand(self, service, capsys):
        from detectmateservice_tpu.client import main as client_main

        rc = client_main([
            "--url", f"http://127.0.0.1:{service.web_server.port}",
            "xla", "--limit", "5"])
        assert rc == 0
        body = json.loads(capsys.readouterr().out)
        assert {"warmup_complete", "totals", "compiles", "batches"} <= set(body)
