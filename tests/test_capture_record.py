"""The capture accounts for itself (utils/profiling.py ProfileManager with
engine/device_obs.py's span helper and idle clock): what a
``POST /admin/profile`` capture cost the process that took it is written
into ``capture.json``, served under ``GET /admin/profile`` and exported as
``profile_capture_*`` gauges — armed when a capture starts, gone when it
ends. Captures here are real and short; nothing asserts a duration.
"""
import glob
import json
import threading
import time
import urllib.request

import pytest
from prometheus_client import REGISTRY, generate_latest

from detectmateservice_tpu.core import Service
from detectmateservice_tpu.engine import device_obs
from detectmateservice_tpu.engine.device_obs import DeviceIdleClock
from detectmateservice_tpu.settings import ServiceSettings
from detectmateservice_tpu.utils.profiling import (
    CAPTURE_MARK,
    PROFILER,
    ProfileManager,
    StallHeartbeat,
    idle_share,
)

RECORD_KEYS = {
    "state", "dir", "seq", "seconds", "started_ts", "finished_ts",
    "start_trace_s", "traced_s", "stop_trace_s", "xplane_bytes",
    "mark_mono_ns", "rusage", "stalls", "spans",
}
RUSAGE_KEYS = {"user_s", "system_s", "voluntary_switches",
               "involuntary_switches", "major_faults", "blocks_in",
               "blocks_out"}


def labels_of(name: str) -> dict:
    return {"component_type": "test_capture", "component_id": name}


def sample(series: str, labels: dict, **extra):
    return REGISTRY.get_sample_value(series, dict(labels, **extra))


def exported(labels: dict) -> list:
    """The ``profile_capture_*`` gauge lines /metrics holds for ``labels``
    (the counter ``profile_captures_total`` is not among them)."""
    ident = f'component_id="{labels["component_id"]}"'
    return [line for line in generate_latest(REGISTRY).decode().splitlines()
            if line.startswith("profile_capture_") and ident in line]


def heartbeats() -> list:
    return [t for t in threading.enumerate() if t.name == "ProfileHeartbeat"]


def capture(manager: ProfileManager, base, seconds=0.2, **kw) -> dict:
    manager.start(str(base), seconds, **kw)
    assert manager.wait(60)
    return manager.status()["last"]


# ---------------------------------------------------------------------------
# one real capture on the CPU: the record, the gauges, the marks, the thread
# ---------------------------------------------------------------------------
class TestTheRecordOfACapture:
    def test_every_key_is_written_and_gauges_appear_only_after_the_end(
            self, tmp_path, monkeypatch):
        labels = labels_of("record-1")
        # a stage with nothing else armed: the spans below are no-ops
        # before and after the capture, and timed during it
        monkeypatch.setattr(device_obs, "_ANNOTATION", None)
        monkeypatch.setattr(device_obs, "_PHASE_CHILDREN", {})
        clock = DeviceIdleClock()
        clock.idle_from(time.monotonic())
        manager = ProfileManager()
        manager.set_idle_reader(lambda now: clock.reading(now, None))
        assert exported(labels) == [] and heartbeats() == []
        assert device_obs.span("dm.send") is device_obs.NULL_SPAN

        manager.start(str(tmp_path), 0.3, labels=labels)
        while manager.status()["running"]:
            if device_obs._CAPTURE is not None:
                assert len(heartbeats()) == 1
                assert exported(labels) == []    # none while it runs
                with device_obs.span("dm.alert_build", batch=7, bucket=8,
                                     rows=2, release="full"):
                    pass
                with device_obs.span("dm.recv_wait"):
                    pass
            time.sleep(0.01)
        assert manager.wait(60)
        info = manager.status()["last"]
        assert info["state"] == "done", info
        assert RECORD_KEYS <= set(info)
        assert set(info["rusage"]) == RUSAGE_KEYS
        assert set(info["stalls"]) == {"count", "sum_s", "max_s", "longest"}
        assert set(info["mark_mono_ns"]) == {"start", "stop"}
        assert info["xplane_bytes"] > 0
        # the fill is kept apart from the spans' maxima
        assert set(info["spans"]) == {"dm.alert_build"}
        assert info["spans"]["dm.alert_build"]["batch"] == 7
        assert set(info["recv_wait"]) == {"seconds", "at_s"}
        # nothing but no_rows over the stretch: a stretch still open at a
        # mark is counted up to the mark
        assert set(info["idle_share"]) == set(DeviceIdleClock.CAUSES)
        assert info["idle_share"]["no_rows"] == pytest.approx(100.0, abs=0.5)
        with open(f"{info['dir']}/capture.json", encoding="utf-8") as fh:
            assert json.load(fh) == info

        # the gauges hold the record's numbers; the thread is gone again,
        # and so is the timing of spans
        for phase, key in (("start", "start_trace_s"), ("traced", "traced_s"),
                           ("stop", "stop_trace_s")):
            assert sample("profile_capture_seconds", labels,
                          phase=phase) == info[key]
        for stat in ("max", "sum"):
            assert sample("profile_capture_stall_seconds", labels,
                          stat=stat) == info["stalls"][f"{stat}_s"]
        assert sample("profile_capture_span_max_seconds", labels,
                      span="dm.alert_build") == \
            info["spans"]["dm.alert_build"]["seconds"]
        assert sample("profile_capture_span_max_seconds", labels,
                      span="dm.recv_wait") is None
        for cause in DeviceIdleClock.CAUSES:
            assert sample("profile_capture_idle_share", labels,
                          cause=cause) == info["idle_share"][cause]
        assert sample("profile_captures_total", labels, state="done") == 1
        assert heartbeats() == []
        assert device_obs._CAPTURE is None
        assert device_obs.span("dm.send") is device_obs.NULL_SPAN

    def test_a_stage_without_a_scorer_records_no_idle_share(self, tmp_path):
        labels = labels_of("record-2")
        info = capture(ProfileManager(), tmp_path, labels=labels)
        assert info["state"] == "done" and "idle_share" not in info
        assert RECORD_KEYS <= set(info)
        assert sample("profile_capture_idle_share", labels,
                      cause="host") is None
        assert sample("profile_capture_seconds", labels,
                      phase="traced") == info["traced_s"]

    def test_both_marks_are_on_the_host_plane_with_their_mono_ns(
            self, tmp_path):
        import jax

        info = capture(ProfileManager(), tmp_path)
        (path,) = glob.glob(f"{info['dir']}/**/*.xplane.pb", recursive=True)
        marks = {}
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            for line in plane.lines:
                for event in line.events:
                    if event.name == CAPTURE_MARK:
                        stats = dict(event.stats)
                        marks[stats["edge"]] = (plane.name, event.start_ns,
                                                stats["mono_ns"])
        assert set(marks) == {"start", "stop"}
        assert all(plane.startswith("/host:") for plane, _, _ in
                   marks.values())
        assert {edge: mono for edge, (_, _, mono) in marks.items()} == \
            info["mark_mono_ns"]
        # one offset lays both clocks on each other: the two marks lie as
        # far apart on the capture's clock as on the program's
        on_capture = marks["stop"][1] - marks["start"][1]
        on_program = marks["stop"][2] - marks["start"][2]
        assert on_program > 0
        assert abs(on_capture - on_program) < 0.05 * on_program

    def test_the_next_capture_replaces_the_gauges(self, tmp_path,
                                                  monkeypatch):
        labels = labels_of("record-3")
        monkeypatch.setattr(device_obs, "_ANNOTATION", None)
        manager = ProfileManager()
        manager.start(str(tmp_path), 0.2, labels=labels)
        while manager.status()["running"]:
            if device_obs._CAPTURE is not None:
                with device_obs.span("dm.send", results=1):
                    pass
            time.sleep(0.01)
        assert manager.wait(60)
        assert sample("profile_capture_span_max_seconds", labels,
                      span="dm.send") is not None
        capture(manager, tmp_path, labels=labels)   # closes no span
        assert sample("profile_capture_span_max_seconds", labels,
                      span="dm.send") is None
        assert sample("profile_captures_total", labels, state="done") == 2


# ---------------------------------------------------------------------------
# a capture that fails says so: in the file, the status and the counter
# ---------------------------------------------------------------------------
def _start_trace_raises(monkeypatch):
    import jax

    def boom(*args, **kwargs):
        raise RuntimeError("no profiler today")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    return "no profiler today"


def _stop_trace_leaves_no_file(monkeypatch):
    import jax

    monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **kw: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    return "left no *.xplane.pb"


def _service(run_service, inproc_factory, tmp_path, name: str) -> Service:
    return run_service(Service(
        ServiceSettings(component_type="core", component_name=name,
                        engine_addr=f"inproc://{name}", http_port=0,
                        log_to_file=False, log_to_console=False,
                        watchdog_enabled=False,
                        profile_dir=str(tmp_path / "profiles")),
        socket_factory=inproc_factory))


class TestACaptureThatFails:
    @pytest.mark.parametrize("fault", [_start_trace_raises,
                                       _stop_trace_leaves_no_file])
    def test_state_error_counter_one_and_no_gauge(self, tmp_path,
                                                  monkeypatch, fault):
        labels = labels_of(f"error-{fault.__name__}")
        manager = ProfileManager()
        sound = capture(manager, tmp_path, labels=labels)
        assert sound["state"] == "done" and exported(labels)
        reason = fault(monkeypatch)
        info = capture(manager, tmp_path, labels=labels)
        assert info["state"] == "error" and reason in info["error"]
        with open(f"{info['dir']}/capture.json", encoding="utf-8") as fh:
            assert json.load(fh)["state"] == "error"
        assert sample("profile_captures_total", labels, state="error") == 1
        assert sample("profile_captures_total", labels, state="done") == 1
        # the sound capture's gauges went with the failed one's end
        assert exported(labels) == []
        assert sample("profile_capture_seconds", labels,
                      phase="traced") is None
        # what the capture cost is on record all the same
        assert set(info["rusage"]) == RUSAGE_KEYS and "stalls" in info
        assert heartbeats() == [] and device_obs._CAPTURE is None

    def test_the_admin_plane_shows_the_failure(self, run_service,
                                               inproc_factory, tmp_path,
                                               monkeypatch):
        svc = _service(run_service, inproc_factory, tmp_path, "capfail")
        port = svc.web_server.port
        reason = _start_trace_raises(monkeypatch)

        def get(path, method="GET"):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}{path}", method=method,
                data=b"" if method == "POST" else None)
            with urllib.request.urlopen(req, timeout=10) as resp:
                return resp.read().decode()

        counted = 'profile_captures_total{component_id="%s",' \
            'component_type="core",state="error"}' % svc.settings.component_id
        before = [line for line in get("/metrics").splitlines()
                  if line.startswith(counted)]
        get("/admin/profile?seconds=0.2", method="POST")
        assert PROFILER.wait(60)
        last = json.loads(get("/admin/profile"))["last"]
        assert last["state"] == "error" and reason in last["error"]
        after = [line for line in get("/metrics").splitlines()
                 if line.startswith(counted)]
        assert len(after) == 1 and after != before
        assert not [line for line in get("/metrics").splitlines()
                    if line.startswith("profile_capture_seconds{")
                    and svc.settings.component_id in line]

    def test_the_legacy_body_is_gone(self, run_service, inproc_factory,
                                     tmp_path):
        """``{"duration_ms": M}`` names no length any more: the capture
        takes the default second, not M milliseconds."""
        svc = _service(run_service, inproc_factory, tmp_path, "capbody")
        req = urllib.request.Request(
            f"http://127.0.0.1:{svc.web_server.port}/admin/profile",
            method="POST", data=json.dumps({"duration_ms": 50}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=10) as resp:
            assert json.loads(resp.read())["seconds"] == 1.0
        assert PROFILER.wait(60)


# ---------------------------------------------------------------------------
# the heartbeat on a scripted clock
# ---------------------------------------------------------------------------
class ScriptedClock:
    """``sleep`` moves the clock by the next scripted stretch, whatever was
    asked for, and ends the heartbeat with the script."""

    def __init__(self, stretches):
        self.now = 100.0
        self._stretches = list(stretches)
        self.heartbeat = None

    def clock(self) -> float:
        return self.now

    def sleep(self, _seconds: float) -> None:
        self.now += self._stretches.pop(0)
        if not self._stretches:
            self.heartbeat.stop()


class TestStallHeartbeat:
    def test_a_late_wake_is_written_down_with_offset_and_lateness(self):
        script = ScriptedClock([0.005, 0.006, 0.405, 0.005, 0.024, 0.105,
                                0.005])
        heartbeat = script.heartbeat = StallHeartbeat(script.clock,
                                                      script.sleep)
        heartbeat.run()                  # on this thread, to the script's end
        summary = heartbeat.summary(origin=100.0)
        # 24 ms asleep is 19 ms late: under the 20 ms that count
        assert summary["count"] == 2
        assert summary["max_s"] == pytest.approx(0.400)
        assert summary["sum_s"] == pytest.approx(0.500)
        first, second = summary["longest"]
        assert first == {"at_s": pytest.approx(0.016),
                         "late_s": pytest.approx(0.400)}
        assert second == {"at_s": pytest.approx(0.450),
                          "late_s": pytest.approx(0.100)}
        assert heartbeats() == []        # run() makes no thread

    def test_the_32_longest_are_kept_and_all_are_counted(self):
        late = [0.030 + 0.001 * i for i in range(40)]
        script = ScriptedClock([0.005 + s for s in late])
        heartbeat = script.heartbeat = StallHeartbeat(script.clock,
                                                      script.sleep)
        heartbeat.run()
        summary = heartbeat.summary(origin=100.0)
        assert summary["count"] == 40
        assert summary["sum_s"] == pytest.approx(sum(late))
        assert summary["max_s"] == pytest.approx(late[-1])
        kept = [entry["late_s"] for entry in summary["longest"]]
        assert len(kept) == StallHeartbeat.KEEP
        assert kept == pytest.approx(late[-StallHeartbeat.KEEP:])

    def test_start_and_stop_leave_no_thread(self):
        heartbeat = StallHeartbeat()
        heartbeat.start()
        assert len(heartbeats()) == 1
        heartbeat.stop()
        assert heartbeats() == []


# ---------------------------------------------------------------------------
# span(): the longest of each name while armed, the shared no-op after
# ---------------------------------------------------------------------------
class TestSpansWhileArmed:
    def test_longest_per_name_only_while_armed(self, monkeypatch):
        monkeypatch.setattr(device_obs, "_ANNOTATION", None)
        monkeypatch.setattr(device_obs, "_PHASE_CHILDREN", {})
        stamps = iter([10.0, 10.5,       # dm.send, 0.5 s
                       11.0, 13.0,       # dm.send, 2.0 s: the longest
                       14.0, 14.25,      # dm.send, 0.25 s
                       20.0, 20.125])    # dm.readback of batch 9
        monkeypatch.setattr(device_obs.time, "monotonic",
                            lambda: next(stamps))
        assert device_obs.span("dm.send") is device_obs.NULL_SPAN
        record = device_obs.CaptureSpans()
        device_obs.arm_capture(record)
        try:
            for _ in range(3):
                with device_obs.span("dm.send", results=4):
                    pass
            with device_obs.span("dm.readback", batch=9, bucket=8, rows=8,
                                 release="full"):
                pass
        finally:
            device_obs.arm_capture(None)
        assert record.longest == {"dm.send": (2.0, 11.0, None),
                                  "dm.readback": (0.125, 20.0, 9)}
        assert device_obs.span("dm.send") is device_obs.NULL_SPAN
        with device_obs.span("dm.send"):
            pass                         # takes no stamp: none are left
        assert record.longest["dm.send"] == (2.0, 11.0, None)

    def test_a_phase_span_still_feeds_its_counter_while_armed(
            self, monkeypatch):
        labels = labels_of("armed-phase")
        monkeypatch.setattr(device_obs, "_PHASE_CHILDREN", {})
        device_obs.arm_spans(labels)
        count0 = sample("detector_phase_total", labels, phase="readback")
        record = device_obs.CaptureSpans()
        device_obs.arm_capture(record)
        try:
            with device_obs.span("dm.readback", batch=1, bucket=8, rows=8,
                                 release="full"):
                pass
        finally:
            device_obs.arm_capture(None)
        assert sample("detector_phase_total", labels,
                      phase="readback") == count0 + 1
        assert record.longest["dm.readback"][2] == 1


# ---------------------------------------------------------------------------
# the idle share over a stretch, from two readings of a clock driven by hand
# ---------------------------------------------------------------------------
class TestIdleShareOverAStretch:
    def test_two_readings_give_the_stretch_by_cause(self):
        clock = DeviceIdleClock()
        clock.idle_from(10.0)
        clock.advance(11.0, release_at=None)           # 1 s, nothing held
        # the first mark at 12.0: rows held since 11.0, due at 12.5
        before = clock.reading(12.0, release_at=12.5)
        assert before == {"no_rows": 1.0, "fill": 1.0, "host": 0.0}
        assert clock.seconds == {"no_rows": 1.0, "fill": 0.0, "host": 0.0}
        clock.advance(13.0, release_at=12.5)           # fill 1.5, host 0.5
        clock.busy_from(13.0, release_at=12.5)         # busy from here
        clock.idle_from(15.0)
        # the second mark at 16.0, the stretch since 15.0 still open
        after = clock.reading(16.0, release_at=None)
        assert after == {"no_rows": 2.0, "fill": 1.5, "host": 0.5}
        # 4 s between the marks: 0.5 s of fill, 0.5 of host, 1 of no_rows
        assert idle_share(before, after, 4.0) == {
            "no_rows": 25.0, "fill": 12.5, "host": 12.5}

    def test_a_reading_changes_nothing_and_a_busy_device_adds_nothing(self):
        clock = DeviceIdleClock()
        assert clock.reading(5.0, None) == dict.fromkeys(clock.CAUSES, 0.0)
        clock.idle_from(1.0)
        assert clock.reading(3.0, -float("inf"))["host"] == 2.0
        assert clock.reading(3.0, None)["no_rows"] == 2.0
        assert clock.seconds == dict.fromkeys(clock.CAUSES, 0.0)
        assert clock.idle


# ---------------------------------------------------------------------------
# a real scorer (CPU): it hands the manager its clock's reader
# ---------------------------------------------------------------------------
class TestTheScorersReader:
    def test_setup_hands_the_profiler_a_reader_of_three_causes(self):
        from tests.test_device_obs import _span_detector

        det = _span_detector("capture-reader")
        det.setup_io()
        try:
            reader = PROFILER._idle_reader
            assert reader == det._idle_reading
            now = time.monotonic()
            assert set(reader(now)) == set(DeviceIdleClock.CAUSES)
            assert exported({"component_id": "capture-reader"}) == []
        finally:
            det.flush_final()
            PROFILER.set_idle_reader(None)

    def test_a_read_that_fell_into_an_update_is_made_again(self, tmp_path):
        calls = []

        def reader(now):
            calls.append(now)
            if len(calls) == 1:
                raise RuntimeError("dictionary changed size during iteration")
            return dict.fromkeys(DeviceIdleClock.CAUSES, 0.0)

        manager = ProfileManager()
        manager.set_idle_reader(reader)
        info = capture(manager, tmp_path, labels=labels_of("reader-retry"))
        assert info["state"] == "done"
        assert info["idle_share"] == dict.fromkeys(DeviceIdleClock.CAUSES,
                                                   0.0)
        assert len(calls) == 3 and calls[0] == calls[1]

