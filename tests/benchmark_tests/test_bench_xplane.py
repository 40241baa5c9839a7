"""``lib/xplane.py`` on a small trace recorded on the chip in PR 23
(``data/trace_logbert_saturate.json``: three scoring calls of
``logbert-256x4`` at full width, trimmed) and on hand-made intervals."""
import bench_helpers  # noqa: F401  (puts the repo root on sys.path)
import os

import pytest

from bench_helpers import REPO, read_json
from benchmark.lib import layers, xplane

FIXTURE = os.path.join(REPO, "tests", "benchmark_tests", "data",
                       "trace_logbert_saturate.json")


def test_union_and_gaps_by_hand():
    intervals = [(0, 10), (5, 12), (20, 30), (22, 25)]
    assert xplane.union_ns(intervals) == 22
    assert xplane.gaps_ns(intervals, -3, 40) == [3, 8, 10]
    assert xplane.union_ns([]) == 0.0
    assert xplane.gaps_ns([], 0, 7) == [7]


def test_nested_ops_are_not_counted_twice():
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [["%while", 0.0, 1e9],
                                       ["%fusion.1", 1e8, 2e8],
                                       ["%fusion.2", 1.5e9, 5e8]]},
        {"name": "XLA Modules", "events": [["jit_f(1)", 0.0, 2e9]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": [["start_trace", -3e9, 1e9]]}]}]}
    out = xplane.reduce(trace)
    assert out["busy_s"] == pytest.approx(1.5)
    assert out["window_s"] == pytest.approx(2.0)
    assert out["idle_gaps"][0] == ["unattributed", pytest.approx(0.5)]
    assert out["modules"]["jit_f(1)"] == {
        "count": 1, "total_s": 2.0, "median_s": 2.0, "whole_count": 1,
        "whole_total_s": 2.0}


def test_a_trace_without_a_device_plane_reads_nothing():
    trace = {"planes": [{"name": "/host:CPU", "lines": [
        {"name": "python3", "events": [["f", 0.0, 1e6]]}]}]}
    out = xplane.reduce(trace)
    assert out["devices"] == 0 and out["busy_s"] == 0.0
    assert layers.evaluate({"kind": "trace", "reducer": "device_idle_share"},
                           {"trace": out}) is None
    assert layers.evaluate({"kind": "trace", "reducer": "step_roofline_share"},
                           {"trace": out}) is None


class TestRecordedTrace:
    @pytest.fixture(scope="class")
    def reduced(self):
        return xplane.reduce(read_json(FIXTURE))

    def test_inventory(self, reduced):
        assert reduced["devices"] == 1
        assert ["/device:TPU:0", "XLA Modules", 3] in reduced["inventory"]

    def test_three_scoring_calls_of_about_314_ms(self, reduced):
        (name,) = reduced["modules"]
        assert name.startswith("jit__score_impl(")
        calls = reduced["modules"][name]
        # the capture's edge cut the first call short: two whole calls
        assert calls["count"] == 3 and calls["whole_count"] == 2
        assert calls["median_s"] == pytest.approx(0.313824693, rel=1e-9)

    def test_busy_time_is_the_modules_time(self, reduced):
        # back-to-back ops inside each call: the union of the op intervals
        # is the calls' own time to a few microseconds
        (calls,) = reduced["modules"].values()
        assert reduced["busy_s"] == pytest.approx(calls["total_s"], abs=1e-4)
        # the host events 0.1 s before and after are outside the window
        assert reduced["window_s"] == pytest.approx(calls["total_s"],
                                                    abs=1e-4)

    def test_back_to_back_calls_leave_only_microsecond_gaps(self, reduced):
        gaps = [seconds for _, seconds in reduced["idle_gaps"]]
        assert gaps and max(gaps) < 1e-4
        assert all(name == "unattributed" for name, _ in
                   reduced["idle_gaps"])

    def test_the_head_dominates(self, reduced):
        top = [name for name, _ in reduced["device_ops"][:3]]
        assert top[0].startswith("%while")
        assert any("f32[16384,32768]" in name for name in top)
        assert all(len(name) <= xplane.NAME_CHARS for name in top)

    def test_roofline_share_of_the_recorded_calls(self, reduced):
        ctx = {"trace": reduced, "capture_buckets": [16384],
               "scorer": {"model": "logbert", "vocab_size": 32768,
                          "dim": 256, "depth": 4, "heads": 4, "seq_len": 32},
               "peak": {"flops_per_s": 197e12, "bytes_per_s": 819e9}}
        share = layers.evaluate({"kind": "trace",
                                 "reducer": "step_roofline_share"}, ctx)
        # 16384 rows * 742,391,808 ops / 197e12 = 61.74 ms of 313.8 ms
        assert share == pytest.approx(19.67, abs=0.05)
        assert share < 100
