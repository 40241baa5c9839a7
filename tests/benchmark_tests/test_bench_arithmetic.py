"""Quantile, schedule, corpus, prometheus-delta and verdict arithmetic."""
import bench_helpers  # noqa: F401  (puts the repo root on sys.path)
import math
import random
import statistics

import pytest

from benchmark.lib import corpus, prom, quantiles, schedule, verdict


class TestQuantiles:
    @pytest.mark.parametrize("q", [0.0, 0.25, 0.5, 0.95, 1.0])
    def test_matches_numpy_linear_rule(self, q):
        import numpy as np

        rng = random.Random(3)
        samples = [rng.random() * 100 for _ in range(997)]
        assert quantiles.quantile(samples, q) == pytest.approx(
            float(np.quantile(samples, q)), rel=1e-12)

    def test_median_of_two_is_their_mean(self):
        assert quantiles.quantile([1.0, 3.0], 0.5) == 2.0

    def test_no_samples_is_an_error(self):
        with pytest.raises(ValueError):
            quantiles.quantile([], 0.5)

    def test_samples_beyond_p95(self):
        assert quantiles.samples_beyond(200, 0.95) == 10


class TestSchedule:
    def test_fixed_gaps_carry_the_rate(self):
        offs = schedule.offsets("fixed", 25600.0, 256, 2.0, seed=1)
        assert len(offs) == 200
        assert offs[0] == 0.0 and offs[1] == pytest.approx(0.01)
        assert offs[-1] < 2.0

    def test_exponential_gaps_are_one_multiset_for_every_seed(self):
        a = schedule.offsets("exponential", 25600.0, 256, 4.0, seed=1)
        b = schedule.offsets("exponential", 25600.0, 256, 4.0, seed=2**31 + 9)
        assert len(a) == len(b) == 400 and a != b

        def gaps(offs):
            return sorted(round(y - x, 12) for x, y in zip(offs, offs[1:]))

        # all but the last gap (which closes the span) are shared; compare
        # the whole multiset through its sum and its spread
        assert sum(gaps(a)) == pytest.approx(sum(gaps(b)), rel=0.02)
        assert max(a) < 4.0 and max(b) < 4.0
        assert a == schedule.offsets("exponential", 25600.0, 256, 4.0, seed=1)

    def test_exponential_gaps_look_exponential(self):
        offs = schedule.offsets("exponential", 256000.0, 256, 10.0, seed=5)
        gaps = [y - x for x, y in zip(offs, offs[1:])]
        mean = statistics.mean(gaps)
        assert mean == pytest.approx(10.0 / len(offs), rel=0.01)
        # an exponential's standard deviation equals its mean
        assert statistics.pstdev(gaps) == pytest.approx(mean, rel=0.05)

    def test_unknown_rule_is_an_error(self):
        with pytest.raises(ValueError):
            schedule.offsets("bursty", 1000.0, 256, 1.0, seed=1)


def _serialize(log_id, line):
    return f"{log_id}|{line}".encode()


class TestCorpus:
    def _pool(self, seed):
        train = corpus.normal_lines(seed, "train", 256, 0)
        return train, corpus.build_pool(seed, 2048, 256, 0.01, train, 0,
                                        _serialize)

    def test_same_seed_same_pool_other_seed_other_pool(self):
        assert self._pool(7)[1].lines == self._pool(7)[1].lines
        assert self._pool(7)[1].lines != self._pool(2**31 + 7)[1].lines

    def test_anomalous_count_is_the_same_for_every_seed(self):
        for seed in (1, 2, 99):
            pool = self._pool(seed)[1]
            assert len(pool.anomalous) == 20
            for i in pool.anomalous:
                assert any(f'comm="{c}"' in pool.lines[i]
                           for c, _, _ in corpus.ANOMALOUS_COMMS)

    def test_lines_are_distinct_and_reuse_training_fields(self):
        train, pool = self._pool(3)
        assert len(set(pool.lines)) == len(pool.lines) == 2048
        stamps = {line.split("audit(")[1].split(")")[0] for line in train}
        pids = {corpus._pid_of(line) for line in train}
        for line in pool.lines:
            assert line.split("audit(")[1].split(")")[0] in stamps
            assert corpus._pid_of(line) in pids

    def test_make_line_is_the_programs(self):
        from detectmateservice_tpu.loadgen import corpus as theirs

        for i, anomaly in ((0, False), (17, True), (123456, False)):
            assert (corpus.make_line(i, random.Random(i), anomaly)
                    == theirs.make_line(i, random.Random(i), anomaly))

    def test_frames_unpack_with_the_programs_framing(self):
        from detectmateservice_tpu.engine.framing import unpack_batch

        pool = self._pool(4)[1]
        assert len(pool.frames) == 8
        assert list(unpack_batch(pool.frames[3])) == pool.messages[768:1024]

    def test_pool_id_is_fixed_width(self):
        pool = self._pool(4)[1]
        assert pool.pool_id(5) == "000005"
        assert pool.messages[5].startswith(b"000005|")


EXPOSITION_A = """\
# HELP processing_duration_seconds End-to-end process() duration
# TYPE processing_duration_seconds histogram
processing_duration_seconds_bucket{component_id="p",le="0.001"} 10.0
processing_duration_seconds_sum{component_id="p",component_type="x"} 1.5
processing_duration_seconds_count{component_id="p",component_type="x"} 40.0
detector_batch_occupancy_sum{component_id="d",path="device"} 3.0
detector_batch_occupancy_count{component_id="d",path="device"} 4.0
detector_batch_occupancy_sum{component_id="d",path="host"} 9.0
engine_ingress_backlog{component_id="p"} 7.0
"""
EXPOSITION_B = EXPOSITION_A.replace(
    '_sum{component_id="p",component_type="x"} 1.5',
    '_sum{component_id="p",component_type="x"} 4.5').replace(
    'path="device"} 3.0', 'path="device"} 9.0').replace(
    'path="device"} 4.0', 'path="device"} 12.0')


class TestProm:
    def test_delta_of_a_histogram_sum(self):
        a, b = prom.parse(EXPOSITION_A), prom.parse(EXPOSITION_B)
        assert prom.delta(a, b, "processing_duration_seconds_sum") == 3.0

    def test_labels_select_the_series(self):
        a, b = prom.parse(EXPOSITION_A), prom.parse(EXPOSITION_B)
        device = {"path": "device"}
        assert prom.delta(a, b, "detector_batch_occupancy_sum", device) == 6.0
        assert prom.delta(a, b, "detector_batch_occupancy_count",
                          device) == 8.0
        assert prom.total(a, "detector_batch_occupancy_sum") == 12.0

    def test_absent_series_is_zero_and_not_present(self):
        a = prom.parse(EXPOSITION_A)
        assert prom.total(a, "no_such_series") == 0.0
        assert not prom.present(a, "no_such_series")
        assert prom.present(a, "engine_ingress_backlog")

    def test_layer_readers_on_the_recorded_exposition(self):
        from benchmark.lib import layers

        a, b = prom.parse(EXPOSITION_A), prom.parse(EXPOSITION_B)
        ctx = {"prom": {"parser": (a, b), "detector": (a, b)},
               "window_s": 10.0, "gauge_samples": {"parser": [a, b]},
               "generator": {"late_ms": [1.0, 2.0, 3.0]}}
        busy = {"kind": "prom-delta", "stage": "parser", "scale": 100,
                "numerator": {"series": "processing_duration_seconds_sum"},
                "denominator": "window_s"}
        assert layers.evaluate(busy, ctx) == pytest.approx(30.0)
        occupancy = {"kind": "prom-delta", "stage": "detector", "scale": 100,
                     "numerator": {"series": "detector_batch_occupancy_sum",
                                   "labels": {"path": "device"}},
                     "denominator": {
                         "series": "detector_batch_occupancy_count",
                         "labels": {"path": "device"}}}
        assert layers.evaluate(occupancy, ctx) == pytest.approx(75.0)
        gauge = {"kind": "prom-gauge", "series": "engine_ingress_backlog",
                 "stages": ["parser", "detector"], "reducer": "max"}
        assert layers.evaluate(gauge, ctx) == 7.0
        late = {"kind": "generator", "field": "late_ms", "reducer": "p95"}
        assert layers.evaluate(late, ctx) == pytest.approx(2.9)
        nothing = dict(busy, numerator={"series": "absent_sum"})
        assert layers.evaluate(nothing, ctx) is None


class TestVerdict:
    REF = {"a": 5.0, "b": 3.0, "c": 4.01}   # threshold 4.0, tolerance 0.05

    def judge(self, alerts, sent=None, thresholds=(4.0,)):
        return verdict.judge(alerts, sent or {"a": 2, "b": 2, "c": 2},
                             self.REF, 4.0,
                             {"tolerance_nats": 0.05, "rms_limit_nats": 0.03},
                             list(thresholds))

    def test_sound_run(self):
        out = self.judge({"a": [5.01, 4.99], "c": [4.02]})
        assert out["ok"] and out["failed"] == 0
        assert out["expected_alerts"] == 2 and out["lines_in_band"] == 1

    @pytest.mark.parametrize("alerts,name", [
        ({"a": [5.0]}, "missing_alerts"),
        ({"a": [5.0, 5.0, 5.0]}, "duplicate_alerts"),
        ({"a": [5.0, 5.0], "b": [4.2]}, "false_alerts"),
        ({"a": [5.0, 5.2]}, "score_gap_max_nats"),
        ({"a": [5.0, 5.0], "c": [4.0, 4.0, 4.0]}, "duplicate_alerts"),
        ({"a": [5.04, 5.04]}, "score_gap_rms_nats"),
    ])
    def test_each_fault_fails_its_own_number(self, alerts, name):
        out = self.judge(alerts)
        assert not out["ok"]
        failed = [n for n, value, limit in out["numbers"] if value > limit]
        assert name in failed

    def test_alerts_must_print_the_fitted_threshold(self):
        out = self.judge({"a": [5.0, 5.0]}, thresholds=(4.0, 4.3))
        assert not out["ok"]
