"""The eight per-layer metrics that read the program's spans and boundary
counters (``dm.*`` phases, row-weighted hold, idle by cause, send-blocked
time, the output stage's busy time): each metric's own file, through
``lib/layers.evaluate``, on two hand-written scrapes — the quotient is done
by hand beside it — and a scrape that lacks the series leaves the metric
out."""
import os

import pytest

from bench_helpers import REPO, read_json

from benchmark.lib import layers, prom

LABELS = 'component_type="jax_scorer",component_id="detector"'

# ten seconds apart; the detector released two full batches of 29,492 rows
DETECTOR_A = f"""
detector_row_hold_seconds_total{{{LABELS}}} 100.0
detector_rows_released_total{{{LABELS},reason="full"}} 1000.0
detector_rows_released_total{{{LABELS},reason="deadline"}} 24.0
detector_rows_released_total{{{LABELS},reason="flush"}} 0.0
detector_phase_seconds_total{{{LABELS},phase="upload"}} 0.5
detector_phase_total{{{LABELS},phase="upload"}} 10.0
detector_phase_seconds_total{{{LABELS},phase="readback"}} 0.25
detector_phase_total{{{LABELS},phase="readback"}} 10.0
detector_phase_seconds_total{{{LABELS},phase="alert_build"}} 1.0
detector_phase_total{{{LABELS},phase="alert_build"}} 12.0
detector_device_idle_seconds_total{{{LABELS},cause="fill"}} 3.0
detector_device_idle_seconds_total{{{LABELS},cause="no_rows"}} 40.0
detector_device_idle_seconds_total{{{LABELS},cause="host"}} 0.5
engine_send_blocked_seconds_total{{{LABELS}}} 0.0
"""
DETECTOR_B = f"""
detector_row_hold_seconds_total{{{LABELS}}} 25800.0
detector_rows_released_total{{{LABELS},reason="full"}} 59984.0
detector_rows_released_total{{{LABELS},reason="deadline"}} 24.0
detector_rows_released_total{{{LABELS},reason="flush"}} 0.0
detector_phase_seconds_total{{{LABELS},phase="upload"}} 0.512
detector_phase_total{{{LABELS},phase="upload"}} 12.0
detector_phase_seconds_total{{{LABELS},phase="readback"}} 0.253
detector_phase_total{{{LABELS},phase="readback"}} 12.0
detector_phase_seconds_total{{{LABELS},phase="alert_build"}} 1.05
detector_phase_total{{{LABELS},phase="alert_build"}} 14.0
detector_device_idle_seconds_total{{{LABELS},cause="fill"}} 5.5
detector_device_idle_seconds_total{{{LABELS},cause="no_rows"}} 40.0
detector_device_idle_seconds_total{{{LABELS},cause="host"}} 0.6
engine_send_blocked_seconds_total{{{LABELS}}} 0.02
"""
OUTPUT_LABELS = 'component_type="output_writer",component_id="output"'
OUTPUT_A = f"processing_duration_seconds_sum{{{OUTPUT_LABELS}}} 2.0\n"
OUTPUT_B = f"processing_duration_seconds_sum{{{OUTPUT_LABELS}}} 2.3\n"

WINDOW_S = 10.0

# name -> the quotient, by hand, on the two scrapes above
BY_HAND = {
    # (25800 - 100) row-seconds over (59984 - 1000) rows, in ms
    "row_hold_mean_ms": 25700.0 / 58984.0 * 1000.0,
    "upload_ms_per_batch": (0.512 - 0.5) / 2.0 * 1000.0,        # 6 ms
    "readback_ms_per_batch": (0.253 - 0.25) / 2.0 * 1000.0,     # 1.5 ms
    "alert_build_ms_per_batch": (1.05 - 1.0) / 2.0 * 1000.0,    # 25 ms
    "idle_fill_share": 2.5 / WINDOW_S * 100.0,                  # 25 %
    "idle_host_share": (0.6 - 0.5) / WINDOW_S * 100.0,          # 1 %
    "detector_send_blocked_share": 0.02 / WINDOW_S * 100.0,     # 0.2 %
    "output_busy_share": (2.3 - 2.0) / WINDOW_S * 100.0,        # 3 %
}


def spec_of(name: str) -> dict:
    return read_json(os.path.join(REPO, "benchmark", "layer_metrics",
                                  name + ".json"))


def context(detector_b: str = DETECTOR_B, output_b: str = OUTPUT_B) -> dict:
    return {"prom": {"detector": (prom.parse(DETECTOR_A),
                                  prom.parse(detector_b)),
                     "output": (prom.parse(OUTPUT_A), prom.parse(output_b))},
            "window_s": WINDOW_S}


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_quotient_by_hand(name):
    assert layers.evaluate(spec_of(name), context()) == pytest.approx(
        BY_HAND[name])


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_a_missing_series_leaves_the_metric_out(name):
    """The parent commit exports none of these series (and no stage but the
    detector the detector's): the reader returns nothing and does not
    raise, and the harness leaves the metric out of the line."""
    numerator = spec_of(name)["numerator"]["series"]
    without = "\n".join(line for line in DETECTOR_B.splitlines()
                        if not line.startswith(numerator))
    out_without = "" if numerator.startswith("processing_") else OUTPUT_B
    assert layers.evaluate(spec_of(name),
                           context(without, out_without)) is None


def test_an_unreached_stage_leaves_the_metric_out():
    ctx = context()
    del ctx["prom"]["output"]
    assert layers.evaluate(spec_of("output_busy_share"), ctx) is None


def test_no_release_in_the_window_leaves_the_ratio_out():
    """A ratio whose denominator did not move (no batch released, no span
    taken between the scrapes) is not a number."""
    ctx = {"prom": {"detector": (prom.parse(DETECTOR_B),
                                 prom.parse(DETECTOR_B))},
           "window_s": WINDOW_S}
    for name in ("row_hold_mean_ms", "upload_ms_per_batch",
                 "readback_ms_per_batch", "alert_build_ms_per_batch"):
        assert layers.evaluate(spec_of(name), ctx) is None
    # a share of the window is a number even when nothing moved: 0
    assert layers.evaluate(spec_of("idle_fill_share"), ctx) == 0.0


def test_every_metric_is_in_the_manifest_for_the_steady_cell():
    manifest = read_json(os.path.join(REPO, "BENCHMARK.json"))
    entries = {m["name"]: m for m in manifest["per_layer"]}
    for name in BY_HAND:
        entry = entries[name]
        spec = spec_of(name)
        assert spec["kind"] == "prom-delta"
        assert entry["moves"] == spec["moves"] == "alert_p50_ms"
        assert entry["layer"] == spec["layer"]
        assert entry["unit"] == spec["unit"]
        # a PR that adds a cell appends its name to the list
        assert "logbert-256x4.steady" in entry["workloads"]
