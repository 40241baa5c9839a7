"""The five per-layer metrics of the layer ``capture``: what the traced run's
own ``POST /admin/profile`` capture cost the detector, read from the
``profile_capture_*`` gauges the program sets when the capture ends
(``utils/profiling.py``). Each is a data file for the ``prom-gauge`` reader
that was there: evaluated through ``lib/layers.evaluate`` on a recorded pair
of scrapes, and left out — ``None``, never 0 — on the scrapes of a program
that does not keep the record, which is the parent's case.

One pin written for fewer generic metrics holds no longer by construction
and is the benchmark's to edit: ``test_bench_moe_delta.py::
test_every_generic_list_has_every_cell_and_a_familys_metric_its_own`` counts
the generic lists as exactly 21 (its twins in ``test_bench_moe_conv.py``,
``test_bench_moe_mla.py`` and ``test_bench_room.py`` failed before). What it
guards is restated here for 26, by a table."""
import os
import time

import pytest

from bench_helpers import REPO, family_metrics, read_json, temp_root

from benchmark.lib import layers, manifest, prom

LABELS = 'component_type="detectors.jax_scorer",component_id="detector"'

# the detector's /metrics half a second before the capture ended: the
# program keeps the record, and has not set a gauge yet
RUNNING = f"""
engine_ingress_backlog{{{LABELS}}} 64.0
detector_phase_total{{{LABELS},phase="alert_build"}} 12.0
profile_captures_total{{{LABELS},state="done"}} 0.0
"""
# and the two samples after it ended (the gauges stay until the next one)
ENDED = RUNNING.replace('state="done"}} 0.0', 'state="done"}} 1.0') + f"""
profile_capture_seconds{{{LABELS},phase="start"}} 0.043
profile_capture_seconds{{{LABELS},phase="traced"}} 4.0007
profile_capture_seconds{{{LABELS},phase="stop"}} 1.92
profile_capture_stall_seconds{{{LABELS},stat="max"}} 0.31
profile_capture_stall_seconds{{{LABELS},stat="sum"}} 0.52
profile_capture_span_max_seconds{{{LABELS},span="dm.alert_build"}} 0.0125
profile_capture_span_max_seconds{{{LABELS},span="dm.readback"}} 0.0004
profile_capture_span_max_seconds{{{LABELS},span="dm.send"}} 2.5
profile_capture_idle_share{{{LABELS},cause="fill"}} 38.5
profile_capture_idle_share{{{LABELS},cause="no_rows"}} 2.25
profile_capture_idle_share{{{LABELS},cause="host"}} 0.75
"""
PARSER = 'engine_ingress_backlog{component_type="parser",' \
    'component_id="parser"} 256.0\n'

# name -> (unit, series, labels of the file, the number by hand on ENDED)
EXPECTED = {
    "capture_start_s": ("s", "profile_capture_seconds",
                        {"phase": "start"}, 0.043),
    "capture_stop_s": ("s", "profile_capture_seconds",
                       {"phase": "stop"}, 1.92),
    "capture_stall_max_s": ("s", "profile_capture_stall_seconds",
                            {"stat": "max"}, 0.31),
    "capture_span_max_s": ("s", "profile_capture_span_max_seconds",
                           {"span": "dm.alert_build"}, 0.0125),
    # no label: the three causes are summed
    "capture_idle_share": ("%", "profile_capture_idle_share", None,
                           38.5 + 2.25 + 0.75),
}
# the metrics that hold for any scorer, by layer, as PR 38 left them (the
# family metrics are those the cells' files name; a later PR may add to
# either, so nothing here counts them)
GENERIC = {
    "served path": {"alert_p95_ms"},
    "load generator": {"gen_late_p95_ms"},
    "transport": {"ingress_backlog_max", "detector_send_blocked_share"},
    "parser stage": {"parser_busy_share"},
    "detector host": {"detector_busy_share", "alert_build_ms_per_batch"},
    "coalescer": {"batch_occupancy", "queue_wait_mean_ms",
                  "row_hold_mean_ms"},
    "device executor": {"dispatch_ready_ms.lat", "upload_ms_per_batch",
                        "readback_ms_per_batch"},
    "kernels": {"step_roofline_share", "attn_share_of_call",
                "head_share_of_call", "lse_pallas_roofline"},
    "device": {"device_idle_share", "idle_fill_share", "idle_host_share"},
    "output stage": {"output_busy_share"},
    "capture": set(EXPECTED),
}


def spec_of(name: str) -> dict:
    return read_json(os.path.join(REPO, "benchmark", "layer_metrics",
                                  name + ".json"))


def context(*detector_scrapes: str) -> dict:
    return {"gauge_samples": {
        "parser": [prom.parse(PARSER)] * len(detector_scrapes),
        "detector": [prom.parse(text) for text in detector_scrapes]}}


@pytest.fixture(scope="module")
def listed():
    return read_json(os.path.join(REPO, "BENCHMARK.json"))


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_the_file_reads_its_gauge_once_the_capture_has_ended(name):
    by_hand = EXPECTED[name][3]
    ctx = context(RUNNING, RUNNING, ENDED, ENDED)
    assert layers.evaluate(spec_of(name), ctx) == pytest.approx(by_hand)
    # one sample after the end is enough
    assert layers.evaluate(spec_of(name),
                           context(RUNNING, ENDED)) == pytest.approx(by_hand)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_the_parents_scrapes_leave_the_metric_out(name):
    """A program without the record — the parent — exports none of the
    gauges: the reader finds no series, returns ``None`` and ``run.py``
    leaves the metric out of the line. So does a run whose capture had not
    ended by the window's last sample."""
    parent = RUNNING.replace(
        f'profile_captures_total{{{LABELS},state="done"}} 0.0\n', "")
    assert "profile_" not in parent
    assert layers.evaluate(spec_of(name), context(parent, parent)) is None
    assert layers.evaluate(spec_of(name), context(RUNNING, RUNNING)) is None
    assert layers.evaluate(spec_of(name), context()) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_a_failed_capture_sets_no_gauge_and_reports_nothing(name):
    failed = RUNNING + f'profile_captures_total{{{LABELS},state="error"}} 1\n'
    assert layers.evaluate(spec_of(name), context(RUNNING, failed)) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_the_file_agrees_with_its_manifest_entry(name, listed):
    from detectmateservice_tpu.engine import metrics as program

    unit, series, labels, _ = EXPECTED[name]
    spec = spec_of(name)
    (entry,) = [m for m in listed["per_layer"] if m["name"] == name]
    assert entry == {
        "name": name, "unit": unit, "better": "lower",
        "source": "program_counter", "layer": "capture",
        "moves": "alert_p50_ms",
        "workloads": [w["name"] for w in listed["workloads"]]}
    assert (spec["name"], spec["unit"], spec["layer"], spec["moves"]) == (
        name, unit, "capture", "alert_p50_ms")
    # a data file for the reader that was there: the detector's gauge,
    # sampled through the window, the largest sample
    assert (spec["kind"], spec["stages"], spec["reducer"]) == (
        "prom-gauge", ["detector"], "max")
    assert spec["series"] == series and spec.get("labels") == labels
    assert spec["note"]
    # the program declares the series the file names
    assert program.REGISTERED_SERIES[series] is program.Gauge


def test_the_five_are_data_files_and_the_last_entries(listed):
    names = [m["name"] for m in listed["per_layer"]]
    assert names[-5:] == ["capture_start_s", "capture_stop_s",
                          "capture_stall_max_s", "capture_span_max_s",
                          "capture_idle_share"]
    here = os.path.join(REPO, "benchmark", "layer_metrics")
    for name in EXPECTED:
        assert os.path.exists(os.path.join(here, name + ".json"))
        assert not os.path.exists(os.path.join(here, name + ".py"))


def test_every_cell_reports_the_layer_capture_and_the_generic_metrics(listed):
    """A family's metric lists its own cells, each generic one lists every
    cell in the manifest's order under the layer it had, and each cell
    reports at least one metric of every layer — ``capture`` among them."""
    cells = [w["name"] for w in listed["workloads"]]
    by_name = {m["name"]: m for m in listed["per_layer"]}
    family = family_metrics(REPO, listed)
    generic = set(by_name) - family
    assert set().union(*GENERIC.values()) <= generic
    for layer, names in GENERIC.items():
        for name in names:
            assert by_name[name]["workloads"] == cells, name
            assert by_name[name]["layer"] == layer, name
    for name in family:
        assert set(by_name[name]["workloads"]) <= set(cells), name
    layers_named = {m["layer"] for m in listed["per_layer"]}
    assert set(GENERIC) <= layers_named
    for cell in cells:
        ours = manifest.load_cell(REPO, cell)["per_layer"]
        assert generic <= {s["name"] for s in ours}
        assert {s["layer"] for s in ours} == layers_named
        assert set(EXPECTED) == {s["name"] for s in ours
                                 if s["layer"] == "capture"}


def test_a_traced_run_on_the_cpu_reports_all_five(tmp_path):
    """The whole chain at a tiny configuration (``backend: cpu`` set by the
    helper): the harness's own capture, the program's record of it, the
    gauges on the detector's ``/metrics``, the window's samples, the five
    files. The window is long enough for the capture (taken 1 s into it,
    for 4 s) to end inside it; no number is asserted but a share's range."""
    from benchmark import run

    root, cell = temp_root(tmp_path, model="logbert", traffic="steady",
                           rate=4000)
    result = run.run_cell(root, cell, 11, 8.0, True, platform="cpu",
                          t_start=time.monotonic())
    assert result["correct"] is True
    ours = {name: entry for name, entry in result["metrics"].items()
            if name.startswith("capture_")}
    assert set(ours) == set(EXPECTED)
    assert {name: entry["unit"] for name, entry in ours.items()} == {
        name: unit for name, (unit, _, _, _) in EXPECTED.items()}
    assert all(entry["value"] >= 0.0 for entry in ours.values())
    assert ours["capture_idle_share"]["value"] <= 101.0
    # the other metrics of a traced run are what they were
    assert {"alert_build_ms_per_batch", "idle_fill_share",
            "ingress_backlog_max"} <= set(result["metrics"])
