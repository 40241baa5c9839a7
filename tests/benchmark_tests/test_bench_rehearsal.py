"""The harness's own functions end to end on the CPU: three service
processes, generator, sink, drain, reference child and verdict, at a tiny
configuration whose ``backend: cpu`` the test sets itself. ``run.py`` as the
command has no such switch and refuses without a chip."""
import bench_helpers  # noqa: F401  (puts the repo root on sys.path)
import json
import os
import subprocess
import sys
import time

import pytest

from bench_helpers import REPO, read_json, temp_root, write_json


def _run(root, cell, seed=11, seconds=3.0, trace=False):
    from benchmark import run

    return run.run_cell(root, cell, seed, seconds, trace, platform="cpu",
                        t_start=time.monotonic())


def test_a_sound_run_is_correct_and_reports_the_cells_metrics(tmp_path,
                                                              capsys):
    root, cell = temp_root(tmp_path, config_name="mlp-compose", model="mlp",
                           traffic="steady", rate=8000)
    result = _run(root, cell)
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
    # every number compared beside its limit: the result's last key, and the
    # last lines on standard error
    assert list(result)[-1] == "compared"
    assert all(set(pair) == {"value", "limit"}
               for pair in result["compared"].values())
    assert {"score_gap_max_nats", "dropped_lines",
            "compiles_after_warmup"} <= set(result["compared"])
    printed = capsys.readouterr()
    last = printed.err.strip().splitlines()[-len(result["compared"]):]
    assert [line.split()[1] for line in last] == list(result["compared"])
    assert all(line.startswith("compared: ") and line.endswith(" ok")
               for line in last)
    assert "configuration: reduced nothing" in printed.out
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"setup_s", "alert_p50_ms"}
    assert result["device"]["platform"] == "cpu"
    json.dumps(result)


def test_a_traced_run_reports_per_layer_metrics_and_an_added_one(tmp_path,
                                                                 capsys):
    """A configuration cut in depth, a traffic mix, a cell and a per-layer
    metric of an existing source kind, added by files and manifest entries
    only."""
    added = {
        "name": "detector_rows_per_call",
        "file": {"name": "detector_rows_per_call", "layer": "detector host",
                 "unit": "rows", "moves": "alert_p50_ms", "kind": "prom-delta",
                 "stage": "detector",
                 "numerator": {"series": "detector_batch_size_sum"},
                 "denominator": {"series": "detector_batch_size_count"}},
        "entry": {"name": "detector_rows_per_call", "unit": "rows",
                  "better": "higher", "source": "program_counter",
                  "layer": "detector host", "moves": "alert_p50_ms"},
    }
    mix = {"name": "overload-poisson", "loop": "open", "frame_lines": 128,
           "arrival": "exponential", "anomaly_share": 0.02, "ramp_s": 1.0,
           "saturating": True}
    root, cell = temp_root(
        tmp_path, model="logbert", traffic="steady", rate=4000, metric=added,
        new_traffic=mix, reduced={"depth": {
            "published": 4, "here": 1, "why": "what the CPU holds"}})
    assert cell == "tiny-logbert.overload-poisson"
    before = {name: read_json(os.path.join(REPO, "benchmark", sub, name))
              for sub in ("configs", "traffic", "cells", "layer_metrics")
              for name in os.listdir(os.path.join(REPO, "benchmark", sub))
              if name.endswith(".json")}
    result = _run(root, cell, trace=True)
    assert result["correct"] is True
    # the report a person reads says what was cut
    assert ("configuration: reduced ['depth']; depth 4 -> 1"
            in capsys.readouterr().out)
    assert {"parser_busy_share", "detector_busy_share", "batch_occupancy",
            "dispatch_ready_ms.lat", "queue_wait_mean_ms", "alert_p95_ms",
            "detector_rows_per_call"} <= set(result["metrics"])
    assert "setup_s" not in result["metrics"]
    # no device trace on the CPU: the trace readers find nothing and the
    # harness leaves their metrics out
    assert "device_idle_share" not in result["metrics"]
    assert "attn_share_of_call" not in result["metrics"]
    assert "lse_pallas_roofline" not in result["metrics"]
    # the copy's pre-existing files are letter for letter the repo's
    for sub in ("configs", "traffic", "cells", "layer_metrics"):
        for name in before:
            if os.path.exists(os.path.join(REPO, "benchmark", sub, name)):
                assert read_json(os.path.join(root, "benchmark", sub,
                                              name)) == before[name]
    assert read_json(os.path.join(REPO, "BENCHMARK.json"))["workloads"] == [
        w for w in read_json(os.path.join(root, "BENCHMARK.json"))[
            "workloads"] if w["name"] != cell]


def test_a_token_altered_where_it_is_produced_is_not_correct(tmp_path,
                                                             monkeypatch):
    """The timed path broken underneath: the scorer the detector serves with
    is not the one the checkpoint describes (its scores are shifted where
    they are produced). The rest of the run is the harness's own."""
    root, cell = temp_root(tmp_path, config_name="mlp-compose", model="mlp",
                           traffic="steady", rate=8000)
    patch_dir = tmp_path / "patch"
    patch_dir.mkdir()
    (patch_dir / "sitecustomize.py").write_text(
        "import os\n"
        "if 'detector' in ' '.join(os.sys.argv):\n"
        "    import detectmateservice_tpu.models.mlp as m\n"
        "    _orig = m.bag_nll\n"
        "    m.bag_nll = lambda logits, tokens: _orig(logits, tokens) + 0.5\n")
    monkeypatch.setenv("PYTHONPATH", str(patch_dir) + os.pathsep
                       + os.environ.get("PYTHONPATH", ""))
    result = _run(root, cell)
    assert result["correct"] is False


def test_the_control_fails_where_the_sound_program_passes(tmp_path, capsys):
    """The builder's sweep through the harness's own ``measure`` and
    ``conclude``: the float32 program at a tiny size passes limits that the
    reference with float8 inputs, put in its place, fails."""
    from benchmark import sweep

    root, cell = temp_root(tmp_path, config_name="mlp-compose", model="mlp",
                           traffic="steady", rate=8000)
    path = os.path.join(root, "benchmark", "configs", "tiny-mlp.json")
    config = read_json(path)
    config["check"].update(tolerance_nats=0.002, rms_limit_nats=0.001)
    write_json(path, config)
    result = sweep.one_run(root, cell, 7, 3.0, control="float8_e4m3fn",
                           platform="cpu")
    assert result["correct"] is True
    assert result["control_fails"] is True
    assert "control float8_e4m3fn: score_gap_max_nats" in capsys.readouterr().out


def test_the_sweep_enters_a_cell_the_manifest_does_not_list():
    """``cells/logbert-256x4.saturate.json`` is kept but not admitted: the
    sweep's copy lists it, with every per-layer metric, and changes no file
    of the repo."""
    import shutil

    from benchmark import sweep
    from benchmark.lib import manifest

    before = read_json(os.path.join(REPO, "BENCHMARK.json"))
    assert "logbert-256x4.saturate" not in {
        w["name"] for w in before["workloads"]}
    root = sweep.variant_root("logbert-256x4.saturate", 1e7)
    try:
        cell = manifest.load_cell(root, "logbert-256x4.saturate")
        assert cell["cell"]["rate_lines_per_s"] == 1e7
        assert cell["traffic"]["saturating"] is True
        assert len(cell["per_layer"]) == len(before["per_layer"])
        other = sweep.variant_root("mlp-compose.steady", 0)
        try:
            cell = manifest.load_cell(other, "mlp-compose.steady")
            assert cell["config"]["name"] == "mlp-compose"
            assert cell["cell"]["rate_lines_per_s"] == 185000
        finally:
            shutil.rmtree(other)
    finally:
        shutil.rmtree(root)
    assert read_json(os.path.join(REPO, "BENCHMARK.json")) == before


def test_the_command_refuses_without_a_chip():
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env["JAX_PLATFORMS"] = "cpu"    # this sandbox: jax is held to the CPU
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", "logbert-256x4.steady", "--seed", "3", "--seconds", "2",
         "--trace", "0"], capture_output=True, text=True, timeout=600,
        env=env, cwd=REPO)
    assert proc.returncode != 0
    assert '"metrics"' not in proc.stdout and '"correct"' not in proc.stdout


def test_the_command_takes_no_fifth_argument():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", "logbert-256x4.steady", "--seed", "3", "--seconds", "2",
         "--trace", "0", "--rehearse-cpu"], capture_output=True, text=True,
        timeout=120, cwd=REPO)
    assert proc.returncode != 0 and '"correct"' not in proc.stdout


def test_trace_readers_on_a_reduced_trace(tmp_path):
    from benchmark.layer_metrics import device_idle_share
    from benchmark.layer_metrics import step_roofline_share

    ctx = {"trace": {"modules": {"jit__score_impl(1)": {
        "count": 2, "total_s": 0.2, "median_s": 0.1, "whole_count": 2,
        "whole_total_s": 0.2}}, "devices": 1,
        "busy_s": 1.0, "window_s": 2.0},
        "capture_buckets": [16384],
        "scorer": {"model": "mlp", "dim": 128, "seq_len": 32},
        "peak": {"flops_per_s": 197e12, "bytes_per_s": 819e9}}
    share = step_roofline_share.read(ctx)
    assert share == pytest.approx(
        100 * 2 * (16384 * 8527872 / 197e12) / 0.2)
    assert device_idle_share.read(ctx) == pytest.approx(50.0)
    ctx["capture_buckets"] = [256, 16384]      # does not pair up: no number
    assert step_roofline_share.read(ctx) is None
