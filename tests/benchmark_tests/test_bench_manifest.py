"""``BENCHMARK.json`` against the contract's shape, and every file it names:
the repo's own, and a temporary copy to which a configuration cut to a chip's
share, its cell and a data-only scope metric were added the way a later PR
adds them (``bench_helpers.room_root``)."""
import bench_helpers  # noqa: F401  (puts the repo root on sys.path)
import os
import re

import pytest

from bench_helpers import REPO, ROOM_TRAFFIC, read_json, room_root
from benchmark.lib import manifest as manifest_lib

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
KINDS = {"prom-delta", "prom-gauge", "generator", "trace"}


@pytest.fixture(scope="module",
                params=["repo"] + ["room-" + mix for mix in ROOM_TRAFFIC])
def root(request, tmp_path_factory):
    """The repo's own manifest, and the rehearsal's copy with a further
    cell of either traffic mix."""
    if request.param == "repo":
        return REPO
    return room_root(tmp_path_factory.mktemp(request.param),
                     traffic=request.param.split("-", 1)[1])[0]


@pytest.fixture(scope="module")
def manifest(root):
    return read_json(os.path.join(root, "BENCHMARK.json"))


def test_top_level_keys(manifest, root):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["command"] == ["python3", "benchmark/run.py"]
    assert 1 <= manifest["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(root, "BENCHMARK.json")) < 64 * 1024


def test_run_seconds_fits_a_full_check_of_24_cells(manifest):
    runs = 2 + 14 * 24
    total = runs * (manifest["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


def test_names_units_and_lines(manifest):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in manifest[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"),
                          entry["name"]))
    assert len(names) == len(set(names))
    for entry in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(entry["unit"]), entry
        assert entry["better"] in ("lower", "higher")
        assert entry["source"] in SOURCES
    for entry in manifest["end_to_end"]:
        assert set(entry) <= {"name", "unit", "better", "bound", "source",
                              "workloads"}
        assert entry["source"] in ("host_clock", "device_trace")
        assert 0.01 <= entry["bound"] <= 0.1
    for entry in manifest["per_layer"]:
        assert set(entry) <= {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
    for entry in manifest["workloads"]:
        assert set(entry) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(entry["config"]) and NAME.match(entry["traffic"])
        assert entry["chips"] in (1, 4)
        assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    for entry in manifest["configs"]:
        assert set(entry) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(entry["source"]) <= 200
        assert 1 <= len(entry["why"]) <= 200


def test_setup_s_is_an_end_to_end_metric_of_every_cell(manifest):
    (setup,) = [m for m in manifest["end_to_end"] if m["name"] == "setup_s"]
    assert "workloads" not in setup and setup["bound"] <= 0.1


def test_every_cells_files_exist_and_agree(manifest, root):
    configs = {c["name"]: c for c in manifest["configs"]}
    assert {w["config"] for w in manifest["workloads"]} == set(configs)
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))
    for entry in manifest["workloads"]:
        cell = manifest_lib.load_cell(root, entry["name"])
        assert cell["config"]["name"] == entry["config"]
        assert cell["traffic"]["name"] == entry["traffic"]
        assert cell["cell"]["config"] == entry["config"]
        assert cell["cell"]["traffic"] == entry["traffic"]
        assert cell["cell"]["rate_lines_per_s"] > 0
        reported = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell["per_layer"], entry["name"]
        for spec in cell["per_layer"]:
            assert spec["kind"] in KINDS
            assert spec["moves"] in reported, (entry["name"], spec["name"])


def test_every_cell_reports_every_layer(manifest, root):
    """A cell cannot be added blind: each ``layer`` the manifest names has a
    metric that lists the cell (or lists none and follows the end-to-end
    metric it moves)."""
    layers = {entry["layer"] for entry in manifest["per_layer"]}
    assert len(layers) >= 10
    for entry in manifest["workloads"]:
        cell = manifest_lib.load_cell(root, entry["name"])
        assert {spec["layer"] for spec in cell["per_layer"]} == layers, \
            entry["name"]


def test_layer_metric_files_match_the_manifest(manifest, root):
    for entry in manifest["per_layer"]:
        spec = read_json(os.path.join(root, "benchmark", "layer_metrics",
                                      entry["name"] + ".json"))
        for key in ("name", "layer", "unit", "moves"):
            assert spec[key] == entry[key], (entry["name"], key)
    reported = {m["name"] for m in manifest["end_to_end"]}
    assert all(entry["moves"] in reported for entry in manifest["per_layer"])
    layers = {}
    for entry in manifest["per_layer"]:
        layers.setdefault(entry["layer"].lower(), set()).add(entry["layer"])
    assert all(len(spellings) == 1 for spellings in layers.values())


def test_configs_state_source_changes_and_guarantees(manifest, root):
    for entry in manifest["configs"]:
        config = read_json(os.path.join(root, entry["file"]))
        assert config["source"] == entry["source"]
        # the rule on ``reduced`` (lib/manifest.py): equal in both places,
        # distinct keys, and each cut written down beside the published value
        assert manifest_lib.reduced_breaches(entry, config) == []
        assert config["reduced"] == entry["reduced"]
        assert sorted(config.get("cut", {})) == sorted(config["reduced"])
        for key in ("assumed", "changed", "guarantees", "stated"):
            assert key in config, (entry["name"], key)
        for stage in ("parser", "detector", "output"):
            settings = config["stages"][stage]["settings"]
            assert settings["out_backpressure"] == "block"
        assert config["stages"]["detector"]["settings"]["backend"] == "tpu"
        assert config["check"]["tolerance_nats"] > 0


def test_paths_hold_only_allowed_file_names(manifest, root):
    allowed = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in manifest["paths"]:
        for folder, dirs, files in os.walk(os.path.join(root, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                rel = os.path.relpath(os.path.join(folder, name), root)
                assert allowed.match(rel), rel


def test_peaks_are_keyed_by_device_kind():
    peaks = read_json(os.path.join(REPO, "benchmark", "peaks.json"))
    assert peaks["TPU v5 lite"]["flops_per_s"] == 197e12
    assert peaks["TPU v5 lite"]["bytes_per_s"] == 819e9


def test_shipped_mlp_compose_matches_the_container_files():
    import yaml

    config = read_json(os.path.join(REPO, "benchmark", "configs",
                                    "mlp-compose.json"))
    with open(os.path.join(REPO, "container", "config",
                           "detector_config.yaml"), encoding="utf-8") as fh:
        shipped = yaml.safe_load(fh)
    assert (config["stages"]["detector"]["component"]["detectors"]
            == shipped["detectors"])
    with open(os.path.join(REPO, "container", "config",
                           "audit_templates.txt"), encoding="utf-8") as fh:
        assert config["traffic_source"]["template"] == fh.read().strip()
    for stage in ("parser", "detector"):
        with open(os.path.join(REPO, "container", "config",
                               f"{stage}_settings.yaml"),
                  encoding="utf-8") as fh:
            shipped = yaml.safe_load(fh)
        ours = config["stages"][stage]["settings"]
        for key in ("component_type", "component_name", "engine_buffer_size",
                    "engine_batch_size", "engine_frame_batch", "log_to_file"):
            assert ours[key] == shipped[key], (stage, key)
