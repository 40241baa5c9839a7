"""The room for a second configuration, rehearsed on a temporary copy of the
benchmark: a configuration cut to a chip's share (``reduced`` non-empty, its
``cut`` beside it), its one cell on the generic per-layer lists and a scope
metric that is a data file only — all by new files and manifest entries, no
file that exists edited. ``test_bench_manifest.py`` runs every manifest test
on the same copy."""
import bench_helpers  # noqa: F401  (puts the repo root on sys.path)
import copy
import os

import pytest

from bench_helpers import (REPO, ROOM_CUT, ROOM_METRIC, read_json, room_root,
                           temp_root, write_json)
from benchmark.lib import layers, manifest

DATA_DIRS = ("configs", "traffic", "cells", "layer_metrics")


@pytest.fixture(scope="module")
def room(tmp_path_factory):
    return room_root(tmp_path_factory.mktemp("room"))


def test_the_harness_takes_the_cut_configuration_and_its_cell(room):
    root, cell_name = room
    assert cell_name == "tiny-logbert.steady"
    cell = manifest.load_cell(root, cell_name)
    assert cell["config"]["reduced"] == ["depth", "vocab_size"]
    assert cell["config"]["cut"] == ROOM_CUT
    assert cell["config"]["deployment"]
    (entry,) = [c for c in cell["manifest"]["configs"]
                if c["name"] == "tiny-logbert"]
    assert entry["reduced"] == ["depth", "vocab_size"]
    assert {m["name"] for m in cell["end_to_end"]} == {"setup_s",
                                                       "alert_p50_ms"}


def test_the_generic_per_layer_metrics_follow_the_cell(room):
    """Everything the admitted cell reports, the new cell reports too, and
    its own data-only metric besides."""
    root, cell_name = room
    ours = {s["name"] for s in
            manifest.load_cell(root, cell_name)["per_layer"]}
    theirs = {s["name"] for s in
              manifest.load_cell(REPO, "logbert-256x4.steady")["per_layer"]}
    assert ours == theirs | {ROOM_METRIC["name"]}
    assert len(theirs) == 21
    listed = {m["name"]: m["workloads"] for m in
              read_json(os.path.join(root, "BENCHMARK.json"))["per_layer"]}
    assert listed[ROOM_METRIC["name"]] == [cell_name]
    assert all("logbert-256x4.steady" in cells or name == ROOM_METRIC["name"]
               for name, cells in listed.items())


def test_a_scope_metric_is_a_data_file(room):
    """The added metric has no code of its own: its file names the generic
    reader and the scopes, and the harness reads it from a reduced trace."""
    root, cell_name = room
    (spec,) = [s for s in manifest.load_cell(root, cell_name)["per_layer"]
               if s["name"] == ROOM_METRIC["name"]]
    assert not os.path.exists(os.path.join(
        REPO, "benchmark", "layer_metrics", spec["name"] + ".py"))
    trace = {"module_scopes": {"jit__score_impl(7)": {
        "Model/blocks_0/layer0/attn/qkv": 0.6,
        "Model/blocks_0/layer0/ffn/mlp_in": 0.25,
        "Model/blocks_0/layer0/ffn": 0.05, "head/nll": 0.1}}}
    assert layers.evaluate(spec, {"trace": trace}) == pytest.approx(30.0)
    assert layers.evaluate(spec, {"trace": {}}) is None


def test_no_file_that_was_there_is_edited(room):
    root, cell_name = room
    added = {"configs": {"tiny-logbert.json"},
             "cells": {cell_name + ".json"},
             "layer_metrics": {ROOM_METRIC["name"] + ".json"},
             "traffic": set()}
    for sub in DATA_DIRS:
        ours = set(os.listdir(os.path.join(root, "benchmark", sub)))
        theirs = set(os.listdir(os.path.join(REPO, "benchmark", sub)))
        assert ours - theirs == added[sub]
        for name in theirs:
            if name.endswith(".json"):
                assert (read_json(os.path.join(root, "benchmark", sub, name))
                        == read_json(os.path.join(REPO, "benchmark", sub,
                                                  name))), name
    before = read_json(os.path.join(REPO, "BENCHMARK.json"))
    after = read_json(os.path.join(root, "BENCHMARK.json"))
    for key in ("command", "paths", "run_seconds", "end_to_end"):
        assert after[key] == before[key]
    assert after["configs"][:-1] == before["configs"]
    assert after["workloads"][:-1] == before["workloads"]
    # entries are added and lists appended to; nothing else of an entry moves
    assert len(after["per_layer"]) == len(before["per_layer"]) + 1
    for old, new in zip(before["per_layer"], after["per_layer"]):
        assert new == dict(old, workloads=old["workloads"] + [cell_name])


def test_logbert_256x4_runs_as_published():
    entry, = read_json(os.path.join(REPO, "BENCHMARK.json"))["configs"]
    config = read_json(os.path.join(REPO, entry["file"]))
    assert entry["reduced"] == config["reduced"] == []
    assert manifest.reduced_breaches(entry, config) == []


def _entry_and_config(room):
    root, _ = room
    (entry,) = [c for c in read_json(os.path.join(root, "BENCHMARK.json"))[
        "configs"] if c["name"] == "tiny-logbert"]
    return copy.deepcopy(entry), read_json(os.path.join(root, entry["file"]))


def test_the_rule_on_reduced_passes_the_cut_configuration(room):
    entry, config = _entry_and_config(room)
    assert manifest.reduced_breaches(entry, config) == []


BREAK = {
    "entry and file disagree":
        lambda entry, config: entry["reduced"].remove("depth"),
    "entry lists nothing":
        lambda entry, config: entry.update(reduced=[]),
    "a cut entry is missing":
        lambda entry, config: config["cut"].pop("vocab_size"),
    "no cut object":
        lambda entry, config: config.pop("cut"),
    "a cut without its published value":
        lambda entry, config: config["cut"]["depth"].pop("published"),
    "a cut without a reason":
        lambda entry, config: config["cut"]["depth"].update(why=""),
    "a key listed twice":
        lambda entry, config: (entry["reduced"].append("depth"),
                               config["reduced"].append("depth")),
    "an empty key":
        lambda entry, config: (entry["reduced"].append(""),
                               config["reduced"].append("")),
    "no deployment":
        lambda entry, config: config.pop("deployment"),
    "reduced is not a list":
        lambda entry, config: config.update(reduced="depth"),
}


@pytest.mark.parametrize("fault", sorted(BREAK))
def test_the_rule_on_reduced_fails(room, fault):
    entry, config = _entry_and_config(room)
    BREAK[fault](entry, config)
    assert manifest.reduced_breaches(entry, config), fault


def test_load_cell_refuses_a_cut_that_is_not_written_down(tmp_path):
    root, cell = temp_root(tmp_path, model="logbert", traffic="steady",
                           reduced=ROOM_CUT)
    manifest.load_cell(root, cell)
    path = os.path.join(root, "benchmark", "configs", "tiny-logbert.json")
    config = read_json(path)
    del config["cut"]["depth"]
    write_json(path, config)
    with pytest.raises(ValueError, match="cut has entries"):
        manifest.load_cell(root, cell)
