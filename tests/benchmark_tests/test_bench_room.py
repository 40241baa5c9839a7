"""The manifest for any number of configurations, cells and metrics, and the
room for a further configuration, rehearsed on a temporary copy of the
benchmark: a configuration cut to a chip's share (``reduced`` non-empty, its
``cut`` beside it), its one cell — of either traffic mix the manifest's
cells offer — on the generic per-layer lists and a scope metric that is a
data file only, all by new files and manifest entries, no file that exists
edited. ``test_bench_manifest.py`` runs every manifest test on the same
copies.

What these tests say of the manifest they say of an entry taken by its name
and of lists derived from data a PR adds with a file of its own: a cell's
file names its family's metrics (``family_metrics``), and a metric that no
cell names is generic (``bench_helpers.cells_due``). No statement counts
configurations, cells or metrics, knows an entry by its place or a family by
its name. Until PR 40 four files restated each other here with such counts,
and each new configuration broke the ones before it."""
import bench_helpers  # noqa: F401  (puts the repo root on sys.path)
import copy
import os
import subprocess

import pytest

from bench_helpers import (REPO, ROOM_CUT, ROOM_METRIC, ROOM_TRAFFIC,
                           cells_due, entry_of, family_metrics,
                           family_metrics_of, metrics_due, read_json,
                           room_root, temp_root, write_json)
from benchmark.lib import layers, manifest

DATA_DIRS = ("configs", "traffic", "cells", "layer_metrics")
LISTED = read_json(os.path.join(REPO, "BENCHMARK.json"))
CONFIGS = [c["name"] for c in LISTED["configs"]]
CELLS = [w["name"] for w in LISTED["workloads"]]
FAMILY = sorted(family_metrics(REPO, LISTED))
# What no PR but a ``benchmark`` PR may edit: the configurations' files
# (sizes, cuts, limits of ``correct``) and the plain reference copies. The
# parent commit is the record of what they were: ``git diff`` against it
# guards them, file by file. A PR that adds a configuration or a reference
# adds a file, which no diff of a file that was there shows. What a
# ``benchmark`` PR may edit besides: ``cells/*.json`` (rates, ``why``,
# ``measured``) and ``layer_metrics/*.json`` (a metric's parameters).
GUARDED = sorted(
    f"benchmark/{sub}/{name}" for sub, end in (("configs", ".json"),
                                               ("reference", ".py"))
    for name in os.listdir(os.path.join(REPO, "benchmark", sub))
    if name.endswith(end))


@pytest.fixture(scope="module")
def listed():
    return copy.deepcopy(LISTED)


@pytest.fixture(scope="module")
def room(tmp_path_factory):
    return room_root(tmp_path_factory.mktemp("room"))


@pytest.fixture(scope="module", params=ROOM_TRAFFIC)
def either_room(request, tmp_path_factory):
    """The rehearsal with a cell of either traffic mix."""
    root, cell = room_root(tmp_path_factory.mktemp("room-" + request.param),
                           traffic=request.param)
    assert cell == "tiny-logbert." + request.param
    return root, cell


# -- the manifest as it stands, entry by entry ------------------------------

@pytest.mark.parametrize("name", CONFIGS)
def test_a_configuration_keeps_the_rule_on_reduced_and_has_a_cell(listed,
                                                                  name):
    entry = entry_of(listed, "configs", name)
    file = read_json(os.path.join(REPO, entry["file"]))
    assert file["name"] == name and file["source"] == entry["source"]
    assert manifest.reduced_breaches(entry, file) == []
    assert entry["file"] == f"benchmark/configs/{name}.json"
    assert [c["file"] for c in listed["configs"]].count(entry["file"]) == 1
    cells = [w for w in listed["workloads"] if w["config"] == name]
    assert cells, "a configuration without a cell"
    for cell in cells:
        loaded = manifest.load_cell(REPO, cell["name"])
        assert loaded["config"]["name"] == name
        assert loaded["traffic"]["name"] == cell["traffic"]
        assert loaded["cell"]["name"] == cell["name"]
        assert [m["name"] for m in loaded["end_to_end"]] == [
            "setup_s", "alert_p50_ms"]


def test_logbert_256x4_runs_as_published(listed):
    entry = entry_of(listed, "configs", "logbert-256x4")
    config = read_json(os.path.join(REPO, entry["file"]))
    assert entry["reduced"] == config["reduced"] == []
    assert manifest.reduced_breaches(entry, config) == []
    assert "cut" not in config


@pytest.mark.parametrize("cell", CELLS)
def test_a_cell_reports_what_the_table_gives_and_every_layer(listed, cell):
    """The generic metrics, its family's, no other family's — and with them
    at least one metric of every layer the manifest names."""
    entry = entry_of(listed, "workloads", cell)
    assert cell == f"{entry['config']}.{entry['traffic']}"
    ours = manifest.load_cell(REPO, cell)["per_layer"]
    assert {s["name"] for s in ours} == metrics_due(REPO, listed, cell)
    assert ({s["layer"] for s in ours}
            == {m["layer"] for m in listed["per_layer"]})


@pytest.mark.parametrize("name", FAMILY)
def test_a_familys_metric_lists_the_cells_that_name_it(listed, name):
    entry = entry_of(listed, "per_layer", name)
    assert entry["workloads"] == cells_due(REPO, listed, name)
    assert entry["workloads"], "a metric no cell reports"
    spec = read_json(os.path.join(REPO, "benchmark", "layer_metrics",
                                  name + ".json"))
    assert (spec["layer"], spec["unit"], spec["moves"]) == (
        entry["layer"], entry["unit"], entry["moves"])
    # data for a reader that is there, none of its own
    assert spec["kind"] in ("trace", "prom-delta")
    assert not os.path.exists(os.path.join(
        REPO, "benchmark", "layer_metrics", name + ".py"))
    if spec["kind"] == "trace":
        assert os.path.exists(os.path.join(
            REPO, "benchmark", "layer_metrics", spec["reducer"] + ".py"))


def test_a_generic_metric_lists_every_cell_in_the_manifests_order(listed):
    cells = [w["name"] for w in listed["workloads"]]
    assert len(set(cells)) == len(cells)
    generic = [m for m in listed["per_layer"] if m["name"] not in FAMILY]
    assert generic
    for entry in generic:
        assert entry["workloads"] == cells, entry["name"]
    # every layer is read by some metric that holds for any scorer
    assert ({m["layer"] for m in listed["per_layer"]}
            == {m["layer"] for m in generic})


def test_the_cells_files_name_metrics_that_exist(listed):
    names = {m["name"] for m in listed["per_layer"]}
    for cell in CELLS:
        ours = family_metrics_of(REPO, cell)
        assert len(set(ours)) == len(ours) and set(ours) <= names, cell
    # the scale-free share took the place of the one that scaled by 16
    # held experts (PERF.md section 6, PR 40)
    assert "expert_skew" not in names
    assert not os.path.exists(os.path.join(
        REPO, "benchmark", "layer_metrics", "expert_skew.json"))


@pytest.mark.parametrize("file", GUARDED)
def test_a_configuration_or_reference_is_the_file_the_parent_has(file):
    """``git diff`` against the parent commit shows no edit of a file that
    was there (a new file shows none either). Outside a git checkout there
    is no parent to hold it to."""
    try:
        diff = subprocess.run(
            ["git", "diff", "--name-status", "HEAD", "--", file], cwd=REPO,
            capture_output=True, text=True, timeout=60)
    except (OSError, subprocess.TimeoutExpired):
        pytest.skip("no git")
    if diff.returncode != 0:
        pytest.skip("not a git checkout")
    assert diff.stdout.strip() == "", (
        f"{file} was edited: only a benchmark PR may, and it says why in "
        "PERF.md")


def test_no_configuration_or_reference_of_the_parent_is_gone():
    try:
        diff = subprocess.run(
            ["git", "diff", "--name-status", "--diff-filter=D", "HEAD", "--",
             "benchmark/configs", "benchmark/reference"], cwd=REPO,
            capture_output=True, text=True, timeout=60)
    except (OSError, subprocess.TimeoutExpired):
        pytest.skip("no git")
    if diff.returncode != 0:
        pytest.skip("not a git checkout")
    assert diff.stdout.strip() == ""


# -- the room: a further configuration by additions alone ----------------------

def test_the_harness_takes_the_cut_configuration_and_its_cell(either_room):
    root, cell_name = either_room
    cell = manifest.load_cell(root, cell_name)
    assert cell["config"]["reduced"] == ["depth", "vocab_size"]
    assert cell["config"]["cut"] == ROOM_CUT
    assert cell["config"]["deployment"]
    entry = entry_of(cell["manifest"], "configs", "tiny-logbert")
    assert entry["reduced"] == ["depth", "vocab_size"]
    assert {m["name"] for m in cell["end_to_end"]} == {"setup_s",
                                                       "alert_p50_ms"}


def test_the_generic_per_layer_metrics_follow_the_cell(either_room, listed):
    """Everything a cell of its family reports, the new cell reports too,
    and its own data-only metric besides; no other family's metric lists
    it."""
    root, cell_name = either_room
    ours = {s["name"] for s in
            manifest.load_cell(root, cell_name)["per_layer"]}
    assert ours == (metrics_due(REPO, listed, "logbert-256x4.steady")
                    | {ROOM_METRIC["name"]})
    after = read_json(os.path.join(root, "BENCHMARK.json"))
    assert entry_of(after, "per_layer", ROOM_METRIC["name"])[
        "workloads"] == [cell_name]
    for entry in after["per_layer"]:
        if entry["name"] in FAMILY:
            assert cell_name not in entry["workloads"], entry["name"]
        elif entry["name"] != ROOM_METRIC["name"]:
            assert entry["workloads"][-1] == cell_name, entry["name"]
    # the table holds on the copy as it holds on the repo
    assert ours == metrics_due(root, after, cell_name)


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


def test_no_file_that_was_there_is_edited(either_room, listed):
    """The rehearsal adds a configuration's file, a cell's and a metric's
    and edits none: every data file of the repo reads the same in the copy,
    the manifest's entries stand where they stood, and a per-layer list
    changes only by the new cell appended — to the generic lists and to no
    family's."""
    root, cell_name = either_room
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
    after = read_json(os.path.join(root, "BENCHMARK.json"))
    for key in ("command", "paths", "run_seconds", "end_to_end"):
        assert after[key] == listed[key]
    assert after["configs"][:-1] == listed["configs"]
    assert after["workloads"][:-1] == listed["workloads"]
    # entries are added and lists appended to; nothing else of an entry moves
    assert after["per_layer"][-1]["name"] == ROOM_METRIC["name"]
    assert ([m["name"] for m in after["per_layer"][:-1]]
            == [m["name"] for m in listed["per_layer"]])
    for old, new in zip(listed["per_layer"], after["per_layer"]):
        follows = old["name"] not in FAMILY
        assert new == dict(old, workloads=old["workloads"]
                           + ([cell_name] if follows else [])), old["name"]


def _entry_and_config(room):
    root, _ = room
    entry = entry_of(read_json(os.path.join(root, "BENCHMARK.json")),
                     "configs", "tiny-logbert")
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
