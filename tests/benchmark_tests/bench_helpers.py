"""Helpers for the benchmark's tests: a temporary copy of the benchmark's
data files with a tiny configuration added, by files and manifest entries
only — which is also how a later PR adds one."""
from __future__ import annotations

import copy
import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def read_json(path):
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def write_json(path, data):
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(data, fh, indent=1)


# Where a per-layer metric is due is data, and a PR adds it with a file of
# its own: a cell's file (``benchmark/cells/<cell>.json``) names under
# ``family_metrics`` the metrics that read its scorer family's scopes,
# kernels or counters. A metric that no cell's file names is generic: it
# reads the served path or the trace, holds for any scorer and lists every
# cell of the manifest, in the manifest's order. Nothing here counts
# configurations, cells or metrics, or knows a family by its name.
def entry_of(listed: dict, group: str, name: str) -> dict:
    """The one entry of ``listed[group]`` called ``name``: an entry is taken
    by its name, never by its place or by how many there are."""
    (entry,) = [e for e in listed[group] if e["name"] == name]
    return entry


def family_metrics_of(root: str, cell: str) -> list:
    """The family metrics ``cell``'s own file names (none: a cell that
    reports the generic metrics alone)."""
    return read_json(os.path.join(root, "benchmark", "cells",
                                  cell + ".json")).get("family_metrics", [])


def family_metrics(root: str, listed: dict) -> set:
    """Every metric that some cell of the manifest names as its family's."""
    return {name for w in listed["workloads"]
            for name in family_metrics_of(root, w["name"])}


def cells_due(root: str, listed: dict, metric: str) -> list:
    """The cells ``metric`` has to list, in the manifest's order: the cells
    whose files name it or, where none does, every cell."""
    cells = [w["name"] for w in listed["workloads"]]
    if metric not in family_metrics(root, listed):
        return cells
    return [c for c in cells if metric in family_metrics_of(root, c)]


def metrics_due(root: str, listed: dict, cell: str) -> set:
    """The per-layer metrics ``cell`` has to report: the generic ones and
    those its own file names."""
    family = family_metrics(root, listed)
    return ({m["name"] for m in listed["per_layer"]
             if m["name"] not in family}
            | set(family_metrics_of(root, cell)))


def tiny_config(base: dict, name: str, model: str, dtype: str,
                reduced=None) -> dict:
    """``base`` cut to a size the CPU holds; the test sets ``backend: cpu``
    itself — the command has no such switch. ``reduced`` is a ``cut`` object
    (``{key: {"published", "here", "why"}}``): the file then lists its keys
    under ``reduced``, as a configuration cut to a chip's share does."""
    config = copy.deepcopy(base)
    config["name"] = name
    if reduced:
        config["reduced"] = list(reduced)
        config["cut"] = copy.deepcopy(reduced)
    config["traffic_source"].update(pool_lines=2048, train_lines=256)
    config["warmup_buckets"] = [256]
    config["check"].update(tolerance_nats=0.05, rms_limit_nats=0.05,
                           normal_sample=16)
    config["stages"]["detector"]["settings"]["backend"] = "cpu"
    (scorer,) = config["stages"]["detector"]["component"][
        "detectors"].values()
    scorer.update(model=model, vocab_size=2048, dim=32, seq_len=32,
                  max_batch=256, data_use_training=256, dtype=dtype)
    if model == "logbert":
        scorer.update(depth=1, heads=2)
    return config


def temp_root(tmp_path, config_name="logbert-256x4", model="logbert",
              traffic="saturate", rate=6000, dtype="float32",
              metric=None, new_traffic=None, reduced=None,
              like=None) -> tuple:
    """A copy of the manifest and the data files under ``tmp_path`` with one
    tiny configuration, one cell on it and (optionally) one traffic mix and
    one per-layer metric added. No file that was there is edited. Returns
    (root, cell name). The cell joins every list that holds the cell
    ``like`` — a cell of its family, whatever that cell's traffic — or,
    without ``like``, every list that holds a cell of ``traffic``.
    ``new_traffic`` is a traffic file's content under a new name.
    ``reduced`` is the configuration's ``cut`` object (``tiny_config``)."""
    root = str(tmp_path)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    bench = os.path.join(root, "benchmark")
    os.makedirs(bench)
    for sub in ("configs", "traffic", "cells", "layer_metrics"):
        shutil.copytree(os.path.join(REPO, "benchmark", sub),
                        os.path.join(bench, sub))
    shutil.copy(os.path.join(REPO, "benchmark", "peaks.json"), bench)
    manifest = read_json(os.path.join(root, "BENCHMARK.json"))
    name = f"tiny-{model}"
    base = read_json(os.path.join(bench, "configs", config_name + ".json"))
    write_json(os.path.join(bench, "configs", name + ".json"),
               tiny_config(base, name, model, dtype, reduced))
    manifest["configs"].append({
        "name": name, "source": base["source"],
        "reduced": list(reduced or []),
        "file": f"benchmark/configs/{name}.json", "why": "test"})
    mix = traffic
    if new_traffic is not None:
        mix = new_traffic["name"]
        write_json(os.path.join(bench, "traffic", mix + ".json"),
                   new_traffic)
    cell = f"{name}.{mix}"
    cell_file = {"name": cell, "config": name, "traffic": mix,
                 "rate_lines_per_s": rate}
    if like and family_metrics_of(root, like):
        cell_file["family_metrics"] = family_metrics_of(root, like)
    write_json(os.path.join(bench, "cells", cell + ".json"), cell_file)
    manifest["workloads"].append({"name": cell, "config": name,
                                  "traffic": mix, "chips": 1,
                                  "why": "test"})
    for group in ("end_to_end", "per_layer"):
        for entry in manifest[group]:
            listed = entry.get("workloads")
            if listed and (like in listed if like else any(
                    w.endswith("." + traffic) for w in listed)):
                listed.append(cell)
    if metric is not None:
        write_json(os.path.join(bench, "layer_metrics",
                                metric["name"] + ".json"), metric["file"])
        manifest["per_layer"].append(dict(metric["entry"],
                                          workloads=[cell]))
    write_json(os.path.join(root, "BENCHMARK.json"), manifest)
    return root, cell


ROOM_CUT = {
    "depth": {"published": 48, "here": 1,
              "why": "the further layers would lie on further chips"},
    "vocab_size": {"published": 128256, "here": 2048,
                   "why": "this chip's eighth of the vocabulary, and less"},
}
ROOM_METRIC = {
    "name": "ffn_share_of_call",
    "file": {"name": "ffn_share_of_call", "layer": "kernels", "unit": "%",
             "moves": "alert_p50_ms", "kind": "trace",
             "reducer": "scope_share", "scopes": ["layer*/ffn"]},
    "entry": {"name": "ffn_share_of_call", "unit": "%", "better": "lower",
              "source": "device_trace", "layer": "kernels",
              "moves": "alert_p50_ms"},
}


ROOM_TRAFFIC = ("steady", "steady64")


def room_root(tmp_path, traffic="steady") -> tuple:
    """What the next ``model_config`` PR does, rehearsed: a further
    configuration cut to a chip's share (``reduced`` non-empty, with its
    ``cut``), its one cell ``<config>.<traffic>`` — of either traffic mix
    the manifest's cells offer — appended to the generic per-layer lists
    (those that hold ``logbert-256x4``'s cell, whose family it shares), and
    one scope metric that is a data file only. Returns (root, cell name);
    every manifest test has to pass on the root."""
    root, cell = temp_root(tmp_path, model="logbert", traffic=traffic,
                           rate=4000, metric=ROOM_METRIC, reduced=ROOM_CUT,
                           like="logbert-256x4.steady")
    path = os.path.join(root, "benchmark", "configs", "tiny-logbert.json")
    config = read_json(path)
    # the file as the PR would commit it asks for the chip; a test that runs
    # the cell on the CPU sets ``backend: cpu`` itself (``tiny_config``)
    config["stages"]["detector"]["settings"]["backend"] = "tpu"
    write_json(path, config)
    return root, cell
