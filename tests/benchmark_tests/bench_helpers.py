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
              metric=None, new_traffic=None, reduced=None) -> tuple:
    """A copy of the manifest and the data files under ``tmp_path`` with one
    tiny configuration, one cell on it and (optionally) one traffic mix and
    one per-layer metric added. No file that was there is edited. Returns
    (root, cell name). ``new_traffic`` is a traffic file's content under a
    new name; the cell then reports what the cells of ``traffic`` report.
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
    write_json(os.path.join(bench, "cells", cell + ".json"),
               {"name": cell, "config": name, "traffic": mix,
                "rate_lines_per_s": rate})
    manifest["workloads"].append({"name": cell, "config": name,
                                  "traffic": mix, "chips": 1,
                                  "why": "test"})
    for group in ("end_to_end", "per_layer"):
        for entry in manifest[group]:
            listed = entry.get("workloads")
            if listed and any(w.endswith("." + traffic) for w in listed):
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


def room_root(tmp_path) -> tuple:
    """What the next ``model_config`` PR does, rehearsed: a second
    configuration cut to a chip's share (``reduced`` non-empty, with its
    ``cut``), its one cell ``<config>.steady`` appended to the generic
    per-layer lists, and one scope metric that is a data file only. Returns
    (root, cell name); every manifest test has to pass on the root."""
    root, cell = temp_root(tmp_path, model="logbert", traffic="steady",
                           rate=4000, metric=ROOM_METRIC, reduced=ROOM_CUT)
    path = os.path.join(root, "benchmark", "configs", "tiny-logbert.json")
    config = read_json(path)
    # the file as the PR would commit it asks for the chip; a test that runs
    # the cell on the CPU sets ``backend: cpu`` itself (``tiny_config``)
    config["stages"]["detector"]["settings"]["backend"] = "tpu"
    write_json(path, config)
    return root, cell
