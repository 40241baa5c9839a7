"""``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, one traffic mix, one cell or
one per-layer metric is a file of its own, found by its name:

    <bench>/configs/<config>.json        (the manifest gives this path)
    <bench>/traffic/<traffic>.json
    <bench>/cells/<cell>.json
    <bench>/layer_metrics/<metric>.json

so a later PR adds a cell or a metric by adding files and manifest entries.
"""
from __future__ import annotations

import json
import os

BENCH_DIR = "benchmark"


def read_json(path: str):
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def due(metric: dict, cell: str, otherwise: bool) -> bool:
    """Whether a metric is due in ``cell``: its ``workloads`` key lists the
    cell or, where it has none, ``otherwise``."""
    return (cell in metric["workloads"] if "workloads" in metric
            else otherwise)


def load_cell(root: str, workload: str) -> dict:
    manifest = read_json(os.path.join(root, "BENCHMARK.json"))
    entries = {w["name"]: w for w in manifest["workloads"]}
    if workload not in entries:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(has {sorted(entries)})")
    entry = entries[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    bench = os.path.join(root, BENCH_DIR)
    config_file = os.path.join(root, configs[entry["config"]]["file"])
    end_to_end = [m for m in manifest["end_to_end"]
                  if due(m, workload, True)]
    reported = {m["name"] for m in end_to_end}
    per_layer = []
    for metric in manifest["per_layer"]:
        # without a list of its own, a per-layer metric is due wherever the
        # end-to-end metric it moves is reported
        if due(metric, workload, metric["moves"] in reported):
            spec = read_json(os.path.join(
                bench, "layer_metrics", metric["name"] + ".json"))
            per_layer.append(dict(spec, name=metric["name"],
                                  unit=metric["unit"]))
    return {
        "manifest": manifest,
        "entry": entry,
        "config_file": config_file,
        "config": read_json(config_file),
        "traffic": read_json(os.path.join(
            bench, "traffic", entry["traffic"] + ".json")),
        "cell": read_json(os.path.join(bench, "cells", workload + ".json")),
        "end_to_end": end_to_end,
        "per_layer": per_layer,
        "peaks": read_json(os.path.join(bench, "peaks.json")),
    }
