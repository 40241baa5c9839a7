"""``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, one traffic mix, one cell or
one per-layer metric is a file of its own, found by its name:

    <bench>/configs/<config>.json        (the manifest gives this path)
    <bench>/traffic/<traffic>.json
    <bench>/cells/<cell>.json
    <bench>/layer_metrics/<metric>.json
    <bench>/reference/<model>.py         score(params, tokens, scorer, lower=None)
    <bench>/flops/<model>.py             ops_and_bytes(scorer, rows)

so a later PR adds a configuration, a cell or a metric by adding files and
manifest entries. Two rules go with that (``tests/benchmark_tests`` hold a
temporary copy of the benchmark to them, ``test_bench_room.py``):

* **``reduced``.** A configuration's manifest entry and its file carry the
  same ``reduced``: a list of distinct, non-empty keys, empty where the
  configuration runs as published. Where it is not empty the file states the
  ``deployment`` whose share this chip holds and a ``cut`` object with one
  entry per key of ``reduced``, ``{"published": …, "here": …, "why": …}``,
  so that a cut is always written down beside the published value.
* **The generic per-layer metrics follow a new cell.** Every per-layer entry
  keeps a ``workloads`` list (the driver wants one). All but the scope and
  kernel metrics of one model read the served path or the trace and hold for
  any scorer (``step_roofline_share`` needs only ``flops/<model>.py``): a PR
  that adds a cell appends the cell's name to each of those lists, and every
  cell has to report at least one metric of every ``layer`` the manifest
  names. A scope's or a kernel's share is a data file for the readers
  ``layer_metrics/scope_share.py`` and ``kernel_roofline_share.py``.
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


def reduced_breaches(entry: dict, config: dict) -> list:
    """Where a configuration's manifest ``entry`` and its file break the
    rule on ``reduced`` (the module's docstring); empty where they keep it."""
    reduced = config.get("reduced")
    if (not isinstance(reduced, list) or len(set(reduced)) != len(reduced)
            or not all(isinstance(key, str) and key for key in reduced)):
        return [f"reduced is not a list of distinct keys: {reduced!r}"]
    found = []
    if entry["reduced"] != reduced:
        found.append(f"the manifest lists {entry['reduced']!r}, the file "
                     f"{reduced!r}")
    if reduced and not config.get("deployment"):
        found.append("a cut configuration states no deployment")
    cut = config.get("cut", {})
    if reduced and sorted(cut) != sorted(reduced):
        found.append(f"cut has entries for {sorted(cut)!r}, reduced lists "
                     f"{sorted(reduced)!r}")
    for key, stated in cut.items():
        if (not isinstance(stated, dict)
                or set(stated) != {"published", "here", "why"}
                or not stated["why"]):
            found.append(f"cut[{key!r}] is not published, here and why")
    return found


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
    config = read_json(config_file)
    breaches = reduced_breaches(configs[entry["config"]], config)
    if breaches:
        raise ValueError(f"configuration {entry['config']!r}: "
                         + "; ".join(breaches))
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
        "config": config,
        "traffic": read_json(os.path.join(
            bench, "traffic", entry["traffic"] + ".json")),
        "cell": read_json(os.path.join(bench, "cells", workload + ".json")),
        "end_to_end": end_to_end,
        "per_layer": per_layer,
        "peaks": read_json(os.path.join(bench, "peaks.json")),
    }
