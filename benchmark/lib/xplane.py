"""Reduction of a ``jax.profiler`` capture (``*.xplane.pb``) to numbers.

    JAX_PLATFORMS=cpu python benchmark/lib/xplane.py <capture_dir> <out.json>

Two steps, so that the second can be checked on a small recorded trace:

* ``load`` reads the capture with ``jax.profiler.ProfileData`` into plain
  lists: every plane, its lines, and each event as ``[name, start_ns,
  duration_ns]``;
* ``reduce`` turns those lists into the device's busy time, the captured
  window, the operations that took most time, the longest idle gaps and the
  executions of each XLA module.

A device plane is one whose name starts with ``/device:TPU:``. On it the
line ``XLA Ops`` holds one event per operation run and ``XLA Modules`` one
per program execution (one scoring call). The traced window is the stretch
the device planes cover, first operation's start to last operation's end:
the host's lines start up to a second and a half earlier, while the
profiler itself starts up, and that stretch says nothing of the program.
The program emits no host annotation yet, so an idle gap carries no cause:
it is named ``unattributed``.
"""
from __future__ import annotations

import glob
import json
import os
import statistics
import sys
from typing import Dict, List

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10
NAME_CHARS = 96     # an op's name is its whole HLO line: keep its head


def load(capture_dir: str) -> dict:
    import jax

    paths = sorted(glob.glob(os.path.join(capture_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {capture_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name, "events": [
                [event.name, float(event.start_ns), float(event.duration_ns)]
                for event in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def union_ns(intervals: List[tuple]) -> float:
    """Total length covered by ``(start, end)`` intervals."""
    covered, reach = 0.0, None
    for start, end in sorted(intervals):
        if reach is None or start > reach:
            covered += end - start
            reach = end
        elif end > reach:
            covered += end - reach
            reach = end
    return covered


def gaps_ns(intervals: List[tuple], lo: float, hi: float) -> List[float]:
    """Lengths of the stretches of ``[lo, hi]`` that no interval covers."""
    gaps, reach = [], lo
    for start, end in sorted(intervals):
        if start > reach:
            gaps.append(start - reach)
        reach = max(reach, end)
    if hi > reach:
        gaps.append(hi - reach)
    return gaps


def reduce(trace: dict) -> dict:
    inventory = [[plane["name"], line["name"], len(line["events"])]
                 for plane in trace["planes"] for line in plane["lines"]]
    devices = [plane for plane in trace["planes"]
               if plane["name"].startswith(DEVICE_PREFIX)]
    spans = [(ev[1], ev[1] + ev[2]) for plane in devices
             for line in plane["lines"] if line["name"] == OPS_LINE
             for ev in line["events"]]
    if not spans:
        return {"inventory": inventory, "devices": 0, "busy_s": 0.0}
    lo = min(start for start, _ in spans)
    hi = max(end for _, end in spans)
    busy, op_seconds, gaps = [], {}, []
    modules: Dict[str, List[float]] = {}
    for plane in devices:
        ops = [ev for line in plane["lines"] if line["name"] == OPS_LINE
               for ev in line["events"]]
        intervals = [(ev[1], ev[1] + ev[2]) for ev in ops]
        busy.append(union_ns(intervals) / 1e9)
        for name, _, duration in ops:
            name = name[:NAME_CHARS]
            op_seconds[name] = op_seconds.get(name, 0.0) + duration / 1e9
        gaps.extend(gap / 1e9 for gap in gaps_ns(intervals, lo, hi))
        for line in plane["lines"]:
            if line["name"] == MODULES_LINE:
                for name, _, duration in line["events"]:
                    modules.setdefault(name, []).append(duration / 1e9)
    top_ops = sorted(op_seconds.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "inventory": inventory,
        "devices": len(devices),
        "window_s": (hi - lo) / 1e9,
        "busy_s": (sum(busy) / len(busy)) if busy else 0.0,
        "device_ops": [[name, seconds] for name, seconds in top_ops],
        "idle_gaps": [["unattributed", gap]
                      for gap in sorted(gaps, reverse=True)[:TOP]],
        "modules": {name: _module_stats(durs)
                    for name, durs in modules.items()},
    }


def _module_stats(durations: List[float]) -> dict:
    """An execution that the capture's edge cut short is not a whole call:
    ``whole_*`` leave out executions under half the median's length."""
    median = statistics.median(durations)
    whole = [d for d in durations if d >= 0.5 * median]
    return {"count": len(durations), "total_s": sum(durations),
            "median_s": median, "whole_count": len(whole),
            "whole_total_s": sum(whole)}


def main(capture_dir: str, out_path: str) -> int:
    result = reduce(load(capture_dir))
    with open(out_path, "w", encoding="utf-8") as fh:
        json.dump(result, fh)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], sys.argv[2]))
