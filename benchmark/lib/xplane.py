"""Reduction of a ``jax.profiler`` capture (``*.xplane.pb``) to numbers.

    JAX_PLATFORMS=cpu python benchmark/lib/xplane.py <capture_dir> <out.json>

Two steps, so that the second can be checked on a small recorded trace:

* ``load`` reads the capture with ``jax.profiler.ProfileData`` into plain
  lists: every plane, its lines, and each event as ``[name, start_ns,
  duration_ns]``. ``ProfileData`` exposes no event *metadata*, and that is
  where an operation's name stack (the ``tf_op`` statistic, which carries
  the program's ``jax.named_scope`` names) and its ``hlo_category`` live. So
  where ``xplane_pb2`` can be imported the same file is read a second time
  through it and each event gains two entries, ``[…, scope, category]``. The
  times stay ``ProfileData``'s, so what was read before reads the same;
* ``reduce`` turns those lists into the device's busy time, the captured
  window, the operations that took most time (control-flow wrappers —
  ``while``, ``conditional`` — are left out: they hold their bodies'
  operations, which are listed themselves), the longest idle gaps (of
  ``MIN_GAP_S`` and more: what lies between two operations of one call is
  no gap anyone waits out), the executions of each XLA module
  (``modules``), the device time of every custom call by kernel name and
  module (``kernels``: seconds and count, within whole executions) and —
  where the events carry scopes — device self time by scope, over
  everything (``scopes``) and within the whole executions of each module
  (``module_scopes``). Whole means at least
  ``WHOLE`` of the module's median length: a call cut anywhere holds some
  scopes and not others (``modules``' own ``whole_*`` keep their older rule,
  half the median, so that what reads them reads as before).

A device plane is one whose name starts with ``/device:TPU:``. On it the
line ``XLA Ops`` holds one event per operation run and ``XLA Modules`` one
per program execution (one scoring call). The traced window is the stretch
the device planes cover, first operation's start to last operation's end:
the host's lines start up to a second and a half earlier, while the
profiler itself starts up, and that stretch says nothing of the program.

An idle gap is named by the program's host annotation (``dm.recv_wait`` …,
events of the host planes whose name starts with ``dm.``) that covers most
of it, and ``unattributed`` where none covers half.
"""
from __future__ import annotations

import bisect
import glob
import importlib
import json
import os
import re
import statistics
import sys
from typing import Dict, List, Optional

DEVICE_PREFIX = "/device:TPU:"
HOST_PREFIX = "/host:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ANNOTATION_PREFIX = "dm."
NO_SCOPE = "no scope"
TOP = 10
NAME_CHARS = 96     # an op's name is its whole HLO line: keep its head
WHOLE = 0.9         # of the median: an execution the capture did not cut
MIN_GAP_S = 1e-3    # a shorter idle stretch is not listed among the gaps
XPLANE_PB2 = ("tensorflow.tsl.profiler.protobuf.xplane_pb2",
              "tsl.profiler.protobuf.xplane_pb2",
              "xprof.protobuf.xplane_pb2")
_WRAPPER = re.compile(r"^(jit|pjit|pmap|xmap|shard_map)\(.*\)$")
_KERNEL = re.compile(r"^%?([^\s=]+?)(?:\.\d+)? = .*?\bcustom-call\(")
_CONTROL_FLOW = re.compile(r"^%?[^\s=]+ = .*?\b(?:while|conditional)\(")


def xplane_pb2():
    """The XSpace protobuf's module, or ``None`` where nothing installed
    carries it: scopes and kernels' categories are then left out."""
    for name in XPLANE_PB2:
        try:
            return importlib.import_module(name)
        except Exception:  # noqa: BLE001 — an absent or broken optional import
            continue
    return None


def scope_of(tf_op: str) -> str:
    """The scope path of an operation's name stack: ``jit(f)/a/b/dot_general:``
    is under ``a/b``. The leading transform wrappers and the primitive at the
    end are not scopes; of a fused operation's several stacks the first."""
    stack = tf_op.split(";")[0].rstrip(":").split("/")[:-1]
    while stack and _WRAPPER.match(stack[0]):
        stack.pop(0)
    return "/".join(stack) or NO_SCOPE


def _metadata(path: str) -> Optional[list]:
    """``[[(scope, category) per event] per line] per plane``, in the file's
    order, from the events' metadata; ``None`` without ``xplane_pb2``."""
    pb2 = xplane_pb2()
    if pb2 is None:
        return None
    space = pb2.XSpace()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    planes = []
    for plane in space.planes:
        stat_names = {key: meta.name
                      for key, meta in plane.stat_metadata.items()}
        known: Dict[int, tuple] = {}

        def describe(metadata_id: int) -> tuple:
            if metadata_id not in known:
                stats = {stat_names.get(stat.metadata_id): stat.str_value
                         for stat in
                         plane.event_metadata[metadata_id].stats}
                tf_op = stats.get("tf_op")
                known[metadata_id] = (scope_of(tf_op) if tf_op else NO_SCOPE,
                                      stats.get("hlo_category") or "")
            return known[metadata_id]

        planes.append([[describe(event.metadata_id) for event in line.events]
                       for line in plane.lines])
    return planes


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
    extra = _metadata(paths[-1])
    if extra is not None and _same_shape(planes, extra):
        for plane, plane_extra in zip(planes, extra):
            for line, line_extra in zip(plane["lines"], plane_extra):
                for event, more in zip(line["events"], line_extra):
                    event.extend(more)
    return {"planes": planes}


def _same_shape(planes: list, extra: list) -> bool:
    """Both readers walk the same file in the same order; where they do not
    agree on how many events each line holds, nothing is joined."""
    return (len(planes) == len(extra) and all(
        [len(line["events"]) for line in plane["lines"]]
        == [len(line) for line in plane_extra]
        for plane, plane_extra in zip(planes, extra)))


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


def gap_spans_ns(intervals: List[tuple], lo: float, hi: float) -> List[tuple]:
    """The ``(start, end)`` stretches of ``[lo, hi]`` no interval covers."""
    gaps, reach = [], lo
    for start, end in sorted(intervals):
        if start > reach:
            gaps.append((reach, start))
        reach = max(reach, end)
    if hi > reach:
        gaps.append((reach, hi))
    return gaps


def gaps_ns(intervals: List[tuple], lo: float, hi: float) -> List[float]:
    """Lengths of the stretches of ``[lo, hi]`` that no interval covers."""
    return [end - start for start, end in gap_spans_ns(intervals, lo, hi)]


def self_ns(events: list) -> List[float]:
    """Each event's duration less what the events nested in it cover (a
    ``%while`` holds its body's operations), in the order given."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    own = [float(event[2]) for event in events]
    open_: List[int] = []
    for i in order:
        start, end = events[i][1], events[i][1] + events[i][2]
        while open_ and events[open_[-1]][1] + events[open_[-1]][2] <= start:
            open_.pop()
        if open_:
            parent = open_[-1]
            parent_end = events[parent][1] + events[parent][2]
            own[parent] -= max(0.0, min(end, parent_end) - start)
        open_.append(i)
    return own


def kernel_of(event: list) -> Optional[str]:
    """The kernel's name where the event is a custom call: ``%lse_pallas.1 =
    … custom-call(…`` is ``lse_pallas``."""
    if len(event) > 4 and event[4] and event[4] != "custom-call":
        return None
    found = _KERNEL.match(event[0])
    return found[1] if found else None


def wraps_others(event: list) -> bool:
    """Whether the event is a control-flow wrapper (``%while.3 = … while(…``,
    ``%conditional.1 = … conditional(…``): its time is its body's, whose
    operations have events of their own."""
    if len(event) > 4 and event[4] in ("while", "conditional"):
        return True
    return bool(_CONTROL_FLOW.match(event[0]))


def annotations(trace: dict) -> Dict[str, List[tuple]]:
    """``{name: [(start, end)]}`` of the program's host annotations; a name
    ends where its arguments begin (``dm.upload#batch=7,…#``)."""
    found: Dict[str, List[tuple]] = {}
    for plane in trace["planes"]:
        if not plane["name"].startswith(HOST_PREFIX):
            continue
        for line in plane["lines"]:
            for event in line["events"]:
                if event[0].startswith(ANNOTATION_PREFIX):
                    found.setdefault(event[0].split("#")[0], []).append(
                        (event[1], event[1] + event[2]))
    return found


def cover(gap: tuple, by_name: Dict[str, List[tuple]]) -> Dict[str, float]:
    """The share of ``gap`` that each annotation's events cover."""
    lo, hi = gap
    shares = {}
    for name, spans in by_name.items():
        inside = [(max(lo, start), min(hi, end)) for start, end in spans
                  if end > lo and start < hi]
        if inside:
            shares[name] = union_ns(inside) / (hi - lo)
    return shares


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
        for line in plane["lines"]:
            if line["name"] == MODULES_LINE:
                for event in line["events"]:
                    modules.setdefault(event[0], []).append(event[2] / 1e9)
    stats = {name: _module_stats(durs) for name, durs in modules.items()}
    scopes: Dict[str, dict] = {}
    module_scopes: Dict[str, Dict[str, float]] = {}
    kernels: Dict[str, dict] = {}
    for plane in devices:
        ops = [ev for line in plane["lines"] if line["name"] == OPS_LINE
               for ev in line["events"]]
        intervals = [(ev[1], ev[1] + ev[2]) for ev in ops]
        busy.append(union_ns(intervals) / 1e9)
        gaps.extend(gap_spans_ns(intervals, lo, hi))
        # whole executions only: what the capture's edge left of a call
        # holds one end of it and not the other
        executions = sorted(
            (ev[1], ev[1] + ev[2], ev[0]) for line in plane["lines"]
            if line["name"] == MODULES_LINE for ev in line["events"]
            if ev[2] / 1e9 >= WHOLE * stats[ev[0]]["median_s"])
        starts = [start for start, _, _ in executions]
        for event, own in zip(ops, self_ns(ops)):
            scope = event[3] if len(event) > 3 else None
            name = (event[0] if scope is None
                    else f"{scope}: {event[0]}")[:NAME_CHARS]
            if not wraps_others(event):
                op_seconds[name] = op_seconds.get(name, 0.0) + event[2] / 1e9
            if scope is not None:
                entry = scopes.setdefault(scope, {"self_s": 0.0, "events": 0})
                entry["self_s"] += own / 1e9
                entry["events"] += 1
            k = bisect.bisect_right(starts, event[1]) - 1
            if k < 0 or event[1] >= executions[k][1]:
                continue
            module = executions[k][2]
            if scope is not None:
                per = module_scopes.setdefault(module, {})
                per[scope] = per.get(scope, 0.0) + own / 1e9
            kernel = kernel_of(event)
            if kernel:
                per = kernels.setdefault(kernel, {}).setdefault(
                    module, {"seconds": 0.0, "count": 0})
                per["seconds"] += event[2] / 1e9
                per["count"] += 1
    by_name = annotations(trace)
    longest = sorted((gap for gap in gaps
                      if gap[1] - gap[0] >= MIN_GAP_S * 1e9),
                     key=lambda gap: gap[0] - gap[1])[:TOP]
    covers = [cover(gap, by_name) for gap in longest]
    top_ops = sorted(op_seconds.items(), key=lambda kv: -kv[1])[:TOP]
    out = {
        "inventory": inventory,
        "devices": len(devices),
        "window_s": (hi - lo) / 1e9,
        "busy_s": (sum(busy) / len(busy)) if busy else 0.0,
        "device_ops": [[name, seconds] for name, seconds in top_ops],
        "idle_gaps": [[_cause(shares), (end - start) / 1e9]
                      for (start, end), shares in zip(longest, covers)],
        "idle_gap_cover": covers,
        "modules": stats,
        "kernels": kernels,
    }
    if scopes:
        out["scopes"] = scopes
        out["module_scopes"] = module_scopes
    return out


def _cause(shares: Dict[str, float]) -> str:
    name = max(shares, key=shares.get, default="")
    return name if name and shares[name] >= 0.5 else "unattributed"


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
