"""Peak device memory of a run, from two sources that do not overlap.

* What the process holds: ``device_hbm_bytes{kind="in_use"}`` (jax's
  allocator), read once the pipeline has drained, when no executable runs.
* What a running executable needs on top: XLA's buffer assignment for it,
  ``temp_bytes`` in the lines ``lib/stage_main.py`` writes for every
  executable the detector compiles ahead of time. Of those, only the buckets
  the detector dispatched between the ramp's start and the window's end
  count (``detector_bucket_selected_total{path="device"}``): a bucket that
  was compiled or warmed and that the traffic never filled adds nothing.
  A bucket the program compiled inside a jitted call has no line and adds
  nothing either, so the sum can fall short and cannot overshoot.

Where the allocator's own peak at exit is larger (a runtime that counts
scratch memory), that is the peak.
"""
from __future__ import annotations

import json
from typing import Dict, Iterable, List, Tuple

from . import prom


def read_programs(path: str) -> Tuple[List[dict], List[dict]]:
    """``(executables, allocator statistics at exit per device)``; both
    empty where the stage wrote nothing."""
    programs, at_exit = [], []
    try:
        with open(path, encoding="utf-8") as fh:
            for line in fh:
                record = json.loads(line)
                if "allocator_at_exit" in record:
                    at_exit = record["allocator_at_exit"]
                else:
                    programs.append(record)
    except OSError:
        pass
    return programs, at_exit


def dispatched_buckets(before: prom.Series, after: prom.Series) -> List[int]:
    """Device buckets whose dispatch counter rose between two scrapes."""
    return sorted(
        int(dict(labels)["bucket"])
        for (name, labels), value in after.items()
        if name == "detector_bucket_selected_total"
        and dict(labels).get("path") == "device"
        and value > before.get((name, labels), 0.0))


def temp_bytes(programs: Iterable[dict], platform: str,
               bucket: int) -> int:
    """The largest scratch allocation among the recorded executables of
    ``platform`` whose token batch has ``bucket`` rows; 0 where none has."""
    return max((int(p["temp_bytes"]) for p in programs
                if p.get("platform") == platform
                and any(len(shape) == 2 and shape[0] == bucket
                        for shape in p.get("int_args", []))), default=0)


def peak(resident_bytes: float, programs: List[dict], at_exit: List[dict],
         platform: str, buckets: Iterable[int]) -> Dict[str, int]:
    by_bucket = {int(b): temp_bytes(programs, platform, int(b))
                 for b in buckets}
    widest = max(by_bucket, key=by_bucket.get, default=0)
    scratch = by_bucket.get(widest, 0)
    allocator_peak = max((int(s.get("peak_bytes_in_use", 0))
                          for s in at_exit), default=0)
    return {
        "resident_bytes": int(resident_bytes),
        "scratch_bytes": int(scratch),
        "scratch_bucket": int(widest if scratch else 0),
        "allocator_peak_bytes": allocator_peak,
        "peak_bytes": max(int(resident_bytes) + int(scratch),
                          allocator_peak),
    }
