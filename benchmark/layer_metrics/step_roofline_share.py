"""Reader of ``step_roofline_share``: least time for the scoring calls in the
capture over their device time.

A scoring call is one execution of an XLA module whose name holds ``score``;
the modules of different buckets are told apart by their duration, matched in
order to the buckets the detector dispatched during the capture. Where the
two do not pair up, nothing is reported. Calls that the capture's edge cut
short are left out. Operations and bytes come from
``benchmark/flops/<model>.py``, the peaks from ``benchmark/peaks.json``."""
from __future__ import annotations

import importlib
from typing import Optional


def read(ctx: dict) -> Optional[float]:
    trace = ctx.get("trace")
    if not trace or not trace.get("modules"):
        return None
    groups = sorted(((g["median_s"], g["whole_count"], g["whole_total_s"])
                     for name, g in trace["modules"].items()
                     if "score" in name), key=lambda g: g[0])
    buckets = sorted(ctx.get("capture_buckets") or [])
    if not groups or len(groups) != len(buckets):
        return None
    peak = ctx["peak"]
    flops = importlib.import_module(
        f"benchmark.flops.{ctx['scorer']['model']}")
    least = spent = 0.0
    for (_, count, total_s), bucket in zip(groups, buckets):
        ops, nbytes = flops.ops_and_bytes(ctx["scorer"], bucket)
        least += count * max(ops / peak["flops_per_s"],
                             nbytes / peak["bytes_per_s"])
        spent += total_s
    return 100.0 * least / spent if spent > 0 else None
