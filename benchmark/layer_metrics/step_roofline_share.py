"""Reader of ``step_roofline_share``: least time for the scoring calls in the
capture over their device time.

The calls and their buckets are ``lib/calls.py``'s; where modules and buckets
do not pair up, nothing is reported. Calls that the capture's edge cut
short are left out. Operations and bytes come from
``benchmark/flops/<model>.py``, the peaks from ``benchmark/peaks.json``."""
from __future__ import annotations

import importlib
from typing import Optional

from benchmark.lib.calls import scoring_calls


def read(ctx: dict) -> Optional[float]:
    calls = scoring_calls(ctx)
    if not calls:
        return None
    peak = ctx["peak"]
    flops = importlib.import_module(
        f"benchmark.flops.{ctx['scorer']['model']}")
    least = spent = 0.0
    for _, stats, bucket in calls:
        ops, nbytes = flops.ops_and_bytes(ctx["scorer"], bucket)
        least += stats["whole_count"] * max(ops / peak["flops_per_s"],
                                            nbytes / peak["bytes_per_s"])
        spent += stats["whole_total_s"]
    return 100.0 * least / spent if spent > 0 else None
