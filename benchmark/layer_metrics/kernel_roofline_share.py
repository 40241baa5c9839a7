"""Reader of a kernel's share of its roofline: the least time the device
could take for the named kernel's calls over their device time, in %.

The metric's file names the ``kernel`` (a custom call's name in the trace,
``lse_pallas`` for ``%lse_pallas.1``) and ``least``, a function of
``benchmark/flops/<model>.py`` that gives the operations and bytes the
kernel's algorithm needs at a bucket: ``least(scorer, rows) -> (ops,
bytes)``. The kernel's events are counted within whole executions of the
scoring calls' modules, each paired with its bucket (``lib/calls.py``); the
peaks are ``benchmark/peaks.json``'s. A kernel that did not run in the
capture, or calls that do not pair up with buckets, report nothing."""
from __future__ import annotations

import importlib
from typing import Optional

from benchmark.lib.calls import scoring_calls


def read(ctx: dict, spec: dict) -> Optional[float]:
    calls = scoring_calls(ctx)
    runs = ((ctx.get("trace") or {}).get("kernels") or {}).get(spec["kernel"])
    if not calls or not runs:
        return None
    peak = ctx["peak"]
    flops = importlib.import_module(
        f"benchmark.flops.{ctx['scorer']['model']}")
    least_of = getattr(flops, spec["least"])
    least = spent = 0.0
    for module, _, bucket in calls:
        if module not in runs:
            continue
        ops, nbytes = least_of(ctx["scorer"], bucket)
        least += runs[module]["count"] * max(ops / peak["flops_per_s"],
                                             nbytes / peak["bytes_per_s"])
        spent += runs[module]["seconds"]
    return 100.0 * least / spent if spent > 0 else None
