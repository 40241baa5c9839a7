"""The scoring calls of a capture, each with the bucket it scored.

A scoring call is one execution of an XLA module whose name holds ``score``
(``jit__score_impl``); every bucket has an executable of its own, so the
modules of different buckets are told apart by their duration, matched in
order to the buckets the detector dispatched during the capture. Where the
two do not pair up, nothing is returned, and a reader reports nothing."""
from __future__ import annotations

from typing import List, Optional, Tuple


def scoring_calls(ctx: dict) -> Optional[List[Tuple[str, dict, int]]]:
    """``[(module name, its statistics in the reduced trace, bucket)]``."""
    trace = ctx.get("trace")
    if not trace or not trace.get("modules"):
        return None
    groups = sorted(((name, stats) for name, stats
                     in trace["modules"].items() if "score" in name),
                    key=lambda group: group[1]["median_s"])
    buckets = sorted(ctx.get("capture_buckets") or [])
    if not groups or len(groups) != len(buckets):
        return None
    return [(name, stats, bucket)
            for (name, stats), bucket in zip(groups, buckets)]
