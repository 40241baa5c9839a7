"""Open-loop arrival schedules: frame *i* is due at ``t0 + offset[i]``.

The arithmetic is that of ``loadgen/generator.py`` ``OpenLoopSchedule``
(copied, not imported): the schedule is fixed before the first send and
nothing a slow consumer does moves a due time. Latency is taken from the due
time, so the wait a stall imposes on later frames counts.

Two arrival rules, named in a traffic file:

* ``fixed`` — equal gaps;
* ``exponential`` — the gaps are the same multiset for every seed (the
  mid-quantiles of the exponential distribution, scaled so that they sum to
  the span), in an order shuffled by the seed. Every seed therefore offers
  the same work and the same burstiness, in another order.
"""
from __future__ import annotations

import math
import random
from typing import List


def n_frames(rate_lines_per_s: float, frame_lines: int, span_s: float) -> int:
    if rate_lines_per_s <= 0 or frame_lines < 1 or span_s <= 0:
        raise ValueError("rate, frame size and span must be positive")
    return max(1, round(rate_lines_per_s * span_s / frame_lines))


def offsets(arrival: str, rate_lines_per_s: float, frame_lines: int,
            span_s: float, seed: int) -> List[float]:
    """Due times, in seconds from the schedule's start, of the frames that
    carry ``rate × span`` lines; all lie in ``[0, span)``."""
    n = n_frames(rate_lines_per_s, frame_lines, span_s)
    if arrival == "fixed":
        return [i * span_s / n for i in range(n)]
    if arrival == "exponential":
        gaps = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
        scale = span_s / sum(gaps)
        random.Random(f"arrivals:{seed}").shuffle(gaps)
        out, t = [], 0.0
        for gap in gaps:
            out.append(t)
            t += gap * scale
        return out
    raise ValueError(f"unknown arrival rule {arrival!r}")
