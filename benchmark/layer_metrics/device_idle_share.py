"""Reader of ``device_idle_share``: 1 - the union of the device's operation
intervals over the stretch the device plane covers, from the reduced trace."""
from __future__ import annotations

from typing import Optional


def read(ctx: dict) -> Optional[float]:
    trace = ctx.get("trace")
    if not trace or not trace.get("devices") or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
