"""The comparison that decides ``correct``: what reached the sink against the
float32 reference's scores of the same lines.

For every pool line the reference scored (each anomalous line, a seeded
sample of normal lines, and a seeded sample of any other line that alerted):

* ``ref > threshold + tol``: the line is *expected* — exactly one alert for
  each time it was sent; fewer is ``missing``, more is ``duplicate``;
* ``ref < threshold - tol``: the line must stay silent — any alert is a
  ``false_alert``;
* inside the band either is right, but never more alerts than sends.

Every alert of a scored line carries the program's score (four decimals in
the alert text). Over the gaps ``alert score - reference score`` of all those
alerts, each number with a limit of its own: the widest and the root mean
square. The threshold printed in the alerts has to be the fitted one.
"""
from __future__ import annotations

from typing import Dict, List


def judge(alerts_by_id: Dict[str, List[float]], sent_by_id: Dict[str, int],
          reference: Dict[str, float], threshold: float, limits: dict,
          alert_thresholds: List[float]) -> dict:
    """``alerts_by_id``: the scores of the alerts seen for each logID;
    ``sent_by_id``: how often each scored line was sent; ``reference``: the
    reference score of each scored line; ``limits``: the configuration's
    ``check`` block (``tolerance_nats``, ``rms_limit_nats``)."""
    tol = float(limits["tolerance_nats"])
    missing = duplicates = false_alerts = expected = in_band = 0
    gaps: List[float] = []
    for log_id, ref in reference.items():
        seen = alerts_by_id.get(log_id, [])
        sent = sent_by_id.get(log_id, 0)
        gaps.extend(score - ref for score in seen)
        if ref > threshold + tol:
            expected += sent
            missing += max(0, sent - len(seen))
            duplicates += max(0, len(seen) - sent)
        elif ref < threshold - tol:
            false_alerts += len(seen)
        else:
            in_band += 1
            duplicates += max(0, len(seen) - sent)
    threshold_gap = max((abs(t - threshold) for t in alert_thresholds),
                        default=0.0)
    count = max(1, len(gaps))
    numbers = [
        # name, value, limit
        ["score_gap_max_nats", max((abs(g) for g in gaps), default=0.0),
         tol],
        ["score_gap_rms_nats", (sum(g * g for g in gaps) / count) ** 0.5,
         float(limits["rms_limit_nats"])],
        ["alert_threshold_gap", threshold_gap, 1e-4],
        ["missing_alerts", missing, 0],
        ["duplicate_alerts", duplicates, 0],
        ["false_alerts", false_alerts, 0],
    ]
    return {
        "numbers": numbers,
        "ok": all(value <= limit for _, value, limit in numbers),
        "failed": missing + duplicates + false_alerts,
        "expected_alerts": expected,
        "lines_in_band": in_band,
        "lines_scored": len(reference),
    }
