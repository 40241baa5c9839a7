"""Per-layer metrics: one small reader per source kind, driven by the
metric's file under ``layer_metrics/``. A reader that finds nothing to read
returns ``None`` and the harness leaves the metric out of the line.

Source kinds:

* ``prom-delta`` — Δ of a counter (or of a histogram's ``_sum``) over the
  window, over the window's seconds or over the Δ of a second series;
* ``prom-gauge`` — a gauge sampled through the window, reduced (``max``);
* ``generator`` — the load generator's own clock (``late_ms``);
* ``trace`` — the device trace, reduced by ``lib/xplane.py`` and read by the
  module the metric's file names (``layer_metrics/<reducer>.py``). Two
  readers take their parameters from the metric's file, so that a metric of
  their kind is a data file: ``scope_share`` (the file's ``scopes`` globs)
  and ``kernel_roofline_share`` (its ``kernel`` and ``least``).
"""
from __future__ import annotations

import importlib
import inspect
from typing import Optional

from . import prom, quantiles


def _prom_delta(spec: dict, ctx: dict) -> Optional[float]:
    before, after = ctx["prom"].get(spec["stage"], (None, None))
    if before is None or after is None:
        return None
    num = spec["numerator"]
    if not prom.present(after, num["series"], num.get("labels")):
        return None
    top = prom.delta(before, after, num["series"], num.get("labels"))
    den = spec["denominator"]
    if den == "window_s":
        bottom = ctx["window_s"]
    else:
        bottom = prom.delta(before, after, den["series"], den.get("labels"))
    if bottom <= 0:
        return None
    return spec.get("scale", 1) * top / bottom


def _prom_gauge(spec: dict, ctx: dict) -> Optional[float]:
    values = [prom.total(sample, spec["series"], spec.get("labels"))
              for stage in spec["stages"]
              for sample in ctx["gauge_samples"].get(stage, [])
              if prom.present(sample, spec["series"], spec.get("labels"))]
    if not values:
        return None
    if spec["reducer"] != "max":
        raise ValueError(f"unknown reducer {spec['reducer']!r}")
    return max(values)


def _generator(spec: dict, ctx: dict) -> Optional[float]:
    samples = ctx["generator"].get(spec["field"])
    if not samples:
        return None
    if spec["reducer"] != "p95":
        raise ValueError(f"unknown reducer {spec['reducer']!r}")
    return quantiles.quantile(samples, 0.95)


def _trace(spec: dict, ctx: dict) -> Optional[float]:
    """The metric's own reader, ``benchmark/layer_metrics/<reducer>.py``:
    ``read(ctx)`` over the reduced trace (``lib/xplane.py``), the scorer's
    block, the buckets dispatched during the capture, the device's peaks and
    the capture's directory — or ``read(ctx, spec)`` where the reader takes
    the metric's file too. A later PR adds a trace metric by adding such a
    reader, or a data file for one that is there."""
    reader = importlib.import_module(
        f"benchmark.layer_metrics.{spec['reducer']}")
    if len(inspect.signature(reader.read).parameters) > 1:
        return reader.read(ctx, spec)
    return reader.read(ctx)


KINDS = {"prom-delta": _prom_delta, "prom-gauge": _prom_gauge,
         "generator": _generator, "trace": _trace}


def evaluate(spec: dict, ctx: dict) -> Optional[float]:
    return KINDS[spec["kind"]](spec, ctx)
