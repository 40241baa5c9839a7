"""Prometheus text exposition → numbers, and deltas between two scrapes."""
from __future__ import annotations

import re
import urllib.request
from typing import Dict, Mapping, Tuple

Series = Dict[Tuple[str, Tuple[Tuple[str, str], ...]], float]

_SAMPLE = re.compile(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})?\s+(\S+)")
_LABEL = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse(text: str) -> Series:
    out: Series = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        found = _SAMPLE.match(line)
        if not found:
            continue
        name, labels, value = found.groups()
        try:
            number = float(value)
        except ValueError:
            continue
        out[(name, tuple(sorted(_LABEL.findall(labels or ""))))] = number
    return out


def scrape(port: int, timeout: float = 5.0) -> Series:
    with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics",
                                timeout=timeout) as resp:
        return parse(resp.read().decode("utf-8", "replace"))


def _selected(series: Series, name: str, labels: Mapping[str, str]):
    want = dict(labels or {})
    return [value for (sample, have), value in series.items()
            if sample == name
            and all(dict(have).get(k) == v for k, v in want.items())]


def total(series: Series, name: str,
          labels: Mapping[str, str] = None) -> float:
    """Sum of every sample of ``name`` whose labels include ``labels``;
    0.0 when none does (a counter that has not ticked yet is absent)."""
    return sum(_selected(series, name, labels))


def present(series: Series, name: str,
            labels: Mapping[str, str] = None) -> bool:
    return bool(_selected(series, name, labels))


def delta(before: Series, after: Series, name: str,
          labels: Mapping[str, str] = None) -> float:
    return total(after, name, labels) - total(before, name, labels)
