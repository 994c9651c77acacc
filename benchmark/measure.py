"""Arithmetic the readers share: percentiles, span statistics, counter
deltas.  A reader that finds nothing to read gets ``None`` from these and
returns it; the harness then leaves the metric out of the line."""

from __future__ import annotations

import math
import statistics
from typing import Dict, List, Optional


def percentile(values: List[float], q: float) -> Optional[float]:
    """Nearest-rank percentile (the smallest value with at least ``q`` of
    the values at or below it)."""
    if not values:
        return None
    v = sorted(values)
    return v[min(len(v) - 1, max(0, math.ceil(q * len(v)) - 1))]


def latencies_ms(run: Dict) -> List[float]:
    """Due -> placed of every attempted operation, failed ones at inf."""
    return [
        (r["placed"] - r["due"]) * 1e3 if r["ok"] else float("inf")
        for r in run["attempted"]
    ]


def span_values_ms(run: Dict, name: str) -> List[float]:
    return [s["dur"] * 1e3 for s in run.get("spans") or [] if s["name"] == name]


def span_median_ms(run: Dict, name: str) -> Optional[float]:
    v = span_values_ms(run, name)
    return statistics.median(v) if v else None


def self_time_ms(run: Dict, names) -> Optional[float]:
    """Summed self time of the named spans: duration less what their child
    spans cover."""
    spans = run.get("spans") or []
    if not spans:
        return None
    child_time: Dict[int, float] = {}
    for s in spans:
        child_time[s["parent"]] = child_time.get(s["parent"], 0.0) + s["dur"]
    total = sum(
        max(0.0, s["dur"] - child_time.get(s["span"], 0.0))
        for s in spans if s["name"] in names
    )
    return total * 1e3


def delta(run: Dict, key: str) -> Optional[float]:
    """Growth of a program counter over the window."""
    m0, m1 = run.get("m0"), run.get("m1")
    if m0 is None or m1 is None or key not in m1:
        return None
    return float(m1[key]) - float(m0.get(key, 0))


def timer_count(run: Dict, key: str) -> Optional[float]:
    m0, m1 = run.get("m0"), run.get("m1")
    if m0 is None or m1 is None or key not in m1:
        return None
    return float(m1[key]["count"]) - float((m0.get(key) or {"count": 0})["count"])


def ratio(a: Optional[float], b: Optional[float]) -> Optional[float]:
    if a is None or b is None or b <= 0:
        return None
    return a / b


def evals_in_window(run: Dict) -> Optional[float]:
    """Evals the workers processed between the window's two counter
    snapshots (the client's count also holds those of the drain)."""
    return delta(run, "nomad.worker.evals_processed")
