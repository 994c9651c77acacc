"""Arithmetic the CPU-time readers share: growth over the window of the
program's pulled gauges ``nomad.runtime.cpu_seconds{group=}`` (CPU seconds
by thread group: the thread's name with trailing digits stripped,
``process`` = ``time.process_time()``, ``native`` = the process less its
Python threads), and what the readers divide it by.  ``None`` where the
program has no such gauge (the parent of the PR that added them, or a
platform without per-thread CPU clocks)."""

from __future__ import annotations

from typing import Dict, Optional

import measure

GAUGE = "nomad.runtime.cpu_seconds{group="
NOT_PYTHON = ("process", "native")


def group_s(run: Dict, group: str) -> Optional[float]:
    """CPU seconds the threads of ``group`` used over the window."""
    return measure.delta(run, GAUGE + group + "}")


def python_s(run: Dict) -> Optional[float]:
    """CPU seconds of every group of Python threads over the window."""
    groups = [k[len(GAUGE):-1] for k in run.get("m1") or {}
              if k.startswith(GAUGE)]
    used = [group_s(run, g) for g in groups if g not in NOT_PYTHON]
    return sum(used) if used else None


def placed_in_window(run: Dict) -> int:
    """Operations placed inside the window: what ``evals_per_s`` counts."""
    t_end = run["client"]["t_end"]
    return sum(1 for r in run["attempted"]
               if r["ok"] and r["placed"] <= t_end)


def share_pct(seconds: Optional[float], run: Dict) -> Optional[float]:
    """``seconds`` as a share of the window (one core the whole window
    reads 100)."""
    return None if seconds is None else 100.0 * seconds / run["seconds"]


def ms_per(seconds: Optional[float], count: Optional[float]
           ) -> Optional[float]:
    return measure.ratio(None if seconds is None else 1e3 * seconds, count)
