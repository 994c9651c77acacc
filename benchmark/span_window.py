"""Time the program spent under one ambient span, as a share of the
window: the sum of that span's records clipped to the window, over the
window's length.  Two readers use it (``coalescer_idle_share``,
``gc_pause_share``)."""

from __future__ import annotations

from typing import Dict, Optional


def share_pct(run: Dict, name: str) -> Optional[float]:
    """``None`` where the program records no such span (its phase timer,
    ``nomad.phase.<name>``, was never observed by the window's end): a
    program without the span is told apart from a window without one."""
    spans = run.get("spans")
    if spans is None or "nomad.phase." + name not in (run.get("m1") or {}):
        return None
    lo = run["client"]["t0"]
    hi = lo + run["seconds"]
    inside = sum(
        max(0.0, min(s["ts"] + s["dur"], hi) - max(s["ts"], lo))
        for s in spans if s["name"] == name
    )
    return 100.0 * inside / run["seconds"]
