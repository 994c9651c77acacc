"""Of the device's idle time in the traced slice, the share during which
the dispatch thread was inside ``coalescer.launch``: the device idles
*while a launch is being prepared* (the launch path: sync, stage, enqueue)
and not *while there is nothing to launch*.

No clock is aligned with any other: ``coalescer.launch`` is an annotated
span (``trace.span(annotate=True)`` enters a ``TraceAnnotation``), so it
stands in the xplane's host plane on the profiler's clock, beside the
device plane's ``XLA Modules``.  The slice starts where the harness's
marker ends and is as long as ``trace_reduce.reduce``'s window.

``load`` turns an ``.xplane.pb`` into ``trace_reduce``'s event tuples;
``overlap`` works on those alone (tests/small_trace_launches.json).
"""

from __future__ import annotations

import glob
import os
from typing import List, Optional

import trace_reduce
from trace_reduce import MARKER, Event

LAUNCH = "coalescer.launch"
TRACE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".bench_trace")  # where run.py leaves the trace


def load(path: str) -> List[Event]:
    """The first device plane's events (the first by name of those that
    hold any: a v5e's trace also has an empty ``/device:CUSTOM`` plane),
    and the host planes' marker and launch spans, seconds from trace
    start."""
    import jax

    host: List[Event] = []
    device: dict = {}
    data = jax.profiler.ProfileData.from_file(path)  # owns the planes
    for plane in data.planes:
        on_device = plane.name.startswith("/device:")
        for line in plane.lines:
            for ev in line.events:
                # An annotation's arguments may ride its name: a#k=v#.
                name = ev.name.split("#", 1)[0]
                if on_device or name in (MARKER, LAUNCH):
                    (device.setdefault(plane.name, []) if on_device
                     else host).append(
                        (plane.name, line.name, name,
                         ev.start_ns / 1e9, ev.duration_ns / 1e9))
    return host + (device[min(device)] if device else [])


def overlap(events: List[Event], window_s: float) -> Optional[dict]:
    """``idle_s`` of the first device plane in the slice (as
    ``trace_reduce.reduce`` defines busy: a program is executing) and
    ``launching_s``, the part of it under a launch span.  ``None`` without
    the marker, a device plane, or any launch span in the trace (a program
    that annotates none)."""
    marks = [e for e in events if e[2] == MARKER]
    planes = sorted({e[0] for e in events if e[0].startswith("/device:")})
    launches = [(s, s + d) for p, _l, name, s, d in events
                if name == LAUNCH and not p.startswith("/device:")]
    if not marks or not planes or not launches:
        return None
    lo = marks[0][3] + marks[0][4]
    hi = lo + window_s
    mine = [e for e in events if e[0] == planes[0]]
    mods = [e for e in mine if e[1] == "XLA Modules"] or [
        e for e in mine if e[1] == "XLA Ops"] or [
        e for e in mine if e[1] != "Steps"]
    busy = trace_reduce._union(
        [(s, e) for s, e, _ in trace_reduce._clip(mods, lo, hi)])
    idle, edge = [], lo
    for s, e in busy:
        if s > edge:
            idle.append((edge, s))
        edge = max(edge, e)
    if hi > edge:
        idle.append((edge, hi))
    idle_s = trace_reduce._total(idle)
    return {"idle_s": idle_s,
            "launching_s": idle_s - trace_reduce._subtract(idle, launches)}


def share_pct(run: dict) -> Optional[float]:
    """From the xplane the traced run left in ``.bench_trace``."""
    d = run.get("device")
    files = glob.glob(os.path.join(
        TRACE_DIR, "plugins", "profile", "*", "*.xplane.pb"))
    if not d or not files:
        return None
    o = overlap(load(sorted(files)[-1]), d["window_s"])
    if o is None or o["idle_s"] <= 0:
        return None
    return 100.0 * o["launching_s"] / o["idle_s"]
