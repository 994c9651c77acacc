"""From the profiler's trace to device metrics: busy and idle time, the
placement program's time per launch, exposed collective time, the ten
heaviest device ops and the ten longest idle gaps.

``load`` turns an ``.xplane.pb`` into plain event tuples (plane, line,
name, start_s, dur_s); ``reduce`` works on those alone, so it is tested on
a small recorded trace without a chip (tests/small_trace.json).
"""

from __future__ import annotations

import collections
from typing import Dict, List, Optional, Tuple

MARKER = "bench.marker"
COLLECTIVES = ("all-reduce", "all-gather", "all-to-all", "reduce-scatter",
               "collective-permute", "allreduce", "allgather")
Event = Tuple[str, str, str, float, float]


def load(path: str) -> List[Event]:
    """Device-plane events and the host marker, seconds from trace start."""
    import jax

    out: List[Event] = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            for ev in line.events:
                if device or ev.name == MARKER:
                    # An op's name is its whole HLO text: keep "while.24".
                    name = ev.name.split(" = ", 1)[0].lstrip("%")
                    out.append((plane.name, line.name, name,
                                ev.start_ns / 1e9, ev.duration_ns / 1e9))
    return out


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def _subtract(a, b) -> float:
    """Length of union(a) not covered by union(b)."""
    a, b = _union(a), _union(b)
    covered, j = 0.0, 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            covered += min(e, b[k][1]) - max(s, b[k][0])
            k += 1
    return _total(a) - covered


def _clip(events, lo, hi):
    return [(max(s, lo), min(s + d, hi), name)
            for (_p, _l, name, s, d) in events if s + d > lo and s < hi]


def reduce(events: List[Event], marker_wall: Optional[float], t0: float,
           seconds: float, kernels=("fused_place_batch",),
           spans: Optional[List[Dict]] = None) -> Optional[Dict]:
    """Device metrics of the window [t0, t0 + seconds] (wall clock).

    ``marker_wall`` is the wall time at which the harness wrote MARKER; it
    ties the trace's clock to the host's.  ``spans`` are the program's own
    span records (wall clock), used to name what the host was doing in
    each long idle gap.  ``kernels``: substrings of the placement
    program's module names (the configuration file lists them)."""
    marks = [e for e in events if e[2] == MARKER]
    if not marks or marker_wall is None:
        return None
    offset = marker_wall - marks[0][3]          # wall = trace + offset
    lo, hi = t0 - offset, t0 + seconds - offset
    planes = sorted({e[0] for e in events if e[0].startswith("/device:")})
    if not planes:
        return None
    busy, coll_exposed = [], []
    op_time: Dict[str, float] = {}
    launches, kernel_s = 0, 0.0
    gaps: List[Tuple[float, float]] = []
    for n, plane in enumerate(planes):
        mine = [e for e in events if e[0] == plane]
        ops = [e for e in mine if e[1] == "XLA Ops"] or [
            e for e in mine if e[1] not in ("Steps", "XLA Modules")
        ]
        mods = [e for e in mine if e[1] == "XLA Modules"]
        clipped = _clip(ops or mods, lo, hi)
        # Busy: a program is executing (its ops do not tile it: between
        # them the core waits on its own DMAs and scalar unit).
        merged = _union([(s, e) for s, e, _ in _clip(mods or ops, lo, hi)])
        busy.append(_total(merged))
        is_coll = lambda name: any(c in name.lower() for c in COLLECTIVES)
        coll = [(s, e) for s, e, name in clipped if is_coll(name)]
        comp = [(s, e) for s, e, name in clipped
                if not is_coll(name) and not name.startswith("while")]
        coll_exposed.append(_subtract(coll, comp))
        for s, e, name in _clip(mods, lo, hi):
            if any(k in name for k in kernels):
                kernel_s += e - s
                launches += 1
        if n == 0:
            for s, e, name in clipped:
                op_time[name] = op_time.get(name, 0.0) + (e - s)
            edge = lo
            for s, e in merged:
                if s > edge:
                    gaps.append((edge, s))
                edge = max(edge, e)
            if hi > edge:
                gaps.append((edge, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    named = []
    for s, e in gaps[:10]:
        mid = (s + e) / 2 + offset
        inside = [sp for sp in (spans or [])
                  if sp["ts"] <= mid <= sp["ts"] + sp["dur"]]
        name = max(inside, key=lambda sp: sp["ts"])["name"] if inside \
            else "unattributed"
        named.append([name, e - s])
    n_dev = len(planes)
    return {
        "busy_s": sum(busy) / n_dev,
        "window_s": seconds,
        "devices": n_dev,
        "kernel_s": kernel_s / n_dev,
        "launches": launches / n_dev,
        "collective_exposed_s": sum(coll_exposed) / n_dev,
        "modules": sorted(collections.Counter(
            name for _s, _e, name in _clip(
                [e for e in events
                 if e[0] == planes[0] and e[1] == "XLA Modules"], lo, hi)
        ).items()),
        "device_ops": [[k, v] for k, v in sorted(
            op_time.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": named,
    }
