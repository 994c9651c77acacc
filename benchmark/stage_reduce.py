"""From the profiler's trace to the kernel's stages: device time of the
placement program's leaf ops, grouped by the ``jax.named_scope`` they were
traced under (``place_scan``, ``place_scan/score/feasibility``,
``verify_scan``, ``pack``, ...).  A share of the program's own time, so no
clock is aligned with any other.

The scope is in each op's ``op_name``, which the xplane keeps as the
``tf_op`` stat of the op's *event metadata*; ``jax.profiler.ProfileData``
shows an event's own stats only (on a v5e: its device offset and
duration), so ``load`` reads the ``.xplane.pb`` itself: the few fields of
the protobuf wire format it needs, and nothing else.  A program compiled
without scopes (or a trace without the stat) groups everything under ``""``
and the readers then have nothing to report.
"""

from __future__ import annotations

import functools
import glob
import os
import re
from typing import Dict, Iterator, List, Optional, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_DIR = os.path.join(ROOT, ".bench_trace")  # where run.py leaves it

# The scopes nomad_tpu/ops/kernels.py and parallel/sharding.py name
# (OBSERVABILITY.md, "Kernel stage scopes").
SCOPES = ("place_scan", "score", "pick", "update", "feasibility", "binpack",
          "affinity_spread", "preemption", "verify_scan", "pack")
_WRAPPED = re.compile(r"\b\w+\(([\w/]+)\)")  # vmap(place_scan) -> place_scan

Op = Tuple[str, float]  # (op_name, seconds)


def scope_of(op_name: str) -> str:
    """``jit(f)/vmap(place_scan)/while/body/closed_call/score/binpack/add``
    -> ``place_scan/score/binpack``."""
    parts = _WRAPPED.sub(r"\1", op_name).split("/")
    return "/".join(p for p in parts if p in SCOPES)


# -- the protobuf wire format, as far as an XSpace needs it ------------------------

def _varint(buf: memoryview, i: int) -> Tuple[int, int]:
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        if b < 0x80:
            return val, i
        shift += 7


def _fields(buf: memoryview) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width fields are
    skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
            yield key >> 3, val
        elif wire == 2:
            size, i = _varint(buf, i)
            yield key >> 3, buf[i:i + size]
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire} in an xplane")


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _map_entry(buf) -> Tuple[int, Optional[memoryview]]:
    key, value = 0, None
    for no, v in _fields(buf):
        if no == 1:
            key = v
        elif no == 2:
            value = v
    return key, value


def _plane(buf) -> Tuple[str, List[memoryview], Dict[int, memoryview],
                         Dict[int, str]]:
    """XPlane: name = 2, lines = 3, event_metadata = 4, stat_metadata = 5."""
    name, lines, event_meta, stat_names = "", [], {}, {}
    for no, v in _fields(buf):
        if no == 2:
            name = _text(v)
        elif no == 3:
            lines.append(v)
        elif no == 4:
            k, m = _map_entry(v)
            if m is not None:
                event_meta[k] = m
        elif no == 5:
            k, m = _map_entry(v)
            if m is not None:
                stat_names[k] = next(
                    (_text(x) for n, x in _fields(m) if n == 2), "")
    return name, lines, event_meta, stat_names


def _line(buf) -> Tuple[str, List[Tuple[int, int, int]]]:
    """XLine: name = 2, events = 4; XEvent: metadata_id = 1, offset_ps = 2,
    duration_ps = 3.  -> (name, [(offset, duration, metadata id)])."""
    name, events = "", []
    for no, v in _fields(buf):
        if no == 2:
            name = _text(v)
        elif no == 4:
            meta = off = dur = 0
            for n, x in _fields(v):
                if n == 1:
                    meta = x
                elif n == 2:
                    off = x
                elif n == 3:
                    dur = x
            events.append((off, dur, meta))
    return name, events


def _meta(buf, stat_names: Dict[int, str]) -> Tuple[str, str]:
    """XEventMetadata: name = 2, stats = 5; XStat: metadata_id = 1,
    str_value = 5, ref_value = 7 (a string kept once, as a stat's name).
    -> (the event's name, its ``tf_op`` stat or "")."""
    name, op_name = "", ""
    for no, v in _fields(buf):
        if no == 2:
            name = _text(v)
        elif no == 5:
            stat, text = 0, ""
            for n, x in _fields(v):
                if n == 1:
                    stat = x
                elif n == 5:
                    text = _text(x)
                elif n == 7:
                    text = stat_names.get(x, "")
            if stat_names.get(stat) == "tf_op":
                op_name = text
    return name, op_name


def load(path: str, programs) -> List[Op]:
    """Leaf ops (those with no op nested inside them: not ``while``,
    ``conditional``, ``call``) of every launch of a placement program
    (``programs``: substrings of its module names), on every device plane."""
    with open(path, "rb") as fh:
        space = memoryview(fh.read())
    out: List[Op] = []
    for no, v in _fields(space):
        if no != 1:
            continue
        name, lines, event_meta, stat_names = _plane(v)
        if not name.startswith("/device:"):
            continue
        by_name = dict(_line(ln) for ln in lines)
        metas: Dict[int, Tuple[str, str]] = {}

        def meta(mid):
            if mid not in metas:
                buf = event_meta.get(mid)
                metas[mid] = _meta(buf, stat_names) if buf is not None \
                    else ("", "")
            return metas[mid]

        launches = sorted(
            (off, off + dur) for off, dur, mid in by_name.get("XLA Modules", [])
            if any(p in meta(mid)[0] for p in programs))
        ops = sorted(by_name.get("XLA Ops", []), key=lambda e: (e[0], -e[1]))
        j = 0
        for k, (off, dur, mid) in enumerate(ops):
            if k + 1 < len(ops) and ops[k + 1][0] < off + dur:
                continue  # the next op starts inside this one: not a leaf
            while j < len(launches) and launches[j][1] <= off:
                j += 1
            if j < len(launches) and launches[j][0] <= off:
                out.append((meta(mid)[1], dur / 1e12))
    return out


def by_scope(ops: List[Op]) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for op_name, seconds in ops:
        key = scope_of(op_name)
        out[key] = out.get(key, 0.0) + seconds
    return out


@functools.lru_cache(maxsize=2)
def _scopes_of_trace(path: str, programs: Tuple[str, ...]) -> Dict[str, float]:
    return by_scope(load(path, programs))


def scope_share_pct(run: Dict, scope: str) -> Optional[float]:
    """Device time under ``scope`` / device time of the placement program's
    leaf ops, in %, from the xplane the traced run left in ``.bench_trace``.
    ``None`` without a device trace, or where no op carries a scope (a
    program that names none)."""
    if not run.get("device"):
        return None
    files = glob.glob(os.path.join(
        TRACE_DIR, "plugins", "profile", "*", "*.xplane.pb"))
    if not files:
        return None
    scopes = _scopes_of_trace(sorted(files)[-1],
                              tuple(run["cfg"]["placement_programs"]))
    total = sum(scopes.values())
    if total <= 0 or set(scopes) <= {""}:
        return None
    under = sum(v for k, v in scopes.items() if k.split("/")[0] == scope)
    return 100.0 * under / total
