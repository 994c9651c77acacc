#!/usr/bin/env python
"""Summarize a nomad-tpu trace dump (Chrome trace-event JSON) in the
terminal — the quick look before loading it into Perfetto.

Usage:
    nomad-tpu trace dump -o trace.json      # or any flight-*.json dump
    python tools/trace_view.py trace.json
    python tools/trace_view.py trace.json --trace eval-abc123
    python tools/trace_view.py trace.json --phase plan.apply --slowest 10

Per-phase table: span count, total/mean/max duration, share of the
summed root-span time, and the CPU the spans' own threads used where the
program recorded it (``trace.span(cpu=True)``: the rest is waiting).  With ``--trace ID`` prints that eval's span
tree with per-span durations instead.  ``--phase NAME`` narrows any
view to spans whose phase name contains NAME (so ``--phase plan``
matches plan.queue_wait + plan.apply); ``--slowest N`` lists the N
longest individual spans — the first question a flight record gets
("which eval blew the p99?") answered without Perfetto.

For the full timeline, load the same file in https://ui.perfetto.dev
(drag the file into the page) — spans are grouped per thread with
trace/span/parent ids in the args pane.

Stdlib-only on purpose: works on any host that can scp the dump over.
"""

from __future__ import annotations

import argparse
import json
import sys
from collections import defaultdict
from typing import Any, Dict, List


def load_events(path: str) -> List[Dict[str, Any]]:
    with open(path, "r", encoding="utf-8") as fh:
        doc = json.load(fh)
    if isinstance(doc, dict):
        events = doc.get("traceEvents", [])
    else:
        events = doc  # bare-array variant is also legal Chrome format
    return [e for e in events if e.get("ph") == "X"]


def summarize(events: List[Dict[str, Any]]) -> None:
    by_name: Dict[str, List[float]] = defaultdict(list)
    cpu_by_name: Dict[str, float] = {}
    roots = 0.0
    for e in events:
        dur_ms = e.get("dur", 0) / 1000.0
        by_name[e["name"]].append(dur_ms)
        if not e.get("args", {}).get("parent"):
            roots += dur_ms
        cpu = e.get("args", {}).get("cpu")  # seconds; spans made with cpu=True
        if cpu is not None:
            cpu_by_name[e["name"]] = cpu_by_name.get(e["name"], 0.0) + cpu * 1e3
    if not by_name:
        print("no complete spans in file")
        return
    rows = []
    for name, durs in sorted(by_name.items()):
        total = sum(durs)
        rows.append((
            name, len(durs), total, total / len(durs), max(durs),
            100.0 * total / roots if roots else 0.0, cpu_by_name.get(name),
        ))
    rows.sort(key=lambda r: -r[2])
    # ``cpu ms``: what of ``total ms`` the spans' own threads ran; the rest
    # they waited (a lock, the GIL, the device).  "-" where not recorded.
    hdr = f"{'phase':<28}{'count':>7}{'total ms':>11}{'mean ms':>10}" \
          f"{'max ms':>10}{'% root':>8}{'cpu ms':>11}"
    print(hdr)
    print("-" * len(hdr))
    for name, n, total, mean, mx, pct, cpu in rows:
        cpu_col = "-" if cpu is None else f"{cpu:.2f}"
        print(f"{name:<28}{n:>7}{total:>11.2f}{mean:>10.3f}"
              f"{mx:>10.3f}{pct:>8.1f}{cpu_col:>11}")
    print(f"\n{len(events)} spans; summed root-span time {roots:.2f} ms")
    print("full timeline: load this file in https://ui.perfetto.dev")


def filter_phase(
    events: List[Dict[str, Any]], phase: str
) -> List[Dict[str, Any]]:
    """Spans whose name contains ``phase`` (substring, so a family
    prefix like ``plan`` selects the whole plan.* group)."""
    return [e for e in events if phase in e.get("name", "")]


def show_slowest(events: List[Dict[str, Any]], n: int) -> None:
    """The N longest individual spans, slowest first."""
    ranked = sorted(events, key=lambda e: -e.get("dur", 0))[:n]
    if not ranked:
        print("no complete spans in file")
        return
    hdr = f"{'phase':<28}{'dur ms':>10}  {'trace':<38}{'ts us':>16}"
    print(hdr)
    print("-" * len(hdr))
    for e in ranked:
        args = e.get("args", {})
        print(f"{e['name']:<28}{e.get('dur', 0) / 1000.0:>10.3f}  "
              f"{str(args.get('trace', '-')):<38}{e.get('ts', 0):>16}")
    print(f"\ntop {len(ranked)} of {len(events)} spans by duration")


def show_trace(events: List[Dict[str, Any]], trace_id: str) -> None:
    mine = [e for e in events
            if e.get("args", {}).get("trace") == trace_id]
    if not mine:
        print(f"no spans for trace {trace_id!r}", file=sys.stderr)
        sys.exit(1)
    by_parent: Dict[Any, List[Dict[str, Any]]] = defaultdict(list)
    for e in mine:
        by_parent[e["args"].get("parent") or 0].append(e)
    for kids in by_parent.values():
        kids.sort(key=lambda e: e.get("ts", 0))
    t0 = min(e["ts"] for e in mine)

    def walk(parent: Any, depth: int) -> None:
        for e in by_parent.get(parent, ()):
            off = (e["ts"] - t0) / 1000.0
            dur = e.get("dur", 0) / 1000.0
            cpu = e["args"].get("cpu")
            print(f"{'  ' * depth}{e['name']:<{30 - 2 * depth}}"
                  f" +{off:8.3f} ms  {dur:8.3f} ms"
                  + ("" if cpu is None else f"  cpu {cpu * 1e3:8.3f} ms"))
            walk(e["args"].get("span"), depth + 1)

    print(f"trace {trace_id} ({len(mine)} spans)")
    walk(0, 0)


def main(argv: List[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path", help="Chrome trace JSON (trace dump or "
                                 "flight-*.json)")
    ap.add_argument("--trace", default="",
                    help="print one trace's span tree instead")
    ap.add_argument("--phase", default="",
                    help="only spans whose phase name contains this")
    ap.add_argument("--slowest", type=int, default=0, metavar="N",
                    help="list the N longest spans instead of the table")
    args = ap.parse_args(argv)
    events = load_events(args.path)
    if args.phase:
        events = filter_phase(events, args.phase)
        if not events:
            print(f"no spans matching phase {args.phase!r}",
                  file=sys.stderr)
            return 1
    if args.trace:
        show_trace(events, args.trace)
    elif args.slowest > 0:
        show_slowest(events, args.slowest)
    else:
        summarize(events)
    return 0


if __name__ == "__main__":
    sys.exit(main())
