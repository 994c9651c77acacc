#!/usr/bin/env python
"""Bench regression ledger — normalize, baseline, verdict.

Any JSON result file (a flat dict of numbers, or a driver wrapper around
one) is normalized into one line of ``BENCH_LEDGER.jsonl``.  The harness
that used to feed it is gone; the driver's own record of the benchmark is
``PERF_LEDGER.jsonl``, which this tool neither reads nor writes:

    {"ts": ..., "source": "...", "ok": true,
     "metrics": {"eval_throughput": 969.5, "p99_ms": 266.0, ...},
     "verdicts": {"eval_throughput": {"verdict": "flat", ...}, ...}}

Two input shapes are understood:

* the driver wrapper ``{"n", "cmd", "rc", "tail", "parsed"}`` — ``parsed``
  is the bench's JSON stdout line (None when the run crashed; the entry
  is kept with ``ok: false`` so the ledger records the failure, but it
  contributes nothing to baselines);
* a flat result dict (numeric leaves become
  metrics; a ``{"metric": name, "value": v}`` pair is folded to
  ``name: v``).

The baseline for a metric is the trailing window (default 8) of prior
*successful* runs that carried it.  A new value's verdict:

    deviation = value - median(baseline)
    threshold = max(MAD_SIGMAS * 1.4826 * MAD, REL_FLOOR * |median|)
    |deviation| <= threshold        -> flat
    else (by the metric's direction) -> improve | regress

Median/MAD instead of mean/stddev because bench history is exactly the
distribution outliers ruin: one swapped-out run would widen a stddev
gate enough to wave real regressions through.  The 1.4826 factor scales
MAD to a normal-equivalent sigma; REL_FLOOR keeps near-constant metrics
(MAD ~ 0) from flagging on noise.  Direction is inferred from the name
(throughput-ish = higher-better, latency/duration-ish = lower-better);
metrics with no inferable direction (batch sizes, node counts) are
recorded but never judged.

CLI:

    python tools/bench_history.py ingest BENCH_*.json   # seed/extend ledger
    python tools/bench_history.py record result.json    # one run + verdicts
    python tools/bench_history.py report [--last N]     # recent verdicts
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

DEFAULT_LEDGER = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "BENCH_LEDGER.jsonl",
)

BASELINE_RUNS = 8      # trailing successful runs per metric
MIN_BASELINE = 3       # fewer than this -> verdict "new"
MAD_SIGMAS = 3.0       # breadth of the MAD gate
REL_FLOOR = 0.05       # never flag a <5% move, however tight the MAD

VERDICT_IMPROVE = "improve"
VERDICT_FLAT = "flat"
VERDICT_REGRESS = "regress"
VERDICT_NEW = "new"    # not enough history to judge

# Direction inference: first match wins, higher-better checked first so
# "evals_per_sec" doesn't fall into the lower-better "_s" suffix rule.
_HIGHER_TOKENS = ("per_sec", "throughput", "per_second", "speedup",
                  "evals_sec", "ops_sec")
_LOWER_TOKENS = ("latency",)
_LOWER_SUFFIXES = ("_ms", "_s", "_ns", "_us")
_LOWER_PREFIX_TOKENS = ("p50", "p90", "p95", "p99", "max_ms", "mean_ms")


def direction(metric: str) -> Optional[int]:
    """+1 = higher is better, -1 = lower is better, None = don't judge."""
    m = metric.lower()
    if any(tok in m for tok in _HIGHER_TOKENS):
        return 1
    if any(tok in m for tok in _LOWER_TOKENS):
        return -1
    leaf = m.rsplit(".", 1)[-1]
    if any(tok in leaf for tok in _LOWER_PREFIX_TOKENS):
        return -1
    if leaf.endswith(_LOWER_SUFFIXES):
        return -1
    return None


# -- normalization -----------------------------------------------------


def _flatten(obj: Dict[str, Any], prefix: str = "",
             out: Optional[Dict[str, float]] = None) -> Dict[str, float]:
    out = out if out is not None else {}
    for k, v in obj.items():
        key = f"{prefix}{k}"
        if isinstance(v, dict):
            _flatten(v, key + ".", out)
        elif isinstance(v, bool):
            continue  # config flags, not metrics
        elif isinstance(v, (int, float)):
            out[key] = float(v)
    return out


def flatten_metrics(result: Dict[str, Any]) -> Dict[str, float]:
    """Numeric leaves of a bench result, dotted keys for nesting; a
    top-level ``{"metric": name, "value": v}`` pair folds to ``name``."""
    result = dict(result)
    name = result.pop("metric", None)
    value = result.get("value")
    if isinstance(name, str) and isinstance(value, (int, float)):
        result.pop("value")
        result[name] = value
    return _flatten(result)


def normalize(raw: Dict[str, Any], source: str = "") -> Dict[str, Any]:
    """One ledger entry from either input shape (see module docstring)."""
    if "tail" in raw and ("rc" in raw or "parsed" in raw):
        parsed = raw.get("parsed")
        ok = raw.get("rc", 1) == 0 and isinstance(parsed, dict)
        metrics = flatten_metrics(parsed) if isinstance(parsed, dict) else {}
        meta = {"rc": raw.get("rc"), "n": raw.get("n")}
    else:
        ok = True
        metrics = flatten_metrics(raw)
        meta = {}
        for k in ("platform", "unit", "note", "phase"):
            if isinstance(raw.get(k), str):
                meta[k] = raw[k]
    return {
        "ts": time.time(),
        "source": source,
        "ok": ok,
        "metrics": metrics,
        "meta": meta,
    }


# -- ledger I/O --------------------------------------------------------


def read_ledger(path: str) -> List[Dict[str, Any]]:
    entries: List[Dict[str, Any]] = []
    if not os.path.exists(path):
        return entries
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                entries.append(json.loads(line))
            except ValueError:
                continue  # a torn write must not poison the history
    return entries


def append_entry(path: str, entry: Dict[str, Any]) -> None:
    with open(path, "a") as fh:
        fh.write(json.dumps(entry, sort_keys=True) + "\n")


# -- baseline + verdicts -----------------------------------------------


def _median(vals: List[float]) -> float:
    s = sorted(vals)
    n = len(s)
    mid = n // 2
    return s[mid] if n % 2 else 0.5 * (s[mid - 1] + s[mid])


def _mad(vals: List[float], med: float) -> float:
    return _median([abs(v - med) for v in vals])


def baseline_values(
    history: List[Dict[str, Any]], metric: str, runs: int = BASELINE_RUNS
) -> List[float]:
    vals: List[float] = []
    for entry in reversed(history):
        if not entry.get("ok"):
            continue
        v = entry.get("metrics", {}).get(metric)
        if isinstance(v, (int, float)):
            vals.append(float(v))
            if len(vals) >= runs:
                break
    vals.reverse()
    return vals


def judge(
    value: float, baseline: List[float], metric: str
) -> Dict[str, Any]:
    d = direction(metric)
    if d is None:
        return {}
    if len(baseline) < MIN_BASELINE:
        return {"verdict": VERDICT_NEW, "baseline_n": len(baseline)}
    med = _median(baseline)
    mad = _mad(baseline, med)
    threshold = max(MAD_SIGMAS * 1.4826 * mad, REL_FLOOR * abs(med))
    deviation = value - med
    if abs(deviation) <= threshold:
        verdict = VERDICT_FLAT
    elif (deviation > 0) == (d > 0):
        verdict = VERDICT_IMPROVE
    else:
        verdict = VERDICT_REGRESS
    return {
        "verdict": verdict,
        "baseline_median": round(med, 6),
        "baseline_mad": round(mad, 6),
        "baseline_n": len(baseline),
        "deviation": round(deviation, 6),
        "threshold": round(threshold, 6),
        "delta_pct": round(100.0 * deviation / med, 2) if med else None,
    }


def judge_entry(
    entry: Dict[str, Any], history: List[Dict[str, Any]]
) -> Dict[str, Dict[str, Any]]:
    verdicts: Dict[str, Dict[str, Any]] = {}
    for metric, value in sorted(entry.get("metrics", {}).items()):
        v = judge(value, baseline_values(history, metric), metric)
        if v:
            verdicts[metric] = v
    return verdicts


def format_verdicts(entry: Dict[str, Any]) -> List[str]:
    lines: List[str] = []
    order = {VERDICT_REGRESS: 0, VERDICT_IMPROVE: 1, VERDICT_FLAT: 2,
             VERDICT_NEW: 3}
    items = sorted(
        entry.get("verdicts", {}).items(),
        key=lambda kv: (order.get(kv[1]["verdict"], 9), kv[0]),
    )
    for metric, v in items:
        if v["verdict"] == VERDICT_NEW:
            lines.append(f"bench[{metric}]: new (baseline "
                         f"{v['baseline_n']}/{MIN_BASELINE} runs)")
            continue
        pct = v.get("delta_pct")
        pct_s = f"{pct:+.1f}%" if pct is not None else "n/a"
        lines.append(
            f"bench[{metric}]: {v['verdict']} "
            f"({entry['metrics'][metric]:g} vs median "
            f"{v['baseline_median']:g}, {pct_s}, "
            f"gate ±{v['threshold']:g}, n={v['baseline_n']})"
        )
    return lines


def record_run(
    result: Dict[str, Any],
    source: str = "result",
    ledger: str = DEFAULT_LEDGER,
) -> Dict[str, Any]:
    """Normalize one run, judge it against the ledger, append, return
    the entry (with ``verdicts``)."""
    history = read_ledger(ledger)
    entry = normalize(result, source=source)
    entry["verdicts"] = judge_entry(entry, history)
    append_entry(ledger, entry)
    return entry


# -- CLI ---------------------------------------------------------------


def cmd_ingest(args) -> int:
    history = read_ledger(args.ledger)
    added = 0
    for path in args.files:
        with open(path) as fh:
            raw = json.load(fh)
        entry = normalize(raw, source=os.path.basename(path))
        entry["verdicts"] = judge_entry(entry, history)
        append_entry(args.ledger, entry)
        history.append(entry)
        added += 1
        status = "ok" if entry["ok"] else "failed-run"
        print(f"ingested {path} ({status}, "
              f"{len(entry['metrics'])} metrics)")
    print(f"{added} entries -> {args.ledger}")
    return 0


def cmd_record(args) -> int:
    if args.file == "-":
        raw = json.load(sys.stdin)
        source = "stdin"
    else:
        with open(args.file) as fh:
            raw = json.load(fh)
        source = os.path.basename(args.file)
    entry = record_run(raw, source=source, ledger=args.ledger)
    for line in format_verdicts(entry):
        print(line)
    if not entry["verdicts"]:
        print("no judged metrics (failed run or no directional metrics)")
    return 1 if any(
        v["verdict"] == VERDICT_REGRESS for v in entry["verdicts"].values()
    ) else 0


def cmd_report(args) -> int:
    history = read_ledger(args.ledger)
    if not history:
        print(f"empty ledger: {args.ledger}")
        return 0
    recent = history[-args.last:]
    for entry in recent:
        stamp = time.strftime("%Y-%m-%d %H:%M:%S",
                              time.localtime(entry.get("ts", 0)))
        ok = "ok" if entry.get("ok") else "FAILED"
        print(f"--- {stamp}  {entry.get('source', '?')}  [{ok}]")
        lines = format_verdicts(entry)
        for line in lines:
            print(f"  {line}")
        if not lines and entry.get("ok"):
            print(f"  {len(entry.get('metrics', {}))} metrics, none judged")
    regress = sum(
        1 for e in recent
        for v in e.get("verdicts", {}).values()
        if v["verdict"] == VERDICT_REGRESS
    )
    print(f"{len(recent)} runs shown, {regress} regressions flagged")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--ledger", default=DEFAULT_LEDGER)
    sub = ap.add_subparsers(dest="cmd", required=True)

    ing = sub.add_parser("ingest", help="normalize BENCH_*.json into the ledger")
    ing.add_argument("files", nargs="+")
    ing.set_defaults(fn=cmd_ingest)

    rec = sub.add_parser("record", help="append one run and print verdicts")
    rec.add_argument("file", help="result JSON path, or - for stdin")
    rec.set_defaults(fn=cmd_record)

    rep = sub.add_parser("report", help="show recent verdicts")
    rep.add_argument("--last", type=int, default=10)
    rep.set_defaults(fn=cmd_report)

    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    raise SystemExit(main())
