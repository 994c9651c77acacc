"""Span-based eval-lifecycle tracing + per-thread ring flight recorder.

Every eval's journey — broker enqueue, dequeue-wait, worker scheduler
compute, coalescer queue-wait, the pipelined device launch/resolve hop,
plan submit/apply, ack — is stitched into one causally-ordered record so
the "host orchestration vs device RTT vs queue-wait" split in the 50x
gap (ROADMAP items 1 and 3) is measured, not guessed.

Design constraints, in order:

1. **Always on, bounded.** The flight recorder keeps the last
   ``NOMAD_TPU_TRACE_RING`` spans *per thread* in a ``deque(maxlen=..)``
   ring. Memory is bounded by ring-size x thread-count; there is no
   "tracing build" to forget to enable when a chaos run trips an
   invariant at 3am.
2. **Lock-cheap on the hot path.** The recording thread appends to its
   own ring (``deque.append`` is atomic under the GIL); the registry
   lock is taken only when a thread's ring is *created* and at dump
   time. Span ids come from ``itertools.count`` (also atomic). The
   tier-1 gate in tests/test_trace_overhead.py holds the per-span cost
   under the host-loop floor budget.
3. **Deterministic sampling.** ``NOMAD_TPU_TRACE_SAMPLE`` in [0, 1]
   decides per *trace* (sha256 of the trace id), mirroring the chaos
   injector's seeded-hash discipline, so the same eval id samples the
   same way on replay and a sampled trace is never half-recorded.
   Unsampled spans skip the ring but still feed the per-phase latency
   histograms (``nomad.phase.*``) — bench breakdowns see every eval.

Cross-thread propagation: capture ``current()`` where the context is
ambient (e.g. ``DeviceCoalescer.place`` on the worker thread), carry the
``SpanContext`` on the struct that crosses the boundary (``_Pending``,
``PendingPlan``, the launch ticket), and stitch the far side in with
``record_span(..., ctx=carried)`` — spans may be recorded retroactively
from whichever thread observed their end.
"""

from __future__ import annotations

import hashlib
import itertools
import threading
import time
import weakref
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Any, Dict, Iterator, List, Optional, Tuple

from ..retry import env_float, env_int

# Phase histograms land in the MetricsRegistry under this prefix
# (the per-phase latency breakdown `/v1/metrics` serves).
PHASE_PREFIX = "nomad.phase."

_span_ids = itertools.count(1)  # process-wide; next() is atomic in CPython


@dataclass(frozen=True)
class SpanContext:
    """What crosses a thread/queue boundary: enough to parent a child
    span on the far side. ``trace_id`` is the eval id for eval-lifecycle
    spans, so a context is reconstructible anywhere the eval is."""

    trace_id: str
    span_id: int
    sampled: bool

    def child(self) -> "SpanContext":
        return SpanContext(self.trace_id, next(_span_ids), self.sampled)


class _Config:
    """Mutable knob block, loaded from env once at import and adjustable
    at runtime via :func:`configure` (the ``/v1/trace/config`` endpoint).
    Env names are the contract documented in OBSERVABILITY.md."""

    def __init__(self) -> None:
        self.reload()

    def reload(self) -> None:
        self.enabled = env_int("NOMAD_TPU_TRACE", 1) != 0
        self.sample = min(1.0, max(0.0, env_float("NOMAD_TPU_TRACE_SAMPLE", 1.0)))
        self.ring = max(16, env_int("NOMAD_TPU_TRACE_RING", 4096))

    def as_dict(self) -> Dict[str, Any]:
        return {
            "enabled": self.enabled,
            "sample": self.sample,
            "ring": self.ring,
        }


_cfg = _Config()


def configure(
    enabled: Optional[bool] = None,
    sample: Optional[float] = None,
    ring: Optional[int] = None,
) -> Dict[str, Any]:
    """Adjust tracing at runtime. Returns the effective config."""
    if enabled is not None:
        _cfg.enabled = bool(enabled)
    if sample is not None:
        _cfg.sample = min(1.0, max(0.0, float(sample)))
    if ring is not None:
        _cfg.ring = max(16, int(ring))
    return _cfg.as_dict()


def config() -> Dict[str, Any]:
    return _cfg.as_dict()


def _trace_sampled(trace_id: str) -> bool:
    """Deterministic per-trace sampling decision (seeded-hash, like the
    chaos injector): same trace id → same verdict, across processes."""
    if _cfg.sample >= 1.0:
        return True
    if _cfg.sample <= 0.0:
        return False
    h = hashlib.sha256(trace_id.encode()).digest()
    frac = int.from_bytes(h[:8], "big") / float(1 << 64)
    return frac < _cfg.sample


# ----------------------------------------------------------------------
# Flight recorder


# Rings of threads that have ended are kept, so that a dump still shows
# what a short-lived thread (an HTTP handler, a test's producer) did; the
# oldest go first once this many are held.
MAX_RETIRED_RINGS = 256


class FlightRecorder:
    """Per-thread ring buffers of finished span/event records.

    The writing thread owns its ring; the registry dict is locked only
    on ring creation and when draining for a dump, so recording never
    contends across threads on the hot path.

    A ring is filed under an id of its own, never under the thread's
    ident: the next thread to start reuses an ended thread's ident, and a
    ring filed under it would replace the ended thread's records."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._ring_ids = itertools.count(1)
        self._rings: Dict[int, deque] = {}
        self._thread_names: Dict[int, str] = {}
        # ring id -> its thread (lanes have none and are never retired)
        self._owners: Dict[int, "weakref.ref[threading.Thread]"] = {}
        self._lanes: Dict[str, int] = {}  # lane name -> synthetic tid (< 0)
        self._tls = threading.local()

    def _ring(self) -> deque:
        ring = getattr(self._tls, "ring", None)
        if ring is None or ring.maxlen != _cfg.ring:
            t = threading.current_thread()
            ring = deque(ring or (), maxlen=_cfg.ring)
            self._tls.ring = ring
            with self._lock:
                rid = getattr(self._tls, "ring_id", None)
                if rid is None:
                    rid = self._tls.ring_id = next(self._ring_ids)
                    self._retire_locked(MAX_RETIRED_RINGS)
                self._rings[rid] = ring
                self._thread_names[rid] = t.name
                self._owners[rid] = weakref.ref(t)
        return ring

    def _retire_locked(self, keep: int) -> None:
        """Drop all but the ``keep`` newest rings of ended threads (a
        dict keeps insertion order, and rings are filed as they are made:
        the oldest come first)."""
        retired = []
        for rid, ref in self._owners.items():
            t = ref()
            if t is None or not t.is_alive():
                retired.append(rid)
        for rid in retired[: max(0, len(retired) - keep)]:
            del self._rings[rid], self._thread_names[rid], self._owners[rid]

    def record(self, rec: Dict[str, Any], lane: Optional[str] = None) -> None:
        (self._ring() if lane is None else self._lane(lane)).append(rec)

    def _lane(self, name: str) -> deque:
        """A ring that belongs to no thread: what stops the whole process
        (``runtime.gc_pause``, ``runtime.stall``) gets a row of its own in
        the dump instead of overlapping the spans of whichever thread
        noticed it."""
        with self._lock:
            tid = self._lanes.get(name)
            if tid is None:
                tid = self._lanes[name] = -(len(self._lanes) + 1)
                self._rings[tid] = deque(maxlen=_cfg.ring)
                self._thread_names[tid] = name
            return self._rings[tid]

    def records(self, limit: Optional[int] = None) -> List[Dict[str, Any]]:
        """Snapshot every ring, those of ended threads included, globally
        ordered by start time.  ``tid`` is the ring's id, not the
        thread's ident."""
        with self._lock:
            rings = [(tid, list(ring)) for tid, ring in self._rings.items()]
            names = dict(self._thread_names)
        out: List[Dict[str, Any]] = []
        for tid, recs in rings:
            for r in recs:
                r = dict(r)
                r["tid"] = tid
                r["thread"] = names.get(tid, "?")
                out.append(r)
        out.sort(key=lambda r: r["ts"])
        if limit is not None and limit >= 0:
            out = out[-limit:] if limit else []
        return out

    def clear(self) -> None:
        with self._lock:
            for ring in self._rings.values():
                ring.clear()
            self._retire_locked(0)

    def span_count(self) -> int:
        with self._lock:
            return sum(len(r) for r in self._rings.values())


_recorder = FlightRecorder()


def recorder() -> FlightRecorder:
    return _recorder


# ----------------------------------------------------------------------
# Metrics hookup (per-phase latency histograms)

_default_metrics = None  # MetricsRegistry | None; set by Server.__init__
_default_metrics_lock = threading.Lock()


def set_default_metrics(registry: Any) -> None:
    """Point ambient spans (no explicit ``metrics=``) at a registry.
    ``Server.__init__`` calls this so scheduler-stack spans — which have
    no server handle — still feed that server's phase histograms."""
    global _default_metrics
    with _default_metrics_lock:
        _default_metrics = registry


def count(name: str, by: int = 1, metrics: Any = None, **labels: Any) -> None:
    """Bump a counter of the registry the ambient spans feed (the
    server's), whether or not spans are being recorded: for code that has
    no server handle (the scheduler stack's ``nomad.sched.*``)."""
    reg = metrics if metrics is not None else _default_metrics
    if reg is not None:
        reg.incr(name, by, **labels)


def _observe_phase(name: str, dur: float, metrics: Any) -> None:
    reg = metrics if metrics is not None else _default_metrics
    if reg is not None:
        try:
            reg.timer(PHASE_PREFIX + name).observe(dur)
        except Exception:
            pass  # telemetry must never take down the eval path


# ----------------------------------------------------------------------
# Thread-local span stack (nesting + ambient context)

_stack_tls = threading.local()


def _stack() -> List[Tuple[SpanContext, Dict[str, Any]]]:
    """Open spans of this thread, innermost last: (context, args)."""
    st = getattr(_stack_tls, "stack", None)
    if st is None:
        st = []
        _stack_tls.stack = st
    return st


def add_args(**args: Any) -> None:
    """Attach args to the innermost span open on this thread: what is
    only known once the work is done (rows synced, bytes moved)."""
    st = _stack()
    if st:
        st[-1][1].update(args)


def current() -> Optional[SpanContext]:
    """Context of the innermost span active on *this* thread (what you
    capture before handing work to another thread), or None."""
    st = _stack()
    return st[-1][0] if st else None


def start_trace(trace_id: str) -> SpanContext:
    """Mint a root context for ``trace_id`` (the eval id). Does not push
    anything on the thread stack — pair with ``span(..., ctx=...)`` or
    ``record_span``."""
    return SpanContext(str(trace_id), next(_span_ids), _trace_sampled(str(trace_id)))


def record_span(
    name: str,
    t0: float,
    t1: float,
    ctx: Optional[SpanContext] = None,
    parent: Optional[int] = None,
    metrics: Any = None,
    lane: Optional[str] = None,
    **args: Any,
) -> None:
    """Retroactively record a finished span — the cross-thread stitch.
    ``ctx`` is the carried context; the recorded span is its *child*
    unless ``parent`` overrides. With no ctx the span is ambient
    (unparented, fresh trace id from the name). ``lane`` files the
    record under a named row of the dump instead of this thread's."""
    if not _cfg.enabled:
        return
    if _runtime_spans:
        _flush_runtime_spans()
    if t1 < t0:
        t1 = t0
    _observe_phase(name, t1 - t0, metrics)
    if ctx is None:
        ctx = start_trace("%s#%d" % (name, next(_span_ids)))
        parent_id = 0
    else:
        parent_id = parent if parent is not None else ctx.span_id
    if not ctx.sampled:
        return
    _recorder.record(
        {
            "name": name,
            "ph": "X",
            "ts": t0,
            "dur": t1 - t0,
            "trace": ctx.trace_id,
            "span": next(_span_ids),
            "parent": parent_id,
            "args": args or {},
        },
        lane,
    )


# What trace/runtime.py saw the runtime do: (name, start, end, args) of a
# full collection (``runtime.gc_pause``) or of a wake of the probe that
# came late (``runtime.stall``).  The gc hook runs wherever the collection
# was triggered — possibly inside a registry or recorder lock — so it
# only appends here; the next span recorded from ordinary code (or the
# next dump) turns them into records of the lane ``runtime``.
_runtime_spans: deque = deque(maxlen=256)


def _flush_runtime_spans() -> None:
    while True:
        try:
            name, t0, t1, args = _runtime_spans.popleft()
        except IndexError:
            return
        record_span(name, t0, t1, lane="runtime", **args)


def event(
    name: str,
    ctx: Optional[SpanContext] = None,
    **args: Any,
) -> None:
    """Instantaneous marker (chaos seams, acks, stale-dispatch hits)."""
    if not _cfg.enabled:
        return
    if ctx is None:
        ctx = current()
    if ctx is not None and not ctx.sampled:
        return
    _recorder.record(
        {
            "name": name,
            "ph": "i",
            "ts": time.time(),
            "dur": 0.0,
            "trace": ctx.trace_id if ctx else "",
            "span": next(_span_ids),
            "parent": ctx.span_id if ctx else 0,
            "args": args or {},
        }
    )


@contextmanager
def span(
    name: str,
    ctx: Optional[SpanContext] = None,
    trace_id: Optional[str] = None,
    metrics: Any = None,
    annotate: bool = False,
    cpu: bool = False,
    **args: Any,
) -> Iterator[Optional[SpanContext]]:
    """Timed span, pushed on this thread's stack for automatic nesting.

    Parentage: explicit ``ctx`` (a carried context — this span becomes
    its child) > enclosing span on this thread > root. ``trace_id``
    starts a fresh root trace (the worker's ``eval.process`` entry
    point). Yields the span's own context for hand-off to other threads.

    ``annotate`` also enters a ``jax.profiler.TraceAnnotation`` of the
    same name, so that a profiler session (and only one: it is inert
    otherwise) shows the span on the profiler's own clock beside the
    device's lanes. For the per-launch and per-batch spans, not the
    per-eval ones.

    ``cpu`` also reads this thread's CPU clock at entry and exit and
    stores the difference as ``cpu`` (seconds) beside ``dur``: what of
    the span the thread ran, the rest being what it waited (a lock, the
    GIL, the device, a socket). A reading costs three times a
    ``time.time()`` on Linux proper and a 6 us call under gVisor, whose
    CPU clocks also tick every 10 ms (read sums and means there, not one
    record): for the same few-hundred-a-second spans as ``annotate``; the per-eval spans' CPU is read per thread group
    (``nomad.runtime.cpu_seconds``). ``record_span`` never carries it:
    the interval it files is another thread's.
    """
    if not _cfg.enabled:
        yield None
        return
    ann = _annotation(name, args) if annotate else None
    st = _stack()
    if trace_id is not None:
        parent_id = 0
        my = start_trace(trace_id)
    elif ctx is not None:
        parent_id = ctx.span_id
        my = ctx.child()
    elif st:
        parent_id = st[-1][0].span_id
        my = st[-1][0].child()
    else:
        parent_id = 0
        my = start_trace("%s#%d" % (name, next(_span_ids)))
    st.append((my, args))
    if ann is not None:
        ann.__enter__()
    t0 = time.time()
    c0 = time.thread_time() if cpu else 0.0
    try:
        yield my
    finally:
        c1 = time.thread_time() if cpu else 0.0
        t1 = time.time()
        if ann is not None:
            ann.__exit__(None, None, None)
        # Pop *our* frame even if a nested span leaked (defensive).
        while st and st[-1][0] is not my:
            st.pop()
        if st:
            st.pop()
        _observe_phase(name, t1 - t0, metrics)
        if my.sampled:
            rec = {
                "name": name,
                "ph": "X",
                "ts": t0,
                "dur": t1 - t0,
                "trace": my.trace_id,
                "span": my.span_id,
                "parent": parent_id,
                "args": args or {},
            }
            if cpu:
                rec["cpu"] = c1 - c0
            _recorder.record(rec)
        if _runtime_spans:
            _flush_runtime_spans()


_TraceAnnotation = None  # jax.profiler's, imported on first use


def _annotation(name: str, args: Dict[str, Any]) -> Any:
    global _TraceAnnotation
    if _TraceAnnotation is None:
        from jax.profiler import TraceAnnotation

        _TraceAnnotation = TraceAnnotation
    return _TraceAnnotation(name, **args)


# ----------------------------------------------------------------------
# Introspection helpers used by the API / CLI / dump hooks


def dump(limit: Optional[int] = None) -> List[Dict[str, Any]]:
    if _runtime_spans and _cfg.enabled:
        _flush_runtime_spans()
    return _recorder.records(limit=limit)


def clear() -> None:
    _recorder.clear()


def traces_by_id(records: Optional[List[Dict[str, Any]]] = None) -> Dict[str, List[Dict[str, Any]]]:
    """Group records by trace id (drops ambient '' traces of events)."""
    grouped: Dict[str, List[Dict[str, Any]]] = {}
    for r in records if records is not None else dump():
        grouped.setdefault(r.get("trace", ""), []).append(r)
    return grouped
