"""Dispatch coalescer — pipelined device dispatch for concurrent selects.

If every worker's ``select()`` held the global DEVICE_LOCK across its own
kernel dispatch and fetched its result buffers one by one, each fetch
would cost a full synchronous device→host round-trip, workers would
serialize behind one another, and the batched kernel would sit unused.

This module makes the batched kernel THE live path: workers enqueue
compiled placement requests and block on a future; a dispatch thread
drains the queue, stacks up to ``max_lanes`` requests, and issues ONE
``ops.kernels.fused_place_batch_live`` dispatch (the same placement body
over a mesh, ``sharded_fused_place_batch_live``, when dispatches span one)
whose packed result costs ONE fetch.

A dispatch thread that performed that fetch itself (``np.asarray`` blocks
until the device is done) would keep exactly one dispatch in flight, so
the loop is a producer/consumer pipeline:

* the **dispatch thread** only launches — it relies on JAX async dispatch
  and never calls ``np.asarray``.  Up to ``pipeline_depth`` launches
  (``PIPELINE_DEPTH`` unless the constructor says otherwise) overlap; the
  bounded ticket queue provides backpressure.
* a **resolver thread** performs the blocking device→host fetch for each
  in-flight ticket and completes the ``_Pending`` futures in launch order.

Because overlapped dispatches read a matrix that plans committed during
their flight may mutate, each ticket records ``matrix.version`` at launch;
a version mismatch at resolve time counts into ``stale_dispatches``.
Correctness does not depend on the count: stale-read placements are
re-checked by the serialized plan applier's authoritative re-verify
(server/plan_apply.py ``_evaluate``) exactly as optimistic-worker plans
already are.

Shape discipline (SURVEY.md §7 hard-part e — p99 means no recompiles):
every dispatch uses the SAME static shapes — ``max_lanes`` lanes (short
batches padded by memset of the preallocated staging buffers) and a
``PLACEMENT_CHUNK``-long output (callers take the first rows they asked
for) — so one executable per ``Features`` variant serves every batch
size; a recompile costs seconds.  A launch is ONE jitted call that hands
jax the resident matrix, two packed buffers, the three node-axis lane
buffers and the carry: the 30-odd small lane operands are views of the two
packs on the host and the placement program unpacks them at its entry
(``kernels.unpack_launch``), since a launch costs its thread by the call and
by the buffer, not by the byte.  What the shapes do not fix is the work:
the fused kernel's two loops take their trip counts from the staged
``lane_steps`` operand (each live lane's ``n_live``, 0 for a dead lane),
so a launch scores all nodes once per placement its widest lane asked
for, not ``PLACEMENT_CHUNK`` times (a full-length launch of 64 lanes x
10,240 nodes measured 12.2 ms on a v5e, 0.5 ms of it per step: PERF.md).

The reference's analog: many schedulers walk nodes concurrently and the
plan applier serializes commits (worker.go:49-53, plan_apply.go:49-69).
The optimistic-concurrency contract is unchanged and the applier's
re-verify stays authoritative, but the selects coalesced into ONE launch
no longer race each other: inside the kernel the lanes take their picks
in lane order, and a lane whose best node an earlier lane has filled
takes its best node that is left (``kernels._fused_place_batch_impl``;
``lane_repicks`` counts those picks, ``verify_conflicts`` the ones for
which no node was left).  Nor do launches in flight race each other as
they did: the picks whose plans the applier has not decided yet are kept in
a claims ledger (``scheduler/claims.py``; the resolver enters them, the
applier and the workers release them) and every launch takes the live ones
as an overlay under its claims image, so its lanes pass over the nodes a
launch before it took.  The picks of a launch whose result had not reached
the host when the next one was enqueued (``launches_unresolved_predecessor``
counts those launches) are in no ledger yet: they reach the next launch on
the device.  Every launch writes its own claims block as a second output of
the placement program and is handed the carry of the launch before it (its
block and the ``CHAIN_DEPTH - 1`` before: one ``jax.Array`` in, one out,
never fetched); ``_overlay`` decides, in one step with its read of the
ledger, which carried blocks are live (``chained_launches``,
``chained_rows_total``).  What a launch still cannot see is an unresolved
launch older than the carry holds (``chain_overflow``) or one that took
another route (the numpy twin while the breaker is open, another mesh); the
applier's re-verify catches what is left.
"""

from __future__ import annotations

import logging
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from .. import trace
from ..obs.breaker import (
    STALL_SLOW,
    STALL_WEDGED,
    DeviceBreaker,
    DeviceWedgedError,
    watchdog_fetch,
)
from ..ops import kernels
from ..ops.encode import RequestSlab, SchedRequest, packed_rows
from ..state.matrix import DEVICE_LOCK
from .claims import (
    CHAIN_DEPTH,
    OVERLAY_ROWS,
    ClaimsLedger,
    Launch,
    empty_carry,
)

log = logging.getLogger(__name__)

# Sparse plan-delta capacity per request; selects with more touched rows
# fall back to the solo dispatch path.
MAX_DELTA_ROWS = 32

# Overlapping dispatches kept in flight when the constructor names no
# depth: the dispatch loop waits for a slot 0.01-0.02 % of a window at this
# depth (PERF.md section 6, PR 24), so nothing is left for a knob to tune.
PIPELINE_DEPTH = 8


def lane_step_count(n_live: int, scan_length: int) -> int:
    """Scan steps a lane runs for a caller that consumes ``n_live``
    placements: 0 (not said) or more than the scan holds mean all of it."""
    return min(n_live or scan_length, scan_length)


@dataclass
class PlaceOutcome:
    """Unpacked per-request result (numpy, host-side)."""

    rows: np.ndarray  # (P,) i32
    scores: np.ndarray  # (P,) f32
    binpack: np.ndarray  # (P,) f32
    preempted: np.ndarray  # (P,) f32, kernels.PACKED_PREEMPT (0.0 = no)
    nodes_evaluated: np.ndarray  # (P,) i32
    nodes_filtered: np.ndarray  # (P,) i32
    nodes_exhausted: np.ndarray  # (P,) i32
    # Device-resident AllocsFit re-verify verdicts ((P,) bool — True =
    # placement survives the sequential cross-lane re-check at
    # `matrix_version`) and the matrix version the dispatch was scored
    # against. At an unchanged version a False verdict is a guaranteed
    # plan-applier rejection; the applier against live state stays
    # authoritative either way.
    fit_verified: np.ndarray
    matrix_version: int


@dataclass
class _DeviceOp:
    fn: "callable"
    done: threading.Event = field(default_factory=threading.Event)
    result: object = None
    error: Optional[BaseException] = None


@dataclass
class _Pending:
    request: SchedRequest
    delta_rows: np.ndarray  # (MAX_DELTA_ROWS,) i32, -1 padded
    delta_vals: np.ndarray  # (MAX_DELTA_ROWS, 3) f32
    tg_count: np.ndarray  # (N,) i32
    spread_counts: np.ndarray  # (S, V) f32
    penalty: np.ndarray  # (N,) bool
    class_elig: np.ndarray  # (pad,) bool
    host_mask: np.ndarray  # (N,) bool
    # Placements the caller will actually consume (0 = all scan_length):
    # the lane's scan stops after this many steps (lane_step_count).
    n_live: int = 0
    enqueued_at: float = 0.0
    done: threading.Event = field(default_factory=threading.Event)
    outcome: Optional[PlaceOutcome] = None
    error: Optional[BaseException] = None
    # Trace context captured on the submitting worker's thread (place());
    # the dispatch thread stitches coalescer.queue_wait onto it and the
    # resolver thread stitches coalescer.device — the launch→resolver hop.
    trace_ctx: Optional[trace.SpanContext] = None
    # The eval this lane places for ("" = none that holds claims) and what
    # its plan advertises on each of ``delta_rows`` to the launches after
    # this one: the deltas with no eviction credited, never below zero
    # (scheduler/claims.py; place() fills in the deltas' positive part).
    eval_id: str = ""
    claim_vals: Optional[np.ndarray] = None  # (MAX_DELTA_ROWS, 3) f32


@dataclass
class _Ticket:
    """One in-flight dispatch: the un-fetched packed result, its lanes, and
    the matrix version its inputs were synced at."""

    packed: object
    entries: List[_Pending]
    matrix_version: int
    launched_at: float = 0.0
    # True when this launch is the half-open breaker's single probe; its
    # fetch verdict decides whether the device path is re-admitted.
    canary: bool = False
    # The chain's record of this launch (scheduler/claims.py): resolved when
    # its lanes' claims are in the ledger, or when it is given up.
    launch: Launch = field(default_factory=lambda: Launch((), 0))


class DeviceCoalescer:
    """The single dispatch port for the shared device matrix."""

    def __init__(
        self,
        matrix,
        max_lanes: int = 64,
        scan_length: Optional[int] = None,
        linger_s: float = 0.002,
        pipeline_depth: Optional[int] = None,
        n_device_shards: Optional[int] = None,
        metrics=None,
    ):
        from .stack import PLACEMENT_CHUNK

        self.matrix = matrix
        self.max_lanes = max_lanes
        self.scan_length = scan_length or PLACEMENT_CHUNK
        self.linger_s = linger_s
        self.pipeline_depth = pipeline_depth or PIPELINE_DEPTH
        # Multi-chip: when >1, dispatches run the fused kernel's one body
        # (parallel/sharding.py sharded_fused_place_batch_live)
        # over a ('batch', 'node') mesh — the live server path the dryrun
        # certifies.  None = auto: all visible devices on real
        # accelerators, single-device on CPU (the virtual 8-CPU rig is a
        # test harness, not a deployment; tests opt in explicitly).
        self.n_device_shards = n_device_shards
        self.metrics = metrics  # optional MetricsRegistry (the server's)
        self._mesh = None
        self._sharded_fused_fn = None
        # Chaos shard.partition bookkeeping: shard -> node ids darkened by
        # the seam (heal_shard_partitions re-lights them).
        self._dark_shards: Dict[int, List[str]] = {}
        self._queue: List[_Pending] = []
        # Arbitrary device closures (system feasibility, bulk plan verify,
        # oversized-delta solo selects) executed on the dispatch thread so
        # the live server has exactly ONE device-LAUNCHING thread
        # (state/matrix.py DEVICE_LOCK note).  The resolver thread only
        # fetches already-launched results.
        self._ops: List["_DeviceOp"] = []
        self._cond = threading.Condition()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._resolver: Optional[threading.Thread] = None
        self._tickets: Optional["queue.Queue"] = None
        self._depth_sem: Optional[threading.Semaphore] = None
        # Preallocated host staging, one set per pipeline slot: (max_lanes,
        # N) lane buffers (row writes instead of per-dispatch np.stack,
        # lane padding by memset — see _staging) plus a (max_lanes, …)
        # request operand slab.  A launch hands these numpy buffers to jax,
        # which may still be reading them (host→device transfer on an
        # accelerator, zero-copy aliasing on CPU) until the dispatch's
        # result has been fetched — so a set is only rewritten once the
        # dispatch that last used it has resolved.  Tickets resolve in
        # launch order and a launch holds one of pipeline_depth permits,
        # so rotating through pipeline_depth sets guarantees exactly that.
        self._stage: List[Optional[Dict[str, np.ndarray]]] = (
            [None] * self.pipeline_depth
        )
        self._req_slabs = [
            RequestSlab(max_lanes) for _ in range(self.pipeline_depth)
        ]
        self._stage_slot = 0
        # Gauges/counters (ints under the GIL; exact enough for telemetry).
        self.dispatches = 0
        self.coalesced_requests = 0
        self.stale_dispatches = 0
        self.inflight = 0
        # Device cost attribution (surfaced as nomad.kernel.* gauges by
        # the server): solo escape-hatch launches and host→device operand
        # traffic staged per batched dispatch.
        self.solo_ops = 0
        self.operand_bytes_total = 0
        # Jitted calls the launches made (one each: the placement program
        # unpacks the two packs itself), and the (route, layouts) of the
        # last launch's program.
        self.device_calls = 0
        self._unpack_variant = None
        # Batched-launch accounting: launches and live lanes
        # (launches-per-eval = fused_dispatches / fused_lanes),
        # verify-column conflicts (placements an earlier lane's plan will
        # make the applier reject: after the in-launch resolution, the
        # picks that found no node left under the launch's claims), the
        # picks the resolution moved to another node than the lane's own
        # arg-max, and the occupancy-features ratchet —
        # a monotone widening union, so each Features variant compiles at
        # most once per process instead of flapping per batch.
        self.fused_dispatches = 0
        self.fused_lanes = 0
        # Scan steps the launches ran: each launch adds its widest
        # lane's step count, worked out on the host from the batch's
        # n_live (steps a launch = scan_steps_total / fused_dispatches).
        self.scan_steps_total = 0
        self.verify_conflicts = 0
        self.lane_repicks = 0
        # Lanes launched with a distinct_property slot live, and the picks
        # such a limit moved off a better-scoring node (PACKED_FILTERED).
        self.distinct_property_lanes = 0
        self.distinct_property_blocked = 0
        self.picks_placed = 0
        self.preempt_picks = 0
        # The in-flight claims overlay (scheduler/claims.py): the ledger,
        # the rows handed to launches, and the launches enqueued while an
        # earlier launch's result was not yet on the host (its picks are in
        # no ledger yet: the race that is left).
        self.claims = ClaimsLedger()
        self.overlay_rows_total = 0
        self.launches_unresolved_predecessor = 0
        # Claims chained on the device: the carry the last launch wrote
        # (its own claims block and the CHAIN_DEPTH - 1 before it; a
        # jax.Array that never visits the host, numpy on the twin's route),
        # the route that wrote it, and the launches not yet known resolved,
        # newest first: the first CHAIN_DEPTH are the carry's blocks in
        # order.  Counted: launches enqueued with a live carried block, the
        # rows of those blocks (on the host, when the block's launch
        # resolves), launches with an unresolved predecessor the carry no
        # longer holds.
        self._carry = self._carry_route = None
        self._launches: List[Launch] = []
        self.chained_launches = 0
        self.chained_rows_total = 0
        self.chain_overflow = 0
        self.feature_recompiles = 0
        self._features = None
        # Device→host result traffic for fused/sharded dispatches (the
        # packed (B, P, 8) fetch — O(lanes·placements), NEVER node-axis
        # shaped; exported as nomad.topk.host_bytes_total).  The parity
        # test pins it to the winner-row budget to prove no (N,)-shaped
        # array rides the fetch.
        self.topk_host_bytes_total = 0
        # Device fault domain (obs/breaker.py): the resolver classifies
        # every fetch ok/slow/wedged under the watchdog deadline; the
        # breaker gates _dispatch between the device path and the numpy
        # twin.  Wedged tickets count here (their futures raise
        # DeviceWedgedError); shard evacuations re-home the matrix onto
        # the surviving shards.
        self.breaker = DeviceBreaker(metrics=metrics)
        self.wedged_dispatches = 0
        self.shard_evacuations = 0
        # Shard count before the first evacuation (heal restores it);
        # None = no evacuation active.
        self._pre_evac_shards: Optional[int] = None
        self._pre_evac_device_shards: Optional[int] = None
        # TSan-lite (lint/tsan.py): lockset checking on the pending queue
        # and device-op list when a test enabled the sanitizer.
        from ..lint.tsan import maybe_instrument

        maybe_instrument("coalescer", self)

    # ------------------------------------------------------------------

    def start(self) -> None:
        if self._thread is not None and self._thread.is_alive():
            return  # leadership can cycle; one dispatch thread only
        self._stop.clear()
        # A fresh leadership term probes the device fresh — a breaker
        # left open by the previous term would silently pin the new one
        # to the degraded path.
        self.breaker.reset()
        # The pipeline bound: a launch consumes a permit, the resolver
        # returns it after the fetch, so exactly pipeline_depth dispatches
        # overlap (depth 1 = the old serial behavior).  The ticket queue
        # itself never blocks — its occupancy is bounded by the permits.
        self._depth_sem = threading.BoundedSemaphore(self.pipeline_depth)
        self._tickets = queue.Queue()
        self._resolver = threading.Thread(
            target=self._resolve_loop, name="resolver-coalescer", daemon=True
        )
        self._resolver.start()
        self._thread = threading.Thread(
            target=self._run, name="device-coalescer", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        with self._cond:
            self._cond.notify_all()
        if self._thread:
            self._thread.join(timeout=10)

    def inflight_depth(self) -> int:
        """Dispatches launched but not yet resolved (pipeline occupancy)."""
        return self.inflight

    # ------------------------------------------------------------------

    def place(
        self,
        request: SchedRequest,
        delta_rows: np.ndarray,
        delta_vals: np.ndarray,
        tg_count: np.ndarray,
        spread_counts: np.ndarray,
        penalty: np.ndarray,
        class_elig: np.ndarray,
        host_mask: np.ndarray,
        timeout: float = 600.0,  # must cover a cold TPU jit compile
        n_live: int = 0,
        eval_id: str = "",
        claim_vals: Optional[np.ndarray] = None,
    ) -> PlaceOutcome:
        """Submit one placement request; blocks until its batch lands.
        Every output is ``scan_length`` long — take ``rows[:k]``.  Only
        the first ``n_live`` steps are computed (0 = all): the rows past
        them read -1 and charge no usage.  ``eval_id`` names the eval the
        picks are entered under in the claims ledger (if a worker holds it
        open), ``claim_vals`` what its plan advertises on ``delta_rows``."""
        p = _Pending(
            request=request,
            delta_rows=delta_rows,
            delta_vals=delta_vals,
            tg_count=tg_count,
            spread_counts=spread_counts,
            penalty=penalty,
            class_elig=class_elig,
            host_mask=host_mask,
            n_live=n_live,
            enqueued_at=time.time(),
            trace_ctx=trace.current(),
            eval_id=eval_id,
            claim_vals=(
                claim_vals if claim_vals is not None or not eval_id
                else np.maximum(delta_vals, 0.0)
            ),
        )
        with self._cond:
            if self._stop.is_set():
                raise RuntimeError("coalescer stopped")
            self._queue.append(p)
            self._cond.notify()
        if not p.done.wait(timeout=timeout):
            raise TimeoutError("coalescer dispatch timed out")
        if p.error is not None:
            raise p.error
        assert p.outcome is not None
        return p.outcome

    def run_device_op(self, fn, timeout: float = 600.0):
        """Execute ``fn()`` on the dispatch thread and return its result.

        The escape hatch for device work that doesn't fit the batched
        placement shape (system feasibility sweeps, bulk plan verification,
        oversized-delta selects): they still run on the one device thread
        instead of racing it."""
        op = _DeviceOp(fn=fn)
        self.solo_ops += 1
        with self._cond:
            if self._stop.is_set():
                raise RuntimeError("coalescer stopped")
            self._ops.append(op)
            self._cond.notify()
        if not op.done.wait(timeout=timeout):
            raise TimeoutError("device op timed out")
        if op.error is not None:
            raise op.error
        return op.result

    def sync_arrays(self):
        """The device snapshot the next launch would read — mesh-resident
        when dispatches are sharded — synced on the dispatch thread."""

        def op():
            if self._resolve_sharding() > 1:
                return self.matrix.sync_sharded(self._mesh)
            return self.matrix.sync()

        return self.run_device_op(op)

    # ------------------------------------------------------------------

    def _state(self, name: str, **args):
        """One state of the dispatch or resolver loop as a span: ambient
        (per launch, not per eval), on the thread that is in that state,
        annotated, so a profiler session shows it beside the device's
        lanes, and with the thread's CPU beside its duration, so that the
        state's work is told from its waiting (OBSERVABILITY.md, "Span
        taxonomy")."""
        return trace.span(
            name, metrics=self.metrics, annotate=True, cpu=True, **args
        )

    def _run(self) -> None:
        """Dispatch (producer) loop: build batches, launch, hand tickets to
        the resolver.  Never blocks on a device→host fetch."""
        from ..chaos import inject

        while True:
            self._drain_ops()
            batch = self._next_batch()
            if batch is None and self._stop.is_set():
                self._shutdown_pipeline()
                return
            if not batch:
                continue
            inject("coalescer.dispatch", lanes=len(batch))
            trace.event("seam.coalescer.dispatch", lanes=len(batch))
            # Wait for a pipeline slot BEFORE launching: the permit bounds
            # overlapping latency windows (and how stale an in-flight read
            # can get).  Requests arriving during the wait coalesce into
            # the NEXT batch — the batch itself is already sealed.
            with self._state("coalescer.slot_wait", inflight=self.inflight):
                self._depth_sem.acquire()
            waited = time.time()
            # Stitch each lane's enqueue→launch wait onto its eval trace
            # (carried here from the worker thread on _Pending.trace_ctx).
            for p in batch:
                if p.trace_ctx is not None:
                    trace.record_span(
                        "coalescer.queue_wait",
                        p.enqueued_at,
                        waited,
                        ctx=p.trace_ctx,
                        metrics=self.metrics,
                    )
            # Device fault domain: while the breaker is open, dispatches
            # degrade to the numpy twin (placements keep flowing at
            # reduced throughput); half-open admits exactly one canary
            # launch whose fetch verdict decides re-admission.
            allowed, canary = self.breaker.allow_device_dispatch()
            if not allowed:
                self.breaker.note_degraded()
            try:
                with self._state("coalescer.launch", lanes=len(batch)):
                    packed, version = self._dispatch(
                        batch, degraded=not allowed
                    )
            except BaseException as exc:  # noqa: BLE001
                if canary:
                    # The probe died before producing a fetch verdict —
                    # release the slot so half-open can retry.
                    self.breaker.cancel_canary()
                # The call may have consumed the carry it was handed.
                self._drop_carry()
                self._depth_sem.release()
                for p in batch:
                    p.error = exc
                    p.done.set()
                continue
            self.dispatches += 1
            self.coalesced_requests += len(batch)
            self.inflight += 1
            self._tickets.put(
                _Ticket(
                    packed, batch, version, launched_at=waited,
                    canary=canary, launch=self._launches[0],
                )
            )

    def _shutdown_pipeline(self) -> None:
        """Stop path: fail queued work, let the resolver drain in-flight
        tickets (their callers are still blocked on real futures), then
        join it."""
        with self._cond:
            leftover_ops, self._ops = self._ops, []
            leftover_q, self._queue = self._queue, []
        err = RuntimeError("coalescer stopped")
        for op in leftover_ops:
            op.error = err
            op.done.set()
        for p in leftover_q:
            p.error = err
            p.done.set()
        self._tickets.put(None)  # sentinel after every real ticket
        self._resolver.join(timeout=10)
        if self._resolver.is_alive():
            # The resolver missed its join window (a fetch past every
            # watchdog bound, or the watchdog disabled): fail whatever is
            # still queued from here so no caller blocks past shutdown.
            self._fail_queued_tickets(err)

    def _fail_queued_tickets(self, err: BaseException) -> None:
        """Drain the ticket queue and fail every undone future — the
        no-caller-blocks-past-shutdown guarantee.  Pipeline accounting
        mirrors _resolve_loop's finally block so the dispatch loop never
        waits on a permit that will not come back."""
        while True:
            try:
                ticket = self._tickets.get_nowait()
            except queue.Empty:
                return
            if ticket is None:
                continue
            if ticket.canary:
                self.breaker.cancel_canary()
            self.claims.register_launch(ticket.launch)
            for p in ticket.entries:
                if not p.done.is_set():
                    p.error = err
                    p.done.set()
            self.inflight -= 1
            try:
                self._depth_sem.release()
            except ValueError:
                pass  # bounded; resolver may have already released it
            with self._cond:
                self._cond.notify_all()

    def _resolve_loop(self) -> None:
        """Resolver (consumer) loop: the ONLY place the live path blocks on
        a device→host fetch.  Tickets complete in launch order."""
        try:
            while True:
                ticket = self._tickets.get()
                if ticket is None:
                    return
                try:
                    self._resolve(ticket)
                except BaseException as exc:  # noqa: BLE001
                    # _resolve guards the fetch itself; this catches
                    # anything after it (outcome unpack, metrics).  Fail
                    # the lanes and keep the resolver alive — pipeline
                    # accounting below must run no matter what, or the
                    # dispatch loop deadlocks on a permit that will never
                    # come back.
                    for p in ticket.entries:
                        if not p.done.is_set():
                            p.error = exc
                            p.done.set()
                finally:
                    # A launch that failed, wedged or raised entered
                    # nothing: its block stops counting all the same.
                    self.claims.register_launch(ticket.launch)
                    self.inflight -= 1
                    self._depth_sem.release()
                    with self._cond:
                        # Wake an idle dispatch loop waiting to quiesce.
                        self._cond.notify_all()
        finally:
            # Resolver exit — clean (sentinel) or death: every in-flight
            # future must still complete, or its caller blocks forever.
            self._fail_queued_tickets(RuntimeError("coalescer stopped"))

    def _drain_ops(self) -> None:
        while True:
            with self._cond:
                if not self._ops:
                    return
                op = self._ops.pop(0)
            try:
                with self._state("coalescer.device_op"):
                    op.result = op.fn()
            except BaseException as exc:  # noqa: BLE001
                op.error = exc
            op.done.set()

    def _next_batch(self) -> Optional[List[_Pending]]:
        with self._cond:
            if not self._queue:
                # Untimed wait: every transition the predicate watches
                # notifies _cond — place()/run_device_op() on enqueue,
                # stop() on shutdown, and the resolver's try/finally
                # guarantees its wake-up even when _resolve raises, so
                # there is no lost-notify hole left to poll around
                # (lint rule L004).
                with self._state("coalescer.idle"):
                    self._cond.wait_for(
                        lambda: bool(self._queue)
                        or bool(self._ops)
                        or self._stop.is_set(),
                    )
            queued = len(self._queue)
            if not queued:
                return None
        # Linger briefly so concurrent workers land in one dispatch.  The
        # fake-device backend answers synchronously, so lingering would only
        # add serial latency on the one dispatch thread — requests still
        # coalesce while a dispatch is in progress.
        from ..ops import fake_device

        if self.linger_s and not fake_device.enabled():
            with self._state("coalescer.linger", queued=queued):
                self._stop.wait(self.linger_s)
        with self._cond:
            batch = self._queue[: self.max_lanes]
            del self._queue[: len(batch)]
        return batch or None

    # ------------------------------------------------------------------

    def _resolve_sharding(self) -> int:
        """Decide (once) how many devices dispatches span, and their
        layout: every visible device on an accelerator (one on a CPU
        unless told otherwise), laid out by ``mesh_layout`` from the
        device count and the matrix's capacity (four chips, 102,400
        rows: batch 2 x node 2)."""
        if self.n_device_shards is None:
            import jax

            devs = jax.devices()
            self.n_device_shards = (
                len(devs) if devs[0].platform != "cpu" and len(devs) > 1
                else 1
            )
        if self.n_device_shards > 1 and self._sharded_fused_fn is None:
            from ..parallel.sharding import (
                make_mesh,
                mesh_layout,
                node_shard_count,
                sharded_fused_place_batch_live,
            )

            batch, _node = mesh_layout(
                self.n_device_shards, int(self.matrix.capacity)
            )
            self._mesh = make_mesh(self.n_device_shards, batch=batch)
            self._sharded_fused_fn = sharded_fused_place_batch_live(
                self._mesh, self.scan_length
            )
            node_shards = node_shard_count(self._mesh)
            # Home rows to their mesh shard so claims balance across the
            # node axis and growth never migrates a row between shards.
            # (Skipped while an evacuation is active: the survivor layout
            # relayout_shards built IS the homing — re-partitioning here
            # would undo it.)
            if (
                node_shards > 1
                and self._pre_evac_shards is None
                and self.matrix.capacity % node_shards == 0
            ):
                self.matrix.set_shard_count(node_shards)
                if self.metrics is not None:
                    # The server registered shard_rows for the init-time
                    # partition; re-register for the homed mesh width.
                    for s in range(node_shards):
                        self.metrics.gauge_fn(
                            "nomad.matrix.shard_rows",
                            lambda s=s: (
                                self.matrix.shard_row_counts()[s]
                                if s < self.matrix.shard_count else 0
                            ),
                            shard=s,
                        )
            log.info(
                "coalescer: multi-chip dispatch over mesh %s",
                dict(zip(self._mesh.axis_names, self._mesh.devices.shape)),
            )
        return self.n_device_shards

    def mesh_shape(self) -> Tuple[int, int]:
        """(batch, node) widths of the mesh dispatches span: what
        _resolve_sharding chose, (1, 1) on one device or before the first
        dispatch has resolved it (the nomad.mesh.* gauges)."""
        mesh = self._mesh
        if mesh is None:
            return 1, 1
        return int(mesh.shape["batch"]), int(mesh.shape["node"])

    def _darken_shard(self) -> None:
        """Chaos ``shard.partition`` effect (kind 'dark'): mark every node
        homed on the most-populated shard ineligible — the authoritative-
        state analog of losing a whole mesh shard.  Deterministic target
        (highest claimed-row count, lowest index on ties) so seeded
        schedules replay identically."""
        counts = self.matrix.shard_row_counts()
        target = max(range(len(counts)), key=lambda s: (counts[s], -s))
        ids = self.matrix.shard_nodes(target)
        for nid in ids:
            self.matrix.set_eligibility(nid, False)
        self._dark_shards.setdefault(target, []).extend(ids)
        trace.event(
            "seam.shard.partition.dark", shard=target, nodes=len(ids)
        )

    def heal_shard_partitions(self) -> List[int]:
        """Re-light every shard darkened by the partition seam; returns the
        healed shard indices (chaos scenarios assert invariants after)."""
        healed = sorted(self._dark_shards)
        for _shard, ids in sorted(self._dark_shards.items()):
            for nid in ids:
                self.matrix.set_eligibility(nid, True)
        self._dark_shards.clear()
        return healed

    def _lose_shard(self) -> None:
        """Chaos ``shard.loss`` effect (kind 'lost'): evacuate the
        most-populated home shard — the same deterministic target rule as
        _darken_shard (highest claimed-row count, lowest index on ties)
        so seeded schedules replay identically."""
        if int(getattr(self.matrix, "shard_count", 1)) <= 1:
            return  # dense layout — nothing to evacuate
        counts = self.matrix.shard_row_counts()
        target = max(range(len(counts)), key=lambda s: (counts[s], -s))
        self.evacuate_shard(target)

    def evacuate_shard(self, shard: int) -> int:
        """Evacuate a lost shard: the node matrix re-lays-out across the
        survivors (state/matrix.py ``relayout_shards`` replays the claim
        policy over nodes in row order, so the result is bit-identical to
        a from-scratch layout on the surviving shards — the PARITY.md
        evacuation proof).  In-flight tickets that launched against the
        old layout invalidate through the matrix version bump + remap
        window, exactly like growth relocations; the compiled sharded
        entry points drop so the next dispatch re-resolves against the
        survivor mesh.  Returns the surviving shard count."""
        with DEVICE_LOCK:
            before = int(self.matrix.shard_count)
            if before <= 1:
                raise ValueError("evacuation requires shard_count > 1")
            if self._pre_evac_shards is None:
                self._pre_evac_shards = before
                self._pre_evac_device_shards = self.n_device_shards
            self.matrix.evacuate_shard(shard)
            survivors = int(self.matrix.shard_count)
            if self.n_device_shards is not None and self.n_device_shards > 1:
                self.n_device_shards -= 1
            self._mesh = None
            self._sharded_fused_fn = None
        self.shard_evacuations += 1
        self.breaker.note_evacuation()
        trace.event(
            "seam.shard.loss.evacuated", shard=shard, survivors=survivors
        )
        if self.metrics is not None:
            self.metrics.incr("nomad.coalescer.shard_evacuations")
        return survivors

    def heal_shard_evacuations(self) -> Optional[int]:
        """Re-admit evacuated shards (chaos ``heal``): a full re-layout
        back to the pre-evacuation shard count, through the same remap
        mechanism as the evacuation itself.  Returns the restored shard
        count, or None when no evacuation is active."""
        restored = self._pre_evac_shards
        if restored is None:
            return None
        with DEVICE_LOCK:
            self.matrix.relayout_shards(restored)
            self._pre_evac_shards = None
            self.n_device_shards = self._pre_evac_device_shards
            self._mesh = None
            self._sharded_fused_fn = None
        trace.event("seam.shard.loss.healed", restored=restored)
        return restored

    def _ratchet_features(self, slab: RequestSlab, k: int):
        """The occupancy-features ratchet: a monotone widening union, so
        each Features variant compiles at most once per process instead of
        flapping per batch — a narrow batch after a wide one reuses the
        wide executable."""
        feats = kernels.features_of(slab.live_view(k))
        widened = (
            feats if self._features is None else self._features.widen(feats)
        )
        if widened != self._features:
            self.feature_recompiles += 1
            self._features = widened
        return self._features

    def _staging(self, n: int, cw: int, sc_shape):
        """The next pipeline slot's preallocated host staging: (lane
        buffers, request slab).  Lanes write rows in place; unused lanes
        are padded by memset — no per-dispatch np.stack allocations, no
        filler _Pending objects.  A slot's buffers are rebuilt only when
        the matrix grows or the class-pad bucket shifts.  Call once per
        launch, after its pipeline permit is held (see __init__)."""
        slot = self._stage_slot
        self._stage_slot = (slot + 1) % self.pipeline_depth
        st = self._stage[slot]
        if (
            st is None
            or st["host_mask"].shape[1] != n
            or st["class_elig"].shape[1] != cw
            or st["spread_counts"].shape[1:] != sc_shape
        ):
            lanes = self.max_lanes
            # The small lane operands are views of one buffer, handed to
            # jax as one operand (kernels.unpack_launch gives them back,
            # inside the placement program).
            # The claims overlay is one flat list to the program; it rides
            # the pack as a few rows a lane (no device buffer of its own).
            # So do the chain's flags (kernels.chain_flags: the lane holds
            # claims; then, as one flat list, carried block d is live) and
            # what a lane's plan advertises on its delta rows.
            ov = -(-OVERLAY_ROWS // lanes)
            pack, small, layout = packed_rows(lanes, [
                ((cw,), bool), (sc_shape, np.float32),
                ((MAX_DELTA_ROWS,), np.int32),
                ((MAX_DELTA_ROWS, 3), np.float32), ((), np.int32),
                ((ov,), np.int32), ((ov, 3), np.float32),
                ((MAX_DELTA_ROWS, 3), np.float32),
                ((1 - (-CHAIN_DEPTH // lanes),), bool),
            ])
            st = self._stage[slot] = {
                "host_mask": np.zeros((lanes, n), bool),
                "tg_count": np.zeros((lanes, n), np.int32),
                "penalty": np.zeros((lanes, n), bool),
                "pack": pack, "layout": layout,
                **dict(zip(kernels.LANE_FIELDS, small)),
            }
            st["class_elig"][:] = True
            st["delta_rows"][:] = -1
            st["overlay_rows"][:] = -1
        return st, self._req_slabs[slot]

    def _sync_matrix(self, n_shards: int, degraded: bool):
        """The snapshot one launch reads: (arrays, sharded arrays, the
        matrix version it holds everything up to and nothing after,
        node-axis width) — dirty rows go host → device here."""
        from ..ops import fake_device

        mx = self.matrix
        rows0, bytes0 = mx.rows_scattered_total, mx.upload_bytes_total
        wait0, operands0 = mx.sync_lock_wait_total, mx.scatter_operands_total
        arrays = sharded = None
        with self._state("coalescer.sync"):
            t_lock, device_wait = time.time(), 0.0
            if n_shards > 1:
                # Multi-chip: the matrix stays RESIDENT across the mesh —
                # sync_sharded scatters only dirty rows to the owning
                # shard instead of re-laying the full matrix per dispatch.
                with DEVICE_LOCK:
                    device_wait = time.time() - t_lock
                    sharded = mx.sync_sharded(self._mesh)
                    version = mx.synced_version
                n = int(mx.capacity)
            elif degraded and not fake_device.enabled():
                # Breaker open on a real backend: feed the host twin from
                # the host mirror directly — sync() would build a device
                # snapshot on the very device the breaker just declared
                # wedged.
                arrays = mx.sync_host()
                version = mx.synced_version
                n = int(arrays.used.shape[0])
            else:
                with DEVICE_LOCK:
                    device_wait = time.time() - t_lock
                    arrays = mx.sync()
                    version = mx.synced_version
                n = int(arrays.used.shape[0])
            trace.add_args(
                rows=mx.rows_scattered_total - rows0,
                bytes=mx.upload_bytes_total - bytes0,
                # Host operands the scatter handed jax (a device buffer
                # each, times the devices on a mesh): 1 a sync that
                # scattered, 0 where nothing was dirty.
                operands=mx.scatter_operands_total - operands0,
                shards=self.mesh_shape()[1] if sharded is not None else 1,
                # Blocked acquiring DEVICE_LOCK and the matrix's host lock
                # (held by the applier's mutators): with the span's
                # ``cpu``, the rest of ``dur`` is the wait for the GIL and
                # the time blocked inside the jitted scatter.
                lock_wait=device_wait + mx.sync_lock_wait_total - wait0,
            )
        return arrays, sharded, version, n

    def _overlay(self, batch: List[_Pending], version: int):
        """What the launch about to be enqueued is told of the launches
        before it: (rows, vals) of the claims overlay, the picks whose
        plans the applier has not decided, or has committed past
        ``version`` (the launch's snapshot), the launch's own lanes' evals
        left out; and which carried blocks are live ((CHAIN_DEPTH,) bool):
        those of the launches whose result is not on the host, whose picks
        are therefore in no entry.  One step of the ledger decides both, so
        a pick is counted once.  Read as late as the launch allows: a
        predecessor whose result arrived during this launch's host part is
        in the overlay."""
        if self.inflight:
            self.launches_unresolved_predecessor += 1
        rows, vals, live = self.claims.overlay(
            version, [p.eval_id for p in batch if p.eval_id],
            self.matrix.relocated_at, self._launches[:CHAIN_DEPTH],
        )
        self.overlay_rows_total += len(rows)
        self.chained_launches += any(live)
        # An unresolved predecessor no block stands for: older than the
        # carry holds, of another route or layout.
        unresolved = sum(not launch.resolved for launch in self._launches)
        self.chain_overflow += unresolved > sum(live)
        blocks = np.zeros((CHAIN_DEPTH,), bool)
        blocks[: len(live)] = live
        return rows, vals, blocks

    def _carry_in(self, route):
        """The carry to hand a launch on ``route`` ("twin", "device" or
        the mesh): the one the launch before it wrote, or blocks of padding
        where there is none or another program wrote it (those launches'
        blocks are lost to the chain: ``Launch.block``)."""
        if self._carry is None or self._carry_route != route:
            self._drop_carry()
            carry = empty_carry(
                self.max_lanes, MAX_DELTA_ROWS + self.scan_length
            )
            if route not in ("twin", "device"):
                from ..parallel.sharding import shard_carry

                carry = shard_carry(route, carry)
            self._carry, self._carry_route = carry, route
        return self._carry

    def _drop_carry(self) -> None:
        self._carry = None
        for launch in self._launches:
            launch.block = False

    def _chain(self, batch: List[_Pending], version: int, carry) -> None:
        """File the launch just made at the head of the chain, with the
        carry it wrote: the blocks shifted by one, and the oldest fell
        off.  Past the carry's depth only the unresolved are kept (for
        ``chain_overflow``)."""
        self._carry = carry
        for launch in self._launches[CHAIN_DEPTH - 1:]:
            launch.block = False
        self._launches = [
            Launch([p.eval_id for p in batch if p.eval_id], version)
        ] + [
            launch for i, launch in enumerate(self._launches)
            if i < CHAIN_DEPTH - 1 or not launch.resolved
        ]

    def _lane_claims(self, p: _Pending, rows: np.ndarray,
                     preempted: np.ndarray):
        """A resolved lane's whole proposed usage, as the ledger takes it
        ((rows, vals); what the lane's part of the launch's claims block
        holds on the device, ``kernels.claims_block``):
        what its plan held before the launch (``claim_vals`` on
        ``delta_rows``) and the picks its caller will consume, the first
        ``n_live`` up to a preempting one (``stack.py`` drops the rows
        after it and re-enters)."""
        n = lane_step_count(p.n_live, self.scan_length)
        picks = rows[:n]
        pre = np.flatnonzero(preempted[:n] != 0.0)
        if len(pre):
            picks = picks[: pre[0] + 1]
        picks = picks[picks >= 0]
        held = p.delta_rows >= 0
        ask = np.asarray(p.request.ask, np.float32)
        rows = np.concatenate([p.delta_rows[held], picks])
        vals = np.concatenate(
            [p.claim_vals[held], np.broadcast_to(ask, (len(picks), 3))]
        )
        claims = vals.any(axis=1)
        return rows[claims], vals[claims]

    def _dispatch(self, batch: List[_Pending], degraded: bool = False):
        """Launch one placement batch; returns (unfetched packed result,
        matrix version at launch).  Three routes, each chosen from what the
        code observes: the numpy twin (fake backend, or ``degraded`` — the
        breaker is open — answering from the host mirror, so placements
        keep flowing while the device is out), the node-sharded program
        when dispatches span a mesh (``_resolve_sharding``), the
        one-device program otherwise."""
        from ..chaos import inject
        from ..ops import fake_device

        fake = fake_device.enabled() or degraded
        if fake:
            n_shards = 1
        else:
            n_shards = self._resolve_sharding()

        arrays, sharded, version, n = self._sync_matrix(n_shards, degraded)

        # Chaos seam: partition an entire matrix shard MID-dispatch — the
        # snapshot above was synced pre-darkening, so this launch still
        # places onto the dark shard and the applier's authoritative
        # re-verify (eligibility-gated) must reject every one of them.
        fault = inject(
            "shard.partition",
            shards=int(getattr(self.matrix, "shard_count", 1)),
            lanes=len(batch),
        )
        trace.event("seam.shard.partition", lanes=len(batch))
        if fault is not None and fault.kind == "dark":
            self._darken_shard()

        # Chaos seam: lose an entire matrix shard (mesh-slice death, not
        # just ineligibility) — kind 'lost' evacuates it: the matrix
        # re-lays-out across the survivors, in-flight tickets invalidate
        # through the version/remap stale-dispatch mechanism, and this
        # launch proceeds against the post-evacuation layout.
        loss = inject(
            "shard.loss",
            shards=int(getattr(self.matrix, "shard_count", 1)),
            lanes=len(batch),
        )
        trace.event("seam.shard.loss", lanes=len(batch))
        if loss is not None and loss.kind == "lost":
            self._lose_shard()
            # The snapshot above was synced pre-evacuation; re-sync so
            # the launch scores the re-homed layout, not freed rows.
            # Evacuation dropped the compiled sharded entry points;
            # re-resolve so this launch runs on the survivor mesh (or the
            # single-device path when one shard remains).
            if not fake:
                n_shards = self._resolve_sharding()
            arrays, sharded, version, n = self._sync_matrix(n_shards, degraded)

        if fake:
            # Fake-device backend: numpy twins answer synchronously from
            # the host snapshot.  No lane padding (shapes need not be
            # static for numpy) and no stacking — the twin takes lists.
            # Requests built just before a matrix growth carry narrower
            # arrays; pad each by its OWN width (new rows masked off —
            # they were not host-checked).
            with self._state("coalescer.stage", lanes=len(batch)):
                for p in batch:
                    if p.host_mask.shape[0] < n:
                        p.host_mask = np.concatenate([
                            p.host_mask,
                            np.zeros((n - p.host_mask.shape[0],), bool),
                        ])
                    if p.tg_count.shape[0] < n:
                        p.tg_count = np.concatenate([
                            p.tg_count,
                            np.zeros((n - p.tg_count.shape[0],), np.int32),
                        ])
                    if p.penalty.shape[0] < n:
                        p.penalty = np.concatenate([
                            p.penalty,
                            np.zeros((n - p.penalty.shape[0],), bool),
                        ])
                lane_lists = (
                    [p.delta_rows for p in batch],
                    [p.delta_vals for p in batch],
                    [p.tg_count for p in batch],
                    [p.spread_counts for p in batch],
                    [p.penalty for p in batch],
                    [p.request for p in batch],
                    [p.class_elig for p in batch],
                    [p.host_mask for p in batch],
                )
            live_counts = [
                lane_step_count(p.n_live, self.scan_length) for p in batch
            ]
            no_claims = np.zeros((MAX_DELTA_ROWS, 3), np.float32)
            with self._state("coalescer.enqueue", lanes=len(batch)):
                carry = self._carry_in("twin")
                rows, vals, blocks = self._overlay(batch, version)
                packed, carry = fake_device.fused_place_batch(
                    arrays,
                    arrays.used,
                    *lane_lists,
                    lane_mask=np.ones((len(batch),), bool),
                    n_placements=self.scan_length,
                    live_counts=live_counts,
                    overlay=(rows, vals),
                    chain=(
                        carry, blocks,
                        [no_claims if p.claim_vals is None else p.claim_vals
                         for p in batch],
                        [bool(p.eval_id) for p in batch],
                    ),
                )
            self._chain(batch, version, carry)
            self.fused_dispatches += 1
            self.fused_lanes += len(batch)
            self.distinct_property_lanes += sum(
                bool((p.request.dp_slot >= 0).any()) for p in batch
            )
            self.scan_steps_total += max(live_counts)
            self.operand_bytes_total += sum(
                p.host_mask.nbytes + p.tg_count.nbytes + p.penalty.nbytes
                + p.class_elig.nbytes + p.spread_counts.nbytes
                + p.delta_rows.nbytes + p.delta_vals.nbytes
                for p in batch
            )
            lat = fake_device.latency_s()
            if lat > 0:
                # Synthetic fetch latency: the fetch pays it, not the launch,
                # so overlapping dispatches overlap their latency windows.
                packed = fake_device.DeferredResult(packed, lat)
            return packed, version

        k = len(batch)
        with self._state("coalescer.stage", lanes=k):
            cw = max(p.class_elig.shape[0] for p in batch)
            sc_shape = batch[0].spread_counts.shape
            st, slab = self._staging(n, cw, sc_shape)
            hm, tg = st["host_mask"], st["tg_count"]
            pen, ce = st["penalty"], st["class_elig"]
            sc = st["spread_counts"]
            dr, dv = st["delta_rows"], st["delta_vals"]
            cv, flags = st["claim_vals"], st["chain_flags"]
            ls = st["lane_steps"]
            ls[k:] = 0
            for i, p in enumerate(batch):
                ls[i] = lane_step_count(p.n_live, self.scan_length)
                # Requests built just before a matrix growth or a class-count
                # pow2 crossing carry narrower arrays; the staging row's tail
                # keeps the inert value (new rows masked off — they were not
                # host-checked; unknown classes eligible, matching
                # _class_eligibility's default).
                w = p.host_mask.shape[0]
                hm[i, :w] = p.host_mask
                hm[i, w:] = False
                w = p.tg_count.shape[0]
                tg[i, :w] = p.tg_count
                tg[i, w:] = 0
                w = p.penalty.shape[0]
                pen[i, :w] = p.penalty
                pen[i, w:] = False
                w = p.class_elig.shape[0]
                ce[i, :w] = p.class_elig
                ce[i, w:] = True
                sc[i] = p.spread_counts
                dr[i] = p.delta_rows
                dv[i] = p.delta_vals
                cv[i] = 0.0 if p.claim_vals is None else p.claim_vals
                flags[i, 0] = bool(p.eval_id)
            if k < self.max_lanes:
                # Pad lanes by memset: an all-False host mask makes every
                # placement in the lane fail cheaply; whatever the other
                # staging rows still hold from earlier dispatches only affects
                # the dead lane's own (discarded) scores.  Deltas are reset so
                # a stale row id can't scatter into the shared used base.
                hm[k:] = False
                dr[k:] = -1

            # Request operands write into the slot's (max_lanes, …) slab;
            # dead-lane rows keep their previous valid contents (masked off by
            # lane_steps 0 / the all-False host mask, never decoded into
            # results).
            for i, p in enumerate(batch):
                slab.fill(i, p.request)
            # Host→device operand traffic for this launch: the node-axis
            # lane buffers, the pack of the small ones and the request slab
            # (cost-attribution gauge; the resident matrix itself transfers
            # via scatter, counted by matrix.upload_bytes_total).
            self.operand_bytes_total += slab.nbytes() + sum(
                a.nbytes for a in (hm, tg, pen, st["pack"])
            )
        # The jitted call: the fused megakernel covers feasibility → binpack
        # → spread/affinity → evict-set → the cross-lane AllocsFit
        # re-verify column in one launch (node-sharded: each mesh shard
        # scores only its local node slice, the winner comes from an
        # election across shards, and the packed (B, P, 8) fetch is the
        # sole device→host traffic).  A launch that widened the features
        # ratchet, or whose packs have another layout than the last one's (a
        # class-count pow2 crossing, a new request-field shape), traces,
        # lowers and compiles (or reads from the cache) a new variant inside
        # the call: that one is named apart.
        state, args = "coalescer.enqueue", {"lanes": k}
        variants = self.feature_recompiles
        feats = self._ratchet_features(slab, k)
        self.fused_dispatches += 1
        self.fused_lanes += k
        self.distinct_property_lanes += int(
            (slab.live_view(k).dp_slot >= 0).any(axis=1).sum()
        )
        self.scan_steps_total += int(ls[:k].max())
        if self.feature_recompiles != variants:
            state = "coalescer.trace_variant"
            args["features"] = str(tuple(feats))
        # The carry goes from call to call on the device, and by route: the
        # second output of the launch before is this launch's operand.
        route = "device" if n_shards == 1 else self._mesh
        layouts = slab.layout, st["layout"]
        if self._unpack_variant != (route, layouts):
            self._unpack_variant = route, layouts
            state = "coalescer.trace_variant"
        calls0 = self.device_calls
        with self._state(state, **args):
            carry = self._carry_in(route)
            # The claims overlay and the chain's live flags go into the
            # pack last, immediately before the call that hands the pack
            # over.
            rows, vals, blocks = self._overlay(batch, version)
            flags[:, 1:].flat[:CHAIN_DEPTH] = blocks
            orows, ovals = st["overlay_rows"], st["overlay_vals"]
            flat = np.full((orows.size,), -1, np.int32)
            flat[: len(rows)] = rows
            orows[:] = flat.reshape(orows.shape)
            if len(rows):
                flat = np.zeros((orows.size, 3), np.float32)
                flat[: len(rows)] = vals
                ovals[:] = flat.reshape(ovals.shape)
            # ONE jitted call a launch.  It takes the resident matrix
            # (shared with in-flight dispatches, never donated), the two
            # packs the 30-odd small lane operands are views of (unpacked
            # at the program's entry; views of this slot, read until the
            # launch resolves), the three node-axis lane buffers and the
            # carry.
            resident = arrays if n_shards == 1 else sharded
            operands = (
                resident, resident.used, slab.pack, st["pack"], tg, pen, hm,
                carry,
            )
            if n_shards > 1:
                packed, carry = self._sharded_fused_fn(
                    *operands, layouts=layouts, features=feats
                )
            else:
                packed, carry = kernels.fused_place_batch_live(
                    *operands, layouts=layouts,
                    n_placements=self.scan_length, features=feats,
                )
            self.device_calls += 1
            # Jitted calls this launch made, and the host and device
            # buffers it handed jax (the matrix's fields one each; times the
            # devices on a mesh).
            trace.add_args(
                calls=self.device_calls - calls0,
                operands=len(resident) + len(operands) - 1,
            )
        self._chain(batch, version, carry)
        return packed, version

    def _resolve(self, ticket: _Ticket) -> None:
        from ..chaos import inject
        from ..ops.fake_device import DeferredResult

        packed, entries = ticket.packed, ticket.entries
        brk = self.breaker

        # Chaos seams: a synthetic wedge (the fetch never returns inside
        # the watchdog bound) or a synthetic slowdown (returns inside the
        # slow band) on this ticket's device→host fetch.
        wedge = inject("device.wedge", lanes=len(entries))
        trace.event("seam.device.wedge", lanes=len(entries))
        slow = None
        if wedge is None or wedge.kind != "wedge":
            slow = inject("device.slow", lanes=len(entries))
        trace.event("seam.device.slow", lanes=len(entries))

        deadline = brk.deadline_s()
        factor = brk.cfg.wedge_factor
        seamed = (wedge is not None and wedge.kind == "wedge") or (
            slow is not None and slow.kind == "slow"
        )

        with self._state("coalescer.fetch", lanes=len(entries)):
            if not seamed and isinstance(packed, np.ndarray):
                # Fast path: the result is already host-resident (fake-device
                # twin, no synthetic latency) — no fetch to watchdog, and no
                # sacrificial thread per ticket.
                arr = packed
                brk.record_ok(0.0, canary=ticket.canary)
            else:
                def _fetch():
                    if wedge is not None and wedge.kind == "wedge":
                        # Synthetic wedge: hold the fetch past every watchdog
                        # bound (duration caps it so abandoned threads die).
                        time.sleep(
                            wedge.duration
                            if wedge.duration > 0
                            else max(deadline * factor * 4.0, 1.0)
                        )
                    elif slow is not None and slow.kind == "slow":
                        # Synthetic slow band: past the deadline, inside the
                        # wedge bound — the result is late but usable.
                        time.sleep(
                            slow.duration
                            if slow.duration > 0
                            else deadline * (1.0 + factor) / 2.0
                        )
                    pk = packed
                    if isinstance(pk, DeferredResult):
                        pk = pk.result()
                    # ONE device→host fetch per dispatch
                    return np.asarray(pk)

                try:
                    verdict, arr, elapsed = watchdog_fetch(
                        _fetch, deadline, factor
                    )
                except BaseException as exc:  # noqa: BLE001
                    if ticket.canary:
                        brk.cancel_canary()
                    for p in entries:
                        p.error = exc
                        p.done.set()
                    return
                if verdict == STALL_WEDGED:
                    # The fetch blew through the wedge bound: abandon it, trip
                    # the breaker, and complete every lane with the typed
                    # error — the worker's exception path nacks the eval back
                    # to the broker for redelivery (via the degraded path once
                    # the breaker opens).  Later tickets still resolve in
                    # launch order; the pipeline permit is returned by
                    # _resolve_loop's finally.
                    brk.record_wedge(elapsed, canary=ticket.canary)
                    self.wedged_dispatches += 1
                    trace.event(
                        "coalescer.wedged_dispatch",
                        lanes=len(entries),
                        elapsed_ms=round(elapsed * 1e3, 1),
                    )
                    err = DeviceWedgedError(
                        f"device fetch wedged after {elapsed * 1e3:.0f}ms "
                        f"(deadline {deadline * 1e3:.0f}ms)",
                        elapsed_s=elapsed,
                        deadline_s=deadline,
                    )
                    for p in entries:
                        p.error = err
                        p.done.set()
                    return
                if verdict == STALL_SLOW:
                    brk.record_slow(elapsed, canary=ticket.canary)
                else:
                    brk.record_ok(elapsed, canary=ticket.canary)
        resolved_at = time.time()
        with self._state("coalescer.unpack", lanes=len(entries)):
            # Result traffic: the packed (lanes, placements, width) fetch is
            # O(B·P) — winner rows only, never node-axis shaped (lint J005
            # guards the call sites; the parity test pins this counter).
            self.topk_host_bytes_total += arr.nbytes
            # The launch→resolver hop: each lane's device window (launch to
            # fetched-on-host) recorded here, on the resolver thread, against
            # the trace context the worker thread captured in place().
            for p in entries:
                if p.trace_ctx is not None:
                    trace.record_span(
                        "coalescer.device",
                        ticket.launched_at or resolved_at,
                        resolved_at,
                        ctx=p.trace_ctx,
                        metrics=self.metrics,
                        lanes=len(entries),
                    )
            if self.matrix.version != ticket.matrix_version:
                # The matrix moved while this dispatch was in flight: its
                # placements were scored against a stale snapshot.  They are
                # still safe to propose — the serialized applier re-verifies
                # every plan against authoritative state — but the count is
                # the pipelining tax worth watching (surfaced as a registry
                # gauge over this attribute by the server).
                self.stale_dispatches += 1
                trace.event("coalescer.stale_dispatch")
            lanes, claims = [], []
            for i, p in enumerate(entries):
                row = arr[i]
                # Shard-preserving capacity growth relocates rows; a dispatch
                # that launched pre-growth reports OLD global row ids.  Map
                # them through the matrix's remap window (no-op when nothing
                # grew; unmappably old rows become -1 = failed placement).
                rows_i = self.matrix.translate_rows(
                    row[:, kernels.PACKED_ROW].astype(np.int32),
                    ticket.matrix_version,
                )
                # The device-resident AllocsFit column: a 0.0 on a real
                # placement means earlier lanes in THIS launch already
                # claimed the capacity and the resolution found the lane
                # no other node — at an unchanged matrix version the
                # applier is guaranteed to reject it; a 2.0 is a placement
                # the resolution moved off such a node, and it fits.
                # Advisory: the serialized applier stays authoritative
                # either way.
                vcol = row[:, kernels.FUSED_PACKED_VERIFIED]
                placed = rows_i >= 0
                fit_verified = ~(placed & (vcol == 0.0))
                self.verify_conflicts += int((~fit_verified).sum())
                self.lane_repicks += int((placed & (vcol == 2.0)).sum())
                pcol = row[:, kernels.PACKED_PREEMPT]
                self.picks_placed += int(placed.sum())
                self.preempt_picks += int((placed & (pcol != 0.0)).sum())
                self.distinct_property_blocked += int(
                    (placed & (row[:, kernels.PACKED_FILTERED] % 1 != 0)).sum()
                )
                if p.eval_id:
                    claims.append(
                        (p.eval_id,) + self._lane_claims(p, rows_i, pcol)
                    )
                lanes.append((p, row, rows_i, pcol, fit_verified))
            # All lanes' claims enter the ledger in one step with the
            # launch's being resolved (a launch being enqueued meanwhile
            # reads them there or in the carried block, not in both), and
            # before any lane's future completes, so the entry is there
            # when its plan reaches the applier.
            carried = self.claims.register_launch(ticket.launch, claims)
            self.chained_rows_total += carried * sum(
                len(rows) for _eval, rows, _vals in claims
            )
            for p, row, rows_i, pcol, fit_verified in lanes:
                p.outcome = PlaceOutcome(
                    rows=rows_i,
                    scores=row[:, kernels.PACKED_SCORE],
                    binpack=row[:, kernels.PACKED_BINPACK],
                    preempted=pcol,
                    nodes_evaluated=row[:, kernels.PACKED_EVALUATED].astype(
                        np.int32
                    ),
                    nodes_filtered=row[:, kernels.PACKED_FILTERED].astype(
                        np.int32
                    ),
                    nodes_exhausted=row[:, kernels.PACKED_EXHAUSTED].astype(
                        np.int32
                    ),
                    fit_verified=fit_verified,
                    matrix_version=ticket.matrix_version,
                )
                p.done.set()
