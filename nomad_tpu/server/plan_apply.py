"""Serialized plan applier — the cluster's single commit point.

Reference: ``nomad/plan_apply.go``. Workers produce plans optimistically
against possibly-stale snapshots; the applier re-verifies every plan against
the freshest state and commits (possibly partially), handing back a
``refresh_index`` that sends the scheduler around the retry loop
(``plan_apply.go:49-69`` design note, ``evaluatePlan`` :400,
``evaluateNodePlan`` :631-682).

The reference fans per-node ``AllocsFit`` checks out to an EvaluatePool of
NumCPU/2 goroutines (``plan_apply_pool.go:18``). Here the whole plan is
verified in ONE vectorized numpy pass against the authoritative matrix
aggregates — the same data the scheduler's device kernels scored against
(the north-star "shared semantics" requirement): the host math is the
exact twin of the ``verify_plan_fit`` kernel, pinned together by
tests/test_kernels.py golden tests.  The device is never touched while
holding the store lock.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from .. import trace

from ..structs.types import (
    Allocation,
    EvalStatus,
    EvalTrigger,
    Evaluation,
    NodeStatus,
    Plan,
    PlanResult,
)
from .plan_queue import PendingPlan, PlanQueue


class StaleEvalTokenError(Exception):
    """The submitting worker's eval delivery was superseded (nack-timeout
    redelivery); its plan must not commit (plan_apply.go token check)."""


class PlanApplier:
    """Single-threaded applier loop over the plan queue."""

    def __init__(self, server):
        self.server = server
        self.queue: PlanQueue = server.plan_queue
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self.plans_applied = 0
        self.plans_partial = 0

    def start(self) -> None:
        if self._thread is not None and self._thread.is_alive():
            return  # leadership can cycle; one applier thread only
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._run, name="plan-applier", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread:
            self._thread.join(timeout=5)

    def _run(self) -> None:
        while not self._stop.is_set():
            batch = self.queue.dequeue_all(timeout=0.2)
            if batch:
                self.apply_batch(batch)

    def apply_batch(self, batch: List[PendingPlan]) -> None:
        """Commit a drained queue batch under ONE _write_lock → _lock
        acquisition.  Each plan is still verified against the state left by
        the plans committed before it (the _apply_locked loop is strictly
        sequential), so the outcome matches the one-at-a-time loop; only the
        per-plan lock round-trip is amortized."""
        broker = self.server.eval_broker
        store = self.server.store
        staged: List[PendingPlan] = []
        for pending in batch:
            plan = pending.plan
            if plan.eval_token and broker.enabled:
                current = broker.outstanding_token(plan.eval_id)
                if current != plan.eval_token:
                    pending.respond(
                        None,
                        StaleEvalTokenError(
                            f"plan for eval {plan.eval_id} has a stale token"
                        ),
                    )
                    continue
            staged.append(pending)
        if not staged:
            return

        outcomes = []
        apply_t0 = time.time()
        spans: List[Tuple[PendingPlan, float, float]] = []
        # The applier's own state, per batch and ambient: what this one
        # thread was doing while the per-plan records below (stitched onto
        # each eval's trace afterwards) say what the evals waited for.
        with trace.span("plan.batch", metrics=self.server.metrics,
                        annotate=True, cpu=True, plans=len(staged)):
            with self.server.metrics.timer("nomad.plan.apply").time():
                wait_t0 = time.time()
                with store._write_lock:
                    with store._lock:
                        # Blocked behind the store's other writers and
                        # readers: with ``cpu``, what of the batch was
                        # neither work nor this wait.
                        trace.add_args(lock_wait=time.time() - wait_t0)
                        for pending in staged:
                            t0 = time.time()
                            try:
                                result, index = self._apply_locked(
                                    pending.plan
                                )
                                outcomes.append(
                                    (pending, result, index, None)
                                )
                            except Exception as exc:  # noqa: BLE001
                                outcomes.append((pending, None, 0, exc))
                            spans.append((pending, t0, time.time()))
        # Trace stitching happens after the store locks are released —
        # per-plan timestamps were collected inside, recorded here onto
        # each plan's carried worker context.
        for pending, t0, t1 in spans:
            if pending.trace_ctx is None:
                continue
            trace.record_span(
                "plan.queue_wait",
                pending.enqueued_at,
                apply_t0,
                ctx=pending.trace_ctx,
                metrics=self.server.metrics,
            )
            trace.record_span(
                "plan.apply",
                t0,
                t1,
                ctx=pending.trace_ctx,
                metrics=self.server.metrics,
                eval=pending.plan.eval_id,
            )
        for pending, result, index, exc in outcomes:
            if exc is not None:
                pending.respond(None, exc)
                continue
            try:
                if index:
                    self.server.on_plan_applied(pending.plan, result, index)
            except Exception as exc2:  # noqa: BLE001
                pending.respond(None, exc2)
                continue
            pending.respond(result, None)

    # ------------------------------------------------------------------

    def apply(self, plan: Plan) -> PlanResult:
        """Verify against authoritative state, commit what fits.

        Verification and commit happen under one store lock so no concurrent
        writer can invalidate the verdict between them — the serialization
        the reference gets from the Raft log + single applier goroutine.
        """
        broker = self.server.eval_broker
        if plan.eval_token and broker.enabled:
            current = broker.outstanding_token(plan.eval_id)
            if current != plan.eval_token:
                raise StaleEvalTokenError(
                    f"plan for eval {plan.eval_id} has a stale token"
                )
        store = self.server.store
        with self.server.metrics.timer("nomad.plan.apply").time():
            # Lock ORDER must match the journaled-writer wrapper
            # (_write_lock → _lock, state/store.py journaled): the commit
            # inside _apply_locked re-enters it, and taking _lock alone
            # first inverts against every concurrent writer — a deadlock
            # observed as a full server freeze under an eval burst.
            # Known cost on REPLICATED clusters: because this frame holds
            # _lock re-entrantly, the nested journaled write's quorum
            # round-trip runs with the read lock held for plan commits
            # (only).  Fixing it means staging the verify outside the
            # locks and re-verifying inside — the pipeline split is
            # tracked, not yet done.
            with store._write_lock:
                with store._lock:
                    result, index = self._apply_locked(plan)
        if index:
            self.server.on_plan_applied(plan, result, index)
        return result

    def _apply_locked(self, plan: Plan):
        with self.server.metrics.timer("nomad.plan.evaluate").time():
            failed_nodes = self._evaluate(plan)
        committed_allocs: Dict[str, List[Allocation]] = {
            nid: allocs
            for nid, allocs in plan.node_allocation.items()
            if nid not in failed_nodes
        }

        allocs = [a for lst in committed_allocs.values() for a in lst]
        allocs.extend(plan.alloc_updates)
        stops = [a for lst in plan.node_update.values() for a in lst]
        node_preemptions = {
            nid: lst
            for nid, lst in plan.node_preemptions.items()
            if nid not in failed_nodes
        }
        preempts = [a for lst in node_preemptions.values() for a in lst]

        result = PlanResult(
            node_allocation=committed_allocs,
            node_update=dict(plan.node_update),
            node_preemptions=node_preemptions,
            deployment=plan.deployment,
            deployment_updates=plan.deployment_updates,
        )

        if not allocs and not stops and not preempts and plan.deployment is None \
                and not plan.deployment_updates:
            # Entirely rejected plan: nothing commits; scheduler refreshes.
            result.refresh_index = self.server.store.latest_index
            self.plans_partial += 1
            self.server.metrics.incr("nomad.plan.result", outcome="rejected")
            self.server.coalescer.claims.refuse(plan.eval_id)
            return result, 0

        index = self.server.next_index()
        result.preemption_evals = self._preemption_evals(preempts)
        self.server.store.upsert_plan_results(
            index,
            allocs,
            stops,
            preempts,
            deployment=plan.deployment,
            deployment_updates=plan.deployment_updates,
            evals=result.preemption_evals,
        )
        result.alloc_index = index
        # The plan's picks leave the in-flight claims ledger: the refused
        # nodes' at once, the committed ones' with the first launch whose
        # snapshot holds this commit.  Every matrix mutator runs under the
        # store lock held here, so ``version`` is this commit's own.
        matrix = self.server.store.matrix
        self.server.coalescer.claims.commit(
            plan.eval_id, matrix.version,
            [matrix.row_of.get(nid, -1) for nid in failed_nodes],
        )
        if preempts:
            self.server.metrics.incr(
                "nomad.plan.preempted_allocs", len(preempts)
            )
            self.server.metrics.incr(
                "nomad.plan.preemption_evals", len(result.preemption_evals)
            )
        if failed_nodes:
            # Partial commit ⇒ RefreshIndex so the worker re-snapshots past
            # this apply (plan_apply.go:166-178).
            result.refresh_index = index
            self.plans_partial += 1
        self.plans_applied += 1
        # One count per plan, where its fate is decided: the herd (evals
        # racing for the same nodes) shows as rejected, not as partial.
        self.server.metrics.incr(
            "nomad.plan.result",
            outcome="partial" if failed_nodes else "committed",
        )
        return result, index

    def _preemption_evals(self, preempts: List[Allocation]) -> List[Evaluation]:
        """One pending eval per job that loses allocations to a plan's
        preemptions, so that the job is placed again or blocks — the
        reference's applyPlan (plan_apply.go: ``TriggeredBy:
        EvalTriggerPreemption``, the job's own type and priority),
        committed with the plan result in one index.  The caller hands
        them to the broker once the store's locks are released
        (``Server.on_plan_applied``)."""
        evals: List[Evaluation] = []
        seen = set()
        for a in preempts:
            key = (a.namespace, a.job_id)
            job = a.job or self.server.store.job_by_id(*key)
            if key in seen or job is None:
                continue
            seen.add(key)
            evals.append(Evaluation(
                namespace=a.namespace,
                priority=job.priority,
                type=job.type,
                triggered_by=EvalTrigger.PREEMPTION.value,
                job_id=a.job_id,
                status=EvalStatus.PENDING.value,
                create_time=time.time(),
            ))
        return evals

    # ------------------------------------------------------------------

    def _evaluate(self, plan: Plan) -> set:
        """Return the set of node ids whose placements do NOT fit current
        state. One vectorized kernel call for the resource check; host-side
        checks for node existence/status and device counts."""
        store = self.server.store
        matrix = store.matrix
        failed: set = set()

        node_ids = list(plan.node_allocation.keys())
        if not node_ids:
            return failed

        # Exclusive-volume writers admitted earlier in THIS plan's walk:
        # (namespace, volume_id) -> count.
        plan_claims: Dict[tuple, int] = {}

        rows: List[int] = []
        deltas: List[np.ndarray] = []
        checked: List[str] = []
        elig_required: List[bool] = []
        for nid in node_ids:
            node = store.nodes.get(nid)
            # Host checks mirroring evaluateNodePlan (plan_apply.go:644-653):
            # node must exist and be schedulable for new placements.
            if node is None or node.status == NodeStatus.DOWN.value:
                failed.add(nid)
                continue
            has_new = any(
                a.id not in store.allocs for a in plan.node_allocation[nid]
            )
            if not node.ready() and has_new:
                failed.add(nid)
                continue

            row = matrix.row_of.get(nid)
            if row is None:
                failed.add(nid)
                continue

            delta = np.zeros(3, np.float32)
            dev_delta: Dict[str, int] = {}
            for a in plan.node_allocation[nid]:
                r = a.resources
                delta += (r.cpu, r.memory_mb, r.disk_mb)
                for d in r.devices:
                    dev_delta[d.name] = dev_delta.get(d.name, 0) + d.count
                prev = store.allocs.get(a.id)
                if prev is not None and not prev.terminal_status() \
                        and prev.node_id == nid:
                    # In-place update: its old usage is already in `used`.
                    pr = prev.resources
                    delta -= (pr.cpu, pr.memory_mb, pr.disk_mb)
                    for d in pr.devices:
                        dev_delta[d.name] = dev_delta.get(d.name, 0) - d.count
            # A victim another plan already evicted (or stopped): what
            # this plan scored the node by is gone, whether or not the
            # placement would still fit.  The worker refreshes and picks
            # again; no allocation is evicted twice.
            if any(
                a.id not in store.allocs
                or store.allocs[a.id].terminal_status()
                for a in plan.node_preemptions.get(nid, [])
            ):
                failed.add(nid)
                continue
            for a in plan.node_update.get(nid, []) + plan.node_preemptions.get(
                nid, []
            ):
                prev = store.allocs.get(a.id)
                if prev is not None and not prev.terminal_status():
                    pr = prev.resources
                    delta -= (pr.cpu, pr.memory_mb, pr.disk_mb)
                    for d in pr.devices:
                        dev_delta[d.name] = dev_delta.get(d.name, 0) - d.count

            # Device-count re-check stays host-side (few nodes carry asks).
            if dev_delta:
                host = matrix.snapshot_host()
                for name, cnt in dev_delta.items():
                    slot = matrix.devices.lookup(name)
                    if slot is None:
                        if cnt > 0:
                            failed.add(nid)
                        continue
                    if host["dev_used"][row, slot] + cnt > host["dev_total"][row, slot]:
                        failed.add(nid)
            if nid in failed:
                continue

            # Port re-verify at commit time (AllocsFit's NetworkIndex,
            # funcs.go:97-150): two optimistically planned allocs claiming
            # the same static port on one node must not both commit.
            if not self._ports_fit(plan, node, nid):
                failed.add(nid)
                continue

            # Volume-claim re-verify: two optimistic plans (or two nodes in
            # one plan) must not both claim an exclusive registered volume
            # (csi_endpoint.go claim serialization — here the serialized
            # applier IS the claim gate).
            if not self._volumes_fit(plan, nid, plan_claims):
                failed.add(nid)
                continue

            rows.append(row)
            deltas.append(delta)
            checked.append(nid)
            # Only new placements need the node eligible; in-place updates on
            # a draining/ineligible node are legitimate (evaluateNodePlan
            # only gates placements).
            elig_required.append(has_new)

        if not checked:
            return failed

        # Vectorized numpy verification over the authoritative aggregates —
        # the exact host twin of the verify_plan_fit kernel (pinned together
        # by tests/test_kernels.py::test_host_twin_matches_kernel).  The
        # applier holds the global store lock here, so the device (a
        # synchronous round-trip) is never touched
        # on this path; O(k) numpy handles any plan size in microseconds.
        host = matrix.snapshot_host()
        rows_np = np.asarray(rows, np.int32)
        used = host["used"][rows_np] + np.stack(deltas)
        fits = np.all(used <= host["totals"][rows_np], axis=1)
        elig = host["eligible"][rows_np]
        verdicts = fits & (~np.asarray(elig_required) | elig)
        for nid, ok in zip(checked, verdicts):
            if not bool(ok):
                failed.add(nid)
        return failed

    def _volumes_fit(
        self, plan: Plan, nid: str, plan_claims: Dict[tuple, int]
    ) -> bool:
        """Re-check registered-volume claims for this node's NEW placements
        against authoritative state + claims granted earlier in this plan."""
        store = self.server.store
        stopping = {
            s.id for lst in plan.node_update.values() for s in lst
        }
        for a in plan.node_allocation[nid]:
            if a.id in store.allocs:
                continue  # in-place update: claim already held
            job = a.job
            tg = job.lookup_task_group(a.task_group) if job else None
            if tg is None or not tg.volumes:
                continue
            for vreq in tg.volumes.values():
                if vreq.type != "csi":
                    continue
                vol = store.volume_by_id(a.namespace, vreq.source)
                if vol is None:
                    return False
                writer = not vreq.read_only
                if not writer or vol.access_mode == "multi-node-multi-writer":
                    continue
                if vol.access_mode != "single-node-writer":
                    return False  # reader-only volume cannot take a writer
                key = (a.namespace, vol.id)
                # Only the claim held by the alloc THIS placement replaces
                # (or one stopping in the same plan) is exempt — a blanket
                # same-job pass would let two live allocs of one job
                # double-claim a single-node-writer volume.
                live_foreign = any(
                    (prev := store.allocs.get(aid)) is not None
                    and not prev.terminal_status()
                    and aid not in stopping
                    and aid != a.previous_allocation
                    for aid in vol.write_claims
                )
                if live_foreign or plan_claims.get(key, 0) > 0:
                    return False
                plan_claims[key] = plan_claims.get(key, 0) + 1
        return True

    def _ports_fit(self, plan: Plan, node, nid: str) -> bool:
        """Exact host-side port check against authoritative state: claimed =
        live allocs on the node minus this plan's evictions/preemptions/
        replacements, plus the plan's own placements in sequence."""
        from ..state.matrix import NodeMatrix

        store = self.server.store
        removed = {
            a.id
            for a in plan.node_update.get(nid, [])
            + plan.node_preemptions.get(nid, [])
        }
        planned = plan.node_allocation[nid]
        replaced = {a.id for a in planned}
        used = set(node.reserved.reserved_ports)
        for existing in store.allocs_by_node(nid):
            if existing.terminal_status():
                continue
            if existing.id in removed or existing.id in replaced:
                continue
            used.update(NodeMatrix.ports_of(existing))
        for a in planned:
            claimed = NodeMatrix.ports_of(a)
            if claimed & used:
                return False
            used |= claimed
        return True
