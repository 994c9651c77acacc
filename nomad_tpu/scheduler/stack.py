"""Selection stacks — host orchestration of the vectorized ranking pipeline.

The reference's GenericStack is a 14-iterator pull chain walking sampled
nodes one at a time (scheduler/stack.go:324-417, sampling at :78-91). Here a
``select`` call compiles the task group once (ops/encode.py), builds the
plan-adjusted proposed usage, and invokes one fused kernel
(ops/kernels.place_task_group) that scores **all** nodes and places N allocs
in a lax.scan — the sampling trade-off disappears because scoring the full
cluster is one matrix pass on the MXU.

Host-side residue (SURVEY.md §7 hard-part b): combinatorial port/device
*assignment* happens only for the chosen node; non-vectorizable constraints
are evaluated per computed class (feasible_host.py); a rare post-check
failure masks the node and re-runs the kernel.
"""

from __future__ import annotations

import math
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import trace
from ..ops.encode import (
    CompiledTaskGroup,
    MAX_DISTINCT_VALUES,
    RequestEncoder,
    SchedRequest,
    distinct_property_limit,
    pow2_bucket as _pow2_bucket,
)
from ..ops import fake_device, kernels
from ..state.matrix import DEVICE_LOCK, NodeMatrix, node_attributes, stable_hash
from ..structs.types import (
    Allocation,
    AllocMetric,
    Job,
    Node,
    Op,
    TaskGroup,
)
from .context import EvalContext
from .feasible_host import attr_name, check_constraint_host
from .preemption import preempting_scores, select_victims

# Dynamic port range (reference: structs/network.go MinDynamicPort/MaxDynamicPort).
from ..state.matrix import MAX_DYNAMIC_PORT, MIN_DYNAMIC_PORT  # noqa: E402
# (canonical port-range constants live beside the port bitmap encoding)

# Placement chunk ceiling: bounds the set of lax.scan lengths the jit cache
# ever sees to {1, 2, 4, 8, 16} (SURVEY.md §7 hard-part e).
PLACEMENT_CHUNK = 16
# Bound on kernel re-entries after host-side rejections (gone node, port
# conflict, a preemptable node without an admissible victim set).  The
# re-entry after a preempting pick is no rejection: it places one.
MAX_SELECT_RETRIES = 8

# Solo-path occupancy ratchet (mirrors DeviceCoalescer._features): the
# Features bucket widens monotonically across the process, so the jit cache
# sees a short chain of variants instead of flapping per request.  Mutated
# only on the device thread (dev_op closures run serialized).
_solo_features: Optional[kernels.Features] = None


def _ratchet_features(request) -> kernels.Features:
    global _solo_features
    feats = kernels.features_of(request)
    _solo_features = (
        feats if _solo_features is None else _solo_features.widen(feats)
    )
    return _solo_features


def _dense_used0(arrays, deltas: Dict[int, np.ndarray]):
    """Proposed base usage: matrix usage + sparse per-row plan deltas.
    Device code — call on the device thread (dev_op closures)."""
    import jax.numpy as jnp

    used0 = arrays.used
    if deltas:
        rows = np.fromiter(deltas.keys(), np.int32)
        dvals = np.stack([deltas[r] for r in rows])
        used0 = used0.at[jnp.asarray(rows)].add(jnp.asarray(dvals))
    return used0


def _full_mask(n: int, host_mask: Optional[np.ndarray]) -> np.ndarray:
    """host_mask with the all-pass default materialized."""
    return host_mask if host_mask is not None else np.ones((n,), bool)


def _and_mask(a: Optional[np.ndarray], b: Optional[np.ndarray]):
    """The conjunction of two optional node masks (None = all pass)."""
    if a is None or b is None:
        return b if a is None else a
    return a & b


def _pad_width(arr: np.ndarray, n: int, fill) -> np.ndarray:
    """Pad a node-axis array to width ``n`` — the matrix can grow between
    building host inputs and the dev_op running on the device thread; new
    rows get the conservative fill (False/0: not host-checked this round)."""
    if arr.shape[0] >= n:
        return arr
    out = np.full((n,) + arr.shape[1:], fill, arr.dtype)
    out[: arr.shape[0]] = arr
    return out


@dataclass
class SelectionOption:
    """One placement decision (reference: rank.RankedNode)."""

    node_id: str
    node: Node
    row: int
    final_score: float
    binpack_score: float
    needs_preempt: bool
    metric: AllocMetric = field(default_factory=AllocMetric)
    # The allocations this placement evicts (a preempting pick's victims,
    # chosen here, on the host, for the one node picked).
    victims: List[Allocation] = field(default_factory=list)
    # task -> {label: port} assigned host-side for the chosen node
    assigned_ports: Dict[str, Dict[str, int]] = field(default_factory=dict)
    # Coalesced-launch advisory: the device's sequential cross-lane
    # AllocsFit verdict for this placement (False = an earlier lane in the
    # same launch claimed the capacity — the applier will reject this plan
    # at an unchanged matrix version).  None on the solo path.
    fit_verified: Optional[bool] = None




class GenericStack:
    """Service/batch ranking stack (reference: stack.go:324-417)."""

    def __init__(
        self,
        ctx: EvalContext,
        matrix: NodeMatrix,
        algorithm: str = "binpack",
        preemption_enabled: bool = False,
        batch: bool = False,
    ):
        self.ctx = ctx
        self.matrix = matrix
        self.algorithm = algorithm
        self.preemption_enabled = preemption_enabled
        self.batch = batch
        # Shared, matrix-lifetime encoder: stacks are rebuilt per eval, so a
        # per-stack encoder would discard the compile cache every eval.
        self.encoder: RequestEncoder = matrix.shared_encoder()
        self.job: Optional[Job] = None
        # Eligibility telemetry consumed by blocked-eval creation
        # (reference: EvalEligibility, context.go:190; fills the eval's
        # ClassEligibility / EscapedComputedClass fields).  A select keeps
        # the verdicts as it computed them, one bool a class id; the dict
        # by class key is built where an eval blocks (``class_eligibility``).
        self._class_verdicts = np.zeros((0,), bool)
        self.escaped_computed_class = False
        # Alloc ids this pass is replacing or stopping — the ONLY live
        # volume claims a new placement may look through (set_replaced).
        self.replaced_allocs: set = set()

    def set_job(self, job: Job) -> None:
        self.job = job

    def set_replaced(self, alloc_ids) -> None:
        """Declare the allocs this scheduling pass replaces/stops; their
        volume claims don't block placement (the reconciler releases them
        in the same plan)."""
        self.replaced_allocs = set(alloc_ids)

    def _record_eligibility(self, class_elig: np.ndarray, host_mask,
                            compiled: CompiledTaskGroup) -> None:
        """Keep one select's verdicts for the classes the matrix knew then.
        A later select (another group of the job) overwrites the classes it
        saw and leaves the rest: no class is visited in Python here."""
        n = min(len(self.matrix.class_ids), len(class_elig))
        seen = self._class_verdicts
        if n >= len(seen):
            self._class_verdicts = class_elig[:n].copy()
        else:
            seen[:n] = class_elig[:n]
        if host_mask is not None or compiled.distinct_props:
            # Per-node (class-unhashable) checks were in play — the eval
            # escapes class caching and must retry on any capacity change.
            self.escaped_computed_class = True

    @property
    def class_eligibility(self) -> Dict[str, bool]:
        """Class key -> the last verdict a select of this eval gave it
        (the reference's ``EvalEligibility`` record), built on demand: only
        an eval that blocks reads it.  ``matrix.class_ids`` is append-only
        and in id order, so its first entries are the classes the selects
        saw."""
        seen = self._class_verdicts.tolist()
        # (list(dict) is one call: a registration cannot grow it midway)
        return dict(zip(list(self.matrix.class_ids), seen))

    # -- proposed-state assembly -------------------------------------------

    def _plan_usage_deltas(self) -> Dict[int, np.ndarray]:
        """Net (cpu, mem, disk) the in-flight plan adds per node row."""
        deltas: Dict[int, np.ndarray] = {}
        plan = self.ctx.plan

        def add(node_id: str, res, sign: float) -> None:
            row = self.matrix.row_of.get(node_id)
            if row is None:
                return
            d = deltas.setdefault(row, np.zeros(3, np.float32))
            d += sign * np.array([res.cpu, res.memory_mb, res.disk_mb], np.float32)

        for node_id, allocs in plan.node_allocation.items():
            for a in allocs:
                add(node_id, a.resources, 1.0)
        for node_id, allocs in plan.node_update.items():
            for a in allocs:
                add(node_id, a.resources, -1.0)
        for node_id, allocs in plan.node_preemptions.items():
            for a in allocs:
                add(node_id, a.resources, -1.0)
        return deltas

    def _plan_evictions(self) -> Dict[int, np.ndarray]:
        """(cpu, mem, disk) the in-flight plan's preemptions free per node
        row: what ``_plan_usage_deltas`` credits and the claims the plan
        advertises to other launches do not (``_dispatch_place``)."""
        freed: Dict[int, np.ndarray] = {}
        for node_id, allocs in self.ctx.plan.node_preemptions.items():
            row = self.matrix.row_of.get(node_id)
            if row is None:
                continue
            d = freed.setdefault(row, np.zeros(3, np.float32))
            for a in allocs:
                r = a.resources
                d += np.array([r.cpu, r.memory_mb, r.disk_mb], np.float32)
        return freed

    def _tg_counts(self, job: Job, tg: TaskGroup) -> Dict[int, int]:
        """Proposed allocs of this job+TG per node row (JobAntiAffinity and
        distinct_hosts inputs)."""
        counts: Dict[int, int] = {}
        plan = self.ctx.plan
        removed = self.ctx.plan_removed_ids()
        for a in self.ctx.snapshot.allocs_by_job(job.namespace, job.id):
            if a.terminal_status() or a.id in removed or a.task_group != tg.name:
                continue
            row = self.matrix.row_of.get(a.node_id)
            if row is not None:
                counts[row] = counts.get(row, 0) + 1
        for node_id, allocs in plan.node_allocation.items():
            n = sum(1 for a in allocs if a.task_group == tg.name)
            if n:
                row = self.matrix.row_of.get(node_id)
                if row is not None:
                    counts[row] = counts.get(row, 0) + n
        return counts

    def _spread_counts(
        self, job: Job, tg: TaskGroup, compiled: CompiledTaskGroup
    ) -> np.ndarray:
        """(S, V) usage counts per attribute value, aligned/extended against
        the compiled s_value_hash table (propertyset.go usage tracking)."""
        req = compiled.request
        s_hash = req.s_value_hash.copy()
        counts = np.zeros_like(s_hash, np.float32)
        if not compiled.spreads:
            return counts
        removed = self.ctx.plan_removed_ids()
        live = [
            a
            for a in self.ctx.snapshot.allocs_by_job(job.namespace, job.id)
            if not a.terminal_status() and a.id not in removed
            and a.task_group == tg.name
        ]
        for allocs in self.ctx.plan.node_allocation.values():
            live.extend(a for a in allocs if a.task_group == tg.name)
        for si, sp in enumerate(compiled.spreads[: s_hash.shape[0]]):
            if req.s_slot[si] < 0:
                continue
            name = sp.attribute
            if name.startswith("${") and name.endswith("}"):
                name = name[2:-1]
            if name.startswith("attr."):
                name = name[len("attr.") :]
            for a in live:
                node = self.ctx.snapshot.node_by_id(a.node_id)
                if node is None:
                    continue
                value = node_attributes(node).get(name)
                if not value:
                    continue
                h = stable_hash(value)
                idx = np.where(s_hash[si] == h)[0]
                if idx.size:
                    counts[si, idx[0]] += 1.0
                else:
                    free = np.where(s_hash[si] == 0)[0]
                    if free.size:
                        s_hash[si, free[0]] = h
                        counts[si, free[0]] = 1.0
        # persist discovered values into the request copy used by the kernel
        compiled.request = req._replace(s_value_hash=s_hash)
        return counts

    def _class_eligibility(self, compiled: CompiledTaskGroup) -> np.ndarray:
        """Escaped non-unique constraints by computed class (the
        ComputedClass cache, feasible.go:1029): a padded bool vector indexed
        by class id.  Each constraint is evaluated once per distinct value
        of its attribute's column and broadcast (``HostFeasibility``); the
        representatives are visited one by one only where an attribute has
        no column, and counted (``nomad.sched.class_walk_total``)."""
        n_classes = max(1, len(self.matrix.class_ids))
        pad = _pow2_bucket(n_classes)
        escaped = [
            e.constraint
            for e in compiled.escaped
            if not e.unique
            and e.constraint.operand
            not in (Op.DISTINCT_HOSTS.value, Op.DISTINCT_PROPERTY.value)
        ]
        if not escaped:
            return np.ones((pad,), bool)
        hf = self.matrix.host_feasibility()
        elig = hf.class_vector(escaped, pad)
        if elig is not None:
            return elig
        elig = np.ones((pad,), bool)
        reprs = list(self.matrix.class_repr.items())
        hf.walked_classes += len(reprs)
        for cid, rep_node_id in reprs:
            node = self.ctx.snapshot.node_by_id(rep_node_id)
            if node is None:
                continue
            hf.predicates_evaluated += len(escaped)
            ok = all(check_constraint_host(c, node) for c in escaped)
            if cid < pad:
                elig[cid] = ok
        return elig

    def _feasibility(self, job: Job, tg: TaskGroup,
                     compiled: CompiledTaskGroup):
        """(class eligibility, host mask or None) of what the kernels do
        not evaluate, under the span ``sched.feasibility`` (tags: host-mask
        terms ``escaped``, computed ``classes``, the class operand's width
        ``class_pad``, predicate ``values`` evaluated in Python: 0 once the
        masks are cached)."""
        hf = self.matrix.host_feasibility()
        values0 = hf.predicates_evaluated
        with trace.span("sched.feasibility", cpu=True):
            class_elig = self._class_eligibility(compiled)
            host_mask = self._host_mask(job, tg, compiled)
            trace.add_args(
                classes=len(self.matrix.class_ids),
                class_pad=len(class_elig),
                values=hf.predicates_evaluated - values0,
            )
        self._record_eligibility(class_elig, host_mask, compiled)
        return class_elig, host_mask

    def _volume_claimable(self, vol, vreq, job: Job) -> bool:
        """Do the volume's live claims admit this request?  Claims from
        terminal (or vanished) allocs don't count — the volume watcher
        releases them lazily; claims from allocs this pass replaces/stops
        (set_replaced) don't block their own replacement.  A blanket
        same-job exemption would let two LIVE allocs of one job
        double-claim a single-node-writer volume."""
        if vreq.read_only or vol.access_mode == "multi-node-multi-writer":
            return True
        if vol.access_mode != "single-node-writer":
            return False  # reader-only volume cannot take a writer
        snap = self.ctx.snapshot
        for alloc_id in vol.write_claims:
            a = snap.alloc_by_id(alloc_id) if hasattr(
                snap, "alloc_by_id"
            ) else None
            if a is None or a.terminal_status():
                continue
            if alloc_id in self.replaced_allocs:
                continue
            return False
        return True

    def _walk(self, pred) -> np.ndarray:
        """(N,) bool — ``pred(node)`` for the matrix's nodes, one by one:
        the fallback where an attribute has no column to read (the
        registry is full).  Counted (``nomad.sched.host_walk_nodes_total``):
        at 10,000 nodes one walk costs several whole placed jobs."""
        m = np.ones((self.matrix.capacity,), bool)
        rows = list(self.matrix.row_of.items())
        self.matrix.host_feasibility().walked_nodes += len(rows)
        for node_id, row in rows:
            node = self.ctx.snapshot.node_by_id(node_id)
            if row < m.shape[0]:
                m[row] = node is not None and pred(node)
        return m

    def _job_rows(self, job: Job) -> np.ndarray:
        """Matrix rows of the job's proposed allocations, one entry an
        alloc: the live ones the plan does not remove, and the plan's own
        (distinct_property counts the job's, whatever their group)."""
        removed = self.ctx.plan_removed_ids()
        node_ids = [
            a.node_id
            for a in self.ctx.snapshot.allocs_by_job(job.namespace, job.id)
            if not a.terminal_status() and a.id not in removed
        ]
        for allocs in self.ctx.plan.node_allocation.values():
            node_ids.extend(a.node_id for a in allocs)
        row_of = self.matrix.row_of
        rows = [row_of.get(nid) for nid in node_ids]
        return np.array([r for r in rows if r is not None], np.int64)

    def _host_mask(
        self, job: Job, tg: TaskGroup, compiled: CompiledTaskGroup
    ) -> Optional[np.ndarray]:
        """Per-node mask for unique-attr escapes, distinct_hosts, a
        datacenter list past the encoding, host volumes, escaped device
        asks and escaped distinct_property constraints.  None when nothing
        applies (the common case).  Every term is a column of the matrix
        read whole (``HostFeasibility``: once per distinct value, cached
        across evals); no term walks the nodes unless its attribute has no
        column (``_walk``)."""
        n = self.matrix.capacity
        hf = self.matrix.host_feasibility()
        terms: List[np.ndarray] = []

        # Registered-volume feasibility (CSIVolumeChecker, feasible.go:209):
        # the volume must exist, its claims must admit this request, and
        # only nodes exposing its backing host volume qualify.
        volumes: List[str] = list(compiled.host_volumes)
        for vreq in compiled.csi_volumes:
            vol = self.ctx.snapshot.volume_by_id(job.namespace, vreq.source)
            if vol is None or not self._volume_claimable(vol, vreq, job):
                return np.zeros((n,), bool)  # nothing feasible → blocked
            volumes.append(vol.source)

        if compiled.dc_escaped:
            terms.append(hf.datacenter_mask(job.datacenters))
        for e in compiled.escaped:
            con = e.constraint
            if not e.unique or con.operand in (
                Op.DISTINCT_HOSTS.value, Op.DISTINCT_PROPERTY.value
            ):
                continue
            m = hf.constraint_mask(con)
            if m is None:
                m = self._walk(lambda node: check_constraint_host(con, node))
            terms.append(m)
        if volumes:
            terms.append(hf.volume_mask(volumes))
        for name, count in compiled.escaped_devices:
            terms.append(hf.device_mask(name, count))

        distinct_hosts = any(
            e.constraint.operand == Op.DISTINCT_HOSTS.value
            for e in compiled.escaped
        )
        if distinct_hosts:
            # Mask nodes already holding a proposed alloc of this job
            # (DistinctHostsIterator, feasible.go:505).
            m = np.ones((n,), bool)
            rows = self._job_rows(job)
            m[rows[rows < n]] = False
            terms.append(m)

        for e in compiled.escaped:
            con = e.constraint
            if con.operand != Op.DISTINCT_PROPERTY.value:
                continue
            # A distinct_property the request had no slot for: the limit as
            # the job's proposed allocs read it now, fixed for this select
            # (DistinctPropertyIterator, feasible.go:604).
            limit = distinct_property_limit(con)
            name = attr_name(con.l_target)
            col = hf.column(name)
            if col is not None:
                held, counts = np.unique(
                    col[self._job_rows(job)], return_counts=True
                )
                full = held[(counts >= limit) & (held != 0)]
                terms.append((col != 0) & ~np.isin(col, full))
                continue
            held: Dict[str, int] = {}
            for row in self._job_rows(job):
                node = self.ctx.snapshot.node_by_id(
                    self.matrix.node_of.get(int(row), "")
                )
                v = node_attributes(node).get(name) if node else None
                if v:
                    held[v] = held.get(v, 0) + 1
            terms.append(self._walk(
                lambda node: bool(node_attributes(node).get(name))
                and held.get(node_attributes(node).get(name), 0) < limit
            ))

        # (a registration can grow the matrix between two reads: rows past
        # a term's width were not checked by it)
        terms = [_pad_width(t, n, False)[:n] for t in terms]
        trace.add_args(escaped=len(terms))
        if not terms:
            return None
        return np.logical_and.reduce(terms) if len(terms) > 1 else terms[0]

    def _distinct_property_seed(
        self, job: Job, compiled: CompiledTaskGroup, chosen_rows=()
    ) -> Tuple[SchedRequest, Optional[np.ndarray]]:
        """The request with its distinct_property stage seeded: per slot,
        the property values the job's proposed allocs hold (those of
        ``chosen_rows`` too: picks of this select the plan has not got yet)
        with their counts, read off the attribute's column.  The scan
        raises them pick by pick (kernels.distinct_property_pick).  Where a
        job holds more values than the request has room for, the values
        already at their limit go into a host mask instead (second result;
        they stay full whatever the scan picks) and what is still left over
        is taken for full too: never a pick past a limit."""
        req = compiled.request
        if not compiled.distinct_props:
            return req, None
        attr_hash = self.matrix.snapshot_host()["attr_hash"]
        rows = np.concatenate(
            [self._job_rows(job), np.asarray(chosen_rows, np.int64)]
        )
        rows = rows[rows < attr_hash.shape[0]]
        value_hash = np.zeros_like(req.dp_value_hash)
        count = np.zeros_like(req.dp_count)
        mask = None
        for di in range(len(compiled.distinct_props)):
            col = attr_hash[:, int(req.dp_slot[di])]
            held, counts = np.unique(col[rows], return_counts=True)
            counts = counts[held != 0]
            held = held[held != 0]
            if len(held) > MAX_DISTINCT_VALUES:
                order = np.argsort(counts >= req.dp_limit[di], kind="stable")
                over = held[order[MAX_DISTINCT_VALUES:]]
                mask = _and_mask(mask, ~np.isin(col, over))
                held = held[order[:MAX_DISTINCT_VALUES]]
                counts = counts[order[:MAX_DISTINCT_VALUES]]
            value_hash[di, : len(held)] = held
            count[di, : len(held)] = counts
        return req._replace(dp_value_hash=value_hash, dp_count=count), mask

    # -- port assignment (host-side, chosen node only) ----------------------

    def _assign_ports(
        self, node: Node, tg: TaskGroup, extra_used: Optional[set] = None
    ) -> Optional[Dict[str, Dict[str, int]]]:
        """Assign reserved + dynamic ports on the chosen node; None on
        conflict (NetworkIndex equivalent, nomad/structs/network.go:35).
        ``extra_used``: ports handed out earlier in the same select batch,
        before the plan reflects them."""
        if not tg.networks and not any(
            t.resources.networks for t in tg.tasks
        ):
            # No port asks — skip the proposed-allocs walk entirely.  That
            # walk (every live alloc on the node, through the MVCC snapshot
            # wrapper) was the single hottest worker frame for port-less
            # jobs, which place on every node the kernel picks.
            return {}
        used = set(node.reserved.reserved_ports)
        if extra_used:
            used |= extra_used
        for a in self.ctx.proposed_allocs(node.id):
            for nets in a.assigned_ports.values():
                used.update(nets.values())
            for net in a.resources.networks:
                used.update(net.reserved_ports)

        result: Dict[str, Dict[str, int]] = {}
        nets = list(tg.networks) + [
            n for t in tg.tasks for n in t.resources.networks
        ]
        owners = ["group"] * len(tg.networks) + [
            t.name for t in tg.tasks for _ in t.resources.networks
        ]
        cursor = MIN_DYNAMIC_PORT
        for net, owner in zip(nets, owners):
            ports: Dict[str, int] = {}
            for port in net.reserved_ports:
                if port in used:
                    return None
                used.add(port)
                ports[str(port)] = port
            for label in net.dynamic_ports:
                while cursor in used and cursor <= MAX_DYNAMIC_PORT:
                    cursor += 1
                if cursor > MAX_DYNAMIC_PORT:
                    return None
                used.add(cursor)
                ports[label] = cursor
            if ports:
                result.setdefault(owner, {}).update(ports)
        return result

    # -- preemption (host-side, chosen node only) ---------------------------

    def _preempt(self, job: Job, tg: TaskGroup, node: Node, row: int,
                 delta, taken: set, est_terms: float, est_final: float,
                 terms: float, spread: bool):
        """The victims of one preempting pick and the score it records:
        (victims, binpack, final, preemption), or None where no
        admissible set of allocations covers the ask.

        The node's room is the matrix's: its ``used`` as the kernel scored
        it and the applier will verify it (usage aggregates included; a
        sum over the node's Allocation objects would miss them), plus this
        plan's ``delta`` on the row.  The victims come from the proposed
        allocations, less those ``taken`` by earlier picks of this select.

        The kernel ranked the node by an estimate (kernels.score_nodes):
        ``est_final`` is a mean of ``terms`` terms, two of which, summing
        to ``est_terms``, depend on the victims.  What is recorded is
        Nomad's: those two replaced by ScoreFit after the victims are
        gone and the logistic of their net priority."""
        ask = tg.combined_resources()
        with trace.span("sched.preempt", node=node.id):
            host = self.matrix.snapshot_host()
            used = host["used"][row].astype(np.float64) + delta
            totals = host["totals"][row].astype(np.float64)
            proposed = [
                a for a in self.ctx.proposed_allocs(node.id)
                if a.id not in taken
            ]
            victims = select_victims(job, proposed, ask, totals - used)
            trace.add_args(
                victims=-1 if victims is None else len(victims)
            )
            if victims is None:
                return None
            binpack, pre = preempting_scores(
                node, used, ask, victims, spread
            )
        others = est_final * terms - est_terms
        if not victims:
            # Room appeared since the launch: a placement like any other.
            return victims, binpack, (others + binpack) / (terms - 1.0), 0.0
        return victims, binpack, (others + binpack + pre) / terms, pre

    # -- the main entry ------------------------------------------------------

    def select(
        self,
        tg: TaskGroup,
        n_placements: int = 1,
        penalty_nodes: Optional[Sequence[str]] = None,
        restrict_nodes: Optional[Sequence[str]] = None,
    ) -> List[Optional[SelectionOption]]:
        """Place ``n_placements`` allocs of ``tg``; one option (or None) per
        requested placement (reference: stack.go:117-179 Select, called per
        missing alloc from generic_sched.go:472).  ``restrict_nodes`` limits
        candidates to the given set (sticky ephemeral-disk preference,
        generic_sched.go:756-770 findPreferredNode).

        With a coalescer attached to the matrix (the live server), the
        kernel call is batched with other workers' selects and this method
        never touches the device directly; otherwise the whole selection
        holds DEVICE_LOCK (tests, solo tools)."""
        if getattr(self.matrix, "coalescer", None) is not None:
            return self._select_locked(
                tg, n_placements, penalty_nodes, restrict_nodes
            )
        with DEVICE_LOCK:
            return self._select_locked(
                tg, n_placements, penalty_nodes, restrict_nodes
            )

    # -- kernel dispatch (coalesced or solo) --------------------------------

    def _dispatch_place(
        self,
        request: SchedRequest,
        deltas: Dict[int, np.ndarray],
        tg_count: np.ndarray,
        spread_counts: np.ndarray,
        penalty: np.ndarray,
        class_elig: np.ndarray,
        host_mask: Optional[np.ndarray],
        remaining: int,
        evicted: Optional[Dict[int, np.ndarray]] = None,
    ):
        """Run one placement scan; returns host-side arrays (rows, scores,
        binpack, preempted, n_eval, n_filt, n_exh, fit_verified) of scan
        length ≥ the bucket for ``remaining``.  fit_verified is the
        coalesced launch's cross-lane verify column, None on the solo path.

        ``evicted``: what the plan's evictions free per row (credited in
        ``deltas``).  The coalesced launch enters this eval's proposed
        usage into the in-flight claims ledger (scheduler/claims.py) with
        no eviction credited and nothing below zero: a node this plan took
        by preempting reads over-full to the launches after it, as it does
        to the later lanes of its own launch, and room a stop will free is
        not offered before the plan commits.

        With a mesh configured the coalescer routes the batch through the
        node-sharded fused entry (parallel/sharding.py, hierarchical
        top-k); either way the rows returned here are GLOBAL and already
        translated through any shard-preserving capacity growth that
        happened while the dispatch was in flight (matrix.translate_rows),
        so the node_of lookup below never sees a pre-relocation id."""
        from .coalescer import MAX_DELTA_ROWS

        # One consistent width for every per-node array in this request:
        # re-reading matrix.capacity here could disagree with the shapes the
        # caller built if a node registration grew the matrix mid-select.
        n = tg_count.shape[0]
        coal = getattr(self.matrix, "coalescer", None)
        if coal is not None and len(deltas) <= MAX_DELTA_ROWS:
            drows = np.full((MAX_DELTA_ROWS,), -1, np.int32)
            dvals = np.zeros((MAX_DELTA_ROWS, 3), np.float32)
            for i, (row, d) in enumerate(deltas.items()):
                drows[i] = row
                dvals[i] = d
            claims = dvals
            if evicted:
                claims = dvals.copy()
                for i, row in enumerate(deltas):
                    claims[i] += evicted.get(row, 0.0)
            out = coal.place(
                request,
                drows,
                dvals,
                tg_count,
                spread_counts,
                penalty,
                class_elig,
                host_mask if host_mask is not None
                else self.matrix.shared_masks()[1],
                n_live=remaining,
                eval_id=self.ctx.plan.eval_id,
                claim_vals=np.maximum(claims, 0.0),
            )
            return (
                out.rows, out.scores, out.binpack, out.preempted,
                out.nodes_evaluated, out.nodes_filtered, out.nodes_exhausted,
                out.fit_verified,
            )

        # Solo path: dense proposed usage, one direct dispatch.  With a
        # coalescer present (live server) the closure still executes on ITS
        # thread — the one device-launching thread.
        def dev_op():
            arrays = self.matrix.sync()
            n_dev = int(arrays.used.shape[0])
            bucket = min(_pow2_bucket(remaining), PLACEMENT_CHUNK)
            if fake_device.enabled():
                result = fake_device.place_task_group(
                    arrays,
                    request,
                    fake_device.dense_used0(arrays, deltas),
                    _pad_width(tg_count, n_dev, 0),
                    spread_counts,
                    _pad_width(penalty, n_dev, False),
                    class_elig,
                    _pad_width(_full_mask(n, host_mask), n_dev, False),
                    n_placements=bucket,
                )
                return (
                    result.rows, result.scores, result.binpack,
                    result.preempted, result.nodes_evaluated,
                    result.nodes_filtered, result.nodes_exhausted,
                    None,
                )

            import jax.numpy as jnp

            result = kernels.place_task_group(
                arrays,
                request,
                _dense_used0(arrays, deltas),
                jnp.asarray(_pad_width(tg_count, n_dev, 0)),
                jnp.asarray(spread_counts),
                jnp.asarray(_pad_width(penalty, n_dev, False)),
                jnp.asarray(class_elig),
                jnp.asarray(_pad_width(_full_mask(n, host_mask), n_dev, False)),
                n_placements=bucket,
                features=_ratchet_features(request),
            )
            return (
                np.asarray(result.rows),
                np.asarray(result.scores),
                np.asarray(result.binpack),
                np.asarray(result.preempted),
                np.asarray(result.nodes_evaluated),
                np.asarray(result.nodes_filtered),
                np.asarray(result.nodes_exhausted),
                None,
            )

        return self.matrix.run_on_device(dev_op)

    def _select_locked(
        self,
        tg: TaskGroup,
        n_placements: int = 1,
        penalty_nodes: Optional[Sequence[str]] = None,
        restrict_nodes: Optional[Sequence[str]] = None,
    ) -> List[Optional[SelectionOption]]:
        assert self.job is not None, "set_job first"
        job = self.job
        start = time.monotonic()

        sched_cfg = self.ctx.snapshot.scheduler_config()
        with trace.span("sched.encode"):
            compiled = self.encoder.compile(
                job,
                tg,
                algorithm=self.algorithm,
                preemption_enabled=self.preemption_enabled,
            )

        n = self.matrix.capacity

        if penalty_nodes:
            penalty = np.zeros((n,), bool)
            for node_id in penalty_nodes:
                row = self.matrix.row_of.get(node_id)
                if row is not None:
                    penalty[row] = True
        else:
            # Steady state: no penalized nodes — reuse the matrix-wide
            # read-only all-False mask instead of allocating per eval.
            penalty = self.matrix.shared_masks()[0]

        class_elig, base_host_mask = self._feasibility(job, tg, compiled)
        if restrict_nodes is not None:
            allowed = np.zeros((n,), bool)
            for node_id in restrict_nodes:
                row = self.matrix.row_of.get(node_id)
                if row is not None:
                    allowed[row] = True
            base_host_mask = (
                allowed if base_host_mask is None
                else (base_host_mask & allowed)
            )

        options: List[Optional[SelectionOption]] = []
        banned_rows: List[int] = []
        # Accounting for selections made in *earlier kernel calls of this
        # select()*: the plan only learns about them after select returns, so
        # later chunks/retries must fold them in here to avoid over-commit.
        chosen_rows: List[int] = []
        chosen_ports: Dict[str, set] = {}
        chosen_victims: List[Tuple[int, Allocation]] = []
        remaining = n_placements
        retries = 0
        while remaining > 0 and retries <= MAX_SELECT_RETRIES:
            host_mask = base_host_mask
            if banned_rows:
                host_mask = (
                    np.ones((n,), bool) if host_mask is None else host_mask.copy()
                )
                host_mask[banned_rows] = False

            deltas = self._plan_usage_deltas()
            evicted = self._plan_evictions()
            for row in chosen_rows:
                d = deltas.setdefault(row, np.zeros(3, np.float32))
                d += np.asarray(compiled.request.ask, np.float32)
            for row, v in chosen_victims:
                r = v.resources
                freed = np.array([r.cpu, r.memory_mb, r.disk_mb], np.float32)
                deltas[row] -= freed
                evicted[row] = evicted.get(row, 0.0) + freed

            tg_counts = self._tg_counts(job, tg)
            for row in chosen_rows:
                tg_counts[row] = tg_counts.get(row, 0) + 1
            if tg_counts:
                tg_count = np.zeros((n,), np.int32)
                for row, c in tg_counts.items():
                    tg_count[row] = c
            else:
                # First placement pass of a fresh job: no proposed allocs
                # anywhere — reuse the matrix-wide read-only zero vector.
                tg_count = self.matrix.shared_zero_i32()

            spread_counts = self._spread_counts(job, tg, compiled)
            # The distinct_property stage's seeds, the picks of this
            # select's earlier launches included.
            request, dp_mask = self._distinct_property_seed(
                job, compiled, chosen_rows
            )
            host_mask = _and_mask(host_mask, dp_mask)

            # Binpack + score are fused into the placement kernel, so one
            # span covers the whole device dispatch (launch + result wait).
            with trace.span("sched.dispatch", lanes=remaining):
                (rows_all, scores_all, binpack_all, preempted_all, n_eval_all,
                 n_filt_all, n_exh_all, verified_all) = self._dispatch_place(
                    request, deltas, tg_count, spread_counts, penalty,
                    class_elig, host_mask, remaining, evicted,
                )
            take = min(len(rows_all), remaining)
            rows_out = rows_all[:take]
            scores = scores_all[:take]
            binpack = binpack_all[:take]
            preempted = preempted_all[:take]
            n_eval = n_eval_all[:take]
            n_filt = n_filt_all[:take]
            n_exh = n_exh_all[:take]

            retry = False
            launched = len(chosen_rows)
            for i, row in enumerate(rows_out):
                metric = AllocMetric(
                    nodes_evaluated=int(n_eval[i]),
                    nodes_filtered=int(n_filt[i]),
                    nodes_exhausted=int(n_exh[i]),
                )
                metric.allocation_time = time.monotonic() - start
                if row < 0:
                    options.append(None)
                    remaining -= 1
                    continue
                node_id = self.matrix.node_of.get(int(row))
                node = (
                    self.ctx.snapshot.node_by_id(node_id) if node_id else None
                )
                if node is None:
                    banned_rows.append(int(row))
                    retries += 1
                    retry = True
                    break
                # Host-side combinatorial residue: port assignment, aware of
                # ports handed out earlier in this same batch.
                ports = self._assign_ports(
                    node, tg, extra_used=chosen_ports.get(node_id)
                )
                if ports is None:
                    banned_rows.append(int(row))
                    retries += 1
                    retry = True
                    break
                binpack_i, final_i = float(binpack[i]), float(scores[i])
                victims: List[Allocation] = []
                if preempted[i]:
                    # This plan's usage on the row as the launch saw it at
                    # this step: the deltas it was handed and the picks of
                    # its earlier steps.
                    delta = deltas.get(int(row), 0.0) + (
                        chosen_rows[launched:].count(int(row))
                        * np.asarray(compiled.request.ask, np.float64)
                    )
                    found = self._preempt(
                        job, tg, node, int(row), delta,
                        {v.id for _, v in chosen_victims},
                        binpack_i, final_i, float(preempted[i]),
                        compiled.request.algorithm == 1,
                    )
                    if found is None:
                        # Evictable usage with no allocation behind it
                        # (an aggregate), or victims another plan took:
                        # not this node again in this eval.
                        trace.count("nomad.sched.preempt_no_victims")
                        banned_rows.append(int(row))
                        retries += 1
                        retry = True
                        break
                    victims, binpack_i, final_i, pre_i = found
                    if victims:
                        metric.score_node(node_id, "preemption", pre_i)
                metric.score_node(node_id, "binpack", binpack_i)
                metric.score_node(node_id, "final", final_i)
                opt = SelectionOption(
                    node_id=node_id,
                    node=node,
                    row=int(row),
                    final_score=final_i,
                    binpack_score=binpack_i,
                    needs_preempt=bool(victims),
                    metric=metric,
                    victims=victims,
                    assigned_ports=ports,
                    fit_verified=(
                        bool(verified_all[i])
                        if verified_all is not None else None
                    ),
                )
                options.append(opt)
                chosen_rows.append(int(row))
                if ports:
                    bag = chosen_ports.setdefault(node_id, set())
                    for per_task in ports.values():
                        bag.update(per_task.values())
                remaining -= 1
                if preempted[i]:
                    # A preempting pick changes the proposed state in a
                    # way the in-scan accounting cannot see (the victims
                    # are chosen here, after the launch): the rows the
                    # launch placed after it are dropped and the loop
                    # re-enters, one launch per such pick, with the ask
                    # and the victims in this node's delta.
                    chosen_victims.extend((int(row), v) for v in victims)
                    if remaining:
                        trace.count("nomad.sched.preempt_reentries")
                    retry = True
                    break
            if not retry:
                # Results beyond `take` from this chunk are discarded;
                # remaining placements loop around with updated accounting.
                continue

        while len(options) < n_placements:
            options.append(None)
        return options


class SystemStack(GenericStack):
    """System-job stack: feasibility for every node at once
    (reference: stack.go:183-321; the system scheduler places one alloc per
    feasible node, system_sched.go:22-54)."""

    def feasible_nodes(self, tg: TaskGroup) -> Tuple[List[str], AllocMetric]:
        assert self.job is not None
        job = self.job
        with trace.span("sched.encode"):
            compiled = self.encoder.compile(
                job, tg, algorithm=self.algorithm, preemption_enabled=False
            )
        class_elig, host_mask = self._feasibility(job, tg, compiled)
        request, dp_mask = self._distinct_property_seed(job, compiled)
        host_mask = _and_mask(host_mask, dp_mask)
        n = self.matrix.capacity

        # Fit must judge the node *without* this job's own TG alloc — a
        # re-evaluation replaces it, it doesn't stack a second copy — and
        # with the in-flight plan's stops/placements folded in.
        deltas = self._plan_usage_deltas()
        for a in self.ctx.snapshot.allocs_by_job(job.namespace, job.id):
            if a.terminal_status() or a.task_group != tg.name:
                continue
            row = self.matrix.row_of.get(a.node_id)
            if row is None:
                continue
            d = deltas.setdefault(row, np.zeros(3, np.float32))
            r = a.resources
            d -= np.array([r.cpu, r.memory_mb, r.disk_mb], np.float32)

        def dev_op():
            arrays = self.matrix.sync()
            n_dev = int(arrays.used.shape[0])
            if fake_device.enabled():
                return fake_device.system_feasible(
                    arrays,
                    fake_device.dense_used0(arrays, deltas),
                    request,
                    class_elig,
                    _pad_width(_full_mask(n, host_mask), n_dev, False),
                )

            import jax.numpy as jnp

            # One stacked (2, N) result = one device→host fetch (each
            # separate fetch is its own synchronous round-trip).
            return np.asarray(kernels.system_feasible(
                arrays,
                _dense_used0(arrays, deltas),
                request,
                jnp.asarray(class_elig),
                jnp.asarray(
                    _pad_width(_full_mask(n, host_mask), n_dev, False)
                ),
            ))

        with trace.span("sched.dispatch"):
            mf = self.matrix.run_on_device(dev_op)
        mask, fits = mf[0], mf[1]
        ok = mask & fits
        metric = AllocMetric(
            nodes_evaluated=int(mask.sum()),
            nodes_filtered=int((~mask).sum()),
            nodes_exhausted=int((mask & ~fits).sum()),
        )
        out = []
        for row in np.nonzero(ok)[0]:
            node_id = self.matrix.node_of.get(int(row))
            if node_id is not None:
                out.append(node_id)
        return out, metric
