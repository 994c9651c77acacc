"""Vectorized scheduling kernels — the hot path, in JAX.

Each kernel is a pure function over the device-resident node matrix
(``state.matrix.DeviceArrays``) and a compiled request
(``ops.encode.SchedRequest``). Where the reference pulls nodes one at a time
through a 14-iterator chain (scheduler/stack.go:324-417) and bounds work by
sampling log₂(n) candidates (stack.go:78-91), these kernels score **all**
nodes in one fused XLA program; placement of ``count`` allocs is a
``lax.scan`` that scatters proposed usage between steps (the reference's
in-plan "proposed allocs" cache, rank.go:41-52).

Score semantics mirror the reference exactly (see tests/test_kernels.py
golden tests against the scalar oracle in structs.funcs):
  binpack     = ScoreFitBinPack/18           (funcs.go:186, rank.go:513)
  anti-aff    = -(collisions+1)/desired      (rank.go:601-607, only if >0)
  penalty     = -1 on penalized nodes        (rank.go:646, only if penalized)
  affinity    = Σ weight·match / Σ|weight|   (rank.go:704-728, only if ≠0)
  spread      = per-stanza boosts            (spread.go:110-178, only if ≠0)
  preemption  = logistic(netPriority)        (rank.go:773-844, only if used)
  final       = mean of appended components  (rank.go:737-771)
"""

from __future__ import annotations

import contextlib
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..state.matrix import PRIORITY_BUCKETS
from .encode import (
    MAX_AFFINITIES,
    MAX_CONSTRAINTS,
    MAX_DISTINCT_PROPS,
    MAX_SPREADS,
    OP_EQ,
    OP_GT,
    OP_GTE,
    OP_IS_NOT_SET,
    OP_IS_SET,
    OP_LT,
    OP_LTE,
    OP_NEQ,
    OP_VER_EQ,
    OP_VER_GT,
    OP_VER_GTE,
    OP_VER_LT,
    OP_VER_LTE,
    SchedRequest,
    pow2_bucket,
)

# Plain float (not a jnp scalar): materializing a device array at import
# time would force backend initialization on `import nomad_tpu`.
NEG_INF = -1e30

# Preemption score constants (reference: rank.go preemptionScore).
PREEMPTION_RATE = 0.0048
PREEMPTION_ORIGIN = 2048.0


# ---------------------------------------------------------------------------
# Static feature occupancy (compile-time work bounds)
# ---------------------------------------------------------------------------


class Features(NamedTuple):
    """Static per-dispatch work bounds, derived from *batch occupancy*.

    The request encoding pads every dispatch to worst-case widths
    (16 constraints, 8 affinities, 2 spreads, preemption tables, port
    bitmaps) so one compile serves every request shape — but a typical
    batch uses 1-2 constraint slots and no preemption, and the padded
    slots still execute (each inactive predicate is two table gathers
    plus the full decode over all N nodes). ``Features`` makes the
    *occupancy* static: widths are pow2-bucketed so the jit cache stays
    bounded (≤ 6·5·3·2·2·3 variants, in practice a handful), and a
    dispatcher that ratchets via :meth:`widen` compiles each variant at
    most once per process.

    Fields are hashable scalars — the whole tuple is a valid
    ``static_argnames`` value.
    """

    c_width: int = MAX_CONSTRAINTS  # active constraint slots (pow2, 0..16)
    a_width: int = MAX_AFFINITIES  # active affinity slots (pow2, 0..8)
    s_width: int = MAX_SPREADS  # active spread stanzas (0..2)
    preempt: bool = True  # any eval has preemption enabled
    ports: bool = True  # any eval asks for static/dynamic ports
    dp_width: int = MAX_DISTINCT_PROPS  # distinct_property slots (0..2)

    def widen(self, other: "Features") -> "Features":
        """Monotone union — the dispatcher's recompile ratchet."""
        return Features(
            c_width=max(self.c_width, other.c_width),
            a_width=max(self.a_width, other.a_width),
            s_width=max(self.s_width, other.s_width),
            preempt=self.preempt or other.preempt,
            ports=self.ports or other.ports,
            dp_width=max(self.dp_width, other.dp_width),
        )


FULL_FEATURES = Features()

# The request's distinct_property operands, its last fields.  At
# ``dp_width`` 0 the placement program takes the request without them (None:
# no stage reads them, and a caller that holds the request as device arrays
# hands over four buffers fewer; a launch of the server hands over the
# request slab's pack whole, and what is not read costs it nothing).
DP_FIELDS = SchedRequest._fields.index("dp_slot")
assert SchedRequest._fields[DP_FIELDS:] == (
    "dp_slot", "dp_limit", "dp_value_hash", "dp_count"
)


def device_request(fields, dp_width: int) -> SchedRequest:
    """The request a launch hands the placement program from its unpacked
    ``fields`` (all of them, or all but the last four at ``dp_width`` 0)."""
    fields = list(fields)[: None if dp_width else DP_FIELDS]
    return SchedRequest(
        *fields, *[None] * (len(SchedRequest._fields) - len(fields))
    )


def _slot_width(slots, max_width: int) -> int:
    """Last active slot index + 1 over a (..., W) slot array. Spread slots
    are positional (an escaped stanza leaves a -1 hole), so occupancy is
    the last-used index, not the active count."""
    s = np.asarray(slots).reshape(-1, max_width)
    active = s >= 0
    if not active.any():
        return 0
    return int(np.max(np.where(active, np.arange(max_width)[None, :], -1))) + 1


def features_of(reqs: SchedRequest) -> Features:
    """Measure a request (or a stacked batch of requests) into a bucketed
    :class:`Features`. Pure numpy — safe to call per dispatch on the
    staging thread (a few µs on (B, 16) slot arrays)."""
    c_w = _slot_width(reqs.c_slot, MAX_CONSTRAINTS)
    a_w = _slot_width(reqs.a_slot, MAX_AFFINITIES)
    return Features(
        c_width=min(MAX_CONSTRAINTS, pow2_bucket(c_w)) if c_w else 0,
        a_width=min(MAX_AFFINITIES, pow2_bucket(a_w)) if a_w else 0,
        s_width=_slot_width(reqs.s_slot, MAX_SPREADS),
        preempt=bool(np.any(np.asarray(reqs.preempt_bucket) >= 0)),
        ports=bool(
            np.any(np.asarray(reqs.p_static) >= 0)
            or np.any(np.asarray(reqs.p_dyn) > 0)
        ),
        dp_width=_slot_width(reqs.dp_slot, MAX_DISTINCT_PROPS),
    )


# ---------------------------------------------------------------------------
# The topology seam
# ---------------------------------------------------------------------------


class Topology:
    """What the topology a placement program runs on decides, and nothing
    else: which rows and lanes this shard holds, how a reduction over its
    nodes becomes one over the cluster's, how a lane's value reaches every
    shard.  The placement step is written once against these methods
    (``_fused_place_batch_impl`` and what it calls) and never asks which
    instance it holds.

    This class is the one-device instance (``ONE_DEVICE``): the device
    holds every row and lane, so each method returns its argument and the
    traced program holds no collective, gather or cast
    (tests/test_topology_seam.py).  The mesh's (``parallel/sharding.py::
    MESH``) overrides each with the collective it stands for.  Whoever
    builds a jitted entry binds one of the two (``fused_place_batch``,
    ``place_task_group`` here, ``_shard_mapped`` there): it is no operand,
    no static argument and no option.
    """

    def shard(self, n_local: int, b_local: int):
        """(global row of this shard's first row, the launch's lane of its
        first lane), for a shard of ``n_local`` rows and ``b_local`` lanes."""
        return 0, 0

    def vary(self, x, nodes: bool = False):
        """``x`` typed as differing from shard to shard over the lanes' axis
        (and the nodes'): a loop carry that starts as a constant."""
        return x

    def all_lanes(self, x, axis: int = 0):
        """``x`` of this shard's lanes -> of all the launch's lanes."""
        return x

    def max(self, x, lanes: bool = False):
        """The largest ``x`` of any node shard (with ``lanes``: any shard)."""
        return x

    def min(self, x, lanes: bool = False):
        """The smallest ``x`` of any node shard (with ``lanes``: any shard)."""
        return x

    def sum(self, x):
        """``x`` summed over the node shards: a count of nodes, or a value
        only a row's owner holds (the others offer zeros)."""
        return x

    def any(self, flag):
        """``flag`` (bool) holds on some node shard."""
        return flag

    def all(self, flags):
        """``flags`` (bool) hold on every node shard: each can veto."""
        return flags

    def exchange(self, name: str):
        """The scope a profile files an exchange between shards under
        (``gather``, ``elect``, ``count``, ``broadcast``,
        ``rules_exchange``): a mesh's alone, no scope on one device."""
        return contextlib.nullcontext()


ONE_DEVICE = Topology()

# No row: what a shard that does not hold the best score offers an election.
_NO_ROW = 2 ** 30


def local_rows(rows, row_offset, n_local: int):
    """(global rows (...,)) -> (held by this shard, index among its
    ``n_local`` rows, in range whether held or not); a launch's lanes among
    a shard's own are told apart the same way."""
    local = rows - row_offset
    return (rows >= 0) & (local >= 0) & (local < n_local), jnp.clip(
        local, 0, n_local - 1
    )


def add_claims(image, rows, vals, row_offset):
    """``image`` (n_local, 3) with ``vals`` (..., 3) added on those of the
    global ``rows`` (...,) this shard holds (a padding row, -1, is held by
    none)."""
    mine, safe = local_rows(rows, row_offset, image.shape[0])
    return image.at[safe.reshape(-1)].add(
        jnp.where(mine[..., None], vals, 0.0).reshape(-1, 3)
    )


def elect(topo: Topology, scores, row_offset, lanes: bool = False):
    """The election every pick is: (the cluster's best of ``scores``, the
    lowest global row that holds it).  ``scores`` (n_local,) are this
    shard's, already masked (NEG_INF = not a candidate).  ``jnp.argmax`` is
    the lowest local index of the shard's own maximum; ``topo.max`` elects
    the winning score and ``topo.min`` the lowest row among the shards that
    hold it, so ties break to the lowest global row whatever the layout:
    one device's arg-max bit for bit (PARITY.md "The election").  With
    ``lanes`` the election spans the batch shards too (a lane's scores live
    on one of them; the others offer NEG_INF).  Nothing wider than a scalar
    crosses a shard."""
    idx = jnp.argmax(scores).astype(jnp.int32)
    with topo.exchange("elect"):
        best = topo.max(scores[idx], lanes)
    row = jnp.where(scores[idx] == best, row_offset + idx, _NO_ROW)
    with topo.exchange("elect"):
        return best, topo.min(row, lanes)


# ---------------------------------------------------------------------------
# Feasibility
# ---------------------------------------------------------------------------


def _check_predicate(hash_T, numver_T, slot, op, want_hash, want_num):
    """Evaluate one predicate for every node against *transposed* attribute
    tables: ``hash_T`` is (A, N), ``numver_T`` is (2A, N) — numeric rows
    then version-packed rows. Each predicate reads exactly two contiguous
    (N,)-rows (hash + the one numeric flavor its op needs). Row-major
    column reads of the old (N, A) layout were strided dynamic-slices that
    walked the whole table per predicate — the dominant memory traffic of a
    batched dispatch (≈3× slower, measured on 10K nodes). The transposes
    are batch-invariant, so XLA hoists them out of the vmap and builds them
    once per dispatch.

    The op decode is a three-way select over scalar op-class masks instead
    of a 13-deep ``jnp.where`` chain: at B=512×C=16×N=10K the chain alone
    was ~2G elementwise ops per dispatch.

    Returns (N,) bool; inactive predicates (slot < 0) return True.

    Missing-attribute semantics follow checkConstraint (feasible.go:793-858):
    ``=`` and ordered comparisons require the attribute to be present; ``!=``
    passes when it is absent. Version ops read the version-packed rows.
    NaN operands (unparseable numerics) fail ordered comparisons exactly as
    the old explicit ``num_ok`` mask did — IEEE NaN compares false.
    """
    nattrs = hash_T.shape[0]
    safe_slot = jnp.maximum(slot, 0)
    h = lax.dynamic_index_in_dim(hash_T, safe_slot, 0, False)  # (N,)
    is_ver = op >= OP_VER_EQ
    v = lax.dynamic_index_in_dim(
        numver_T, safe_slot + jnp.where(is_ver, nattrs, 0), 0, False
    )  # (N,) contiguous
    present = h != 0

    # Scalar op-class selectors (broadcast against the (N,) vectors).
    is_num = ((op >= OP_LT) & (op <= OP_GTE)) | is_ver
    is_pres = (op == OP_IS_SET) | (op == OP_IS_NOT_SET)
    negate = (op == OP_NEQ) | (op == OP_IS_NOT_SET)
    want_lt = (op == OP_LT) | (op == OP_LTE) | (op == OP_VER_LT) | (op == OP_VER_LTE)
    want_gt = (op == OP_GT) | (op == OP_GTE) | (op == OP_VER_GT) | (op == OP_VER_GTE)
    want_eq = (
        (op == OP_LTE)
        | (op == OP_GTE)
        | (op == OP_VER_EQ)
        | (op == OP_VER_LTE)
        | (op == OP_VER_GTE)
    )
    cmp = (want_lt & (v < want_num)) | (want_gt & (v > want_num)) | (
        want_eq & (v == want_num)
    )
    inner = jnp.where(is_num, cmp, jnp.where(is_pres, True, h == want_hash))
    res = (present & inner) ^ negate
    return res | (slot < 0)


def _tables(arrays):
    """Transposed attribute tables ((A, N) hash, (2A, N) numeric‖version)
    for _check_predicate. Batch-invariant: identical across every lane of a
    dispatch, so XLA computes (and CSEs) them once per launch."""
    hash_T = arrays.attr_hash.T
    numver_T = jnp.concatenate([arrays.attr_num.T, arrays.attr_ver.T], axis=0)
    return hash_T, numver_T


def _attr_rows(table_T, slots, width: int):
    """(width, N): row ``slots[i]`` (row 0 for an empty slot, -1) of a
    transposed attribute table for the first ``width`` (static) slots, one
    contiguous dynamic slice a slot.  A ``table_T[slots]`` (or a ``vmap``
    over the slots) is a gather, which the TPU compiler runs as a serial
    loop with one strided read-modify-write a turn (PERF.md section 5,
    PR 47)."""
    if width == 0:  # no lane carries the stage: nothing of it is read
        return jnp.zeros((0,) + table_T.shape[1:], table_T.dtype)
    return jnp.stack([
        lax.dynamic_index_in_dim(
            table_T, jnp.maximum(slots[i], 0), 0, keepdims=False
        )
        for i in range(width)
    ])


def _check_predicates(arrays, slot, op, want_hash, want_num, width: int):
    """(width, N) bool: ``_check_predicate`` of a request's first ``width``
    (static) predicate slots, constraints or affinities."""
    hash_T, numver_T = _tables(arrays)
    return jnp.stack([
        _check_predicate(
            hash_T, numver_T, slot[i], op[i], want_hash[i], want_num[i]
        )
        for i in range(width)
    ])


def constraint_mask(
    arrays, req: SchedRequest, c_width: int = MAX_CONSTRAINTS
) -> jnp.ndarray:
    """(N,) bool — all hard constraints pass (ConstraintChecker equivalent).

    ``c_width`` (static) bounds the predicate loop to the batch's slot
    occupancy; padded requests are always left-packed so slicing is exact.
    """
    n = arrays.attr_hash.shape[0]
    if c_width == 0:
        return jnp.ones((n,), bool)
    per_constraint = _check_predicates(
        arrays, req.c_slot, req.c_op, req.c_hash, req.c_num, c_width
    )  # (c_width, N)
    return jnp.all(per_constraint, axis=0)


def datacenter_mask(arrays, req: SchedRequest) -> jnp.ndarray:
    """(N,) bool — node's datacenter is in the job's list (util.go
    readyNodesInDCs). Attribute slot 0 is node.datacenter by registry order."""
    dc = arrays.attr_hash[:, 0]  # (N,)
    member = (dc[:, None] == req.dc_hash[None, :]) & (req.dc_hash[None, :] > 0)
    skip = req.dc_hash[0] == -1  # escaped: host filters datacenters instead
    return jnp.any(member, axis=1) | skip


def device_mask(arrays, req: SchedRequest) -> jnp.ndarray:
    """(N,) bool — free device instances cover the ask (DeviceChecker +
    accounting, feasible.go:1173, structs DeviceAccounter)."""
    free = arrays.dev_total - arrays.dev_used  # (N, D)
    ok = (free >= req.dev_ask[None, :]) | (req.dev_ask[None, :] == 0)
    return jnp.all(ok, axis=1)


def port_mask(arrays, req: SchedRequest, enabled: bool = True) -> jnp.ndarray:
    """(N,) bool — no requested static port collides with the node's
    occupied-port bitmap, and the dynamic range has room (the vectorized
    half of NetworkIndex, structs/network.go:35; exact assignment stays
    host-side on the chosen node, re-verified at plan apply).

    ``enabled=False`` (static, from Features) short-circuits to all-True
    when no eval in the batch asks for any port."""
    from ..state.matrix import DYN_PORT_CAPACITY

    if not enabled:
        return jnp.ones((arrays.port_words.shape[0],), bool)
    p = req.p_static  # (P,)
    valid = p >= 0
    word = jnp.maximum(p, 0) >> 5  # (P,)
    bit = (jnp.maximum(p, 0) & 31).astype(jnp.uint32)
    words = arrays.port_words[:, word]  # (N, P)
    taken = (words >> bit[None, :]) & jnp.uint32(1)
    conflict = jnp.any(valid[None, :] & (taken == 1), axis=1)  # (N,)
    dyn_ok = arrays.dyn_used + req.p_dyn <= DYN_PORT_CAPACITY
    return (~conflict) & dyn_ok


@jax.named_scope("feasibility")
def feasibility_mask(arrays, req: SchedRequest,
                     class_elig: Optional[jnp.ndarray] = None,
                     host_mask: Optional[jnp.ndarray] = None,
                     features: Features = FULL_FEATURES):
    """(N,) bool — eligible ∧ dc ∧ constraints ∧ devices ∧ escaped checks.

    ``class_elig``: (num_classes,) bool from host-side evaluation of escaped
    constraints, gathered per node via class_id (the computed-class cache,
    feasible.go:1029). ``host_mask``: optional (N,) bool for unique-attr
    escapes. ``features`` (static) bounds the work to the batch occupancy.
    """
    mask = arrays.eligible
    mask &= datacenter_mask(arrays, req)
    mask &= constraint_mask(arrays, req, features.c_width)
    mask &= device_mask(arrays, req)
    mask &= port_mask(arrays, req, features.ports)
    if class_elig is not None:
        mask &= class_mask(arrays, class_elig)
    if host_mask is not None:
        mask &= host_mask
    return mask


def class_mask(arrays, class_elig):
    """(N,) bool — the node's computed class is eligible: ``class_elig``
    ((num_classes,) bool) gathered per node by its class id."""
    cid = jnp.maximum(arrays.class_id, 0)
    return jnp.where(arrays.class_id < 0, False, class_elig[cid])


def distinct_property_columns(arrays, req: SchedRequest, dp_width: int):
    """(W, N) i32 — every node's value id of the request's first
    ``dp_width`` distinct_property slots: the attribute's hash column, 0
    where a node lacks it.  The same in every step of a scan
    (``lane_invariants``)."""
    return _attr_rows(arrays.attr_hash.T, req.dp_slot, dp_width)


def distinct_property_counts(col, req: SchedRequest, dp_width: int):
    """(W, N) f32 — the scan's first carry of the distinct_property stage:
    for every node, the allocs the job already holds on nodes that share
    its value of the property (``req.dp_value_hash`` / ``req.dp_count``,
    seeded by the stack from the live and proposed allocations).  ``col``:
    ``distinct_property_columns``."""
    value_hash = req.dp_value_hash[:dp_width]  # (W, V)
    vmatch = (col[:, :, None] == value_hash[:, None, :]) & (
        value_hash[:, None, :] != 0
    )  # (W, N, V), at most one hit a node: a masked sum, as spread_score
    return jnp.sum(
        jnp.where(vmatch, req.dp_count[:dp_width, None, :], 0.0), axis=2
    )


def distinct_property_mask(col, req: SchedRequest, dp_cnt, dp_width: int):
    """(N,) bool — the nodes every distinct_property limit still admits
    (DistinctPropertyIterator, feasible.go:604-700): the node has the
    property, and its value holds fewer allocs of the job than the limit.
    ``dp_cnt`` (W, N) changes from pick to pick (``distinct_property_pick``):
    this is the feasibility term that is NOT loop-invariant."""
    active = req.dp_slot[:dp_width] >= 0
    full = (col == 0) | (dp_cnt >= req.dp_limit[:dp_width, None])
    return ~jnp.any(active[:, None] & full, axis=0)


def distinct_property_values_at(arrays, req: SchedRequest, row):
    """Per-slot property value of node ``row`` ((DP,) i32), as
    ``spread_values_at``."""
    return arrays.attr_hash[row, jnp.maximum(req.dp_slot, 0)]


def distinct_property_pick(col, req: SchedRequest, dp_cnt, values,
                           dp_width: int):
    """``dp_cnt`` after a pick on a node whose property values are
    ``values`` ((DP,) i32): one more alloc on every node that shares one."""
    active = req.dp_slot[:dp_width] >= 0
    v = values[:dp_width, None]
    return dp_cnt + ((col == v) & (v != 0) & active[:, None])


@jax.jit
def system_feasible(arrays, used0, req: SchedRequest, class_elig, host_mask):
    """Fused system-scheduler pass: feasibility ∧ fit for every node in one
    compiled program (SystemStack, stack.go:183-321 — system jobs need no
    ranking, just the all-node mask).

    Returns ONE stacked (2, N) bool array [mask, fits] so the host pays a
    single device→host fetch (each separate fetch is its own synchronous
    round-trip)."""
    mask = feasibility_mask(arrays, req, class_elig, host_mask)
    # A system job places one alloc a node: the limit as the seeds alone
    # read it (the host re-checks what it takes, system.py).
    col = distinct_property_columns(arrays, req, MAX_DISTINCT_PROPS)
    mask &= distinct_property_mask(
        col, req,
        distinct_property_counts(col, req, MAX_DISTINCT_PROPS),
        MAX_DISTINCT_PROPS,
    )
    fits, _, _ = fit_and_binpack(arrays, used0, req)
    return jnp.stack([mask, fits])


# ---------------------------------------------------------------------------
# Scoring
# ---------------------------------------------------------------------------


@jax.named_scope("binpack")
def fit_and_binpack(arrays, used, req: SchedRequest):
    """Resource fit + normalized fit score for all nodes.

    Returns (fits (N,) bool, score (N,) f32, exhausted_dim (N,) i32).
    util = current used + ask; fit requires util ≤ totals in all dims
    (AllocsFit, funcs.go:97-160); score per scheduler_algorithm
    (rank.go:166-170, funcs.go:186/213) normalized by 18 (rank.go:513-516).
    """
    util = used + req.ask[None, :]  # (N, 3)
    fits_dim = util <= arrays.totals  # (N, 3)
    fits = jnp.all(fits_dim, axis=1)
    # first exhausted dim index for metrics (0=cpu,1=mem,2=disk, -1 = fits)
    exhausted = jnp.argmax(~fits_dim, axis=1).astype(jnp.int32)
    exhausted = jnp.where(fits, -1, exhausted)
    return fits, score_fit(arrays, util, req), exhausted


def score_fit(arrays, util, req: SchedRequest):
    """(N,) f32 — ScoreFit of the utilisation ``util`` (N, 3), normalized
    by 18: ``fit_and_binpack``'s score of ``used + ask``, and the score of
    a preempting node's utilisation after eviction (``score_nodes``)."""
    denom = jnp.maximum(arrays.totals, 1.0)
    free = 1.0 - util / denom  # (N, 3)
    free_cpu, free_mem = free[:, 0], free[:, 1]
    # 10**x as exp2(x·log₂10): XLA CPU lowers pow() through a generic
    # expf/logf pair ~4× slower than a bare exp2; identical to ~1e-7 rel.
    log2_10 = jnp.float32(3.321928094887362)
    total = jnp.exp2(free_cpu * log2_10) + jnp.exp2(free_mem * log2_10)
    binpack = jnp.clip(20.0 - total, 0.0, 18.0)
    spread = jnp.clip(total - 2.0, 0.0, 18.0)
    return jnp.where(req.algorithm == 1, spread, binpack) / 18.0


@jax.named_scope("affinity_spread")
def anti_affinity_score(tg_count, req: SchedRequest):
    """(score (N,), appended (N,)) — JobAntiAffinityIterator (rank.go:560-607).

    ``tg_count`` (N,) i32 = proposed allocs of this job+TG per node."""
    collisions = tg_count.astype(jnp.float32)
    score = -(collisions + 1.0) / req.desired_count
    appended = collisions > 0
    return jnp.where(appended, score, 0.0), appended


@jax.named_scope("affinity_spread")
def penalty_score(penalty_mask):
    """NodeReschedulingPenaltyIterator (rank.go:630-646)."""
    return jnp.where(penalty_mask, -1.0, 0.0), penalty_mask


@jax.named_scope("affinity_spread")
def affinity_score(arrays, req: SchedRequest, a_width: int = MAX_AFFINITIES):
    """NodeAffinityIterator (rank.go:698-728): Σ weight·match / Σ|weight|,
    appended only when non-zero. ``a_width`` (static) bounds the stanza loop
    to the batch occupancy; 0 skips the pass entirely."""
    n = arrays.attr_hash.shape[0]
    if a_width == 0:
        zeros = jnp.zeros((n,), jnp.float32)
        return zeros, jnp.zeros((n,), bool)
    a_slot = req.a_slot[:a_width]
    a_weight = req.a_weight[:a_width]
    matches = _check_predicates(
        arrays, req.a_slot, req.a_op, req.a_hash, req.a_num, a_width
    )  # (a_width, N)
    active = (a_slot >= 0)[:, None]  # (a_width, 1)
    matched = matches & active
    sum_weight = jnp.sum(jnp.abs(a_weight) * (a_slot >= 0))
    total = jnp.sum(matched * a_weight[:, None], axis=0)  # (N,)
    norm = total / jnp.maximum(sum_weight, 1e-9)
    appended = (total != 0.0) & (sum_weight > 0)
    return jnp.where(appended, norm, 0.0), appended


@jax.named_scope("affinity_spread")
def spread_columns(arrays, req: SchedRequest, s_width: int = MAX_SPREADS):
    """(s_width, N) i32 — every node's value of each spread stanza's
    attribute (its hash, 0 = unset).  The same in every step of a scan
    (``lane_invariants``); what a step matches against the value table and
    the counts it carries (``spread_score``)."""
    return _attr_rows(arrays.attr_hash.T, req.s_slot, s_width)


@jax.named_scope("affinity_spread")
def spread_score(nvalues, req: SchedRequest, spread_counts,
                 s_width: int = MAX_SPREADS):
    """SpreadIterator (spread.go:110-257).

    ``nvalues`` (s_width, N) i32 — ``spread_columns``.
    ``spread_counts`` (S, V) f32 — usage count per known attribute value
    (existing + proposed allocs of this TG), aligned with req.s_value_hash.
    ``s_width`` (static) bounds the stanza loop to the batch occupancy.
    Returns (score (N,), appended (N,)).
    """
    n = nvalues.shape[1]
    if s_width == 0:
        return jnp.zeros((n,), jnp.float32), jnp.zeros((n,), bool)

    def one_stanza(slot, nvalue, weight, even, value_hash, desired, implicit,
                   counts):
        active = slot >= 0
        node_has = nvalue != 0

        # match node value against the known-values table
        vmatch = (nvalue[:, None] == value_hash[None, :]) & (
            value_hash[None, :] != 0
        )  # (N, V)
        found = jnp.any(vmatch, axis=1)
        # Per-node lookups as masked reductions over the (small) V axis.
        # ``counts[vidx]``-style element gathers lower to scalarized TPU
        # gathers (slice_sizes={1,1,1}) that serialize 5M+ loads and
        # dominated the whole scoring pipeline; vmatch has at most one hit
        # per row, so a masked sum is the same value at VPU speed.
        count_at = jnp.sum(jnp.where(vmatch, counts[None, :], 0.0), axis=1)
        used_count = count_at + 1.0  # +1 = this placement

        # ---- targeted mode (spread.go:134-165)
        desired_ok = ~jnp.isnan(desired)  # (V,)
        has_target = jnp.any(vmatch & desired_ok[None, :], axis=1)
        desired_at = jnp.sum(
            jnp.where(vmatch & desired_ok[None, :], desired[None, :], 0.0),
            axis=1,
        )
        desired_v = jnp.where(has_target, desired_at, jnp.nan)
        use_implicit = ~has_target & ~jnp.isnan(implicit)
        desired_v = jnp.where(use_implicit, implicit, desired_v)
        no_target = jnp.isnan(desired_v)
        rel_weight = weight / jnp.maximum(req.s_sum_weights, 1e-9)
        boost_t = ((desired_v - used_count) / jnp.maximum(desired_v, 1e-9)) * rel_weight
        targeted = jnp.where(no_target, -1.0, boost_t)

        # ---- even mode (spread.go evenSpreadScoreBoost:178-230)
        valid = (value_hash != 0) & (counts > 0)
        any_use = jnp.any(valid)
        big = jnp.float32(1e30)
        mn = jnp.min(jnp.where(valid, counts, big))
        mx = jnp.max(jnp.where(valid, counts, -big))
        current = count_at
        delta_boost = jnp.where(mn == 0, -1.0, (mn - current) / jnp.maximum(mn, 1e-9))
        even_b = jnp.where(
            current != mn,
            delta_boost,
            jnp.where(
                mn == mx,
                -1.0,
                jnp.where(mn == 0, 1.0, (mx - mn) / jnp.maximum(mn, 1e-9)),
            ),
        )
        even_b = jnp.where(any_use, even_b, 0.0)
        even_b = jnp.where(node_has, even_b, -1.0)  # attr unset → max penalty

        score = jnp.where(even, even_b, targeted)
        return jnp.where(active, score, 0.0)

    per_stanza = jax.vmap(one_stanza)(
        req.s_slot[:s_width],
        nvalues,
        req.s_weight[:s_width],
        req.s_even[:s_width],
        req.s_value_hash[:s_width],
        req.s_desired[:s_width],
        req.s_implicit[:s_width],
        spread_counts[:s_width],
    )  # (s_width, N)
    total = jnp.sum(per_stanza, axis=0)
    has_spread = jnp.any(req.s_slot[:s_width] >= 0)
    appended = (total != 0.0) & has_spread
    return jnp.where(appended, total, 0.0), appended


@jax.named_scope("preemption")
def preemption_state(arrays, req: SchedRequest):
    """Vectorized preemption candidate math.

    The reference walks per-node alloc lists greedily
    (preemption.go:198-557). Here ``prio_used`` (N, P, 3) holds usage per
    priority bucket; everything strictly below ``preempt_bucket`` is
    evictable, so freeable = Σ lower buckets. netPriority is approximated
    from bucket midpoints.

    The bucket-axis reductions are expressed as *prefix* scans that depend
    only on ``arrays`` — batch-invariant, computed once per dispatch — and
    each eval then reads a single column at its ``preempt_bucket``. The
    previous form re-reduced the full (N, P, 3) tensor per eval, which at
    B=4096 re-read ~8 GB of HBM per dispatch.

    Returns (extra_free (N,3), preempt_score (N,), usable (N,) bool).
    """
    buckets = jnp.arange(PRIORITY_BUCKETS)
    # Shared prefix tables with the bucket axis LEADING and a zero row so
    # index k = "buckets < k". Leading-axis layout makes each eval's lookup
    # a contiguous (N, ...) row read instead of a strided column walk; the
    # tables depend only on ``arrays`` so XLA hoists them out of the vmap.
    csum = jnp.cumsum(jnp.moveaxis(arrays.prio_used, 1, 0), axis=0)  # (P, N, 3)
    csum = jnp.concatenate(
        [jnp.zeros_like(csum[:1]), csum], axis=0
    )  # (P+1, N, 3)
    mid = (buckets.astype(jnp.float32) + 0.5) * (101.0 / PRIORITY_BUCKETS)
    present = jnp.any(arrays.prio_used > 0, axis=2).T  # (P, N)
    mid_masked = jnp.where(present, mid[:, None], 0.0)
    mid_max = lax.cummax(mid_masked, axis=0)
    mid_max = jnp.concatenate(
        [jnp.zeros_like(mid_max[:1]), mid_max], axis=0
    )  # (P+1, N)
    mid_sum = jnp.cumsum(mid_masked, axis=0)
    mid_sum = jnp.concatenate(
        [jnp.zeros_like(mid_sum[:1]), mid_sum], axis=0
    )  # (P+1, N)

    # Per-eval: one row each (the only batch-dependent reads).
    k = jnp.clip(req.preempt_bucket, 0, PRIORITY_BUCKETS)
    freeable = csum[k]  # (N, 3)
    max_prio = mid_max[k]  # (N,)
    sum_prio = mid_sum[k]  # (N,)
    net = jnp.where(max_prio > 0, max_prio + sum_prio / jnp.maximum(max_prio, 1e-9), 0.0)
    score = 1.0 / (1.0 + jnp.exp(PREEMPTION_RATE * (net - PREEMPTION_ORIGIN)))

    usable = (req.preempt_bucket >= 0) & jnp.any(freeable > 0, axis=1)
    return freeable, score, usable


class ScoreResult(NamedTuple):
    final: jnp.ndarray  # (N,) f32, NEG_INF where infeasible
    feasible: jnp.ndarray  # (N,) bool (constraints, pre-resource)
    fits: jnp.ndarray  # (N,) bool (resources, incl. preemption assist)
    needs_preempt: jnp.ndarray  # (N,) bool: in the arg-max only by eviction
    # (N,) f32.  On a ``needs_preempt`` node: the part of the ranked mean
    # that depends on the victims, an ESTIMATE (below).
    binpack: jnp.ndarray
    exhausted_dim: jnp.ndarray  # (N,) i32
    # (N,) f32: how many terms ``final`` is the mean of on a
    # ``needs_preempt`` node, 0.0 elsewhere; None with preemption off.
    pre_terms: Optional[jnp.ndarray] = None
    # () f32: the best ``final`` among the nodes a distinct_property limit
    # alone excluded (NEG_INF where none); None at ``dp_width`` 0.
    dp_blocked_best: Optional[jnp.ndarray] = None


class LaneInvariants(NamedTuple):
    """What every placement step of a lane's scan shares: a function of
    the matrix snapshot and the lane's request alone, never of the scan's
    carry (``lane_invariants``)."""

    feasible: jnp.ndarray  # (N,) bool: ``feasibility_mask``, all of it
    affinity: tuple  # ``affinity_score``: (score (N,) f32, appended (N,) bool)
    spread_values: jnp.ndarray  # (s_width, N) i32: ``spread_columns``
    dp_values: jnp.ndarray  # (dp_width, N) i32: ``distinct_property_columns``
    # ``preemption_state``: (freeable (N, 3), score (N,), usable (N,));
    # None with preemption off.
    preemption: Optional[tuple]


def lane_invariants(arrays, req: SchedRequest, class_elig, host_mask,
                    features: Features = FULL_FEATURES) -> LaneInvariants:
    """The terms of ``score_nodes`` that no pick changes, computed once a
    launch, before the placement scan and inside the same program (PR 47):
    the static feasibility mask, the affinity term, the attribute columns
    the spread and distinct_property stages match a step's carry against,
    and the preemption tables' rows.  Each under the scope its work always
    had, so a profile attributes it as before."""
    with jax.named_scope("feasibility"), jax.named_scope("distinct_property"):
        dp_values = distinct_property_columns(arrays, req, features.dp_width)
    return LaneInvariants(
        feasible=feasibility_mask(arrays, req, class_elig, host_mask, features),
        affinity=affinity_score(arrays, req, features.a_width),
        spread_values=spread_columns(arrays, req, features.s_width),
        dp_values=dp_values,
        preemption=preemption_state(arrays, req) if features.preempt else None,
    )


def launch_invariants(topo: Topology, arrays, reqs, class_eligs, host_masks,
                      features: Features, n_lanes):
    """``lane_invariants`` of a launch's first ``n_lanes`` lanes (traced:
    the last live lane + 1 among the shard's own), stacked on a leading lane
    axis; the lanes past them are dead, nothing reads theirs, and they keep
    zeros.

    One loop over those lanes, not a ``vmap`` over all of them: in a lane's
    turn every attribute column it reads is a contiguous row slice that
    fuses into the arithmetic that reads it, where the vmapped read is a
    gather, which the TPU compiler runs as a serial loop of its own with a
    strided write a turn, for every lane the launch is padded to (64, of
    which a closed loop fills 6-8: PERF.md section 5, PR 47)."""
    lanes = class_eligs.shape[0]
    # The class table's gather is the one read that is better vmapped: the
    # class ids are every lane's, so one gather fetches all lanes' verdicts
    # of a node, where a lane alone gathers 51,200 scalars (0.38 ms a lane
    # on a v5e: PERF.md section 6, PR 47).
    with jax.named_scope("feasibility"):
        masks = host_masks & jax.vmap(
            lambda ce: class_mask(arrays, ce)
        )(class_eligs)

    def one(b):
        req, mask = jax.tree_util.tree_map(
            lambda x: lax.dynamic_index_in_dim(x, b, 0, keepdims=False),
            (reqs, masks),
        )
        return lane_invariants(arrays, req, None, mask, features)

    shapes, tree = jax.tree_util.tree_flatten(jax.eval_shape(one, 0))
    with jax.named_scope("lane_invariants"):
        _, outs = scan_steps(
            lambda carry, b: (carry, jax.tree_util.tree_leaves(one(b))),
            (),
            [topo.vary(jnp.zeros((lanes,) + s.shape, s.dtype), nodes=True)
             for s in shapes],
            n_lanes,
        )
    return jax.tree_util.tree_unflatten(tree, outs)


def score_nodes(
    arrays,
    used,
    tg_count,
    spread_counts,
    penalty_mask,
    req: SchedRequest,
    class_elig,
    host_mask,
    features: Features = FULL_FEATURES,
    dp_cnt=None,
) -> ScoreResult:
    """The full ranking pipeline in one call (GenericStack.Select,
    stack.go:117-179, minus the sampling the TPU design makes unnecessary):
    ``rank_nodes`` on ``lane_invariants``, on one device.  A scan computes
    the invariants once and ranks every step against them."""
    inv = lane_invariants(arrays, req, class_elig, host_mask, features)
    return rank_nodes(
        ONE_DEVICE, arrays, inv, used, tg_count, spread_counts, penalty_mask,
        req, features, dp_cnt,
    )


def rank_nodes(
    topo: Topology,
    arrays,
    inv: LaneInvariants,
    used,
    tg_count,
    spread_counts,
    penalty_mask,
    req: SchedRequest,
    features: Features = FULL_FEATURES,
    dp_cnt=None,
) -> ScoreResult:
    """One step's ranking from the lane's invariants (``inv``) and what the
    scan carries: proposed usage, the job's allocs per node, the spread
    stage's value table (``req.s_value_hash``) and counts, the
    distinct_property stage's counts.

    ``features`` (static) bounds every sub-pass to the dispatch's batch
    occupancy — padded constraint/affinity/spread slots, unused preemption
    tables and port bitmaps cost nothing when no eval in the batch uses
    them.

    **Preemption** (``features.preempt``), as Nomad makes it
    (generic_sched.go:773-792: select without preemption, and only when no
    option was found select again with it).  Two tiers in ONE arg-max: a
    node that needs an eviction is in this step's arg-max only if NO
    feasible node fits without one (one more reduction a step, and
    ``topo.any`` says "no node" of the whole cluster).  Among preempting
    nodes the rank is Nomad's mean with two of its terms ESTIMATED, because
    the victims are chosen on the host, after the launch, for the one node
    picked (scheduler/preemption.py):

    * binpack: ScoreFit of the utilisation after the LEAST eviction the
      bucket tables can express, ``used + ask - min(freeable, deficit)``
      (rank.go scores ``proposed`` less the allocations to preempt; whole
      allocations free more than the deficit, so the exact score is lower
      or equal).  Not the clipped 1.0 an over-full node would read.
    * preemption: the logistic of the net priority of the evictable
      buckets' midpoints (``preemption_state``), not of the victims.

    The host records the exact score (stack.py: binpack after the chosen
    victims, logistic of their net priority).  For that the packed output
    of a preempting pick carries, beside the ranked mean in SCORE, the sum
    of the two estimated terms in BINPACK and the number of terms of the
    mean in PREEMPT (0.0 = no eviction): the host swaps the two terms."""
    # distinct_hosts: one proposed alloc of this job+TG per node, enforced
    # in-scan via tg_count so multi-placement batches can't stack a node.
    feas = inv.feasible & ~(req.distinct_hosts & (tg_count > 0))
    # distinct_property: at most ``limit`` allocs of the job a value, by the
    # per-node counts the scan carries (``dp_cnt`` (W, N); None = the seeds).
    dp_ok = None
    if features.dp_width:
        with jax.named_scope("feasibility"), jax.named_scope(
            "distinct_property"
        ):
            if dp_cnt is None:
                dp_cnt = distinct_property_counts(
                    inv.dp_values, req, features.dp_width
                )
            dp_ok = distinct_property_mask(
                inv.dp_values, req, dp_cnt, features.dp_width
            )
            feas_open, feas = feas, feas & dp_ok
    fits, binpack, exhausted = fit_and_binpack(arrays, used, req)

    if features.preempt:
        freeable, pre_score, pre_usable = inv.preemption
        with jax.named_scope("preemption"):
            util = used + req.ask[None, :]
            deficit = jnp.maximum(util - arrays.totals, 0.0)
            can_preempt = (
                ~fits & jnp.all(deficit <= freeable, axis=1) & pre_usable
            )
            needs_preempt = can_preempt & ~topo.any(jnp.any(feas & fits))
            fits_all = fits | needs_preempt
            evicted = jnp.minimum(freeable, deficit)
            binpack = jnp.where(
                needs_preempt, score_fit(arrays, util - evicted, req), binpack
            )
            pre_component = jnp.where(needs_preempt, pre_score, 0.0)
    else:
        needs_preempt = jnp.zeros_like(fits)
        fits_all = fits
        pre_component = jnp.zeros(fits.shape, jnp.float32)

    aa_score, aa_app = anti_affinity_score(tg_count, req)
    pen_score, pen_app = penalty_score(penalty_mask)
    aff_score, aff_app = inv.affinity
    spr_score, spr_app = spread_score(inv.spread_values, req, spread_counts,
                                      features.s_width)

    total = binpack + aa_score + pen_score + aff_score + spr_score + pre_component
    count = (
        1.0
        + aa_app.astype(jnp.float32)
        + pen_app.astype(jnp.float32)
        + aff_app.astype(jnp.float32)
        + spr_app.astype(jnp.float32)
        + needs_preempt.astype(jnp.float32)
    )
    final = total / count
    dp_blocked_best = None
    if dp_ok is not None:
        with jax.named_scope("feasibility"), jax.named_scope(
            "distinct_property"
        ):
            dp_blocked_best = jnp.max(
                jnp.where(feas_open & ~dp_ok & fits_all, final, NEG_INF)
            )
    final = jnp.where(feas & fits_all, final, NEG_INF)
    pre_terms = None
    if features.preempt:
        pre_terms = jnp.where(needs_preempt, count, 0.0)
        binpack = binpack + pre_component
    return ScoreResult(
        final=final,
        feasible=feas,
        fits=fits_all,
        needs_preempt=needs_preempt,
        binpack=binpack,
        exhausted_dim=exhausted,
        pre_terms=pre_terms,
        dp_blocked_best=dp_blocked_best,
    )


# ---------------------------------------------------------------------------
# Placement scan
# ---------------------------------------------------------------------------


class PlacementResult(NamedTuple):
    rows: jnp.ndarray  # (P,) i32 chosen node row, -1 = failed
    scores: jnp.ndarray  # (P,) f32 final score of chosen node
    binpack: jnp.ndarray  # (P,) f32 binpack component
    preempted: jnp.ndarray  # (P,) bool placement requires preemption
    nodes_evaluated: jnp.ndarray  # (P,) i32
    nodes_filtered: jnp.ndarray  # (P,) i32 failed constraints
    nodes_exhausted: jnp.ndarray  # (P,) i32 feasible but resource-exhausted
    used_after: jnp.ndarray  # (N, 3) proposed usage after placements
    tg_count_after: jnp.ndarray  # (N,)


def spread_values_at(arrays, req: SchedRequest, row):
    """Per-stanza attribute hash of node ``row`` ((S,) i32), a local row:
    the shard that holds the picked node reads it (``_commit_step``)."""
    return arrays.attr_hash[row, jnp.maximum(req.s_slot, 0)]


def apply_spread_values(spread_counts, req: SchedRequest, nvalues):
    """Bump per-stanza counts for the placed node's attribute values
    (propertyset.go usage tracking). Claims an empty value slot on first
    sight of a new value.  ``nvalues``: (S,) i32 from spread_values_at."""

    def one(slot, value_hash, counts, nvalue):
        match = (value_hash == nvalue) & (nvalue != 0)
        have = jnp.any(match)
        free_slot = jnp.argmax(value_hash == 0)
        idx = jnp.where(have, jnp.argmax(match), free_slot)
        can = (slot >= 0) & (nvalue != 0) & (have | (value_hash[free_slot] == 0))
        new_hash = jnp.where(
            can & ~have, value_hash.at[idx].set(nvalue), value_hash
        )
        new_counts = jnp.where(can, counts.at[idx].add(1.0), counts)
        return new_hash, new_counts

    return jax.vmap(one)(
        req.s_slot, req.s_value_hash, spread_counts, nvalues
    )


def scan_steps(step, init, outs, trip):
    """``lax.scan(step, init, jnp.arange(P))`` cut off after ``trip`` steps
    (traced i32 scalar): step ``i``'s outputs land at index ``i`` of the
    ``outs`` buffers, the rows never reached keep what ``outs`` held."""

    def body(i, state):
        carry, bufs = state
        carry, out = step(carry, i)
        # As lax.scan stacks its outputs: a plain dynamic-update-slice
        # (``.at[i].set`` adds index wrapping and a bounds check to every
        # iteration: 0.7 us of the verify loop's 4 us a slot on a v5e).
        return carry, tuple(
            lax.dynamic_update_index_in_dim(b, o, i, 0)
            for b, o in zip(bufs, out)
        )

    return lax.fori_loop(0, trip, body, (init, tuple(outs)))


def _score_step(topo: Topology, arrays, inv: LaneInvariants,
                req: SchedRequest, carry, penalty_mask, features: Features,
                row_offset):
    """The first half of a placement step, for one lane on one shard: its
    scores from the lane's invariants and the scan's carry, and its own
    pick.  Returns (the request as this step reads it, its ``ScoreResult``,
    the three node counts, the elected global row: -1 where no node of the
    cluster is feasible and fits)."""
    used, tg_cnt, s_hash, s_counts, dp_cnt = carry
    req_step = req._replace(s_value_hash=s_hash)
    with jax.named_scope("score"):
        res = rank_nodes(
            topo, arrays, inv, used, tg_cnt, s_counts, penalty_mask,
            req_step, features, dp_cnt,
        )
    with jax.named_scope("pick"):
        best, own = elect(topo, res.final, row_offset)
        own = jnp.where(best > NEG_INF / 2, own, -1)
        counts = (
            jnp.sum(res.feasible.astype(jnp.int32)),
            jnp.sum((~res.feasible & arrays.eligible).astype(jnp.int32)),
            jnp.sum((res.feasible & ~res.fits).astype(jnp.int32)),
        )
        with topo.exchange("count"):
            counts = tuple(topo.sum(c) for c in counts)
    return req_step, res, counts, own


def scan_carry(inv: LaneInvariants, req: SchedRequest, used0, tg_count,
               spread_counts, features: Features):
    """A request's carry at the scan's first step: proposed usage, the
    job's allocs per node, the spread stage's value table and counts, the
    distinct_property stage's counts per node ((dp_width, N))."""
    if not features.dp_width:
        # No lane carries the stage: nothing of it is read (the launch may
        # not even hand its operands over: ``DP_FIELDS``).
        dp_cnt = jnp.zeros((0,) + tg_count.shape, jnp.float32)
        return used0, tg_count, req.s_value_hash, spread_counts, dp_cnt
    with jax.named_scope("feasibility"), jax.named_scope("distinct_property"):
        dp_cnt = distinct_property_counts(
            inv.dp_values, req, features.dp_width
        )
    return used0, tg_count, req.s_value_hash, spread_counts, dp_cnt


def _commit_step(topo: Topology, arrays, inv: LaneInvariants, carry,
                 req_step: SchedRequest, res: ScoreResult, counts, row,
                 features: Features, row_offset):
    """The second half: charge a step's pick (``row``, global; -1 =
    nothing) to the scan's carry on the shard that holds the row, and read
    what the output and the rule stages need of that node from its owner.
    Returns (carry, the step's seven output columns, and at ``dp_width`` >
    0 an eighth: a node a distinct_property limit alone excluded scored
    higher than the node taken)."""
    used, tg_cnt, s_hash, s_counts, dp_cnt = carry
    ok = row >= 0
    owner, lrow = local_rows(row, row_offset, used.shape[0])
    with jax.named_scope("update"):
        used2 = jnp.where(owner, used.at[lrow].add(req_step.ask), used)
        tg2 = jnp.where(owner, tg_cnt.at[lrow].add(1), tg_cnt)

        # The picked node's spread and property values, from its owner.
        nvals = spread_values_at(arrays, req_step, lrow)
        if features.dp_width:
            with topo.exchange("rules_exchange"):
                nvals = jnp.concatenate([
                    nvals,
                    distinct_property_values_at(arrays, req_step, lrow),
                ])
        nvals = jnp.where(owner, nvals, 0)
        with topo.exchange("broadcast"):
            nvals = topo.sum(nvals)
        n_spreads = req_step.s_slot.shape[0]
        new_hash, new_counts = apply_spread_values(
            s_counts, req_step, nvals[:n_spreads]
        )
        s_hash2 = jnp.where(ok, new_hash, s_hash)
        s_counts2 = jnp.where(ok, new_counts, s_counts)
        if features.dp_width:
            dp_cnt = jnp.where(ok, distinct_property_pick(
                inv.dp_values, req_step, dp_cnt, nvals[n_spreads:],
                features.dp_width,
            ), dp_cnt)

        own_score = jnp.where(
            owner, jnp.stack([res.final[lrow], res.binpack[lrow]]), 0.0
        )
        own_pre = owner & res.needs_preempt[lrow]
        if res.pre_terms is not None:  # the count of the mean's terms, no flag
            own_pre = jnp.where(own_pre, res.pre_terms[lrow], 0.0)
        with topo.exchange("broadcast"):
            final, binpack = topo.sum(own_score)
            preempted = (
                topo.any(own_pre) if res.pre_terms is None
                else topo.max(own_pre)
            )
    out = (row, final, binpack, preempted) + counts
    if features.dp_width:
        with topo.exchange("broadcast"), topo.exchange("rules_exchange"):
            blocked = topo.max(res.dp_blocked_best)
        out += (ok & (blocked > final),)
    return (used2, tg2, s_hash2, s_counts2, dp_cnt), out


def _place_scan(
    arrays,
    req: SchedRequest,
    used0,
    tg_count,
    spread_counts,
    penalty_mask,
    class_elig,
    host_mask,
    n_placements: int,
    features: Features = FULL_FEATURES,
) -> PlacementResult:
    """Traceable core of the solo placement scan (``place_task_group``): a
    static ``lax.scan`` of ``n_placements`` steps, each the arg-max of the
    request's own scores, on one device.  The batched program runs the same
    two halves of a step (``_score_step``, ``_commit_step``) with the
    lanes' picks resolved between them (``_fused_place_batch_impl``)."""

    def step(carry, _):
        req_step, res, counts, row = _score_step(
            ONE_DEVICE, arrays, inv, req, carry, penalty_mask, features, 0
        )
        return _commit_step(
            ONE_DEVICE, arrays, inv, carry, req_step, res, counts, row,
            features, 0,
        )

    inv = lane_invariants(arrays, req, class_elig, host_mask, features)
    init = scan_carry(inv, req, used0, tg_count, spread_counts, features)
    with jax.named_scope("place_scan"):
        (used_after, tg_after, *_), outs = lax.scan(
            step, init, None, length=n_placements
        )
    rows, scores, binpack, preempted, n_eval, n_filt, n_exh = outs[:7]
    return PlacementResult(
        rows=rows,
        scores=scores,
        binpack=binpack,
        preempted=preempted,
        nodes_evaluated=n_eval,
        nodes_filtered=n_filt,
        nodes_exhausted=n_exh,
        used_after=used_after,
        tg_count_after=tg_after,
    )


@functools.partial(jax.jit, static_argnames=("n_placements", "features"))
def place_task_group(
    arrays,
    req: SchedRequest,
    used0,
    tg_count,
    spread_counts,
    penalty_mask,
    class_elig,
    host_mask,
    n_placements: int,
    features: Features = FULL_FEATURES,
) -> PlacementResult:
    """Place ``n_placements`` allocs of one TG — the kernel behind
    computePlacements (generic_sched.go:472).

    A lax.scan over placements: each step scores all nodes, takes the argmax
    (replacing Limit/MaxScore sampling, stack.go:78-91), and scatters the
    proposed usage so subsequent placements see it (ProposedAllocs semantics,
    rank.go:41-52).

    ``used0`` (N, 3) is the proposed base usage — the authoritative matrix
    usage already adjusted by the reconciler's planned stops/evictions
    (the reference's ProposedAllocs = existing − plan.NodeUpdate + in-plan,
    scheduler/context.go ProposedAllocs).
    """
    return _place_scan(
        arrays, req, used0, tg_count, spread_counts, penalty_mask,
        class_elig, host_mask, n_placements, features,
    )


# Per-placement columns of a batched launch's packed output (one fetch per
# dispatch; each separate device→host fetch is its own round-trip).
PACKED_ROW = 0
PACKED_SCORE = 1
PACKED_BINPACK = 2
# 0.0 = no eviction; on a preempting pick the number of terms of the mean
# in PACKED_SCORE, and PACKED_BINPACK the sum of its two estimated terms
# (``score_nodes``): what the host needs to record the exact score.
PACKED_PREEMPT = 3
PACKED_EVALUATED = 4
# A whole number of nodes; + 0.5 where a node a distinct_property limit
# alone excluded scored higher than the node taken (``pack_fused_lanes``):
# ``astype(int)`` reads the count, ``% 1`` the flag.
PACKED_FILTERED = 5
PACKED_EXHAUSTED = 6
PACKED_WIDTH = 7


# ---------------------------------------------------------------------------
# Fused megakernel (mega-batched eval pipeline + device-resident re-verify)
# ---------------------------------------------------------------------------

# Columns of the fused kernel's packed output: the PACKED_WIDTH
# per-placement columns above, then the VERIFIED column: the in-launch
# pick resolution's outcome and the device-resident AllocsFit re-verify
# verdict per placement:
#   1.0  the lane's own arg-max, and it survives the sequential cross-lane
#        re-check (also what an empty or never-asked-for slot reads)
#   2.0  resolved: an earlier lane of this launch had claimed the room the
#        lane's own arg-max needed, the lane took its best node that still
#        fits under the launch's claims, and that survives the re-check
#   0.0  placement would be rejected (no node fits under the claims, so
#        the lane kept its unresolved pick, or a preempting pick: the
#        applier decides)
#  -1.0  not computed (dead/padded lane)
FUSED_PACKED_VERIFIED = 7
FUSED_PACKED_WIDTH = 8


def fused_trip_counts(lane_steps, n_placements: int):
    """The two loop bounds of a fused launch, from its per-lane step
    counts: (largest count, capped at the output's length; index of the
    last live lane + 1).  Scalars, worked out once outside any ``vmap``."""
    if not jnp.issubdtype(lane_steps.dtype, jnp.integer):
        # A bool lane mask would read as "one step a lane".
        raise TypeError(
            f"lane_steps must be an integer array, got {lane_steps.dtype}"
        )
    trip = jnp.minimum(jnp.max(lane_steps), n_placements).astype(jnp.int32)
    lanes = jnp.arange(1, lane_steps.shape[0] + 1, dtype=jnp.int32)
    last_lane = jnp.max(jnp.where(lane_steps > 0, lanes, 0))
    return trip, last_lane


def chain_flags(flags, depth: int):
    """The chain's flags as they ride the packed lane buffer ((B, 1 + w)
    bool): column 0 says whether the lane holds claims (an eval stands
    behind it: ``_Pending.eval_id``), the rest, read as one flat list, whether
    carried block d is live (its launch's result is not on the host).
    Returns (holds (B,), live (depth,))."""
    return flags[:, 0], flags[:, 1:].reshape(-1)[:depth]


def carried_claims(carry, live):
    """The carried blocks ((D, B, R, 4) f32: row, cpu, mem, disk; row -1 =
    padding) as (rows (D, B, R) i32, vals (D, B, R, 3)) with everything but
    the ``live`` (D,) blocks' rows zeroed."""
    rows = carry[..., 0].astype(jnp.int32)
    valid = (rows >= 0) & live[:, None, None]
    return rows, jnp.where(valid[..., None], carry[..., 1:], 0.0)


@jax.named_scope("chain")
def claims_block(delta_rows, claim_vals, rows, preempted, ask, holds):
    """The claims block a launch writes for the launches after it: for
    every lane that ``holds`` claims (live, with an eval behind it) what
    the resolver will enter into the ledger when the result reaches the
    host (``coalescer._lane_claims``) — the rows its plan held before the
    launch with what the plan advertises on them (``claim_vals``: no
    eviction credited, nothing below zero), then its picks up to and
    including the first preempting one (the host drops the rows after it
    and re-enters), each with the lane's ask.  (B, K + P, 4) f32: row,
    cpu, mem, disk; row -1 = padding (a row id is exact in float32 below
    2**24, as in the packed output's ROW column).  Independent of the node
    count, and never fetched: the next launch reads it on the device."""
    held = (delta_rows >= 0) & holds[:, None]  # (B, K)
    pre = (preempted != 0).astype(jnp.int32)
    first = jnp.cumsum(pre, axis=1) - pre == 0  # no preempting pick before
    picked = (rows >= 0) & holds[:, None] & first  # (B, P)
    b_rows = jnp.concatenate(
        [jnp.where(held, delta_rows, -1), jnp.where(picked, rows, -1)], axis=1
    )
    b_vals = jnp.concatenate(
        [
            jnp.where(held[..., None], claim_vals, 0.0),
            jnp.where(picked[..., None], ask[:, None, :], 0.0),
        ],
        axis=1,
    )
    return jnp.concatenate(
        [b_rows.astype(jnp.float32)[..., None], b_vals], axis=2
    )


def chained_carry(own, carry):
    """What a launch hands the next one: its own block first, the blocks
    it was handed shifted by one (the oldest falls off): one buffer in, one
    buffer out, however deep the chain."""
    return jnp.concatenate([own[None], carry[:-1]], axis=0)


@jax.named_scope("pack")
def pack_fused_lanes(
    rows, scores, binpack, preempted, n_eval, n_filt, n_exh, verified,
    repicked, live, dp_moved=None
):
    """Stack per-lane placement outputs into the fused (B, P, 8) layout with
    dead-lane masking: row/-1, VERIFIED/-1.0, zeros elsewhere.  VERIFIED
    reads 2.0 where the placement fits and the lane re-picked.

    ``dp_moved`` (``Features.dp_width`` > 0): the FILTERED column, a whole
    number of nodes, carries + 0.5 where a distinct_property limit moved the
    pick (``PACKED_FILTERED``); all false it is bit for bit the column
    without it.
    """
    lv = live[:, None]
    filtered = jnp.where(lv, n_filt, 0).astype(jnp.float32)
    if dp_moved is not None:
        filtered = filtered + jnp.where(lv & dp_moved, 0.5, 0.0)
    fits = verified.astype(bool)
    vcol = jnp.where(fits, jnp.where(repicked, 2.0, 1.0), 0.0)
    vcol = jnp.where(lv, vcol, -1.0)
    return jnp.stack(
        [
            rows.astype(jnp.float32),
            jnp.where(lv, scores, 0.0),
            jnp.where(lv, binpack, 0.0),
            jnp.where(lv, preempted, False).astype(jnp.float32),
            jnp.where(lv, n_eval, 0).astype(jnp.float32),
            filtered,
            jnp.where(lv, n_exh, 0).astype(jnp.float32),
            vcol,
        ],
        axis=2,
    )  # (B, P, FUSED_PACKED_WIDTH)


def inert_lane_outputs(lanes: int, all_lanes: int, n_placements: int,
                       preempt: bool, dp: bool) -> tuple:
    """The stacked outputs of a launch in which no step ran, step-major
    ((P, B): a step's outputs of all lanes land at one index), in the
    order a step of the batched program writes them: row -1 (of every lane
    of the launch, ``all_lanes``: each shard's verify replays them all; the
    other buffers are of this shard's ``lanes``), zero scores, flags and
    node counts (what a failed-or-never-asked placement reads, and what the
    numpy twin fills its tail rows with), the re-pick flag.  With
    ``preempt`` the PREEMPT buffer holds a count, not a flag
    (``score_nodes``); with ``dp`` (``Features.dp_width`` > 0) a last buffer
    holds the distinct_property stage's flag."""
    shape = (n_placements, lanes)
    moved = (jnp.zeros(shape, bool),) if dp else ()
    return (
        jnp.full((n_placements, all_lanes), -1, jnp.int32),
        jnp.zeros(shape, jnp.float32),
        jnp.zeros(shape, jnp.float32),
        jnp.zeros(shape, jnp.float32 if preempt else bool),
        jnp.zeros(shape, jnp.int32),
        jnp.zeros(shape, jnp.int32),
        jnp.zeros(shape, jnp.int32),
        jnp.zeros(shape, bool),
    ) + moved


def _fused_place_batch_impl(
    topo: Topology,
    arrays,
    used,
    delta_rows,
    delta_vals,
    tg_counts,
    spread_counts,
    penalties,
    reqs,
    class_eligs,
    host_masks,
    lane_steps,
    overlay=None,
    chain=None,
    *,
    n_placements: int,
    features: Features = FULL_FEATURES,
):
    """The mega-batched ranking megakernel: B eval pipelines — feasibility →
    binpack → spread/affinity → preemption evict-state → placement scan —
    with the lanes' picks resolved in lane order inside every placement
    step, PLUS the ``AllocsFit`` plan re-verify, in ONE launch.  This is the
    program of one shard, written once: ``topo`` (``Topology``) says what
    the shard holds and how it hears of the others.  On one device it holds
    everything and hears nothing (``fused_place_batch``,
    ``fused_place_batch_live``); under ``shard_map`` the same lines are the
    mesh's program (``parallel/sharding.py``; "Across shards", below).

    Per-request args lead with a B axis.  ``delta_rows``/``delta_vals``
    ((B, K) i32 / (B, K, 3) f32, row -1 = padding) carry each request's
    sparse in-flight plan usage deltas, applied to the shared ``used``
    inside the kernel so the host never materializes a dense per-request
    usage matrix.

    * ``lane_steps`` (B,) i32 says how many placements each eval slot
      asked for, 0 for a dead slot (batch occupancy < B).  The placement
      scan and the verify pass run as many iterations as the launch's
      live lanes asked for (the largest count; the last live lane), read
      from this operand: ``n_placements`` is only the static length of
      the output, so one compile serves every occupancy and every mix of
      counts.  A live lane's rows past its own count are inert (row -1,
      zeros, VERIFIED 1.0) and charge nothing.  Dead lanes produce row=-1 /
      zero outputs and contribute nothing to the resolution or the verify
      pass — no host-side request-faking, no shape-polymorphic recompiles.
    * **In-launch pick resolution.**  Every lane scores the nodes against
      its own proposed usage, exactly as alone.  Then, within a step, the
      live lanes take their picks in lane order against one image of the
      launch's claims (``claims0``: the shared usage with the
      in-flight overlay under it, every live lane's in-flight deltas,
      every pick so far): a lane takes the arg-max of its own scores over
      the nodes where that image still has room for its ask, and adds its
      ask to the image.  So a lane passes over a node only because lanes
      of this launch (or plans still in flight: the overlay, below) took
      the room it needed, and then takes its best node that is left —
      exact arg-max, float32 scores, nothing sampled.  If no feasible node has room under
      the claims, the lane keeps its own arg-max (never an empty slot that
      its own scores would fill: an empty slot reads "no node can take it"
      on the host and blocks the eval).  A node a lane may only take by
      preempting is not masked for want of room (eviction frees it at
      apply time) but by a claim that already over-fills it: an earlier
      lane of this launch took it by preempting, the host would choose
      the same victims for both, and the applier would reject the second
      (on a full cluster every lane's arg-max is the same node).  A
      launch whose lanes' picks never overflow a node — one live
      lane in particular — is bit for bit every lane's solo scan: the
      behaviour follows from the picks and the claims, there is no switch.
    * The packed output's VERIFIED column is a device-resident
      sequential AllocsFit re-check of every lane's chosen placements
      against the authoritative matrix usage *plus all earlier lanes'
      deltas and placements*, in lane (= resolve) order, the re-picked
      ones included: 1.0 fits with the lane's own arg-max, 2.0 fits on
      the node the lane re-picked, 0.0 would be rejected.  After the
      resolution only an unresolved pick (no node left under the claims;
      a pick that preempts; a later lane's pick on a node such a pick
      overflowed) reads 0.0 — exactly the conflicts the plan applier's
      optimistic-concurrency re-verify (plan_apply.py:_evaluate) rejects
      one plan-apply round-trip later.  The applier against live state
      stays authoritative and serialized; any lane's *stop* is credited to
      the claims before its plan commits (the verify column, which adds
      deltas in lane order, credits it to the later lanes only and reads
      0.0 otherwise).
    * **The in-flight claims overlay** (PR 38; ``overlay`` = (rows (B, K)
      i32, -1 padded, vals (B, K, 3) f32), read as one flat list; None =
      empty).  Launches in flight used not to see one another: the picks
      of the launch before, whose plans are still being built, queued or
      applied, were in neither the matrix nor this launch's deltas, and
      two launches named the same nodes.  The coalescer's ``ClaimsLedger``
      hands each launch those undecided picks, and they are added to the
      usage under the claims image and under the verify pass
      (``claimed``) and to NOTHING else: every lane still scores the
      nodes against its own proposed usage, so the overlay only decides
      which nodes have ``room`` (and are ``unclaimed``, for a node taken
      by preempting).  A lane passes over a node because lanes of this
      launch or plans still in flight took the room it needed, takes its
      best node that is left, and keeps its own arg-max where none is
      (VERIFIED 0.0, never row -1).  Advisory, like the VERIFIED column,
      and a departure from the reference, whose workers never see each
      other's plans.  With an empty overlay the output is bit for bit
      what it is without one.
    * **Claims chained on the device** (PR 41; ``chain`` = (carry (D, B,
      K + P, 4) f32, flags (B, 1 + w) bool, claim_vals (B, K, 3) f32); None
      = the program without it).  The ledger knows a launch's picks only
      once its result is on the host; a launch that leaves before that
      found them nowhere.  So every launch writes its own claims block
      (``claims_block``: lane for lane what the resolver will enter) as a
      second, device-resident output, and takes the blocks of the D
      launches before it (``carry``) with one flag a block (``chain_flags``):
      live = that launch's result is not on the host yet, decided by the
      host in one step with its read of the ledger, so a pick is in the
      overlay or in a live block and never in both.  Live blocks enter
      exactly where the overlay enters (``claimed``) and nowhere
      else.  The second output is ``chained_carry``: this launch's block,
      then the carried ones shifted by one.  With no live block the packed
      output is bit for bit what it is without the operand.
    * **Across shards.**  A shard holds ``n_local`` rows of the node axis
      (every (N, ...) operand is its slice; ``delta_rows``, the overlay,
      the carry and the output name rows globally, and ``local_rows`` says
      which a shard holds) and ``b_local`` of the launch's lanes.  It
      scores its own lanes on its own rows with no communication; what
      crosses a shard is a scalar or a lane-sized vector, never a score
      vector, each under a scope of its own:

      - ``gather``: asks, in-flight deltas, step counts, the overlay and
        the carried blocks of ALL lanes, once a launch (the walk and the
        verify visit every lane on every shard, each against its slice of
        the claims; so the trip counts are taken over the whole batch, one
        number on every shard), and every lane's unresolved pick, once a
        step;
      - ``elect``: a pick (``elect``): the best score, then the lowest row
        that holds it.  A lane's own pick is elected over the node shards;
        a turn of the walk over the batch shards too, which is how every
        shard learns every lane's winner for the verify;
      - ``count``: the three node counts, summed;
      - ``broadcast``: what the output and the spread stage need of the
        picked node (its two scores, its eviction count, its attribute
        values), from the one shard that holds it;
      - ``rules_exchange`` (``Features.dp_width`` > 0): the picked node's
        property values riding the spread stage's broadcast, and "a node
        the limit alone excluded scored higher", one more maximum a step.

      Beside those: "no feasible node fits without an eviction" is said of
      the cluster (``rank_nodes``), and each row's owner alone decides its
      verify verdict.  Every shard writes its own lanes' part of the claims
      block: the carry costs no collective.  The five scopes are the mesh's
      (``topo.exchange``): the one-device program has none of them.

    Returns (B, n_placements, FUSED_PACKED_WIDTH) f32 — one fetch; with a
    ``chain``, that and the carry for the next launch (never fetched).
    """
    n_local = used.shape[0]
    b_local = lane_steps.shape[0]
    row_offset, b_first = topo.shard(n_local, b_local)
    live = lane_steps > 0  # (b_local,)
    # What every shard needs of every lane, gathered once (the winners
    # themselves reach every shard through the resolution's elections).
    with jax.named_scope("verify_scan"), topo.exchange("gather"):
        g_steps = topo.all_lanes(lane_steps)  # (B,)
        g_ask = topo.all_lanes(reqs.ask)  # (B, 3)
        g_drows = topo.all_lanes(delta_rows)  # (B, K)
        g_dvals = topo.all_lanes(delta_vals)
        if overlay is not None:
            g_orows, g_ovals = (topo.all_lanes(o) for o in overlay)
        if chain is not None:
            carry, flags, claim_vals = chain
            g_carry = topo.all_lanes(carry, axis=1)
            g_flags = topo.all_lanes(flags)
    g_live = g_steps > 0  # (B,)
    lanes = g_steps.shape[0]
    trip, last_lane = fused_trip_counts(g_steps, n_placements)

    def step(state, i):
        carry, claims = state
        req_step, res, counts, own = jax.vmap(
            lambda inv, carry, pen, req: _score_step(
                topo, arrays, inv, req, carry, pen, features, row_offset
            )
        )(invs, carry, penalties, reqs)
        # A lane that asked for fewer steps than the launch runs takes no
        # placement here: no usage charged, inert row.
        active = i < lane_steps  # (b_local,)
        own = jnp.where(active, own, -1)
        counts = tuple(jnp.where(active, c, 0) for c in counts)
        with jax.named_scope("pick"), jax.named_scope("resolve"):
            # Every lane's unresolved pick on every shard: the walk's
            # fallback, and what says whether the lane places at all.
            with topo.exchange("gather"):
                g_own = topo.all_lanes(own)  # (B,)

            def take(b, picked):
                claims, rows = picked
                # Whether this lane is mine, and which of mine it is.
                holds, bl = local_rows(b, b_first, b_local)
                ask = g_ask[b]
                room = jnp.all(claims + ask[None, :] <= arrays.totals, axis=1)
                # A node taken only by preempting is masked by a claim that
                # already over-fills it, not for want of room (docstring).
                unclaimed = jnp.all(claims <= arrays.totals, axis=1)
                best, row = elect(topo, jnp.where(
                    (room | (res.needs_preempt[bl] & unclaimed)) & holds,
                    res.final[bl], NEG_INF,
                ), row_offset, lanes=True)
                # Its unresolved pick where no feasible row has room (never
                # an empty slot: the re-verify then reads 0.0 and the
                # applier decides).
                row = jnp.where(best > NEG_INF / 2, row, g_own[b])
                row = jnp.where(g_own[b] >= 0, row, -1)
                mine, safe = local_rows(row, row_offset, n_local)
                return (
                    claims.at[safe].add(jnp.where(mine, ask, 0.0)),
                    lax.dynamic_update_index_in_dim(rows, row, b, 0),
                )

            claims, g_rows = lax.fori_loop(
                0, last_lane, take,
                (claims, topo.vary(jnp.full((lanes,), -1, jnp.int32))),
            )
        rows = lax.dynamic_slice_in_dim(g_rows, b_first, b_local)
        carry, out = jax.vmap(
            lambda inv, carry, req_step, res, counts, row: _commit_step(
                topo, arrays, inv, carry, req_step, res, counts, row,
                features, row_offset,
            )
        )(invs, carry, req_step, res, counts, rows)
        return (carry, claims), (
            (g_rows,) + out[1:7] + ((rows >= 0) & (rows != own),) + out[7:]
        )

    # What the steps of a lane all share, once a launch (PR 47), for this
    # shard's rows and the live lanes among its own.
    invs = launch_invariants(
        topo, arrays, reqs, class_eligs, host_masks, features,
        jnp.clip(last_lane - b_first, 0, b_local),
    )
    init = jax.vmap(
        lambda inv, req, drows, dvals, tg, sc: scan_carry(
            inv, req, add_claims(used, drows, dvals, row_offset), tg, sc,
            features,
        )
    )(invs, reqs, delta_rows, delta_vals, tg_counts, spread_counts)
    # Shared usage as the claims and the verify see it; the scores do not.
    claimed = topo.vary(used)
    with jax.named_scope("overlay"):
        if overlay is not None:
            claimed = add_claims(claimed, g_orows, g_ovals, row_offset)
        if chain is not None:
            claimed = add_claims(claimed, *carried_claims(
                g_carry, chain_flags(g_flags, carry.shape[0])[1]
            ), row_offset)
    # What is claimed before any lane places: every live lane's in-flight
    # deltas on top (with one live lane and nothing in flight, bit for bit
    # that lane's own proposed usage).
    claims0 = add_claims(
        claimed, jnp.where(g_live[:, None], g_drows, -1), g_dvals, row_offset
    )
    bufs = inert_lane_outputs(
        b_local, lanes, n_placements, features.preempt, bool(features.dp_width)
    )
    with jax.named_scope("place_scan"):
        _, outs = scan_steps(
            step, (init, claims0), tuple(topo.vary(o) for o in bufs), trip
        )
    g_rows, scores, binpack, preempted, n_eval, n_filt, n_exh, repicked = (
        o.T for o in outs[:8]
    )  # each (b_local, P); g_rows (B, P): every lane's rows on every shard
    rows = lax.dynamic_slice_in_dim(g_rows, b_first, b_local)
    dp_moved = outs[8].T if features.dp_width else None

    # Sequential cross-lane AllocsFit: a loop over ALL lanes, in resolve
    # order, carrying the cumulative proposed usage of this shard's rows.
    # Each lane first applies its own in-flight deltas, then commits its
    # placements one by one, checking used <= totals on every touched row
    # (funcs.go:97-160 AllocsFit, in plan-apply order); a row this shard
    # does not hold fits vacuously, its owner decides (``topo.all``).  Lanes
    # past the last live one and slots past the launch's largest count are
    # never visited and read "fits".
    def lane_step(b, state):
        cum_used, fits_all = state
        l_rows, l_ask, l_live = g_rows[b], g_ask[b], g_live[b]
        base = add_claims(
            cum_used, jnp.where(l_live, g_drows[b], -1), g_dvals[b], row_offset
        )

        def p_step(u, p):
            mine, safe = local_rows(l_rows[p], row_offset, n_local)
            mine &= l_live
            u2 = u.at[safe].add(jnp.where(mine, l_ask, 0.0))
            fit = jnp.all(u2[safe] <= arrays.totals[safe]) | ~mine
            return u2, (fit,)

        after, (fits,) = scan_steps(p_step, base, (fits_all[b],), trip)
        return (
            jnp.where(l_live, after, cum_used),
            lax.dynamic_update_index_in_dim(fits_all, fits, b, 0),
        )

    with jax.named_scope("verify_scan"):
        _, fits_all = lax.fori_loop(
            0, last_lane, lane_step,
            (claimed, topo.vary(jnp.ones(g_rows.shape, bool), nodes=True)),
        )  # (B, P) bool
        verified = topo.all(fits_all)
    packed = pack_fused_lanes(
        rows, scores, binpack, preempted, n_eval, n_filt, n_exh,
        lax.dynamic_slice_in_dim(verified, b_first, b_local),
        repicked, live, dp_moved,
    )
    if chain is None:
        return packed
    own = claims_block(
        delta_rows, claim_vals, rows, preempted, reqs.ask,
        live & chain_flags(flags, carry.shape[0])[0],
    )
    return packed, chained_carry(own, carry)


# The body bound to one device, under the body's own name (a profile's module
# is ``jit_`` + that).
_place_on_one_device = functools.partial(_fused_place_batch_impl, ONE_DEVICE)
_place_on_one_device.__name__ = _fused_place_batch_impl.__name__

fused_place_batch = jax.jit(
    _place_on_one_device, static_argnames=("n_placements", "features")
)

def unpack_rows(buf, layout):
    """The fields of ``encode.packed_rows``' ``(lanes, W)`` uint8 buffer,
    on the device: bit for bit what the host wrote into its views."""
    fields = []
    for offset, shape, dtype, size in layout:
        x = buf[:, offset:offset + size]
        if dtype == "bool":
            x = x != 0
        else:
            x = jax.lax.bitcast_convert_type(
                x.reshape(buf.shape[0], -1, 4), jnp.dtype(dtype)
            )
        fields.append(x.reshape((buf.shape[0],) + shape))
    return fields


# The fields of a launch's lane pack, in its layout's order: every small
# lane operand that is no part of the request (the coalescer's staging slot
# builds the pack from these names).
LANE_FIELDS = (
    "class_elig", "spread_counts", "delta_rows", "delta_vals", "lane_steps",
    "overlay_rows", "overlay_vals", "claim_vals", "chain_flags",
)


def unpack_launch(request_pack, lane_pack, layouts, dp_width: int):
    """What a launch's two packed buffers hold, on the device: the request
    (``RequestSlab.pack``; ``device_request`` leaves the distinct_property
    fields out at ``dp_width`` 0) and the lane pack's fields by name
    (``LANE_FIELDS``).  Slices and bitcasts at a placement program's entry:
    the packs are the program's operands, and no field is a buffer of its
    own on the launching thread."""
    request_layout, lane_layout = layouts
    with jax.named_scope("unpack"):
        reqs = device_request(
            unpack_rows(request_pack, request_layout), dp_width
        )
        lane = dict(zip(LANE_FIELDS, unpack_rows(lane_pack, lane_layout)))
    return reqs, lane


def place_launch(place, arrays, used, reqs, lane, tg_counts, penalties,
                 host_masks, carry, **static):
    """``place`` (a placement program's body: ``fused_place_batch``'s
    signature) on a launch's operands as ``unpack_launch`` gives them: the
    overlay, the chain's flags and ``claim_vals`` are the lane pack's."""
    return place(
        arrays, used, lane["delta_rows"], lane["delta_vals"], tg_counts,
        lane["spread_counts"], penalties, reqs, lane["class_elig"],
        host_masks, lane["lane_steps"],
        overlay=(lane["overlay_rows"], lane["overlay_vals"]),
        chain=(carry, lane["chain_flags"], lane["claim_vals"]),
        **static,
    )


# Live entry: what a launch of the server hands jax, in ONE call — the
# resident matrix, the two packs every small lane operand is a view of
# (unpacked here, at the program's entry), the three node-axis lane buffers
# and the carry.  The node-axis buffers are DONATED, so XLA reuses their
# freshly-transferred device buffers as scratch instead of holding them live
# alongside the outputs; the carry a launch is handed is consumed by that
# launch alone, and the carry it hands on takes its buffer.  The packs are
# views of a staging slot read until the launch resolves, and
# ``arrays``/``used`` stay shared with in-flight pipelined dispatches: never
# donated.  Kept apart from ``fused_place_batch`` because callers of the
# un-donated entry (tests, the smoke) reuse their inputs across calls.
@functools.partial(
    jax.jit,
    static_argnames=("layouts", "n_placements", "features"),
    donate_argnames=("tg_counts", "penalties", "host_masks", "carry"),
)
def fused_place_batch_live(arrays, used, request_pack, lane_pack, tg_counts,
                           penalties, host_masks, carry, *, layouts,
                           n_placements: int, features: Features):
    reqs, lane = unpack_launch(
        request_pack, lane_pack, layouts, features.dp_width
    )
    return place_launch(
        _place_on_one_device, arrays, used, reqs, lane, tg_counts, penalties,
        host_masks, carry, n_placements=n_placements, features=features,
    )


# ---------------------------------------------------------------------------
# Plan-apply verification (AllocsFit re-check at commit time)
# ---------------------------------------------------------------------------


@jax.jit
def verify_plan_fit(arrays, rows, deltas, eligible_required):
    """Vectorized optimistic-concurrency check for the plan applier.

    The reference fans per-node AllocsFit checks out to an EvaluatePool of
    goroutines (plan_apply.go:439-682, plan_apply_pool.go:18). Here the whole
    plan verifies in one kernel against the authoritative matrix: for each
    plan row i, (used + delta ≤ totals) ∧ node still schedulable.

    rows: (K,) i32 node rows (-1 padded); deltas: (K, 3) f32 net usage the
    plan adds to that node; returns (K,) bool per-node verdicts.
    """
    safe = jnp.maximum(rows, 0)
    used = arrays.used[safe] + deltas  # (K, 3)
    fits = jnp.all(used <= arrays.totals[safe], axis=1)
    ok = fits & (~eligible_required | arrays.eligible[safe])
    return jnp.where(rows < 0, True, ok)
