"""Fake-device backend — numpy twins of the scheduling kernels.

``NOMAD_TPU_FAKE_DEVICE=1`` swaps every device dispatch for an instant
host-side numpy evaluation with identical semantics (golden-tested against
the JAX kernels in tests/test_fake_device.py).  The point is isolation:
with the device answering in microseconds, a profile of the live server
shows ONLY the host path — broker dequeue, snapshot sync, reconcile,
encode, plan submit/apply — the part that caps end-to-end throughput far
below what the kernels sustain.  It also lets tier-1 CI exercise the full
server loop without paying JAX dispatch/compile cost.

Twins mirror ops/kernels.py exactly (same score semantics, same packed
result layout).  Two exact-output shortcuts keep them fast:

* feasibility, penalty, affinity and preemption state depend only on the
  matrix and the request — not on the scan carry — so they are computed
  once per request instead of once per scan step;
* once a scan step fails to place, the carry is unchanged, so every
  later step produces byte-identical output — computed once, replicated.
"""

from __future__ import annotations

import os
import time
from typing import Any, Dict, List, NamedTuple, Optional

import numpy as np

from .encode import (
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
)
from ..retry import env_float

NEG_INF = -1e30
PREEMPTION_RATE = 0.0048
PREEMPTION_ORIGIN = 2048.0

_ENV = "NOMAD_TPU_FAKE_DEVICE"
_LATENCY_ENV = "NOMAD_TPU_FAKE_DEVICE_LATENCY_MS"


def enabled() -> bool:
    """True when the fake-device backend is active (env-gated)."""
    return os.environ.get(_ENV, "") == "1"


def latency_s() -> float:
    """Synthetic device→host fetch latency (seconds), from
    ``NOMAD_TPU_FAKE_DEVICE_LATENCY_MS``.

    Models device latency the way JAX async dispatch exposes it:
    launching a computation is cheap, *fetching* its result blocks until
    the device is done.  The coalescer therefore wraps fake dispatch
    results in a :class:`DeferredResult` whose clock starts at launch —
    overlapping in-flight dispatches overlap their latency windows exactly
    like real pipelined fetches, which is what makes pipeline speedup
    provable in CI without a device."""
    return max(0.0, env_float(_LATENCY_ENV, 0.0)) / 1000.0


class DeferredResult:
    """A fake in-flight dispatch: the value is already computed, but
    ``result()`` blocks until ``launched_at + latency`` — the fake twin of
    ``np.asarray`` on an async jax array."""

    __slots__ = ("value", "ready_at")

    def __init__(self, value, latency: float):
        self.value = value
        self.ready_at = time.monotonic() + latency

    def result(self):
        remaining = self.ready_at - time.monotonic()
        if remaining > 0:
            time.sleep(remaining)
        return self.value


# ---------------------------------------------------------------------------
# Feasibility
# ---------------------------------------------------------------------------


def _check_predicates(attr_hash, attr_num, attr_ver, slots, ops, want_hash,
                      want_num) -> np.ndarray:
    """(C, N) bool — every predicate against every node; inactive predicates
    (slot < 0) pass.  Twin of kernels._check_predicate (vmapped axis first)."""
    slots = np.asarray(slots, np.int64)
    ops = np.asarray(ops, np.int64)
    want_hash = np.asarray(want_hash)
    want_num = np.asarray(want_num, np.float32)
    safe = np.maximum(slots, 0)
    h = attr_hash[:, safe].T  # (C, N)
    is_ver = (ops >= OP_VER_EQ)[:, None]
    v = np.where(is_ver, attr_ver[:, safe].T, attr_num[:, safe].T)  # (C, N)
    present = h != 0
    num_ok = present & ~np.isnan(v) & ~np.isnan(want_num)[:, None]

    wh = want_hash[:, None]
    wn = want_num[:, None]
    o = ops[:, None]
    eq = present & (h == wh)
    res = np.ones_like(present)
    res = np.where(o == OP_EQ, eq, res)
    res = np.where(o == OP_NEQ, ~eq, res)
    with np.errstate(invalid="ignore"):
        res = np.where(o == OP_LT, num_ok & (v < wn), res)
        res = np.where(o == OP_LTE, num_ok & (v <= wn), res)
        res = np.where(o == OP_GT, num_ok & (v > wn), res)
        res = np.where(o == OP_GTE, num_ok & (v >= wn), res)
        res = np.where(o == OP_VER_EQ, num_ok & (v == wn), res)
        res = np.where(o == OP_VER_LT, num_ok & (v < wn), res)
        res = np.where(o == OP_VER_LTE, num_ok & (v <= wn), res)
        res = np.where(o == OP_VER_GT, num_ok & (v > wn), res)
        res = np.where(o == OP_VER_GTE, num_ok & (v >= wn), res)
    res = np.where(o == OP_IS_SET, present, res)
    res = np.where(o == OP_IS_NOT_SET, ~present, res)
    return np.where(slots[:, None] < 0, True, res)


def constraint_mask(arrays, req: SchedRequest) -> np.ndarray:
    c_slot = np.asarray(req.c_slot)
    active = c_slot >= 0
    if not active.any():
        return np.ones((arrays.attr_hash.shape[0],), bool)
    # Only active predicates pay the (C, N) gather.
    per = _check_predicates(
        arrays.attr_hash, arrays.attr_num, arrays.attr_ver,
        c_slot[active], np.asarray(req.c_op)[active],
        np.asarray(req.c_hash)[active], np.asarray(req.c_num)[active],
    )
    return np.all(per, axis=0)


def datacenter_mask(arrays, req: SchedRequest) -> np.ndarray:
    dc_hash = np.asarray(req.dc_hash)
    dc = arrays.attr_hash[:, 0]
    member = (dc[:, None] == dc_hash[None, :]) & (dc_hash[None, :] > 0)
    skip = dc_hash[0] == -1
    return np.any(member, axis=1) | skip


def device_mask(arrays, req: SchedRequest) -> np.ndarray:
    dev_ask = np.asarray(req.dev_ask)
    if not (dev_ask > 0).any():
        return np.ones((arrays.dev_total.shape[0],), bool)
    free = arrays.dev_total - arrays.dev_used
    ok = (free >= dev_ask[None, :]) | (dev_ask[None, :] == 0)
    return np.all(ok, axis=1)


def port_mask(arrays, req: SchedRequest) -> np.ndarray:
    from ..state.matrix import DYN_PORT_CAPACITY

    p = np.asarray(req.p_static)
    p_dyn = int(req.p_dyn)
    valid = p >= 0
    n = arrays.port_words.shape[0]
    if valid.any():
        word = np.maximum(p, 0) >> 5
        bit = (np.maximum(p, 0) & 31).astype(np.uint32)
        words = arrays.port_words[:, word]  # (N, P)
        taken = (words >> bit[None, :]) & np.uint32(1)
        conflict = np.any(valid[None, :] & (taken == 1), axis=1)
    else:
        conflict = np.zeros((n,), bool)
    dyn_ok = arrays.dyn_used + p_dyn <= DYN_PORT_CAPACITY
    return (~conflict) & dyn_ok


def feasibility_mask(arrays, req: SchedRequest,
                     class_elig: Optional[np.ndarray] = None,
                     host_mask: Optional[np.ndarray] = None) -> np.ndarray:
    mask = arrays.eligible.copy()
    mask &= datacenter_mask(arrays, req)
    mask &= constraint_mask(arrays, req)
    mask &= device_mask(arrays, req)
    mask &= port_mask(arrays, req)
    if class_elig is not None:
        class_elig = np.asarray(class_elig)
        cid = np.maximum(arrays.class_id, 0)
        mask &= np.where(arrays.class_id < 0, False, class_elig[cid])
    if host_mask is not None:
        mask &= np.asarray(host_mask)
    return mask


def _distinct_property_columns(arrays, req: SchedRequest):
    """((DP,) bool active, (DP, N) value id of every node): twin of
    kernels._distinct_property_columns at the request's full width."""
    slot = np.asarray(req.dp_slot)
    return slot >= 0, arrays.attr_hash[:, np.maximum(slot, 0)].T


def distinct_property_counts(arrays, req: SchedRequest) -> np.ndarray:
    """(DP, N) f32: twin of kernels.distinct_property_counts."""
    active, col = _distinct_property_columns(arrays, req)
    value_hash = np.asarray(req.dp_value_hash)
    counts = np.asarray(req.dp_count, np.float32)
    out = np.zeros(col.shape, np.float32)
    for d in np.flatnonzero(active):
        for v in np.flatnonzero(value_hash[d]):
            out[d] += np.where(col[d] == value_hash[d, v], counts[d, v], 0.0)
    return out


def distinct_property_mask(arrays, req: SchedRequest, dp_cnt) -> np.ndarray:
    """(N,) bool: twin of kernels.distinct_property_mask."""
    active, col = _distinct_property_columns(arrays, req)
    limit = np.asarray(req.dp_limit, np.float32)[:, None]
    full = (col == 0) | (dp_cnt >= limit)
    return ~np.any(active[:, None] & full, axis=0)


# ---------------------------------------------------------------------------
# Scoring
# ---------------------------------------------------------------------------


def fit_and_binpack(arrays, used, req: SchedRequest):
    ask = np.asarray(req.ask, np.float32)
    util = used + ask[None, :]
    fits_dim = util <= arrays.totals
    fits = np.all(fits_dim, axis=1)
    exhausted = np.argmax(~fits_dim, axis=1).astype(np.int32)
    exhausted = np.where(fits, -1, exhausted).astype(np.int32)
    return fits, score_fit(arrays, util, req), exhausted


def score_fit(arrays, util, req: SchedRequest):
    """Twin of kernels.score_fit: ScoreFit of the utilisation ``util``."""
    denom = np.maximum(arrays.totals, np.float32(1.0))
    free = np.float32(1.0) - util / denom
    # exp2(x·log₂10) mirrors the kernel's 10**x lowering exactly (see
    # kernels.score_fit).
    log2_10 = np.float32(3.321928094887362)
    total = np.exp2(free[:, 0] * log2_10) + np.exp2(free[:, 1] * log2_10)
    binpack = np.clip(np.float32(20.0) - total, 0.0, 18.0)
    spread = np.clip(total - np.float32(2.0), 0.0, 18.0)
    score = np.where(int(req.algorithm) == 1, spread, binpack) / np.float32(18.0)
    return score.astype(np.float32)


def anti_affinity_score(tg_count, req: SchedRequest):
    collisions = tg_count.astype(np.float32)
    score = -(collisions + 1.0) / np.float32(req.desired_count)
    appended = collisions > 0
    return np.where(appended, score, 0.0).astype(np.float32), appended


def penalty_score(penalty_mask):
    return np.where(penalty_mask, -1.0, 0.0).astype(np.float32), penalty_mask


def affinity_score(arrays, req: SchedRequest):
    a_slot = np.asarray(req.a_slot)
    n = arrays.attr_hash.shape[0]
    active = a_slot >= 0
    if not active.any():
        zero = np.zeros((n,), np.float32)
        return zero, np.zeros((n,), bool)
    matches = _check_predicates(
        arrays.attr_hash, arrays.attr_num, arrays.attr_ver,
        req.a_slot, req.a_op, req.a_hash, req.a_num,
    )  # (A, N)
    a_weight = np.asarray(req.a_weight, np.float32)
    matched = matches & active[:, None]
    sum_weight = np.sum(np.abs(a_weight) * active)
    total = np.sum(matched * a_weight[:, None], axis=0)
    norm = total / max(sum_weight, 1e-9)
    appended = (total != 0.0) & (sum_weight > 0)
    return np.where(appended, norm, 0.0).astype(np.float32), appended


def spread_score(arrays, req: SchedRequest, spread_counts):
    s_slot = np.asarray(req.s_slot)
    n = arrays.attr_hash.shape[0]
    if not (s_slot >= 0).any():
        return np.zeros((n,), np.float32), np.zeros((n,), bool)

    total = np.zeros((n,), np.float32)
    rel_denom = max(float(req.s_sum_weights), 1e-9)
    for s in range(s_slot.shape[0]):
        slot = int(s_slot[s])
        if slot < 0:
            continue
        weight = np.float32(req.s_weight[s])
        even = bool(req.s_even[s])
        value_hash = np.asarray(req.s_value_hash[s])
        desired = np.asarray(req.s_desired[s], np.float32)
        implicit = float(req.s_implicit[s])
        counts = np.asarray(spread_counts[s], np.float32)

        nvalue = arrays.attr_hash[:, slot]  # (N,)
        node_has = nvalue != 0
        vmatch = (nvalue[:, None] == value_hash[None, :]) & (
            value_hash[None, :] != 0
        )  # (N, V)
        count_at = np.sum(np.where(vmatch, counts[None, :], 0.0), axis=1)
        used_count = count_at + 1.0

        if even:
            valid = (value_hash != 0) & (counts > 0)
            any_use = valid.any()
            if any_use:
                mn = counts[valid].min()
                mx = counts[valid].max()
            else:
                mn = mx = 0.0
            current = count_at
            delta_boost = np.where(
                mn == 0, -1.0, (mn - current) / max(mn, 1e-9)
            )
            if mn == mx:
                at_min = -1.0
            elif mn == 0:
                at_min = 1.0
            else:
                at_min = (mx - mn) / max(mn, 1e-9)
            stanza = np.where(current != mn, delta_boost, at_min)
            if not any_use:
                stanza = np.zeros_like(stanza)
            stanza = np.where(node_has, stanza, -1.0)
        else:
            desired_ok = ~np.isnan(desired)
            has_target = np.any(vmatch & desired_ok[None, :], axis=1)
            with np.errstate(invalid="ignore"):
                desired_at = np.sum(
                    np.where(vmatch & desired_ok[None, :],
                             desired[None, :], 0.0),
                    axis=1,
                )
            desired_v = np.where(has_target, desired_at, np.nan)
            use_implicit = ~has_target & ~np.isnan(implicit)
            desired_v = np.where(use_implicit, implicit, desired_v)
            no_target = np.isnan(desired_v)
            rel_weight = float(weight) / rel_denom
            with np.errstate(invalid="ignore"):
                boost_t = (
                    (desired_v - used_count) / np.maximum(desired_v, 1e-9)
                ) * rel_weight
            stanza = np.where(no_target, -1.0, boost_t)

        total += stanza.astype(np.float32)

    appended = total != 0.0
    return np.where(appended, total, 0.0).astype(np.float32), appended


def preemption_state(arrays, req: SchedRequest):
    from ..state.matrix import PRIORITY_BUCKETS

    n = arrays.prio_used.shape[0]
    bucket = int(req.preempt_bucket)
    if bucket < 0:
        return (
            np.zeros((n, 3), np.float32),
            np.zeros((n,), np.float32),
            np.zeros((n,), bool),
        )
    k = min(max(bucket, 0), PRIORITY_BUCKETS)
    freeable = (
        np.sum(arrays.prio_used[:, :k], axis=1)
        if k > 0
        else np.zeros((n, 3), np.float32)
    )
    buckets = np.arange(PRIORITY_BUCKETS, dtype=np.float32)
    mid = (buckets + 0.5) * (101.0 / PRIORITY_BUCKETS)
    present = np.any(arrays.prio_used > 0, axis=2)  # (N, P)
    mid_masked = np.where(present, mid[None, :], 0.0)
    if k > 0:
        max_prio = np.max(mid_masked[:, :k], axis=1)
        sum_prio = np.sum(mid_masked[:, :k], axis=1)
    else:
        max_prio = np.zeros((n,), np.float32)
        sum_prio = np.zeros((n,), np.float32)
    net = np.where(
        max_prio > 0, max_prio + sum_prio / np.maximum(max_prio, 1e-9), 0.0
    )
    score = 1.0 / (1.0 + np.exp(PREEMPTION_RATE * (net - PREEMPTION_ORIGIN)))
    usable = np.any(freeable > 0, axis=1)
    return (
        freeable.astype(np.float32),
        score.astype(np.float32),
        usable,
    )


class _StaticParts(NamedTuple):
    """Per-request state that does not change across scan steps."""

    feas: np.ndarray  # (N,) bool — pre-distinct-hosts feasibility
    pen_score: np.ndarray  # (N,) f32
    pen_app: np.ndarray  # (N,) bool
    aff_score: np.ndarray  # (N,) f32
    aff_app: np.ndarray  # (N,) bool
    extra_free: np.ndarray  # (N, 3) f32
    pre_score: np.ndarray  # (N,) f32
    pre_usable: np.ndarray  # (N,) bool
    ask: np.ndarray  # (3,) f32
    distinct: bool


# Per-(arrays, inputs) memo for _static_parts.  Distinct jobs with identical
# constraint/affinity content compile to byte-identical request tensors, and
# steady-state bursts are dominated by such twins — the feasibility sweep
# over (N, A) attr tensors is the fake backend's single hottest block.  The
# key is the full input content (all req fields + the three mask vectors),
# so a hit is exact by construction; entries are dropped whenever a new
# device snapshot appears (syncs invalidate node state).
_STATIC_MEMO: Dict[bytes, _StaticParts] = {}
_STATIC_MEMO_ARRAYS: List[Any] = [None]  # strong ref; identity-checked
_STATIC_MEMO_MAX = 256


def _static_parts_key(req, penalty_mask, class_elig, host_mask) -> bytes:
    parts = [np.ascontiguousarray(f).tobytes() for f in req]
    parts.append(np.ascontiguousarray(penalty_mask).tobytes())
    parts.append(np.ascontiguousarray(class_elig).tobytes())
    parts.append(np.ascontiguousarray(host_mask).tobytes())
    return b"\x00".join(parts)


def _static_parts(arrays, req: SchedRequest, penalty_mask, class_elig,
                  host_mask) -> _StaticParts:
    if _STATIC_MEMO_ARRAYS[0] is not arrays:
        _STATIC_MEMO.clear()
        _STATIC_MEMO_ARRAYS[0] = arrays
    key = _static_parts_key(req, penalty_mask, class_elig, host_mask)
    hit = _STATIC_MEMO.get(key)
    if hit is not None:
        return hit
    sp = _compute_static_parts(arrays, req, penalty_mask, class_elig,
                               host_mask)
    if len(_STATIC_MEMO) >= _STATIC_MEMO_MAX:
        _STATIC_MEMO.pop(next(iter(_STATIC_MEMO)))
    _STATIC_MEMO[key] = sp
    return sp


def _compute_static_parts(arrays, req: SchedRequest, penalty_mask,
                          class_elig, host_mask) -> _StaticParts:
    feas = feasibility_mask(arrays, req, class_elig, host_mask)
    pen_score, pen_app = penalty_score(np.asarray(penalty_mask, bool))
    aff_score, aff_app = affinity_score(arrays, req)
    extra_free, pre_score, pre_usable = preemption_state(arrays, req)
    return _StaticParts(
        feas=feas,
        pen_score=pen_score,
        pen_app=pen_app,
        aff_score=aff_score,
        aff_app=aff_app,
        extra_free=extra_free,
        pre_score=pre_score,
        pre_usable=pre_usable,
        ask=np.asarray(req.ask, np.float32),
        distinct=bool(req.distinct_hosts),
    )


class _Ranked(NamedTuple):
    """One step's ranking of some rows, before the two-tier gate
    (kernels.score_nodes): a row is ranked as a node that fits, as a node
    that fits after an eviction, or not at all."""

    fits: np.ndarray  # bool: the ask fits without eviction
    can_pre: np.ndarray  # bool: it fits only after one
    final_fit: np.ndarray  # f32 mean as a fitting node, NEG_INF elsewhere
    final_pre: np.ndarray  # f32 mean as a preempting node, NEG_INF elsewhere
    bin_fit: np.ndarray  # f32 ScoreFit of used + ask
    bin_pre: np.ndarray  # f32 the two estimated terms of ``final_pre``
    terms_pre: np.ndarray  # f32 number of terms ``final_pre`` is the mean of


def _rank_rows(view, req: SchedRequest, sp: _StaticParts, r, feas, used,
               tg_count, spr_score=None, spr_app=None) -> _Ranked:
    """Rank the rows ``r`` (a slice) of the request's static parts;
    ``view`` (totals), ``feas``, ``used``, ``tg_count`` and the spread
    terms are already cut to them.  The float32 expressions of
    kernels.score_nodes, term for term."""
    f32 = np.float32
    fits, bin_fit, _ = fit_and_binpack(view, used, req)
    util = used + sp.ask[None, :]
    deficit = np.maximum(util - view.totals, f32(0.0))
    freeable = sp.extra_free[r]
    can_pre = (
        ~fits & np.all(deficit <= freeable, axis=1) & sp.pre_usable[r]
    )
    aa_score, aa_app = anti_affinity_score(tg_count, req)
    count = (
        f32(1.0)
        + aa_app.astype(f32)
        + sp.pen_app[r].astype(f32)
        + sp.aff_app[r].astype(f32)
    )
    if spr_score is not None:
        count = count + spr_app.astype(f32)

    def total(binpack):  # the kernel's order of additions
        t = binpack + aa_score + sp.pen_score[r] + sp.aff_score[r]
        return t if spr_score is None else t + spr_score

    final_fit = np.where(
        feas & fits, total(bin_fit) / count, NEG_INF
    ).astype(f32)
    if not can_pre.any():
        none = np.full(fits.shape, NEG_INF, f32)
        zero = np.zeros(fits.shape, f32)
        return _Ranked(fits, can_pre, final_fit, none, bin_fit, zero, zero)
    evicted = np.minimum(freeable, deficit)
    bin_est = score_fit(view, util - evicted, req)
    pre_score = sp.pre_score[r]
    terms_pre = count + f32(1.0)
    final_pre = np.where(
        feas & can_pre, (total(bin_est) + pre_score) / terms_pre, NEG_INF
    ).astype(f32)
    return _Ranked(
        fits, can_pre, final_fit, final_pre, bin_fit,
        (bin_est + pre_score).astype(f32), terms_pre.astype(f32),
    )


class _TotalsView(NamedTuple):
    """1-row stand-in for DeviceArrays when rescoring a single node."""

    totals: np.ndarray


class _LaneScan:
    """One request's placement scan, a step at a time: ``final`` is the
    step's score vector, ``commit(row)`` charges the pick and returns the
    step's seven packed columns.  Twin of the two halves of a kernel step
    (kernels._score_step / _commit_step); the solo scan takes the arg-max
    between them, the fused twin resolves the lanes' picks there.

    Every row is ranked both ways (``_Ranked``) and the two-tier gate is
    applied on reading: nodes that need an eviction are in the arg-max
    only while no feasible node fits without one (``n_fit`` == 0).

    Without spread stanzas every node is scored once and only the placed
    row is rescored between steps (the carry changes nowhere else; the
    single-row rescore runs the same float32 expressions on 1-element
    slices, so the outputs are identical to a full recompute).  Spread
    stanzas shift every node's score when a placement bumps a value
    count, and a distinct_property limit takes every node that shares the
    picked node's value out at once: full recompute per step.

    With a distinct_property slot the rows are ranked as the limit's
    absence would rank them and the limit is applied after, so that
    ``dp_blocked_best`` (the best score among the nodes it alone excluded)
    is there for the ``moved`` flag (kernels._commit_step)."""

    def __init__(self, arrays, req: SchedRequest, used0, tg_count,
                 spread_counts, penalty_mask, class_elig, host_mask):
        self.arrays, self.req = arrays, req
        self.sp = sp = _static_parts(
            arrays, req, penalty_mask, class_elig, host_mask
        )
        self.used = np.array(used0, np.float32, copy=True)
        self.tg = np.array(tg_count, np.int32, copy=True)
        self.spreads = bool((np.asarray(req.s_slot) >= 0).any())
        self.feas = sp.feas & ~(self.tg > 0) if sp.distinct else sp.feas
        self.dp = bool((np.asarray(req.dp_slot) >= 0).any())
        if self.dp:
            self.dp_cnt = distinct_property_counts(arrays, req)
            self.feas_open = self.feas
        if self.spreads:
            self.s_hash = np.array(req.s_value_hash, copy=True)
            self.s_counts = np.array(spread_counts, np.float32, copy=True)
        self._rescore_all()

    def _rescore_all(self):
        spr = (None, None)
        self.req_step = self.req
        if self.spreads:
            self.req_step = self.req._replace(s_value_hash=self.s_hash)
            spr = spread_score(self.arrays, self.req_step, self.s_counts)
        open_ = self.feas_open if self.dp else self.feas
        # Arrays the single-row rescore writes into: own copies.
        self.rk = rk = _Ranked(*(
            np.array(x) for x in _rank_rows(
                self.arrays, self.req_step, self.sp, slice(None), open_,
                self.used, self.tg, *spr,
            )
        ))
        self.dp_blocked_best = np.float32(NEG_INF)
        if self.dp:
            dp_ok = distinct_property_mask(self.arrays, self.req, self.dp_cnt)
            self.feas = open_ & dp_ok
            gate_pre = (
                not np.any(self.feas & rk.fits)
                and np.any(self.feas & rk.can_pre)
            )
            blocked = open_ & ~dp_ok
            best = rk.final_fit[blocked]
            if gate_pre:
                best = np.maximum(best, rk.final_pre[blocked])
            if best.size:
                self.dp_blocked_best = best.max()
            rk.final_fit[~dp_ok] = NEG_INF
            rk.final_pre[~dp_ok] = NEG_INF
        feas = self.feas
        self.n_eval = int(np.sum(feas))
        self.n_filt = int(np.sum(~feas & self.arrays.eligible))
        self.n_fit = int(np.sum(feas & rk.fits))
        self.n_pre = int(np.sum(feas & rk.can_pre))

    # -- the two-tier gate, on reading -------------------------------------

    @property
    def preempting(self) -> bool:
        """This step's arg-max is over the nodes that need an eviction."""
        return self.n_fit == 0 and self.n_pre > 0

    @property
    def final(self) -> np.ndarray:
        return self.rk.final_pre if self.preempting else self.rk.final_fit

    @property
    def needs_pre(self) -> np.ndarray:
        if self.preempting:
            return self.rk.can_pre
        return np.zeros(self.rk.can_pre.shape, bool)

    @property
    def n_exh(self) -> int:
        # feasible, and not in this step's arg-max for want of room
        open_ = self.n_eval - self.n_fit
        return open_ - self.n_pre if self.preempting else open_

    def moved(self, row: int) -> bool:
        """A node a distinct_property limit alone excluded scored higher
        than ``row`` (read before ``commit``)."""
        return bool(self.dp and self.dp_blocked_best > self.final[row])

    def failed_row(self) -> tuple:
        """The packed columns of a step in which no node can take the
        request (the carry stays as it is, so every later step reads the
        same)."""
        return (-1.0, 0.0, 0.0, 0.0, self.n_eval, self.n_filt, self.n_exh)

    def commit(self, row: int) -> tuple:
        arrays, sp, rk = self.arrays, self.sp, self.rk
        pre = self.preempting and bool(rk.can_pre[row])
        out = (
            row,
            self.final[row],
            rk.bin_pre[row] if pre else rk.bin_fit[row],
            rk.terms_pre[row] if pre else 0.0,
            self.n_eval,
            self.n_filt,
            self.n_exh,
        )
        old_feas = bool(self.feas[row])
        old_fit = old_feas and bool(rk.fits[row])
        old_pre = old_feas and bool(rk.can_pre[row])
        self.used[row] += sp.ask
        self.tg[row] += 1
        if sp.distinct:
            which = "feas_open" if self.dp else "feas"
            if getattr(self, which) is sp.feas:
                setattr(self, which, sp.feas.copy())
            getattr(self, which)[row] = False
        if self.dp:
            active, col = _distinct_property_columns(arrays, self.req)
            v = col[:, row:row + 1]
            self.dp_cnt += (col == v) & (v != 0) & active[:, None]
        if self.spreads:
            nvalues = arrays.attr_hash[
                row, np.maximum(np.asarray(self.req_step.s_slot), 0)
            ]
            _apply_spread_values(
                self.req_step, self.s_hash, self.s_counts, nvalues
            )
        if self.spreads or self.dp:
            self._rescore_all()
            return out

        r = slice(row, row + 1)
        new = _rank_rows(
            _TotalsView(arrays.totals[r]), self.req, sp, r, self.feas[r],
            self.used[r], self.tg[r],
        )
        for dst, src in zip(rk, new):
            dst[row] = src[0]
        new_feas = bool(self.feas[row])
        if new_feas != old_feas:
            self.n_eval += 1 if new_feas else -1
            if bool(arrays.eligible[row]):
                self.n_filt += -1 if new_feas else 1
        self.n_fit += int(new_feas and bool(rk.fits[row])) - int(old_fit)
        self.n_pre += int(new_feas and bool(rk.can_pre[row])) - int(old_pre)
        return out


def _apply_spread_values(req: SchedRequest, s_hash, s_counts, nvalues):
    """In-place twin of kernels.apply_spread_values for the chosen node."""
    s_slot = np.asarray(req.s_slot)
    for s in range(s_slot.shape[0]):
        slot = int(s_slot[s])
        nv = int(nvalues[s])
        vh = s_hash[s]
        match = (vh == nv) & (nv != 0)
        have = bool(match.any())
        zeros = vh == 0
        free_slot = int(np.argmax(zeros)) if zeros.any() else 0
        idx = int(np.argmax(match)) if have else free_slot
        can = slot >= 0 and nv != 0 and (have or vh[free_slot] == 0)
        if can and not have:
            vh[idx] = nv
        if can:
            s_counts[s, idx] += 1.0


def _place_scan(arrays, req: SchedRequest, used0, tg_count, spread_counts,
                penalty_mask, class_elig, host_mask,
                n_placements: int) -> np.ndarray:
    """Twin of kernels._place_scan; returns packed (n_placements, 7) f32."""
    lane = _LaneScan(arrays, req, used0, tg_count, spread_counts,
                     penalty_mask, class_elig, host_mask)
    out = np.zeros((n_placements, 7), np.float32)
    for step in range(n_placements):
        row = int(np.argmax(lane.final))
        if not lane.final[row] > NEG_INF / 2:
            # Failed step leaves the carry unchanged — every remaining step
            # is byte-identical; replicate instead of recomputing.
            out[step:, :] = lane.failed_row()
            break
        out[step] = lane.commit(row)
    return out


# ---------------------------------------------------------------------------
# Kernel-twin entry points (same shapes/semantics as ops.kernels)
# ---------------------------------------------------------------------------


class FakePlacementResult(NamedTuple):
    rows: np.ndarray
    scores: np.ndarray
    binpack: np.ndarray
    preempted: np.ndarray
    nodes_evaluated: np.ndarray
    nodes_filtered: np.ndarray
    nodes_exhausted: np.ndarray


def place_task_group(arrays, req: SchedRequest, used0, tg_count,
                     spread_counts, penalty_mask, class_elig, host_mask,
                     n_placements: int) -> FakePlacementResult:
    """Solo-path twin of kernels.place_task_group (host-side result views)."""
    packed = _place_scan(
        arrays, req, used0, tg_count, spread_counts, penalty_mask,
        class_elig, host_mask, n_placements,
    )
    return FakePlacementResult(
        rows=packed[:, 0].astype(np.int32),
        scores=packed[:, 1],
        binpack=packed[:, 2],
        preempted=packed[:, 3],
        nodes_evaluated=packed[:, 4].astype(np.int32),
        nodes_filtered=packed[:, 5].astype(np.int32),
        nodes_exhausted=packed[:, 6].astype(np.int32),
    )


# Packed-output constants of the fused megakernel, mirrored from
# ops/kernels.py (this module stays importable without JAX).
PACKED_FILTERED = 5  # + 0.5: a distinct_property limit moved the pick
FUSED_PACKED_VERIFIED = 7
FUSED_PACKED_WIDTH = 8


def fused_place_batch(arrays, used, delta_rows: List[np.ndarray],
                      delta_vals: List[np.ndarray],
                      tg_counts: List[np.ndarray],
                      spread_counts: List[np.ndarray],
                      penalties: List[np.ndarray],
                      reqs: List[SchedRequest],
                      class_eligs: List[np.ndarray],
                      host_masks: List[np.ndarray],
                      lane_mask,
                      n_placements: int,
                      live_counts: Optional[List[int]] = None,
                      overlay=None, chain=None):
    """Twin of kernels.fused_place_batch — (B, P, FUSED_PACKED_WIDTH) f32.

    The lanes' scans run in lockstep.  Within a step the live lanes take
    their picks in lane order against one image of the launch's claims
    (the shared usage with the in-flight overlay under it, every live
    lane's in-flight deltas, every pick so far): the arg-max of the lane's own scores over the nodes where the
    image has room for its ask (a node it may take by preempting counts
    as having room until a claim over-fills it), its own arg-max where
    none has.  Then the sequential
    cross-lane AllocsFit VERIFIED column: lanes commit their in-flight
    deltas and placements to a cumulative usage image in lane order, and
    each placement is checked against it (1.0 fits on the lane's own
    arg-max, 2.0 fits on a re-picked node, 0.0 an earlier lane claimed the
    capacity, -1.0 dead lane). ``lane_mask`` marks live lanes explicitly;
    dead lanes emit row=-1 / zeros and touch nothing.

    ``live_counts[i]`` is the kernel's ``lane_steps[i]`` for a live lane
    (None = all ``n_placements``; 0 = a dead lane): the lane's scan stops
    after that many steps and its tail rows are inert (row=-1, zeros,
    verified=1.0, nothing added to either usage image) —
    kernel-exact, tests/test_megakernel.py compares all eight columns.

    ``overlay`` = (rows, vals), -1 padded, any leading shape: the
    in-flight claims of the launches before this one
    (``claimed`` in the kernel), added to the usage under the claims image
    and the verify pass and to no score; None = empty.

    ``chain`` = (carry (D, Bc, K + P, 4) f32 with Bc >= B, live (D,) bool,
    claim_vals [(K, 3)] a lane, holds [bool] a lane): the claims chained
    from launch to launch (``kernels.claims_block`` / ``chained_carry``).
    The live blocks' rows are added where the overlay's are, and the twin
    returns (packed, the carry for the next launch: this launch's block,
    then the carried ones shifted by one); None = the packed result alone.
    """
    b = len(reqs)
    lane_mask = np.asarray(lane_mask, bool)
    out = np.zeros((b, n_placements, FUSED_PACKED_WIDTH), np.float32)
    out[:, :, 0] = -1.0
    steps = [
        min(n_placements, int(live_counts[i]))
        if live_counts is not None else n_placements
        for i in range(b)
    ]
    live = [i for i in range(b) if lane_mask[i] and steps[i] > 0]
    totals = arrays.totals
    claims = np.array(used, np.float32, copy=True)
    if overlay is not None:
        orows = np.asarray(overlay[0]).reshape(-1)
        ovals = np.asarray(overlay[1], np.float32).reshape(-1, 3)
        # As the kernel's scatter: padding adds nothing, a row past the
        # snapshot (registered after a growth this launch has not synced)
        # is dropped.
        inside = (orows >= 0) & (orows < claims.shape[0])
        np.add.at(claims, orows[inside], ovals[inside])
    if chain is not None:
        carry, live_blocks, claim_vals, holds = chain
        carry = np.asarray(carry, np.float32)
        blocks = carry[np.asarray(live_blocks, bool)[: len(carry)]]
        crows = blocks[..., 0].astype(np.int64).reshape(-1)
        inside = (crows >= 0) & (crows < claims.shape[0])
        np.add.at(claims, crows[inside], blocks[..., 1:].reshape(-1, 3)[inside])
    cum_used = claims.copy()  # the verify pass starts from the same image
    scans = {}
    for i in live:
        drows = np.asarray(delta_rows[i])
        dvals = np.asarray(delta_vals[i])
        valid = drows >= 0
        used0 = used
        if valid.any():
            used0 = used.copy()
            np.add.at(used0, drows[valid], dvals[valid])
            np.add.at(claims, drows[valid], dvals[valid])
        scans[i] = _LaneScan(
            arrays, reqs[i], used0, tg_counts[i], spread_counts[i],
            penalties[i], class_eligs[i], host_masks[i],
        )
    repicked = np.zeros((b, n_placements), bool)
    for step in range(max((steps[i] for i in live), default=0)):
        for i in live:
            if step >= steps[i]:
                continue
            lane = scans[i]
            own = int(np.argmax(lane.final))
            if not lane.final[own] > NEG_INF / 2:
                out[i, step, :7] = lane.failed_row()
                continue
            ask = lane.sp.ask
            room = np.all(claims + ask[None, :] <= totals, axis=1)
            unclaimed = np.all(claims <= totals, axis=1)
            masked = np.where(
                room | (lane.needs_pre & unclaimed), lane.final, NEG_INF
            )
            row = int(np.argmax(masked))
            if not masked[row] > NEG_INF / 2:
                row = own
            repicked[i, step] = row != own
            moved = lane.moved(row)
            out[i, step, :7] = lane.commit(row)
            out[i, step, PACKED_FILTERED] += 0.5 * moved
            claims[row] += ask
    # Sequential AllocsFit re-verify against the cumulative image.
    out[:, :, FUSED_PACKED_VERIFIED] = -1.0
    for i in live:
        drows = np.asarray(delta_rows[i])
        valid = drows >= 0
        if valid.any():
            np.add.at(cum_used, drows[valid], np.asarray(delta_vals[i])[valid])
        ask = scans[i].sp.ask
        for p in range(n_placements):
            row = int(out[i, p, 0])
            if row < 0:
                out[i, p, FUSED_PACKED_VERIFIED] = 1.0
                continue
            cum_used[row] += ask
            fits = np.all(cum_used[row] <= totals[row])
            out[i, p, FUSED_PACKED_VERIFIED] = (
                (2.0 if repicked[i, p] else 1.0) if fits else 0.0
            )
    if chain is None:
        return out
    own = np.zeros(carry.shape[1:], np.float32)
    own[..., 0] = -1.0
    for i in live:
        if not holds[i]:
            continue
        drows = np.asarray(delta_rows[i])
        held = np.flatnonzero(drows >= 0)
        own[i, held, 0] = drows[held]
        own[i, held, 1:] = np.asarray(claim_vals[i], np.float32)[held]
        # The picks up to and including the first preempting one.
        pre = np.flatnonzero(out[i, :, 3] != 0.0)
        cut = pre[0] + 1 if len(pre) else n_placements
        picks = np.flatnonzero(out[i, :cut, 0] >= 0)
        own[i, len(drows) + picks, 0] = out[i, picks, 0]
        own[i, len(drows) + picks, 1:] = scans[i].sp.ask
    return out, np.concatenate([own[None], carry[:-1]])


def sharded_fused_place_batch(arrays, used, delta_rows, delta_vals,
                              tg_counts, spread_counts, penalties, reqs,
                              class_eligs, host_masks, lane_mask,
                              n_shards: int, n_placements: int,
                              live_counts: Optional[List[int]] = None,
                              overlay=None, chain=None):
    """Twin of parallel.sharding.sharded_fused_place_batch for host-only CI.

    The placement body's election across shards (``kernels.elect``: each
    shard's arg-max → the cluster's best score by pmax → the lowest row
    that holds it by pmin) provably reproduces the dense argmax
    row-for-row, and its owner-veto verify reproduces the sequential
    cross-lane AllocsFit scan (PARITY.md "The election") — so the
    bit-compatible numpy reference IS the dense twin, run after validating
    the shard partition the mesh would impose.
    """
    n = int(np.asarray(used).shape[0])
    if n_shards < 1 or n % n_shards:
        raise ValueError(
            f"node axis of {n} rows does not split into {n_shards} shards"
        )
    return fused_place_batch(
        arrays, used, delta_rows, delta_vals, tg_counts, spread_counts,
        penalties, reqs, class_eligs, host_masks, lane_mask,
        n_placements=n_placements, live_counts=live_counts, overlay=overlay,
        chain=chain,
    )


def system_feasible(arrays, used0, req: SchedRequest, class_elig,
                    host_mask) -> np.ndarray:
    """Twin of kernels.system_feasible — stacked (2, N) [mask, fits]."""
    mask = feasibility_mask(arrays, req, class_elig, host_mask)
    mask &= distinct_property_mask(
        arrays, req, distinct_property_counts(arrays, req)
    )
    fits, _, _ = fit_and_binpack(arrays, used0, req)
    return np.stack([mask, fits])


def verify_plan_fit(arrays, rows, deltas, eligible_required) -> np.ndarray:
    """Twin of kernels.verify_plan_fit — (K,) bool verdicts."""
    rows = np.asarray(rows)
    deltas = np.asarray(deltas, np.float32)
    eligible_required = np.asarray(eligible_required, bool)
    safe = np.maximum(rows, 0)
    used = arrays.used[safe] + deltas
    fits = np.all(used <= arrays.totals[safe], axis=1)
    ok = fits & (~eligible_required | arrays.eligible[safe])
    return np.where(rows < 0, True, ok)


def dense_used0(arrays, deltas) -> np.ndarray:
    """Numpy twin of stack._dense_used0 (proposed base usage)."""
    used0 = arrays.used
    if deltas:
        used0 = used0.copy()
        for row, d in deltas.items():
            used0[row] += d
    return used0
