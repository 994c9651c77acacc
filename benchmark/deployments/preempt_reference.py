"""Nomad's preemption, written straight: the plain reference of
``c2m-10k-preempt``.

numpy and plain Python; shares no code with ``nomad_tpu`` and imports
nothing of it (``reference.py``, the benchmark's own, gives ScoreFit and the
reading of "room").  An allocation here is a plain dict: ``id``, ``node``
(row), ``job``, ``priority``, ``res`` = (cpu, memory_mb, disk_mb).

* ``select`` — the two passes of ``generic_sched.go:773-792``: rank the
  eligible nodes the ask fits on; only if there is none, rank the nodes
  it fits on after ``preempt_for_task_group``'s evictions.
* ``preempt_for_task_group`` — ``Preemptor.PreemptForTaskGroup`` on one
  node (preemption.go:198-268): candidates with priority < job priority
  - 10 (:663), lowest priority first, within a priority the allocation
  closest to what is still needed (``basicResourceDistance``, :608), until
  node room + freed covers the ask; then ``filterSuperset`` (:702).
* ``preempting_scores`` — what such a placement records: ScoreFit of the
  utilisation after the victims are gone (rank.go BinPackIterator scores
  ``proposed`` less the allocations to preempt), and the logistic of the
  victims' net priority (rank.go:773-844: max priority + sum / max).
* ``final_score`` — the mean of the terms that apply (rank.go:737-771).

Departures from the reference's text, each on purpose: equal distances fall
to the lower allocation id (Nomad takes list order, which its own swap
removal reorders); ``maxParallel``'s penalty is left out (batch jobs have
no migrate stanza, and this deployment's victims are batch jobs); network
and device preemption are not modelled (no job of the mix asks for either).
"""

from __future__ import annotations

import math

import numpy as np

import reference as ref

PRIORITY_DELTA = 10          # preemption.go:663
RATE, ORIGIN = 0.0048, 2048.0  # rank.go preemptionScore


def distance(needed, res) -> float:
    """``basicResourceDistance``: per dimension relative to the need; a
    dimension nothing is needed of does not count."""
    return math.sqrt(sum(
        ((n - r) / n) ** 2 for n, r in zip(needed, res) if n > 0))


def covers(avail, ask) -> bool:
    return all(a >= q for a, q in zip(avail, ask))


def evictable(job_priority: int, alloc_priority: int) -> bool:
    return alloc_priority < job_priority - PRIORITY_DELTA


def preempt_for_task_group(job_priority, ask, room, allocs):
    """The allocations of ``allocs`` (one node's) to evict so that ``ask``
    fits into ``room`` (what the node has left) + what they free: [] when
    it fits as it is, None when no admissible set covers it."""
    ask = [float(x) for x in ask]
    avail = [float(x) for x in room]
    if covers(avail, ask):
        return []
    groups = {}
    for a in allocs:
        if evictable(job_priority, a["priority"]):
            groups.setdefault(a["priority"], []).append(a)
    needed, best, met = list(ask), [], False
    for prio in sorted(groups):
        group = sorted(groups[prio], key=lambda a: a["id"])
        while group and not met:
            k = min(range(len(group)),
                    key=lambda i: distance(needed, group[i]["res"]))
            a = group.pop(k)
            best.append(a)
            avail = [x + r for x, r in zip(avail, a["res"])]
            needed = [n - r for n, r in zip(needed, a["res"])]
            met = covers(avail, ask)
        if met:
            break
    if not met:
        return None
    # filterSuperset: farthest from the ask first, until it is covered.
    best.sort(key=lambda a: -distance(ask, a["res"]))
    avail, out = [float(x) for x in room], []
    for a in best:
        out.append(a)
        avail = [x + r for x, r in zip(avail, a["res"])]
        if covers(avail, ask):
            break
    return out


def net_priority(priorities) -> float:
    mx = float(max(priorities))
    return mx + sum(priorities) / mx


def preemption_score(priorities, dtype=np.float64):
    """The logistic of the victims' net priority."""
    net = dtype(net_priority(priorities))
    return dtype(1.0) / (dtype(1.0) + np.exp(dtype(RATE) * (net - dtype(ORIGIN))))


def preempting_scores(used, ask, totals, victims, dtype=np.float64):
    """(binpack, preemption) a preempting placement records: ScoreFit of
    ``used`` + ``ask`` less the victims; the logistic of their net
    priority.  ``used`` (..., 3) may hold several states of the node."""
    used = np.asarray(used, np.float64)
    for v in victims:
        used = used - np.asarray(v["res"], np.float64)
    binpack = ref.binpack_score(used, ask, totals, dtype)
    return binpack, preemption_score([v["priority"] for v in victims], dtype)


def final_score(binpack, preemption, collisions, desired_count, affinity,
                dtype=np.float64):
    """Mean of the terms that apply: binpack always; the preemption term
    where something is evicted (``preemption`` None otherwise); job
    anti-affinity where the job has instances on the node; node affinity
    where something matched."""
    b = np.asarray(binpack, dtype)
    c = np.asarray(collisions, dtype)
    aff = np.asarray(affinity, dtype)
    aa = np.where(c > 0, -(c + dtype(1)) / dtype(desired_count), dtype(0))
    n = dtype(1) + (c > 0).astype(dtype) + (aff != 0).astype(dtype)
    total = b + aa.astype(dtype) + aff
    if preemption is not None:
        total = total + np.asarray(preemption, dtype)
        n = n + dtype(1)
    return (total / n).astype(dtype)


def select(job_priority, ask, count, used, totals, eligible, affinity,
           collisions, allocs_by_node):
    """One placement on a cluster: (row, victims, scores) or None.

    ``used`` (N, 3) every node's usage, whatever stands behind it;
    ``allocs_by_node`` row -> the allocations there that have an object
    behind them (only those can be evicted).  First pass: the eligible
    nodes the ask fits on, by score.  Second pass, only if the first found
    none: the eligible nodes it fits on after eviction."""
    used = np.asarray(used, np.float64)
    ask = np.asarray(ask, np.float64)
    totals = np.asarray(totals, np.float64)
    eligible = np.asarray(eligible, bool)
    fits = eligible & (used + ask <= totals).all(axis=1)
    aff = np.broadcast_to(np.asarray(affinity, np.float64), eligible.shape)
    col = np.broadcast_to(np.asarray(collisions), eligible.shape)
    if fits.any():
        b = ref.binpack_score(used, ask, totals)
        f = np.where(fits, final_score(b, None, col, count, aff), -np.inf)
        row = int(np.argmax(f))
        return row, [], {"binpack": float(b[row]), "final": float(f[row])}
    best = None
    for row in np.nonzero(eligible)[0]:
        row = int(row)
        victims = preempt_for_task_group(
            job_priority, ask, totals - used[row],
            allocs_by_node.get(row, []))
        if not victims:
            continue
        b, p = preempting_scores(used[row], ask, totals, victims)
        f = float(final_score(b, p, col[row], count, aff[row]))
        if best is None or f > best[2]["final"]:
            best = (row, victims, {
                "binpack": float(b), "preemption": float(p), "final": f})
    return best
