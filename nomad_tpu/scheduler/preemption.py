"""Preemption on the host: the victims of one pick, and its exact score.

The kernel says which nodes could take the ask after evicting
lower-priority work, and ranks them by an estimate (``prio_used`` prefix
sums, ops/kernels.score_nodes).  This module does what needs the node's
allocations themselves, for the ONE node picked — the reference's
``Preemptor.PreemptForTaskGroup`` (scheduler/preemption.go:198-268):

* candidates have priority < job.priority − 10 (preemption.go:663),
  grouped by priority, lowest first;
* within a group the allocation closest to what is still needed
  (``basicResourceDistance``, :608) is taken, and what is needed shrinks by
  it, until node room + freed covers the whole ask;
* then the set is filtered (``filterSuperset``, :702): farthest from the
  ask first, stop as soon as the ask is covered.

The node's room is the caller's to state, and the caller states the
matrix's (aggregates included): the view the kernel scored and the applier
verifies.  Equal distances fall to the lower allocation id (the reference
takes list order).

``preempting_scores`` is the score such a pick RECORDS: Nomad's ScoreFit of
the utilisation after the victims are gone (rank.go BinPackIterator scores
``proposed`` less the allocations to preempt) and the logistic of the
victims' net priority (rank.go:773-844).
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple

from ..structs.funcs import (
    net_priority,
    preemption_score,
    score_fit_binpack,
    score_fit_spread,
)
from ..structs.types import (
    Allocation,
    Job,
    Node,
    PREEMPTION_PRIORITY_DELTA,
    Resources,
)


def _dims(r) -> Tuple[float, float, float]:
    return (float(r.cpu), float(r.memory_mb), float(r.disk_mb))


def resource_distance(needed: Sequence[float], used: Sequence[float]) -> float:
    """``basicResourceDistance``: how far an allocation's resources are
    from what is needed, per dimension relative to the need; a dimension
    nothing is needed of does not count."""
    return math.sqrt(sum(
        ((n - u) / n) ** 2 for n, u in zip(needed, used) if n > 0
    ))


def _covers(avail: Sequence[float], ask: Sequence[float]) -> bool:
    return all(a >= q for a, q in zip(avail, ask))


def select_victims(
    job: Job,
    proposed: List[Allocation],
    ask: Resources,
    room: Sequence[float],
) -> Optional[List[Allocation]]:
    """Allocations of ``proposed`` to evict so that ``ask`` fits into
    ``room`` (cpu, memory, disk the node has left) + what they free.
    [] when it fits as it is; None when no admissible set covers it."""
    want = _dims(ask)
    avail = [float(x) for x in room]
    if _covers(avail, want):
        return []

    threshold = job.priority - PREEMPTION_PRIORITY_DELTA
    groups: Dict[int, List[Allocation]] = {}
    for a in proposed:
        if not a.terminal_status() and a.job_priority() < threshold:
            groups.setdefault(a.job_priority(), []).append(a)

    needed = list(want)
    best: List[Allocation] = []
    met = False
    for prio in sorted(groups):
        group = sorted(groups[prio], key=lambda a: a.id)
        while group and not met:
            i = min(
                range(len(group)),
                key=lambda k: resource_distance(
                    needed, _dims(group[k].resources)
                ),
            )
            a = group.pop(i)
            best.append(a)
            res = _dims(a.resources)
            avail = [x + r for x, r in zip(avail, res)]
            needed = [n - r for n, r in zip(needed, res)]
            met = _covers(avail, want)
        if met:
            break
    if not met:
        return None

    # filterSuperset: farthest from the ask first, until it is covered.
    best.sort(key=lambda a: -resource_distance(want, _dims(a.resources)))
    avail = [float(x) for x in room]
    out: List[Allocation] = []
    for a in best:
        out.append(a)
        avail = [x + r for x, r in zip(avail, _dims(a.resources))]
        if _covers(avail, want):
            break
    return out


def preempting_scores(
    node: Node,
    used: Sequence[float],
    ask: Resources,
    victims: List[Allocation],
    spread: bool,
) -> Tuple[float, float]:
    """(binpack, preemption) as a preempting placement records them:
    ScoreFit (funcs.go:186/213, over 18) of ``used`` + ``ask`` less the
    victims on ``node``; the logistic of the victims' net priority.
    Plain float64: the reference's own arithmetic."""
    util = [u + q for u, q in zip(used, _dims(ask))]
    for v in victims:
        util = [u - r for u, r in zip(util, _dims(v.resources))]
    fit = score_fit_spread if spread else score_fit_binpack
    binpack = fit(node, Resources(cpu=util[0], memory_mb=util[1])) / 18.0
    pre = preemption_score(
        net_priority([v.job_priority() for v in victims])
    )
    return binpack, pre
