"""How ``correct`` is decided for ``c2m-10k-preempt``: the HTTP read-back
replayed IN COMMIT ORDER against the plain reference (preempt_reference.py).

Usage is not monotone here (an eviction frees what a placement took), so
``check.py``'s "had room at the end" argument is not reused for a
preempting pick: everything is replayed by the allocations' and evals' own
indexes.  The installed tier comes from ``state`` (what the set-up
returned), the window's placements and every eviction (``desired_status``
evict, "Preempted by alloc ID <id>") from the read-back; one index is one
commit (a plan's evictions and placements together).

Numbers, each printed beside its limit (``LIMITS``), and why the limit:

* ``nodes_wrong``, ``constraint_violations``: as ``check.py``; 0, exact.
* ``count_mismatch`` 0: every placed operation's job reached exactly its
  asked count of live allocations at some commit and never more (a job
  whose allocation a later, higher job justly evicted counts as placed).
  An allocation that was JUSTLY evicted before the last commit of the
  job's own (non-``preemption``) evals counts as live at that commit: an
  eval whose plan committed in part can lose one of those allocations to a
  higher job's plan while its next attempt, from a snapshot that still
  held it, commits the rest and ends ``complete``; the follow-up
  ``preemption`` eval repairs the count, on a full cluster perhaps after
  the cut (PERF.md section 6, PR 38).  The check cannot tell that race
  from an eval that miscounted by exactly its justly evicted allocations.
* ``overcommitted_nodes`` 0: seeded usage + placements - evictions <= the
  node's totals after EVERY commit, beyond what float32 sums can decide.
* ``evicted_unjustly`` 0: every eviction names a preemptor that exists, on
  the same node, committed in the same index, of a job whose priority
  exceeds the victim's by more than 10 (preemption.go:663).
* ``evicted_with_room`` 0: over the sampled preempting decisions, no other
  eligible node had room for the ask (and for this plan's other
  allocations on it), beyond what float32 sums can decide
  (``reference.has_room``), in EVERY state from the eval's creation to the
  plan's commit: only then was a node passed over for certain under
  optimistic workers.  (The in-launch resolution never makes a lane
  preempt: a lane whose fitting nodes are all claimed keeps its own pick.)
* ``evictions_without_followup`` 0: every evicted allocation's job has an
  eval triggered by ``preemption`` created in the eviction's own index.
* ``tier_lost`` 0: an installed allocation is live, or justly evicted.
* ``score_gap`` <= 3e-5 (``check.py``'s limit and reasons): the recorded
  binpack / final of the sampled decisions against the reference for every
  state the node can have shown between the eval's creation and the
  commit; for a preempting decision the recorded binpack, preemption and
  final against the reference's for the victims the plan named (ScoreFit
  after they are gone, logistic of their net priority).
* ``rank_gap`` <= 1e-5 (``check.py``'s limit): for a sampled placement
  WITHOUT eviction, the lowest score any other eligible node had in any
  state from the eval's creation to the END of the run, if it had room in
  all of them, less the recorded score.  To the end, not to the commit:
  the in-launch resolution passes over a node lanes of the same launch
  claimed, and such a claim shows in the usage only when its plan commits.

``dump`` is written in ``check.py``'s form (``control.py`` reads
``samples``): a preempting decision is there with the victims taken off
its states and compared as ``check.py`` compares a spread job's (binpack
only: its final has a term ``check.py`` does not know); this module
compares its preemption term and its final itself.
"""

from __future__ import annotations

import bisect
import itertools
import json
import random
import re
import time

import numpy as np

import check
import preempt_reference as pref
import reference as ref

LIMITS = {
    "nodes_wrong": 0,
    "count_mismatch": 0,
    "overcommitted_nodes": 0,
    "constraint_violations": 0,
    "evicted_unjustly": 0,
    "evicted_with_room": 0,
    "evictions_without_followup": 0,
    "tier_lost": 0,
    "score_gap": check.LIMITS["score_gap"],
    "rank_gap": check.LIMITS["rank_gap"],
}
QUIET_WAIT_S = 5.0      # the evicted tier's evals run on after the drain
READ_TRIES = 4          # a list read can fail while they commit
MAX_GROUP_SUBSETS = 6   # events of one commit on one node enumerated whole
PREEMPTED_BY = re.compile(r"Preempted by alloc ID (\S+)")
TERMINAL_CLIENT = ("complete", "failed", "lost")


def res_of(a):
    r = a["resources"]
    return np.array([r["cpu"], r["memory_mb"], r["disk_mb"]], np.float64)


def terminal(a) -> bool:
    return (a["desired_status"] in ("stop", "evict")
            or a["client_status"] in TERMINAL_CLIENT)


def freed_at(a) -> int:
    """The index at which a terminal allocation left its node (a later
    stamp on it, ``next_allocation``, moves ``modify_index`` alone)."""
    return int(a.get("alloc_modify_index") or a["modify_index"])


def wait_quiet(get, limit_s=QUIET_WAIT_S) -> float:
    """Until the broker and the plan queue are empty (the evals of evicted
    jobs run on after the window's drain), or ``limit_s``."""
    t0 = time.time()
    while time.time() - t0 < limit_s:
        m = get("/v1/metrics")
        if not any(m.get(k, 0) for k in (
                "nomad.broker.total_ready", "nomad.broker.total_unacked",
                "nomad.plan.queue_depth")):
            break
        time.sleep(0.25)
    return time.time() - t0


def patient(get):
    """``get``, tried again where the server failed a read (it is still
    committing the evicted tier's evals while the check reads)."""
    def tried(path):
        for k in range(READ_TRIES):
            try:
                return get(path)
            except OSError:  # urllib's HTTPError and URLError are OSErrors
                if k == READ_TRIES - 1:
                    raise
                time.sleep(0.5 * (k + 1))
    return tried


def read_back(get, namespaces):
    """Evals and allocations of every namespace, cut at one index: what is
    committed after ``cut`` (the newest index the first pass over the evals
    saw; every later read holds all of it) is left out of the replay."""
    first = [e for ns in namespaces for e in get(f"/v1/evaluations?namespace={ns}")]
    cut = max((int(e["modify_index"]) for e in first), default=0)
    allocs = [a for ns in namespaces for a in get(f"/v1/allocations?namespace={ns}")]
    evals = [e for ns in namespaces for e in get(f"/v1/evaluations?namespace={ns}")]
    return allocs, evals, cut


class Replay:
    """Usage of every node after every commit, from the events' indexes."""

    def __init__(self, used0, totals, row_of, allocs, cut):
        self.totals = totals
        self.used = used0.astype(np.float64).copy()
        self.row_of = row_of
        events = []  # (index, 0 free | 1 place, alloc)
        for a in allocs:
            if a["node_id"] not in row_of or int(a["create_index"]) > cut:
                continue
            events.append((int(a["create_index"]), 1, a))
            if terminal(a) and freed_at(a) <= cut:
                events.append((freed_at(a), 0, a))
        events.sort(key=lambda e: (e[0], e[1], e[2]["id"]))
        self.events = events
        self.indexes = [e[0] for e in events]
        # per node: commits that touched it, (index, frees, places)
        self.groups = {}
        self.over = set()

    def run(self, snapshot_at):
        """Replay; ``snapshot_at``: sorted indexes i at which a copy of the
        usage after every commit <= i is wanted.  Returns {i: used}."""
        snaps, want, k = {}, list(snapshot_at), 0
        slack = ref.fit_slack(self.totals)
        for index, group in itertools.groupby(self.events, key=lambda e: e[0]):
            while k < len(want) and want[k] < index:
                snaps[want[k]] = self.used.copy()
                k += 1
            touched = {}
            for _, kind, a in group:
                row = self.row_of[a["node_id"]]
                g = touched.setdefault(row, ([], []))
                g[kind].append(res_of(a))
                self.used[row] += res_of(a) if kind else -res_of(a)
            for row, (frees, places) in touched.items():
                self.groups.setdefault(row, []).append((index, frees, places))
                if (self.used[row] > self.totals + slack).any():
                    self.over.add(row)
        while k < len(want):
            snaps[want[k]] = self.used.copy()
            k += 1
        return snaps

    def touched_between(self, i0, i1):
        """Rows a commit with i0 < index < i1 touched."""
        lo = bisect.bisect_right(self.indexes, i0)
        hi = bisect.bisect_left(self.indexes, i1)
        return {self.row_of[e[2]["node_id"]] for e in self.events[lo:hi]}

    def states(self, row, base, i0, i1):
        """Every usage the node can have shown to a launch between the
        commits i0 (whole) and i1 (not begun): ``base`` is its usage after
        every commit <= i0.  A commit is applied allocation by allocation,
        evictions first, so a launch can see it half applied."""
        out, cur = [base.copy()], base.copy()
        for index, frees, places in self.groups.get(row, ()):
            if index <= i0:
                continue
            if index >= i1:
                break
            for part, sign, done in ((frees, -1.0, 0.0), (places, 1.0, 1.0)):
                start = cur - done * sum(frees, np.zeros(3))
                if len(part) <= MAX_GROUP_SUBSETS:
                    subsets = itertools.chain.from_iterable(
                        itertools.combinations(part, n)
                        for n in range(1, len(part) + 1))
                else:  # too many to enumerate: in the order read
                    subsets = (part[:n] for n in range(1, len(part) + 1))
                out.extend(start + sign * sum(s, np.zeros(3)) for s in subsets)
            cur = cur - sum(frees, np.zeros(3)) + sum(places, np.zeros(3))
        return out


def _rel(ref_value, recorded):
    ref_value = np.asarray(ref_value, np.float64)
    return np.abs(ref_value - recorded) / np.maximum(np.abs(ref_value), 0.05)


def preempting_gap(s) -> float:
    """Widest relative gap of a preempting decision's recorded binpack,
    preemption and final to the reference's, each against the state of
    the node it matches best."""
    if None in (s["binpack"], s["preemption"], s["final"]):
        return float("inf")
    prios = [p for p, _ in s["victims"]]
    b = ref.binpack_score(np.array(s["candidates"]), s["ask"], s["totals"])
    p = float(pref.preemption_score(prios))
    gap = max(float(_rel(b, s["binpack"]).min()),
              float(_rel(p, s["preemption"])))
    if not s["spread_shape"]:
        cols = np.arange(s["collisions_max"] + 1)
        f = pref.final_score(b[:, None], p, cols[None, :], s["count"],
                             s["affinity"])
        gap = max(gap, float(_rel(f, s["final"]).min()))
    return gap


def decide(get, cfg, traffic, records, used0, seed, dump=None, state=None):
    cluster, n = cfg["cluster"], cfg["nodes"]
    row_of = {check.node_id(i): i for i in range(n)}
    totals = ref.node_totals(cluster)
    tables = ref.attr_tables(n, cluster)
    state = state or {}
    tier_ns = state.get("namespace")
    get = patient(get)
    numbers = {"nodes_wrong": check.check_nodes(get, n, cluster, seed)}

    waited = wait_quiet(get)
    namespaces = ["default"] + [
        f"tenant-{i}" for i in range(1, traffic["tenants"])]
    if tier_ns:
        namespaces.append(tier_ns)
    allocs, evals, cut = read_back(get, namespaces)
    by_id = {a["id"]: a for a in allocs}
    numbers["nodes_wrong"] += sum(a["node_id"] not in row_of for a in allocs)
    eval_by_id = {e["id"]: e for e in evals}
    priority = {(e["namespace"], e["job_id"]): int(e["priority"])
                for e in evals}
    followups = {(e["namespace"], e["job_id"], int(e["create_index"]))
                 for e in evals if e["triggered_by"] == "preemption"}

    def prio_of(a):
        if a["namespace"] == tier_ns:
            return int(state["priority"])
        return priority.get((a["namespace"], a["job_id"]))

    # -- the evictions, one by one ---------------------------------------------
    notes = []
    evicted = [a for a in allocs if a["desired_status"] == "evict"
               and freed_at(a) <= cut]
    unjust, no_followup, just = 0, 0, set()
    victims_of = {}  # preemptor id -> its victims
    for v in evicted:
        m = PREEMPTED_BY.match(v.get("desired_description") or "")
        by = by_id.get(m.group(1)) if m else None
        pv, pb = prio_of(v), prio_of(by) if by else None
        ok = (by is not None and by["node_id"] == v["node_id"]
              and int(by["create_index"]) == freed_at(v)
              and pv is not None and pb is not None
              and pref.evictable(pb, pv))
        if ok:
            just.add(v["id"])
            victims_of.setdefault(by["id"], []).append(v)
        else:
            unjust += 1
            notes.append(
                f"evicted_unjustly: {v['id']} (priority {pv}) on "
                f"{v['node_id']} at {freed_at(v)}: "
                f"{v.get('desired_description')!r}; preemptor "
                + (f"priority {pb} on {by['node_id']} at {by['create_index']}"
                   if by else "not found"))
        if (v["namespace"], v["job_id"], freed_at(v)) not in followups:
            no_followup += 1
            notes.append(f"evictions_without_followup: {v['id']} of "
                         f"{v['job_id']} at {freed_at(v)}")
    numbers["evicted_unjustly"] = unjust
    numbers["evictions_without_followup"] = no_followup

    lost = 0
    for aid in state.get("ids", []):
        a = by_id.get(aid)
        if a is None or (
                terminal(a) and freed_at(a) <= cut and aid not in just):
            lost += 1
            notes.append(f"tier_lost: {aid}: " + (
                "not read back" if a is None else
                f"{a['desired_status']}/{a['client_status']} "
                f"{a.get('desired_description')!r}"))
    numbers["tier_lost"] = lost

    # -- the window's jobs: counts and constraints --------------------------------
    by_job = {}
    for a in allocs:
        if a["node_id"] in row_of and int(a["create_index"]) <= cut:
            by_job.setdefault(a["job_id"], []).append(a)
    mismatch = violations = 0
    for r in records:
        mine = by_job.get(r["job_id"], [])
        if r["status"] == "placed":
            steps = sorted(
                [(int(a["create_index"]), 1) for a in mine]
                + [(freed_at(a), -1) for a in mine
                   if terminal(a) and freed_at(a) <= cut])
            # The last commit of the job's own evals (a follow-up
            # ``preemption`` eval is not the operation's).
            last_own = max(
                (int(a["create_index"]) for a in mine
                 if (eval_by_id.get(a.get("eval_id")) or {}).get(
                     "triggered_by") != "preemption"), default=0)
            live = peak = at_last_own = 0
            for index, group in itertools.groupby(steps, key=lambda s: s[0]):
                live += sum(d for _, d in group)
                peak = max(peak, live)
                if index <= last_own:
                    at_last_own = live
            credited = at_last_own + sum(
                a["id"] in just and freed_at(a) <= last_own for a in mine)
            if (peak > r["width"] or r["width"] not in (peak, credited)
                    or any(a["task_group"] != "g" for a in mine)):
                mismatch += 1
                notes.append(
                    f"count_mismatch: {r['job_id']} asked {r['width']}, at "
                    f"most {peak} live at once of {len(mine)} allocations "
                    f"({credited} at its last commit, {last_own}, with the "
                    f"justly evicted); registered {r.get('registers')} times")
        if mine:
            shape = traffic["shapes"][r["shape"]]
            elig = ref.eligible(tables, shape["datacenters"],
                                shape["constraints"])
            violations += sum(not elig[row_of[a["node_id"]]] for a in mine)
    numbers["count_mismatch"] = mismatch
    numbers["constraint_violations"] = violations

    # -- the sampled decisions ---------------------------------------------------------
    placed = [r for r in records if r["status"] == "placed"]
    chosen = []
    if placed:
        rng = random.Random(f"{seed}:sample")
        widest = max(placed, key=lambda r: (r["width"], -r["i"]))
        rest = [r for r in placed if r is not widest]
        chosen = [widest] + rng.sample(
            rest, min(check.SAMPLE_OPS - 1, len(rest)))
    decisions = []
    for r in chosen:
        for a in by_job.get(r["job_id"], []):
            ev = eval_by_id.get(a.get("eval_id"))
            i0 = int(ev["create_index"]) if ev else 0
            decisions.append((r, a, min(i0, int(a["create_index"]) - 1)))

    replay = Replay(used0, totals, row_of, allocs, cut)
    snaps = replay.run(sorted({i0 for _, _, i0 in decisions}))
    used_end = replay.used
    numbers["overcommitted_nodes"] = len(replay.over)

    samples, preempting, with_room = [], [], 0
    for r, a, i0 in decisions:
        shape = traffic["shapes"][r["shape"]]
        elig = ref.eligible(tables, shape["datacenters"], shape["constraints"])
        aff = ref.affinity_term(tables, shape["affinities"])
        row, i1 = row_of[a["node_id"]], int(a["create_index"])
        ask = res_of(a)
        snap = snaps[i0]
        mates = [b for b in by_job[r["job_id"]]
                 if int(b["create_index"]) == i1 and b["id"] != a["id"]]
        siblings = [b for b in mates if b["node_id"] == a["node_id"]]
        victims = victims_of.get(a["id"], [])
        # States of the node before this placement: what the cluster
        # showed, plus any of this plan's other placements on it (each
        # with its own victims gone).
        shown = replay.states(row, snap[row], i0, i1)
        deltas = [res_of(b) - sum((res_of(v) for v in victims_of.get(
            b["id"], [])), np.zeros(3)) for b in siblings]
        plans = [sum(c, np.zeros(3)) for n_ in range(len(deltas) + 1)
                 for c in itertools.combinations(deltas, n_)]
        before = sum(
            1 for b in by_job[r["job_id"]]
            if b["node_id"] == a["node_id"] and int(b["create_index"]) < i1)
        scores = (a.get("metrics") or {}).get("scores", {}).get(
            a["node_id"], {})
        sample = {
            "job_id": a["job_id"], "alloc": a["id"], "row": row,
            "ask": ask.tolist(), "totals": totals.tolist(),
            "count": r["width"], "siblings": 0,
            "collisions_max": len(siblings) + before,
            "affinity": float(aff[row]),
            "binpack": scores.get("binpack"), "final": scores.get("final"),
            "floor": None, "floor_loose": None, "interval": [i0, i1],
        }
        gone = sum((res_of(v) for v in victims), np.zeros(3))
        sample["candidates"] = [
            (s + p - gone).tolist() for s in shown for p in plans]
        own_rows = {row_of[b["node_id"]] for b in by_job[r["job_id"]]}
        others = elig.copy()
        others[list(own_rows)] = False
        if victims:
            # An eligible node with room in every state from the eval's
            # creation to this commit, for the ask and for whatever else
            # of this plan went onto it.
            need = {}
            for b in mates:
                need[row_of[b["node_id"]]] = need.get(
                    row_of[b["node_id"]], 0) + 1
            room = others & ref.has_room(snap, ask, totals)
            moved = replay.touched_between(i0, i1)
            for x in np.nonzero(room)[0]:
                x = int(x)
                want = ask * (1 + need.get(x, 0))
                if x in moved or x in need:
                    st = np.array(replay.states(x, snap[x], i0, i1))
                    room[x] = bool(ref.has_room(st, want, totals).all())
            if room.any():
                with_room += 1
                x = int(np.nonzero(room)[0][0])
                notes.append(
                    f"evicted_with_room: {a['id']} of {a['job_id']} evicted "
                    f"{len(victims)} on row {row} at {i1} (eval at {i0}) "
                    f"while row {x} had {(totals - snap[x]).tolist()} left "
                    f"for {ask.tolist()}")
            sample.update({
                "spread": True, "preempting": True,
                "spread_shape": bool(shape["spreads"]),
                "preemption": scores.get("preemption"),
                "victims": [[prio_of(v), res_of(v).tolist()]
                            for v in victims],
            })
            preempting.append(sample)
        else:
            sample["spread"] = bool(shape["spreads"])
            room = others & ref.has_room(used_end, ask, totals)
            if not sample["spread"] and room.any():
                floor = float("-inf")
                for x in np.nonzero(room)[0]:
                    x = int(x)
                    st = np.array(replay.states(
                        x, snap[x], i0, float("inf")))
                    if not ref.has_room(st, ask, totals).all():
                        continue
                    b = ref.binpack_score(st, ask, totals)
                    floor = max(floor, float(ref.final_score(
                        b, 0, r["width"], aff[x]).min()))
                if floor > float("-inf"):
                    sample["floor"] = floor
        samples.append(sample)

    numbers["evicted_with_room"] = with_room
    score_gap, rank_gap = check.score_gaps(samples)
    for s in preempting:
        score_gap = max(score_gap, preempting_gap(s))
    numbers["score_gap"], numbers["rank_gap"] = score_gap, rank_gap
    if dump:
        with open(dump, "w") as fh:
            json.dump({"seed": seed, "numbers": numbers,
                       "samples": samples}, fh)

    lines = [f"check: {k} = {numbers[k]:.6g} (limit {LIMITS[k]:g})"
             for k in LIMITS]
    installed = set(state.get("ids", []))
    replaced = sum(a["namespace"] == tier_ns and a["id"] not in installed
                   for a in allocs)
    lines.append(
        f"check: replayed {len(replay.events)} events to index {cut} "
        f"(quiet after {waited:.1f}s): {len(evicted)} evictions "
        f"({sum(a['namespace'] == tier_ns for a in evicted)} of the "
        f"installed tier's {len(state.get('ids', []))}), {replaced} "
        f"allocations of the tier placed again, "
        f"{sum(e['triggered_by'] == 'preemption' for e in evals)} "
        f"preemption evals")
    by_status = {}
    for e in evals:
        key = f"{e['triggered_by']}/{e['status']}"
        by_status[key] = by_status.get(key, 0) + 1
    lines.append(
        f"check: the workers processed "
        f"{get('/v1/metrics').get('nomad.worker.evals_processed')} evals "
        f"since boot (warm-up and drain included); evals read back by "
        f"trigger and status: {dict(sorted(by_status.items()))}")
    lines.append(
        f"check: compared {len(placed)} operations, {len(samples)} sampled "
        f"placement decisions, {len(preempting)} of them preempting; "
        f"{int(ref.has_room(used_end, [100, 128, 300], totals).sum())} "
        f"nodes end with room for the smallest ask")
    correct = (bool(samples)
               and all(numbers[k] <= LIMITS[k] for k in LIMITS))
    if not correct:
        if len(replay.over):
            notes.append("overcommitted_nodes: rows " + ", ".join(
                f"{int(x)} ends at {used_end[x].tolist()} of "
                f"{totals.tolist()}" for x in sorted(replay.over)[:4]))
        if samples and not numbers["score_gap"] <= LIMITS["score_gap"]:
            worst = max(samples, key=lambda s: (
                preempting_gap(s) if s.get("preempting")
                else check._sample_gaps(s)[0]))
            notes.append("score_gap: worst sample " + str({
                k: worst.get(k) for k in (
                    "job_id", "alloc", "row", "ask", "count",
                    "collisions_max", "affinity", "binpack", "preemption",
                    "final", "victims", "interval")}
            ) + f", {len(worst['candidates'])} states, the first "
                f"{worst['candidates'][:3]}")
        lines.extend(f"check: over its limit: {n_}" for n_ in notes[:12])
    return correct, numbers, lines
