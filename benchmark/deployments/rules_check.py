"""How ``correct`` is decided for ``c2m-10k-rules``: the read-back against
``rules_reference``.

``check.py``'s numbers, with eligibility by ``rules_reference`` (every
operator of the jobspec, not ``=`` / ``!=`` alone), plus, exact and over
EVERY placed job of the run (from the read-back, nothing sampled):

* ``distinct_hosts_violations`` — live allocations of a group with
  ``distinct_hosts`` beyond the first on a node;
* ``distinct_property_violations`` — live allocations of a job beyond
  ``limit`` on one value of a ``distinct_property`` attribute, or on a node
  without it;
* ``constraint_violations`` — live allocations on a node where a constraint
  operator (``=``, ``!=``, ``is_set``, ``version``, ``regexp``,
  ``set_contains``) or the job's datacenters do not hold.

``score_gap`` (3e-5) is read over the sampled decisions as ``check.py``
reads it, and for the shapes with a spread also over the recorded mean: the
reference's mean of ScoreFit, job anti-affinity, affinity (negative weights
too) and the allocation-spread term (``percent`` targets, the implicit
target, even spread) for every state the job's own earlier allocations
allow: any subset of its other live allocations may have been placed
before this one (``check.py`` does the same with the node's usage
prefixes).  ``rank_gap`` is read for the shapes without a spread; the nodes
a ``distinct_property`` may have closed (a value the job's other
allocations hold ``limit`` times) are not ones it passed over.

A run in which no job wider than one of a ``distinct_property`` shape was
placed compared nothing of what this deployment is for, and is not correct.

The dump (``--check-dump``) has ``check.py``'s form, with the spread states
beside each sample, so ``control.py`` reads it as it reads any.
"""

from __future__ import annotations

import itertools
import json
import random

import numpy as np

import check
import reference as ref
import rules_reference as rules

LIMITS = {
    "nodes_wrong": 0,
    "count_mismatch": 0,
    "overcommitted_nodes": 0,
    "constraint_violations": 0,
    "distinct_hosts_violations": 0,
    "distinct_property_violations": 0,
    "score_gap": 3e-5,
    "rank_gap": 1e-5,
}
MAX_STATES = 256  # subsets of a job's other allocations (2**7 at width 8)


def operands(shape):
    return [c["operand"] for c in shape["constraints"]]


def check_rule_attributes(get, n_nodes, cluster, seed) -> int:
    """A seeded sample of whole nodes against the rule attributes the
    configuration states (``check.check_nodes`` holds the rest)."""
    wrong = 0
    rng = random.Random(f"{seed}:rule-nodes")
    for i in rng.sample(range(n_nodes), min(check.NODE_SAMPLE, n_nodes)):
        node = get(f"/v1/node/{check.node_id(i)}")
        same = True
        for name, value in rules.expected_attributes(i, cluster).items():
            kind, key = name.split(".", 1)
            have = node.get("meta" if kind == "meta" else "attributes") or {}
            same &= have.get(key) == value
        wrong += not same
    return wrong


def spread_states(shape, tables, row, other_rows):
    """(collisions, spread boost) of node ``row`` for every state the
    job's other allocations (on ``other_rows``) allow: any subset of them
    placed first.  Float64."""
    spreads = shape["spreads"]
    cols = [rules.column(tables, s["attribute"]) for s in spreads]
    values = [str(c[row]) for c in cols]
    seen, states = set(), []
    subsets = itertools.chain.from_iterable(
        itertools.combinations(other_rows, k)
        for k in range(len(other_rows) + 1))
    for subset in itertools.islice(subsets, MAX_STATES):
        held = []
        for c in cols:
            use = {}
            for r in subset:
                use[str(c[r])] = use.get(str(c[r]), 0) + 1
            held.append(use)
        key = (subset.count(row),
               tuple(tuple(sorted(h.items())) for h in held))
        if key in seen:
            continue
        seen.add(key)
        states.append(key[:1] + (held,))
    return values, states


def build_samples(records, by_job, by_node, used0, row_of, traffic, tables,
                  totals, used_start, used_end, seed, eligible_of, aff_of):
    """``check.build_samples`` with eligibility, affinity and the spread
    states by ``rules_reference``: the same keys, plus ``spread_final``
    (the reference's spread boosts and collisions, one per state)."""
    placed = [r for r in records if r["status"] == "placed"]
    if not placed:
        return []
    rng = random.Random(f"{seed}:sample")
    widest = max(placed, key=lambda r: (r["width"], -r["i"]))
    rest = [r for r in placed if r is not widest]
    chosen = [widest] + rng.sample(rest, min(check.SAMPLE_OPS - 1, len(rest)))
    samples = []
    for r in chosen:
        shape = traffic["shapes"][r["shape"]]
        elig, aff = eligible_of[r["shape"]], aff_of[r["shape"]]
        mine = by_job[r["job_id"]]
        own_rows = [row_of[a["node_id"]] for a in mine]
        for k, a in enumerate(mine):
            res = a["resources"]
            ask = [res["cpu"], res["memory_mb"], res["disk_mb"]]
            row = row_of[a["node_id"]]
            on_node = by_node[a["node_id"]]
            siblings = sum(
                1 for b in on_node
                if b["create_index"] == a["create_index"]
                and b["job_id"] == a["job_id"]
            ) - 1
            earlier = sorted(
                (b for b in on_node if b["create_index"] < a["create_index"]),
                key=lambda b: (b["create_index"], b["id"]))
            base = used0[row].astype(np.float64)
            prefixes = [base.copy()]
            for b in earlier:
                br = b["resources"]
                base = base + (br["cpu"], br["memory_mb"], br["disk_mb"])
                prefixes.append(base.copy())
            same_job_before = sum(b["job_id"] == a["job_id"] for b in earlier)
            scores = a["metrics"]["scores"].get(a["node_id"], {})
            other_rows = own_rows[:k] + own_rows[k + 1:]
            others = elig.copy()
            others[own_rows] = False
            others &= ~rules.blocked_by_distinct_property(
                tables, shape["constraints"], other_rows)
            floors = []
            for sure in (True, False):
                room = others & ref.has_room(used_end, ask, totals, sure)
                if shape["spreads"] or not room.any():
                    floors.append(None)
                    continue
                b_start = ref.binpack_score(used_start[room], ask, totals)
                floors.append(float(ref.final_score(
                    b_start, 0, r["width"], aff[room]).max()))
            sample = {
                "job_id": a["job_id"], "alloc": a["id"], "row": row,
                "shape": shape["name"],
                "ask": ask, "totals": totals.tolist(),
                "count": r["width"],
                "candidates": [p.tolist() for p in prefixes],
                "siblings": siblings,
                "collisions_max": siblings + same_job_before,
                "affinity": float(aff[row]),
                "spread": bool(shape["spreads"]),
                "binpack": scores.get("binpack"),
                "final": scores.get("final"),
                "floor": floors[0], "floor_loose": floors[1],
            }
            if shape["spreads"]:
                values, states = spread_states(shape, tables, row, other_rows)
                sample["spread_final"] = [
                    [c, float(rules.spread_boost(
                        shape["spreads"], r["width"], values, held))]
                    for c, held in states]
            samples.append(sample)
    return samples


def spread_gap(s) -> float:
    """Gap of the recorded mean of a sample with a spread to the nearest of
    the reference's means over the node's usage states x the job's own
    states (``spread_final``)."""
    if s["final"] is None:
        return float("inf")
    b64, _ = check._candidates(s, np.float64)
    coll = np.array([c for c, _ in s["spread_final"]], np.float64)
    boost = np.array([b for _, b in s["spread_final"]], np.float64)
    f64 = rules.final_score(
        b64[:, None], coll[None, :], s["count"], s["affinity"], boost[None, :])
    return float(np.min(
        np.abs(f64 - s["final"]) / np.maximum(np.abs(f64), 0.05)))


def score_gaps(samples):
    """``check.score_gaps`` and, for the samples with a spread, the gap of
    the recorded mean too."""
    score_gap, rank_gap = check.score_gaps(samples)
    for s in samples:
        if s["spread"]:
            score_gap = max(score_gap, spread_gap(s))
    return score_gap, rank_gap


def gap_notes(samples, numbers, limits):
    """What to print where ``score_gap`` or ``rank_gap`` is over its limit:
    the worst sample of each (``net_check`` prints the same)."""
    notes = []
    if samples and not numbers["score_gap"] <= limits["score_gap"]:
        def gap(s):
            g = check._sample_gaps(s)[0]
            return max(g, spread_gap(s)) if s["spread"] else g
        worst = max(samples, key=gap)
        notes.append("score_gap: worst sample " + str({
            k: worst.get(k) for k in (
                "job_id", "shape", "alloc", "row", "ask", "count",
                "siblings", "collisions_max", "affinity", "binpack",
                "final", "spread_final")}))
    if samples and not numbers["rank_gap"] <= limits["rank_gap"]:
        worst = max(samples, key=lambda s: (
            s["floor"] - s["final"] if s["floor"] is not None
            and s["final"] is not None and not s["spread"]
            else float("-inf")))
        notes.append("rank_gap: worst sample " + str({
            k: worst.get(k) for k in (
                "job_id", "shape", "alloc", "row", "count", "binpack",
                "final", "floor")}))
    return notes


def decide(get, cfg, traffic, records, used0, seed, dump=None, state=None):
    cluster, n = cfg["cluster"], cfg["nodes"]
    row_of = {check.node_id(i): i for i in range(n)}
    totals = ref.node_totals(cluster)
    tables = rules.attr_tables(n, cluster)
    shapes = traffic["shapes"]
    eligible_of = [
        rules.eligible(tables, s["datacenters"], s["constraints"])
        for s in shapes]
    aff_of = [rules.affinity_term(tables, s["affinities"]) for s in shapes]
    numbers = {"nodes_wrong": check.check_nodes(get, n, cluster, seed)
               + check_rule_attributes(get, n, cluster, seed)}

    allocs = []
    for ns in ["default"] + [f"tenant-{i}" for i in range(1, traffic["tenants"])]:
        allocs.extend(get(f"/v1/allocations?namespace={ns}"))
    live = [a for a in allocs if a["desired_status"] == "run"]
    unknown = [a for a in live if a["node_id"] not in row_of]
    numbers["nodes_wrong"] += len(unknown)
    live = [a for a in live if a["node_id"] in row_of]
    by_job, by_node = {}, {}
    for a in live:
        by_job.setdefault(a["job_id"], []).append(a)
        by_node.setdefault(a["node_id"], []).append(a)

    used_end = ref.usage_after(used0, live, row_of)
    numbers["overcommitted_nodes"] = int(len(ref.overcommitted(used_end, totals)))

    mismatch = violations = hosts = prop = wide_distinct = 0
    notes = []
    run_jobs = {r["job_id"]: r for r in records}
    for r in records:
        mine = by_job.get(r["job_id"], [])
        if r["status"] == "placed" and (
            len(mine) != r["width"] or any(a["task_group"] != "g" for a in mine)
        ):
            mismatch += 1
            notes.append(
                f"count_mismatch: {r['job_id']} asked {r['width']}, has "
                f"{len(mine)} live; registered {r.get('registers')} times")
        if not mine:
            continue
        shape = shapes[r["shape"]]
        rows = [row_of[a["node_id"]] for a in mine]
        bad = int((~eligible_of[r["shape"]][rows]).sum())
        if bad:
            notes.append(f"constraint_violations: {r['job_id']} ({shape['name']}) "
                         f"on rows {rows}")
        violations += bad
        ops = operands(shape)
        if "distinct_hosts" in ops:
            v = rules.distinct_hosts_violations(rows)
            if v:
                notes.append(f"distinct_hosts_violations: {r['job_id']} "
                             f"({shape['name']}) on rows {rows}")
            hosts += v
        for c in shape["constraints"]:
            if c["operand"] != "distinct_property":
                continue
            v = rules.distinct_property_violations(tables, c, rows)
            if v:
                notes.append(
                    f"distinct_property_violations: {r['job_id']} "
                    f"({shape['name']}, limit {rules.distinct_limit(c)}) has "
                    f"{rules.column(tables, c['l_target'])[rows].tolist()}")
            prop += v
            wide_distinct += r["status"] == "placed" and len(mine) > 1
    numbers["count_mismatch"] = mismatch
    numbers["constraint_violations"] = violations
    numbers["distinct_hosts_violations"] = hosts
    numbers["distinct_property_violations"] = prop

    before = [a for a in live if a["job_id"] not in run_jobs]
    used_start = ref.usage_after(used0, before, row_of)
    samples = build_samples(
        records, by_job, by_node, used0, row_of, traffic, tables, totals,
        used_start, used_end, seed, eligible_of, aff_of)
    numbers["score_gap"], numbers["rank_gap"] = score_gaps(samples)
    if dump:
        with open(dump, "w") as fh:
            json.dump({"seed": seed, "numbers": numbers, "samples": samples}, fh)

    lines = [f"check: {k} = {numbers[k]:.6g} (limit {LIMITS[k]:g})"
             for k in LIMITS]
    lines.append(
        f"check: compared {sum(r['status'] == 'placed' for r in records)} "
        f"operations, {len(live)} live allocations, {len(samples)} sampled "
        f"placement decisions ({sum(s['spread'] for s in samples)} with a "
        f"spread); {wide_distinct} jobs wider than one under a "
        f"distinct_property")
    correct = bool(samples) and all(numbers[k] <= LIMITS[k] for k in LIMITS)
    if not wide_distinct:
        correct = False
        notes.append("distinct_property: no job wider than one was placed "
                     "under it: nothing of it was compared")
    if not correct:
        if numbers["overcommitted_nodes"]:
            over = ref.overcommitted(used_end, totals)[:4]
            notes.append("overcommitted_nodes: " + "; ".join(
                f"row {int(r)} used {used_end[r].tolist()} of {totals.tolist()}"
                for r in over))
        notes.extend(gap_notes(samples, numbers, LIMITS))
        lines.extend(f"check: over its limit: {n}" for n in notes[:8])
    return correct, numbers, lines
