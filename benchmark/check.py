"""How ``correct`` is decided: the HTTP read-back against the plain reference.

Runs after the window and its drain, outside every timing.  Each number
compared is printed beside its limit, in every run (``LIMITS`` below;
PERF.md section 2 gives the readings each limit was set from).

(a) every completed operation's job has exactly its asked count of live
    allocations in its one group;
(b) per node, seeded usage + the allocations placed in this run <= the
    node's totals in cpu, memory and disk, by plain sums;
(c) every allocation's node satisfies its job's datacenters and
    constraints, evaluated on the configuration's own statement of the
    cluster (which (n) holds the registered nodes to);
(d) a seeded sample of the window's placement decisions (64 operations, the
    widest among them) replayed through the plain reference at full width:
    the score the program recorded for the chosen node equals the
    reference's for the usage that node can have had (seeded usage + a
    prefix of the allocations on it, in commit order), and no node that was
    eligible and still had room at the end scores higher at the window's
    start than the chosen node did — binpack scores only rise as a node
    fills, so a true arg-max can never be below that.  "Had room" means
    beyond what float32 sums can decide (reference.has_room): the program
    decides what fits by float32 sums, as the configuration states, and a
    node it rightly saw as full is not one it passed over.
(e) where the traffic registers resident jobs again, unchanged
    (``register_again_fraction``): every resident job that an ``again``
    operation ended placed on still has the version its first registration
    got (``resubmit_version_bumped``) and, id for id and node for node, the
    live allocations the resident phase placed, none stopped and none added
    (``resubmit_allocs_replaced``), which are its asked count
    (``count_mismatch``).  ``state["resident"]`` is run.py's read-back of
    the resident set before the window; a job it does not hold counts under
    both numbers.  Which numbers a run compares follows from its TRAFFIC
    (``expected_numbers``), not from its records: with
    ``register_again_fraction`` above 0 both are required and at least one
    ``again`` operation has to have ended placed, or the run is not correct;
    without the key a run compares exactly the numbers it compared before
    there was one.
"""

from __future__ import annotations

import random

import numpy as np

import reference as ref

LIMITS = {
    "nodes_wrong": 0,
    "count_mismatch": 0,
    "overcommitted_nodes": 0,
    "constraint_violations": 0,
    "resubmit_version_bumped": 0,
    "resubmit_allocs_replaced": 0,
    "score_gap": 3e-5,
    "rank_gap": 1e-5,
}
SAMPLE_OPS = 64
NODE_SAMPLE = 32


def node_id(i: int) -> str:
    return f"sim-node-{i:06d}"


def check_nodes(get, n_nodes, cluster, seed):
    """Stubs of every node against the configuration's shape, and a seeded
    sample of whole nodes (attributes and resources)."""
    wrong = 0
    stubs = get("/v1/nodes")
    by_id = {s["id"]: s for s in stubs}
    if len(stubs) != n_nodes:
        wrong += abs(len(stubs) - n_nodes)
    for i in range(n_nodes):
        s, want = by_id.get(node_id(i)), ref.expected_node(i, cluster)
        if (
            s is None or s["status"] != "ready"
            or s["scheduling_eligibility"] != "eligible"
            or s["datacenter"] != want["datacenter"]
            or s["node_class"] != want["node_class"]
        ):
            wrong += 1
    rng = random.Random(f"{seed}:nodes")
    for i in rng.sample(range(n_nodes), min(NODE_SAMPLE, n_nodes)):
        node, want = get(f"/v1/node/{node_id(i)}"), ref.expected_node(i, cluster)
        same = all(
            node["attributes"].get(k) == v
            for k, v in want["attributes"].items()
        )
        for d in ref.DIMS:
            same &= node["resources"][d] == cluster["node_resources"][d]
            same &= node["reserved"].get(d, 0) == cluster["node_reserved"].get(d, 0)
        wrong += not same
    return wrong


def build_samples(records, allocs_by_job, allocs_by_node, used0, row_of,
                  traffic, tables, totals, used_start, used_end, seed):
    """The sampled placement decisions, each with everything the score
    comparison needs (plain lists: this is also what ``--check-dump``
    writes for the control)."""
    placed = [r for r in records if r["status"] == "placed"]
    if not placed:
        return []
    rng = random.Random(f"{seed}:sample")
    widest = max(placed, key=lambda r: (r["width"], -r["i"]))
    rest = [r for r in placed if r is not widest]
    chosen = [widest] + rng.sample(rest, min(SAMPLE_OPS - 1, len(rest)))
    samples = []
    for r in chosen:
        shape = traffic["shapes"][r["shape"]]
        elig = ref.eligible(tables, shape["datacenters"], shape["constraints"])
        aff = ref.affinity_term(tables, shape["affinities"])
        own_rows = {row_of[a["node_id"]] for a in allocs_by_job[r["job_id"]]}
        for a in allocs_by_job[r["job_id"]]:
            res = a["resources"]
            ask = [res["cpu"], res["memory_mb"], res["disk_mb"]]
            row = row_of[a["node_id"]]
            on_node = allocs_by_node[a["node_id"]]
            siblings = sum(
                1 for b in on_node
                if b["create_index"] == a["create_index"]
                and b["job_id"] == a["job_id"]
            ) - 1
            earlier = sorted(
                (b for b in on_node if b["create_index"] < a["create_index"]),
                key=lambda b: (b["create_index"], b["id"]),
            )
            # Usage the node can have shown: seeded + a prefix of the
            # earlier allocations on it in commit order (the store adds a
            # plan's allocations to the matrix one by one, so a launch can
            # see a commit half applied), + 0..siblings of this plan.
            base = used0[row].astype(np.float64)
            prefixes = [base.copy()]
            for b in earlier:
                br = b["resources"]
                base = base + (br["cpu"], br["memory_mb"], br["disk_mb"])
                prefixes.append(base.copy())
            same_job_before = sum(b["job_id"] == a["job_id"] for b in earlier)
            scores = a["metrics"]["scores"].get(a["node_id"], {})
            # Best score any other eligible node with room to the end had
            # at the window's start (a lower bound of what it had later).
            # Room beyond what float32 sums can decide: a node the program
            # rightly saw as full by 0.0005 MHz is not one it passed over.
            others = elig.copy()
            others[list(own_rows)] = False
            floors = []
            for sure in (True, False):
                room = others & ref.has_room(used_end, ask, totals, sure)
                if shape["spreads"] or not room.any():
                    floors.append(None)
                    continue
                b_start = ref.binpack_score(used_start[room], ask, totals)
                floors.append(float(ref.final_score(
                    b_start, 0, r["width"], aff[room]).max()))
            floor, floor_loose = floors
            samples.append({
                "job_id": a["job_id"], "alloc": a["id"], "row": row,
                "ask": ask, "totals": totals.tolist(),
                "count": r["width"],
                "candidates": [p.tolist() for p in prefixes],
                "siblings": siblings,
                "collisions_max": siblings + same_job_before,
                "affinity": float(aff[row]),
                "spread": bool(shape["spreads"]),
                "binpack": scores.get("binpack"),
                "final": scores.get("final"),
                "floor": floor, "floor_loose": floor_loose,
            })
    return samples


def _candidates(s, dtype):
    """Reference (binpack, final) for every state the node can have been
    in: (K,) and (K, C) arrays."""
    ask = np.array(s["ask"], np.float64)
    used = np.array([
        np.array(c) + k * ask
        for c in s["candidates"] for k in range(s["siblings"] + 1)
    ])
    b = ref.binpack_score(used, ask, s["totals"], dtype)
    cols = np.arange(s["collisions_max"] + 1)
    f = ref.final_score(b[:, None], cols[None, :], s["count"],
                        s["affinity"], dtype)
    return b, f


RESUBMIT = ("resubmit_version_bumped", "resubmit_allocs_replaced")


def expected_numbers(traffic):
    """The numbers a run of this traffic has to compare, in LIMITS' order:
    decided by the traffic file, so a run that lost its ``again`` records
    cannot pass by comparing less."""
    again = float(traffic.get("register_again_fraction", 0) or 0) > 0
    return [k for k in LIMITS if again or k not in RESUBMIT]


def _sample_gaps(s, recorded_dtype=None, floor="floor"):
    """(score gap, rank gap or None) of one sampled decision."""
    if s["binpack"] is None or s["final"] is None:
        return float("inf"), float("inf")
    b64, f64 = _candidates(s, np.float64)
    rec_b, rec_f = s["binpack"], s["final"]
    if recorded_dtype is not None:
        # What a kernel in that precision would have recorded for the
        # state the program's own record matches best.
        k = int(np.argmin(np.abs(b64 - rec_b)))
        c = int(np.argmin(np.abs(f64[k] - rec_f)))
        bl, fl = _candidates(s, recorded_dtype)
        rec_b, rec_f = float(bl[k]), float(fl[k, c])
    gap = float(np.min(np.abs(b64 - rec_b) / np.maximum(np.abs(b64), 0.05)))
    rank = None
    if not s["spread"]:
        gap = max(gap, float(np.min(
            np.abs(f64 - rec_f) / np.maximum(np.abs(f64), 0.05))))
        if s.get(floor) is not None:
            rank = s[floor] - rec_f
    return gap, rank


def score_gaps(samples, recorded_dtype=None, floor="floor"):
    """(score_gap, rank_gap) over the samples.  ``recorded_dtype`` None:
    the program's recorded scores.  Otherwise the CONTROL: the reference,
    computed in that lower precision, put in the program's place.
    ``floor`` "floor_loose" reads the rank against the loose reading of
    room (diagnostics only)."""
    score_gap, rank_gap = 0.0, float("-inf")
    for s in samples:
        gap, rank = _sample_gaps(s, recorded_dtype, floor)
        score_gap = max(score_gap, gap)
        if rank is not None:
            rank_gap = max(rank_gap, rank)
    return score_gap, rank_gap


def decide(get, cfg, traffic, records, used0, seed, dump=None, state=None):
    """The numbers compared, ``correct``, and the lines to print.  ``state``
    is what a deployment's own set-up returned (run.py); this check has no
    such set-up and ignores it."""
    cluster, n = cfg["cluster"], cfg["nodes"]
    row_of = {node_id(i): i for i in range(n)}
    totals = ref.node_totals(cluster)
    tables = ref.attr_tables(n, cluster)
    numbers = {"nodes_wrong": check_nodes(get, n, cluster, seed)}

    allocs = []
    for ns in ["default"] + [f"tenant-{i}" for i in range(1, traffic["tenants"])]:
        allocs.extend(get(f"/v1/allocations?namespace={ns}"))
    live = [a for a in allocs if a["desired_status"] == "run"]
    by_job, by_node = {}, {}
    for a in live:
        by_job.setdefault(a["job_id"], []).append(a)
        by_node.setdefault(a["node_id"], []).append(a)
    unknown = [a for a in live if a["node_id"] not in row_of]
    numbers["nodes_wrong"] += len(unknown)
    live = [a for a in live if a["node_id"] in row_of]

    used_end = ref.usage_after(used0, live, row_of)
    numbers["overcommitted_nodes"] = int(len(ref.overcommitted(used_end, totals)))

    mismatch = violations = 0
    notes = []  # what to print where a number is over its limit
    # Several ``again`` operations name one resident job: it is held once.
    again = {r["job_id"]: r for r in records
             if r.get("kind") == "again" and r["status"] == "placed"}
    fresh = [r for r in records if r.get("kind") != "again"]
    run_jobs = {r["job_id"]: r for r in fresh}
    for r in fresh + list(again.values()):
        mine = by_job.get(r["job_id"], [])
        if r["status"] == "placed" and (
            len(mine) != r["width"] or any(a["task_group"] != "g" for a in mine)
        ):
            mismatch += 1
            notes.append(
                f"count_mismatch: {r['job_id']} asked {r['width']}, has "
                f"{len(mine)} live; registered {r.get('registers')} times, "
                f"{r.get('evals_failed')} evals failed")
        if mine:
            shape = traffic["shapes"][r["shape"]]
            elig = ref.eligible(tables, shape["datacenters"], shape["constraints"])
            violations += sum(not elig[row_of[a["node_id"]]] for a in mine)
    numbers["count_mismatch"] = mismatch
    numbers["constraint_violations"] = violations

    expected = expected_numbers(traffic)
    if RESUBMIT[0] in expected:
        resident = (state or {}).get("resident") or {}
        bumped = replaced = 0
        versions = {
            j["id"]: j["version"]
            for ns in sorted({r["namespace"] for r in again.values()})
            for j in get(f"/v1/jobs?namespace={ns}&prefix=res-")}
        for jid in again:
            first = resident.get(jid) or {}
            if (first.get("version") is None
                    or versions.get(jid) != first["version"]):
                bumped += 1
                notes.append(
                    f"resubmit_version_bumped: {jid} reads version "
                    f"{versions.get(jid)}, its first registration got "
                    f"{first.get('version')}")
            now = {a["id"]: a["node_id"] for a in by_job.get(jid, [])}
            if now != first.get("allocs"):
                replaced += 1
                notes.append(
                    f"resubmit_allocs_replaced: {jid} had "
                    f"{first.get('allocs')}, has {now}")
        numbers["resubmit_version_bumped"] = bumped
        numbers["resubmit_allocs_replaced"] = replaced

    before = [a for a in live if a["job_id"] not in run_jobs]
    used_start = ref.usage_after(used0, before, row_of)
    # The window's NEW jobs alone are sampled: a resident job's allocations
    # were placed before the window's start, which the rank is read against.
    samples = build_samples(
        fresh, by_job, by_node, used0, row_of, traffic, tables, totals,
        used_start, used_end, seed,
    )
    numbers["score_gap"], numbers["rank_gap"] = score_gaps(samples)
    # Diagnostics, compared with nothing: the rank under the loose reading
    # of room, and how many (node, ask) pairs only that reading admits.
    _, rank_loose = score_gaps(samples, floor="floor_loose")
    asks = {tuple(s["ask"]) for s in samples}
    undecided = sum(
        int((ref.has_room(used_end, a, totals, sure=False)
             & ~ref.has_room(used_end, a, totals)).sum())
        for a in asks)
    if dump:
        import json

        with open(dump, "w") as fh:
            json.dump({"seed": seed, "numbers": numbers, "samples": samples}, fh)

    lines = [
        f"check: {k} = {numbers[k]:.6g} (limit {LIMITS[k]:g})"
        for k in expected
    ]
    lines.append(
        f"check: rank_gap under the loose reading of room = {rank_loose:.6g} "
        f"(compared with nothing; {undecided} (node, ask) pairs within "
        f"float32 rounding of full)")
    lines.append(
        f"check: compared {sum(r['status'] == 'placed' for r in records)} "
        f"operations, {len(live)} live allocations, {len(samples)} sampled "
        f"placement decisions"
        + (f"; {sum(r.get('kind') == 'again' for r in records)} of the "
           f"operations registered {len(again)} resident jobs again"
           if RESUBMIT[0] in expected else "")
    )
    correct = bool(samples) and all(numbers[k] <= LIMITS[k] for k in expected)
    if RESUBMIT[0] in expected and not again:
        correct = False
        notes.append("resubmit: the traffic registers resident jobs again "
                     "and no such operation ended placed: nothing of it "
                     "was compared")
    if not correct:
        if numbers["nodes_wrong"]:
            states = {}
            for st in get("/v1/nodes"):
                k = f"{st['status']}/{st['scheduling_eligibility']}"
                states[k] = states.get(k, 0) + 1
            notes.append(f"nodes_wrong: node states {states}, "
                         f"{len(unknown)} allocations on unknown nodes")
        if numbers["overcommitted_nodes"]:
            rows = ref.overcommitted(used_end, totals)[:4]
            notes.append("overcommitted_nodes: " + "; ".join(
                f"row {int(r)} used {used_end[r].tolist()} of {totals.tolist()}"
                for r in rows))
        for key, pick in (("score_gap", 0), ("rank_gap", 1)):
            if samples and not numbers[key] <= LIMITS[key]:
                worst = max(samples, key=lambda s: (
                    _sample_gaps(s)[pick] if _sample_gaps(s)[pick] is not None
                    else float("-inf")))
                notes.append(f"{key}: worst sample " + str({
                    k: worst[k] for k in (
                        "job_id", "alloc", "row", "ask", "count", "siblings",
                        "collisions_max", "affinity", "binpack", "final",
                        "floor", "floor_loose")}
                ) + f", {len(worst['candidates'])} states, reference binpack "
                    f"{_candidates(worst, np.float64)[0][:6].tolist()}")
        lines.extend(f"check: over its limit: {n}" for n in notes[:8])
    return correct, numbers, lines
