"""How ``correct`` is decided for ``c2m-10k-net``: the read-back against
``net_reference``.

``check.py``'s numbers for the window's jobs (counts, over-commitment of
cpu, memory AND disk by plain sums over seeded usage + the resident
allocations + everything placed, datacenters and constraints, the sampled
scores and ranks), with the reference's feasibility of a sampled decision
taking ports and devices into account: a node is not one the program
passed over where, at the END of the run, a live allocation holds an asked
static port or fewer instances are free than asked (ports and instances are
only ever taken in a run, so what is closed at the end may have been open
at the decision, never the other way round: the reading that raises no
false alarm, as ``reference.has_room`` at the end).  The scores of the
shape with a spread are read as ``rules_check`` reads them (the recorded
mean against ``rules_reference``'s even spread).

Plus, exact with limit 0, over EVERY live allocation read back and the
resident ones of the set-up's ``state`` (nothing sampled):

* ``port_collisions`` -- holders of one port of one node beyond the first;
* ``port_ask_unmet`` -- allocations of the run's jobs without an asked
  static port, or without a port of the dynamic range (20000-32000) under
  one of their dynamic labels;
* ``device_overcommit`` -- nodes with more instances of a device taken than
  they have;
* ``device_on_wrong_node`` -- allocations that ask for a device on a node
  that has none.

What an allocation asks for is read off its job's SHAPE in the traffic file
(the body as sent), not off the allocation: a program that drops the ask on
the way is then held to it all the same.  A run that placed no job with a
static port or none with a device compared nothing of what this deployment
is for, and is not correct.

The dump (``--check-dump``) has ``check.py``'s form, so ``control.py``
reads it as it reads any.
"""

from __future__ import annotations

import json
import random
import re

import check
import net_reference as net
import reference as ref
import rules_check
import traffic as traffic_mod

LIMITS = {
    "nodes_wrong": 0,
    "count_mismatch": 0,
    "overcommitted_nodes": 0,
    "constraint_violations": 0,
    "port_collisions": 0,
    "port_ask_unmet": 0,
    "device_overcommit": 0,
    "device_on_wrong_node": 0,
    "score_gap": 3e-5,
    "rank_gap": 1e-5,
}
_WARM = re.compile(r"^w\d+([sb])-(?:warm|op)-(\d+)$")


def check_node_devices(get, n_nodes, cluster, seed) -> int:
    """A seeded sample of whole nodes against the device groups the
    configuration states (``check.check_nodes`` holds the rest)."""
    wrong = 0
    rng = random.Random(f"{seed}:device-nodes")
    for i in rng.sample(range(n_nodes), min(check.NODE_SAMPLE, n_nodes)):
        have = get(f"/v1/node/{check.node_id(i)}")["resources"].get(
            "devices") or {}
        wrong += {k: len(v) for k, v in have.items() if v} != \
            net.node_devices(i, cluster)
    return wrong


def shape_of(job_id, by_record, by_index, warm):
    """Index of the shape a live allocation's job was sent with: a job of
    the window by its record; a job of the warm-up by its place in
    ``traffic.warmup_ops`` (``w<k>s-warm-<i>``) or, for the burst of the
    window's own first operations the warm-up ends with (``w<k>b-op-<i>``),
    by the window's operation of that index; None for any other."""
    r = by_record.get(job_id)
    if r is not None:
        return r["shape"]
    m = _WARM.match(job_id)
    if not m:
        return None
    table = warm if m.group(1) == "s" else by_index
    return table[int(m.group(2))]["shape"] if int(m.group(2)) in table \
        else None


def decide(get, cfg, traffic, records, used0, seed, dump=None, state=None):
    cluster, n = cfg["cluster"], cfg["nodes"]
    row_of = {check.node_id(i): i for i in range(n)}
    totals = ref.node_totals(cluster)
    tables = ref.attr_tables(n, cluster)
    shapes = traffic["shapes"]
    ask_of = [net.asks(s) for s in shapes]
    state = state or {c: [] for c in net.COLUMNS}
    # What the set-up installed stands beside the seeded usage.
    used0 = used0.astype("float64") + net.resident_usage(n, state)
    numbers = {"nodes_wrong": check.check_nodes(get, n, cluster, seed)
               + check_node_devices(get, n, cluster, seed)}

    allocs = []
    for ns in traffic_mod.namespaces(traffic):
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

    # Who holds what at the end: the residents and every live allocation,
    # each by what its job's shape asked for.
    held = net.Tables(n, cluster).add_residents(state)
    run_jobs = {r["job_id"]: r for r in records}
    by_index = {r["i"]: r for r in records}
    warm = dict(enumerate(traffic_mod.warmup_ops(traffic)))
    notes, unmet, unasked, asking, dropped = [], 0, 0, 0, 0
    for a in live:
        shape = shape_of(a["job_id"], run_jobs, by_index, warm)
        if shape is None:
            unasked += 1
            continue
        ask, row = ask_of[shape], row_of[a["node_id"]]
        have = net.assigned(a)
        held.add(row, have.values(), ask["devices"])
        if ask["devices"]:
            asking += 1
            dropped += not (a.get("resources") or {}).get("devices")
        if net.ask_unmet(have, ask, held.range):
            unmet += 1
            notes.append(
                f"port_ask_unmet: {a['job_id']} ({shapes[shape]['name']}) "
                f"asked {ask['static']} + {ask['dynamic']}, holds "
                f"{a.get('assigned_ports')}")
    numbers["nodes_wrong"] += unasked  # an allocation of no job of this run
    numbers["port_collisions"] = net.port_collisions(held)
    numbers["port_ask_unmet"] = unmet
    numbers["device_overcommit"] = net.device_overcommit(held)
    numbers["device_on_wrong_node"] = held.misplaced

    eligible_of = [ref.eligible(tables, s["datacenters"], s["constraints"])
                   for s in shapes]
    aff_of = [ref.affinity_term(tables, s["affinities"]) for s in shapes]
    mismatch = violations = with_static = with_device = 0
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
        rows = [row_of[a["node_id"]] for a in mine]
        bad = int((~eligible_of[r["shape"]][rows]).sum())
        if bad:
            notes.append(f"constraint_violations: {r['job_id']} "
                         f"({shapes[r['shape']]['name']}) on rows {rows}")
        violations += bad
        if r["status"] == "placed":
            with_static += bool(ask_of[r["shape"]]["static"])
            with_device += bool(ask_of[r["shape"]]["devices"])
    numbers["count_mismatch"] = mismatch
    numbers["constraint_violations"] = violations

    before = [a for a in live if a["job_id"] not in run_jobs]
    used_start = ref.usage_after(used0, before, row_of)
    # A node closed to the ask at the end is not one the program passed over.
    open_of = [e & ~net.blocked(held, ask)
               for e, ask in zip(eligible_of, ask_of)]
    samples = rules_check.build_samples(
        records, by_job, by_node, used0, row_of, traffic, tables, totals,
        used_start, used_end, seed, open_of, aff_of)
    numbers["score_gap"], numbers["rank_gap"] = rules_check.score_gaps(samples)
    if dump:
        with open(dump, "w") as fh:
            json.dump({"seed": seed, "numbers": numbers, "samples": samples}, fh)

    lines = [f"check: {k} = {numbers[k]:.6g} (limit {LIMITS[k]:g})"
             for k in LIMITS]
    lines.append(
        f"check: compared {sum(r['status'] == 'placed' for r in records)} "
        f"operations, {len(live)} live allocations beside {len(state['node'])} "
        f"resident ones, {len(samples)} sampled placement decisions; "
        f"{with_static} jobs placed with a static port, {with_device} with "
        f"a device")
    lines.append(
        f"check: {dropped} of the {asking} live allocations whose job asks "
        f"for a device carry no device in their own resources (compared "
        f"with nothing: the ask is read off the job's shape)")
    correct = bool(samples) and all(numbers[k] <= LIMITS[k] for k in LIMITS)
    if not (with_static and with_device):
        correct = False
        notes.append("no job with a static port, or none with a device, was "
                     "placed: nothing of it was compared")
    if not correct:
        if numbers["port_collisions"]:
            notes.append("port_collisions: " + "; ".join(
                f"row {i} port {p} held {c} times"
                for i, h in enumerate(held.held) for p, c in h.items()
                if c > 1)[:600])
        if numbers["device_overcommit"]:
            for name, total in held.dev_total.items():
                over = (held.dev_used[name] > total).nonzero()[0]
                notes.append(f"device_overcommit: {name}: " + "; ".join(
                    f"row {int(r)} has {int(held.dev_used[name][r])} of "
                    f"{int(total[r])} taken" for r in over[:4])
                    + f" ({len(over)} nodes, the fullest with "
                    f"{int(held.dev_used[name].max())})")
        if numbers["overcommitted_nodes"]:
            over = ref.overcommitted(used_end, totals)[:4]
            notes.append("overcommitted_nodes: " + "; ".join(
                f"row {int(r)} used {used_end[r].tolist()} of {totals.tolist()}"
                for r in over))
        notes.extend(rules_check.gap_notes(samples, numbers, LIMITS))
        lines.extend(f"check: over its limit: {x}" for x in notes[:8])
    return correct, numbers, lines
