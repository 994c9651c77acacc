"""The ``c2m-10k-preempt`` deployment on a CPU at a tiny size: end to end
through ``run.py --rehearse``; ``preempt_check`` on a hand-made read-back,
sound and with one fault of each kind injected; ``preempt_reference``'s
victim search on hand-worked cases; each new reader on a recorded run; and
the same scheduler configuration and check on a cluster with room, where
nothing is evicted."""

import copy
import importlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import BENCH, ROOT, checkout

sys.path.insert(0, os.path.join(BENCH, "deployments"))

import preempt_check  # noqa: E402
import preempt_reference as pref  # noqa: E402
import reference as ref  # noqa: E402
import stage_reduce  # noqa: E402
from test_readers import MS, entry, event, field, line, op_meta  # noqa: E402

CELL = "c2m-10k-preempt.tiers-backlog"
ENV = dict(os.environ, JAX_PLATFORMS="cpu",
           JAX_COMPILATION_CACHE_DIR=os.path.join(ROOT, ".jax_cache"))


def _run(root, workload, *extra, seconds="4"):
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmark/run.py"),
         "--workload", workload, "--seed", str(2 ** 31 + 37), "--seconds",
         seconds, "--trace", "0", "--rehearse", *extra],
        capture_output=True, text=True, env=ENV, timeout=900)


# -- end to end ------------------------------------------------------------------

@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    dump = tmp_path_factory.mktemp("dump") / "dump.json"
    p = _run(ROOT, CELL, "--check-dump", str(dump))
    assert p.returncode == 0, p.stderr[-3000:]
    return p, dump


def test_the_deployment_end_to_end_at_480_nodes(rehearsal):
    p, dump = rehearsal
    out = p.stdout.strip().splitlines()
    result = json.loads(out[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 100
    # every number, the new ones too, beside its limit: on standard
    # output, as the last lines on standard error, and under `compared`
    assert list(result["compared"]) == list(preempt_check.LIMITS)
    err = p.stderr.strip().splitlines()
    for k, limit in preempt_check.LIMITS.items():
        assert result["compared"][k]["limit"] == limit
        assert any(l.startswith(f"check: {k} = ") and
                   l.endswith(f"(limit {limit:g})") for l in out), k
        assert any(l.startswith(f"check: {k} = ") for l in err[-len(
            preempt_check.LIMITS):]), k
    replayed = [l for l in out if l.startswith("check: replayed")][0]
    evictions = int(replayed.split(": ")[2].split(" evictions")[0])
    assert evictions > 100
    detail = json.loads(
        [l for l in out if l.startswith("detail: ")][-1][len("detail: "):])
    assert detail["setup"]["install_s"] > 0
    # the control: the reference in bfloat16 in the program's place is not
    # correct on this run's dump, preempting decisions among its samples
    samples = json.load(open(dump))["samples"]
    assert sum(bool(s.get("preempting")) for s in samples) > 10
    c = subprocess.run(
        [sys.executable, os.path.join(BENCH, "control.py"), str(dump)],
        capture_output=True, text=True, env=ENV, timeout=300)
    assert c.returncode == 0, c.stdout + c.stderr
    assert "bfloat16 control" in c.stdout


def test_on_a_cluster_with_room_preemption_on_evicts_nothing(tmp_path):
    """The scheduler configuration and the check of ``c2m-10k-preempt``,
    without its set-up, under a temporary root: room on every node, so
    ``evicted_with_room`` would read every eviction, and there is none."""
    bench = checkout(tmp_path)
    base = json.load(open(tmp_path / "benchmark/configs/c2m-10k.json"))
    mine = json.load(open(tmp_path / "benchmark/configs/c2m-10k-preempt.json"))
    cfg = {**base, "name": "room", "check": mine["check"],
           "scheduler_config": mine["scheduler_config"]}
    (tmp_path / "benchmark/configs/room.json").write_text(json.dumps(cfg))
    bench["configs"].append({
        "name": "room", "source": "none", "reduced": [], "why": "fixture",
        "file": "benchmark/configs/room.json"})
    bench["workloads"].append({
        "name": "room.tiers", "config": "room", "traffic": "tiers-backlog",
        "chips": 1, "why": "fixture"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    p = _run(str(tmp_path), "room.tiers", seconds="3")
    assert p.returncode == 0, p.stderr[-3000:]
    out = p.stdout.strip().splitlines()
    result = json.loads(out[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["compared"]["evicted_with_room"] == {"value": 0, "limit": 0}
    replayed = [l for l in out if l.startswith("check: replayed")][0]
    assert ": 0 evictions" in replayed and " 0 preemption evals" in replayed


def test_the_set_up_ends_the_run_where_its_probe_is_not_placed():
    """A program that cannot place by eviction (the parent of PR 37) fails
    in the set-up, in seconds, and not in a warm-up of 100 re-registrations
    a job."""
    import types

    import preempt_tiers

    failed = types.SimpleNamespace(
        id="e1", status="failed",
        status_description="maximum attempts reached")
    srv = types.SimpleNamespace(
        submit_job=lambda job: failed,
        wait_for_eval=lambda eid, timeout: failed,
        store=types.SimpleNamespace(allocs_by_job=lambda ns, jid: []))
    cfg = json.load(open(os.path.join(
        BENCH, "configs", "c2m-10k-preempt.json")))
    with pytest.raises(SystemExit) as e:
        preempt_tiers.probe(srv, cfg)
    assert "nothing was measured" in str(e.value)
    assert "maximum attempts reached" in str(e.value)
    live = types.SimpleNamespace(terminal_status=lambda: False)
    srv.store.allocs_by_job = lambda ns, jid: [live]
    preempt_tiers.probe(srv, cfg)   # placed: the set-up goes on


# -- the check, on a hand-made read-back --------------------------------------------

CLUSTER = {
    "datacenters": 4, "node_classes": 6, "racks": 32,
    "node_resources": {"cpu": 4000, "memory_mb": 8192, "disk_mb": 102400},
    "node_reserved": {"cpu": 100, "memory_mb": 256, "disk_mb": 0},
}
SHAPE = {"name": "s0", "cpu": 200, "memory_mb": 256,
         "datacenters": ["dc1", "dc2", "dc3", "dc4"], "constraints": [],
         "affinities": [], "spreads": [], "kind": "binpack"}
N = 12
TOTALS = ref.node_totals(CLUSTER)


def _alloc(aid, node, job, ns, cpu, mem, index, **kw):
    return {"id": aid, "node_id": f"sim-node-{node:06d}", "job_id": job,
            "namespace": ns, "task_group": "g", "eval_id": kw.pop("eval", ""),
            "resources": {"cpu": cpu, "memory_mb": mem, "disk_mb": 300},
            "desired_status": "run", "desired_description": "",
            "client_status": "running", "create_index": index,
            "modify_index": index, "alloc_modify_index": index,
            "metrics": {"scores": {}}, **kw}


def _eval(eid, job, ns, prio, trigger, index, jtype="service"):
    return {"id": eid, "job_id": job, "namespace": ns, "priority": prio,
            "type": jtype, "triggered_by": trigger, "status": "complete",
            "create_index": index, "modify_index": index + 1}


class World:
    """Twelve nodes, each with 3,000 MHz of seeded usage and four tier
    allocations of 200 MHz (3,800 of 3,900): no room for an ask of 200.
    One service job of two instances arrives: each evicts one tier
    allocation, on nodes 3 and 5, in one plan at index 25."""

    def __init__(self):
        self.used0 = np.zeros((N, 3))
        self.used0[:, 0], self.used0[:, 1] = 3000.0, 2000.0
        self.allocs, ids, nodes, jobs = [], [], [], []
        for i in range(N):
            for k in range(4):
                a = _alloc(f"tier-{i:02d}-{k}", i, f"tier-{k}", "best-effort",
                           200, 256, 10)
                self.allocs.append(a)
                ids.append(a["id"]), nodes.append(i), jobs.append(a["job_id"])
        self.state = {
            "namespace": "best-effort", "priority": 10, "ids": ids,
            "node": nodes, "job": jobs, "cpu": [200] * len(ids),
            "memory_mb": [256] * len(ids), "disk_mb": [300] * len(ids)}
        self.evals = [
            _eval("ev-1", "op-000000", "default", 50, "job-register", 20)]
        self.evals[0]["modify_index"] = 26  # complete, after its plan
        for node, aid in ((3, "placed-a"), (5, "placed-b")):
            victim = self.by_id(f"tier-{node:02d}-0")
            victim.update(
                desired_status="evict", modify_index=25, alloc_modify_index=25,
                desired_description=f"Preempted by alloc ID {aid}")
            used = self.used0[node] + (800, 1024, 1200)
            ask = (200, 256, 300)
            b, p = pref.preempting_scores(
                used, ask, TOTALS, [{"priority": 10, "res": (200, 256, 300)}])
            scores = {"binpack": float(b), "preemption": float(p),
                      "final": float(pref.final_score(b, p, 0, 2, 0.0))}
            self.allocs.append(_alloc(
                aid, node, "op-000000", "default", 200, 256, 25, eval="ev-1",
                metrics={"scores": {f"sim-node-{node:06d}": scores}}))
            self.evals.append(_eval(
                f"ev-pre-{node}", "tier-0", "best-effort", 10, "preemption",
                25, "batch"))
        self.evals[-1]["id"] = "ev-pre"  # one eval per job and plan
        del self.evals[-2]
        self.records = [{
            "i": 0, "job_id": "op-000000", "namespace": "default", "width": 2,
            "type": "service", "shape": 0, "status": "placed",
            "registers": 1}]
        self.traffic = {"tenants": 1, "shapes": [SHAPE]}
        self.cfg = {"cluster": CLUSTER, "nodes": N}

    def by_id(self, aid):
        return next(a for a in self.allocs if a["id"] == aid)

    def get(self, path):
        if path == "/v1/metrics":
            return {}
        if path == "/v1/nodes":
            return [{"id": f"sim-node-{i:06d}", "status": "ready",
                     "scheduling_eligibility": "eligible",
                     **{k: ref.expected_node(i, CLUSTER)[k]
                        for k in ("datacenter", "node_class")}}
                    for i in range(N)]
        if path.startswith("/v1/node/"):
            i = int(path.rsplit("-", 1)[1])
            return {"attributes": ref.expected_node(i, CLUSTER)["attributes"],
                    "resources": CLUSTER["node_resources"],
                    "reserved": CLUSTER["node_reserved"]}
        kind, ns = path.split("?namespace=")
        rows = self.allocs if kind == "/v1/allocations" else self.evals
        return [copy.deepcopy(x) for x in rows if x["namespace"] == ns]

    def decide(self):
        return preempt_check.decide(
            self.get, self.cfg, self.traffic, self.records, self.used0, 7,
            state=self.state)


def test_the_check_passes_a_sound_read_back():
    correct, numbers, lines = World().decide()
    assert correct, lines
    assert numbers["score_gap"] < 1e-9
    assert all(numbers[k] == 0 for k in preempt_check.LIMITS
               if preempt_check.LIMITS[k] == 0)
    assert any("2 evictions (2 of the installed tier's 48)" in l
               for l in lines)


def _unjust(w):       # the victim's job is of the preemptor's own tier
    w.state["priority"] = 45


def _other_node(w):   # the preemptor sits on another node than its victim
    w.by_id("placed-a")["node_id"] = "sim-node-000007"


def _later_plan(w):   # the eviction was not committed with the placement
    for k in ("modify_index", "alloc_modify_index"):
        w.by_id("tier-03-0")[k] = 27
    w.evals.append(_eval("ev-x", "tier-0", "best-effort", 10, "preemption",
                         27, "batch"))


def _with_room(w):    # node 7 never carried its tier: room all along
    w.allocs = [a for a in w.allocs if not a["id"].startswith("tier-07")]
    keep = [k for k, i in enumerate(w.state["ids"])
            if not i.startswith("tier-07")]
    for key in ("ids", "node", "job", "cpu", "memory_mb", "disk_mb"):
        w.state[key] = [w.state[key][k] for k in keep]


def _no_followup(w):  # the evicted job is never evaluated again
    w.evals = [e for e in w.evals if e["triggered_by"] != "preemption"]


def _stopped(w):      # an installed allocation stopped by nobody's plan
    w.by_id("tier-09-2").update(desired_status="stop", modify_index=24,
                                alloc_modify_index=24)


def _missing(w):      # ... or gone from the read-back
    w.allocs = [a for a in w.allocs if a["id"] != "tier-09-2"]


def _overcommit(w):   # a placement that evicted nothing after all
    w.by_id("tier-05-0").update(desired_status="run", desired_description="",
                                modify_index=10, alloc_modify_index=10)


def _wrong_score(w):  # binpack of the node BEFORE the eviction
    s = w.by_id("placed-a")["metrics"]["scores"]["sim-node-000003"]
    s["binpack"] = float(ref.binpack_score(
        w.used0[3] + (800, 1024, 1200), (200, 256, 300), TOTALS))


@pytest.mark.parametrize("fault,number", [
    (_unjust, "evicted_unjustly"), (_other_node, "evicted_unjustly"),
    (_later_plan, "evicted_unjustly"), (_with_room, "evicted_with_room"),
    (_no_followup, "evictions_without_followup"), (_stopped, "tier_lost"),
    (_missing, "tier_lost"), (_overcommit, "overcommitted_nodes"),
    (_wrong_score, "score_gap"),
], ids=lambda f: getattr(f, "__name__", f))
def test_the_check_fails_a_read_back_with_one_fault(fault, number):
    w = World()
    fault(w)
    correct, numbers, lines = w.decide()
    assert not correct
    assert numbers[number] > preempt_check.LIMITS[number], lines
    assert any(l.startswith(f"check: over its limit: {number}")
               for l in lines), lines


def _preempting(w, aid, node, job, width, index, ev, victim, victim_prio):
    """``aid`` of ``job`` placed on the full ``node`` at ``index`` by
    evicting ``victim`` in the same plan, scored as the reference scores
    it, with the victim's job evaluated again."""
    v = w.by_id(victim)
    v.update(desired_status="evict", modify_index=index,
             alloc_modify_index=index,
             desired_description=f"Preempted by alloc ID {aid}")
    b, p = pref.preempting_scores(
        w.used0[node] + (800, 1024, 1200), (200, 256, 300), TOTALS,
        [{"priority": victim_prio, "res": (200, 256, 300)}])
    scores = {"binpack": float(b), "preemption": float(p),
              "final": float(pref.final_score(b, p, 0, width, 0.0))}
    w.allocs.append(_alloc(
        aid, node, job, "default", 200, 256, index, eval=ev,
        metrics={"scores": {f"sim-node-{node:06d}": scores}}))
    w.evals.append(_eval(f"ev-pre-{aid}", v["job_id"], v["namespace"],
                         victim_prio, "preemption", index, "batch"))


def _race(w, second_attempt=True):
    """PERF.md section 6, PR 38: a batch job of two (priority 30) commits
    its plan in part (b1, index 31); a service job (50) justly evicts b1
    (index 33); the batch eval's second attempt, from a snapshot that still
    held b1, commits the rest (b2, index 34) and ends ``complete`` with one
    live; the follow-up eval of index 33 has placed nothing by the cut."""
    w.evals.append(_eval("ev-2", "op-000001", "default", 30, "job-register",
                         30, "batch"))
    w.evals[-1]["modify_index"] = 35
    w.evals.append(_eval("ev-3", "op-000002", "default", 50, "job-register",
                         32))
    _preempting(w, "b1", 7, "op-000001", 2, 31, "ev-2", "tier-07-0", 10)
    _preempting(w, "s1", 7, "op-000002", 1, 33, "ev-3", "b1", 30)
    if second_attempt:
        _preempting(w, "b2", 8, "op-000001", 2, 34, "ev-2", "tier-08-0", 10)
    w.records += [
        {"i": 1, "job_id": "op-000001", "namespace": "default", "width": 2,
         "type": "batch", "shape": 0, "status": "placed", "registers": 1},
        {"i": 2, "job_id": "op-000002", "namespace": "default", "width": 1,
         "type": "service", "shape": 0, "status": "placed", "registers": 1}]


def test_an_allocation_justly_evicted_before_the_evals_last_commit_counts():
    w = World()
    _race(w)
    # By the rule before PR 43 (the most live at once) this read 1: the
    # job of two never has more than one live.
    steps = sorted(
        [(a["create_index"], 1) for a in w.allocs
         if a["job_id"] == "op-000001"]
        + [(a["modify_index"], -1) for a in w.allocs
           if a["job_id"] == "op-000001" and a["desired_status"] == "evict"])
    live, peak = 0, 0
    for _, d in steps:
        live += d
        peak = max(peak, live)
    assert peak == 1
    correct, numbers, lines = w.decide()
    assert numbers["count_mismatch"] == 0, lines
    assert correct, lines
    assert any("5 evictions" in l for l in lines)


def test_an_eviction_after_the_evals_last_commit_earns_no_credit():
    """The same job one short because its second attempt never committed:
    b1 was evicted AFTER the eval's last commit, so nothing excuses it."""
    w = World()
    _race(w, second_attempt=False)
    correct, numbers, lines = w.decide()
    assert not correct and numbers["count_mismatch"] == 1, lines
    assert any(l.startswith("check: over its limit: count_mismatch")
               for l in lines), lines


def test_a_commit_after_the_cut_is_left_out():
    """The evicted tier's evals run on while the check reads: what the
    first pass over the evals has not seen is not replayed."""
    w = World()
    w.allocs.append(_alloc("late", 9, "tier-1", "best-effort", 200, 256, 99))
    correct, numbers, lines = w.decide()
    assert correct and numbers["overcommitted_nodes"] == 0, lines


# -- the reference's victim search, by hand --------------------------------------------

def _a(aid, prio, cpu, mem=100, disk=0):
    return {"id": aid, "priority": prio, "res": (cpu, mem, disk)}


@pytest.mark.parametrize("case,job,ask,room,allocs,want", [
    # it fits as it is: nothing goes
    ("fits", 50, (200, 100, 0), (250, 500, 0), [_a("x", 10, 100)], []),
    # priority 40 is not more than 10 below 50: no admissible victim
    ("delta", 50, (200, 100, 0), (0, 500, 0), [_a("x", 40, 300)], None),
    ("delta_ok", 50, (200, 100, 0), (0, 500, 0), [_a("x", 39, 300)], ["x"]),
    # lowest priority first, although the other one is the better match
    ("lowest_first", 50, (200, 100, 0), (0, 500, 0),
     [_a("match", 30, 200), _a("low", 10, 250)], ["low"]),
    # within a priority the closest to what is needed; equal distances
    # fall to the lower id
    ("closest", 50, (200, 100, 0), (0, 500, 0),
     [_a("a", 10, 100), _a("b", 10, 210), _a("c", 10, 400)], ["b"]),
    ("tie", 50, (200, 100, 0), (0, 500, 0),
     [_a("b", 10, 200), _a("a", 10, 200)], ["a"]),
    # what is needed shrinks as victims are taken: c (300) is closest to
    # 400, then b (100) is exactly the 100 still needed; filterSuperset
    # walks them farthest from the ask first and needs both
    ("two", 50, (400, 100, 0), (0, 500, 0),
     [_a("a", 10, 150), _a("b", 10, 100), _a("c", 10, 300)], ["b", "c"]),
    # filterSuperset: the small one of the lower priority was taken first;
    # the large one, farther from the ask, covers it alone
    ("superset", 50, (300, 100, 0), (0, 500, 0),
     [_a("small", 10, 100), _a("big", 20, 700)], ["big"]),
    # ... and does not where the large one is the closer match
    ("no_superset", 50, (300, 100, 0), (0, 500, 0),
     [_a("small", 10, 100), _a("big", 20, 300)], ["small", "big"]),
    # a second priority group is opened only when the first is used up,
    # a third never
    ("next_group", 70, (300, 100, 0), (0, 500, 0),
     [_a("p10", 10, 100), _a("p30", 30, 250), _a("p50", 50, 999)],
     ["p10", "p30"]),
    # everything evictable does not cover it
    ("not_enough", 50, (500, 100, 0), (0, 500, 0),
     [_a("a", 10, 200), _a("b", 10, 200), _a("c", 45, 900)], None),
], ids=lambda v: v if isinstance(v, str) else None)
def test_victim_search_by_hand(case, job, ask, room, allocs, want):
    got = pref.preempt_for_task_group(job, ask, room, allocs)
    assert (got if got is None else [a["id"] for a in got]) == want


def test_scores_of_a_preempting_placement_by_hand():
    # 3,900 / 7,936 schedulable; 3,800 / 4,000 in use, the ask 200 / 256,
    # one victim of 200 / 256: utilisation after = 3,800 / 4,000
    b, p = pref.preempting_scores(
        (3800, 4000, 0), (200, 256, 0), TOTALS, [_a("v", 10, 200, 256)])
    want = (20 - 10 ** (1 - 3800 / 3900) - 10 ** (1 - 4000 / 7936)) / 18
    assert float(b) == pytest.approx(want, rel=1e-12)
    # net priority = max + sum / max = 10 + 1
    assert float(p) == pytest.approx(
        1 / (1 + np.exp(0.0048 * (11 - 2048))), rel=1e-12)
    # two victims of 10 and 30: 30 + 40 / 30
    assert pref.net_priority([10, 30]) == pytest.approx(30 + 40 / 30)
    # binpack, preemption and an affinity of 0.5: the mean of three
    assert float(pref.final_score(0.9, 0.8, 0, 1, 0.5)) == pytest.approx(
        (0.9 + 0.8 + 0.5) / 3)
    assert float(pref.final_score(0.9, None, 2, 4, 0.0)) == pytest.approx(
        (0.9 - 3 / 4) / 2)


def test_select_takes_a_node_with_room_first():
    used = np.array([[3800, 4000, 0], [1000, 1000, 0], [3800, 5000, 0]], float)
    allocs = {0: [_a("a", 10, 200, 256)], 2: [_a("b", 10, 200, 256)]}
    row, victims, _ = pref.select(
        50, (200, 256, 0), 1, used, TOTALS, [True] * 3, 0.0, 0, allocs)
    assert (row, victims) == (1, [])
    used[1] = (3850, 1000, 0)    # no room anywhere: the fuller node wins
    row, victims, scores = pref.select(
        50, (200, 256, 0), 1, used, TOTALS, [True] * 3, 0.0, 0, allocs)
    assert row == 2 and [v["id"] for v in victims] == ["b"]
    assert set(scores) == {"binpack", "preemption", "final"}
    # nothing to evict on any eligible node: no placement
    assert pref.select(50, (200, 256, 0), 1, used, TOTALS,
                       [False, True, False], 0.0, 0, allocs) is None


# -- the new readers, on a recorded run ---------------------------------------------------

def _span(name, ts, dur, span_id=0, parent=0):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur, "span": span_id,
            "parent": parent, "args": {}}


@pytest.fixture()
def run():
    return {
        "seconds": 10.0, "loop": "closed",
        "client": {"t0": 1000.0, "t_end": 1010.0},
        "attempted": [
            {"ok": True, "placed": 1003.0}, {"ok": True, "placed": 1009.0},
            {"ok": True, "placed": 1011.0},    # placed in the drain
            {"ok": False, "placed": None}],
        "spans": [
            _span("sched.dispatch", 1001.0, 0.020, span_id=1),
            _span("sched.preempt", 1001.02, 0.003, span_id=2),
            _span("trace.child", 1001.021, 0.001, parent=2),
            _span("sched.preempt", 1002.0, 0.004, span_id=3)],
        "m0": {"nomad.kernel.preempt_picks_total": 100,
               "nomad.kernel.picks_placed_total": 200,
               "nomad.plan.preempted_allocs": 10,
               "nomad.sched.preempt_reentries": 5,
               "nomad.worker.evals_processed": 40,
               "nomad.kernel.launches{path=fused}": 10,
               "nomad.kernel.fused_lanes": 40,
               "nomad.kernel.scan_steps_total": 20},
        "m1": {"nomad.kernel.preempt_picks_total": 400,
               "nomad.kernel.picks_placed_total": 600,
               "nomad.plan.preempted_allocs": 16,
               "nomad.sched.preempt_reentries": 35,
               "nomad.worker.evals_processed": 60,
               "nomad.kernel.launches{path=fused}": 110,
               "nomad.kernel.fused_lanes": 840,
               "nomad.kernel.scan_steps_total": 420},
    }


def read(name, run):
    return importlib.import_module(name).read(run)


@pytest.mark.parametrize("name,want", [
    ("preempt_picks_share", 75.0),            # 300 of 400 picks
    ("evictions_per_op", 3.0),                # 6 evictions, 2 ops in window
    ("sched_preempt_ms", (2.0 + 4.0) / 20),   # self time, per eval
    ("preempt_reentries_per_eval", 1.5),      # 30 re-entries, 20 evals
])
def test_new_reader_on_a_recorded_run(run, name, want):
    assert read(name, run) == pytest.approx(want)


@pytest.mark.parametrize("name", [
    "preempt_picks_share", "evictions_per_op", "sched_preempt_ms",
    "preempt_reentries_per_eval", "kernel_preempt_share",
    "preempt_place_batch_roofline"])
def test_new_reader_finds_nothing_on_a_program_without_the_counters(run, name):
    """The parent of PR 37 has none of the counters, spans and scopes."""
    for m in (run["m0"], run["m1"]):
        for k in list(m):
            if "preempt" in k or "picks_placed" in k:
                del m[k]
    run["spans"] = [s for s in run["spans"] if s["name"] != "sched.preempt"]
    run["device"] = None
    assert read(name, run) is None


@pytest.fixture()
def xplane(tmp_path, monkeypatch):
    """One launch of the placement program: 6 ms under place_scan, 2 of
    them in the preemption stage (the tables, and the stage of a step)."""
    scan = "jit(_fused_place_batch_impl)/vmap(place_scan)/while/body/closed_call/"
    metas = [
        op_meta(1, "jit__fused_place_batch_impl(77)"),
        op_meta(2, "%fusion.1 = ...", op_name=scan + "score/feasibility/and"),
        op_meta(3, "%fusion.2 = ...", op_name=scan + "score/preemption/min"),
        op_meta(4, "%fusion.3 = ...",
                op_name=scan + "score/vmap(preemption)/cumsum"),
    ]
    stat_names = [entry(1, field(1, 1) + field(2, "tf_op"))]
    ops = [event(2, 0, 4 * MS), event(3, 4 * MS, 1 * MS),
           event(4, 5 * MS, 1 * MS), event(2, 6 * MS, 2 * MS)]
    device = (field(2, "/device:TPU:0")
              + field(3, line("XLA Modules", [event(1, 0, 10 * MS)]))
              + field(3, line("XLA Ops", ops))
              + b"".join(field(4, m) for m in metas)
              + b"".join(field(5, s) for s in stat_names))
    d = tmp_path / "plugins" / "profile" / "2026_01_01"
    d.mkdir(parents=True)
    (d / "vm.xplane.pb").write_bytes(field(1, device))
    monkeypatch.setattr(stage_reduce, "TRACE_DIR", str(tmp_path))


def test_kernel_preempt_share(run, xplane):
    run["device"] = {"busy_s": 1.0}
    run["cfg"] = {"placement_programs": ["fused_place_batch"]}
    assert read("kernel_preempt_share", run) == pytest.approx(25.0)
    run["cfg"] = {"placement_programs": ["no_such_program"]}
    assert read("kernel_preempt_share", run) is None


def test_preempt_place_batch_roofline(run):
    import roofline
    import roofline_preempt

    rows, matrix = 10240, 48.8e6
    run.update({
        "device": {"launches": 50, "kernel_s": 0.25, "devices": 1},
        "matrix_bytes": matrix, "device_kind": "TPU v5 lite",
        "cfg": {"node_capacity": rows}})
    # 8 lanes and 4 steps a launch, 5 ms a launch
    work = roofline_preempt.launch_work(matrix, rows, 8.0, 4.0)
    plain = roofline.launch_work(matrix, rows, 8.0)
    assert work["bytes"] == pytest.approx(
        plain["bytes"] + rows * 16 * 3 * 4 + 8 * 4 * rows * 20)
    assert work["flop"] > plain["flop"]
    got = read("preempt_place_batch_roofline", run)
    assert got == pytest.approx(100 * (work["bytes"] / 819e9) / 0.005)
    assert 0 < got < 100
    # more than the launch's own share: the stage's bytes are in it
    run["m1"]["nomad.kernel.preempt_picks_total"] = 100  # no preempting pick
    assert read("preempt_place_batch_roofline", run) is None
