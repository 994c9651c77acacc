"""What ``check.py`` holds an ``again`` operation to (PR 43), on a hand-made
read-back: 0 on the sound one, 1 for each planted fault; and that the numbers
a run compares follow from its traffic, not from its records."""

import copy

import numpy as np
import pytest

import check
import reference as ref
import traffic

CLUSTER = {
    "datacenters": 4, "node_classes": 6, "racks": 32,
    "node_resources": {"cpu": 4000, "memory_mb": 8192, "disk_mb": 102400},
    "node_reserved": {"cpu": 100, "memory_mb": 256, "disk_mb": 0},
}
N = 48
TOTALS = ref.node_totals(CLUSTER)


def _alloc(aid, job, row, ask, index, scores=None):
    return {
        "id": aid, "job_id": job, "task_group": "g",
        "node_id": check.node_id(row), "desired_status": "run",
        "create_index": index,
        "resources": {"cpu": ask[0], "memory_mb": ask[1], "disk_mb": ask[2]},
        "metrics": {"scores": {check.node_id(row): scores or {}}},
    }


class World:
    """48 nodes.  Before the window: resident job res-000000 (two instances
    on nodes 10 and 11, version 0).  In the window: one new service job on
    the fullest node (scored by the reference), two ``again`` operations on
    the resident job."""

    def __init__(self):
        self.t = dict(traffic.load("backlog"), tenants=1,
                      register_again_fraction=0.5, resident_jobs=1)
        self.cfg = {"cluster": CLUSTER, "nodes": N}
        self.used0 = np.tile(
            np.array([[1500.0, 3000.0, 900.0]], np.float32), (N, 1))
        self.used0[:, 0] += np.arange(N) * 7.0
        self.nodes = {}
        for i in range(N):
            e = ref.expected_node(i, CLUSTER)
            self.nodes[check.node_id(i)] = {
                "id": check.node_id(i), "status": "ready",
                "scheduling_eligibility": "eligible",
                "datacenter": e["datacenter"], "node_class": e["node_class"],
                "attributes": e["attributes"],
                "resources": CLUSTER["node_resources"],
                "reserved": CLUSTER["node_reserved"]}
        s0 = self.t["shapes"][0]
        ask = [s0["cpu"], s0["memory_mb"], 300]
        self.allocs = [_alloc("r0", "res-000000", 10, ask, 100),
                       _alloc("r1", "res-000000", 11, ask, 100)]
        self.state = {"resident": {"res-000000": {
            "namespace": "default", "width": 2, "version": 0,
            "allocs": {"r0": check.node_id(10), "r1": check.node_id(11)}}}}
        self.jobs = [{"id": "res-000000", "version": 0}]
        b = float(ref.binpack_score(self.used0[N - 1], ask, TOTALS))
        self.allocs.append(_alloc(
            "n0", "op-000000", N - 1, ask, 200,
            {"binpack": b, "final": float(ref.final_score(b, 0, 1, 0.0))}))
        base = {"namespace": "default", "status": "placed", "registers": 1}
        self.records = [
            {**base, "i": 0, "job_id": "op-000000", "kind": "new",
             "type": "service", "width": 1, "shape": 0},
            {**base, "i": 1, "job_id": "res-000000", "kind": "again",
             "type": "service", "width": 2, "shape": 0},
            {**base, "i": 2, "job_id": "res-000000", "kind": "again",
             "type": "service", "width": 2, "shape": 0},
        ]

    def get(self, path):
        if path == "/v1/nodes":
            return list(self.nodes.values())
        if path.startswith("/v1/node/"):
            return self.nodes[path.rsplit("/", 1)[1]]
        if path == "/v1/allocations?namespace=default":
            return copy.deepcopy(self.allocs)
        if path == "/v1/jobs?namespace=default&prefix=res-":
            return copy.deepcopy(self.jobs)
        raise AssertionError(path)

    def decide(self):
        return check.decide(self.get, self.cfg, self.t, self.records,
                            self.used0, seed=1, state=self.state)

    def alloc(self, aid):
        return next(a for a in self.allocs if a["id"] == aid)


def test_the_sound_read_back_reads_0_everywhere():
    correct, numbers, lines = World().decide()
    assert correct, lines
    assert [k for k in check.LIMITS if k in numbers] == list(check.LIMITS)
    assert all(numbers[k] == 0 for k in (
        "count_mismatch", "constraint_violations", "overcommitted_nodes",
        "resubmit_version_bumped", "resubmit_allocs_replaced"))
    assert numbers["score_gap"] < 1e-12
    assert any("resubmit_version_bumped = 0 (limit 0)" in l for l in lines)
    assert any("2 of the operations registered 1 resident jobs again" in l
               for l in lines)
    # The new job alone is sampled: a resident job's placement was made
    # before the window's start.
    assert any(", 1 sampled placement decisions" in l for l in lines), lines


def _bumped(w):          # the re-registration made a new version
    w.jobs[0]["version"] = 1


def _replaced(w):        # ... or placed one allocation anew, on another node
    w.alloc("r1").update(desired_status="stop")
    a = copy.deepcopy(w.alloc("r1"))
    a.update(id="r1x", desired_status="run", node_id=check.node_id(12),
             create_index=205)
    w.allocs.append(a)


def _stopped(w):         # ... or stopped one and placed nothing
    w.alloc("r0").update(desired_status="stop")


def _added(w):           # ... or added one beside those that were there
    a = copy.deepcopy(w.alloc("r1"))
    a.update(id="r2", node_id=check.node_id(12), create_index=205)
    w.allocs.append(a)


@pytest.mark.parametrize("fault,reads", [
    (_bumped, {"resubmit_version_bumped": 1}),
    (_replaced, {"resubmit_allocs_replaced": 1}),
    (_stopped, {"resubmit_allocs_replaced": 1, "count_mismatch": 1}),
    (_added, {"resubmit_allocs_replaced": 1, "count_mismatch": 1}),
], ids=lambda f: getattr(f, "__name__", ""))
def test_each_planted_fault_reads_1(fault, reads):
    w = World()
    fault(w)
    correct, numbers, lines = w.decide()
    assert not correct
    exact = ("count_mismatch", "constraint_violations",
             "resubmit_version_bumped", "resubmit_allocs_replaced")
    assert {k: numbers[k] for k in exact if numbers[k]} == reads, lines
    for k in reads:
        assert any(l.startswith(f"check: over its limit: {k}")
                   for l in lines), lines


def test_a_resident_job_no_again_operation_placed_on_is_not_held():
    """Only an ``again`` operation that ended placed holds its job."""
    w = World()
    w.records.append(dict(w.records[1], i=3, job_id="res-000001",
                          status="failed"))
    w.jobs.append({"id": "res-000001", "version": 3})
    correct, numbers, lines = w.decide()
    assert correct and numbers["resubmit_version_bumped"] == 0, lines


def _no_again_placed(w):  # every ``again`` operation failed
    for r in w.records:
        if r["kind"] == "again":
            r["status"] = "failed"


def _kinds_lost(w):       # the records say nothing of their kind
    for r in w.records:
        del r["kind"]


def _resident_lost(w):    # run.py handed over no read-back of the set
    w.state = {}


@pytest.mark.parametrize("fault", [_no_again_placed, _kinds_lost,
                                   _resident_lost],
                         ids=lambda f: f.__name__)
def test_a_run_that_compared_nothing_of_the_kinds_is_not_correct(fault):
    """The traffic registers jobs again, so both numbers are required: a
    run that cannot show one ``again`` operation held is not correct,
    though every number it has reads 0."""
    w = World()
    fault(w)
    correct, numbers, lines = w.decide()
    assert not correct
    assert set(check.RESUBMIT) <= set(numbers)
    if fault is _resident_lost:
        assert numbers["resubmit_version_bumped"] == 1
        assert numbers["resubmit_allocs_replaced"] == 1
    else:
        assert all(numbers[k] == 0 for k in check.RESUBMIT)
        assert any("no such operation ended placed" in l for l in lines)


def test_the_numbers_compared_follow_from_the_traffic():
    w = World()
    assert check.expected_numbers(w.t) == list(check.LIMITS)
    del w.t["register_again_fraction"], w.t["resident_jobs"]
    w.records = [r for r in w.records if r["kind"] == "new"]
    for r in w.records:
        del r["kind"]
    correct, numbers, lines = w.decide()
    assert correct, lines
    assert check.expected_numbers(w.t) == [
        k for k in check.LIMITS if k not in check.RESUBMIT]
    assert set(numbers) == set(check.expected_numbers(w.t))
    assert not any("resubmit" in l for l in lines)
