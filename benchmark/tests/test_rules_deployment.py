"""The ``c2m-10k-rules`` deployment on a CPU at a tiny size: the cell from
files alone through ``run.py --rehearse``; ``rules_reference`` on hand-made
cases of every operator, the implicit spread target and a negative
affinity; ``rules_check`` on a hand-made read-back, sound and with one fault
of each kind planted; the four new readers on a recorded run; the traffic
file against ``backlog.json``; every shape PUT to a live server and read
back."""

import copy
import importlib
import json
import os
import subprocess
import sys
import urllib.request

import numpy as np
import pytest

from conftest import BENCH, ROOT

sys.path.insert(0, os.path.join(BENCH, "deployments"))

import check  # noqa: E402
import reference as ref  # noqa: E402
import roofline  # noqa: E402
import roofline_rules  # noqa: E402
import rules_check  # noqa: E402
import rules_reference as rules  # noqa: E402
import stage_reduce  # noqa: E402
import traffic  # noqa: E402
from test_readers import MS, entry, event, field, line, op_meta  # noqa: E402
from test_traffic import _digest  # noqa: E402

CELL = "c2m-10k-rules.rules-backlog"
ENV = dict(os.environ, JAX_PLATFORMS="cpu",
           JAX_COMPILATION_CACHE_DIR=os.path.join(ROOT, ".jax_cache"))
with open(os.path.join(BENCH, "configs", "c2m-10k-rules.json")) as _fh:
    CFG = json.load(_fh)
CLUSTER = CFG["cluster"]
TRAFFIC = traffic.load("rules-backlog")
SHAPE = {s["name"]: i for i, s in enumerate(TRAFFIC["shapes"])}


# -- end to end ------------------------------------------------------------------

@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    dump = tmp_path_factory.mktemp("dump") / "dump.json"
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark/run.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 44), "--seconds", "5",
         "--trace", "0", "--rehearse", "--check-dump", str(dump)],
        capture_output=True, text=True, env=ENV, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    return p, dump


def test_the_cell_from_files_alone_reads_correct(rehearsal):
    p, dump = rehearsal
    out = p.stdout.strip().splitlines()
    result = json.loads(out[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 100
    assert list(result["compared"]) == list(rules_check.LIMITS)
    for k, limit in rules_check.LIMITS.items():
        assert result["compared"][k]["limit"] == limit
        assert any(l.startswith(f"check: {k} = ") and
                   l.endswith(f"(limit {limit:g})") for l in out), k
    for k in ("distinct_hosts_violations", "distinct_property_violations",
              "constraint_violations"):
        assert result["compared"][k]["value"] == 0
    compared = [l for l in out if l.startswith("check: compared")][0]
    assert int(compared.split("; ")[1].split(" jobs wider")[0]) > 5
    assert "with a spread" in compared
    detail = json.loads(
        [l for l in out if l.startswith("detail: ")][-1][len("detail: "):])
    assert detail["setup"]["install_s"] > 0
    assert detail["compiles_in_window"] == 0
    samples = json.load(open(dump))["samples"]
    assert {s["shape"] for s in samples} >= {"r1", "r2", "r5", "r6"}
    assert all("spread_final" in s for s in samples if s["spread"])
    # the control: the reference in bfloat16 is not correct on this dump
    c = subprocess.run(
        [sys.executable, os.path.join(BENCH, "control.py"), str(dump)],
        capture_output=True, text=True, env=ENV, timeout=300)
    assert c.returncode == 0, c.stdout + c.stderr


# -- the traffic file ------------------------------------------------------------

def test_the_traffic_is_backlogs_with_other_shapes():
    base = traffic.load("backlog")
    differ = {k for k in set(base) | set(TRAFFIC) if base.get(k) != TRAFFIC.get(k)}
    assert differ <= {"shapes", "why", "max_rate_per_s", "name"}
    assert [s["name"] for s in TRAFFIC["shapes"]] == [f"r{i}" for i in range(8)]
    assert [(s["cpu"], s["memory_mb"]) for s in TRAFFIC["shapes"]] == [
        (s["cpu"], s["memory_mb"]) for s in base["shapes"]]
    ops = traffic.schedule(TRAFFIC, 7, 50)
    counts = np.bincount([o["shape"] for o in ops[:512]], minlength=8)
    assert set(counts) == {64}  # equal shares, block by block
    assert TRAFFIC["max_rate_per_s"] % 50 == 0


@pytest.mark.parametrize("name,want", [
    ("steady", ["dbd4e0215117fca1", "9c5b4a374cff0773"]),
    ("backlog", ["4b2a222a08b12aeb", "0e35a2a754e91e60"]),
    ("backlog-x4", ["4b2a222a08b12aeb", "0e35a2a754e91e60"]),
    ("tiers-backlog", ["0f01b3959f660c85", "38f9281dd1cd4ab9"]),
])
def test_the_four_files_that_were_there_are_unchanged(name, want):
    assert [_digest(name, s) for s in (7, 2 ** 31 + 4300)] == want


@pytest.fixture(scope="module")
def agent():
    os.environ["NOMAD_TPU_FAKE_DEVICE"] = "1"
    try:
        from nomad_tpu import cli, simcluster

        agent = cli.build_agent(cli.build_parser().parse_args(
            ["agent", "--server-only", "--port", "0", "--workers", "2"]))
        agent.start()
        for i in range(8):
            node = simcluster.sim_node(i)
            node.id = check.node_id(i)
            agent.server.register_node(node)
        yield agent
        agent.shutdown()
    finally:
        del os.environ["NOMAD_TPU_FAKE_DEVICE"]


@pytest.mark.parametrize("shape", range(8))
def test_every_shape_is_put_in_the_servers_wire_form_and_read_back(agent, shape):
    """``traffic.load`` validates nothing and ``job_payload`` copies a
    shape's rules as they stand: a key the server does not know would be
    dropped in silence and the rule with it."""
    s = TRAFFIC["shapes"][shape]
    op = {"namespace": "default", "width": 2, "type": "service",
          "priority": 50, "shape": shape, "job_id": f"put-{s['name']}"}
    payload = traffic.job_payload(TRAFFIC, op)
    req = urllib.request.Request(
        agent.rpc_addr + "/v1/jobs", method="PUT",
        data=json.dumps({"Job": payload}).encode())
    with urllib.request.urlopen(req, timeout=30) as r:
        assert json.loads(r.read()).get("EvalID")
    job = agent.server.store.job_by_id("default", op["job_id"])
    tg = job.task_groups[0]
    assert job.datacenters == s["datacenters"]
    assert [(c.l_target, c.operand, c.r_target) for c in tg.constraints] == [
        (c["l_target"], c["operand"], c["r_target"]) for c in s["constraints"]]
    assert [(a.l_target, a.operand, a.r_target, a.weight)
            for a in tg.affinities] == [
        (a["l_target"], a["operand"], a["r_target"], a["weight"])
        for a in s["affinities"]]
    assert [(x.attribute, x.weight, [(t.value, t.percent) for t in x.targets])
            for x in tg.spreads] == [
        (x["attribute"], x["weight"],
         [(t["value"], t["percent"]) for t in x["targets"]])
        for x in s["spreads"]]


# -- rules_reference, by hand -------------------------------------------------------

def test_the_cluster_as_the_configuration_states_it():
    a = rules.expected_attributes(7, CLUSTER)
    assert a["meta.rack"] == "r7" and a["attr.unique.hostname"] == "sim-000007"
    assert a["attr.kernel.version"] == "5.4.0"       # 7 % 5 = 2
    assert "meta.cached_binaries" not in a           # 7 % 4 = 3: ""
    assert rules.expected_attributes(32, CLUSTER)["meta.rack"] == "r0"
    t = rules.attr_tables(960, CLUSTER)
    assert (t["${meta.rack}"] == t["${attr.rack}"]).all()
    keys = ["${node.datacenter}", "${node.class}", "${attr.rack}",
            "${attr.platform.tpu.type}", "${attr.kernel.version}",
            "${meta.cached_binaries}"]
    classes = {tuple(str(t[k][i]) for k in keys) for i in range(960)}
    assert len(classes) == CLUSTER["computed_classes"] == 480


@pytest.mark.parametrize("value,operand,want,holds", [
    ("v5e", "=", "v5e", True), ("v5p", "=", "v5e", False), ("", "=", "v5e", False),
    ("class-1", "!=", "class-1", False), ("class-2", "!=", "class-1", True),
    ("", "!=", "class-1", True),
    ("1", "is_set", "", True), ("", "is_set", "", False),
    ("", "is_not_set", "", True),
    ("4.15.0", "version", ">= 4.19, < 5.15", False),
    ("4.19.0", "version", ">= 4.19, < 5.15", True),
    ("5.10.0", "version", ">= 4.19, < 5.15", True),
    ("5.15.0", "version", ">= 4.19, < 5.15", False),
    ("22.04", "version", ">= 20.04", True), ("18.04", "version", ">= 20.04", False),
    ("5.4", "version", "= 5.4.0", True), ("", "version", ">= 1", False),
    ("junk", "version", ">= 1", False), ("5.4.0", "version", "!= 5.4", False),
    ("sim-000012", "regexp", "[02468]$", True),
    ("sim-000013", "regexp", "[02468]$", False),
    ("sim-000013", "regexp", "^sim-0+13$", True), ("", "regexp", ".*", False),
    ("redis,cassandra,haproxy", "set_contains", "redis,cassandra", True),
    ("redis", "set_contains", "redis,cassandra", False),
    ("cassandra, haproxy", "set_contains", "haproxy , cassandra", True),
    ("", "set_contains", "redis", False),
])
def test_every_operator_by_hand(value, operand, want, holds):
    assert rules._holds(value, operand, want) is holds
    got = rules.match(np.array([value, "other", value]), operand, want)
    assert got[0] == got[2] == holds


def test_eligibility_of_the_shapes_on_the_stated_cluster():
    t = rules.attr_tables(960, CLUSTER)
    idx = np.arange(960)
    r3 = TRAFFIC["shapes"][SHAPE["r3"]]
    want = np.isin(idx % 5, (1, 2, 3)) & (idx % 3 != 0)
    np.testing.assert_array_equal(
        rules.eligible(t, r3["datacenters"], r3["constraints"]), want)
    r4 = TRAFFIC["shapes"][SHAPE["r4"]]  # distinct_hosts is no node predicate
    np.testing.assert_array_equal(
        rules.eligible(t, r4["datacenters"], r4["constraints"]), idx % 4 == 0)
    r5 = TRAFFIC["shapes"][SHAPE["r5"]]
    np.testing.assert_array_equal(
        rules.eligible(t, r5["datacenters"], r5["constraints"]), idx % 2 == 0)
    r7 = TRAFFIC["shapes"][SHAPE["r7"]]
    np.testing.assert_array_equal(
        rules.eligible(t, r7["datacenters"], r7["constraints"]),
        (idx % 4 < 2) & (idx % 6 != 1) & (idx % 3 != 0))
    with pytest.raises(NotImplementedError):
        rules.eligible(t, ["dc1"], [{"l_target": "${node.class}",
                                     "operand": "<", "r_target": "x"}])


def test_a_negative_affinity_by_hand():
    t = rules.attr_tables(12, CLUSTER)
    r2 = TRAFFIC["shapes"][SHAPE["r2"]]["affinities"]
    aff = rules.affinity_term(t, r2)          # class-0 at weight -50
    assert aff[0] == aff[6] == -1.0 and aff[1] == 0.0
    r6 = TRAFFIC["shapes"][SHAPE["r6"]]["affinities"]
    aff = rules.affinity_term(t, r6)          # v5e +50, class-3 -30, over 80
    assert aff[1] == pytest.approx(50 / 80)   # v5e, class-1
    assert aff[3] == pytest.approx(-30 / 80)  # v5p (3 % 3 == 0), class-3
    assert aff[9] == pytest.approx(-30 / 80) and aff[0] == 0.0
    assert aff[4] == pytest.approx(50 / 80)
    # the mean takes a negative term like any other
    assert rules.final_score(0.5, 0, 4, -1.0, 0.0) == pytest.approx(-0.25)
    assert rules.final_score(0.5, 0, 4, 0.0, 0.0) == pytest.approx(0.5)
    assert rules.final_score(0.5, 1, 4, 0.0, -0.2) == pytest.approx(
        (0.5 - 2 / 4 - 0.2) / 3)


def test_spread_targets_and_the_implicit_target_by_hand():
    r2 = TRAFFIC["shapes"][SHAPE["r2"]]["spreads"]  # dc1 50 %, dc2 30 %, rest
    none = [{}]
    # count 10: desired 5, 3, implicit 2; nothing placed: (d - 1) / d
    assert rules.spread_boost(r2, 10, ["dc1"], none) == pytest.approx(0.8)
    assert rules.spread_boost(r2, 10, ["dc2"], none) == pytest.approx(2 / 3)
    assert rules.spread_boost(r2, 10, ["dc3"], none) == pytest.approx(0.5)
    assert rules.spread_boost(r2, 10, ["dc4"], none) == pytest.approx(0.5)
    # over the target it goes negative; met exactly it is 0 and no term
    assert rules.spread_boost(r2, 10, ["dc2"], [{"dc2": 3}]) == pytest.approx(-1 / 3)
    assert rules.spread_boost(r2, 10, ["dc1"], [{"dc1": 4, "dc2": 1}]) == 0.0
    # the implicit target counts per value, not over the rest together
    assert rules.spread_boost(r2, 10, ["dc4"], [{"dc3": 2}]) == pytest.approx(0.5)
    # targets that sum to the count leave no implicit target: -1 elsewhere
    r6 = TRAFFIC["shapes"][SHAPE["r6"]]["spreads"]
    got = rules.spread_boost(r6, 5, ["r1", "dc1"], [{}, {}])
    assert got == pytest.approx(0.0 + -1.0)
    got = rules.spread_boost(r6, 5, ["r1", "dc3"], [{"r1": 1, "r2": 2}, {"dc3": 1}])
    # even: at the minimum (1) with a maximum of 2: (2 - 1) / 1; target:
    # (3 - 2) / 3 at half the weight
    assert got == pytest.approx(1.0 + (1 / 3) * 0.5)
    # a node without the attribute takes the greatest penalty
    assert rules.spread_boost(r2, 10, [""], none) == -1.0


def test_even_spread_by_hand():
    r0 = TRAFFIC["shapes"][SHAPE["r0"]]["spreads"]
    assert rules.spread_boost(r0, 4, ["dc1"], [{}]) == 0.0          # nothing yet
    assert rules.spread_boost(r0, 4, ["dc1"], [{"dc1": 1}]) == -1.0  # even: worst
    assert rules.spread_boost(r0, 4, ["dc2"], [{"dc1": 1}]) == 1.0   # (1 - 0) / 1
    use = [{"dc1": 2, "dc2": 1}]
    assert rules.spread_boost(r0, 4, ["dc2"], use) == 1.0           # (2 - 1) / 1
    assert rules.spread_boost(r0, 4, ["dc1"], use) == -1.0          # (1 - 2) / 1
    assert rules.spread_boost(r0, 4, ["dc3"], use) == 1.0           # (1 - 0) / 1


def test_the_distinct_rules_by_hand():
    t = rules.attr_tables(96, CLUSTER)
    assert rules.distinct_hosts_violations([1, 2, 3]) == 0
    assert rules.distinct_hosts_violations([1, 2, 2, 2]) == 2
    one = {"l_target": "${meta.rack}", "operand": "distinct_property",
           "r_target": ""}
    two = dict(one, r_target="2")
    assert rules.distinct_limit(one) == 1 and rules.distinct_limit(two) == 2
    assert rules.distinct_property_violations(t, one, [0, 1, 2]) == 0
    assert rules.distinct_property_violations(t, one, [0, 32, 64, 1]) == 2
    assert rules.distinct_property_violations(t, two, [0, 32, 64, 1]) == 1
    # a node without the property is one violation itself
    none = dict(one, l_target="${meta.cached_binaries}", r_target="9")
    assert rules.distinct_property_violations(t, none, [0, 3, 7]) == 2
    blocked = rules.blocked_by_distinct_property(t, [two], [0, 32, 5])
    assert blocked[64] and not blocked[5] and not blocked[37]


# -- rules_check on a hand-made read-back ----------------------------------------------

N = 96


class World:
    """A read-back of ``N`` nodes and a few placed jobs, sound by
    construction: every recorded score is the reference's own."""

    def __init__(self):
        self.cfg = dict(copy.deepcopy(CFG), nodes=N)
        self.used0 = np.tile(np.array([[400.0, 800.0, 300.0]]), (N, 1))
        self.totals = ref.node_totals(CLUSTER)
        self.tables = rules.attr_tables(N, CLUSTER)
        self.allocs, self.records = [], []
        self.index = 100
        for k, (shape, rows) in enumerate([
            ("r1", [2, 3, 4, 5]),          # four racks
            ("r0", [1, 7]),                # two nodes, two datacenters
            ("r3", [1, 2]),                # kernels 4.19, 5.4, both v5e
            ("r5", [0, 32, 2]),            # even hosts; rack r0 twice
            ("r2", [8]), ("r6", [10]), ("r4", [4, 16]), ("r7", [4]),
        ]):
            self.place(f"op-{k:06d}", shape, rows)

    def place(self, jid, shape_name, rows):
        si = SHAPE[shape_name]
        shape = TRAFFIC["shapes"][si]
        self.index += 1
        self.records.append({
            "job_id": jid, "status": "placed", "width": len(rows),
            "shape": si, "i": len(self.records), "namespace": "default",
            "registers": 1})
        ask = np.array([shape["cpu"], shape["memory_mb"], 300.0])
        aff = rules.affinity_term(self.tables, shape["affinities"])
        for k, row in enumerate(rows):
            b = float(ref.binpack_score(self.used0[row], ask, self.totals))
            spread = 0.0
            if shape["spreads"]:
                cols = [rules.column(self.tables, s["attribute"])
                        for s in shape["spreads"]]
                held = []
                for c in cols:
                    use = {}
                    for r in rows[:k]:
                        use[str(c[r])] = use.get(str(c[r]), 0) + 1
                    held.append(use)
                spread = float(rules.spread_boost(
                    shape["spreads"], len(rows), [str(c[row]) for c in cols],
                    held))
            final = float(rules.final_score(
                b, 0, len(rows), aff[row], spread))
            nid = check.node_id(row)
            self.allocs.append({
                "id": f"{jid}-{k}", "job_id": jid, "node_id": nid,
                "task_group": "g", "desired_status": "run",
                "create_index": self.index, "namespace": "default",
                "resources": {"cpu": shape["cpu"],
                              "memory_mb": shape["memory_mb"], "disk_mb": 300},
                "metrics": {"scores": {nid: {"binpack": b, "final": final}}}})

    def get(self, path):
        if path == "/v1/nodes":
            return [dict(ref.expected_node(i, CLUSTER), id=check.node_id(i),
                         status="ready", scheduling_eligibility="eligible")
                    for i in range(N)]
        if path.startswith("/v1/node/"):
            i = int(path.rsplit("-", 1)[1])
            want = ref.expected_node(i, CLUSTER)
            attrs, meta = dict(want["attributes"]), {}
            for name, v in rules.expected_attributes(i, CLUSTER).items():
                kind, key = name.split(".", 1)
                (meta if kind == "meta" else attrs)[key] = v
            return {"attributes": attrs, "meta": meta,
                    "resources": CLUSTER["node_resources"],
                    "reserved": CLUSTER["node_reserved"]}
        if path.startswith("/v1/allocations?namespace="):
            ns = path.split("=")[1]
            return [a for a in self.allocs if a["namespace"] == ns]
        raise AssertionError(path)

    def decide(self):
        return rules_check.decide(
            self.get, self.cfg, TRAFFIC, self.records, self.used0, 44)


def test_the_check_passes_a_sound_read_back():
    correct, numbers, lines = World().decide()
    assert correct, lines
    assert numbers["score_gap"] < 1e-9
    assert all(numbers[k] == 0 for k in rules_check.LIMITS
               if k not in ("score_gap", "rank_gap"))


def _move(w, alloc_id, row):
    a = next(a for a in w.allocs if a["id"] == alloc_id)
    score = a["metrics"]["scores"].pop(a["node_id"])
    a["node_id"] = check.node_id(row)
    a["metrics"]["scores"][a["node_id"]] = score


def _two_in_a_rack(w):       # a second alloc of the r1 job in rack r2
    _move(w, "op-000000-1", 34)


def _two_on_a_node(w):       # both allocs of the r0 job on one node
    _move(w, "op-000001-1", 1)


def _outside_the_range(w):   # kernel 4.15.0: under ">= 4.19"
    _move(w, "op-000002-0", 10)


def _regexp_miss(w):         # host name sim-000033 does not end even
    _move(w, "op-000003-1", 33)


def _third_in_a_rack(w):     # limit 2: a third alloc of the r5 job in r0
    _move(w, "op-000003-2", 64)


def _without_the_binaries(w):  # set_contains on a node that caches redis only
    _move(w, "op-000006-1", 17)


def _wrong_spread(w):        # the mean as if the spread were no term
    a = next(a for a in w.allocs if a["id"] == "op-000004-0")
    s = a["metrics"]["scores"][a["node_id"]]
    s["final"] = (s["binpack"] + 0.0) / 1.0


def _fingerprint(w):         # a node that lost an attribute rules read
    get = w.get

    def without(path):
        out = get(path)
        if path.startswith("/v1/node/"):
            out["meta"].pop("rack", None)
        return out
    w.get = without


@pytest.mark.parametrize("fault,number", [
    (_two_in_a_rack, "distinct_property_violations"),
    (_third_in_a_rack, "distinct_property_violations"),
    (_two_on_a_node, "distinct_hosts_violations"),
    (_outside_the_range, "constraint_violations"),
    (_regexp_miss, "constraint_violations"),
    (_without_the_binaries, "constraint_violations"),
    (_wrong_spread, "score_gap"),
    (_fingerprint, "nodes_wrong"),
])
def test_the_check_fails_a_read_back_with_one_fault(fault, number):
    w = World()
    fault(w)
    correct, numbers, lines = w.decide()
    assert not correct
    assert numbers[number] > rules_check.LIMITS[number], lines
    if number in ("distinct_property_violations", "distinct_hosts_violations"):
        assert numbers[number] == 1


def test_a_run_that_placed_no_wide_job_under_the_limit_compared_nothing():
    w = World()
    w.allocs = [a for a in w.allocs
                if not a["id"].startswith(("op-000000", "op-000003"))
                or a["id"].endswith("-0")]
    for r in w.records:
        if r["job_id"] in ("op-000000", "op-000003"):
            r["width"] = 1
    correct, numbers, lines = w.decide()
    assert not correct
    assert all(numbers[k] <= limit for k, limit in rules_check.LIMITS.items())
    assert any("nothing of it was compared" in l for l in lines)


# -- the new readers, on a recorded run ---------------------------------------------------

def _span(name, ts, dur, span_id=0, parent=0):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur, "span": span_id,
            "parent": parent, "args": {}}


@pytest.fixture()
def run():
    return {
        "seconds": 10.0, "loop": "closed", "traffic": TRAFFIC,
        "client": {"t0": 1000.0, "t_end": 1010.0},
        "spans": [_span("sched.feasibility", 1001.0, 0.0004, span_id=1),
                  _span("sched.feasibility", 1002.0, 0.0002, span_id=2),
                  _span("sched.feasibility", 1003.0, 0.0300, span_id=3),
                  _span("sched.dispatch", 1001.0, 0.020, span_id=4)],
        "m0": {"nomad.sched.host_walk_nodes_total": 0,
               "nomad.kernel.distinct_property_lanes_total": 5,
               "nomad.worker.evals_processed": 40,
               "nomad.kernel.launches{path=fused}": 10,
               "nomad.kernel.fused_lanes": 40,
               "nomad.kernel.scan_steps_total": 20},
        "m1": {"nomad.sched.host_walk_nodes_total": 0,
               "nomad.kernel.distinct_property_lanes_total": 105,
               "nomad.worker.evals_processed": 60,
               "nomad.kernel.launches{path=fused}": 110,
               "nomad.kernel.fused_lanes": 840,
               "nomad.kernel.scan_steps_total": 420},
    }


def read(name, run):
    return importlib.import_module(name).read(run)


def test_sched_feasibility_ms_and_host_walk_nodes_per_eval(run):
    assert read("sched_feasibility_ms", run) == pytest.approx(0.4)
    assert read("host_walk_nodes_per_eval", run) == 0.0
    run["m1"]["nomad.sched.host_walk_nodes_total"] = 200_000
    assert read("host_walk_nodes_per_eval", run) == pytest.approx(10_000.0)


@pytest.mark.parametrize("name", [
    "sched_feasibility_ms", "host_walk_nodes_per_eval",
    "kernel_feasibility_share", "rules_place_batch_roofline"])
def test_a_reader_finds_nothing_on_a_program_without_the_sources(run, name):
    """The parent of PR 44 has neither counter; an untraced run no spans
    and no device block."""
    for m in (run["m0"], run["m1"]):
        for k in list(m):
            if "host_walk" in k or "distinct_property" in k:
                del m[k]
    run["spans"] = None
    run["device"] = None
    assert read(name, run) is None


@pytest.fixture()
def xplane(tmp_path, monkeypatch):
    """One launch: 10 ms of leaf ops, 6 of them under a feasibility scope
    (the mask of a step, its distinct_property part, the scan's seeding)."""
    scan = "jit(_fused_place_batch_impl)/vmap(place_scan)/while/body/closed_call/"
    metas = [
        op_meta(1, "jit__fused_place_batch_impl(77)"),
        op_meta(2, "%fusion.1 = ...", op_name=scan + "score/feasibility/and"),
        op_meta(3, "%fusion.2 = ...",
                op_name=scan + "score/feasibility/distinct_property/ge"),
        op_meta(4, "%fusion.3 = ...", op_name=scan + "score/affinity_spread/sum"),
        op_meta(5, "%fusion.4 = ...",
                op_name="jit(_fused_place_batch_impl)/vmap(feasibility)/"
                        "distinct_property/eq"),
    ]
    stat_names = [entry(1, field(1, 1) + field(2, "tf_op"))]
    ops = [event(2, 0, 3 * MS), event(3, 3 * MS, 2 * MS),
           event(4, 5 * MS, 4 * MS), event(5, 9 * MS, 1 * MS)]
    device = (field(2, "/device:TPU:0")
              + field(3, line("XLA Modules", [event(1, 0, 10 * MS)]))
              + field(3, line("XLA Ops", ops))
              + b"".join(field(4, m) for m in metas)
              + b"".join(field(5, s) for s in stat_names))
    d = tmp_path / "plugins" / "profile" / "2026_01_01"
    d.mkdir(parents=True)
    (d / "vm.xplane.pb").write_bytes(field(1, device))
    monkeypatch.setattr(stage_reduce, "TRACE_DIR", str(tmp_path))


def test_kernel_feasibility_share(run, xplane):
    run["device"] = {"busy_s": 1.0}
    run["cfg"] = {"placement_programs": ["fused_place_batch"]}
    assert read("kernel_feasibility_share", run) == pytest.approx(60.0)
    run["cfg"] = {"placement_programs": ["no_such_program"]}
    assert read("kernel_feasibility_share", run) is None


def test_rules_place_batch_roofline(run):
    rows, matrix = 10240, 48.8e6
    w = roofline_rules.widths(TRAFFIC)
    assert w == {"c": 8, "a": 2, "s": 2, "dp": 1}
    assert roofline_rules.widths(traffic.load("backlog")) == {
        "c": 4, "a": 1, "s": 1, "dp": 0}
    run.update({
        "device": {"launches": 50, "kernel_s": 0.5, "devices": 1},
        "matrix_bytes": matrix, "device_kind": "TPU v5 lite",
        "cfg": {"node_capacity": rows}})
    # 8 lanes and 4 steps a launch, 10 ms a launch
    work = roofline_rules.launch_work(matrix, rows, 8.0, 4.0, w)
    plain = roofline.launch_work(matrix, rows, 8.0)
    assert work["bytes"] == pytest.approx(
        plain["bytes"] + 8 * rows * (10 * 8 + 4 + 4 * (2 * 4 + 12)))
    assert work["flop"] > plain["flop"]
    got = read("rules_place_batch_roofline", run)
    assert got == pytest.approx(100.0 * (work["bytes"] / 819e9) / 0.010)
    assert 0 < got < 100
    # a window in which no lane carried a distinct_property: nothing to read
    run["m1"]["nomad.kernel.distinct_property_lanes_total"] = 5
    assert read("rules_place_batch_roofline", run) is None
