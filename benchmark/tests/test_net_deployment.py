"""The ``c2m-10k-net`` deployment on a CPU at a tiny size (PR 51): the cell
from files alone through ``run.py --rehearse`` (from a copy of the tree with
its entries added: ``net_checkout.py``); every shape PUT to a live server
and read back with ``GET /v1/job/<id>``; ``net_reference`` by hand on a node
whose 8080 is taken; ``net_check`` on a hand-made read-back, sound and with
one fault of each kind planted; the two new readers on recorded counters.

The program as it stands drops a job's device ask on the way to its
allocations (PERF.md section 7, "Cells left out", first): the rehearsal
reads ``device_overcommit`` over 0 and ``correct`` false, every other number
0.  ``test_the_cell_reads_correct`` is the test that turns green with the
program's repair."""

import copy
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
import net_check  # noqa: E402
import net_checkout  # noqa: E402
import net_reference as net  # noqa: E402
import reference as ref  # noqa: E402
import roofline  # noqa: E402
import roofline_net  # noqa: E402
import rules_reference as rules  # noqa: E402
import traffic  # noqa: E402
from test_readers import read  # noqa: E402

CELL = "c2m-10k-net.net-backlog"
ENV = dict(os.environ, JAX_PLATFORMS="cpu",
           JAX_COMPILATION_CACHE_DIR=os.path.join(ROOT, ".jax_cache"))
with open(os.path.join(BENCH, "configs", "c2m-10k-net.json")) as _fh:
    CFG = json.load(_fh)
with open(os.path.join(BENCH, "configs", "c2m-10k.json")) as _fh:
    BASE = json.load(_fh)
CLUSTER = CFG["cluster"]
TRAFFIC = traffic.load("net-backlog")
SHAPE = {s["name"]: i for i, s in enumerate(TRAFFIC["shapes"])}
EXACT = [k for k in net_check.LIMITS if k not in ("score_gap", "rank_gap")]


# -- the files ----------------------------------------------------------------------

def test_the_configuration_is_c2m_10ks_with_devices_and_residents():
    for k in ("nodes", "node_capacity", "sim_allocs", "workers",
              "heartbeat_min_ttl", "heartbeat_max_ttl", "placement_programs",
              "reduced", "precision"):
        assert CFG[k] == BASE[k], k
    assert CFG["guarantees"][:5] == BASE["guarantees"]
    assert len(CFG["guarantees"]) == 8 and len(CFG["source"]) <= 200
    assert {k: v for k, v in CLUSTER.items() if k in BASE["cluster"]} \
        == BASE["cluster"]
    assert CFG["setup"] == "net_cluster" and CFG["check"] == "net_check"
    e = net_checkout.entries()
    assert e["configs"][0]["source"] == CFG["source"]
    assert e["configs"][0]["reduced"] == sorted(
        CFG["reduced"], key=list(CFG["reduced"]).index)
    assert e["workloads"][0] == dict(
        e["workloads"][0], name=CELL, config="c2m-10k-net",
        traffic="net-backlog", chips=1)
    bench = net_checkout.added(
        json.load(open(os.path.join(ROOT, "BENCHMARK.json"))), e)
    assert len(bench["workloads"]) == 7
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 2
    names = [m["name"] for m in bench["per_layer"]]
    assert len(names) == len(set(names))
    assert all(len(w["why"]) <= 200 for w in bench["workloads"])


def test_the_residents_are_a_pure_function_of_the_nodes_index():
    n = 10_000
    state = net.residents(n, CLUSTER)
    kinds = {k: state["kind"].count(k) for k in set(state["kind"])}
    assert kinds == {"res-svc-a": 10_000, "res-svc-b": 10_000,
                     "res-gpu": 1_667, "res-8080": 1_429, "res-edge": 1_429,
                     "res-9090": 715}
    totals = net.device_totals(n, CLUSTER)["nvidia/gpu"]
    assert int((totals > 0).sum()) == 3_334 and int(totals.sum()) == 13_336
    held = net.Tables(n, CLUSTER).add_residents(state)
    assert int((totals - held.dev_used["nvidia/gpu"]).sum()) == 10_002
    # every node's two services hold the range's first two ports
    assert held.held[5] == {20000: 1, 20001: 1}
    assert held.held[1] == {20000: 1, 20001: 1, 8080: 1}
    assert held.held[2] == {20000: 1, 20001: 1, 80: 1, 443: 1}
    assert held.held[3][9090] == 1 and held.held[17][9090] == 1
    assert net.port_collisions(held) == 0 and net.device_overcommit(held) == 0
    # every datacenter has its share of the nodes free for 8080 and 80 (7 is
    # coprime to the four datacenters); 14 is not, so 9090's residents sit
    # in dc2 and dc4 alone and dc1 and dc3 are all free (NET.md)
    for port in (8080, 80):
        free = ~held.port_taken(port)
        assert {int(free[d::4].sum()) for d in range(4)} <= set(
            range(int(free.sum()) // 4 - 1, int(free.sum()) // 4 + 2))
    free = ~held.port_taken(9090)
    assert [int(free[d::4].sum()) for d in range(4)] == [2500, 2143, 2500, 2142]
    usage = net.resident_usage(n, state)
    assert usage[0].tolist() == [60.0, 96.0, 0.0]   # two services + the GPU one
    assert usage[5].tolist() == [40.0, 64.0, 0.0]
    # nothing runs out even at the deck's ceiling (ISSUE 51's reckoning)
    deck = traffic.schedule(TRAFFIC, 7, 50)
    assert len(deck) == 20_992
    want = {}
    for op in deck:
        ask = net.asks(TRAFFIC["shapes"][op["shape"]])
        for p in ask["static"]:
            want[p] = want.get(p, 0) + op["width"]
        for name, c in ask["devices"].items():
            want[name] = want.get(name, 0) + c * op["width"]
    for port in (8080, 80, 443, 9090):
        assert want[port] < int((~held.port_taken(port)).sum())
    assert want["nvidia/gpu"] < 10_002


# -- end to end -----------------------------------------------------------------------

@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    root = net_checkout.make(tmp_path_factory.mktemp("checkout"))
    dump = os.path.join(root, "dump.json")
    p = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark/run.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 51), "--seconds", "5",
         "--trace", "1", "--rehearse", "--check-dump", dump],
        capture_output=True, text=True, env=ENV, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    out = p.stdout.strip().splitlines()
    return out, json.loads(out[-1]), dump


def test_the_cell_from_files_alone_compares_every_number(rehearsal):
    out, result, dump = rehearsal
    assert result["failed"] == 0 and result["attempted"] > 100
    assert list(result["compared"]) == list(net_check.LIMITS)
    for k, limit in net_check.LIMITS.items():
        assert result["compared"][k]["limit"] == limit
        assert any(l.startswith(f"check: {k} = ") and
                   l.endswith(f"(limit {limit:g})") for l in out), k
    for k in EXACT:
        if k != "device_overcommit":
            assert result["compared"][k]["value"] == 0, k
    assert result["compared"]["score_gap"]["value"] <= 3e-5
    assert result["compared"]["rank_gap"]["value"] <= 1e-5
    compared = [l for l in out if l.startswith("check: compared")][0]
    assert int(compared.split("; ")[1].split(" jobs placed")[0]) > 5
    assert int(compared.split("static port, ")[1].split(" with")[0]) > 5
    detail = json.loads(
        [l for l in out if l.startswith("detail: ")][-1][len("detail: "):])
    assert detail["setup"]["install_s"] > 0
    assert detail["compiles_in_window"] == 0
    # the two new readers find their sources in a traced run of the cell
    # (no device trace on a CPU, so the roofline has no kernel time to read)
    assert result["metrics"]["launches_per_eval"]["value"] >= 1.0
    assert "net_place_batch_roofline" not in result["metrics"]
    for name in net_checkout.entries()["append_to"]:
        if name not in ("kernel_scan_share", "kernel_verify_share",
                        "kernel_feasibility_share"):  # device trace
            assert name in result["metrics"], name
    # the control: the reference in bfloat16 is not correct on this dump
    c = subprocess.run(
        [sys.executable, os.path.join(BENCH, "control.py"), dump],
        capture_output=True, text=True, env=ENV, timeout=300)
    assert c.returncode == 0, c.stdout + c.stderr


@pytest.mark.xfail(strict=False, reason=(
    "the program drops a job's device ask on the way to its allocations "
    "(TaskGroup.combined_resources), so no instance is ever counted as "
    "taken: PERF.md section 7, 'Cells left out', first"))
def test_the_cell_reads_correct(rehearsal):
    _out, result, _dump = rehearsal
    assert result["compared"]["device_overcommit"]["value"] == 0
    assert result["correct"] is True


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
            node.resources.devices = {"nvidia/gpu": ["a", "b", "c", "d"]}
            agent.server.register_node(node)
        yield agent
        agent.shutdown()
    finally:
        del os.environ["NOMAD_TPU_FAKE_DEVICE"]


def _http(agent, path, body=None):
    req = urllib.request.Request(
        agent.rpc_addr + path, method="GET" if body is None else "PUT",
        data=None if body is None else json.dumps(body).encode())
    with urllib.request.urlopen(req, timeout=30) as r:
        return json.loads(r.read())


@pytest.mark.parametrize("shape", range(8))
def test_every_shape_is_read_back_as_sent(agent, shape):
    """``traffic.load`` validates nothing and ``job_payload`` copies a
    shape's asks as they stand: a key the server's decoder does not know
    would be dropped in silence and the ask with it."""
    s = TRAFFIC["shapes"][shape]
    op = {"namespace": "default", "width": 2, "type": "service",
          "priority": 50, "shape": shape, "job_id": f"put-{s['name']}"}
    assert _http(agent, "/v1/jobs",
                 {"Job": traffic.job_payload(TRAFFIC, op)}).get("EvalID")
    job = _http(agent, f"/v1/job/{op['job_id']}")
    tg = job["task_groups"][0]
    ask = net.asks(s)
    assert tg["count"] == 2 and tg["ephemeral_disk"]["size_mb"] == s["disk_mb"]
    assert [p for n in tg["networks"] for p in n["reserved_ports"]] \
        == ask["static"]
    assert [p for n in tg["networks"] for p in n["dynamic_ports"]] \
        == ask["dynamic"]
    res = tg["tasks"][0]["resources"]
    assert (res["cpu"], res["memory_mb"]) == (s["cpu"], s["memory_mb"])
    assert {d["name"]: d["count"] for d in res["devices"]} == ask["devices"]


# -- net_reference, by hand ---------------------------------------------------------------

def test_feasibility_by_hand_on_a_node_whose_8080_is_taken():
    n = 42
    t = net.Tables(n, CLUSTER).add_residents(net.residents(n, CLUSTER))
    n2, n3, n4, n6 = (net.asks(TRAFFIC["shapes"][SHAPE[k]])
                      for k in ("n2", "n3", "n4", "n6"))
    assert n2 == {"static": [8080], "dynamic": ["admin"], "devices": {}}
    assert n4["devices"] == {"nvidia/gpu": 1} and n3["static"] == [80, 443]
    # residents: 8080 where i % 7 == 1, 80 + 443 where i % 7 == 2, 9090 where
    # i % 14 == 3; four GPU instances where i % 3 == 0, two taken where
    # i % 6 == 0
    assert net.blocked(t, n2).nonzero()[0].tolist() == [1, 8, 15, 22, 29, 36]
    assert net.blocked(t, n3).nonzero()[0].tolist() == [2, 9, 16, 23, 30, 37]
    assert net.blocked(t, n6).nonzero()[0].tolist() == [3, 17, 31]
    assert (~net.blocked(t, n4)).nonzero()[0].tolist() == list(range(0, 42, 3))
    t.add(4, [8080, 20002], {})              # an n2 allocation lands on node 4
    assert net.blocked(t, n2)[4] and not net.blocked(t, n3)[4]
    t.add(6, [20002], {"nvidia/gpu": 1})     # 3 of 4 taken on node 6
    assert not net.blocked(t, n4)[6]
    t.add(6, [20003], {"nvidia/gpu": 1})     # the last instance
    assert net.blocked(t, n4)[6] and net.device_overcommit(t) == 0
    t.add(6, [20004], {"nvidia/gpu": 1})     # a fifth
    assert net.device_overcommit(t) == 1
    assert net.blocked(t, {"static": [], "dynamic": [],
                           "devices": {"amd/gpu": 1}}).all()


# -- net_check on a hand-made read-back ----------------------------------------------------

N = 84


class World:
    """A read-back of ``N`` nodes with their residents and one placed job
    of every shape, sound by construction: every recorded score is the
    reference's own, every port and instance free where it is taken."""

    def __init__(self):
        self.cfg = dict(copy.deepcopy(CFG), nodes=N)
        self.state = net.residents(N, CLUSTER)
        self.seeded = np.tile(np.array([[400.0, 800.0, 300.0]]), (N, 1))
        resident = net.resident_usage(N, self.state)
        # The nodes the jobs land on are the fullest, and as full as each
        # other: every pick is an arg-max of the binpack score.
        picked = [4, 5, 7, 9, 10, 11, 12, 13, 16, 19, 20, 25]
        self.seeded[picked, :2] = np.array([1900.0, 3800.0]) - resident[
            picked, :2]
        self.used0 = self.seeded + resident
        self.totals = ref.node_totals(CLUSTER)
        self.tables = ref.attr_tables(N, CLUSTER)
        self.allocs, self.records = [], []
        self.index = 100
        for k, (shape, rows) in enumerate([
            ("n0", [5]), ("n1", [7, 10]), ("n2", [4, 11]), ("n3", [13]),
            ("n4", [9, 12]),             # node 12's resident holds 2 of its 4
            ("n5", [19]), ("n6", [20, 25]),
            ("n7", [16]),                # dc1, v5e, not class-3
        ]):
            self.place(f"op-{k:06d}", shape, rows)

    def place(self, jid, shape_name, rows):
        si = SHAPE[shape_name]
        shape = TRAFFIC["shapes"][si]
        ask = net.asks(shape)
        self.index += 1
        self.records.append({
            "job_id": jid, "status": "placed", "width": len(rows),
            "shape": si, "i": len(self.records), "namespace": "default",
            "registers": 1})
        res = np.array([shape["cpu"], shape["memory_mb"], shape["disk_mb"]],
                       float)
        for k, row in enumerate(rows):
            before = rows[:k].count(row)
            b = float(ref.binpack_score(
                self.used0[row] + before * res, res, self.totals))
            spread = 0.0
            if shape["spreads"]:
                col = rules.column(self.tables, shape["spreads"][0]["attribute"])
                use = {}
                for r in rows[:k]:
                    use[str(col[r])] = use.get(str(col[r]), 0) + 1
                spread = float(rules.spread_boost(
                    shape["spreads"], len(rows), [str(col[row])], [use]))
            final = float(rules.final_score(
                b, before, len(rows), 0.0, spread))
            nid = check.node_id(row)
            taken = {p for a in self.allocs if a["node_id"] == nid
                     for p in net.assigned(a).values()}
            taken |= {p for r, ports in zip(self.state["node"],
                                            self.state["ports"])
                      if r == row for p in ports}
            ports = {str(p): p for p in ask["static"]}
            cursor = 20000
            for label in ask["dynamic"]:
                while cursor in taken:
                    cursor += 1
                ports[label] = cursor
                taken.add(cursor)
            self.allocs.append({
                "id": f"{jid}-{k}", "job_id": jid, "node_id": nid,
                "task_group": "g", "desired_status": "run",
                "create_index": self.index, "namespace": "default",
                "assigned_ports": {"group": ports} if ports else {},
                "resources": dict(zip(ref.DIMS, res.tolist())),
                "metrics": {"scores": {nid: {"binpack": b, "final": final}}}})

    def get(self, path):
        if path == "/v1/nodes":
            return [dict(ref.expected_node(i, CLUSTER), id=check.node_id(i),
                         status="ready", scheduling_eligibility="eligible")
                    for i in range(N)]
        if path.startswith("/v1/node/"):
            i = int(path.rsplit("-", 1)[1])
            devices = {name: [f"{name}-{k}" for k in range(c)]
                       for name, c in net.node_devices(i, CLUSTER).items()}
            return {"attributes": ref.expected_node(i, CLUSTER)["attributes"],
                    "resources": dict(CLUSTER["node_resources"],
                                      devices=devices),
                    "reserved": CLUSTER["node_reserved"]}
        if path.startswith("/v1/allocations?namespace="):
            ns = path.split("=")[1]
            return [a for a in self.allocs if a["namespace"] == ns]
        raise AssertionError(path)

    def decide(self):
        return net_check.decide(
            self.get, self.cfg, TRAFFIC, self.records, self.seeded, 51,
            state=json.loads(json.dumps(self.state)))

    def alloc(self, alloc_id):
        return next(a for a in self.allocs if a["id"] == alloc_id)


def test_the_check_passes_a_sound_read_back():
    correct, numbers, lines = World().decide()
    assert correct, lines
    assert numbers["score_gap"] < 1e-9
    assert all(numbers[k] == 0 for k in EXACT)


def _move(w, alloc_id, row):
    a = w.alloc(alloc_id)
    score = a["metrics"]["scores"].pop(a["node_id"])
    a["node_id"] = check.node_id(row)
    a["metrics"]["scores"][a["node_id"]] = score


def _shared_port(w):           # node 8's resident holds 8080 (8 % 7 == 1)
    _move(w, "op-000002-1", 8)


def _shared_dynamic_port(w):   # the job-init job on its node's resident's port
    w.alloc("op-000000-0")["assigned_ports"]["group"]["db"] = 20001


def _missing_static_port(w):   # the edge proxy without its 443
    del w.alloc("op-000003-0")["assigned_ports"]["group"]["443"]


def _dynamic_outside_the_range(w):
    w.alloc("op-000001-0")["assigned_ports"]["group"]["metrics"] = 32001


def _fifth_instance(w):        # node 6 has 4, its resident holds 2: 2 + 3
    w.place("op-000008", "n4", [6, 6, 6])


def _gpu_job_without_a_gpu(w):  # node 22 has no device group
    _move(w, "op-000004-1", 22)


def _disk_over_the_nodes(w):   # 1,000 MB scratch on a node with 500 left
    w.seeded[19, 2] = w.totals[2] - 500.0


def _unfingerprinted(w):       # a GPU node that reports no device
    get = w.get

    def without(path):
        out = get(path)
        if path.startswith("/v1/node/"):
            out["resources"] = dict(out["resources"], devices={})
        return out
    w.get = without


@pytest.mark.parametrize("fault,number", [
    (_shared_port, "port_collisions"),
    (_shared_dynamic_port, "port_collisions"),
    (_missing_static_port, "port_ask_unmet"),
    (_dynamic_outside_the_range, "port_ask_unmet"),
    (_fifth_instance, "device_overcommit"),
    (_gpu_job_without_a_gpu, "device_on_wrong_node"),
    (_disk_over_the_nodes, "overcommitted_nodes"),
])
def test_the_check_reads_one_for_one_planted_fault(fault, number):
    w = World()
    fault(w)
    correct, numbers, lines = w.decide()
    assert not correct
    assert numbers[number] == 1, lines
    assert all(numbers[k] == 0 for k in EXACT if k != number), lines


def test_the_check_holds_the_nodes_to_their_device_groups():
    w = World()
    _unfingerprinted(w)
    correct, numbers, _ = w.decide()
    assert not correct and numbers["nodes_wrong"] > 0


def test_a_closed_node_is_not_one_the_program_passed_over():
    """Node 8 is fuller than the n2 job's nodes, so it scores higher, but
    its resident holds 8080: closed to the job, and the rank reads no gap;
    with 8080 free there it reads one.  (The n2 job alone: node 8 is open to
    the shapes without that port.)"""
    w = World()
    w.allocs = [a for a in w.allocs if a["job_id"] == "op-000002"]
    w.records = [r for r in w.records if r["job_id"] == "op-000002"]
    w.seeded[8, :2] = (2500.0, 5000.0)
    _, numbers, lines = w.decide()
    assert numbers["rank_gap"] <= 1e-5, lines
    k = [i for i, (r, kind) in enumerate(zip(w.state["node"], w.state["kind"]))
         if r == 8 and kind == "res-8080"][0]
    w.state["ports"][k], w.state["labels"][k] = [], []
    _, numbers, lines = w.decide()
    assert numbers["rank_gap"] > 1e-3, lines


def test_a_run_that_placed_no_device_job_compared_nothing():
    w = World()
    w.allocs = [a for a in w.allocs if a["job_id"] != "op-000004"]
    w.records = [r for r in w.records if r["job_id"] != "op-000004"]
    correct, numbers, lines = w.decide()
    assert not correct
    assert all(numbers[k] <= limit for k, limit in net_check.LIMITS.items())
    assert any("nothing of it was compared" in l for l in lines)


def test_an_allocation_of_the_warm_up_counts_by_its_shape():
    """The warm-up's jobs stay live through the window: their ports and
    instances are held like any."""
    w = World()
    warm = traffic.warmup_ops(TRAFFIC)
    k = next(o["i"] for o in warm if o["shape"] == SHAPE["n4"])
    w.place("w0s-" + warm[k]["job_id"], "n4", [6, 6, 6])
    w.records.pop()                       # no record: it is no window job
    correct, numbers, _ = w.decide()
    assert not correct and numbers["device_overcommit"] == 1
    assert numbers["nodes_wrong"] == 0


# -- the two new readers, on recorded counters -------------------------------------------

def _run(**grown):
    m0 = {k: 10 for k in grown}
    return {"m0": m0, "m1": {k: 10 + v for k, v in grown.items()},
            "traffic": TRAFFIC, "cfg": CFG, "device_kind": "TPU v5 lite",
            "matrix_bytes": 7.1e6,
            "device": {"launches": 90, "kernel_s": 0.27, "devices": 1}}


def test_launches_per_eval():
    run = _run(**{"nomad.kernel.fused_lanes": 1500,
                  "nomad.kernel.launches{path=solo}": 100,
                  "nomad.kernel.launches{path=fused}": 200,
                  "nomad.worker.evals_processed": 1000})
    assert read("launches_per_eval", run) == pytest.approx(1.6)
    del run["m1"]["nomad.kernel.launches{path=solo}"]
    assert read("launches_per_eval", run) == pytest.approx(1.5)
    assert read("launches_per_eval", {"m0": {}, "m1": {}}) is None


def test_net_place_batch_roofline():
    run = _run(**{"nomad.kernel.fused_lanes": 1600,
                  "nomad.kernel.launches{path=fused}": 200})
    rows, lanes = CFG["node_capacity"], 8.0
    base = roofline.launch_work(7.1e6, rows, lanes)
    work = roofline_net.launch_work(7.1e6, rows, lanes)
    assert work["bytes"] - base["bytes"] == lanes * rows * (8 * 4 + 4 + 64)
    want = 100.0 * (work["bytes"] / 819e9) / (0.27 / 90)
    got = read("net_place_batch_roofline", run)
    assert got == pytest.approx(want) and 0 < got < 100
    # nothing where the traffic asks for no port, or without a device trace
    assert read("net_place_batch_roofline",
                dict(run, traffic=traffic.load("backlog"))) is None
    assert read("net_place_batch_roofline", dict(run, device=None)) is None
