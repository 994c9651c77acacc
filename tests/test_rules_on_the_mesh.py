"""Placement rules through the live server on a mesh (PR 46).

The rule stages of the node-sharded placement program (``distinct_property``
inside the sharded scan, the wide constraint / affinity / spread variants)
were held by kernel-level tests alone; no server had ever sent a job with
rules down the mesh route.  Here a live agent places jobs of each of the
eight rule shapes of ``benchmark/traffic/rules-backlog-x4.json`` over HTTP,
one after the other, on four routes over the same seeded cluster: one
device, a mesh of four and of eight forced host devices, and the numpy twin.
Every placement holds its rules by the benchmark's plain reference
(``benchmark/deployments/rules_reference.py``), and the mesh and the twin
place every allocation where the one-device program does, scores within
the tolerance ``tests/test_distinct_property.py`` states.
"""

from __future__ import annotations

import copy
import json
import os
import sys
import types
import urllib.request

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NODES, CAPACITY, SIM_ALLOCS, SEED = 240, 256, 48_000, 2 ** 31 + 46
RACKS = 8          # meta.rack's period here: a limit of 1 binds at width 8
ROUTES = {"one_device": 1, "mesh4": 4, "mesh8": 8, "twin": 1}
# (shape, width): every shape narrow and wide; r1 at width 8 takes a node in
# every rack, r5 at its limit of 2 takes up to two.
JOBS = [(s, w) for w in (3, 8) for s in range(8)]


@pytest.fixture(scope="module")
def bench():
    """``benchmark/``'s own modules, imported by path as its tests do."""
    paths = [os.path.join(ROOT, "benchmark"),
             os.path.join(ROOT, "benchmark", "deployments")]
    sys.path[:0] = paths
    try:
        import rules_reference
        import traffic

        with open(os.path.join(
                ROOT, "benchmark/configs/c2m-100k-rules.json")) as fh:
            cluster = copy.deepcopy(json.load(fh)["cluster"])
        rack = cluster["rule_attributes"][0]
        assert rack["name"] == "meta.rack" and rack["period"] == 2560
        rack["period"] = RACKS
        yield types.SimpleNamespace(
            rules=rules_reference, traffic=traffic, cluster=cluster,
            mix=traffic.load("rules-backlog-x4"),
            tables=rules_reference.attr_tables(NODES, cluster))
    finally:
        for p in paths:
            sys.path.remove(p)


def _place_all(bench, route):
    """Boot the agent on ``route``, register the seeded cluster, PUT the
    jobs one at a time; per job the nodes (by index) and recorded scores of
    its allocations in placement order, and what the coalescer counted."""
    from nomad_tpu import simcluster
    from nomad_tpu.api import Agent, AgentConfig
    from nomad_tpu.server import ServerConfig

    agent = Agent(AgentConfig(
        client_enabled=False,
        server_config=ServerConfig(
            num_workers=2, heartbeat_min_ttl=600, heartbeat_max_ttl=900,
            node_capacity=CAPACITY, n_device_shards=ROUTES[route],
            slo_enabled=False),
    ))
    agent.start()
    srv = agent.server
    try:
        index_of = {}
        for i in range(NODES):
            node = simcluster.sim_node(i)
            node.id, node.name = f"sim-node-{i:06d}", f"sim-{i:06d}"
            node.meta = dict(node.meta)
            for name, value in bench.rules.expected_attributes(
                    i, bench.cluster).items():
                kind, key = name.split(".", 1)
                (node.meta if kind == "meta" else node.attributes)[key] = value
            srv.register_node(node)
            index_of[node.id] = i
        rows = np.fromiter((srv.matrix.row_of[nid] for nid in index_of),
                           np.int64, NODES)
        totals = srv.matrix.snapshot_host()["totals"][rows].copy()
        used0, prio0 = simcluster.sim_usage(totals, SIM_ALLOCS, SEED % 2 ** 32)
        srv.matrix.set_usage(rows, used0, prio0)
        placed = []
        for k, (shape, width) in enumerate(JOBS):
            op = {"namespace": "default", "width": width, "type": "service",
                  "priority": 50, "shape": shape, "job_id": f"mesh-{k:02d}"}
            req = urllib.request.Request(
                agent.rpc_addr + "/v1/jobs", method="PUT",
                data=json.dumps(
                    {"Job": bench.traffic.job_payload(bench.mix, op)}).encode())
            with urllib.request.urlopen(req, timeout=60) as r:
                eval_id = json.loads(r.read())["EvalID"]
            ev = srv.wait_for_eval(eval_id, timeout=300)
            assert ev is not None and ev.status == "complete", (route, op, ev)
            allocs = sorted(
                (a for a in srv.store.allocs_by_job("default", op["job_id"])
                 if not a.terminal_status()), key=lambda a: a.name)
            placed.append({
                "nodes": [index_of[a.node_id] for a in allocs],
                "scores": [[a.metrics.scores[a.node_id][k]
                            for k in ("binpack", "final")] for a in allocs],
                "queued": dict(ev.queued_allocations),
            })
        coal = srv.coalescer
        return {
            "placed": placed, "mesh": coal.mesh_shape(),
            "fused": (coal.fused_dispatches, coal.dispatches),
            "dp_lanes": coal.distinct_property_lanes,
            "dp_blocked": coal.distinct_property_blocked,
            "degraded": coal.breaker.brief()["degraded_dispatches"],
            "features": coal._features,
            "class_walk": srv.matrix.host_feasibility().walked_classes,
            "node_walk": srv.matrix.host_feasibility().walked_nodes,
        }
    finally:
        agent.shutdown()


@pytest.fixture(scope="module")
def routes(bench, eight_devices):
    out = {}
    for route in ROUTES:
        if route == "twin":
            os.environ["NOMAD_TPU_FAKE_DEVICE"] = "1"
        try:
            out[route] = _place_all(bench, route)
        finally:
            os.environ.pop("NOMAD_TPU_FAKE_DEVICE", None)
    return out


@pytest.mark.parametrize("route", list(ROUTES))
def test_the_route_is_the_one_asked_for(routes, route):
    from nomad_tpu.parallel.sharding import mesh_layout

    got = routes[route]
    want = {"mesh4": mesh_layout(4, CAPACITY), "mesh8": mesh_layout(8, CAPACITY)}
    assert got["mesh"] == want.get(route, (1, 1))
    assert got["fused"][0] == got["fused"][1] >= len(JOBS)
    assert got["degraded"] == 0
    # counted on the host, so on every route alike: the four jobs under a
    # distinct_property (r1, r5; two widths) launched with the stage live
    assert got["dp_lanes"] >= 4
    if route != "twin":  # the twin compiles nothing, so ratchets nothing
        assert got["features"].dp_width >= 1
    assert got["dp_lanes"] == routes["one_device"]["dp_lanes"]
    assert got["dp_blocked"] == routes["one_device"]["dp_blocked"] > 0
    assert got["class_walk"] == got["node_walk"] == 0


@pytest.mark.parametrize("shape", range(8))
@pytest.mark.parametrize("route", list(ROUTES))
def test_every_placement_holds_its_rules_by_the_reference(routes, bench,
                                                          route, shape):
    rules, s = bench.rules, bench.mix["shapes"][shape]
    ok = rules.eligible(bench.tables, s["datacenters"], s["constraints"])
    compared = 0
    for (sh, width), job in zip(JOBS, routes[route]["placed"]):
        if sh != shape:
            continue
        nodes = job["nodes"]
        assert len(nodes) == width and not any(job["queued"].values()), job
        assert ok[nodes].all(), (s["name"], nodes)
        for c in s["constraints"]:
            if c["operand"] == "distinct_hosts":
                assert rules.distinct_hosts_violations(nodes) == 0
            if c["operand"] == "distinct_property":
                assert rules.distinct_property_violations(
                    bench.tables, c, nodes) == 0
                held = np.unique(rules.column(
                    bench.tables, c["l_target"])[nodes], return_counts=True)[1]
                # the limit binds: eight allocations over eight racks
                assert held.max() == rules.distinct_limit(c) or width < RACKS
        compared += 1
    assert compared == 2


def test_the_shapes_are_the_eight_the_issue_names(bench):
    kinds = [s["kind"] for s in bench.mix["shapes"]]
    assert kinds == [
        "distinct_hosts+even-spread", "distinct_property",
        "target-spread+negative-affinity", "version-range",
        "set_contains+distinct_hosts", "regexp+distinct_property-2",
        "two-spreads+two-affinities", "six-constraints"]


@pytest.mark.parametrize("route", ["mesh4", "mesh8", "twin"])
def test_the_route_places_where_one_device_does(routes, route):
    want, got = routes["one_device"]["placed"], routes[route]["placed"]
    for (shape, width), w, g in zip(JOBS, want, got):
        assert g["nodes"] == w["nodes"], (route, shape, width)
        np.testing.assert_allclose(
            np.array(g["scores"]), np.array(w["scores"]), rtol=2e-6,
            atol=1e-6, err_msg=f"{route} r{shape} x{width}")
