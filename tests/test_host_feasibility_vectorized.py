"""The host's side of feasibility without a walk over the nodes (PR 44).

``GenericStack._host_mask`` and ``_class_eligibility`` used to visit
``matrix.row_of`` one node at a time, calling ``check_constraint_host`` per
node (or per class representative) per eval.  They now read the matrix's
columns whole (``feasible_host.HostFeasibility``: a predicate is evaluated
once per distinct value of its column and broadcast; host volumes and device
asks come from ``matrix.volume_rows`` / ``device_rows``).  The straight
per-node loop is kept HERE, and every escaped operator is held to it on
seeded random clusters; through an eval with placement rules on a live
server ``nomad.sched.host_walk_nodes_total`` stays 0.
"""

from __future__ import annotations

import random

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.ops.encode import MAX_DATACENTERS
from nomad_tpu.scheduler.context import EvalContext
from nomad_tpu.scheduler.feasible_host import check_constraint_host
from nomad_tpu.scheduler.stack import GenericStack
from nomad_tpu.scheduler.testing import Harness
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.structs.types import (
    Constraint,
    EvalStatus,
    Op,
    Plan,
    RequestedDevice,
    VolumeRequest,
)

KERNELS = ["4.15.0", "4.19.0", "5.4.0", "5.10.0", "5.15.0", ""]
BINARIES = ["redis,cassandra,haproxy", "redis", "cassandra, haproxy", ""]


def random_cluster(seed: int, n: int = 60) -> Harness:
    rng = random.Random(seed)
    h = Harness()
    for i in range(n):
        node = mock.node()
        node.name = f"host-{rng.randrange(10_000):05d}"
        node.datacenter = f"dc{rng.randrange(12) + 1}"
        node.node_class = f"class-{rng.randrange(3)}"
        node.attributes = dict(node.attributes)
        node.attributes["unique.hostname"] = f"sim-{rng.randrange(10 ** 6):06d}"
        node.attributes["kernel.version"] = rng.choice(KERNELS)
        node.attributes["os.name"] = rng.choice(["ubuntu", "debian", "alpine"])
        node.meta = {"cached_binaries": rng.choice(BINARIES),
                     "rack": f"r{rng.randrange(5)}"}
        if rng.random() < 0.2:
            del node.meta["rack"]
        if rng.random() < 0.5:
            node.host_volumes = {"data": "/srv/data"}
        if rng.random() < 0.3:
            node.host_volumes = dict(node.host_volumes, logs="/var/log")
        h.store.upsert_node(h.next_index(), node)
    return h


# One of every operator that escapes to the host (encode._encode_predicate
# returns None for it), on unique and on class attributes.
ESCAPED = [
    ("${attr.unique.hostname}", Op.REGEXP.value, "[02468]$"),
    ("${attr.unique.hostname}", Op.REGEXP.value, "^sim-0"),
    ("${node.unique.name}", Op.REGEXP.value, "host-[0-4]"),
    ("${node.unique.name}", Op.LT.value, "host-05000"),          # lexical
    ("${attr.unique.hostname}", Op.SET_CONTAINS.value, "sim-000001"),
    ("${attr.kernel.version}", Op.VERSION.value, ">= 4.19, < 5.15"),
    ("${attr.kernel.version}", Op.SEMVER.value, ">= 5.4.0, != 5.10.0"),
    ("${attr.kernel.version}", Op.VERSION.value, "~> 5.4"),
    ("${meta.cached_binaries}", Op.SET_CONTAINS.value, "redis,cassandra"),
    ("${meta.cached_binaries}", Op.SET_CONTAINS_ANY.value, "haproxy,nginx"),
    ("${attr.os.name}", Op.REGEXP.value, "^(ubuntu|debian)$"),
    ("${attr.os.name}", Op.GTE.value, "debian"),                  # lexical
    ("${attr.no.such.attribute}", Op.REGEXP.value, "."),
    ("${meta.rack}", Op.REGEXP.value, "r[0-2]"),
]


def stack_for(h: Harness, job) -> GenericStack:
    ctx = EvalContext(h.store.snapshot(), Plan(job=job))
    stack = GenericStack(ctx, h.store.matrix)
    stack.set_job(job)
    return stack


def loop_host_mask(h: Harness, job, compiled) -> np.ndarray:
    """The straight per-node loop (the parent's ``_host_mask``, less
    distinct_hosts and distinct_property, which read allocations)."""
    m = h.store.matrix
    mask = np.ones((m.capacity,), bool)
    unique = [e.constraint for e in compiled.escaped if e.unique]
    dcs = set(job.datacenters)
    for node_id, row in m.row_of.items():
        node = h.store.node_by_id(node_id)
        ok = True
        if compiled.dc_escaped and node.datacenter not in dcs:
            ok = False
        if not all(check_constraint_host(c, node) for c in unique):
            ok = False
        if not all(v in node.host_volumes for v in compiled.host_volumes):
            ok = False
        for name, count in compiled.escaped_devices:
            if len(node.resources.devices.get(name, [])) < count:
                ok = False
        mask[row] = ok
    return mask


def loop_class_eligibility(h: Harness, compiled, pad: int) -> np.ndarray:
    m = h.store.matrix
    elig = np.ones((pad,), bool)
    escaped = [e.constraint for e in compiled.escaped if not e.unique]
    for cid, rep in m.class_repr.items():
        elig[cid] = all(
            check_constraint_host(c, h.store.node_by_id(rep)) for c in escaped)
    return elig


def live_rows(h: Harness) -> np.ndarray:
    return np.array(sorted(h.store.matrix.row_of.values()))


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("l_target,operand,r_target", ESCAPED)
def test_escaped_operator_equals_the_per_node_loop(seed, l_target, operand,
                                                   r_target):
    h = random_cluster(seed)
    job = mock.job()
    job.datacenters = [f"dc{i + 1}" for i in range(12)]
    job.constraints = [
        Constraint(l_target=l_target, r_target=r_target, operand=operand)]
    stack = stack_for(h, job)
    tg = job.task_groups[0]
    compiled = stack.encoder.compile(job, tg)
    assert compiled.escaped, "the operator did not escape: wrong fixture"
    rows = live_rows(h)
    want = loop_host_mask(h, job, compiled)
    got = stack._host_mask(job, tg, compiled)
    if got is None:
        got = np.ones_like(want)
    np.testing.assert_array_equal(got[rows], want[rows])
    elig = stack._class_eligibility(compiled)
    np.testing.assert_array_equal(
        elig, loop_class_eligibility(h, compiled, elig.shape[0]))
    # A node of the class gets what its representative got.
    host = h.store.matrix.snapshot_host()
    by_class = elig[host["class_id"][rows]]
    node_wise = np.array([
        all(check_constraint_host(e.constraint, h.store.node_by_id(
            h.store.matrix.node_of[int(r)]))
            for e in compiled.escaped if not e.unique)
        for r in rows])
    np.testing.assert_array_equal(by_class, node_wise)
    assert h.store.matrix.host_feasibility().walked_nodes == 0


@pytest.mark.parametrize("seed", [4, 5])
def test_datacenters_volumes_and_devices_equal_the_loop(seed):
    h = random_cluster(seed)
    m = h.store.matrix
    # more device types than the registry holds: the ninth escapes
    rng = random.Random(seed)
    for node_id in list(m.row_of):
        node = h.store.node_by_id(node_id)
        node.resources.devices = {
            f"vendor/dev{k}": [f"id{j}" for j in range(rng.randrange(3))]
            for k in range(9)}
        h.store.upsert_node(h.next_index(), node)
    job = mock.job()
    job.datacenters = [f"dc{i + 1}" for i in range(MAX_DATACENTERS + 2)]
    tg = job.task_groups[0]
    tg.volumes = {"d": VolumeRequest(name="d", type="host", source="data"),
                  "l": VolumeRequest(name="l", type="host", source="logs")}
    tg.tasks[0].resources.devices = [
        RequestedDevice(name=f"vendor/dev{k}", count=1 + (k == 8))
        for k in range(9)]
    stack = stack_for(h, job)
    compiled = stack.encoder.compile(job, tg)
    assert compiled.dc_escaped and compiled.host_volumes
    assert compiled.escaped_devices == [("vendor/dev8", 2)]
    rows = live_rows(h)
    got = stack._host_mask(job, tg, compiled)
    np.testing.assert_array_equal(
        got[rows], loop_host_mask(h, job, compiled)[rows])
    assert 0 < got[rows].sum() < len(rows) or len(rows) == 0
    assert m.host_feasibility().walked_nodes == 0


def test_cached_masks_follow_the_cluster():
    """A mask is cached across evals by the predicate's content and is
    valid for one ``attr_version``: a node that registers, re-registers
    with another fingerprint or leaves is seen; a status update is not a
    new version; and only values not seen before are evaluated again."""
    h = random_cluster(7, n=30)
    m = h.store.matrix
    hf = m.host_feasibility()
    con = Constraint(l_target="${attr.unique.hostname}", r_target="[02468]$",
                     operand=Op.REGEXP.value)

    def loop():
        out = np.ones((m.capacity,), bool)
        for nid, row in m.row_of.items():
            out[row] = check_constraint_host(con, h.store.node_by_id(nid))
        return out

    rows = live_rows(h)
    first = hf.constraint_mask(con)
    np.testing.assert_array_equal(first[rows], loop()[rows])
    n_eval = hf.predicates_evaluated
    assert hf.constraint_mask(con) is first            # a hit: same object
    some = h.store.node_by_id(m.node_of[int(rows[0])])
    h.store.update_node_eligibility(h.next_index(), some.id, "ineligible")
    assert hf.constraint_mask(con) is first            # no new version
    # re-registers with another hostname; one new node; one leaves
    changed = h.store.node_by_id(m.node_of[int(rows[1])])
    changed.attributes = dict(changed.attributes)
    changed.attributes["unique.hostname"] = "sim-999998"
    h.store.upsert_node(h.next_index(), changed)
    fresh = mock.node()
    fresh.attributes = dict(fresh.attributes)
    fresh.attributes["unique.hostname"] = "sim-999997"
    h.store.upsert_node(h.next_index(), fresh)
    h.store.delete_node(h.next_index(), m.node_of[int(rows[2])])
    second = hf.constraint_mask(con)
    assert second is not first
    rows = live_rows(h)
    np.testing.assert_array_equal(second[rows], loop()[rows])
    assert second[m.row_of[changed.id]] and not second[m.row_of[fresh.id]]
    # two values were new (a fresh node lacks none of the others')
    assert hf.predicates_evaluated - n_eval <= 3
    assert hf.walked_nodes == 0


def test_an_attribute_without_a_column_falls_back_to_a_counted_walk():
    """With the attribute registry full a constraint on an attribute that
    got no slot cannot be read off a column: the stack walks, gives the
    loop's answer, and counts every node (the regression alarm)."""
    h = Harness()
    for i in range(12):
        node = mock.node()
        node.attributes = dict(node.attributes)
        node.attributes.update({f"filler.{k}": "x" for k in range(40)})
        node.attributes["unique.zzz"] = f"v{i}"
        h.store.upsert_node(h.next_index(), node)
    m = h.store.matrix
    assert m.attrs.lookup("unique.zzz") is None
    job = mock.job()
    job.constraints = [Constraint(l_target="${attr.unique.zzz}",
                                  r_target="v[0-5]$",
                                  operand=Op.REGEXP.value)]
    stack = stack_for(h, job)
    tg = job.task_groups[0]
    compiled = stack.encoder.compile(job, tg)
    got = stack._host_mask(job, tg, compiled)
    rows = live_rows(h)
    np.testing.assert_array_equal(
        got[rows], loop_host_mask(h, job, compiled)[rows])
    assert got[rows].sum() == 6
    assert m.host_feasibility().walked_nodes == 12


def test_escaped_distinct_property_holds_between_selects():
    """A third distinct_property has no slot in the request: the host
    masks the values at their limit (and the nodes without the property),
    from the column."""
    h = random_cluster(9, n=40)
    job = mock.job()
    job.datacenters = [f"dc{i + 1}" for i in range(8)]
    tg = job.task_groups[0]
    tg.count = 1
    tg.constraints = [
        Constraint(l_target=t, operand=Op.DISTINCT_PROPERTY.value)
        for t in ("${attr.os.name}", "${attr.kernel.version}", "${meta.rack}")]
    stack = stack_for(h, job)
    compiled = stack.encoder.compile(job, tg)
    assert len(compiled.distinct_props) == 2
    assert [e.constraint.l_target for e in compiled.escaped] == ["${meta.rack}"]
    m = h.store.matrix
    # the job holds a node of rack r1 already
    holder = next(n for n in h.store.nodes.values() if n.meta.get("rack") == "r1")
    alloc = mock.alloc(job, holder)
    h.store.upsert_allocs(h.next_index(), [alloc])
    stack = stack_for(h, job)
    got = stack._host_mask(job, tg, compiled)
    for nid, row in m.row_of.items():
        rack = h.store.node_by_id(nid).meta.get("rack")
        assert got[row] == (rack is not None and rack != "r1"), (nid, rack)
    assert m.host_feasibility().walked_nodes == 0


RULES = [
    [("${attr.unique.hostname}", Op.REGEXP.value, "[02468]$"),
     ("${meta.rack}", Op.DISTINCT_PROPERTY.value, "2")],
    [("${attr.kernel.version}", Op.VERSION.value, ">= 4.19, < 5.15")],
    [("${meta.cached_binaries}", Op.SET_CONTAINS.value, "redis,cassandra"),
     ("", Op.DISTINCT_HOSTS.value, "")],
]


def test_no_node_is_walked_through_a_rules_eval_on_the_live_server():
    s = Server(ServerConfig(num_workers=2, heartbeat_min_ttl=60,
                            heartbeat_max_ttl=90))
    s.start()
    try:
        for i in range(40):
            node = mock.node()
            node.attributes = dict(node.attributes)
            node.attributes["unique.hostname"] = f"sim-{i:06d}"
            node.attributes["kernel.version"] = KERNELS[i % 5]
            node.meta = {"rack": f"r{i % 8}",
                         "cached_binaries": BINARIES[i % 4]}
            s.register_node(node)
        for rules in RULES:
            job = mock.job()
            tg = job.task_groups[0]
            tg.count = 4
            tg.tasks[0].resources.cpu = 100
            tg.constraints = [
                Constraint(l_target=t, operand=o, r_target=r)
                for t, o, r in rules]
            ev = s.submit_job(job)
            done = s.wait_for_eval(ev.id, timeout=120)
            assert done is not None
            assert done.status == EvalStatus.COMPLETE.value
            live = [a for a in s.store.allocs_by_job(job.namespace, job.id)
                    if not a.terminal_status()]
            assert len(live) == 4
            nodes = [s.store.node_by_id(a.node_id) for a in live]
            for node in nodes:
                for t, o, r in rules:
                    if o not in (Op.DISTINCT_HOSTS.value,
                                 Op.DISTINCT_PROPERTY.value):
                        assert check_constraint_host(
                            Constraint(l_target=t, operand=o, r_target=r), node)
            if rules is RULES[0]:
                racks = [n.meta["rack"] for n in nodes]
                assert max(racks.count(r) for r in racks) <= 2
            if rules is RULES[2]:
                assert len({n.id for n in nodes}) == 4
        metrics = s.metrics.snapshot() if hasattr(
            s.metrics, "snapshot") else {}
        assert s.store.matrix.host_feasibility().walked_nodes == 0
        assert s.store.matrix.host_feasibility().predicates_evaluated > 0
        assert s.coalescer.distinct_property_lanes >= 1
        if metrics:
            flat = str(metrics)
            assert "nomad.sched.host_walk_nodes_total" in flat
            assert "nomad.kernel.distinct_property_lanes_total" in flat
    finally:
        s.shutdown()
