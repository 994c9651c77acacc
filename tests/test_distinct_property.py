"""distinct_property inside the placement scan (PR 44).

``generic.py`` asks for all of a group's placements in ONE select, so the
limit has to hold from pick to pick inside one launch: the scan carries a
count per node (of the job's allocs on nodes sharing its property value),
seeded from the live and proposed allocations and raised at each pick
(kernels.distinct_property_*; the numpy twin and the node-sharded program
write the same).  Every case runs on a cluster where binpack alone stacks a
rack, so a limit that only held between selects would show.
"""

from __future__ import annotations

import hashlib
from collections import Counter

import numpy as np
import pytest

from helpers import lane_operands
from test_megakernel import host_view
from test_preemption_tiers import PARENT

from nomad_tpu import mock, simcluster
from nomad_tpu.ops import RequestEncoder, fake_device, kernels
from nomad_tpu.ops.encode import MAX_DISTINCT_VALUES
from nomad_tpu.parallel.sharding import (
    make_mesh,
    shard_matrix_arrays,
    sharded_fused_place_batch,
)
from nomad_tpu.scheduler import GenericScheduler
from nomad_tpu.scheduler.testing import Harness
from nomad_tpu.state.matrix import stable_hash
from nomad_tpu.structs.types import Constraint, Op, Spread

RACKS = 8


def rack_node(i: int, racks: int = RACKS, **meta):
    node = mock.node()
    node.meta = {"rack": f"r{i % racks}", "zone": f"z{i % 2}", **meta}
    return node


def cluster(n: int = 24, racks: int = RACKS) -> Harness:
    """``n`` nodes over ``racks`` racks; the nodes of rack r0 carry usage,
    so binpack alone ranks them first, again and again."""
    h = Harness()
    for i in range(n):
        h.store.upsert_node(h.next_index(), rack_node(i, racks))
    m = h.store.matrix
    rows = np.array([r for nid, r in m.row_of.items()
                     if h.store.node_by_id(nid).meta["rack"] == "r0"])
    used = np.tile(np.array([[1500.0, 3000.0, 100.0]], np.float32),
                   (len(rows), 1))
    m.set_usage(rows, used, np.zeros((len(rows), 16, 3), np.float32))
    return h


def rules_job(count: int, *constraints: Constraint):
    job = mock.job()
    tg = job.task_groups[0]
    tg.count = count
    tg.tasks[0].resources.cpu = 100
    tg.tasks[0].resources.memory_mb = 64
    tg.constraints = list(constraints)
    return job


def distinct(prop: str = "${meta.rack}", limit: str = "") -> Constraint:
    return Constraint(l_target=prop, r_target=limit,
                      operand=Op.DISTINCT_PROPERTY.value)


def place(h: Harness, job):
    h.store.upsert_job(h.next_index(), job)
    sched = h.process(
        lambda snap, planner, matrix: GenericScheduler(
            "service", snap, planner, matrix),
        mock.eval_for(job))
    live = [a for a in h.store.allocs_by_job(job.namespace, job.id)
            if not a.terminal_status()]
    return sched, live


def per_value(h: Harness, allocs, key: str = "rack") -> Counter:
    return Counter(h.store.node_by_id(a.node_id).meta.get(key) for a in allocs)


@pytest.fixture(params=["program", "twin"])
def backend(request, monkeypatch):
    """The jitted program and the numpy twin, through the same stack."""
    if request.param == "twin":
        monkeypatch.setenv("NOMAD_TPU_FAKE_DEVICE", "1")
    return request.param


def test_binpack_alone_stacks_a_rack(backend):
    """The control: without the constraint the picks pile into rack r0."""
    h = cluster()
    _, live = place(h, rules_job(8))
    assert len(live) == 8
    assert max(per_value(h, live).values()) > 2


@pytest.mark.parametrize("limit", [1, 2])
@pytest.mark.parametrize("count", [4, 8])
def test_limit_holds_within_one_select(backend, limit, count):
    h = cluster()
    _, live = place(h, rules_job(count, distinct(limit=str(limit))))
    assert len(live) == count
    assert len(h.plans) == 1  # one plan: every pick came from one select
    assert max(per_value(h, live).values()) <= limit


def test_default_limit_is_one(backend):
    h = cluster()
    _, live = place(h, rules_job(8, distinct()))
    assert sorted(per_value(h, live).values()) == [1] * 8


def test_two_constraints_at_once(backend):
    """One alloc a rack AND at most three a zone: six placed (two zones),
    each limit held."""
    h = cluster()
    sched, live = place(h, rules_job(
        8, distinct(), distinct("${meta.zone}", "3")))
    assert len(live) == 6
    assert max(per_value(h, live).values()) == 1
    assert max(per_value(h, live, "zone").values()) == 3
    assert sched.queued_allocs.get("web") == 2


def test_seeded_from_live_and_proposed_allocations(backend):
    """A job scaled up: the racks its live allocs hold are full for the
    new ones; and a count past one launch's scan (16): the second launch
    is seeded with the first one's picks, which no plan holds yet."""
    h = cluster(n=48, racks=24)
    job = rules_job(4, distinct())
    _, first = place(h, job)
    held = set(per_value(h, first))
    job2 = job.copy() if hasattr(job, "copy") else job
    job2.task_groups[0].count = 20
    job2.version += 1
    _, live = place(h, job2)
    assert len(live) == 20
    assert sorted(per_value(h, live).values()) == [1] * 20
    assert held <= set(per_value(h, live))


def test_a_node_without_the_property_is_infeasible(backend):
    h = cluster(n=8, racks=4)
    bare = mock.node()  # no meta.rack
    h.store.upsert_node(h.next_index(), bare)
    sched, live = place(h, rules_job(5, distinct()))
    assert len(live) == 4
    assert bare.id not in {a.node_id for a in live}
    assert sched.queued_allocs.get("web") == 1


def test_count_above_values_times_limit_fails_the_rest(backend):
    """count = 7 over 3 racks at limit 2: six placed, one failed; never a
    third in a rack (as test_distinct_hosts_fails_overflow_instead_of_stacking)."""
    h = cluster(n=12, racks=3)
    sched, live = place(h, rules_job(7, distinct(limit="2")))
    assert len(live) == 6
    assert sorted(per_value(h, live).values()) == [2, 2, 2]
    assert sched.queued_allocs.get("web") == 1


def test_more_values_held_than_the_request_seeds(backend):
    """A job that already holds more racks than the request has seed slots:
    the full ones go into the host mask, and no limit is passed."""
    racks = MAX_DISTINCT_VALUES + 8
    h = cluster(n=2 * racks, racks=racks)
    job = rules_job(MAX_DISTINCT_VALUES + 4, distinct())
    _, first = place(h, job)
    assert len(first) == MAX_DISTINCT_VALUES + 4
    job.task_groups[0].count = racks
    job.version += 1
    _, live = place(h, job)
    assert len(live) == racks
    assert sorted(per_value(h, live).values()) == [1] * racks


# -- the three writings of the program -----------------------------------------------

SCAN = 8


def _rules_matrix():
    """The seeded simcluster with a rack (32 values) and a zone (5) meta
    attribute on every node but one in seven."""
    m = simcluster.build_cluster(96, 128, 19_200, seed=5)
    host = m.snapshot_host()
    rack, zone = m.attrs.register("meta.rack"), m.attrs.register("meta.zone")
    for row in range(96):
        if row % 7 == 3:
            continue
        host["attr_hash"][row, rack] = stable_hash(f"r{row % 32}")
        host["attr_hash"][row, zone] = stable_hash(f"z{row % 5}")
    m.invalidate()
    return m


def _rules_requests(m):
    enc = RequestEncoder(m)
    reqs = []
    for i, cons in enumerate((
        [distinct()],
        [distinct(limit="2")],
        [distinct(), distinct("${meta.zone}", "3")],
        [],
        [distinct("${meta.zone}", "1")],
    )):
        job = rules_job(SCAN, *cons)
        job.datacenters = ["dc1", "dc2", "dc3", "dc4"]
        if i == 1:
            job.task_groups[0].spreads = [
                Spread(attribute="${meta.zone}", weight=50)]
        req = enc.compile(job, job.task_groups[0]).request
        if i == 2:  # seeded: two racks held, one zone at two of three
            vh, cnt = req.dp_value_hash.copy(), req.dp_count.copy()
            vh[0, :2] = [stable_hash("r0"), stable_hash("r9")]
            cnt[0, :2] = 1.0
            vh[1, 0], cnt[1, 0] = stable_hash("z1"), 2.0
            req = req._replace(dp_value_hash=vh, dp_count=cnt)
        reqs.append(req)
    return reqs


@pytest.fixture(scope="module")
def rules_launch():
    m = _rules_matrix()
    reqs = _rules_requests(m)
    ops = lane_operands(m, reqs)
    ls = np.array([8, 8, 6, 3, 8], np.int32)
    feats = kernels.features_of(ops[5])
    return m, reqs, ops, ls, feats


def _program(m, ops, ls, feats):
    arrays = m.sync()
    return np.asarray(kernels.fused_place_batch(
        arrays, arrays.used, *ops, ls, n_placements=SCAN, features=feats))


def test_the_scan_holds_the_limits(rules_launch):
    m, reqs, ops, ls, feats = rules_launch
    assert feats.dp_width == 2
    out = _program(m, ops, ls, feats)
    host = m.snapshot_host()["attr_hash"]
    rack, zone = m.attrs.lookup("meta.rack"), m.attrs.lookup("meta.zone")

    def values(lane, slot):
        rows = out[lane, :, kernels.PACKED_ROW].astype(int)
        assert (rows[: ls[lane]] >= 0).sum() > 0
        got = host[rows[rows >= 0], slot]
        assert (got != 0).all()  # never a node without the property
        return Counter(got.tolist())

    assert max(values(0, rack).values()) == 1
    assert max(values(1, rack).values()) <= 2
    lane2 = values(2, rack)
    assert max(lane2.values()) == 1
    assert stable_hash("r0") not in lane2 and stable_hash("r9") not in lane2
    zones = values(2, zone)
    assert max(zones.values()) <= 3 and zones.get(stable_hash("z1"), 0) <= 1
    # five zones at limit 1: five placed, the rest fail
    rows4 = out[4, :, kernels.PACKED_ROW]
    assert (rows4 >= 0).sum() == 5 and max(values(4, zone).values()) == 1
    # a limit moved picks off better-scoring nodes, and says so
    moved = out[:, :, kernels.PACKED_FILTERED] % 1 != 0
    assert moved[0].any() and not moved[3].any()


def test_the_numpy_twin_agrees(rules_launch):
    m, reqs, ops, ls, feats = rules_launch
    drows, dvals, tg, sc, pen, _reqs, ce, hm = ops
    host = host_view(m.sync())
    twin = fake_device.fused_place_batch(
        host, host.used, *[list(a) for a in (drows, dvals, tg, sc, pen)],
        reqs, list(ce), list(hm), ls > 0, n_placements=SCAN,
        live_counts=list(ls))
    np.testing.assert_allclose(twin, _program(m, ops, ls, feats),
                               rtol=2e-6, atol=1e-6)
    np.testing.assert_array_equal(
        twin[:, :, [0, 3, 4, 5, 6, 7]],
        _program(m, ops, ls, feats)[:, :, [0, 3, 4, 5, 6, 7]])


@pytest.mark.parametrize("devices,batch", [(4, 1), (4, 2), (8, 2)])
def test_the_sharded_program_agrees(rules_launch, eight_devices, devices,
                                    batch):
    m, reqs, ops, ls, feats = rules_launch
    # lanes padded to a multiple of the batch axis
    pad = (-len(reqs)) % batch
    ops_p = lane_operands(m, reqs + [reqs[3]] * pad)
    ls_p = np.concatenate([ls, np.zeros((pad,), np.int32)])
    mesh = make_mesh(devices, batch=batch)
    sharded = shard_matrix_arrays(mesh, m.sync())
    out = np.asarray(sharded_fused_place_batch(mesh, SCAN)(
        sharded, sharded.used, *ops_p, ls_p, features=feats))
    np.testing.assert_array_equal(
        out[: len(reqs)], _program(m, ops, ls, feats))


def test_solo_scan_agrees_with_the_batched_lane(rules_launch):
    m, reqs, ops, ls, feats = rules_launch
    arrays = m.sync()
    out = _program(m, ops, ls, feats)
    n = int(arrays.used.shape[0])
    solo = kernels.place_task_group(
        arrays, reqs[0], arrays.used, np.zeros((n,), np.int32), ops[3][0],
        np.zeros((n,), bool), ops[6][0], np.ones((n,), bool),
        n_placements=SCAN, features=feats)
    np.testing.assert_array_equal(
        np.asarray(solo.rows), out[0, :, kernels.PACKED_ROW].astype(np.int32))


@pytest.mark.parametrize("case", sorted(PARENT))
def test_masked_off_the_packed_output_is_the_parents(case):
    """No lane carries a distinct_property: at ``dp_width`` 2 (the stage
    compiled in, every slot inactive) and at 0 the bytes of the packed
    output are the parent's (the digests test_preemption_tiers.py pins)."""
    seed, lanes, steps = case
    m = simcluster.build_cluster(480, 512, 96_000, seed=seed)
    shapes = simcluster.build_requests(m)
    rng = np.random.default_rng(seed)
    deltas = {i: [(int(rng.integers(0, 480)), (120.0, 64.0, 10.0))]
              for i in range(0, lanes, 3)}
    ops = lane_operands(m, [shapes[i % 8] for i in range(lanes)],
                        deltas=deltas)
    arrays = m.sync()
    ls = np.array([steps[i % len(steps)] for i in range(lanes)], np.int32)
    for width in (0, 2):
        out = np.asarray(kernels.fused_place_batch(
            arrays, arrays.used, *ops, ls, n_placements=8,
            features=kernels.FULL_FEATURES._replace(
                preempt=False, dp_width=width)))
        digest = hashlib.sha256(
            np.ascontiguousarray(out).tobytes()).hexdigest()
        assert digest == PARENT[case], width


# -- at dp_width 0 the stage's operands stay on the host --------------------------------

def test_a_launch_without_the_stage_takes_no_operand_of_it(eight_devices):
    """``device_request`` leaves the four distinct_property fields out at
    ``dp_width`` 0 (four device buffers fewer a launch): the same bytes
    out of the one-device and the sharded program."""
    m = simcluster.build_cluster(96, 128, 19_200, seed=5)
    shapes = simcluster.build_requests(m)
    ops = list(lane_operands(m, [shapes[i % 8] for i in range(4)]))
    ls = np.array([8, 3, 0, 5], np.int32)
    feats = kernels.features_of(ops[5])
    assert feats.dp_width == 0
    bare = kernels.device_request(ops[5], 0)
    assert bare.dp_slot is None and bare.dp_count is None
    assert kernels.device_request(ops[5], 1).dp_slot is ops[5].dp_slot
    arrays = m.sync()
    full = _program(m, ops, ls, feats)
    ops[5] = bare
    np.testing.assert_array_equal(full, np.asarray(kernels.fused_place_batch(
        arrays, arrays.used, *ops, ls, n_placements=SCAN, features=feats)))
    mesh = make_mesh(4, batch=2)
    sharded = shard_matrix_arrays(mesh, arrays)
    np.testing.assert_array_equal(full, np.asarray(
        sharded_fused_place_batch(mesh, SCAN)(
            sharded, sharded.used, *ops, ls, features=feats)))


def test_the_live_server_widens_to_the_stage_when_a_job_brings_it():
    """Plain jobs first (launches at ``dp_width`` 0, the operands left on
    the host), then a job with a distinct_property: the ratchet widens, the
    operands ride along, the limit holds."""
    from nomad_tpu.server import Server, ServerConfig

    s = Server(ServerConfig(num_workers=2, heartbeat_min_ttl=60,
                            heartbeat_max_ttl=90))
    s.start()
    try:
        for i in range(16):
            s.register_node(rack_node(i))
        for cons in ([], [distinct()]):
            job = rules_job(6, *cons)
            done = s.wait_for_eval(s.submit_job(job).id, timeout=120)
            assert done is not None and done.status == "complete"
            live = [a for a in s.store.allocs_by_job(job.namespace, job.id)
                    if not a.terminal_status()]
            assert len(live) == 6
            racks = Counter(
                s.store.node_by_id(a.node_id).meta["rack"] for a in live)
            if cons:
                assert max(racks.values()) == 1
                assert s.coalescer._features.dp_width == 1
            else:
                assert s.coalescer._features.dp_width == 0
    finally:
        s.shutdown()
