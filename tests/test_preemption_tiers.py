"""Preemption as Nomad makes it, against the plain reference of the
``c2m-10k-preempt`` deployment (benchmark/deployments/preempt_reference.py:
numpy and plain Python, nothing of the program's).

* two tiers in one arg-max: a node with room beats every node that needs
  an eviction, in the fused program, its mesh twin and its numpy twin;
  where no node has room the pick is the reference's, and the victims the
  host then chooses on it are the reference's;
* the score a preempting placement RECORDS is Nomad's (ScoreFit after the
  victims are gone, the logistic of their net priority) to 3e-5;
* the host's room is the matrix's: real allocations on top of usage
  aggregates (PERF.md section 7, PR 36: every service eval ended "maximum
  attempts reached") commit with their eviction at the first attempt;
* the applier creates one ``preemption`` eval per job that lost
  allocations and none otherwise; a preemptable node whose evictable usage
  has no allocation behind it is banned for the eval and counted;
* on a cluster with room, preemption on evicts nothing;
* with preemption off the packed output of ``fused_place_batch`` is bit
  for bit the parent's (digests taken on commit 7482429).
"""

import hashlib
import os
import sys

import numpy as np
import pytest

from nomad_tpu import mock, simcluster
from nomad_tpu.ops import RequestEncoder, fake_device, kernels
from nomad_tpu.scheduler import GenericScheduler
from nomad_tpu.scheduler.preemption import select_victims
from nomad_tpu.scheduler.testing import Harness
from nomad_tpu.state import NodeMatrix
from nomad_tpu.state.matrix import PRIORITY_BUCKETS, priority_bucket
from nomad_tpu.structs import Allocation, Job, Resources
from nomad_tpu.structs.types import (
    EvalTrigger,
    PreemptionConfig,
    SchedulerConfiguration,
)

from helpers import _wait, lane_operands

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
for _p in (BENCH, os.path.join(BENCH, "deployments")):
    if _p not in sys.path:
        sys.path.append(_p)

import preempt_reference as pref  # noqa: E402

PROGRAMS = ("fused", "mesh", "twin")
SHAPES = ((100, 128), (150, 192), (200, 256), (250, 128))
ASK = (200, 256)
PREEMPTION_ON = SchedulerConfiguration(preemption_config=PreemptionConfig(
    system_scheduler_enabled=True, batch_scheduler_enabled=True,
    service_scheduler_enabled=True))


def tier_cluster(seed, n_nodes=24, capacity=32, room_on=None):
    """A full cluster in tiers: production usage as aggregates in the
    bucket of priority 70 (nothing behind them to evict), a best-effort
    tier of real allocations at priority 10 (a few at 30) on every node
    until its free cpu is under 100 MHz.  ``room_on``: that node gets no
    tier at all.  Returns the matrix, the nodes, and the reference's copy
    of the allocations by row."""
    rng = np.random.default_rng(seed)
    m = NodeMatrix(capacity=capacity)
    nodes = [mock.node() for _ in range(n_nodes)]
    for n in nodes:
        m.upsert_node(n)
    rows = np.array([m.row_of[n.id] for n in nodes])
    totals = m.snapshot_host()["totals"][rows]
    used = np.zeros((n_nodes, 3), np.float32)
    used[:, 0] = rng.uniform(0.3, 0.5, n_nodes) * totals[:, 0]
    used[:, 1] = rng.uniform(0.15, 0.6, n_nodes) * totals[:, 1]
    prio = np.zeros((n_nodes, PRIORITY_BUCKETS, 3), np.float32)
    prio[:, priority_bucket(70)] = used
    m.set_usage(rows, used, prio)
    objects, plain = {}, {}
    jobs = {p: Job(priority=p) for p in (10, 30)}
    for i, n in enumerate(nodes):
        row = int(rows[i])
        objects[row], plain[row] = [], []
        if i == room_on:
            continue
        free, k = float(totals[i, 0] - used[i, 0]), 0
        while free >= 100:
            fits = [s for s in SHAPES if s[0] <= free]
            cpu, mem = fits[int(rng.integers(len(fits)))]
            p = 30 if rng.random() < 0.2 else 10
            a = Allocation(
                id=f"tier-{row:03d}-{k:02d}", node_id=n.id, job=jobs[p],
                resources=Resources(cpu=cpu, memory_mb=mem, disk_mb=30))
            m.add_alloc(a)
            objects[row].append(a)
            plain[row].append({"id": a.id, "node": row, "priority": p,
                               "res": (cpu, mem, 30)})
            free, k = free - cpu, k + 1
    return m, nodes, objects, plain


def preempting_request(m, priority=50, ask=ASK):
    job = mock.job(priority=priority)
    tg = job.task_groups[0]
    tg.tasks[0].resources = Resources(cpu=ask[0], memory_mb=ask[1])
    tg.ephemeral_disk.size_mb = 0
    req = RequestEncoder(m).compile(job, tg, preemption_enabled=True).request
    return job, tg, req


def launch(program, m, reqs, lane_steps, n_placements=2):
    """One launch of ``reqs`` (a lane each; ``lane_steps`` 0 = dead)
    through one of the three programs: the packed (B, P, 8) output."""
    ops = lane_operands(m, reqs)
    ls = np.asarray(lane_steps, np.int32)
    arrays = m.sync()
    if program == "fused":
        out = kernels.fused_place_batch(
            arrays, arrays.used, *ops, ls, n_placements=n_placements)
    elif program == "mesh":
        from nomad_tpu.parallel import (
            make_mesh,
            shard_matrix_arrays,
            sharded_fused_place_batch,
        )

        mesh = make_mesh(4, batch=2)
        sharded = shard_matrix_arrays(mesh, arrays)
        out = sharded_fused_place_batch(mesh, n_placements)(
            sharded, sharded.used, *ops, ls)
    else:
        host = type(arrays)(*[np.asarray(x) for x in arrays])
        drows, dvals, tg, sc, pen, _, ce, hm = ops
        out = fake_device.fused_place_batch(
            host, host.used, *[list(a) for a in (drows, dvals, tg, sc, pen)],
            list(reqs), list(ce), list(hm), ls > 0,
            n_placements=n_placements, live_counts=list(ls))
    return np.asarray(out)


def first_pick(program, m, req):
    """The packed row of the first step of lane 0 (8 lanes, the others
    dead)."""
    out = launch(program, m, [req] * 8, [1] + [0] * 7)
    assert (out[1:, :, kernels.PACKED_ROW] == -1).all()
    return out[0, 0]


def reference_select(m, plain, n_nodes, priority=50, ask=ASK):
    host = m.snapshot_host()
    eligible = np.zeros((m.capacity,), bool)
    eligible[:n_nodes] = True
    return pref.select(
        priority, (*ask, 0), 1, host["used"].astype(np.float64),
        host["totals"][0], eligible, 0.0, 0, plain)


@pytest.mark.parametrize("program", PROGRAMS)
def test_a_node_with_room_beats_every_preemptable_node(program,
                                                       eight_devices):
    """The one node with room is nearly empty (the lowest binpack score of
    the cluster); every other node could take the ask by evicting and
    would score ~1.0 if it competed."""
    m, nodes, _, plain = tier_cluster(seed=5, room_on=7)
    _, _, req = preempting_request(m)
    got = first_pick(program, m, req)
    want = reference_select(m, plain, len(nodes))
    assert want[1] == [] and want[0] == m.row_of[nodes[7].id]
    assert int(got[kernels.PACKED_ROW]) == want[0]
    assert got[kernels.PACKED_PREEMPT] == 0.0
    np.testing.assert_allclose(
        got[kernels.PACKED_SCORE], want[2]["final"], rtol=3e-5)


@pytest.mark.parametrize("seed", (2, 3, 4))
@pytest.mark.parametrize("program", PROGRAMS)
def test_with_no_room_the_pick_and_the_victims_are_the_references(
        program, seed, eight_devices):
    """The kernel ranks preempting nodes by an ESTIMATE (the least
    eviction the bucket tables can express; kernels.score_nodes), the
    reference by the exact score after whole allocations are gone: these
    are seeds on which the two agree on the arg-max (on seed 1 the
    reference's two best nodes are 1.5e-4 apart and the estimate takes the
    second)."""
    m, nodes, objects, plain = tier_cluster(seed=seed)
    job, tg, req = preempting_request(m)
    got = first_pick(program, m, req)
    row, victims, _ = reference_select(m, plain, len(nodes))
    assert victims, "the reference found room: the case lost its teeth"
    assert int(got[kernels.PACKED_ROW]) == row
    # binpack + preemption, and whatever else the request scores by
    assert got[kernels.PACKED_PREEMPT] >= 2.0
    host = m.snapshot_host()
    room = (host["totals"][row] - host["used"][row]).astype(np.float64)
    mine = select_victims(job, objects[row], tg.combined_resources(), room)
    assert [v.id for v in mine] == [v["id"] for v in victims]


@pytest.mark.parametrize("program", PROGRAMS)
def test_lanes_of_one_launch_preempt_on_different_nodes(program,
                                                        eight_devices):
    """On a full cluster every lane's arg-max is the same node, and the
    host would name the same victims on it for every lane: the applier
    commits one such plan and rejects the rest.  A node an earlier lane of
    the launch took by preempting (its claim over-fills the node) is
    passed over by the later ones, which take their next best; each lane's
    own ranking of the nodes is untouched."""
    m, nodes, _, _ = tier_cluster(seed=3)
    _, _, req = preempting_request(m)
    ls = np.array([1, 1, 1, 1, 1, 0, 1, 1], np.int32)
    out = launch(program, m, [req] * 8, ls)
    live = ls > 0
    rows = out[live, 0, kernels.PACKED_ROW].astype(int)
    assert (rows >= 0).all() and len(set(rows)) == live.sum()
    assert (out[live, 0, kernels.PACKED_PREEMPT] >= 2.0).all()
    assert (out[~live, :, kernels.PACKED_ROW] == -1).all()
    # in lane order down each lane's own ranking: scores never rise
    scores = out[live, 0, kernels.PACKED_SCORE]
    assert (np.diff(scores) <= 0).all()
    assert rows[0] == first_pick(program, m, req)[0]


# -- through the scheduler: what a preempting placement records -------------------

def harness_cluster(seed, n_nodes=12):
    """``tier_cluster`` in a state store: aggregates under real objects."""
    rng = np.random.default_rng(seed)
    h = Harness()
    h.store.set_scheduler_config(h.next_index(), PREEMPTION_ON)
    nodes = [mock.node() for _ in range(n_nodes)]
    for n in nodes:
        h.store.upsert_node(h.next_index(), n)
    m = h.store.matrix
    rows = np.array([m.row_of[n.id] for n in nodes])
    totals = m.snapshot_host()["totals"][rows]
    used = np.zeros((n_nodes, 3), np.float32)
    used[:, 0] = rng.uniform(0.3, 0.5, n_nodes) * totals[:, 0]
    used[:, 1] = rng.uniform(0.15, 0.6, n_nodes) * totals[:, 1]
    prio = np.zeros((n_nodes, PRIORITY_BUCKETS, 3), np.float32)
    prio[:, priority_bucket(70)] = used
    m.set_usage(rows, used, prio)
    plain = {}
    tier = []
    for s, (cpu, mem) in enumerate(SHAPES):
        j = mock.batch_job(priority=10)
        j.id, j.namespace = f"tier-{s}", "default"
        j.task_groups[0].tasks[0].resources = Resources(
            cpu=cpu, memory_mb=mem)
        j.task_groups[0].ephemeral_disk.size_mb = 30
        tier.append(j)
    counts = [0] * len(tier)
    allocs = []
    for i, n in enumerate(nodes):
        row = int(rows[i])
        plain[row] = []
        free, k = float(totals[i, 0] - used[i, 0]), 0
        while free >= 100:
            fits = [s for s in range(len(SHAPES)) if SHAPES[s][0] <= free]
            s = fits[int(rng.integers(len(fits)))]
            a = mock.alloc(tier[s], n, id=f"tier-{row:03d}-{k:02d}")
            a.name = f"{tier[s].id}.web[{counts[s]}]"
            counts[s] += 1
            allocs.append(a)
            plain[row].append({
                "id": a.id, "node": row, "job": tier[s].id, "priority": 10,
                "res": (*SHAPES[s], 30)})
            free, k = free - SHAPES[s][0], k + 1
    for j, c in zip(tier, counts):
        j.task_groups[0].count = max(c, 1)
        h.store.upsert_job(h.next_index(), j)
    h.store.upsert_allocs(h.next_index(), allocs)
    return h, nodes, plain


@pytest.mark.parametrize("seed", (11, 12, 13))
def test_the_recorded_score_of_a_preempting_pick_is_the_references(seed):
    h, nodes, plain = harness_cluster(seed)
    m = h.store.matrix
    host = m.snapshot_host()
    used0 = host["used"].astype(np.float64).copy()
    job = mock.job(priority=50)
    tg = job.task_groups[0]
    tg.count = 1
    tg.tasks[0].resources = Resources(cpu=ASK[0], memory_mb=ASK[1])
    tg.ephemeral_disk.size_mb = 0
    h.store.upsert_job(h.next_index(), job)
    eligible = np.zeros((m.capacity,), bool)
    eligible[[m.row_of[n.id] for n in nodes]] = True
    row, victims, scores = pref.select(
        50, (*ASK, 0), 1, used0, host["totals"][m.row_of[nodes[0].id]],
        eligible, 0.0, 0, plain)
    assert victims

    h.process(
        lambda snap, planner, matrix: GenericScheduler(
            "service", snap, planner, matrix),
        mock.eval_for(job))
    plan = h.plans[-1]
    (node_id, placed), = plan.node_allocation.items()
    assert m.row_of[node_id] == row
    evicted = [a.id for a in plan.node_preemptions[node_id]]
    assert evicted == [v["id"] for v in victims]
    assert all(a.desired_description == f"Preempted by alloc ID {placed[0].id}"
               for a in plan.node_preemptions[node_id])
    got = placed[0].metrics.scores[node_id]
    for term in ("binpack", "preemption", "final"):
        assert abs(got[term] - scores[term]) <= 3e-5 * max(
            abs(scores[term]), 0.05), (term, got, scores)


# -- through the server: the applier, the follow-up evals, the counters ---------------

@pytest.fixture
def server():
    from nomad_tpu.server import Server, ServerConfig

    srv = Server(ServerConfig(
        num_workers=2, heartbeat_min_ttl=60, heartbeat_max_ttl=90,
        node_capacity=16, scheduler_config=PREEMPTION_ON))
    srv.start()
    yield srv
    srv.shutdown()


def _counter(srv, name):
    return srv.metrics.snapshot().get(name, 0)


def _full_node(srv, tier_jobs=2, objects=True):
    """One node, full: production aggregates in bucket 70 up to 1,900 MHz,
    then 2,000 MHz of priority-10 work: real allocations of ``tier_jobs``
    jobs, or (``objects`` False) an aggregate in the bucket of priority 10
    with nothing behind it."""
    node = mock.node()
    srv.register_node(node)
    m = srv.matrix
    row = m.row_of[node.id]
    used = np.array([[1900.0, 2000.0, 0.0]], np.float32)
    prio = np.zeros((1, PRIORITY_BUCKETS, 3), np.float32)
    prio[0, priority_bucket(70)] = used[0]
    if not objects:
        prio[0, priority_bucket(10)] = (2000.0, 2000.0, 0.0)
        used = used + prio[0, priority_bucket(10)]
    m.set_usage(np.array([row]), used, prio)
    if objects:
        allocs = []
        for t in range(tier_jobs):
            j = mock.batch_job(priority=10)
            j.id = f"tier-{t}"
            tg = j.task_groups[0]
            tg.count = 8 // tier_jobs
            tg.tasks[0].resources = Resources(cpu=250, memory_mb=250)
            tg.ephemeral_disk.size_mb = 0
            srv.store.upsert_job(srv.next_index(), j)
            for k in range(tg.count):
                a = mock.alloc(j, node)
                a.name = f"{j.id}.web[{k}]"
                allocs.append(a)
        srv.store.upsert_allocs(srv.next_index(), allocs)
    return node


def _service_job(count=1, cpu=400, mem=300, priority=50):
    job = mock.job(priority=priority)
    tg = job.task_groups[0]
    tg.count = count
    tg.tasks[0].resources = Resources(cpu=cpu, memory_mb=mem)
    tg.ephemeral_disk.size_mb = 0
    return job


def test_aggregates_under_real_objects_commit_at_the_first_attempt(server):
    """PERF.md section 7 (PR 36): the plan of a service job on a full node
    named no eviction, because the host summed the node's objects and
    missed the aggregates under them."""
    srv = server
    node = _full_node(srv, tier_jobs=2)
    job = _service_job(count=1, cpu=400)
    ev = srv.submit_job(job)
    done = srv.wait_for_eval(ev.id, timeout=120)
    assert done is not None and done.status == "complete", done
    assert not done.failed_tg_allocs
    snap = srv.metrics.snapshot()
    assert snap.get("nomad.plan.result{outcome=committed}", 0) == 1
    assert snap.get("nomad.plan.result{outcome=rejected}", 0) == 0
    live = [a for a in srv.store.allocs_by_job(job.namespace, job.id)
            if not a.terminal_status()]
    assert len(live) == 1 and live[0].node_id == node.id
    evicted = [a for a in srv.store.allocs.values()
               if a.desired_status == "evict"]
    # 400 MHz on a node with 0 left: two allocations of 250
    assert len(evicted) == 2
    assert {a.desired_description for a in evicted} == {
        f"Preempted by alloc ID {live[0].id}"}
    assert snap["nomad.plan.preempted_allocs"] == 2
    host = srv.matrix.snapshot_host()
    row = srv.matrix.row_of[node.id]
    assert (host["used"][row] <= host["totals"][row]).all()
    # One follow-up eval per job that lost allocations, in the plan's index.
    lost = {a.job_id for a in evicted}
    follow = [e for e in srv.store.evals.values()
              if e.triggered_by == EvalTrigger.PREEMPTION.value]
    assert sorted(e.job_id for e in follow) == sorted(lost)
    assert snap["nomad.plan.preemption_evals"] == len(lost)
    for e in follow:
        assert e.type == "batch" and e.priority == 10
        assert e.create_index == live[0].create_index
    assert snap["nomad.kernel.preempt_picks_total"] == 1
    # The evicted jobs find no room (priority 10 evicts nothing) and block.
    assert _wait(lambda: srv.blocked_evals.blocked_count() == len(lost), 30)


def test_a_plan_without_preemptions_creates_no_eval(server):
    srv = server
    srv.register_node(mock.node())
    ev = srv.submit_job(_service_job(count=2))
    done = srv.wait_for_eval(ev.id, timeout=120)
    assert done is not None and done.status == "complete"
    assert not [e for e in srv.store.evals.values()
                if e.triggered_by == EvalTrigger.PREEMPTION.value]
    assert _counter(srv, "nomad.plan.preempted_allocs") == 0
    assert _counter(srv, "nomad.plan.preemption_evals") == 0


def test_evictable_usage_with_nothing_behind_it_bans_the_node(server):
    """The kernel reads the node as preemptable (its bucket of priority
    10 holds 2,000 MHz), no allocation stands behind that usage: the eval
    bans the node as it would for a port conflict, counts it, and ends
    with the placement failed instead of naming the node attempt after
    attempt."""
    srv = server
    _full_node(srv, objects=False)
    ev = srv.submit_job(_service_job(count=1))
    done = srv.wait_for_eval(ev.id, timeout=120)
    assert done is not None and done.status == "complete"
    assert done.failed_tg_allocs
    assert _counter(srv, "nomad.sched.preempt_no_victims") == 1
    assert _counter(srv, "nomad.plan.preempted_allocs") == 0
    assert not [a for a in srv.store.allocs.values()
                if a.desired_status == "evict"]


def test_on_a_cluster_with_room_preemption_on_evicts_nothing(server):
    """Twelve nodes carrying priority-10 work with room on each: jobs of
    priority 50 that may evict it never do."""
    srv = server
    nodes = [mock.node() for _ in range(12)]
    for n in nodes:
        srv.register_node(n)
    tier = mock.batch_job(priority=10)
    tier.id = "tier"
    tg = tier.task_groups[0]
    tg.count = 4 * len(nodes)
    tg.tasks[0].resources = Resources(cpu=600, memory_mb=600)
    tg.ephemeral_disk.size_mb = 0
    srv.store.upsert_job(srv.next_index(), tier)
    allocs = []
    for i, n in enumerate(nodes):
        for k in range(4):
            a = mock.alloc(tier, n)
            a.name = f"tier.web[{4 * i + k}]"
            allocs.append(a)
    srv.store.upsert_allocs(srv.next_index(), allocs)
    evs = [srv.submit_job(_service_job(count=3, cpu=300)) for _ in range(6)]
    for ev in evs:
        done = srv.wait_for_eval(ev.id, timeout=120)
        assert done is not None and done.status == "complete"
        assert not done.failed_tg_allocs
    assert _counter(srv, "nomad.plan.preempted_allocs") == 0
    assert srv.coalescer.preempt_picks == 0
    assert not [a for a in srv.store.allocs.values()
                if a.desired_status == "evict"]


@pytest.mark.parametrize("program", ("fused", "twin"))
def test_on_a_cluster_with_room_the_program_never_preempts(program,
                                                           eight_devices):
    """Every step of an eight-step scan on the seeded simcluster (room on
    every node, four random priority buckets in use): no PREEMPT column
    is set although the request may evict."""
    m = simcluster.build_cluster(480, 512, 96_000, seed=29)
    _, _, req = preempting_request(m, priority=90)
    out = launch(program, m, [req] * 2, [8, 3], n_placements=8)
    assert (out[0, :, kernels.PACKED_ROW] >= 0).all()
    assert (out[:, :, kernels.PACKED_PREEMPT] == 0.0).all()


# -- with preemption off nothing changed ----------------------------------------------

# sha256 of the packed output of ``fused_place_batch`` on commit 7482429
# (the parent of PR 37), CPU backend: (cluster seed, lanes, lane steps).
PARENT = {
    (29, 16, (1, 2, 3, 8, 0, 5)):
        "89e1a1c79dd020edab120f5e82ab9103867cc1a97e741fcb8c217ed5e0cb2fd6",
    (7, 64, (8,)):
        "732ee375ae843bfc6b7e0a7e85ccec1254cd664ec25c4d92cbea6208f2c962c0",
    (11, 8, (4, 1)):
        "e636bf5cb17e72589fd2ab46b459dff114825ec3c543ca1ad481723fc7a65813",
}


@pytest.mark.parametrize("case", sorted(PARENT))
def test_with_preemption_off_the_packed_output_is_the_parents(case):
    """The eight shapes of the served traffic on the seeded simcluster,
    in-flight deltas on every third lane, ``Features.preempt`` false: the
    bytes of the packed output."""
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
    out = np.asarray(kernels.fused_place_batch(
        arrays, arrays.used, *ops, ls, n_placements=8,
        features=kernels.FULL_FEATURES._replace(preempt=False)))
    digest = hashlib.sha256(np.ascontiguousarray(out).tobytes()).hexdigest()
    assert digest == PARENT[case]
