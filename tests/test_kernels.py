"""Golden tests: JAX scheduling kernels vs. the scalar oracle.

Tier-1 strategy from SURVEY.md §4: the vectorized kernels are parity-tested
against the scalar reference implementation (nomad_tpu.structs.funcs, which
mirrors nomad/structs/funcs.go and scheduler/rank.go semantics).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from nomad_tpu.ops import RequestEncoder, place_task_group, verify_plan_fit
from nomad_tpu.ops.kernels import NEG_INF, score_nodes
from nomad_tpu.state import NodeMatrix
from nomad_tpu.structs import (
    Affinity,
    Allocation,
    Constraint,
    DriverInfo,
    Job,
    Node,
    NodeResources,
    Resources,
    Spread,
    SpreadTarget,
    Task,
    TaskGroup,
    score_fit_binpack,
    score_fit_spread,
)


def make_node(cpu=4000, mem=8192, dc="dc1", node_class="", attrs=None, **kw):
    return Node(
        datacenter=dc,
        node_class=node_class,
        attributes=attrs or {},
        resources=NodeResources(cpu=cpu, memory_mb=mem, disk_mb=100 * 1024),
        drivers={"mock": DriverInfo()},
        **kw,
    )


def make_job(cpu=500, mem=256, count=1, constraints=None, affinities=None,
             spreads=None, **kw):
    tg = TaskGroup(
        name="web",
        count=count,
        tasks=[Task(resources=Resources(cpu=cpu, memory_mb=mem))],
        constraints=constraints or [],
        affinities=affinities or [],
        spreads=spreads or [],
    )
    return Job(task_groups=[tg], **kw)


def setup(nodes):
    m = NodeMatrix(capacity=max(16, len(nodes)))
    for n in nodes:
        m.upsert_node(n)
    return m


def run_place(m, job, count=1, algorithm="binpack", penalty_rows=(),
              preemption=False):
    enc = RequestEncoder(m)
    tg = job.task_groups[0]
    compiled = enc.compile(job, tg, algorithm=algorithm,
                           preemption_enabled=preemption)
    arrays = m.sync()
    n = arrays.used.shape[0]
    penalty = np.zeros((n,), bool)
    for r in penalty_rows:
        penalty[r] = True
    from nomad_tpu.ops.encode import MAX_SPREADS, MAX_SPREAD_VALUES

    spread_counts = jnp.zeros((MAX_SPREADS, MAX_SPREAD_VALUES), jnp.float32)
    tg_count = jnp.zeros((n,), jnp.int32)
    return place_task_group(
        arrays,
        compiled.request,
        arrays.used,
        tg_count,
        spread_counts,
        jnp.asarray(penalty),
        None,
        None,
        count,
    )


class TestBinpackSelection:
    def test_picks_most_packed_node(self):
        # Binpack prefers the node whose post-placement utilization is higher.
        busy, idle = make_node(), make_node()
        m = setup([busy, idle])
        job0 = Job()
        m.add_alloc(Allocation(node_id=busy.id, job=job0,
                               resources=Resources(cpu=2000, memory_mb=4096)))
        res = run_place(m, make_job())
        assert int(res.rows[0]) == m.row_of[busy.id]

    def test_spread_algorithm_picks_empty_node(self):
        busy, idle = make_node(), make_node()
        m = setup([busy, idle])
        m.add_alloc(Allocation(node_id=busy.id, job=Job(),
                               resources=Resources(cpu=2000, memory_mb=4096)))
        res = run_place(m, make_job(), algorithm="spread")
        assert int(res.rows[0]) == m.row_of[idle.id]

    def test_binpack_score_matches_oracle(self):
        node = make_node(cpu=4000, mem=8192)
        m = setup([node])
        m.add_alloc(Allocation(node_id=node.id, job=Job(),
                               resources=Resources(cpu=1000, memory_mb=2048)))
        res = run_place(m, make_job(cpu=500, mem=256))
        util = Resources(cpu=1500, memory_mb=2304)
        expected = score_fit_binpack(node, util) / 18.0
        assert np.isclose(float(res.binpack[0]), expected, atol=1e-5)

    def test_resource_exhaustion(self):
        node = make_node(cpu=1000, mem=1024)
        m = setup([node])
        res = run_place(m, make_job(cpu=2000, mem=100))
        assert int(res.rows[0]) == -1
        assert int(res.nodes_exhausted[0]) == 1

    def test_sequential_placements_account_usage(self):
        # Two placements of 600 CPU on a 1000-CPU node: second must go elsewhere.
        small, big = make_node(cpu=1000, mem=8192), make_node(cpu=4000, mem=8192)
        m = setup([small, big])
        res = run_place(m, make_job(cpu=600, mem=100, count=2), count=2)
        rows = {int(res.rows[0]), int(res.rows[1])}
        assert rows == {m.row_of[small.id], m.row_of[big.id]} or rows == {m.row_of[big.id]}
        # used_after reflects both placements
        assert float(res.used_after.sum()) >= 1200


class TestFeasibility:
    def test_datacenter_filter(self):
        n1, n2 = make_node(dc="dc1"), make_node(dc="dc2")
        m = setup([n1, n2])
        job = make_job()
        job.datacenters = ["dc2"]
        res = run_place(m, job)
        assert int(res.rows[0]) == m.row_of[n2.id]

    def test_constraint_eq(self):
        n1 = make_node(attrs={"kernel.name": "linux"})
        n2 = make_node(attrs={"kernel.name": "darwin"})
        m = setup([n1, n2])
        job = make_job(constraints=[
            Constraint(l_target="${attr.kernel.name}", operand="=", r_target="linux")
        ])
        res = run_place(m, job)
        assert int(res.rows[0]) == m.row_of[n1.id]

    def test_constraint_neq_passes_missing_attr(self):
        # "!=" passes when the attribute is absent (feasible.go:797).
        n1 = make_node(attrs={"foo.bar": "x"})
        n2 = make_node()
        m = setup([n1, n2])
        job = make_job(constraints=[
            Constraint(l_target="${attr.foo.bar}", operand="!=", r_target="x")
        ])
        res = run_place(m, job)
        assert int(res.rows[0]) == m.row_of[n2.id]

    def test_numeric_comparison(self):
        n1 = make_node(attrs={"cpu.numcores": "4"})
        n2 = make_node(attrs={"cpu.numcores": "16"})
        m = setup([n1, n2])
        job = make_job(constraints=[
            Constraint(l_target="${attr.cpu.numcores}", operand=">=", r_target="8")
        ])
        res = run_place(m, job)
        assert int(res.rows[0]) == m.row_of[n2.id]

    def test_version_constraint(self):
        n1 = make_node(attrs={"os.version": "1.2.3"})
        n2 = make_node(attrs={"os.version": "2.0.0"})
        m = setup([n1, n2])
        job = make_job(constraints=[
            Constraint(l_target="${attr.os.version}", operand="version",
                       r_target=">= 2.0")
        ])
        res = run_place(m, job)
        assert int(res.rows[0]) == m.row_of[n2.id]

    def test_driver_filter(self):
        n1 = make_node()
        n2 = make_node()
        n2.drivers = {"docker": DriverInfo()}  # no mock driver
        m = setup([n1, n2])
        res = run_place(m, make_job())  # mock driver task
        assert int(res.rows[0]) == m.row_of[n1.id]

    def test_ineligible_node_filtered(self):
        n1, n2 = make_node(), make_node()
        n2.drain = True
        m = setup([n1, n2])
        res = run_place(m, make_job())
        assert int(res.rows[0]) == m.row_of[n1.id]

    def test_no_feasible_nodes(self):
        m = setup([make_node(dc="dc9")])
        res = run_place(m, make_job())  # wants dc1
        assert int(res.rows[0]) == -1
        assert int(res.nodes_filtered[0]) == 1

    def test_device_constraint(self):
        gpu_node = make_node()
        gpu_node.resources.devices = {"gpu": ["g0", "g1"]}
        plain = make_node()
        m = setup([gpu_node, plain])
        from nomad_tpu.structs import RequestedDevice

        job = make_job()
        job.task_groups[0].tasks[0].resources.devices = [
            RequestedDevice(name="gpu", count=1)
        ]
        res = run_place(m, job)
        assert int(res.rows[0]) == m.row_of[gpu_node.id]


class TestScoring:
    def test_anti_affinity_spreads_same_job(self):
        # With equal binpack, a node already hosting this TG is penalized
        # (rank.go:601: -(collisions+1)/desired_count appended when >0).
        a, b = make_node(), make_node()
        m = setup([a, b])
        res = run_place(m, make_job(count=2), count=2)
        assert {int(res.rows[0]), int(res.rows[1])} == {0, 1}

    def test_reschedule_penalty_avoids_prev_node(self):
        a, b = make_node(), make_node()
        m = setup([a, b])
        res = run_place(m, make_job(), penalty_rows=[m.row_of[a.id]])
        assert int(res.rows[0]) == m.row_of[b.id]

    def test_affinity_attracts(self):
        n1 = make_node(attrs={"rack": "r1"})
        n2 = make_node(attrs={"rack": "r2"})
        m = setup([n1, n2])
        job = make_job(affinities=[
            Affinity(l_target="${attr.rack}", operand="=", r_target="r2", weight=100)
        ])
        res = run_place(m, job)
        assert int(res.rows[0]) == m.row_of[n2.id]

    def test_negative_affinity_repels(self):
        n1 = make_node(attrs={"rack": "r1"})
        n2 = make_node(attrs={"rack": "r2"})
        m = setup([n1, n2])
        job = make_job(affinities=[
            Affinity(l_target="${attr.rack}", operand="=", r_target="r2", weight=-100)
        ])
        res = run_place(m, job)
        assert int(res.rows[0]) == m.row_of[n1.id]

    def test_even_spread(self):
        # Even spread over node.datacenter: 4 placements over 2 DCs → 2+2.
        nodes = [make_node(dc="dc1"), make_node(dc="dc1"),
                 make_node(dc="dc2"), make_node(dc="dc2")]
        m = setup(nodes)
        job = make_job(count=4, spreads=[Spread(attribute="${node.datacenter}")])
        job.datacenters = ["dc1", "dc2"]
        res = run_place(m, job, count=4)
        dcs = [nodes[int(r)].datacenter for r in res.rows]
        assert sorted(dcs) == ["dc1", "dc1", "dc2", "dc2"]

    def test_targeted_spread(self):
        # 70/30 split over 10 placements lands ~7/3.
        nodes = [make_node(dc="dc1", cpu=100000, mem=100000),
                 make_node(dc="dc2", cpu=100000, mem=100000)]
        m = setup(nodes)
        job = make_job(
            cpu=10, mem=10, count=10,
            spreads=[Spread(attribute="${node.datacenter}", weight=100,
                            targets=[SpreadTarget(value="dc1", percent=70),
                                     SpreadTarget(value="dc2", percent=30)])],
        )
        job.datacenters = ["dc1", "dc2"]
        res = run_place(m, job, count=10)
        dcs = [nodes[int(r)].datacenter for r in res.rows]
        # Job anti-affinity (always active in the generic stack) interleaves
        # with targeted spread, so the split lands near — not exactly on —
        # 7/3 (hand-tracing the reference formulas gives 6/4..7/3).
        assert dcs.count("dc1") in (6, 7)
        assert dcs.count("dc2") == 10 - dcs.count("dc1")


class TestPreemption:
    def test_preemption_enables_placement(self):
        # Node full of low-priority work; high-priority job preempts.
        node = make_node(cpu=1000, mem=1024)
        m = setup([node])
        low = Job(priority=10)
        m.add_alloc(Allocation(node_id=node.id, job=low,
                               resources=Resources(cpu=900, memory_mb=900)))
        job = make_job(cpu=500, mem=500)
        job.priority = 70
        res = run_place(m, job, preemption=False)
        assert int(res.rows[0]) == -1
        res = run_place(m, job, preemption=True)
        assert int(res.rows[0]) == m.row_of[node.id]
        assert bool(res.preempted[0])

    def test_no_preemption_of_high_priority(self):
        # Victims must be > 10 priority points below (preemption.go:663).
        node = make_node(cpu=1000, mem=1024)
        m = setup([node])
        m.add_alloc(Allocation(node_id=node.id, job=Job(priority=65),
                               resources=Resources(cpu=900, memory_mb=900)))
        job = make_job(cpu=500, mem=500)
        job.priority = 70
        res = run_place(m, job, preemption=True)
        assert int(res.rows[0]) == -1


class TestVerifyPlanFit:
    def test_verify(self):
        n1 = make_node(cpu=1000, mem=1024)
        n2 = make_node(cpu=4000, mem=8192)
        m = setup([n1, n2])
        m.add_alloc(Allocation(node_id=n1.id, job=Job(),
                               resources=Resources(cpu=800, memory_mb=100)))
        arrays = m.sync()
        rows = jnp.asarray([m.row_of[n1.id], m.row_of[n2.id], -1], jnp.int32)
        deltas = jnp.asarray(
            [[500.0, 10.0, 0.0], [500.0, 10.0, 0.0], [0, 0, 0]], jnp.float32
        )
        elig = jnp.asarray([True, True, True])
        ok = verify_plan_fit(arrays, rows, deltas, elig)
        assert not bool(ok[0])  # 800+500 > 1000
        assert bool(ok[1])
        assert bool(ok[2])  # padding passes

    def test_host_twin_matches_kernel(self):
        """The plan applier's host fast path (plan_apply._evaluate) must be
        bit-identical to verify_plan_fit over the same aggregates."""
        rng = np.random.default_rng(3)
        nodes = [
            make_node(cpu=int(c), mem=int(mm))
            for c, mm in rng.integers(500, 8000, (12, 2))
        ]
        m = setup(nodes)
        for n in nodes[:6]:
            m.add_alloc(Allocation(node_id=n.id, job=Job(), resources=(
                Resources(cpu=int(rng.integers(100, 2000)),
                          memory_mb=int(rng.integers(100, 2000))))))
        m.snapshot_host()["eligible"][3] = False
        m._dirty.add(3)
        arrays = m.sync()
        host = m.snapshot_host()

        k = 12
        rows = np.arange(k, dtype=np.int32)
        deltas = rng.uniform(0, 4000, (k, 3)).astype(np.float32)
        elig_required = rng.random(k) < 0.5

        kernel = np.asarray(verify_plan_fit(
            arrays, jnp.asarray(rows), jnp.asarray(deltas),
            jnp.asarray(elig_required),
        ))
        used = host["used"][rows] + deltas
        fits = np.all(used <= host["totals"][rows], axis=1)
        host_v = fits & (~elig_required | host["eligible"][rows])
        assert (kernel == host_v).all()


class TestPlaceBatch:
    def test_matches_solo_scan(self):
        """A lane of fused_place_batch (the coalescer kernel) must equal
        that request's own place_task_group run, including sparse delta
        application."""
        from helpers import lane_operands, solo_reference
        from nomad_tpu.ops.kernels import fused_place_batch

        nodes = [make_node(cpu=2000 + 500 * i, mem=4096) for i in range(6)]
        m = setup(nodes)
        jobs = [make_job(cpu=300 + 100 * i, mem=256) for i in range(3)]
        enc = RequestEncoder(m)
        reqs = [enc.compile(j, j.task_groups[0]).request for j in jobs]
        arrays = m.sync()

        scan_len = 4
        # Request 1 carries an in-flight delta on row 5.
        ops = lane_operands(
            m, reqs, deltas={1: [(5, (1500.0, 0.0, 0.0))]}, max_deltas=8
        )
        packed = np.asarray(fused_place_batch(
            arrays, arrays.used, *ops, np.full((3,), scan_len, np.int32),
            n_placements=scan_len,
        ))
        solo = solo_reference(arrays, ops, scan_len)
        assert (packed[:, :, 0] >= 0).any()
        np.testing.assert_array_equal(packed[:, :, 0], solo[:, :, 0])
        np.testing.assert_allclose(packed[:, :, 1], solo[:, :, 1], rtol=1e-5)
        # The delta moved lane 1 off what it would pick without it.
        bare = solo_reference(arrays, lane_operands(m, reqs), scan_len)
        assert (bare[1, :, 0] != solo[1, :, 0]).any()


class TestEncodingEscapes:
    def test_version_two_component_attr(self):
        # Node attr "2.0" must satisfy "version >= 1.5" (version packing is
        # applied on both sides; plain-numeric and version columns are split).
        n1 = make_node(attrs={"os.version": "2.0"})
        m = setup([n1])
        job = make_job(constraints=[
            Constraint(l_target="${attr.os.version}", operand="version",
                       r_target=">= 1.5")
        ])
        res = run_place(m, job)
        assert int(res.rows[0]) == m.row_of[n1.id]

    def test_device_registry_overflow_escapes(self):
        m = setup([make_node()])
        for i in range(m.devices.slots):
            m.devices.register(f"dev{i}")
        from nomad_tpu.structs import RequestedDevice
        from nomad_tpu.ops import RequestEncoder

        job = make_job()
        job.task_groups[0].tasks[0].resources.devices = [
            RequestedDevice(name="unregistered/tpu", count=1)
        ]
        enc = RequestEncoder(m)
        compiled = enc.compile(job, job.task_groups[0])
        assert compiled.escaped_devices == [("unregistered/tpu", 1)]

    def test_datacenter_overflow_escapes(self):
        n = make_node(dc="dc9")
        m = setup([n])
        from nomad_tpu.ops import RequestEncoder

        job = make_job()
        job.datacenters = [f"dc{i}" for i in range(12)]  # > MAX_DATACENTERS
        enc = RequestEncoder(m)
        compiled = enc.compile(job, job.task_groups[0])
        assert compiled.dc_escaped
        # Kernel skips the dc check; host filter takes over.
        res = run_place(m, job)
        assert int(res.rows[0]) == m.row_of[n.id]
