"""Parity suite: the fake-device numpy twins vs. the JAX kernels.

The fake-device backend (NOMAD_TPU_FAKE_DEVICE=1, ops/fake_device.py) must
be semantically identical to the kernels it replaces — same chosen rows,
same scores, same metric counters — on small matrices where the JAX
versions are cheap to run.  The host-loop throughput work is only honest
if the isolation layer doesn't change scheduling decisions.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from nomad_tpu.ops import RequestEncoder
from nomad_tpu.ops import fake_device, kernels
from nomad_tpu.ops.encode import MAX_SPREADS, MAX_SPREAD_VALUES
from nomad_tpu.state import NodeMatrix
from nomad_tpu.state.matrix import DeviceArrays
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


def host_view(arrays) -> DeviceArrays:
    """Numpy copy of a (jax) DeviceArrays snapshot."""
    return DeviceArrays(
        **{f: np.asarray(getattr(arrays, f)) for f in DeviceArrays._fields}
    )


def assert_same_placement(m, job, count=1, algorithm="binpack",
                          preemption=False, penalty_rows=(),
                          host_mask=None, class_elig=None):
    enc = RequestEncoder(m)
    tg = job.task_groups[0]
    compiled = enc.compile(job, tg, algorithm=algorithm,
                           preemption_enabled=preemption)
    arrays = m.sync()
    host = host_view(arrays)
    n = host.used.shape[0]
    penalty = np.zeros((n,), bool)
    for r in penalty_rows:
        penalty[r] = True
    sc = np.zeros((MAX_SPREADS, MAX_SPREAD_VALUES), np.float32)
    tgc = np.zeros((n,), np.int32)
    hm = np.ones((n,), bool) if host_mask is None else host_mask
    ce = np.ones((4,), bool) if class_elig is None else class_elig

    kres = kernels.place_task_group(
        arrays, compiled.request, arrays.used, jnp.asarray(tgc),
        jnp.asarray(sc), jnp.asarray(penalty), jnp.asarray(ce),
        jnp.asarray(hm), count,
    )
    fres = fake_device.place_task_group(
        host, compiled.request, host.used, tgc, sc, penalty, ce, hm, count,
    )
    assert (np.asarray(kres.rows) == fres.rows).all(), (
        np.asarray(kres.rows), fres.rows,
    )
    np.testing.assert_allclose(
        np.asarray(kres.scores), fres.scores, rtol=1e-4, atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(kres.binpack), fres.binpack, rtol=1e-4, atol=1e-5
    )
    assert (np.asarray(kres.preempted) == fres.preempted).all()
    assert (np.asarray(kres.nodes_evaluated) == fres.nodes_evaluated).all()
    assert (np.asarray(kres.nodes_filtered) == fres.nodes_filtered).all()
    assert (np.asarray(kres.nodes_exhausted) == fres.nodes_exhausted).all()
    return kres, fres


class TestPlacementParity:
    def test_binpack_pick(self):
        busy, idle = make_node(), make_node()
        m = setup([busy, idle])
        m.add_alloc(Allocation(node_id=busy.id, job=Job(),
                               resources=Resources(cpu=2000, memory_mb=4096)))
        assert_same_placement(m, make_job())

    def test_spread_algorithm(self):
        busy, idle = make_node(), make_node()
        m = setup([busy, idle])
        m.add_alloc(Allocation(node_id=busy.id, job=Job(),
                               resources=Resources(cpu=2000, memory_mb=4096)))
        assert_same_placement(m, make_job(), algorithm="spread")

    def test_multi_placement_accounting(self):
        small = make_node(cpu=1000, mem=8192)
        big = make_node(cpu=4000, mem=8192)
        m = setup([small, big])
        assert_same_placement(m, make_job(cpu=600, mem=100, count=2), count=2)

    def test_exhaustion_and_replication(self):
        # One feasible-but-full node: the failed-step replication path must
        # match the kernel's scan output for every remaining step.
        m = setup([make_node(cpu=1000, mem=1024)])
        assert_same_placement(m, make_job(cpu=2000, mem=100), count=4)

    def test_constraints(self):
        n1 = make_node(attrs={"kernel.name": "linux", "cpu.numcores": "4"})
        n2 = make_node(attrs={"kernel.name": "darwin", "cpu.numcores": "16"})
        m = setup([n1, n2])
        job = make_job(constraints=[
            Constraint(l_target="${attr.kernel.name}", operand="=",
                       r_target="linux"),
        ])
        assert_same_placement(m, job)
        job2 = make_job(constraints=[
            Constraint(l_target="${attr.cpu.numcores}", operand=">=",
                       r_target="8"),
        ])
        assert_same_placement(m, job2)

    def test_version_constraint(self):
        n1 = make_node(attrs={"os.version": "1.2.3"})
        n2 = make_node(attrs={"os.version": "2.0.0"})
        m = setup([n1, n2])
        job = make_job(constraints=[
            Constraint(l_target="${attr.os.version}", operand="version",
                       r_target=">= 2.0"),
        ])
        assert_same_placement(m, job)

    def test_datacenter_filter(self):
        m = setup([make_node(dc="dc1"), make_node(dc="dc2")])
        job = make_job()
        job.datacenters = ["dc2"]
        assert_same_placement(m, job)

    def test_affinity(self):
        n1 = make_node(attrs={"rack": "r1"})
        n2 = make_node(attrs={"rack": "r2"})
        m = setup([n1, n2])
        for w in (100, -100):
            job = make_job(affinities=[
                Affinity(l_target="${attr.rack}", operand="=",
                         r_target="r2", weight=w)
            ])
            assert_same_placement(m, job)

    def test_penalty(self):
        a, b = make_node(), make_node()
        m = setup([a, b])
        assert_same_placement(m, make_job(), penalty_rows=[m.row_of[a.id]])

    def test_even_spread(self):
        nodes = [make_node(dc="dc1"), make_node(dc="dc1"),
                 make_node(dc="dc2"), make_node(dc="dc2")]
        m = setup(nodes)
        job = make_job(count=4,
                       spreads=[Spread(attribute="${node.datacenter}")])
        job.datacenters = ["dc1", "dc2"]
        assert_same_placement(m, job, count=4)

    def test_targeted_spread(self):
        nodes = [make_node(dc="dc1", cpu=100000, mem=100000),
                 make_node(dc="dc2", cpu=100000, mem=100000)]
        m = setup(nodes)
        job = make_job(
            cpu=10, mem=10, count=8,
            spreads=[Spread(attribute="${node.datacenter}", weight=100,
                            targets=[SpreadTarget(value="dc1", percent=70),
                                     SpreadTarget(value="dc2", percent=30)])],
        )
        job.datacenters = ["dc1", "dc2"]
        assert_same_placement(m, job, count=8)

    def test_preemption(self):
        node = make_node(cpu=1000, mem=1024)
        m = setup([node])
        m.add_alloc(Allocation(node_id=node.id, job=Job(priority=10),
                               resources=Resources(cpu=900, memory_mb=900)))
        job = make_job(cpu=500, mem=500)
        job.priority = 70
        assert_same_placement(m, job, preemption=True)

    def test_device_ask(self):
        gpu = make_node()
        gpu.resources.devices = {"gpu": ["g0", "g1"]}
        m = setup([gpu, make_node()])
        from nomad_tpu.structs import RequestedDevice

        job = make_job()
        job.task_groups[0].tasks[0].resources.devices = [
            RequestedDevice(name="gpu", count=1)
        ]
        assert_same_placement(m, job)

    def test_randomized_clusters(self):
        # Property check over randomized capacities/usages: identical rows
        # and metrics on every scan step.
        rng = np.random.default_rng(7)
        for trial in range(5):
            nodes = [
                make_node(cpu=int(c), mem=int(mm),
                          dc=f"dc{int(d)}")
                for c, mm, d in zip(
                    rng.integers(1000, 16000, 10),
                    rng.integers(1024, 32768, 10),
                    rng.integers(1, 3, 10),
                )
            ]
            m = setup(nodes)
            for n in nodes[: 5 + trial]:
                m.add_alloc(Allocation(
                    node_id=n.id, job=Job(priority=int(rng.integers(1, 90))),
                    resources=Resources(
                        cpu=int(rng.integers(100, 900)),
                        memory_mb=int(rng.integers(64, 900)),
                    ),
                ))
            job = make_job(cpu=int(rng.integers(100, 2000)),
                           mem=int(rng.integers(64, 2000)), count=3)
            job.datacenters = ["dc1", "dc2"]
            assert_same_placement(m, job, count=3)


class TestSystemAndVerifyParity:
    def test_system_feasible(self):
        nodes = [make_node(cpu=1000 + 700 * i, mem=2048) for i in range(5)]
        nodes[2].drain = True
        m = setup(nodes)
        job = make_job(cpu=1500, mem=512)
        enc = RequestEncoder(m)
        compiled = enc.compile(job, job.task_groups[0])
        arrays = m.sync()
        host = host_view(arrays)
        n = host.used.shape[0]
        ce = np.ones((4,), bool)
        hm = np.ones((n,), bool)
        kern = np.asarray(kernels.system_feasible(
            arrays, arrays.used, compiled.request, jnp.asarray(ce),
            jnp.asarray(hm),
        ))
        fake = fake_device.system_feasible(
            host, host.used, compiled.request, ce, hm,
        )
        assert (kern == fake).all()

    def test_verify_plan_fit(self):
        rng = np.random.default_rng(11)
        nodes = [make_node(cpu=int(c), mem=int(mm))
                 for c, mm in rng.integers(500, 8000, (8, 2))]
        m = setup(nodes)
        for n in nodes[:4]:
            m.add_alloc(Allocation(node_id=n.id, job=Job(), resources=(
                Resources(cpu=int(rng.integers(100, 2000)),
                          memory_mb=int(rng.integers(100, 2000))))))
        arrays = m.sync()
        host = host_view(arrays)
        rows = np.array([0, 1, 2, 3, -1], np.int32)
        deltas = rng.uniform(0, 4000, (5, 3)).astype(np.float32)
        elig = rng.random(5) < 0.5
        kern = np.asarray(kernels.verify_plan_fit(
            arrays, jnp.asarray(rows), jnp.asarray(deltas),
            jnp.asarray(elig),
        ))
        fake = fake_device.verify_plan_fit(host, rows, deltas, elig)
        assert (kern == fake).all()


class TestFakeSyncPath:
    def test_sync_returns_numpy_and_tracks_dirty(self, monkeypatch):
        m = setup([make_node(), make_node()])
        monkeypatch.setenv("NOMAD_TPU_FAKE_DEVICE", "1")
        m.invalidate()
        arrays = m.sync()
        assert isinstance(arrays.used, np.ndarray)
        # A host mutation must reach the next snapshot via the dirty set.
        node = make_node(cpu=12345)
        m.upsert_node(node)
        arrays2 = m.sync()
        row = m.row_of[node.id]
        assert float(arrays2.totals[row, 0]) == 12345.0
        # Flipping the backend back rebuilds a device-flavor snapshot.
        monkeypatch.delenv("NOMAD_TPU_FAKE_DEVICE")
        arrays3 = m.sync()
        assert not isinstance(arrays3.used, np.ndarray)
        assert float(np.asarray(arrays3.totals)[row, 0]) == 12345.0
