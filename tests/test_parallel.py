"""The batched placement program over a mesh: the sharded program must
agree exactly with the single-device one (tier-1 parity testing on the
8-device virtual CPU mesh)."""

import json
import os
import sys
import time
import types
import urllib.request

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.ops import kernels
from nomad_tpu.ops.encode import RequestEncoder
from nomad_tpu.state.matrix import NodeMatrix

from helpers import (
    LAUNCH_FILLS,
    assert_bits_equal,
    check_enqueue_span,
    check_packed_launch,
    check_sync_span,
    collectives,
    dirty_hard_rows,
    host_mirror,
    lane_operands,
    plain_mesh_program,
)


def _cluster(n_nodes=32, capacity=64, seed=0):
    rng = np.random.default_rng(seed)
    m = NodeMatrix(capacity=capacity)
    nodes = []
    for i in range(n_nodes):
        n = mock.node()
        n.attributes = dict(n.attributes)
        n.attributes["rack"] = f"r{i % 4}"
        nodes.append(n)
        m.upsert_node(n)
    # Random pre-existing usage.
    host = m.snapshot_host()
    rows = [m.row_of[n.id] for n in nodes]
    for r in rows:
        host["used"][r] = rng.uniform(0, 0.5, 3) * host["totals"][r]
        m._dirty.add(r)
    return m, nodes


class TestShardedStep:
    def test_mesh_factoring(self, eight_devices):
        from nomad_tpu.parallel import make_mesh

        mesh = make_mesh(8)
        assert mesh.devices.shape == (2, 4)
        assert mesh.axis_names == ("batch", "node")


class TestMeshLayout:
    """The rule a server lays its devices out by (PERF.md section 6, PR 26)."""

    @pytest.mark.parametrize("devices,capacity,want", [
        (1, 64, (1, 1)),
        (2, 512, (1, 2)),
        (4, 102_400, (2, 2)),      # the four-chip cell
        (8, 512, (2, 4)),
        (3, 102_402, (1, 3)),      # survivors, matrix re-laid to a multiple
        (3, 102_400, (3, 1)),      # survivors the capacity does not divide by
        (6, 102_400, (6, 1)),
    ])
    def test_layout_from_devices_and_capacity(self, devices, capacity, want):
        from nomad_tpu.parallel.sharding import mesh_layout

        batch, node = mesh_layout(devices, capacity)
        assert (batch, node) == want
        assert batch * node == devices and capacity % node == 0


class TestMultichipLiveServer:
    def test_live_placements_match_single_device(self, eight_devices, tmp_path):
        """VERDICT r4 weak #7: the multi-chip step must be the code the
        server RUNS.  Boot two live servers — one single-device, one
        sharding dispatches over the 8-CPU mesh — submit identical jobs
        through broker/worker/applier, and require identical placements."""
        from nomad_tpu.server import Server, ServerConfig

        def run_cluster(shards):
            srv = Server(ServerConfig(
                num_workers=2,
                heartbeat_min_ttl=60, heartbeat_max_ttl=90,
                node_capacity=64,
                n_device_shards=shards,
            ))
            srv.start()
            try:
                for i in range(16):
                    node = mock.node()
                    node.name = f"n{i}"
                    node.attributes = dict(node.attributes)
                    node.attributes["rack"] = f"r{i % 4}"
                    srv.register_node(node)
                placements = {}
                for i in range(6):
                    job = mock.job()
                    job.id = f"job-{i}"
                    tg = job.task_groups[0]
                    tg.count = 2
                    tg.tasks[0].resources.cpu = 100 + 50 * (i % 3)
                    tg.tasks[0].resources.memory_mb = 64
                    ev = srv.submit_job(job)
                    done = srv.wait_for_eval(ev.id, timeout=120)
                    assert done is not None and done.status == "complete"
                    for a in srv.store.allocs_by_job("default", job.id):
                        node = srv.store.node_by_id(a.node_id)
                        placements[(job.id, a.name)] = node.name
                assert srv.coalescer.dispatches > 0
                return placements, srv.coalescer.n_device_shards
            finally:
                srv.shutdown()

        single, shards1 = run_cluster(1)
        multi, shards8 = run_cluster(8)
        assert shards1 == 1 and shards8 == 8
        assert single and multi == single


class TestShardedFusedParity:
    """The election across shards: the placement body on a mesh must agree
    EXACTLY with the same body on one device — winners, the device-resident
    VERIFIED column, preemption flags — at every shard count, a ``(1, 1)``
    mesh (the mesh's side of the seam on one device) included, and the only
    host-visible product is the packed (B, P, 8) winner block (PARITY.md
    "The election" has the tie-break proof)."""

    # (devices, batch shards): meshes (1, 1), (1, 2), (2, 2), (2, 4).
    MESHES = ((1, 1), (2, 1), (4, 2), (8, 2))

    def _deltas(self, b, n_nodes):
        """A few random in-flight deltas per lane, as GLOBAL rows: each
        shard has to apply the slice it owns."""
        rng = np.random.default_rng(3)
        return {
            i: list(zip(
                rng.choice(n_nodes, size=3, replace=False).tolist(),
                rng.uniform(0, 50, (3, 3)).tolist(),
            ))
            for i in range(b)
        }

    def _ref_and_sharded(self, m, ops, steps, scan, nshards, batch):
        from nomad_tpu.parallel import (
            make_mesh,
            shard_matrix_arrays,
            sharded_fused_place_batch,
        )

        arrays = m.sync()
        ref = kernels.fused_place_batch(
            arrays, arrays.used, *ops, steps, n_placements=scan,
        )
        mesh = make_mesh(nshards, batch=batch)
        sharded = shard_matrix_arrays(mesh, arrays)
        out = sharded_fused_place_batch(mesh, scan)(
            sharded, sharded.used, *ops, steps,
        )
        return np.asarray(ref), out

    def _assert_parity(self, r, out, where):
        o = np.asarray(out)
        for col in range(kernels.FUSED_PACKED_WIDTH):
            np.testing.assert_array_equal(
                o[:, :, col], r[:, :, col], err_msg=f"col {col} {where}"
            )
        assert o.tobytes() == r.tobytes(), where  # scores too, bit for bit

    # Per-lane step counts (0 = a dead lane, which must stay dead across
    # shardings): every live lane the whole scan, and mixed counts whose
    # largest sits on one batch shard only — the loops' trip count has to
    # be one number on every shard all the same.
    STEPS = {
        "full": [4, 4, 4, 4, 4, 4, 4, 0],
        "mixed": [1, 2, 0, 1, 3, 1, 4, 0],
    }

    @pytest.mark.parametrize("steps", sorted(STEPS))
    @pytest.mark.parametrize("nshards,batch", MESHES)
    def test_matches_unsharded_fused(self, eight_devices, nshards, batch,
                                     steps):
        m, nodes = _cluster(n_nodes=48, capacity=64)
        job1 = mock.job()
        job2 = mock.job()
        job2.task_groups[0].spreads = []
        b, scan = 8, 4
        enc = RequestEncoder(m)
        reqs_list = [
            enc.compile(j, j.task_groups[0]).request for j in (job1, job2)
        ]
        ops = lane_operands(
            m, (reqs_list * 4)[:b], deltas=self._deltas(b, 48),
            max_deltas=32,
        )
        ls = np.array(self.STEPS[steps], np.int32)
        ref, out = self._ref_and_sharded(m, ops, ls, scan, nshards, batch)
        assert (ref[ls == 0, :, kernels.PACKED_ROW] == -1).all()
        for lane, k in enumerate(ls):
            assert (ref[lane, :k, kernels.PACKED_ROW] >= 0).all()
            assert (ref[lane, k:, kernels.PACKED_ROW] == -1).all()
        # The fetched winner block is node-count independent: (B, P, 8).
        assert np.asarray(out).shape == (
            b, scan, kernels.FUSED_PACKED_WIDTH
        )
        self._assert_parity(ref, out, f"mesh ({nshards},{batch}) {steps}")

    # (nodes, lane_steps, what the resolution must have done): fat asks, a
    # node holds two or three.  Few nodes: the later lanes find no node
    # left and keep their picks (0.0).  Many: every lane passed over finds
    # another node (2.0) and nothing is left to the applier.
    CONFLICTS = {
        "no_node_left": (4, [2, 1, 2, 2, 1, 2, 2, 2], {0.0, 1.0, 2.0}),
        "all_resolved": (24, [2, 1, 3, 2, 1, 4, 2, 3], {1.0, 2.0}),
    }

    @pytest.mark.parametrize("case", sorted(CONFLICTS))
    @pytest.mark.parametrize("nshards,batch", MESHES)
    def test_cross_lane_conflicts_match(self, eight_devices, nshards,
                                        batch, case):
        """Small cluster + fat asks: later lanes collide with earlier
        winners, so the in-launch resolution (every lane's turn elects its
        best row with room across both mesh axes) and the device-resident
        AllocsFit re-verify column must give the same picks and verdicts
        under every sharding, lanes on another batch shard included."""
        n_nodes, steps, verdicts = self.CONFLICTS[case]
        m, nodes = _cluster(n_nodes=n_nodes, capacity=2 * n_nodes)
        job = mock.job()
        job.task_groups[0].tasks[0].resources.cpu = 1200
        job.task_groups[0].tasks[0].resources.memory_mb = 900
        b, scan = 8, 4
        req = RequestEncoder(m).compile(job, job.task_groups[0]).request
        ls = np.array(steps, np.int32)
        ref, out = self._ref_and_sharded(
            m, lane_operands(m, [req] * b), ls, scan, nshards, batch
        )
        placed = ref[:, :, kernels.PACKED_ROW] >= 0
        assert set(ref[placed][:, kernels.FUSED_PACKED_VERIFIED]) == verdicts, (
            "conflict case lost its teeth"
        )
        # C1 on the mesh: every asked-for slot holds a node.
        assert (placed == (np.arange(scan)[None, :] < ls[:, None])).all()
        self._assert_parity(ref, out, f"mesh ({nshards},{batch}) {case}")


class TestPackedLaunch:
    """On a mesh a launch is ONE jitted call too: the sharded placement
    program takes the two packs split over ``batch`` alone and unpacks them
    at its entry, every field held to the split its ``in_specs`` ask."""

    MESHES = [(2, (1, 2)), (4, (2, 2))]

    @pytest.mark.parametrize("rules", [False, True], ids=["dp0", "dp2"])
    @pytest.mark.parametrize("k", LAUNCH_FILLS)
    @pytest.mark.parametrize("devices,mesh_shape", MESHES)
    def test_a_launch_hands_over_two_packs_and_the_node_axis_operands(
        self, eight_devices, monkeypatch, devices, mesh_shape, k, rules,
    ):
        """tests/test_coalescer.py's check on the layouts the server gives
        two and four devices (under (2, 2) a batch shard holds 32 lanes)."""
        coal, _call = check_packed_launch(monkeypatch, k, devices, rules)
        assert coal.mesh_shape() == mesh_shape

    @pytest.mark.parametrize("devices,mesh_shape", MESHES)
    def test_the_packs_arrive_split_over_batch_and_no_field_crosses_a_chip(
        self, eight_devices, monkeypatch, devices, mesh_shape,
    ):
        """The compiled program's own word: both packs are laid out over
        ``batch`` alone, and it holds the collectives of the plain placement
        program (every operand its own, each laid out as ``in_specs`` say)
        and no other: unpacking at the entry moves nothing between chips."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        coal, (operands, static) = check_packed_launch(
            monkeypatch, 9, devices)
        assert coal.mesh_shape() == mesh_shape
        monkeypatch.undo()  # (the spy has no ``lower``)
        operands = operands[:7] + (np.asarray(coal._carry),)
        live = coal._sharded_fused_fn.lower(*operands, **static).compile()
        lanes = NamedSharding(coal._mesh, P("batch"))
        _arrays, _used, *packs = live.input_shardings[0][:4]
        assert len(packs) == 2
        assert all(s.is_equivalent_to(lanes, 2) for s in packs), packs

        st, slab = coal._stage[0], coal._req_slabs[0]
        plain = kernels.place_launch(
            plain_mesh_program(coal).lower, *operands[:2],
            kernels.device_request(slab.batch(), static["features"].dp_width),
            {f: st[f] for f in kernels.LANE_FIELDS}, *operands[4:],
            features=static["features"],
        ).compile()
        assert collectives(live.as_text()) == collectives(plain.as_text())
        assert sum(collectives(plain.as_text()).values()) > 0

    @pytest.mark.parametrize("devices,mesh_shape", MESHES)
    def test_a_sync_span_counts_the_one_operand_the_scatter_hands_over(
        self, eight_devices, devices, mesh_shape,
    ):
        coal = check_sync_span(devices)
        assert coal.mesh_shape() == mesh_shape
        assert coal.matrix.shard_count == mesh_shape[1]

    @pytest.mark.parametrize("devices,mesh_shape", MESHES)
    def test_an_enqueue_span_counts_the_one_call_and_its_operands(
        self, eight_devices, devices, mesh_shape,
    ):
        coal = check_enqueue_span(devices)
        assert coal.mesh_shape() == mesh_shape


class TestTopkHostBytes:
    def test_host_fetch_is_node_count_independent(self, monkeypatch):
        """The coalescer's ``nomad.topk.host_bytes_total`` counts the one
        packed (B, P, 8) fetch per dispatch — growing the node axis 8x
        must not change a byte of host traffic (the runtime counterpart
        of lint rule J005)."""
        from nomad_tpu.scheduler.coalescer import (
            MAX_DELTA_ROWS,
            DeviceCoalescer,
        )

        monkeypatch.setenv("NOMAD_TPU_FAKE_DEVICE", "1")

        def fetched_bytes(capacity, n_nodes):
            m = NodeMatrix(capacity=capacity)
            for _ in range(n_nodes):
                m.upsert_node(mock.node())
            job = mock.job()
            compiled = RequestEncoder(m).compile(job, job.task_groups[0])
            n = m.capacity
            coal = DeviceCoalescer(
                m, max_lanes=2, linger_s=0.0, pipeline_depth=1
            )
            coal.start()
            try:
                out = coal.place(
                    request=compiled.request,
                    delta_rows=np.full((MAX_DELTA_ROWS,), -1, np.int32),
                    delta_vals=np.zeros((MAX_DELTA_ROWS, 3), np.float32),
                    tg_count=np.zeros((n,), np.int32),
                    spread_counts=np.zeros_like(
                        compiled.request.s_desired
                    ),
                    penalty=np.zeros((n,), bool),
                    class_elig=np.ones((2,), bool),
                    host_mask=np.ones((n,), bool),
                )
                assert out.rows[0] >= 0
            finally:
                coal.stop()
            assert coal.topk_host_bytes_total > 0
            return coal.topk_host_bytes_total

        assert fetched_bytes(32, 8) == fetched_bytes(256, 128)


class TestShardHoming:
    def test_grow_preserves_home_shards_and_balance(self):
        """Row claims balance across home shards, capacity growth keeps
        every row on its home shard (relocating within the shard's new
        block), and translate_rows maps pre-growth row ids forward."""
        m = NodeMatrix(capacity=16)
        m.set_shard_count(4)
        nodes = [mock.node() for _ in range(12)]
        for n in nodes:
            m.upsert_node(n)
        assert m.shard_row_counts() == [3, 3, 3, 3]
        homes = {n.id: m.home_shard(m.row_of[n.id]) for n in nodes}
        v0 = m.version
        old_rows = np.array([m.row_of[n.id] for n in nodes], np.int32)

        for n in [mock.node() for _ in range(8)]:
            m.upsert_node(n)
        assert m.capacity == 32
        for n in nodes:
            assert m.home_shard(m.row_of[n.id]) == homes[n.id], n.id

        tr = m.translate_rows(old_rows, v0)
        want = np.array([m.row_of[n.id] for n in nodes], np.int32)
        np.testing.assert_array_equal(tr, want)
        # Failed placements (-1) pass through untranslated.
        np.testing.assert_array_equal(
            m.translate_rows(np.array([-1, -1], np.int32), v0), [-1, -1]
        )
        # Current-version rows are already in the new coordinate space.
        np.testing.assert_array_equal(
            m.translate_rows(want, m.version), want
        )

        # Removal + reclaim stays shard-balanced.
        for n in nodes[:4]:
            m.remove_node(n.id)
        m.upsert_node(mock.node())
        assert sum(m.shard_row_counts()) == 17

    def test_unsharded_matrix_unchanged(self):
        """shard_count == 1 is the legacy dense policy: contiguous claims,
        no remap log, identity translate."""
        u = NodeMatrix(capacity=16)
        for _ in range(20):
            u.upsert_node(mock.node())
        assert u.capacity == 32 and u.n_rows == 20 and not u._remaps
        np.testing.assert_array_equal(
            u.translate_rows(np.array([5], np.int32), 0), [5]
        )


# ---------------------------------------------------------------------------
# The 100,000-node region's served path at a small size: HTTP -> broker ->
# workers -> coalescer -> node-sharded fused launch -> applier, held to the
# benchmark's plain reference with the limits of the chip run.
# ---------------------------------------------------------------------------

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REGION = {"nodes": 480, "node_capacity": 512, "sim_allocs": 96_000}
SEED = 2 ** 31 + 26


@pytest.fixture(scope="module")
def bench():
    """``benchmark/``'s own modules, imported by path as its tests do."""
    path = os.path.join(ROOT, "benchmark")
    sys.path.insert(0, path)
    try:
        import check
        import client
        import traffic

        yield types.SimpleNamespace(check=check, client=client,
                                    traffic=traffic)
    finally:
        sys.path.remove(path)


def _region_agent(shards):
    """The agent of benchmark/run.py at --rehearse size, its coalescer
    told to span ``shards`` of the CPU's virtual devices."""
    from nomad_tpu import simcluster
    from nomad_tpu.api import Agent, AgentConfig
    from nomad_tpu.server import ServerConfig

    agent = Agent(AgentConfig(
        client_enabled=False,
        server_config=ServerConfig(
            num_workers=4, heartbeat_min_ttl=600, heartbeat_max_ttl=900,
            node_capacity=REGION["node_capacity"], n_device_shards=shards,
        ),
    ))
    agent.start()
    srv = agent.server
    ids = []
    for i in range(REGION["nodes"]):
        node = simcluster.sim_node(i)
        node.id, node.name = f"sim-node-{i:06d}", f"sim-{i:06d}"
        srv.register_node(node)
        ids.append(node.id)
    rows = np.fromiter((srv.matrix.row_of[nid] for nid in ids), np.int64,
                       len(ids))
    totals = srv.matrix.snapshot_host()["totals"][rows].copy()
    used0, prio0 = simcluster.sim_usage(totals, REGION["sim_allocs"],
                                        SEED % 2 ** 32)
    srv.matrix.set_usage(rows, used0.copy(), prio0)
    return agent, used0


class TestShardedPackedScatter:
    """``sync_sharded`` hands the mesh ONE packed host operand a sync (a
    buffer a device, where thirteen operands were thirteen a device), and
    the resident mirror stays the host's bit for bit."""

    @staticmethod
    def _resident(mesh_devices=8, node_shards=4):
        from nomad_tpu.parallel.sharding import make_mesh

        m = NodeMatrix(capacity=64)
        m.set_shard_count(node_shards)
        for _ in range(44):
            m.upsert_node(mock.node())
        mesh = make_mesh(mesh_devices, batch=mesh_devices // node_shards)
        m.sync_sharded(mesh)
        return m, mesh

    @pytest.mark.parametrize("k", [1, 2, 3, 33])
    def test_mirror_equals_the_host_bit_for_bit(self, eight_devices, k):
        m, mesh = self._resident()
        # Claims balance over the home shards, so the first rows of each
        # block are live: walk the blocks round robin.
        blk = m.capacity // m.shard_count
        rows = [(i % 4) * blk + i // 4 for i in range(k)]
        assert all(r in m.node_of for r in rows)
        if k > 1:
            assert len({m.home_shard(r) for r in rows}) == min(k, 4)
        for n in range(1, 3):  # twice: a scatter onto a scattered mirror
            dirty_hard_rows(m, rows)
            dev = m.sync_sharded(mesh)
            assert_bits_equal(dev, host_mirror(m), f"{k} rows, sync {n}")
            assert (m.scatter_syncs, m.scatter_operands_total) == (n, n)
            assert dev.used.sharding.spec[0] == "node"
        assert m.full_uploads == 1
        assert m.rows_scattered_total == 2 * k

    def test_one_operand_a_sync_and_a_failed_scatter_retries(
            self, eight_devices):
        m, mesh = self._resident()
        before, real, calls = m._sharded_device, m._sharded_scatter, []

        def spy(*operands):
            calls.append(operands)
            if len(calls) == 1:
                raise RuntimeError("device lost")
            return real(*operands)

        m._sharded_scatter = spy
        dirty_hard_rows(m, [1, 17, 34])
        with pytest.raises(RuntimeError, match="device lost"):
            m.sync_sharded(mesh)
        assert m._sharded_dirty == {1, 17, 34}
        assert m._sharded_device is before
        assert (m.scatter_syncs, m.scatter_operands_total) == (0, 0)
        assert_bits_equal(m.sync_sharded(mesh), host_mirror(m), "the retry")
        assert (m.scatter_syncs, m.scatter_operands_total) == (1, 1)
        for device, pack in calls:  # the matrix and ONE host buffer
            assert isinstance(pack, np.ndarray) and pack.dtype == np.uint8
            assert pack.shape[0] == 4  # three rows in the bucket of four
        # The one-chip copy keeps a dirty set of its own.
        assert m._dirty >= {1, 17, 34}


class TestShardedRegionServed:
    @pytest.mark.parametrize("shards", (2, 4, 8))
    def test_deck_placed_through_the_sharded_server_is_correct(
            self, eight_devices, bench, shards):
        """A seeded deck of the eight shapes of traffic/backlog-x4.json,
        eight operations outstanding: check.py against reference.py reads
        correct, every launch went through the sharded fused entry, and
        the mesh is the one the layout rule gives this many devices."""
        from nomad_tpu.parallel.sharding import mesh_layout

        with open(os.path.join(ROOT, "benchmark/configs/c2m-100k.json")) as fh:
            cfg = {**json.load(fh), **REGION}
        mix = bench.traffic.load("backlog-x4")
        agent, used0 = _region_agent(shards)
        try:
            cl = bench.client.Client(agent.rpc_addr, mix, SEED, 5.0)
            cl.start()
            deck = bench.traffic.schedule(mix, SEED, 5.0)[:48]
            assert {op["shape"] for op in deck} == set(range(8))
            ops, _ = cl.drive(deck, "", "all", time.time(), None, 8, 300.0,
                              "run")
            cl.stop = True
            records = [op.record() for op in ops]
            assert all(r["status"] == "placed" for r in records), records

            def get(path):
                with urllib.request.urlopen(agent.rpc_addr + path,
                                            timeout=60) as r:
                    return json.loads(r.read())

            correct, numbers, lines = bench.check.decide(
                get, cfg, mix, records, used0, SEED)
            assert correct, lines
            assert numbers["score_gap"] <= 3e-5 and numbers["rank_gap"] <= 1e-5
            coal = agent.server.coalescer
            assert coal.n_device_shards == shards
            assert coal.fused_dispatches > 0
            assert coal.fused_dispatches == coal.dispatches
            assert coal.mesh_shape() == mesh_layout(
                shards, REGION["node_capacity"])
        finally:
            agent.shutdown()

    def test_mesh_gauges_report_the_layout_chosen(self, eight_devices):
        from nomad_tpu import mock
        from nomad_tpu.parallel.sharding import mesh_layout
        from nomad_tpu.server import Server, ServerConfig

        def gauges(shards):
            srv = Server(ServerConfig(
                num_workers=1, heartbeat_min_ttl=60, heartbeat_max_ttl=90,
                node_capacity=64, n_device_shards=shards,
            ))
            srv.start()
            try:
                for _ in range(8):
                    srv.register_node(mock.node())
                ev = srv.submit_job(mock.job())
                done = srv.wait_for_eval(ev.id, timeout=120)
                assert done is not None and done.status == "complete"
                snap = srv.metrics.snapshot()
                return tuple(int(snap[f"nomad.mesh.{k}"]) for k in
                             ("devices", "node_shards", "batch_shards"))
            finally:
                srv.shutdown()

        batch, node = mesh_layout(4, 64)
        assert gauges(4) == (4, node, batch)
        assert gauges(1) == (1, 1, 1)
