"""Pipelined live dispatch (round 6): the coalescer's producer/consumer
pipeline must change THROUGHPUT only — placements stay identical to the
serial path (any batching, any chaos timing), stale in-flight reads are
counted and caught by the applier's re-verify, the sharded mirror stays
resident (dirty-row scatter, not full re-lay), and depth=4 must beat
depth=1 by >=2x under 20ms synthetic fetch latency (the tier-1 floor
for the whole optimisation)."""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.chaos import FaultSpec, injected
from nomad_tpu.scheduler.coalescer import MAX_DELTA_ROWS, DeviceCoalescer
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.state import NodeMatrix
from nomad_tpu.state.matrix import DeviceArrays
from nomad_tpu.structs.types import Plan


def _matrix(n=8):
    m = NodeMatrix(capacity=16)
    for _ in range(n):
        m.upsert_node(mock.node())
    return m


def _inputs(m, job):
    from nomad_tpu.ops.encode import RequestEncoder

    enc = RequestEncoder(m)
    tg = job.task_groups[0]
    compiled = enc.compile(job, tg)
    n = m.capacity
    return dict(
        request=compiled.request,
        delta_rows=np.full((MAX_DELTA_ROWS,), -1, np.int32),
        delta_vals=np.zeros((MAX_DELTA_ROWS, 3), np.float32),
        tg_count=np.zeros((n,), np.int32),
        spread_counts=np.zeros_like(compiled.request.s_desired),
        penalty=np.zeros((n,), bool),
        class_elig=np.ones((2,), bool),
        host_mask=np.ones((n,), bool),
    )


def _drive(coal, inputs, n_threads):
    """Submit every request through `coal.place` from a thread pool;
    returns outcomes in request order."""
    outcomes = [None] * len(inputs)
    errors = []
    todo = list(range(len(inputs)))
    lock = threading.Lock()

    def worker():
        while True:
            with lock:
                if not todo:
                    return
                i = todo.pop()
            try:
                outcomes[i] = coal.place(**inputs[i])
            except BaseException as e:  # noqa: BLE001
                errors.append(e)
                return

    threads = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors, errors
    assert all(o is not None for o in outcomes)
    return outcomes


class TestPipelineParity:
    def test_pipelined_matches_serial_under_chaos_delays(self, monkeypatch):
        """Same matrix, same requests: depth=8 with chaos-perturbed batch
        boundaries must produce the exact placements the serial depth=1
        loop does — each lane is an independent pure function of
        (matrix arrays, request), so batching/overlap may not leak in."""
        monkeypatch.setenv("NOMAD_TPU_FAKE_DEVICE", "1")
        monkeypatch.setenv("NOMAD_TPU_FAKE_DEVICE_LATENCY_MS", "10")
        # TSan-lite rides along: matrix + coalescers are built inside the
        # sanitized block, so 8 worker threads x pipelined resolver get
        # lockset-checked under the chaos-perturbed batch boundaries.
        from nomad_tpu.lint import tsan

        self._tsan = tsan
        tsan.enable()
        m = _matrix(8)
        jobs = [mock.job() for _ in range(24)]
        for i, j in enumerate(jobs):
            j.task_groups[0].tasks[0].resources.cpu = 100 + 30 * (i % 7)
            j.task_groups[0].tasks[0].resources.memory_mb = 64 + 16 * (i % 5)
        inputs = [_inputs(m, j) for j in jobs]

        schedule = [
            FaultSpec(
                "coalescer.dispatch", "delay", p=0.5, duration=0.004
            )
        ]

        def run(depth, seed):
            coal = DeviceCoalescer(
                m, max_lanes=4, linger_s=0.0, pipeline_depth=depth
            )
            coal.start()
            try:
                with injected(seed=seed, schedule=schedule):
                    return run_outcomes(coal)
            finally:
                coal.stop()

        def run_outcomes(coal):
            return _drive(coal, inputs, n_threads=8)

        try:
            serial = run(depth=1, seed=11)
            piped = run(depth=8, seed=23)
            races = tsan.reports()
        finally:
            tsan.disable()
        assert races == [], "\n".join(
            f"{r['label']} {r['op']} in {r['thread']} held={r['held']}\n{r['stack']}"
            for r in races
        )

        for i, (a, b) in enumerate(zip(serial, piped)):
            np.testing.assert_array_equal(
                a.rows, b.rows, err_msg=f"request {i} rows diverged"
            )
            np.testing.assert_allclose(
                a.scores, b.scores, rtol=1e-6,
                err_msg=f"request {i} scores diverged",
            )
        # The pipelined run actually overlapped (not degenerate serial).
        assert all(o.rows.shape[0] > 0 for o in piped)


class TestLaneSteps:
    @pytest.mark.parametrize("n_live,steps", [(3, 3), (0, 16), (40, 16)])
    def test_n_live_reaches_the_jitted_kernel(self, n_live, steps):
        """`place(n_live=k)` through the real jitted fused entry: the scan
        stops after k steps (rows past them read -1), and the launch adds
        its largest count to `scan_steps_total`.  0 ("not said") and more
        than the scan holds both mean all of it."""
        m = _matrix(8)
        coal = DeviceCoalescer(m, max_lanes=4, linger_s=0.0)
        assert coal.scan_length == 16
        job = mock.job()
        job.task_groups[0].tasks[0].resources.cpu = 100
        coal.start()
        try:
            out = coal.place(**_inputs(m, job), n_live=n_live)
        finally:
            coal.stop()
        assert out.rows.shape == (16,)
        assert (out.rows[:steps] >= 0).all()
        assert (out.rows[steps:] == -1).all()
        assert (out.scores[steps:] == 0.0).all()
        assert out.fit_verified.all()
        assert coal.fused_dispatches == 1
        assert coal.scan_steps_total == steps


class TestStagingReuse:
    def test_operands_untouched_until_their_dispatch_resolves(
        self, monkeypatch
    ):
        """A launch hands the staging buffers to jax, which may read them
        (async host→device transfer; zero-copy aliasing on CPU) until the
        result is fetched.  With several dispatches in flight, no staged
        operand may be rewritten before the dispatch that read it has
        resolved — a stand-in kernel compares each launch's operands at
        fetch time with the copy it took at launch."""
        from nomad_tpu.ops import kernels

        m = _matrix(8)
        coal = DeviceCoalescer(
            m, max_lanes=2, linger_s=0.0, pipeline_depth=4
        )
        launches = []
        overlap = []

        class InFlight:
            def __init__(self, operands):
                self.live = operands
                self.at_launch = [np.asarray(a).tobytes() for a in operands]
                self.intact = None

            def __array__(self, dtype=None, copy=None):
                time.sleep(0.03)  # hold the fetch: later launches overlap
                overlap.append(coal.inflight_depth())
                self.intact = all(
                    np.asarray(a).tobytes() == b
                    for a, b in zip(self.live, self.at_launch)
                )
                out = np.zeros(
                    (coal.max_lanes, coal.scan_length,
                     kernels.FUSED_PACKED_WIDTH), np.float32,
                )
                out[:, :, kernels.PACKED_ROW] = -1.0
                return out

        def stand_in(arrays, used, request_pack, lane_pack, tg, pen, hm,
                     carry, **_static):
            launches.append(InFlight([request_pack, lane_pack, tg, pen, hm]))
            return launches[-1], carry  # packed, the carry

        monkeypatch.setattr(kernels, "fused_place_batch_live", stand_in)
        inputs = []
        for i in range(24):
            inp = _inputs(m, mock.job())
            # Distinct staged content per request, so a rewrite shows.
            inp["tg_count"] = np.full((m.capacity,), i + 1, np.int32)
            inputs.append(inp)
        coal.start()
        try:
            _drive(coal, inputs, n_threads=8)
        finally:
            coal.stop()
        assert len(launches) >= 12
        assert max(overlap) > 1, "dispatches never overlapped"
        assert all(lz.intact for lz in launches)


class TestStaleDispatch:
    def test_stale_inflight_dispatch_is_counted(self, monkeypatch):
        """A matrix mutation while a dispatch is in flight bumps
        `stale_dispatches` at resolve time — the pipelining tax gauge."""
        monkeypatch.setenv("NOMAD_TPU_FAKE_DEVICE", "1")
        monkeypatch.setenv("NOMAD_TPU_FAKE_DEVICE_LATENCY_MS", "250")
        m = _matrix(8)
        coal = DeviceCoalescer(m, max_lanes=4, linger_s=0.0,
                               pipeline_depth=4)
        coal.start()
        got = {}
        try:
            def submit():
                got["out"] = coal.place(**_inputs(m, mock.job()))

            t = threading.Thread(target=submit)
            t.start()
            deadline = time.time() + 10.0
            while coal.inflight_depth() == 0 and time.time() < deadline:
                time.sleep(0.002)
            assert coal.inflight_depth() >= 1, "dispatch never launched"
            # Mutate the matrix mid-flight (well inside the 250ms window).
            m.upsert_node(mock.node())
            t.join(timeout=30)
        finally:
            coal.stop()
        assert "out" in got
        assert (got["out"].rows[:1] >= 0).all()
        assert coal.stale_dispatches == 1

    def test_applier_rejects_stale_overcommit(self, monkeypatch):
        """The correctness backstop: a plan scored against a snapshot the
        cluster has since outgrown is rejected by the serialized applier's
        re-verify — nothing commits, the scheduler gets a refresh index."""
        monkeypatch.setenv("NOMAD_TPU_FAKE_DEVICE", "1")
        srv = Server(ServerConfig(
            num_workers=2,
            heartbeat_min_ttl=3600.0,
            heartbeat_max_ttl=7200.0,
        ))
        srv.start()
        try:
            node = mock.node()  # 4000 cpu, 100 reserved
            srv.register_node(node)
            big = mock.job()
            big.task_groups[0].count = 1
            big.task_groups[0].tasks[0].resources.cpu = 3500
            ev = srv.submit_job(big)
            assert srv.wait_for_eval(ev.id, timeout=60.0)
            assert srv.store.allocs_by_job(big.namespace, big.id)

            # A plan built against the EMPTY node (stale snapshot): another
            # 3500-cpu alloc no longer fits next to the committed one.
            j2 = mock.job()
            j2.task_groups[0].count = 1
            j2.task_groups[0].tasks[0].resources.cpu = 3500
            stale = mock.alloc(j2, node)
            plan = Plan(job=j2, node_allocation={node.id: [stale]})

            before_partial = srv.plan_applier.plans_partial
            n_allocs = len(srv.store.allocs)
            result = srv.plan_applier.apply(plan)

            assert not result.node_allocation, "overcommit was committed"
            assert result.refresh_index > 0
            assert srv.plan_applier.plans_partial == before_partial + 1
            assert len(srv.store.allocs) == n_allocs
        finally:
            srv.shutdown()


class TestShardedResidency:
    def test_incremental_sync_scatters_only_dirty_rows(self, eight_devices):
        """After the first full lay-out the sharded mirror is resident:
        dirty mutations scatter O(rows) bytes, never the whole matrix."""
        from nomad_tpu.parallel.sharding import make_mesh

        m = NodeMatrix(capacity=16)
        nodes = [mock.node() for _ in range(12)]
        for n in nodes:
            m.upsert_node(n)
        mesh = make_mesh(8, batch=2)

        def assert_parity(dev):
            for f in DeviceArrays._fields:
                np.testing.assert_array_equal(
                    np.asarray(getattr(dev, f)), m._alloc[f],
                    err_msg=f"sharded field {f} diverged from host",
                )

        dev = m.sync_sharded(mesh)
        assert m.full_uploads == 1
        assert m.scatter_syncs == 0
        bytes_full = m.upload_bytes_total
        assert bytes_full > 0
        assert_parity(dev)

        # Clean sync: no transfer at all.
        dev2 = m.sync_sharded(mesh)
        assert dev2 is dev
        assert m.upload_bytes_total == bytes_full

        # Dirty two rows; the next sync must scatter, not re-lay.
        m.set_eligibility(nodes[3].id, False)
        m.add_alloc(mock.alloc(mock.job(), nodes[5]))
        dev3 = m.sync_sharded(mesh)
        assert m.full_uploads == 1, "dirty sync re-laid the full matrix"
        assert m.scatter_syncs == 1
        assert 1 <= m.rows_scattered_total <= 4
        delta = m.upload_bytes_total - bytes_full
        assert 0 < delta < bytes_full // 2, (
            f"scatter moved {delta}B vs {bytes_full}B full upload — "
            "not incremental"
        )
        assert_parity(dev3)


@pytest.mark.parametrize("latency_ms", [20])
def test_pipeline_depth4_beats_serial_floor(monkeypatch, latency_ms):
    """Tier-1 floor for the whole optimisation: with a 20ms synthetic
    fetch latency, depth=4 must deliver >=2x the placement rate of the
    serial depth=1 loop (theory: 4x — each overlapped dispatch hides a
    full latency window; 2x leaves headroom for loaded CI boxes)."""
    monkeypatch.setenv("NOMAD_TPU_FAKE_DEVICE", "1")
    monkeypatch.setenv("NOMAD_TPU_FAKE_DEVICE_LATENCY_MS", str(latency_ms))
    m = _matrix(8)
    jobs = [mock.job() for _ in range(48)]
    for i, j in enumerate(jobs):
        j.task_groups[0].tasks[0].resources.cpu = 100 + 20 * (i % 8)
    inputs = [_inputs(m, j) for j in jobs]

    def rate(depth):
        coal = DeviceCoalescer(
            m, max_lanes=2, linger_s=0.0, pipeline_depth=depth
        )
        coal.start()
        try:
            coal.place(**inputs[0])  # warm outside the timed region
            t0 = time.time()
            _drive(coal, inputs, n_threads=16)
            wall = time.time() - t0
        finally:
            coal.stop()
        return len(inputs) / wall

    r1 = rate(1)
    r4 = rate(4)
    assert r4 >= 2.0 * r1, (
        f"pipeline depth=4 managed {r4:.1f}/s vs serial {r1:.1f}/s at "
        f"{latency_ms}ms latency — expected >=2x overlap win"
    )
