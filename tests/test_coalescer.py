"""Dispatch coalescer (VERDICT r3 item 2): concurrent selects batch into
single device dispatches; results match the solo path; the live server
schedules through it."""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

import helpers
from helpers import _client, _small, _wait
from nomad_tpu import mock
from nomad_tpu.scheduler.coalescer import DeviceCoalescer, MAX_DELTA_ROWS
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.state import NodeMatrix
from nomad_tpu.structs.types import AllocClientStatus


def _matrix(n=8):
    m = NodeMatrix(capacity=16)
    for i in range(n):
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


class TestDeviceCoalescer:
    def test_concurrent_places_coalesce_and_match(self):
        m = _matrix()
        coal = DeviceCoalescer(m, max_lanes=8, linger_s=0.02)
        coal.start()
        try:
            jobs = [mock.job() for _ in range(6)]
            for i, j in enumerate(jobs):
                j.task_groups[0].tasks[0].resources.cpu = 100 + 50 * i
            results = {}
            errors = []

            def run(i, j):
                try:
                    results[i] = coal.place(**_inputs(m, j))
                except BaseException as e:  # noqa: BLE001
                    errors.append(e)

            threads = [
                threading.Thread(target=run, args=(i, j))
                for i, j in enumerate(jobs)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert not errors, errors
            assert len(results) == 6
            # Coalescing happened (strictly fewer dispatches than requests;
            # an exact count would be timing-dependent on loaded machines).
            assert coal.dispatches < 6, coal.dispatches
            assert coal.coalesced_requests == 6
            for i, out in results.items():
                assert out.rows.shape[0] == coal.scan_length
                assert (out.rows[:1] >= 0).all(), f"request {i} failed"
        finally:
            coal.stop()

    def test_inert_lane_padding_places_nothing(self):
        m = _matrix()
        coal = DeviceCoalescer(m, max_lanes=4, linger_s=0.0)
        coal.start()
        try:
            out = coal.place(**_inputs(m, mock.job()))
            assert (out.rows[:1] >= 0).all()
        finally:
            coal.stop()

    def test_capacity_growth_mid_queue(self):
        """A request built before matrix growth still dispatches (padded,
        new rows masked off)."""
        m = _matrix(4)
        coal = DeviceCoalescer(m, max_lanes=4, linger_s=0.05)
        coal.start()
        try:
            inp = _inputs(m, mock.job())
            got = {}

            def submit():
                got["out"] = coal.place(**inp)

            t = threading.Thread(target=submit)
            t.start()
            # Grow the matrix while the request lingers in the queue.
            for _ in range(20):
                m.upsert_node(mock.node())
            t.join(timeout=120)
            assert "out" in got
            assert int(got["out"].rows[0]) < 4 or int(got["out"].rows[0]) == -1
        finally:
            coal.stop()


def test_server_schedules_through_coalescer(tmp_path):
    srv = Server(ServerConfig(
        num_workers=4, heartbeat_min_ttl=60, heartbeat_max_ttl=90
    ))
    srv.start()
    c = _client(srv, tmp_path, "c1")
    try:
        jobs = [_small(mock.job()) for _ in range(8)]
        for j in jobs:
            # 8 jobs x 2 allocs x 20cpu = 320 — fits the single mock node.
            j.task_groups[0].count = 2
        evals = [srv.submit_job(j) for j in jobs]
        for ev in evals:
            assert srv.wait_for_eval(ev.id, timeout=120) is not None
        assert srv.coalescer.dispatches > 0
        assert srv.coalescer.coalesced_requests >= 8
        for j in jobs:
            assert _wait(lambda j=j: [
                a for a in srv.store.allocs_by_job(j.namespace, j.id)
                if a.client_status == AllocClientStatus.RUNNING.value
            ], timeout=60)
    finally:
        c.shutdown()
        srv.shutdown()


@pytest.mark.parametrize("route", ["one_device", "mesh", "breaker_open"])
def test_every_route_returns_the_verify_column(route, eight_devices):
    """_dispatch keeps three routes (one device, a mesh, the numpy twin
    while the breaker is open): on each the outcome carries the cross-lane
    verify column as an array, and the launch counts as a batched one."""
    m = NodeMatrix(capacity=16)
    for _ in range(8):
        m.upsert_node(mock.node())
    coal = DeviceCoalescer(
        m, max_lanes=4, linger_s=0.0,
        n_device_shards=8 if route == "mesh" else 1,
    )
    coal.start()
    try:
        if route == "breaker_open":
            coal.breaker.record_wedge(1.0)
            assert coal.breaker.brief()["breaker"] == "open"
        out = coal.place(**_inputs(m, mock.job()), n_live=2)
    finally:
        coal.stop()
    assert isinstance(out.fit_verified, np.ndarray)
    assert out.fit_verified.shape == out.rows.shape == (coal.scan_length,)
    assert out.fit_verified.dtype == bool and out.fit_verified.all()
    assert (out.rows[:2] >= 0).all() and (out.rows[2:] == -1).all()
    assert out.matrix_version == m.version
    assert coal.fused_dispatches == coal.dispatches == 1
    assert coal.fused_lanes == 1 and coal.scan_steps_total == 2
    assert coal.breaker.brief()["degraded_dispatches"] == (
        1 if route == "breaker_open" else 0
    )
    assert (coal.mesh_shape() != (1, 1)) == (route == "mesh")


# ---------------------------------------------------------------------------
# A launch is one jitted call: the small lane operands cross as two packed
# buffers that the placement program unpacks itself (kernels.unpack_launch)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rules", [False, True], ids=["dp0", "dp2"])
@pytest.mark.parametrize("k", helpers.LAUNCH_FILLS)
def test_a_launch_hands_over_two_packs_and_the_node_axis_operands(
    monkeypatch, k, rules,
):
    coal, _call = helpers.check_packed_launch(monkeypatch, k, rules=rules)
    assert coal.mesh_shape() == (1, 1)


def test_a_sync_span_counts_the_one_operand_the_scatter_hands_over():
    coal = helpers.check_sync_span()
    assert coal.mesh_shape() == (1, 1)


def test_an_enqueue_span_counts_the_one_call_and_its_operands():
    coal = helpers.check_enqueue_span()
    assert coal.mesh_shape() == (1, 1)


def test_a_lane_live_in_one_launch_and_dead_in_the_next_leaves_no_trace(
    monkeypatch,
):
    """One slot (depth 1): 16 lanes, then 3.  The second launch's lanes
    3.. are padded by memset on the host and read as dead lanes."""
    from nomad_tpu.ops import kernels

    coal = helpers.wide_coalescer()
    helpers.launch_lanes(coal, 16)
    st = coal._stage[0]
    assert st["host_mask"][3:16].any()
    calls = helpers.spy_on_launch(monkeypatch, coal)
    got = helpers.launch_lanes(coal, 3, seed=2)
    ((operands, _static),) = calls
    hm, ls = operands[6], st["lane_steps"]
    assert hm is st["host_mask"] and np.shares_memory(ls, operands[3])
    assert hm[:3].any() and not hm[3:].any()
    assert ls[:3].all() and not ls[3:].any()
    assert (got[3:, :, kernels.PACKED_ROW] == -1).all()
    assert (got[3:, :, kernels.FUSED_PACKED_VERIFIED] == -1.0).all()


def test_no_fill_compiles_and_a_new_layout_is_named_a_variant(monkeypatch):
    """After the first launch at a node width, fills 1..max_lanes compile
    nothing; after a matrix growth the same holds.  A launch whose packs
    have another layout than the last one's (here: the class-eligibility
    width doubles) compiles a variant of the one program a launch calls
    and says so (``coalescer.trace_variant``); the next does not."""
    from nomad_tpu.ops import kernels

    coal = helpers.wide_coalescer(nodes=10, capacity=16, lanes=8)
    states = []
    state = coal._state
    monkeypatch.setattr(
        coal, "_state", lambda name, **a: states.append(name) or state(name, **a))
    program = kernels.fused_place_batch_live

    def launch_states(k, **kw):
        del states[:]
        helpers.launch_lanes(coal, k, **kw)
        return [s for s in states if s != "coalescer.stage"]

    for n in (16, 32):
        assert int(coal.matrix.capacity) == n
        assert launch_states(1)[-1] == (
            "coalescer.trace_variant" if n == 16 else "coalescer.enqueue")
        # (the second launch is handed a carry that lives on the device,
        # the first one of numpy: an entry of the jit cache each, one
        # executable)
        assert launch_states(1)[-1] == "coalescer.enqueue"
        before = helpers.backend_compiles(), program._cache_size()
        for k in range(1, 9):
            assert launch_states(k)[-1] == "coalescer.enqueue"
        assert (helpers.backend_compiles(), program._cache_size()) == before
        for _ in range(10):  # past the capacity: the matrix grows
            coal.matrix.upsert_node(mock.node())
    size = program._cache_size()
    assert launch_states(2, classes=4)[-1] == "coalescer.trace_variant"
    assert program._cache_size() == size + 1
    assert launch_states(2, classes=4)[-1] == "coalescer.enqueue"
    assert coal.device_calls == coal.fused_dispatches


@pytest.mark.parametrize("devices", [1, 4])
def test_packed_lane_operands_unpack_bit_for_bit(eight_devices, devices):
    """Fields of every dtype and rank the lane operands have (scalars a
    lane, bools of odd width, matrices) are views of one buffer on the host
    and come back bit for bit on the device, NaN payloads and -0.0 too; on
    a mesh split over ``batch`` alone."""
    from nomad_tpu.ops import kernels
    from nomad_tpu.ops.encode import packed_rows
    from nomad_tpu.parallel import make_mesh

    specs = [((3,), bool), ((2, 5), np.float32), ((7,), np.int32),
             ((), np.int32), ((), bool), ((4, 3), np.float32), ((1,), bool)]
    lanes = 8
    buf, views, layout = packed_rows(lanes, specs)
    assert buf.dtype == np.uint8 and buf.shape[0] == lanes
    assert all(field[0] % 4 == 0 for field in layout)
    rng = np.random.default_rng(5)
    for v, (shape, dtype) in zip(views, specs):
        assert np.shares_memory(v, buf)
        assert v.shape == (lanes,) + shape and v.dtype == dtype
        if dtype == bool:
            v[...] = rng.random(v.shape) < 0.5
        else:  # any bit pattern: NaNs, denormals, -0.0, negative ints
            v[...] = rng.integers(
                -2**31, 2**31, v.shape, np.int64).astype(np.int32).view(dtype)
    views[1][0, 0, 0] = -0.0
    import jax

    shardings = {}
    if devices > 1:
        from jax.sharding import NamedSharding, PartitionSpec as P

        lanes_over_batch = NamedSharding(
            make_mesh(devices, batch=2), P("batch"))
        shardings = dict(
            in_shardings=lanes_over_batch, out_shardings=lanes_over_batch)
    unpack = jax.jit(
        lambda *packs: [kernels.unpack_rows(p, layout) for p in packs],
        **shardings)
    fields, again = unpack(buf, buf.copy())
    for got, twin, want in zip(fields, again, views):
        assert got.shape == want.shape and got.dtype == want.dtype
        assert np.asarray(got).tobytes() == np.asarray(twin).tobytes() \
            == np.ascontiguousarray(want).tobytes()
        if devices > 1:
            assert got.sharding.spec[0] == "batch"
            assert all(s is None for s in got.sharding.spec[1:])
