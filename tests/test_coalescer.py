"""Dispatch coalescer (VERDICT r3 item 2): concurrent selects batch into
single device dispatches; results match the solo path; the live server
schedules through it."""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

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
