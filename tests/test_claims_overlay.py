"""The in-flight claims overlay (ISSUE 38): launches in flight stop racing
each other.

(a) the placement program, three writings (one device, a mesh, the numpy
    twin): an empty overlay changes no bit, a seeded one moves the same
    lanes to the same nodes in all three, scores never read it;
(b) the ledger alone (``scheduler/claims.py``): who enters, who releases,
    and that nothing is left behind;
(c) the live server on the fake device: a burst of identical batch jobs
    ends with fewer refused plans than with the overlay emptied.
"""

from __future__ import annotations

import hashlib
import time

import numpy as np
import pytest

from helpers import fill_frontier, lane_operands, solo_reference
from test_megakernel import build_cluster_1k, host_view, make_job

from nomad_tpu import mock
from nomad_tpu.chaos import FaultSpec, injected
from nomad_tpu.ops import fake_device, kernels
from nomad_tpu.ops.encode import RequestEncoder
from nomad_tpu.ops.kernels import FUSED_PACKED_VERIFIED, fused_place_batch
from nomad_tpu.scheduler import claims as claims_mod
from nomad_tpu.scheduler.claims import OVERLAY_ROWS, ClaimsLedger
from nomad_tpu.server import Server, ServerConfig

ASK_CPU, ASK_MEM = 300, 200
LANES, SCAN = 8, 8
INT_COLS = (0, 3, 4, 5, 6, FUSED_PACKED_VERIFIED)
MESHES = ((2, 1), (4, 2))  # (devices, batch shards)


@pytest.fixture(scope="module")
def herd():
    """1,000 nodes with a frontier of 40 that hold exactly one more ask, and
    eight lanes that all want it (plain binpack: the herd)."""
    m, nodes = build_cluster_1k()
    rows = fill_frontier(
        m, nodes, np.random.default_rng(5).choice(1000, 40, False),
        ASK_CPU, ASK_MEM,
    )
    j = make_job(cpu=ASK_CPU, mem=ASK_MEM, count=8)
    req = RequestEncoder(m).compile(j, j.task_groups[0]).request
    ops = lane_operands(
        m, [req] * LANES,
        deltas={2: [(rows[3], (ASK_CPU, ASK_MEM, 0.0))]}, max_deltas=4,
    )
    ls = np.array([8, 1, 3, 0, 2, 8, 1, 4], np.int32)
    return m, rows, req, ops, ls


def _overlay(pairs, lanes=LANES, width=8):
    """[(row, (cpu, mem, disk)), ...] as the (lanes, width) operand."""
    rows = np.full((lanes * width,), -1, np.int32)
    vals = np.zeros((lanes * width, 3), np.float32)
    for i, (row, v) in enumerate(pairs):
        rows[i], vals[i] = row, v
    return rows.reshape(lanes, width), vals.reshape(lanes, width, 3)


def _one_device(m, ops, ls, overlay):
    arrays = m.sync()
    return np.asarray(fused_place_batch(
        arrays, arrays.used, *ops, ls, n_placements=SCAN, overlay=overlay,
    ))


def _mesh(m, ops, ls, overlay, devices, batch):
    from nomad_tpu.parallel import (
        make_mesh, shard_matrix_arrays, sharded_fused_place_batch,
    )

    mesh = make_mesh(devices, batch=batch)
    sharded = shard_matrix_arrays(mesh, m.sync())
    return np.asarray(sharded_fused_place_batch(mesh, SCAN)(
        sharded, sharded.used, *ops, ls, overlay=overlay,
    ))


def _twin(m, req, ops, ls, overlay):
    drows, dvals, tg, sc, pen, _reqs, ce, hm = ops
    host = host_view(m.sync())
    return fake_device.fused_place_batch(
        host, host.used, *[list(a) for a in (drows, dvals, tg, sc, pen)],
        [req] * len(ls), list(ce), list(hm), ls > 0, n_placements=SCAN,
        live_counts=list(ls), overlay=overlay,
    )


def _digest(out) -> str:
    """The decisions of a launch (rows, flags, counts, verdicts: every
    column that is an integer) as one hash."""
    return hashlib.sha256(
        np.ascontiguousarray(out[:, :, INT_COLS].astype(np.int64)).tobytes()
    ).hexdigest()[:16]


# What the PARENT's program (commit cb1914b, no overlay operand anywhere)
# decides on this fixture, from tools run against its tree: every writing
# of this PR's program with an empty overlay must decide the same.
PARENT_DECISIONS = "35c72631435ce8d8"


class TestEmptyOverlayIsTheParent:
    """With nothing in the overlay the program is what it was."""

    def test_one_device(self, herd):
        m, _rows, _req, ops, ls = herd
        none = _one_device(m, ops, ls, None)
        empty = _one_device(m, ops, ls, _overlay([]))
        np.testing.assert_array_equal(none, empty)  # floats too: bitwise
        assert _digest(none) == PARENT_DECISIONS

    @pytest.mark.parametrize("devices,batch", MESHES)
    def test_mesh(self, herd, eight_devices, devices, batch):
        m, _rows, _req, ops, ls = herd
        none = _mesh(m, ops, ls, None, devices, batch)
        empty = _mesh(m, ops, ls, _overlay([]), devices, batch)
        np.testing.assert_array_equal(none, empty)
        assert _digest(none) == PARENT_DECISIONS

    def test_numpy_twin(self, herd):
        m, _rows, req, ops, ls = herd
        none = _twin(m, req, ops, ls, None)
        empty = _twin(m, req, ops, ls, _overlay([]))
        np.testing.assert_array_equal(none, empty)
        assert _digest(none) == PARENT_DECISIONS


class TestSeededOverlay:
    """Plans in flight hold half the frontier and a few other nodes."""

    def _seeded(self, herd):
        m, rows, _req, _ops, _ls = herd
        rng = np.random.default_rng(11)
        pairs = [(r, (ASK_CPU, ASK_MEM, 0.0)) for r in rows[::2]]
        pairs += [
            (int(r), (50.0, 25.0, 0.0))  # a claim that leaves room
            for r in rng.choice(m.n_rows, 12, replace=False)
        ]
        pairs += [(rows[0], (ASK_CPU, ASK_MEM, 0.0))]  # a row named twice
        return pairs

    def test_three_writings_agree(self, herd, eight_devices):
        m, rows, req, ops, ls = herd
        pairs = self._seeded(herd)
        overlay = _overlay(pairs)
        one = _one_device(m, ops, ls, overlay)
        twin = _twin(m, req, ops, ls, overlay)
        np.testing.assert_array_equal(
            one[:, :, INT_COLS], twin[:, :, INT_COLS]
        )
        np.testing.assert_allclose(
            one[:, :, 1:3], twin[:, :, 1:3], rtol=1e-5, atol=1e-5
        )
        for devices, batch in MESHES:
            mesh = _mesh(m, ops, ls, overlay, devices, batch)
            np.testing.assert_array_equal(
                one[:, :, INT_COLS], mesh[:, :, INT_COLS],
                err_msg=f"mesh ({devices}, {batch})",
            )
            np.testing.assert_allclose(
                one[:, :, 1:3], mesh[:, :, 1:3], rtol=1e-5, atol=1e-6
            )
        # Teeth: the overlay moved picks, no lane took a node a plan in
        # flight had filled, and every placement still verifies.
        base = _one_device(m, ops, ls, None)
        placed = one[:, :, 0] >= 0
        assert (one[:, :, 0] != base[:, :, 0]).any()
        held = {r for r, v in pairs if v[0] == ASK_CPU}
        assert held & set(base[:, :, 0][placed].astype(int))
        assert not held & set(one[:, :, 0][placed].astype(int))
        assert np.isin(one[:, :, FUSED_PACKED_VERIFIED][placed], (1.0, 2.0)).all()
        np.testing.assert_array_equal(placed, base[:, :, 0] >= 0)

    def test_scores_are_the_lanes_own(self, herd):
        """The overlay enters no score: whatever node a lane takes, the
        score recorded is the one its own scan gives that node in the state
        the lane had reached (here: the first step, one live lane at a
        time, against the solo scan with the overlaid nodes masked off)."""
        m, rows, _req, ops, _ls = herd
        pairs = [(r, (ASK_CPU, ASK_MEM, 0.0)) for r in rows[::2]]
        overlay = _overlay(pairs)
        arrays = m.sync()
        for lane in (0, 2, 5):
            ls = np.zeros((LANES,), np.int32)
            ls[lane] = 1
            got = _one_device(m, ops, ls, overlay)[lane, 0]
            hm = ops[7].copy()
            hm[lane, [r for r, _ in pairs]] = False
            masked = ops[:7] + (hm,)
            want = solo_reference(arrays, masked, 1, lanes=[lane])[0][0]
            # Row, score, binpack, preempt flag: the solo scan's on the
            # best node the claims left (the node counts differ: the mask
            # stands in for the claims here, and it filters).
            np.testing.assert_array_equal(got[:4], want[:4])


def test_a_row_past_the_snapshot_is_dropped_as_the_kernel_drops_it(herd):
    """An entry registered after a growth the launch has not synced names a
    row the snapshot lacks: the kernel's scatter drops it, and so does the
    twin's."""
    m, _rows, req, ops, ls = herd
    overlay = _overlay([(m.capacity + 7, (ASK_CPU, ASK_MEM, 0.0))])
    np.testing.assert_array_equal(
        _one_device(m, ops, ls, overlay), _one_device(m, ops, ls, None)
    )
    np.testing.assert_array_equal(
        _twin(m, req, ops, ls, overlay), _twin(m, req, ops, ls, None)
    )


class TestOneLaneUnderTheOverlay:
    def _alone(self, herd, overlay, usable=None):
        m, rows, req, ops, _ls = herd
        if usable is not None:
            hm = np.zeros_like(ops[7])
            hm[:, usable] = True
            ops = ops[:7] + (hm,)
        ls = np.zeros((LANES,), np.int32)
        ls[0] = 2
        return m, req, ops, ls

    def test_filled_arg_max_gives_way_to_the_next_best(self, herd,
                                                       eight_devices):
        m, req, ops, ls = self._alone(herd, None)
        solo = _one_device(m, ops, ls, None)[0]
        first, second = int(solo[0, 0]), int(solo[1, 0])
        assert first != second and solo[0, FUSED_PACKED_VERIFIED] == 1.0
        overlay = _overlay([(first, (ASK_CPU, ASK_MEM, 0.0))])
        for got in (
            _one_device(m, ops, ls, overlay),
            _twin(m, req, ops, ls, overlay),
            _mesh(m, ops, ls, overlay, 4, 2),
        ):
            lane = got[0]
            # The node a plan in flight filled is passed over; the lane's
            # next best node is the one its own second step named.
            assert int(lane[0, 0]) == second
            assert lane[0, FUSED_PACKED_VERIFIED] == 2.0
            assert int(lane[1, 0]) not in (first, -1)
            assert lane[1, FUSED_PACKED_VERIFIED] in (1.0, 2.0)

    def test_no_room_anywhere_keeps_the_own_arg_max(self, herd,
                                                    eight_devices):
        m, rows, _req, _ops, _ls = herd
        usable = rows[:4]
        m, req, ops, ls = self._alone(herd, None, usable=usable)
        solo = _one_device(m, ops, ls, None)[0]
        overlay = _overlay([(r, (ASK_CPU, ASK_MEM, 0.0)) for r in usable])
        for got in (
            _one_device(m, ops, ls, overlay),
            _twin(m, req, ops, ls, overlay),
            _mesh(m, ops, ls, overlay, 2, 1),
        ):
            lane = got[0]
            # Never row -1 where the lane's own scan places (PR 28): the
            # lane keeps its picks, the verdict says the applier decides.
            np.testing.assert_array_equal(lane[:2, 0], solo[:2, 0])
            assert (lane[:2, 0] >= 0).all()
            assert (lane[:2, FUSED_PACKED_VERIFIED] == 0.0).all()
            np.testing.assert_allclose(lane[:2, 1:3], solo[:2, 1:3],
                                       rtol=1e-6)


# ---------------------------------------------------------------------------
# (b) the ledger alone
# ---------------------------------------------------------------------------

V = (100.0, 50.0, 0.0)


def _rows_of(ledger, version, **kw):
    return sorted(ledger.overlay(version, **kw)[0].tolist())


class TestLedger:
    def test_refused_is_gone_at_once(self):
        led = ClaimsLedger()
        led.open("e1")
        led.register("e1", [3, 4], [V, V], layout=0)
        assert _rows_of(led, 0) == [3, 4]
        led.refuse("e1")
        assert _rows_of(led, 0) == [] and led.held_rows() == 0
        assert led.counts["released_refused"] == 2

    def test_committed_stays_until_a_launch_has_synced_the_commit(self):
        led = ClaimsLedger()
        led.open("e1")
        led.register("e1", [3, 4], [V, V], layout=0)
        led.commit("e1", version=17)
        # Synced before the commit: the matrix does not hold it, the
        # overlay does.  At or past it: the matrix holds it, the overlay
        # lets go (never both, never neither).
        assert _rows_of(led, 16) == [3, 4]
        assert _rows_of(led, 16) == [3, 4]
        assert _rows_of(led, 17) == []
        assert led.held_rows() == 0
        assert led.counts["released_committed"] == 2

    def test_a_partly_committed_plan_keeps_only_what_committed(self):
        led = ClaimsLedger()
        led.open("e1")
        led.register("e1", [3, 4, 5], [V, V, V], layout=0)
        led.commit("e1", version=9, refused_rows=[4, -1])
        assert _rows_of(led, 8) == [3, 5]
        assert led.counts["released_refused"] == 1
        assert _rows_of(led, 9) == []

    def test_the_same_eval_entering_again_replaces_its_entry(self):
        led = ClaimsLedger()
        led.open("e1")
        led.open("e2")
        led.register("e1", [3], [V], layout=0)
        led.register("e2", [8], [V], layout=0)
        # A launch leaves out its own lanes' evals: the lane carries that
        # usage itself, as its deltas.
        assert _rows_of(led, 0, lane_evals=["e1"]) == [8]
        led.register("e1", [3, 6], [V, V], layout=0)
        assert _rows_of(led, 0) == [3, 6, 8]
        assert led.counts["dropped_reentry"] == 1
        assert led.counts["registered"] == 4

    def test_only_an_eval_a_worker_holds_open_can_claim(self):
        led = ClaimsLedger()
        led.register("dry-run", [3], [V], layout=0)  # nobody opened it
        assert led.held_rows() == 0
        led.open("e1")
        led.register("e1", [3], [V], layout=0)
        led.close("e1")  # the worker's finally: nack, failure, raise
        assert led.held_rows() == 0
        led.register("e1", [3], [V], layout=0)  # a result that came late
        assert led.held_rows() == 0

    def test_close_leaves_a_committed_entry_to_the_launches(self):
        led = ClaimsLedger()
        led.open("e1")
        led.register("e1", [3], [V], layout=0)
        led.commit("e1", version=5)
        led.close("e1")
        assert _rows_of(led, 4) == [3]
        assert _rows_of(led, 5) == []

    def test_more_than_k_rows_truncates_and_counts(self):
        led = ClaimsLedger()
        for i in range(OVERLAY_ROWS // 8 + 3):
            led.open(f"e{i}")
            led.register(
                f"e{i}", np.arange(8) + 8 * i, np.tile(V, (8, 1)), layout=0
            )
        rows, vals, _live = led.overlay(0)
        assert len(rows) == OVERLAY_ROWS and vals.shape == (OVERLAY_ROWS, 3)
        assert led.counts["truncated"] == 24
        # The newest go.
        assert rows.min() == 24 and rows.max() == 8 * (OVERLAY_ROWS // 8 + 3) - 1

    def test_rows_of_another_layout_go(self):
        led = ClaimsLedger()
        led.open("e1")
        led.open("e2")
        led.register("e1", [3, 900], [V, V], layout=4)
        led.register("e2", [5], [V], layout=9)
        # The matrix moved its rows at version 7: e1's rows name another
        # layout.
        assert _rows_of(led, 0, stale_before=7) == [5]
        assert led.held_rows() == 1

    def test_the_books_balance(self):
        led = ClaimsLedger()
        rng = np.random.default_rng(0)
        for i in range(200):
            e = f"e{i % 23}"
            led.open(e)
            led.register(e, rng.integers(0, 64, 3), np.tile(V, (3, 1)), 0)
            act = rng.integers(0, 4)
            if act == 0:
                led.refuse(e)
            elif act == 1:
                led.commit(e, version=i, refused_rows=rng.integers(0, 64, 2))
            elif act == 2:
                led.close(e)
            led.overlay(i - 5)
        c = led.counts
        assert c["registered"] == (
            c["released_committed"] + c["released_refused"]
            + c["dropped_reentry"] + led.held_rows()
        )


# ---------------------------------------------------------------------------
# through the live server (fake device)
# ---------------------------------------------------------------------------


def _server(monkeypatch, latency_ms=2, **cfg):
    monkeypatch.setenv("NOMAD_TPU_FAKE_DEVICE", "1")
    monkeypatch.setenv("NOMAD_TPU_FAKE_DEVICE_LATENCY_MS", str(latency_ms))
    srv = Server(ServerConfig(
        node_capacity=128, heartbeat_min_ttl=3600.0,
        heartbeat_max_ttl=7200.0, **cfg,
    ))
    srv.start()
    for _ in range(64):
        srv.register_node(mock.node())
    return srv


def _batch_job():
    job = mock.job()
    job.type = "batch"
    tg = job.task_groups[0]
    tg.count = 2
    tg.tasks[0].resources.cpu = 500
    tg.tasks[0].resources.memory_mb = 256
    return job


def _burst(srv, n):
    evals = [srv.submit_job(_batch_job()) for _ in range(n)]
    for e in evals:
        assert srv.wait_for_eval(e.id, timeout=60.0) is not None
    return [srv.store.eval_by_id(e.id).status for e in evals]


def _plan_results(srv):
    snap = srv.metrics.snapshot()
    return {
        o: int(snap.get(f"nomad.plan.result{{outcome={o}}}", 0))
        for o in ("committed", "partial", "rejected")
    }


def _overcommitted(srv) -> int:
    host = srv.store.matrix.snapshot_host()
    return int((host["used"] > host["totals"]).any(axis=1).sum())


def _quiet(srv, timeout=10.0):
    """Every worker idle and nothing queued: what is still held is held for
    good."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        stats = srv.eval_broker.stats
        if not any(stats[k] for k in (
            "total_ready", "total_unacked", "total_pending", "total_waiting"
        )):
            return
        time.sleep(0.02)
    raise AssertionError(f"broker never went quiet: {stats}")


class TestLiveServer:
    N_JOBS = 96

    def _run(self, monkeypatch, emptied):
        if emptied:
            # The test hook: the ledger is kept as it is, but no launch is
            # handed a row of it (the program itself has no switch).
            read = ClaimsLedger.overlay

            def nothing(self, *a, **kw):
                live = read(self, *a, **kw)[2]
                return claims_mod._NO_ROWS, claims_mod._NO_VALS, live

            monkeypatch.setattr(ClaimsLedger, "overlay", nothing)
        # Depth 1: a launch leaves when its predecessor's result is on the
        # host, as on the chip, where a launch's host part outlasts the
        # kernel (PERF.md section 6, PR 38).
        srv = _server(
            monkeypatch, num_workers=16, coalescer_lanes=8, pipeline_depth=1
        )
        try:
            assert _burst(srv, 1) == ["complete"]
            statuses = _burst(srv, self.N_JOBS)
            _quiet(srv)
            return (
                statuses, _plan_results(srv), _overcommitted(srv),
                srv.coalescer,
            )
        finally:
            srv.shutdown()

    def test_a_burst_of_identical_batch_jobs_is_refused_less(self, monkeypatch):
        statuses, plans, over, coal = self._run(monkeypatch, emptied=False)
        with monkeypatch.context() as mp:
            statuses0, plans0, over0, coal0 = self._run(mp, emptied=True)
        assert over == over0 == 0
        refused = plans["rejected"] + plans["partial"]
        refused0 = plans0["rejected"] + plans0["partial"]
        # Identical jobs score identically: without the overlay every
        # launch names the nodes the launch before it named.
        assert refused0 >= 10, (plans0, "the herd lost its teeth")
        assert refused * 4 <= refused0, (plans, plans0)
        assert statuses.count("failed") <= statuses0.count("failed")
        assert statuses.count("complete") >= self.N_JOBS - 2, statuses
        assert coal.overlay_rows_total > 0 and coal0.overlay_rows_total == 0
        assert coal.launches_unresolved_predecessor == 0
        # Nothing is left behind once the workers are idle but what the
        # next launch will release (committed, not yet synced).
        c = coal.claims.counts
        assert c["registered"] > 0 and c["truncated"] == 0
        assert not coal.claims._live and not coal.claims._open
        coal.claims.overlay(coal.matrix.version)
        assert coal.claims.held_rows() == 0
        assert c["registered"] == (
            c["released_committed"] + c["released_refused"]
            + c["dropped_reentry"]
        )

    def test_the_counters_are_on_the_registry(self, monkeypatch):
        srv = _server(monkeypatch, num_workers=4, coalescer_lanes=4)
        try:
            assert _burst(srv, 8).count("complete") >= 7
            snap = srv.metrics.snapshot()
            for key in (
                "nomad.kernel.overlay_rows_total",
                "nomad.coalescer.launches_unresolved_predecessor",
            ) + tuple(
                f"nomad.coalescer.claims{{event={e}}}"
                for e in claims_mod.EVENTS
            ):
                assert key in snap, key
            assert snap["nomad.coalescer.claims{event=registered}"] > 0
        finally:
            srv.shutdown()

    @pytest.mark.parametrize("fault", ("wedged_launch", "worker_raises",
                                       "nacked_eval"))
    def test_nothing_is_left_behind(self, monkeypatch, fault):
        """A wedged launch (the chaos seam ``device.wedge``: the lanes'
        futures raise and the workers nack), a worker that raises after its
        picks were entered, and an eval nacked on its way: the ledger holds
        nothing once the crowd has drained."""
        monkeypatch.setenv("NOMAD_TPU_DEVICE_DEADLINE_MS", "100")
        monkeypatch.setenv("NOMAD_TPU_DEVICE_COLD_SCALE", "1")
        srv = _server(monkeypatch, num_workers=8, coalescer_lanes=4)
        coal = srv.coalescer
        try:
            assert _burst(srv, 1) == ["complete"]
            if fault == "wedged_launch":
                schedule = [FaultSpec(
                    "device.wedge", "wedge", p=1.0, count=1, duration=0.5
                )]
                with injected(seed=3, schedule=schedule):
                    statuses = _burst(srv, 24)
                assert coal.wedged_dispatches >= 1
            else:
                from nomad_tpu.server.worker import Worker

                submit = Worker.submit_plan
                raised = []

                def flaky(self, plan):
                    if len(raised) < 3 and plan.node_allocation:
                        raised.append(plan.eval_id)
                        # The picks are in the ledger by now: the resolver
                        # entered them before the launch's future completed.
                        assert plan.eval_id in coal.claims._live
                        if fault == "worker_raises":
                            raise RuntimeError("worker died mid-eval")
                        return None, srv.store.snapshot()  # no verdict
                    return submit(self, plan)

                monkeypatch.setattr(Worker, "submit_plan", flaky)
                statuses = _burst(srv, 24)
                assert len(raised) == 3
            assert statuses.count("complete") >= 20, statuses
            _quiet(srv, timeout=30.0)
            assert not coal.claims._live and not coal.claims._open
            coal.claims.overlay(coal.matrix.version)
            assert coal.claims.held_rows() == 0
            assert _overcommitted(srv) == 0
        finally:
            srv.shutdown()


# ---------------------------------------------------------------------------
# the benchmark's reader of the new counter
# ---------------------------------------------------------------------------


@pytest.fixture()
def overlay_reader(monkeypatch):
    import importlib
    import os

    bench = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                         "benchmark")
    monkeypatch.syspath_prepend(bench)
    monkeypatch.syspath_prepend(os.path.join(bench, "readers"))
    return importlib.import_module("overlay_rows_per_launch").read


LAUNCHES = "nomad.kernel.launches{path=fused}"
ROWS = "nomad.kernel.overlay_rows_total"


@pytest.mark.parametrize("m0,m1,want", [
    ({ROWS: 100, LAUNCHES: 10}, {ROWS: 700, LAUNCHES: 30}, 30.0),
    ({ROWS: 5, LAUNCHES: 10}, {ROWS: 5, LAUNCHES: 30}, 0.0),  # steady
    ({LAUNCHES: 10}, {LAUNCHES: 30}, None),  # the parent: no such counter
    ({ROWS: 0, LAUNCHES: 10}, {ROWS: 9, LAUNCHES: 10}, None),  # no launch
], ids=["closed_loop", "nothing_in_flight", "parent", "no_launch"])
def test_overlay_rows_per_launch_reader(overlay_reader, m0, m1, want):
    assert overlay_reader({"m0": m0, "m1": m1}) == want
    assert overlay_reader({}) is None


def test_the_benchmark_lists_the_metric_in_all_four_cells():
    import json
    import os

    root = os.path.dirname(os.path.dirname(__file__))
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    (entry,) = [m for m in bench["per_layer"]
                if m["name"] == "overlay_rows_per_launch"]
    assert entry["layer"] == "coalescer" and entry["moves"] == "evals_per_s"
    # the four cells there were at PR 38; a cell a later PR adds enters
    # a list-bound metric through a ``benchmark`` PR (PERF.md section 7)
    assert entry["workloads"] == [w["name"] for w in bench["workloads"]][:4]
