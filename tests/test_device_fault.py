"""Device fault domain (round 20): watchdogged resolver fetches classify
wedged-vs-slow and never hang a caller, the per-path circuit breaker
degrades dispatch to the staged host twin under hysteresis + flip budget,
a lost matrix home shard evacuates with layout parity, and the broker's
unack-lease renewal keeps a legitimately slow scheduler invocation from
racing a nack-timeout redelivery."""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.chaos import FaultSpec, injected
from nomad_tpu.obs.breaker import (
    BREAKER_CLOSED,
    BREAKER_HALF_OPEN,
    BREAKER_OPEN,
    BreakerConfig,
    DeviceBreaker,
    DeviceWedgedError,
    STALL_OK,
    STALL_SLOW,
    STALL_WEDGED,
    classify_stall,
    watchdog_fetch,
)
from nomad_tpu.scheduler.coalescer import MAX_DELTA_ROWS, DeviceCoalescer
from nomad_tpu.server.eval_broker import EvalBroker
from nomad_tpu.state import NodeMatrix
from nomad_tpu.structs.types import Evaluation


def _matrix(n=8):
    m = NodeMatrix(capacity=16)
    for _ in range(n):
        m.upsert_node(mock.node())
    return m


def _inputs(m, job):
    from nomad_tpu.ops.encode import RequestEncoder

    enc = RequestEncoder(m)
    compiled = enc.compile(job, job.task_groups[0])
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


# ----------------------------------------------------------------------
# Watchdog verdicts
# ----------------------------------------------------------------------


class TestClassifyStall:
    def test_bands(self):
        assert classify_stall(0.05, 0.1, 1.5) == STALL_OK
        assert classify_stall(0.1, 0.1, 1.5) == STALL_OK  # inclusive
        assert classify_stall(0.12, 0.1, 1.5) == STALL_SLOW
        assert classify_stall(0.15, 0.1, 1.5) == STALL_SLOW  # inclusive
        assert classify_stall(0.2, 0.1, 1.5) == STALL_WEDGED

    def test_disabled_watchdog_is_always_ok(self):
        assert classify_stall(3600.0, 0.0, 1.5) == STALL_OK
        assert classify_stall(3600.0, -1.0, 1.5) == STALL_OK


class TestWatchdogFetch:
    def test_fast_fetch_is_ok(self):
        verdict, value, elapsed = watchdog_fetch(lambda: 42, 5.0)
        assert (verdict, value) == (STALL_OK, 42)
        assert elapsed < 5.0

    def test_slow_fetch_returns_usable_value(self):
        verdict, value, _ = watchdog_fetch(
            lambda: (time.sleep(0.15), "late")[1], 0.1, wedge_factor=4.0
        )
        assert (verdict, value) == (STALL_SLOW, "late")

    def test_wedged_fetch_abandoned(self):
        release = threading.Event()
        try:
            verdict, value, elapsed = watchdog_fetch(
                lambda: release.wait(10), 0.05, wedge_factor=1.5
            )
        finally:
            release.set()  # unstick the sacrificial thread
        assert (verdict, value) == (STALL_WEDGED, None)
        assert elapsed >= 0.05

    def test_fetch_error_reraises(self):
        def boom():
            raise ValueError("fetch exploded")

        with pytest.raises(ValueError, match="fetch exploded"):
            watchdog_fetch(boom, 5.0)

    def test_disabled_deadline_blocks_inline(self):
        verdict, value, _ = watchdog_fetch(lambda: "x", 0.0)
        assert (verdict, value) == (STALL_OK, "x")


# ----------------------------------------------------------------------
# Breaker state machine (synthetic clocks — no sleeps)
# ----------------------------------------------------------------------


def _cfg(**over):
    base = dict(
        deadline_ms=100.0, cold_scale=2.0, wedge_factor=1.5,
        trip_wedges=1, slow_ratio=0.5, min_samples=4, window_s=30.0,
        probation_s=5.0, cooldown_s=0.0, max_flips=10, flip_window_s=60.0,
    )
    base.update(over)
    return BreakerConfig(**base)


class TestBreakerStateMachine:
    def test_cold_deadline_scales_first_fetch_only(self):
        b = DeviceBreaker(config=_cfg())
        assert b.deadline_s() == pytest.approx(0.2)  # cold: 100ms × 2
        b.record_ok(0.05, now=1000.0)
        assert b.deadline_s() == pytest.approx(0.1)

    def test_wedge_trips_then_probation_then_canary_closes(self):
        b = DeviceBreaker(config=_cfg())
        t = 1000.0
        assert b.record_wedge(0.5, now=t) == BREAKER_OPEN
        assert b.trips_total == 1
        # Open: denied until probation elapses.
        assert b.allow_device_dispatch(now=t + 1.0) == (False, False)
        # Probation expired: half-open admits exactly one canary.
        assert b.allow_device_dispatch(now=t + 6.0) == (True, True)
        assert b.state == BREAKER_HALF_OPEN
        assert b.allow_device_dispatch(now=t + 6.1) == (False, False)
        # Canary verdict lands ok → closed, dispatch re-admitted.
        assert b.record_ok(0.05, canary=True, now=t + 7.0) == BREAKER_CLOSED
        assert b.allow_device_dispatch(now=t + 7.1) == (True, False)

    def test_canary_wedge_reopens(self):
        b = DeviceBreaker(config=_cfg())
        t = 1000.0
        b.record_wedge(0.5, now=t)
        assert b.allow_device_dispatch(now=t + 6.0) == (True, True)
        assert b.record_wedge(0.5, canary=True, now=t + 7.0) == BREAKER_OPEN
        assert b.trips_total == 2

    def test_cancel_canary_releases_slot(self):
        b = DeviceBreaker(config=_cfg())
        t = 1000.0
        b.record_wedge(0.5, now=t)
        assert b.allow_device_dispatch(now=t + 6.0) == (True, True)
        b.cancel_canary()
        assert b.allow_device_dispatch(now=t + 6.1) == (True, True)

    def test_slow_ratio_trips_only_past_min_samples(self):
        b = DeviceBreaker(config=_cfg(trip_wedges=99))
        t = 1000.0
        b.record_ok(0.01, now=t)
        b.record_ok(0.01, now=t + 1)
        assert b.record_slow(0.12, now=t + 2) == BREAKER_CLOSED  # 3 < 4
        assert b.record_slow(0.12, now=t + 3) == BREAKER_OPEN  # 2/4 ≥ 0.5
        assert b.trips_total == 1

    def test_flip_budget_freezes_instead_of_flapping(self):
        b = DeviceBreaker(config=_cfg(max_flips=2))
        t = 1000.0
        b.record_wedge(0.5, now=t)  # flip 1: closed → open
        assert b.allow_device_dispatch(now=t + 6.0) == (True, True)  # flip 2
        assert b.state == BREAKER_HALF_OPEN
        # Budget exhausted: the canary verdict cannot re-close — the
        # breaker freezes in place and counts the suppression.
        b.record_ok(0.05, canary=True, now=t + 7.0)
        assert b.state == BREAKER_HALF_OPEN
        assert b.flips_total == 2
        assert b.flips_suppressed >= 1

    def test_reset_force_closes_without_spending_budget(self):
        b = DeviceBreaker(config=_cfg(max_flips=1))
        b.record_wedge(0.5, now=1000.0)
        assert b.state == BREAKER_OPEN
        flips = b.flips_total
        b.reset()
        assert b.state == BREAKER_CLOSED
        assert b.flips_total == flips
        assert b.allow_device_dispatch(now=2000.0) == (True, False)

    def test_brief_shape(self):
        b = DeviceBreaker(config=_cfg())
        brief = b.brief()
        assert brief["breaker"] == BREAKER_CLOSED
        for key in (
            "trips", "wedged", "slow", "consecutive_wedges",
            "degraded_dispatches", "evacuations",
        ):
            assert brief[key] == 0


# ----------------------------------------------------------------------
# Pipeline integration: the seeded wedge at depth 8
# ----------------------------------------------------------------------


class TestPipelineWedge:
    def _pin(self, monkeypatch, **extra):
        monkeypatch.setenv("NOMAD_TPU_FAKE_DEVICE", "1")
        monkeypatch.setenv("NOMAD_TPU_DEVICE_DEADLINE_MS", "120")
        monkeypatch.setenv("NOMAD_TPU_DEVICE_COLD_SCALE", "1")
        for k, v in extra.items():
            monkeypatch.setenv(k, v)

    def _drive(self, coal, inputs, n_threads=8):
        """Like test_pipeline._drive but per-request exceptions are
        outcomes, not failures — the wedged lane SHOULD raise."""
        results = [None] * len(inputs)
        todo = list(range(len(inputs)))
        lock = threading.Lock()

        def worker():
            while True:
                with lock:
                    if not todo:
                        return
                    i = todo.pop(0)
                try:
                    results[i] = coal.place(**inputs[i], timeout=30.0)
                except BaseException as e:  # noqa: BLE001
                    results[i] = e

        threads = [
            threading.Thread(target=worker) for _ in range(n_threads)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads), "caller hung"
        assert all(r is not None for r in results)
        return results

    def test_depth8_seeded_wedge_fails_one_lane_resolves_rest(
        self, monkeypatch
    ):
        """One seeded wedged ticket in a depth-8 pipeline: its future
        raises ``DeviceWedgedError`` (never hangs), every other ticket
        still resolves, the breaker trips, and the wedged-dispatch
        counter reconciles with the raised errors."""
        # Long probation pins the breaker open so the count is exact.
        self._pin(monkeypatch, NOMAD_TPU_DEVICE_PROBATION="600")
        m = _matrix(8)
        inputs = [_inputs(m, mock.job()) for _ in range(10)]
        coal = DeviceCoalescer(
            m, max_lanes=1, linger_s=0.0, pipeline_depth=8
        )
        coal.start()
        try:
            schedule = [
                FaultSpec(
                    "device.wedge", "wedge", at_step=2, duration=0.6
                )
            ]
            with injected(seed=13, schedule=schedule) as inj:
                results = self._drive(coal, inputs)
        finally:
            coal.stop()
        assert any(f.seam == "device.wedge" for f in inj.log), inj.log
        wedged = [r for r in results if isinstance(r, DeviceWedgedError)]
        other_errs = [
            r for r in results
            if isinstance(r, BaseException)
            and not isinstance(r, DeviceWedgedError)
        ]
        placed = [
            r for r in results if not isinstance(r, BaseException)
        ]
        assert not other_errs, other_errs
        assert len(wedged) == 1, results
        assert len(placed) == 9
        assert all(o.rows[0] >= 0 for o in placed)
        # The typed error carries the watchdog's measurements.
        err = wedged[0]
        assert err.elapsed_s > err.deadline_s > 0
        # Counters reconcile: one wedged dispatch, one breaker trip.
        assert coal.wedged_dispatches == 1
        brief = coal.breaker.brief()
        assert brief["trips"] == 1
        assert brief["breaker"] == BREAKER_OPEN
        assert coal.inflight_depth() == 0

    def test_slow_launch_is_not_a_stall(self, monkeypatch):
        """A jit call compiles synchronously inside the LAUNCH (dispatch
        thread); the watchdog clocks only the fetch of an already-launched
        result.  A launch far longer than the wedge bound — a cold XLA
        compile — must therefore never read as slow or wedged."""
        from nomad_tpu.ops import kernels

        monkeypatch.setenv("NOMAD_TPU_DEVICE_DEADLINE_MS", "50")
        monkeypatch.setenv("NOMAD_TPU_DEVICE_COLD_SCALE", "1")
        m = _matrix(8)
        coal = DeviceCoalescer(m, max_lanes=2, linger_s=0.0, pipeline_depth=2)

        class Launched:
            def __array__(self, dtype=None, copy=None):
                out = np.zeros(
                    (coal.max_lanes, coal.scan_length,
                     kernels.FUSED_PACKED_WIDTH), np.float32,
                )
                out[:, :, kernels.PACKED_ROW] = -1.0
                return out

        def compiling_launch(*_operands, **_static):
            time.sleep(0.4)  # 8x the deadline, 5x the wedge bound
            return Launched(), _operands[-1]  # packed, the carry

        monkeypatch.setattr(
            kernels, "fused_place_batch_live", compiling_launch
        )
        coal.start()
        try:
            results = self._drive(
                coal, [_inputs(m, mock.job()) for _ in range(4)], n_threads=4
            )
        finally:
            coal.stop()
        assert not any(isinstance(r, BaseException) for r in results), results
        brief = coal.breaker.brief()
        assert brief["breaker"] == BREAKER_CLOSED
        assert brief["wedged"] == 0 and brief["slow"] == 0
        assert coal.wedged_dispatches == 0

    def test_degraded_dispatches_still_place(self, monkeypatch):
        """With the breaker held open, dispatches take the staged host
        path and still produce placements (availability backstop)."""
        self._pin(monkeypatch, NOMAD_TPU_DEVICE_PROBATION="600")
        m = _matrix(8)
        coal = DeviceCoalescer(
            m, max_lanes=1, linger_s=0.0, pipeline_depth=1
        )
        coal.start()
        try:
            with injected(
                13,
                [FaultSpec(
                    "device.wedge", "wedge", count=1, duration=0.6
                )],
            ):
                with pytest.raises(DeviceWedgedError):
                    coal.place(**_inputs(m, mock.job()), timeout=30.0)
            assert coal.breaker.brief()["breaker"] == BREAKER_OPEN
            out = coal.place(**_inputs(m, mock.job()), timeout=30.0)
            assert out.rows[0] >= 0
            assert coal.breaker.brief()["degraded_dispatches"] >= 1
        finally:
            coal.stop()

    def test_shutdown_completes_all_inflight_futures(self, monkeypatch):
        """Stop with a full pipeline of slow tickets + queued work: every
        caller's future completes (outcome or error) — nobody blocks
        past shutdown."""
        self._pin(monkeypatch, NOMAD_TPU_DEVICE_DEADLINE_MS="400")
        m = _matrix(8)
        inputs = [_inputs(m, mock.job()) for _ in range(6)]
        coal = DeviceCoalescer(
            m, max_lanes=1, linger_s=0.0, pipeline_depth=4
        )
        coal.start()
        results = [None] * len(inputs)
        started = threading.Barrier(len(inputs) + 1)

        def caller(i):
            started.wait(timeout=10)
            try:
                results[i] = coal.place(**inputs[i], timeout=30.0)
            except BaseException as e:  # noqa: BLE001
                results[i] = e

        threads = [
            threading.Thread(target=caller, args=(i,))
            for i in range(len(inputs))
        ]
        for t in threads:
            t.start()
        # Slow every fetch into the watchdog's slow band so tickets are
        # genuinely in flight when stop() lands.
        with injected(7, [FaultSpec("device.slow", "slow", p=1.0)]):
            started.wait(timeout=10)
            time.sleep(0.15)  # let the pipeline fill
            coal.stop()
            for t in threads:
                t.join(timeout=20)
        assert not any(t.is_alive() for t in threads), (
            "a caller blocked past shutdown"
        )
        for r in results:
            assert r is not None
            if isinstance(r, BaseException):
                assert isinstance(r, (RuntimeError, DeviceWedgedError)), r
        # Pipeline accounting drained with the futures.
        assert coal.inflight_depth() == 0

    def test_place_after_stop_raises_immediately(self, monkeypatch):
        self._pin(monkeypatch)
        m = _matrix(4)
        coal = DeviceCoalescer(
            m, max_lanes=1, linger_s=0.0, pipeline_depth=1
        )
        coal.start()
        coal.stop()
        with pytest.raises(RuntimeError, match="stopped"):
            coal.place(**_inputs(m, mock.job()), timeout=5.0)


# ----------------------------------------------------------------------
# Shard evacuation parity (matrix-level unit; the scenario covers the
# full loss → heal round trip under the server)
# ----------------------------------------------------------------------


class TestShardEvacuationParity:
    def test_evacuated_layout_matches_from_scratch_survivors(self):
        m = NodeMatrix(capacity=16)
        m.set_shard_count(4)
        nodes = [mock.node() for _ in range(12)]
        for n in nodes:
            m.upsert_node(n)
        order = [m.node_of[r] for r in sorted(m.node_of)]
        by_id = {n.id: n for n in nodes}
        version_before = m.version

        m.evacuate_shard(1)
        assert m.shard_count == 3
        assert m.version > version_before  # stale-dispatch invalidation

        twin = NodeMatrix(capacity=m.capacity)
        twin.set_shard_count(3)
        for nid in order:
            twin.upsert_node(by_id[nid])
        mismatches = [
            nid for nid in order if twin.row_of[nid] != m.row_of[nid]
        ]
        assert mismatches == [], (
            f"evacuated layout diverges from from-scratch survivor "
            f"layout: {mismatches}"
        )

    def test_relayout_translates_inflight_rows(self):
        """Rows claimed before the evacuation translate through the remap
        window (the growth-relocation mechanism) — a stale in-flight
        placement resolves to the node's new row, not garbage."""
        m = NodeMatrix(capacity=16)
        m.set_shard_count(4)
        nodes = [mock.node() for _ in range(8)]
        for n in nodes:
            m.upsert_node(n)
        old_rows = {nid: m.row_of[nid] for nid in m.row_of}
        old_version = m.version
        m.evacuate_shard(0)
        nids = sorted(old_rows)
        olds = np.array([old_rows[nid] for nid in nids], np.int32)
        translated = m.translate_rows(olds, old_version)
        for nid, got in zip(nids, translated):
            assert got == m.row_of[nid]


# ----------------------------------------------------------------------
# Broker lease renewal (satellite: slow-but-alive beats nack timeout)
# ----------------------------------------------------------------------


class TestLeaseRenewal:
    def _broker(self, **kw):
        b = EvalBroker(**kw)
        b.set_enabled(True)
        return b

    def test_renew_extends_unack_lease(self):
        b = self._broker(nack_timeout=0.3)
        ev = Evaluation(type="service", job_id="a")
        b.enqueue(ev)
        got, tok = b.dequeue(["service"], timeout=1)
        assert got.id == ev.id
        # Outlive several nack timeouts, renewing each third.
        deadline = time.time() + 1.0
        while time.time() < deadline:
            b.renew(ev.id, tok)
            time.sleep(0.1)
        # Never redelivered: the original token still settles the eval.
        assert b.outstanding_token(ev.id) == tok
        b.ack(ev.id, tok)
        assert b.unacked_count() == 0

    def test_without_renew_timeout_redelivers_and_stales_token(self):
        b = self._broker(nack_timeout=0.2)
        ev = Evaluation(type="service", job_id="a")
        b.enqueue(ev)
        got, tok = b.dequeue(["service"], timeout=1)
        got2, tok2 = b.dequeue(["service"], timeout=3)
        assert got2 is not None and got2.id == ev.id
        assert tok2 != tok
        with pytest.raises(ValueError):
            b.renew(ev.id, tok)  # stale token cannot extend the lease
        b.ack(ev.id, tok2)

    def test_renew_unknown_eval_raises(self):
        b = self._broker()
        with pytest.raises(ValueError):
            b.renew("nope", "tok")

    def test_worker_renews_through_slow_scheduler(self, monkeypatch):
        """A scheduler invocation outlasting the nack timeout must not be
        redelivered: the worker's renewal thread keeps the lease alive,
        the eval is processed exactly once, and it settles cleanly."""
        from nomad_tpu.scheduler import generic
        from nomad_tpu.server import Server, ServerConfig

        monkeypatch.setenv("NOMAD_TPU_FAKE_DEVICE", "1")
        orig = generic.GenericScheduler.process

        def slow_process(self, ev):
            time.sleep(1.0)  # > 2× the nack timeout below
            return orig(self, ev)

        monkeypatch.setattr(
            generic.GenericScheduler, "process", slow_process
        )
        srv = Server(ServerConfig(
            num_workers=1,
            heartbeat_min_ttl=3600.0, heartbeat_max_ttl=7200.0,
            eval_nack_timeout=0.4,
        ))
        srv.start()
        try:
            srv.register_node(mock.node())
            srv.submit_job(mock.job())
            deadline = time.time() + 15
            b = srv.eval_broker
            worker = srv.workers[0]
            while time.time() < deadline:
                if (
                    worker.evals_processed >= 1
                    and b.ready_count() == 0
                    and b.pending_count() == 0
                    and b.unacked_count() == 0
                ):
                    break
                time.sleep(0.05)
            assert worker.evals_processed >= 1
            assert b.pending_count() == 0 and b.unacked_count() == 0
            assert worker.leases_renewed >= 1
            # Exactly one delivery did the work — no timeout redelivery
            # re-ran the scheduler.
            assert worker.evals_processed == 1
            assert b.failed_evals() == []
        finally:
            srv.shutdown()


# ----------------------------------------------------------------------
# Surfaces: /v1/health device block + nomad top row
# ----------------------------------------------------------------------


class TestSurfaces:
    def test_health_report_carries_device_breaker(self, monkeypatch):
        from nomad_tpu.server import Server, ServerConfig

        monkeypatch.setenv("NOMAD_TPU_FAKE_DEVICE", "1")
        srv = Server(ServerConfig(
            num_workers=1,
            heartbeat_min_ttl=3600.0, heartbeat_max_ttl=7200.0,
        ))
        srv.start()
        try:
            report = srv.observatory.health_report()
            assert report["device"]["breaker"] == BREAKER_CLOSED
            assert report["device"]["trips"] == 0
        finally:
            srv.shutdown()

    def test_top_renders_device_row(self):
        from nomad_tpu.obs.top import render

        frame = render(
            metrics={},
            slo=None,
            health={
                "status": "ok", "score": 99.0,
                "device": {
                    "breaker": "open", "trips": 2, "wedged": 3,
                    "slow": 1, "degraded_dispatches": 7,
                    "evacuations": 1,
                },
            },
        )
        line = next(
            ln for ln in frame.splitlines() if ln.startswith("device")
        )
        assert "open" in line
        assert "trips 2" in line
        assert "evac 1" in line
