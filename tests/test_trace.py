"""Eval-lifecycle tracing: span semantics, deterministic sampling, ring
bounding, cross-thread propagation through the pipelined coalescer under
chaos delays (TSan-lite checked), the /v1/trace surface, and the
acceptance gate — per-eval spans must account for >=95% of measured
end-to-end eval latency on a live fake-device burst."""

from __future__ import annotations

import json
import tempfile
import threading
import time

import numpy as np
import pytest

from nomad_tpu import mock, trace
from nomad_tpu.chaos import FaultSpec, injected
from nomad_tpu.metrics import MetricsRegistry
from nomad_tpu.server import Server, ServerConfig


@pytest.fixture(autouse=True)
def _clean_trace():
    """Tracing is process-global: every test starts from a cleared
    recorder and the default config."""
    trace.configure(enabled=True, sample=1.0, ring=4096)
    trace.clear()
    yield
    trace.configure(enabled=True, sample=1.0, ring=4096)
    trace.clear()


def _by_name(records, name):
    return [r for r in records if r["name"] == name]


class TestSpanCore:
    def test_nesting_parents_inner_to_outer(self):
        with trace.span("eval.process", trace_id="ev-1") as root:
            with trace.span("sched.encode"):
                pass
        recs = trace.dump()
        outer = _by_name(recs, "eval.process")[0]
        inner = _by_name(recs, "sched.encode")[0]
        assert outer["trace"] == inner["trace"] == "ev-1"
        assert outer["parent"] == 0
        assert inner["parent"] == root.span_id
        assert inner["ts"] >= outer["ts"]
        assert inner["dur"] <= outer["dur"]

    def test_current_reflects_innermost(self):
        assert trace.current() is None
        with trace.span("a", trace_id="t") as a:
            assert trace.current() is a
            with trace.span("b") as b:
                assert trace.current() is b
            assert trace.current() is a
        assert trace.current() is None

    def test_ambient_spans_get_distinct_traces(self):
        with trace.span("solo.op"):
            pass
        with trace.span("solo.op"):
            pass
        recs = _by_name(trace.dump(), "solo.op")
        assert len(recs) == 2
        assert recs[0]["trace"] != recs[1]["trace"]

    def test_record_span_stitches_carried_context(self):
        # The cross-thread idiom: capture on one side, record on the other.
        ctx = trace.start_trace("ev-9")
        t0 = time.time()
        t1 = t0 + 0.005
        trace.record_span("coalescer.device", t0, t1, ctx=ctx, lanes=3)
        (rec,) = _by_name(trace.dump(), "coalescer.device")
        assert rec["trace"] == "ev-9"
        assert rec["parent"] == ctx.span_id
        assert rec["args"]["lanes"] == 3
        assert abs(rec["dur"] - 0.005) < 1e-6

    def test_event_attaches_to_enclosing_span(self):
        with trace.span("eval.process", trace_id="ev-2") as ctx:
            trace.event("seam.rpc.call", path="/x")
        (ev,) = _by_name(trace.dump(), "seam.rpc.call")
        assert ev["ph"] == "i"
        assert ev["trace"] == "ev-2"
        assert ev["parent"] == ctx.span_id

    def test_disabled_records_nothing(self):
        trace.configure(enabled=False)
        with trace.span("x", trace_id="t") as ctx:
            assert ctx is None
            trace.event("y")
        trace.record_span("z", 0.0, 1.0)
        assert trace.dump() == []

    def test_negative_duration_clamped(self):
        ctx = trace.start_trace("ev-c")
        trace.record_span("p", 10.0, 9.0, ctx=ctx)
        (rec,) = trace.dump()
        assert rec["dur"] == 0.0

    def test_phase_histograms_fed(self):
        reg = MetricsRegistry()
        with trace.span("plan.apply", trace_id="t", metrics=reg):
            pass
        trace.record_span("plan.queue_wait", 0.0, 0.010, metrics=reg,
                          ctx=trace.start_trace("t"))
        snap = reg.snapshot()
        assert snap["nomad.phase.plan.apply"]["count"] == 1
        assert snap["nomad.phase.plan.queue_wait"]["count"] == 1
        assert snap["nomad.phase.plan.queue_wait"]["p50_ms"] == 10.0


class TestSampling:
    def test_deterministic_per_trace(self):
        trace.configure(sample=0.5)
        verdicts = {f"ev-{i}": trace.start_trace(f"ev-{i}").sampled
                    for i in range(200)}
        # Same id -> same verdict, every time.
        for tid, v in verdicts.items():
            assert trace.start_trace(tid).sampled == v
        kept = sum(verdicts.values())
        assert 40 <= kept <= 160, f"sample=0.5 kept {kept}/200"

    def test_sample_zero_and_one(self):
        trace.configure(sample=0.0)
        assert not trace.start_trace("ev-x").sampled
        trace.configure(sample=1.0)
        assert trace.start_trace("ev-x").sampled

    def test_unsampled_trace_skips_ring_but_feeds_histograms(self):
        trace.configure(sample=0.0)
        reg = MetricsRegistry()
        with trace.span("sched.dispatch", trace_id="ev-u", metrics=reg):
            pass
        assert trace.dump() == []
        assert reg.snapshot()["nomad.phase.sched.dispatch"]["count"] == 1

    def test_sampled_trace_is_never_half_recorded(self):
        # Children inherit the root's verdict through the context chain.
        trace.configure(sample=0.5)
        sampled_id = next(
            f"ev-{i}" for i in range(1000)
            if trace.start_trace(f"ev-{i}").sampled
        )
        unsampled_id = next(
            f"ev-{i}" for i in range(1000)
            if not trace.start_trace(f"ev-{i}").sampled
        )
        for tid in (sampled_id, unsampled_id):
            with trace.span("eval.process", trace_id=tid):
                with trace.span("sched.encode"):
                    pass
        by_trace = trace.traces_by_id()
        assert len(by_trace.get(sampled_id, [])) == 2
        assert unsampled_id not in by_trace


class TestRingBounding:
    def test_ring_bounds_per_thread_memory(self):
        trace.configure(ring=16)
        for i in range(200):
            with trace.span("churn", trace_id=f"ev-{i}"):
                pass
        assert trace.recorder().span_count() <= 16
        # The survivors are the most recent.
        names = {r["trace"] for r in trace.dump()}
        assert "ev-199" in names
        assert "ev-0" not in names

    def test_ended_threads_keep_their_records(self):
        """64 short threads in turn, each of which ends before the next
        starts (so the next reuses its ident): every span is in the dump.
        A ring filed under ``get_ident()`` lost all but the last."""
        def one(i):
            with trace.span("short.thread", trace_id=f"ev-{i}"):
                pass

        for i in range(64):
            t = threading.Thread(target=one, args=(i,), name=f"short-{i}")
            t.start()
            t.join()
        recs = [r for r in trace.dump() if r["name"] == "short.thread"]
        assert sorted(r["trace"] for r in recs) == sorted(
            f"ev-{i}" for i in range(64))
        assert {r["thread"] for r in recs} == {f"short-{i}" for i in range(64)}
        # One row of the dump per thread, not per ident.
        assert len({r["tid"] for r in recs}) == 64

    def test_retired_rings_are_bounded_oldest_first(self, monkeypatch):
        from nomad_tpu.trace import core

        monkeypatch.setattr(core, "MAX_RETIRED_RINGS", 8)

        def one(i):
            with trace.span("short.thread", trace_id=f"ev-{i}"):
                pass

        for i in range(20):
            t = threading.Thread(target=one, args=(i,))
            t.start()
            t.join()
        kept = {r["trace"] for r in trace.dump()
                if r["name"] == "short.thread"}
        # The bound is applied as a ring is made: the newest thread's ring
        # and the eight newest ended ones.
        assert kept == {f"ev-{i}" for i in range(11, 20)}
        # A clear drops the rings nobody will write to again.
        trace.clear()
        assert len(trace.recorder()._rings) <= 1 + len(
            trace.recorder()._lanes) + threading.active_count()

    def test_ring_resize_keeps_the_thread_its_row(self):
        with trace.span("before", trace_id="ev-a"):
            pass
        trace.configure(ring=64)
        with trace.span("after", trace_id="ev-b"):
            pass
        recs = {r["name"]: r for r in trace.dump()}
        assert recs["before"]["tid"] == recs["after"]["tid"]

    def test_limit_returns_most_recent(self):
        for i in range(10):
            with trace.span("s", trace_id=f"ev-{i}"):
                pass
        recs = trace.dump(limit=3)
        assert len(recs) == 3
        assert recs[-1]["trace"] == "ev-9"


class TestCrossThreadPropagation:
    def test_context_survives_coalescer_hop_under_chaos(self, monkeypatch):
        """The launch ticket carries each lane's SpanContext across the
        place() -> dispatch-thread -> resolver-thread hops; with seeded
        chaos delays perturbing batch boundaries, every request's
        coalescer.queue_wait and coalescer.device spans must land in its
        own trace — no leakage between concurrently-coalesced evals —
        and TSan-lite must see no races on the shared rings."""
        monkeypatch.setenv("NOMAD_TPU_FAKE_DEVICE", "1")
        monkeypatch.setenv("NOMAD_TPU_FAKE_DEVICE_LATENCY_MS", "10")
        from test_pipeline import _drive, _inputs, _matrix

        from nomad_tpu.lint import tsan
        from nomad_tpu.scheduler.coalescer import DeviceCoalescer

        tsan.enable()
        try:
            m = _matrix(8)
            jobs = [mock.job() for _ in range(16)]
            inputs = [_inputs(m, j) for j in jobs]
            coal = DeviceCoalescer(m, max_lanes=4, linger_s=0.0,
                                   pipeline_depth=4)
            coal.start()
            try:
                schedule = [FaultSpec("coalescer.dispatch", "delay",
                                      p=0.5, duration=0.004)]
                outcomes = [None] * len(inputs)

                def place_traced(i):
                    with trace.span("eval.process", trace_id=f"ev-{i}"):
                        outcomes[i] = coal.place(**inputs[i])

                with injected(seed=37, schedule=schedule):
                    threads = [
                        threading.Thread(target=place_traced, args=(i,))
                        for i in range(len(inputs))
                    ]
                    for t in threads:
                        t.start()
                    for t in threads:
                        t.join(timeout=120)
            finally:
                coal.stop()
            races = tsan.reports()
        finally:
            tsan.disable()
        assert races == [], races
        assert all(o is not None for o in outcomes)

        by_trace = trace.traces_by_id()
        for i in range(len(inputs)):
            tid = f"ev-{i}"
            names = [r["name"] for r in by_trace.get(tid, [])]
            assert "coalescer.queue_wait" in names, (tid, names)
            assert "coalescer.device" in names, (tid, names)
            # Each trace is one eval: exactly one device-RTT span each.
            assert names.count("coalescer.device") == 1, (tid, names)
            root = [r for r in by_trace[tid]
                    if r["name"] == "eval.process"][0]
            for r in by_trace[tid]:
                assert r["trace"] == tid
                if r["name"] in ("coalescer.queue_wait",
                                 "coalescer.device"):
                    # Parented under the carried context, not another
                    # request's — by id, not by comparing clocks.
                    assert r["parent"] == root["span"], (root, r)
            (wait,) = [r for r in by_trace[tid]
                       if r["name"] == "coalescer.queue_wait"]
            (dev,) = [r for r in by_trace[tid]
                      if r["name"] == "coalescer.device"]
            # Order, on stamps one thread took in program order: a lane's
            # wait ends where its launch begins (the same reading), the
            # fetch ends after the launch, and never before the 10 ms the
            # fake device was told to take.
            assert wait["ts"] + wait["dur"] == pytest.approx(
                dev["ts"], abs=1e-6)
            assert dev["dur"] >= 0.010


class TestHTTPSurfaceAndCLI:
    @pytest.fixture()
    def agent(self, tmp_path, monkeypatch):
        monkeypatch.setenv("NOMAD_TPU_FAKE_DEVICE", "1")
        from nomad_tpu.api.agent import Agent, AgentConfig
        from nomad_tpu.client.client import ClientConfig

        a = Agent(AgentConfig(
            server_config=ServerConfig(
                num_workers=1, heartbeat_min_ttl=60, heartbeat_max_ttl=90
            ),
            client_config=ClientConfig(data_dir=str(tmp_path / "client")),
        ))
        a.start()
        yield a
        a.shutdown()

    def test_v1_trace_roundtrip(self, agent):
        import urllib.request

        with trace.span("unit.op", trace_id="ev-http"):
            pass
        base = f"http://127.0.0.1:{agent.http.port}"
        with urllib.request.urlopen(base + "/v1/trace?limit=100",
                                    timeout=10) as r:
            doc = json.loads(r.read())
        assert doc["config"]["enabled"] is True
        assert any(rec["name"] == "unit.op" for rec in doc["records"])

        with urllib.request.urlopen(base + "/v1/trace?format=chrome",
                                    timeout=10) as r:
            assert r.headers.get("Content-Type") == "application/json"
            chrome = json.loads(r.read())
        names = [e["name"] for e in chrome["traceEvents"]
                 if e["ph"] == "X"]
        assert "unit.op" in names
        # Perfetto needs thread metadata and either X or B/E phases.
        assert any(e["ph"] == "M" for e in chrome["traceEvents"])
        assert chrome["displayTimeUnit"] == "ms"

    def test_v1_trace_config_put(self, agent):
        import urllib.request

        base = f"http://127.0.0.1:{agent.http.port}"
        req = urllib.request.Request(
            base + "/v1/trace/config",
            data=json.dumps({"sample": 0.25, "ring": 64}).encode(),
            method="PUT", headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=10) as r:
            cfg = json.loads(r.read())
        assert cfg["sample"] == 0.25 and cfg["ring"] == 64
        assert trace.config()["sample"] == 0.25

    def test_cli_trace_dump_writes_perfetto_file(self, agent, tmp_path):
        from nomad_tpu import cli

        with trace.span("cli.op", trace_id="ev-cli"):
            pass
        out = str(tmp_path / "trace.json")
        rc = cli.main([
            "--address", f"http://127.0.0.1:{agent.http.port}",
            "trace", "dump", "-o", out,
        ])
        assert rc == 0
        doc = json.load(open(out))
        assert any(e["name"] == "cli.op" for e in doc["traceEvents"])

    def test_prometheus_exposition_over_http(self, agent):
        import urllib.request

        base = f"http://127.0.0.1:{agent.http.port}"
        with urllib.request.urlopen(
            base + "/v1/metrics?format=prometheus", timeout=10
        ) as r:
            assert "text/plain" in r.headers.get("Content-Type", "")
            text = r.read().decode()
        assert "nomad_kernel_launches" in text


class TestFlightRecorderDump:
    def test_dump_carries_chaos_seed(self, tmp_path, monkeypatch):
        monkeypatch.setenv("NOMAD_TPU_TRACE_DIR", str(tmp_path))
        with trace.span("doomed.op", trace_id="ev-d"):
            pass
        with injected(seed=123, schedule=[]):
            path = trace.dump_flight_record(reason="unit")
        doc = json.load(open(path))
        assert doc["metadata"]["reason"] == "unit"
        assert doc["metadata"]["chaos_seed"] == 123
        assert any(e["name"] == "doomed.op" for e in doc["traceEvents"])

    def test_invariant_violation_dumps_flight_record(
        self, tmp_path, monkeypatch
    ):
        monkeypatch.setenv("NOMAD_TPU_TRACE_DIR", str(tmp_path))
        from nomad_tpu.chaos import check_cluster
        from nomad_tpu.state.store import StateStore

        with trace.span("pre.violation", trace_id="ev-v"):
            pass

        # Over-committed node: two allocs that each alone fill it.
        store = StateStore()
        node = mock.node()
        store.upsert_node(1, node)
        job = mock.job()
        allocs = []
        for _ in range(2):
            a = mock.alloc(job, node)
            a.resources.cpu = node.resources.cpu
            allocs.append(a)
        store.upsert_allocs(2, allocs)
        srv = type("S", (), {"store": store})()
        violations = check_cluster([srv])
        assert violations, "fixture failed to violate"
        dumped = [v for v in violations if "flight record dumped" in v]
        assert dumped, violations
        path = dumped[0].split("dumped: ", 1)[1]
        doc = json.load(open(path))
        assert doc["metadata"]["reason"] == "invariant"
        assert doc["metadata"]["violations"]  # extra merged into metadata


class TestEndToEndCoverage:
    def test_spans_cover_95pct_of_eval_latency(self, monkeypatch):
        """Acceptance gate: on a live fake-device burst, the per-eval
        span tree (broker.queue_wait + eval.process) must account for
        >=95% of the measured end-to-end eval latency — i.e. the trace
        explains where the time went, with <5% unattributed."""
        monkeypatch.setenv("NOMAD_TPU_FAKE_DEVICE", "1")
        srv = Server(ServerConfig(
            num_workers=2,
            heartbeat_min_ttl=3600.0,
            heartbeat_max_ttl=7200.0,
        ))
        srv.start()
        try:
            for _ in range(4):
                srv.register_node(mock.node())
            evals = [srv.submit_job(mock.job()) for _ in range(12)]
            for ev in evals:
                assert srv.wait_for_eval(ev.id, timeout=60.0)
        finally:
            srv.shutdown()

        by_trace = trace.traces_by_id()
        covered_total = 0.0
        e2e_total = 0.0
        seen = 0
        for ev in evals:
            recs = by_trace.get(ev.id, [])
            waits = _by_name(recs, "broker.queue_wait")
            procs = _by_name(recs, "eval.process")
            if not procs:
                continue
            seen += 1
            start = min(r["ts"] for r in waits + procs)
            end = max(r["ts"] + r["dur"] for r in procs)
            e2e_total += end - start
            covered_total += sum(r["dur"] for r in waits + procs)
        assert seen >= 10, f"only {seen} evals traced"
        assert e2e_total > 0
        coverage = covered_total / e2e_total
        assert coverage >= 0.95, (
            f"spans cover {coverage:.1%} of e2e eval latency "
            f"({covered_total * 1e3:.1f}ms / {e2e_total * 1e3:.1f}ms)"
        )

    def test_lifecycle_phases_present_in_trace(self, monkeypatch):
        """One traced eval shows the full taxonomy: scheduler compute
        children under eval.process and the plan submit/apply chain."""
        monkeypatch.setenv("NOMAD_TPU_FAKE_DEVICE", "1")
        srv = Server(ServerConfig(
            num_workers=1,
            heartbeat_min_ttl=3600.0,
            heartbeat_max_ttl=7200.0,
        ))
        srv.start()
        try:
            srv.register_node(mock.node())
            ev = srv.submit_job(mock.job())
            assert srv.wait_for_eval(ev.id, timeout=60.0)
        finally:
            srv.shutdown()
        names = {r["name"] for r in trace.traces_by_id().get(ev.id, [])}
        for expected in (
            "broker.queue_wait",
            "eval.process",
            "worker.invoke_scheduler",
            "sched.encode",
            "sched.feasibility",
            "sched.dispatch",
            "plan.submit",
            "plan.queue_wait",
            "plan.apply",
        ):
            assert expected in names, (expected, sorted(names))
        snap = srv.metrics.snapshot()
        assert snap["nomad.phase.eval.process"]["count"] >= 1
        assert snap["nomad.phase.plan.apply"]["count"] >= 1
