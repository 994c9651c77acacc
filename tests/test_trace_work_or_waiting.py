"""Work told from waiting on the host:

* ``trace.span(cpu=True)``: the thread's CPU beside the span's duration;
* ``lock_wait`` on ``coalescer.sync`` and ``plan.batch``: the time blocked
  acquiring the launch path's and the applier's locks;
* ``nomad.runtime.cpu_seconds{group=}``: CPU by thread group, monotone
  across a thread's end, the groups adding up to the process;
* the probe of trace/runtime.py: wake lateness, and ``runtime.stall`` when
  one C call holds the interpreter.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

from helpers import hold_gil
from nomad_tpu import mock, trace
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.trace import runtime

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GAUGE = "nomad.runtime.cpu_seconds{group=%s}"


@pytest.fixture(autouse=True)
def _clean_trace():
    trace.configure(enabled=True, sample=1.0, ring=4096)
    trace.clear()
    yield
    trace.configure(enabled=True, sample=1.0, ring=4096)
    trace.clear()


def _server(**kw):
    return Server(ServerConfig(num_workers=1, heartbeat_min_ttl=3600.0,
                               heartbeat_max_ttl=7200.0, slo_enabled=False,
                               **kw))


def _busy(seconds):
    """Bytecode in a loop: work the GIL is handed round for."""
    end = time.perf_counter() + seconds
    n = 0
    while time.perf_counter() < end:
        n += 1
    return n


def _cpu_tick():
    """The step of the thread CPU clock: about a microsecond on Linux
    proper, 10 ms where the kernel accounts CPU by ticks (gVisor: the chip
    machines)."""
    a = time.thread_time()
    while True:
        b = time.thread_time()
        if b != a:
            return b - a


TICK = _cpu_tick()
TOL = max(1e-3, TICK)          # a CPU reading against a wall-clock one
BUSY = max(0.05, 25 * TICK)    # a loop long enough to read its CPU


def _attempts(fn, n=3):
    """A CPU clock against a wall clock on a loaded box: the first of
    ``n`` tries that holds decides."""
    for k in range(n):
        try:
            return fn()
        except AssertionError:
            if k == n - 1:
                raise


# ----------------------------------------------------------------------
# (1) cpu beside dur


def _one(name):
    (rec,) = [r for r in trace.dump() if r["name"] == name]
    return rec


def test_cpu_of_a_busy_span_is_its_duration():
    def once():
        trace.clear()
        with trace.span("busy.op", cpu=True):
            _busy(BUSY)
        rec = _one("busy.op")
        assert rec["cpu"] <= rec["dur"] + TOL
        assert rec["cpu"] > 0.8 * rec["dur"]

    _attempts(once)


def test_cpu_of_a_sleeping_span_is_next_to_nothing():
    with trace.span("sleep.op", cpu=True):
        time.sleep(BUSY)
    rec = _one("sleep.op")
    assert 0.0 <= rec["cpu"] < 0.2 * rec["dur"]
    assert rec["dur"] >= BUSY


def test_cpu_is_the_calling_threads_own():
    """Another thread's work inside the span's interval is not in it."""
    other = threading.Thread(target=_busy, args=(BUSY,))
    with trace.span("waits.for.other", cpu=True):
        other.start()
        other.join()
    rec = _one("waits.for.other")
    assert rec["cpu"] < 0.5 * rec["dur"]


def test_cpu_is_opt_in_and_never_on_a_stitched_span():
    with trace.span("plain.op"):
        pass
    now = time.time()
    trace.record_span("stitched.op", now - 0.01, now)
    assert "cpu" not in _one("plain.op")
    assert "cpu" not in _one("stitched.op")


def test_cpu_passes_through_the_chrome_export_and_trace_view(tmp_path):
    with trace.span("busy.op", cpu=True):
        _busy(0.01)
    with trace.span("plain.op"):
        pass
    doc = trace.chrome_trace()
    by_name = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
    rec = _one("busy.op")
    assert by_name["busy.op"]["args"]["cpu"] == rec["cpu"]
    assert "cpu" not in by_name["plain.op"]["args"]
    path = tmp_path / "t.json"
    path.write_text(json.dumps(doc))
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "trace_view.py"),
         str(path)], capture_output=True, text=True, check=True).stdout
    header, rows = out.splitlines()[0], out.splitlines()[2:]
    assert "cpu ms" in header
    busy = next(r for r in rows if r.startswith("busy.op"))
    plain = next(r for r in rows if r.startswith("plain.op"))
    assert float(busy.split()[-1]) == pytest.approx(rec["cpu"] * 1e3, abs=0.01)
    assert plain.split()[-1] == "-"


# ----------------------------------------------------------------------
# (2) lock_wait where the launch path and the applier block


@pytest.fixture()
def fake_coalescer(monkeypatch):
    from test_pipeline import _inputs, _matrix

    from nomad_tpu.scheduler.coalescer import DeviceCoalescer

    monkeypatch.setenv("NOMAD_TPU_FAKE_DEVICE", "1")
    m = _matrix(8)
    inputs = _inputs(m, mock.job())
    coal = DeviceCoalescer(m, max_lanes=4, linger_s=0.0, pipeline_depth=1)
    coal.start()
    coal.place(**inputs)  # the first sync lays the whole matrix out
    trace.clear()
    yield m, coal, inputs
    coal.stop()


def _syncs():
    return [r for r in trace.dump() if r["name"] == "coalescer.sync"]


def test_sync_lock_wait_reads_how_long_the_host_lock_was_held(fake_coalescer):
    m, coal, inputs = fake_coalescer
    held = 0.020
    placed = threading.Thread(target=lambda: coal.place(**inputs))
    with m._host_lock:
        placed.start()
        time.sleep(held)
    placed.join(timeout=30)
    (sync,) = _syncs()
    # Blocked from a moment after the lock was taken until its release.
    assert 0.5 * held <= sync["args"]["lock_wait"] <= sync["dur"]
    assert sync["cpu"] <= sync["dur"] + TOL
    # The wait is no work: the thread's CPU is the rest at most.
    assert sync["cpu"] <= sync["dur"] - sync["args"]["lock_wait"] + TOL
    (launch,) = [r for r in trace.dump() if r["name"] == "coalescer.launch"]
    assert launch["cpu"] <= launch["dur"] + TOL
    assert launch["dur"] >= sync["dur"]


def test_sync_lock_wait_reads_zero_when_nothing_holds_the_locks(fake_coalescer):
    m, coal, inputs = fake_coalescer
    for _ in range(3):
        coal.place(**inputs)
    syncs = _syncs()
    assert len(syncs) == 3
    assert min(r["args"]["lock_wait"] for r in syncs) < 1e-3
    assert all(0.0 <= r["args"]["lock_wait"] <= r["dur"] for r in syncs)


def test_every_dispatcher_state_carries_cpu(fake_coalescer):
    m, coal, inputs = fake_coalescer
    coal.place(**inputs)
    coal.run_device_op(lambda: None)
    states = [r for r in trace.dump() if r["name"].startswith("coalescer.")
              and r["thread"] in ("device-coalescer", "resolver-coalescer")
              and r["name"] not in ("coalescer.queue_wait", "coalescer.device")]
    assert {"coalescer.launch", "coalescer.sync", "coalescer.stage",
            "coalescer.enqueue", "coalescer.fetch", "coalescer.unpack",
            "coalescer.device_op"} <= {r["name"] for r in states}
    assert all("cpu" in r and r["cpu"] <= r["dur"] + TOL for r in states)
    # The per-eval records stitched from another thread's stamps have none.
    assert all("cpu" not in r for r in trace.dump()
               if r["name"] in ("coalescer.queue_wait", "coalescer.device"))


def test_plan_batch_reads_its_wait_for_the_store(monkeypatch):
    from nomad_tpu.server.plan_queue import PendingPlan
    from nomad_tpu.structs.types import Plan

    monkeypatch.setenv("NOMAD_TPU_FAKE_DEVICE", "1")
    srv = _server()
    try:
        node = mock.node()
        srv.register_node(node)
        held = 0.020
        store = srv.store

        def batch():
            plan = Plan(priority=50)
            plan.append_alloc(mock.alloc(n=node))
            pending = PendingPlan(plan)
            srv.plan_applier.apply_batch([pending])
            pending.wait(timeout=30)

        applier = threading.Thread(target=batch)
        with store._write_lock, store._lock:   # the writers' own order
            applier.start()
            time.sleep(held)
        applier.join(timeout=30)
        batch()                                # and with nobody in its way
        blocked, free = [r for r in trace.dump() if r["name"] == "plan.batch"]
        assert 0.5 * held <= blocked["args"]["lock_wait"] <= blocked["dur"]
        assert blocked["cpu"] <= (
            blocked["dur"] - blocked["args"]["lock_wait"] + TOL)
        assert 0.0 <= free["args"]["lock_wait"] < 1e-3
        assert free["cpu"] <= free["dur"] + TOL
    finally:
        srv.shutdown()


# ----------------------------------------------------------------------
# (3) CPU by thread group


def _groups(srv):
    snap = srv.metrics.snapshot()
    return {g: snap[GAUGE % g] for g in runtime.cpu_groups()}


def test_group_names():
    assert runtime.group_of("worker") == "worker"
    assert runtime.group_of("worker-3") == "worker"
    assert runtime.group_of("worker-renew") == "worker-renew"
    assert runtime.group_of("http-api") == "http-api"
    assert runtime.group_of("Thread-12 (process_request_thread)") == "http-api"
    assert runtime.group_of("MainThread") == "other"
    assert runtime.group_of("raft-election-2") == "other"
    assert set(runtime.cpu_groups()) == set(runtime.PYTHON_GROUPS) | {
        "process", "native"}


def test_a_busy_worker_threads_cpu_lands_in_its_group(monkeypatch):
    monkeypatch.setenv("NOMAD_TPU_FAKE_DEVICE", "1")
    srv = _server()

    def once():
        before = _groups(srv)
        t = threading.Thread(target=_busy, args=(0.2,), name="worker")
        t.start()
        time.sleep(0.1)  # past the snapshot's freshness: a live reading
        during = _groups(srv)
        t.join()
        time.sleep(0.06)
        after = _groups(srv)
        assert during["worker"] > before["worker"]
        grown = after["worker"] - before["worker"]
        assert 0.1 <= grown <= 0.25
        # Nobody else ran the loop.
        assert after["plan-applier"] - before["plan-applier"] < 0.05
        return before, after

    try:
        before, after = _attempts(once)
        # Monotone across the thread's end, every group.
        time.sleep(0.06)
        later = _groups(srv)
        for g in runtime.cpu_groups():
            assert before[g] <= after[g] <= later[g], g
    finally:
        srv.shutdown()


def test_the_groups_add_up_to_the_process(monkeypatch):
    monkeypatch.setenv("NOMAD_TPU_FAKE_DEVICE", "1")
    srv = _server()
    try:
        _busy(0.05)
        time.sleep(0.06)
        g = _groups(srv)
        python = sum(g[k] for k in runtime.PYTHON_GROUPS)
        assert python <= g["process"]
        assert g["native"] >= 0.0
        # (to the microseconds between the readings of the clocks)
        assert python + g["native"] == pytest.approx(g["process"], abs=1e-3)
        assert g["process"] == pytest.approx(time.process_time(), abs=0.05)
    finally:
        srv.shutdown()


def test_a_handler_threads_cpu_is_kept_when_it_ends(monkeypatch):
    """A handler thread lives for one connection: it hands its CPU to
    ``http-api`` as it ends, and is counted once."""
    monkeypatch.setenv("NOMAD_TPU_FAKE_DEVICE", "1")
    from nomad_tpu.api.agent import Agent, AgentConfig

    agent = Agent(AgentConfig(server_config=ServerConfig(
        num_workers=1, heartbeat_min_ttl=3600.0, heartbeat_max_ttl=7200.0,
        slo_enabled=False)))
    agent.start()
    try:
        before = agent.metrics()[GAUGE % "http-api"]
        base = f"http://127.0.0.1:{agent.http.port}"
        for _ in range(20):
            with urllib.request.urlopen(base + "/v1/metrics", timeout=30) as r:
                snap = json.loads(r.read())
        assert GAUGE % "process" in snap
        time.sleep(0.06)
        after = agent.metrics()[GAUGE % "http-api"]
        # Twenty snapshots of a registry, serialized: milliseconds each.
        assert 0.002 < after - before < 5.0
        time.sleep(0.06)
        assert agent.metrics()[GAUGE % "http-api"] >= after
    finally:
        agent.shutdown()


# ----------------------------------------------------------------------
# (4) the probe


def _stalls():
    return [r for r in trace.dump() if r["name"] == "runtime.stall"]


def _probe_threads():
    return [t for t in threading.enumerate() if t.name == "runtime-probe"]


def test_probe_runs_while_a_server_does_and_ends_with_the_last(monkeypatch):
    monkeypatch.setenv("NOMAD_TPU_FAKE_DEVICE", "1")
    assert _probe_threads() == []
    a, b = _server(), _server()
    a.start()
    b.start()
    try:
        assert len(_probe_threads()) == 1
        w0 = runtime.wakes_total
        time.sleep(0.2)
        # A hundred wakes a second, give or take a loaded box.
        assert 5 <= runtime.wakes_total - w0 <= 25
        a.shutdown()
        assert len(_probe_threads()) == 1
    finally:
        a.shutdown()
        b.shutdown()
    assert _probe_threads() == []
    w1 = runtime.wakes_total
    time.sleep(0.05)
    assert runtime.wakes_total == w1


def test_a_held_interpreter_is_one_stall_with_its_args(monkeypatch):
    monkeypatch.setenv("NOMAD_TPU_FAKE_DEVICE", "1")
    srv = _server()
    srv.start()
    try:
        time.sleep(0.15)          # quiet: the probe wakes on time
        s0 = runtime.stall_seconds_total
        quiet = _stalls()
        held = hold_gil(0.4)
        time.sleep(0.05)          # the probe wakes, late, and files it
        (rec,) = _stalls()
        snap = srv.metrics.snapshot()
    finally:
        srv.shutdown()
    assert quiet == []
    assert rec["thread"] == "runtime" and rec["parent"] == 0
    args = rec["args"]
    assert STALL <= args["late"] <= held + 0.1
    assert rec["dur"] == pytest.approx(args["late"], abs=0.02)
    # One thread on a core the whole time: the C call that held the GIL.
    assert args["cpu"] >= 0.7 * args["late"]
    assert args["gc_overlap"] is False
    assert args["vol_switches"] >= 0 and args["invol_switches"] >= 0
    assert 0.0 <= args["since"] <= 2.0 + held
    if "run_delay" in args:       # where the kernel keeps schedstat
        assert args["run_delay"] >= 0.0
    assert runtime.stall_seconds_total - s0 == pytest.approx(args["late"])
    assert snap["nomad.runtime.stall_seconds_total"] >= args["late"]
    assert snap["nomad.runtime.wake_late_seconds_total"] >= args["late"]
    assert snap["nomad.runtime.wakes_total"] > 0
    assert snap["nomad.phase.runtime.stall"]["count"] == 1


STALL = runtime.STALL_THRESHOLD_S


@pytest.mark.parametrize("late,stalls", [
    (-0.001, 0), (0.004, 0), (STALL - 0.001, 0), (STALL + 0.001, 1),
    (3.0, 1),
])
def test_only_a_wake_past_the_threshold_is_a_stall(late, stalls):
    probe = runtime._Probe()   # never started: one wake, by hand
    w0, l0, s0 = (runtime.wakes_total, runtime.wake_late_seconds_total,
                  runtime.stall_seconds_total)
    probe.woke(late, time.time() - late, time.process_time())
    assert runtime.wakes_total - w0 == 1
    assert runtime.wake_late_seconds_total - l0 == pytest.approx(max(0, late))
    assert runtime.stall_seconds_total - s0 == pytest.approx(
        late if stalls else 0.0)
    assert len(_stalls()) == stalls


@pytest.mark.parametrize("ended", [True, False],
                         ids=["filed", "inside_the_hook_that_ends_it"])
def test_a_stall_under_a_collection_says_so(monkeypatch, ended):
    """The probe may get the interpreter back inside the gc hook's last
    call, before the collection is filed: it is known by its start then."""
    probe = runtime._Probe()
    now = time.time()
    if ended:
        monkeypatch.setattr(runtime, "_last_gc", (now - 0.2, now - 0.1))
    else:
        monkeypatch.setattr(runtime, "_gc_t0", now - 0.2)
    probe.woke(0.3, now - 0.31, time.process_time())
    (rec,) = _stalls()
    assert rec["args"]["gc_overlap"] is True


def test_run_delay_is_monotone_where_the_kernel_keeps_it():
    first = runtime.run_delay_seconds()
    if first is None:
        pytest.skip("no /proc/self/task/*/schedstat here")
    t = threading.Thread(target=_busy, args=(0.02,))
    t.start()
    t.join()
    assert runtime.run_delay_seconds() >= first >= 0.0
