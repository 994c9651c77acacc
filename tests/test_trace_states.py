"""What the host, the kernel and the runtime were doing, by name:

* the span taxonomy of OBSERVABILITY.md against what one live eval through
  ``Server`` + coalescer really records (every listed name emitted, none
  that is not listed);
* the dispatch thread's state spans: properly nested, and covering the
  loop's wall time;
* the kernel's stage scopes (``jax.named_scope``) in the compiled text of
  the fused entry points, single-device and sharded;
* ``nomad.plan.result`` telling a committed, a partial and an entirely
  rejected plan apart;
* the runtime hooks (full collections as ``runtime.gc_pause``): on while a
  server runs, gone after the last one stops (the probe and
  ``runtime.stall``: tests/test_trace_work_or_waiting.py).
"""

from __future__ import annotations

import functools
import gc
import json
import os
import re
import threading
import time
import urllib.request

import pytest

from helpers import hold_gil
from nomad_tpu import mock, trace
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.structs.types import (
    Plan,
    PreemptionConfig,
    Resources,
    SchedulerConfiguration,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _documented_spans():
    """First column of the table between the span-taxonomy markers."""
    with open(os.path.join(ROOT, "OBSERVABILITY.md")) as fh:
        text = fh.read()
    block = text.split("<!-- span-taxonomy:begin -->", 1)[1]
    block = block.split("<!-- span-taxonomy:end -->", 1)[0]
    return re.findall(r"^\| `([a-z_.]+)` \|", block, flags=re.M)


DOCUMENTED = _documented_spans()


@pytest.fixture()
def clean_trace():
    trace.configure(enabled=True, sample=1.0, ring=4096)
    trace.clear()
    yield
    trace.configure(enabled=True, sample=1.0, ring=4096)
    trace.clear()


# ----------------------------------------------------------------------
# (1) the taxonomy


@pytest.fixture(scope="module")
def emitted(tmp_path_factory):
    """Span names of one live run: a real-jit agent on the CPU (the fake
    device neither lingers nor traces a Features variant), two jobs over
    HTTP, a device op, a forced collection, the interpreter held by one C
    call, a fresh compile, a flight dump."""
    import jax

    from nomad_tpu.api.agent import Agent, AgentConfig

    saved = os.environ.pop("NOMAD_TPU_FAKE_DEVICE", None)
    trace.configure(enabled=True, sample=1.0, ring=4096)
    trace.clear()
    agent = Agent(AgentConfig(server_config=ServerConfig(
        num_workers=1, node_capacity=16, coalescer_lanes=4,
        heartbeat_min_ttl=3600.0, heartbeat_max_ttl=7200.0,
        slo_enabled=False,
        scheduler_config=SchedulerConfiguration(
            preemption_config=PreemptionConfig(
                service_scheduler_enabled=True)),
    )))
    agent.start()
    try:
        srv = agent.server
        for _ in range(4):
            srv.register_node(mock.node())
        from nomad_tpu.jobspec import job_to_api

        base = f"http://127.0.0.1:{agent.http.port}"
        # Two jobs of one shape, one after the other: the first launch
        # widens the Features ratchet (coalescer.trace_variant), the
        # second reuses the variant (coalescer.enqueue).
        for _ in range(2):
            req = urllib.request.Request(
                base + "/v1/jobs", method="PUT",
                data=json.dumps({"Job": job_to_api(mock.job())}).encode(),
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=120) as r:
                eval_id = json.loads(r.read())["EvalID"]
            assert srv.wait_for_eval(eval_id, timeout=300.0)
        # Two instances that each need a node nearly to themselves, on
        # four nodes that carry twenty allocations of 500 MHz (at most one
        # of them empty): lower-priority work is evicted (sched.preempt).
        big = mock.job(priority=90)
        big.task_groups[0].count = 2
        big.task_groups[0].tasks[0].resources.cpu = 3600
        assert srv.wait_for_eval(srv.submit_job(big).id, timeout=300.0)
        srv.coalescer.sync_arrays()                        # a device op
        gc.collect()                                       # a full collection
        hold_gil(0.4)                    # the probe wakes late: runtime.stall
        time.sleep(0.05)
        salt = float(time.time_ns() % 1000003)             # never cached
        jax.jit(lambda x: x * salt + 1.0)(1.0).block_until_ready()
        trace.dump_flight_record(
            path=str(tmp_path_factory.mktemp("flight") / "f.json"),
            reason="taxonomy",
        )
    finally:
        agent.shutdown()
        if saved is not None:
            os.environ["NOMAD_TPU_FAKE_DEVICE"] = saved
    names = {r["name"] for r in trace.dump() if r["ph"] == "X"}
    trace.clear()
    return names


def test_taxonomy_is_listed():
    assert len(DOCUMENTED) >= 28 and len(set(DOCUMENTED)) == len(DOCUMENTED)


@pytest.mark.parametrize("name", DOCUMENTED)
def test_documented_span_is_emitted(emitted, name):
    assert name in emitted, (name, sorted(emitted))


def test_no_span_outside_the_taxonomy(emitted):
    assert emitted - set(DOCUMENTED) == set()


# ----------------------------------------------------------------------
# (2) dispatcher states: nested, and covering the loop

STATES = ("coalescer.idle", "coalescer.linger", "coalescer.slot_wait",
          "coalescer.launch", "coalescer.device_op")
CHILDREN = ("coalescer.sync", "coalescer.stage", "coalescer.enqueue",
            "coalescer.trace_variant")


@pytest.fixture(scope="module")
def dispatch_thread_spans():
    """>= 1 s of the dispatch loop on the fake device (5 ms a fetch, one
    pipeline slot, so the loop also waits for its slot), four producers."""
    from test_pipeline import _inputs, _matrix

    from nomad_tpu.scheduler.coalescer import DeviceCoalescer

    saved = {k: os.environ.get(k) for k in
             ("NOMAD_TPU_FAKE_DEVICE", "NOMAD_TPU_FAKE_DEVICE_LATENCY_MS")}
    os.environ["NOMAD_TPU_FAKE_DEVICE"] = "1"
    os.environ["NOMAD_TPU_FAKE_DEVICE_LATENCY_MS"] = "5"
    trace.configure(enabled=True, sample=1.0, ring=1 << 16)
    trace.clear()
    try:
        m = _matrix(8)
        inputs = _inputs(m, mock.job())
        coal = DeviceCoalescer(m, max_lanes=4, linger_s=0.0, pipeline_depth=1)
        coal.start()
        stop = time.time() + 1.2

        def produce():
            while time.time() < stop:
                coal.place(**inputs)
                time.sleep(0.003)

        threads = [threading.Thread(target=produce) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        coal.run_device_op(lambda: None)
        coal.stop()
        recs = [r for r in trace.dump()
                if r["ph"] == "X" and r["thread"] == "device-coalescer"
                and r["name"] in STATES + CHILDREN]
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        trace.configure(enabled=True, sample=1.0, ring=4096)
        trace.clear()
    return sorted(recs, key=lambda r: (r["ts"], -r["dur"]))


def test_dispatcher_states_nest_and_never_overlap(dispatch_thread_spans):
    open_spans = []  # the enclosing spans, outermost first
    for r in dispatch_thread_spans:
        while open_spans and open_spans[-1]["ts"] + open_spans[-1]["dur"] \
                <= r["ts"]:
            open_spans.pop()
        if open_spans:
            outer = open_spans[-1]
            # Inside another span: wholly, and as its recorded child.
            assert r["ts"] + r["dur"] <= outer["ts"] + outer["dur"], (outer, r)
            assert r["parent"] == outer["span"], (outer, r)
            assert outer["name"] == "coalescer.launch"
            assert r["name"] in CHILDREN
        else:
            assert r["parent"] == 0 and r["name"] in STATES, r
        open_spans.append(r)


@pytest.mark.parametrize("name", STATES[:1] + STATES[2:] + CHILDREN[:3])
def test_dispatcher_state_recorded(dispatch_thread_spans, name):
    # (no linger on the fake device, which answers synchronously; one
    # Features variant, traced by nobody: the twin is numpy)
    assert any(r["name"] == name for r in dispatch_thread_spans)


def test_dispatcher_states_cover_the_loop(dispatch_thread_spans):
    top = [r for r in dispatch_thread_spans if r["name"] in STATES]
    start = top[0]["ts"]
    end = max(r["ts"] + r["dur"] for r in top)
    assert end - start >= 1.0
    covered = sum(r["dur"] for r in top)
    assert covered >= 0.95 * (end - start), (covered, end - start)


# ----------------------------------------------------------------------
# (3) kernel stage scopes

SCOPES = ("feasibility", "binpack", "affinity_spread", "preemption",
          "place_scan", "place_scan/score", "place_scan/pick",
          "place_scan/update", "verify_scan", "pack")


def _unwrapped(names):
    """Scope paths with the wrappers transformations put around a scope
    (``vmap(place_scan)``) taken off."""
    return {re.sub(r"\b\w+\(([\w/]+)\)", r"\1", n) for n in names}


@functools.lru_cache(maxsize=None)
def _lowered(entry: str, variant: str):
    from nomad_tpu.lint.contracts import Grid, fused_operands
    from nomad_tpu.ops import kernels
    from nomad_tpu.parallel import sharding

    feats = kernels.FULL_FEATURES if variant == "full" else kernels.Features(
        c_width=2, a_width=0, s_width=0, preempt=False, ports=False,
        dp_width=0 if variant == "plain" else kernels.FULL_FEATURES.dp_width)
    g = Grid(nodes=64, batch=4, placements=16, deltas=4, live=3,
             features=feats)
    if entry == "fused_place_batch":
        return kernels.fused_place_batch.lower(
            *fused_operands(g), n_placements=g.placements, features=feats)
    fn = sharding.sharded_fused_place_batch(
        sharding.make_mesh(4), g.placements)
    return fn.lower(*fused_operands(g), features=feats)


@functools.lru_cache(maxsize=None)
def _op_names(entry: str, variant: str):
    """``op_name`` of every instruction of the compiled entry point."""
    text = _lowered(entry, variant).compile().as_text()
    return _unwrapped(re.findall(r'op_name="([^"]*)"', text))


def _traced_names(entry: str, variant: str):
    """Name stacks of the program as traced, before XLA merges ops (it
    combines a step's small all-reduces into one, under one of their
    names)."""
    text = _lowered(entry, variant).as_text(debug_info=True)
    return _unwrapped(re.findall(r'loc\("([^"]*)"', text))


@pytest.mark.parametrize("scope", SCOPES)
@pytest.mark.parametrize("variant", ("full", "narrow"))
@pytest.mark.parametrize("entry",
                         ("fused_place_batch", "sharded_fused_place_batch"))
def test_kernel_stage_scope_in_compiled_text(eight_devices, entry, variant,
                                             scope):
    names = _op_names(entry, variant)
    head, _, tail = scope.partition("/")
    pat = re.compile(
        r"(^|/)%s/(.*/)?%s(/|$)" % (head, tail) if tail
        else r"(^|/)%s(/|$)" % head)
    found = any(pat.search(n) for n in names)
    # The narrow variant has no preemption tables to read: that stage is
    # compiled out, and its scope with it.
    assert found == (not (variant == "narrow" and scope == "preemption")), (
        scope, sorted(names)[:20])


# What crosses shards in the sharded entry: one scope around each group of
# exchanges (OBSERVABILITY.md), so that a profile says which exchange the
# collective time belongs to.  The one-chip entry has none of them.
EXCHANGES = ("place_scan/pick/elect", "place_scan/pick/count",
             "place_scan/update/broadcast", "verify_scan/gather")


@pytest.mark.parametrize("scope", EXCHANGES)
def test_exchange_scope_in_the_sharded_entry_alone(eight_devices, scope):
    outer, _, inner = scope.partition("/")
    pat = re.compile(r"(^|/)%s/(.*/)?%s(/|$)" % (outer, inner))
    assert any(pat.search(n)
               for n in _op_names("sharded_fused_place_batch", "full")), scope
    last = scope.rsplit("/", 1)[1]
    one_chip = _op_names("fused_place_batch", "full")
    # (a scope is never an op_name's last part: that is the primitive, and
    # one of JAX's is called ``gather``)
    assert not any(re.search(r"(^|/)%s/" % last, n) for n in one_chip)


def test_rules_exchange_scope_where_a_distinct_property_is_compiled_in(
        eight_devices):
    """What the distinct_property stage adds across shards (its values on
    the spread stage's broadcast, one ``pmax`` a step) is under
    ``rules_exchange``, inside the scopes those ops were under; a program
    at ``dp_width`` 0, and the one-chip entry, have no such scope."""
    pat = re.compile(r"(^|/)rules_exchange/")
    wide = _op_names("sharded_fused_place_batch", "full")
    assert any(re.search(r"place_scan/(.*/)?update/(.*/)?rules_exchange/", n)
               for n in wide), sorted(wide)[:20]
    traced = _traced_names("sharded_fused_place_batch", "full")
    assert any(re.search(r"broadcast/rules_exchange/pmax$", n)
               for n in traced), sorted(n for n in traced if pat.search(n))
    for entry, variant in (("sharded_fused_place_batch", "plain"),
                           ("fused_place_batch", "full")):
        assert not any(pat.search(n) for n in _op_names(entry, variant))
        assert not any(pat.search(n) for n in _traced_names(entry, variant))


# ----------------------------------------------------------------------
# (4) plan outcomes


def test_plan_result_counts_each_outcome_once(monkeypatch, clean_trace):
    monkeypatch.setenv("NOMAD_TPU_FAKE_DEVICE", "1")
    srv = Server(ServerConfig(num_workers=1, heartbeat_min_ttl=3600.0,
                              heartbeat_max_ttl=7200.0, slo_enabled=False))
    srv.start()
    try:
        roomy, full = mock.node(), mock.node()
        srv.register_node(roomy)
        srv.register_node(full)
        big = mock.alloc(n=full)
        big.resources = Resources(cpu=3500, memory_mb=7000)
        srv.store.upsert_allocs(srv.next_index(), [big])

        def plan(*nodes):
            p = Plan(priority=50)
            for n in nodes:
                a = mock.alloc(n=n)
                a.resources = Resources(cpu=1000, memory_mb=1000)
                p.append_alloc(a)
            return p

        def counts():
            snap = srv.metrics.snapshot()
            return tuple(
                snap.get("nomad.plan.result{outcome=%s}" % o, 0)
                for o in ("committed", "partial", "rejected"))

        assert counts() == (0, 0, 0)
        srv.plan_applier.apply(plan(roomy))
        assert counts() == (1, 0, 0)
        res = srv.plan_applier.apply(plan(roomy, full))
        assert list(res.node_allocation) == [roomy.id]
        assert counts() == (1, 1, 0)
        res = srv.plan_applier.apply(plan(full))
        assert res.node_allocation == {}
        assert counts() == (1, 1, 1)
        # nomad.plan.partial keeps its meaning: every plan not wholly kept.
        assert srv.plan_applier.plans_partial == 2
        assert srv.plan_applier.plans_applied == 2
    finally:
        srv.shutdown()


# ----------------------------------------------------------------------
# (5) runtime hooks


def _pauses():
    return [r for r in trace.dump() if r["name"] == "runtime.gc_pause"]


@pytest.fixture()
def only_forced_collections():
    """No automatic collection while the test counts pauses: a server's
    start allocates enough to trigger a full one of its own."""
    gc.collect()
    gc.disable()
    yield
    gc.enable()


def _server():
    return Server(ServerConfig(num_workers=1, heartbeat_min_ttl=3600.0,
                               heartbeat_max_ttl=7200.0, slo_enabled=False))


def test_full_collection_is_a_span_while_a_server_runs(
        monkeypatch, clean_trace, only_forced_collections):
    from nomad_tpu.trace import runtime

    monkeypatch.setenv("NOMAD_TPU_FAKE_DEVICE", "1")
    assert runtime._on_gc not in gc.callbacks
    srv = _server()
    srv.start()
    try:
        assert gc.callbacks.count(runtime._on_gc) == 1
        gc.collect(0)  # a young collection: nothing recorded
        assert _pauses() == []
        gc.collect()
        (rec,) = _pauses()
        assert rec["args"]["generation"] == 2
        assert rec["args"]["collected"] >= 0
        assert rec["thread"] == "runtime" and rec["parent"] == 0
        assert srv.metrics.snapshot()[
            "nomad.phase.runtime.gc_pause"]["count"] == 1
    finally:
        srv.shutdown()
    assert runtime._on_gc not in gc.callbacks
    gc.collect()
    assert len(_pauses()) == 1  # none after shutdown


def test_runtime_hooks_last_until_the_last_server_stops(
        monkeypatch, clean_trace, only_forced_collections):
    from nomad_tpu.trace import runtime

    monkeypatch.setenv("NOMAD_TPU_FAKE_DEVICE", "1")
    servers = [_server() for _ in range(3)]
    for s in servers:
        s.start()
    try:
        assert gc.callbacks.count(runtime._on_gc) == 1
        servers[0].shutdown()
        servers[0].shutdown()  # twice: released once
        servers[1].shutdown()
        gc.collect()
        assert len(_pauses()) == 1
    finally:
        for s in servers:
            s.shutdown()
    assert runtime._on_gc not in gc.callbacks
