"""A select visits no computed class in Python (PR 46).

``GenericStack._record_eligibility`` used to fill a dict key by key from
``matrix.class_ids`` in every select of every eval: at a real rack count (a
region of 100,000 machines in racks of 40 has 7,680 computed classes) that
loop cost more than the rest of the eval.  A select now keeps the verdicts
as the vector it computed; the dict by class key (the reference's
``EvalEligibility`` record) is built where an eval blocks.  The loop is kept
HERE, as the parent wrote it, and what a blocked eval carries is held to it.
"""

from __future__ import annotations

import hashlib
import json
from typing import Dict, List, Tuple

import numpy as np
import pytest

from test_host_feasibility_vectorized import loop_class_eligibility, stack_for

from nomad_tpu import mock
from nomad_tpu.ops.encode import pow2_bucket
from nomad_tpu.scheduler import GenericScheduler
from nomad_tpu.scheduler.stack import GenericStack
from nomad_tpu.scheduler.testing import Harness
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.structs.types import Constraint, EvalStatus, Op

NODES = 2048
KERNELS = ["4.15.0", "4.19.0", "5.4.0", "5.10.0", "5.15.0"]
BINARIES = ["redis,cassandra,haproxy", "redis", "cassandra,haproxy", ""]

VERSION = Constraint(l_target="${attr.kernel.version}",
                     r_target=">= 4.19, < 5.15", operand=Op.VERSION.value)
BINARIES_HELD = Constraint(l_target="${meta.cached_binaries}",
                           r_target="redis,cassandra",
                           operand=Op.SET_CONTAINS.value)
EVEN_HOST = Constraint(l_target="${attr.unique.hostname}",
                       r_target="[02468]$", operand=Op.REGEXP.value)
PER_RACK = Constraint(l_target="${meta.rack}", r_target="",
                      operand=Op.DISTINCT_PROPERTY.value)


def rack_node(i: int):
    """1,024 racks x 5 kernels x 4 binary sets over 2,048 nodes: every node
    a computed class of its own."""
    node = mock.node()
    node.attributes = dict(node.attributes)
    node.attributes["kernel.version"] = KERNELS[i % 5]
    node.attributes["unique.hostname"] = f"sim-{i:06d}"
    node.meta = {"rack": f"r{i % 1024}"}
    if BINARIES[i % 4]:
        node.meta["cached_binaries"] = BINARIES[i % 4]
    return node


@pytest.fixture(scope="module")
def region() -> Harness:
    h = Harness()
    for i in range(NODES):
        h.store.upsert_node(h.next_index(), rack_node(i))
    assert len(h.store.matrix.class_ids) >= 1024
    return h


def ruled_job(count: int, cpu: int, *constraints: Constraint):
    job = mock.job()
    tg = job.task_groups[0]
    tg.count = count
    tg.tasks[0].resources.cpu = cpu
    tg.tasks[0].resources.memory_mb = 64
    tg.constraints = list(constraints)
    return job


def parents_record_eligibility(
    class_ids: Dict[str, int], selects: List[Tuple[np.ndarray, bool]],
) -> Tuple[Dict[str, bool], bool]:
    """``_record_eligibility`` as the parent (847d727) had it, over an
    eval's selects in order: (class vector, per-node checks in play)."""
    class_eligibility: Dict[str, bool] = {}
    escaped_computed_class = False
    for class_elig, per_node in selects:
        for key, cid in class_ids.items():
            if cid < len(class_elig):
                class_eligibility[key] = bool(class_elig[cid])
        if per_node:
            escaped_computed_class = True
    return class_eligibility, escaped_computed_class


@pytest.fixture()
def recorded(monkeypatch):
    """Every select's arguments to ``_record_eligibility``, in order."""
    calls: List[Tuple[np.ndarray, bool]] = []
    real = GenericStack._record_eligibility

    def spy(self, class_elig, host_mask, compiled):
        calls.append((np.array(class_elig), bool(
            host_mask is not None or compiled.distinct_props)))
        return real(self, class_elig, host_mask, compiled)

    monkeypatch.setattr(GenericStack, "_record_eligibility", spy)
    return calls


def process(h: Harness, job):
    h.store.upsert_job(h.next_index(), job)
    before = len(h.created_evals)
    sched = h.process(
        lambda snap, planner, matrix: GenericScheduler(
            "service", snap, planner, matrix),
        mock.eval_for(job))
    live = [a for a in h.store.allocs_by_job(job.namespace, job.id)
            if not a.terminal_status()]
    return sched, live, h.created_evals[before:]


@pytest.mark.parametrize("constraints", [
    (), (VERSION,), (VERSION, BINARIES_HELD), (EVEN_HOST, PER_RACK),
], ids=["plain", "version", "version+set_contains", "regexp+distinct"])
def test_a_placing_select_visits_no_class(region, recorded, constraints):
    hf = region.store.matrix.host_feasibility()
    walked = hf.walked_classes, hf.walked_nodes
    job = ruled_job(4, 100, *constraints)
    _sched, live, blocked = process(region, job)
    assert len(live) == 4 and not blocked
    assert recorded, "the eval made no select"
    assert (hf.walked_classes, hf.walked_nodes) == walked == (0, 0)


@pytest.mark.parametrize("constraints", [
    (VERSION,), (BINARIES_HELD,), (VERSION, BINARIES_HELD),
], ids=["version", "set_contains", "both"])
def test_the_class_vector_is_each_representatives_verdict(region, constraints):
    m = region.store.matrix
    job = ruled_job(1, 100, *constraints)
    stack = stack_for(region, job)
    compiled = stack.encoder.compile(job, job.task_groups[0])
    assert len(compiled.escaped) == len(constraints)
    elig = stack._class_eligibility(compiled)
    assert elig.shape == (pow2_bucket(len(m.class_ids)),)
    assert elig.shape[0] >= NODES
    want = loop_class_eligibility(region, compiled, elig.shape[0])
    np.testing.assert_array_equal(elig, want)
    assert 0 < want[: len(m.class_ids)].sum() < len(m.class_ids)
    assert m.host_feasibility().walked_classes == 0


@pytest.mark.parametrize("constraints,escapes,digest", [
    ((VERSION,), False, "e5e641a91e14479d"),
    ((VERSION, BINARIES_HELD), False, "5405afe358e1ad8a"),
    # a per-node predicate: a host mask
    ((VERSION, EVEN_HOST), True, "e5e641a91e14479d"),
    ((BINARIES_HELD, PER_RACK), True, "7ef148aae9b236f9"),  # distinct_property
], ids=["version", "version+set_contains", "version+regexp",
        "set_contains+distinct"])
def test_a_blocked_eval_carries_what_the_loop_gave(region, recorded,
                                                   constraints, escapes,
                                                   digest):
    """An ask no node has room for: nothing places, the eval blocks, and its
    record is the parent's, class key by class key (``digest``: of the
    dict the parent's own code, 847d727, put on this eval on this cluster)."""
    m = region.store.matrix
    job = ruled_job(2, 1_000_000, *constraints)
    _sched, live, created = process(region, job)
    assert not live
    blocked = [e for e in created if e.status == EvalStatus.BLOCKED.value]
    assert len(blocked) == 1
    want, want_escaped = parents_record_eligibility(m.class_ids, recorded)
    assert blocked[0].class_eligibility == want
    assert list(blocked[0].class_eligibility) == list(want)
    assert hashlib.sha256(json.dumps(list(
        blocked[0].class_eligibility.items())).encode()).hexdigest()[
            :16] == digest
    assert len(want) == len(m.class_ids) >= 1024
    assert 0 < sum(want.values()) < len(want)
    assert blocked[0].escaped_computed_class is want_escaped is escapes
    assert m.host_feasibility().walked_classes == 0


def test_a_later_select_overwrites_the_classes_it_saw_and_no_other():
    """A job of several groups: each select records over the last one's
    verdicts key by key, and the classes a narrower vector does not reach
    (the matrix grew between the selects) keep what they had."""
    h = Harness()
    for i in range(12):
        h.store.upsert_node(h.next_index(), rack_node(i))
    m = h.store.matrix
    job = ruled_job(1, 100)
    rng = np.random.default_rng(46)
    selects = [(rng.random(width) < 0.5, per_node) for width, per_node in
               ((16, False), (8, False), (4, True), (16, False), (2, False))]
    for upto in range(1, len(selects) + 1):
        stack = stack_for(h, job)
        compiled = stack.encoder.compile(job, job.task_groups[0])
        for class_elig, per_node in selects[:upto]:
            stack._record_eligibility(
                class_elig, np.ones((m.capacity,), bool) if per_node else None,
                compiled)
        want, want_escaped = parents_record_eligibility(
            m.class_ids, selects[:upto])
        assert stack.class_eligibility == want
        assert stack.escaped_computed_class is want_escaped
    # the matrix learns a class after the selects: the record does not
    h.store.upsert_node(h.next_index(), rack_node(500))
    assert len(m.class_ids) == 13
    assert stack.class_eligibility == want


def test_the_counter_counts_the_fallback_and_the_server_exposes_it():
    """Where an attribute has no column (the registry is full) the class
    vector falls back to the representatives, one by one, and says so."""
    srv = Server(ServerConfig(num_workers=1, heartbeat_min_ttl=3600.0,
                              heartbeat_max_ttl=7200.0, slo_enabled=False))
    srv.start()
    try:
        for i in range(6):
            srv.register_node(rack_node(i))
        m = srv.matrix
        assert srv.metrics.snapshot()["nomad.sched.class_walk_total"] == 0
        job = ruled_job(1, 100, VERSION)
        ev = srv.wait_for_eval(srv.submit_job(job).id, timeout=120)
        assert ev is not None and ev.status == "complete"
        assert srv.metrics.snapshot()["nomad.sched.class_walk_total"] == 0
        # no column for the attribute any more, and no free slot for one
        hf = m.host_feasibility()
        real = hf.class_vector
        hf.class_vector = lambda cons, pad: None
        try:
            job = ruled_job(1, 100, VERSION)
            ev = srv.wait_for_eval(srv.submit_job(job).id, timeout=120)
            assert ev is not None and ev.status == "complete"
        finally:
            hf.class_vector = real
        walked = srv.metrics.snapshot()["nomad.sched.class_walk_total"]
        assert walked == len(m.class_repr) > 0
    finally:
        srv.shutdown()
