"""The program behaviour that the ``again`` operations' numbers assume, on the
live server over the fake device: a job registered again unchanged keeps
its version, gets a second eval that ends ``complete``, submits no plan and
keeps its allocations id for id; with one field changed its version is
bumped.  (ISSUE 43 asked for this test in tier-1's ``tests/``; a benchmark
PR adds files under ``benchmark/`` only: PERF.md section 7.)"""

import os
import time

import pytest

import traffic


@pytest.fixture(scope="module")
def server():
    os.environ["NOMAD_TPU_FAKE_DEVICE"] = "1"
    try:
        from nomad_tpu import simcluster
        from nomad_tpu.server.server import Server, ServerConfig

        srv = Server(ServerConfig(num_workers=2))
        srv.start()
        for i in range(16):
            node = simcluster.sim_node(i)
            node.id = f"sim-node-{i:06d}"
            srv.register_node(node)
        yield srv
        srv.shutdown()
    finally:
        del os.environ["NOMAD_TPU_FAKE_DEVICE"]


def _register(srv, payload):
    from nomad_tpu.api.http_server import api_to_job

    ev = srv.submit_job(api_to_job(payload))
    for _ in range(500):
        got = srv.store.evals.get(ev.id)
        if got is not None and got.status in ("complete", "failed"):
            return got
        time.sleep(0.01)
    raise AssertionError(f"eval {ev.id} did not end")


def _state(srv, jid):
    job = srv.store.job_by_id("default", jid)
    allocs = sorted((a.id, a.node_id, a.desired_status)
                    for a in srv.store.allocs.values() if a.job_id == jid)
    return job.version, allocs


def _plans(srv):
    return (srv.metrics.snapshot().get("nomad.plan.evaluate") or {}).get(
        "count", 0)


@pytest.mark.parametrize("jtype", ["service", "batch"])
def test_a_job_registered_again_unchanged(server, jtype):
    t = traffic.load("backlog")
    op = {"namespace": "default", "width": 3, "type": jtype,
          "priority": t[jtype + "_priority"], "shape": 0,
          "job_id": f"res-{jtype}"}
    payload = traffic.job_payload(t, op)
    first = _register(server, payload)
    assert first.status == "complete"
    version, allocs = _state(server, op["job_id"])
    assert version == 0
    assert len(allocs) == 3
    plans = _plans(server)
    assert plans > 0

    second = _register(server, traffic.job_payload(t, op))
    assert second.id != first.id and second.status == "complete"
    assert not second.queued_allocations and not second.failed_tg_allocs
    assert _state(server, op["job_id"]) == (0, allocs)
    assert _plans(server) == plans  # a no-op plan is never submitted

    payload["priority"] += 1        # one field changed: a new version
    third = _register(server, payload)
    assert third.status == "complete"
    assert _state(server, op["job_id"])[0] == 1
