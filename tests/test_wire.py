"""Real wire boundary (VERDICT #6): server agent and client agent as two
separate OS processes, talking only over HTTP — node registration,
heartbeats, the blocking-query alloc watch, and batched status updates all
cross a real socket (reference seam: client/client.go:1997 dialing
Node.GetClientAllocs, nomad/node_endpoint.go:915)."""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time
import urllib.request

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SERVER_SCRIPT = r"""
import sys, time
sys.path.insert(0, {repo!r})
from nomad_tpu.api.agent import Agent, AgentConfig
from nomad_tpu.server.server import ServerConfig

agent = Agent(AgentConfig(
    client_enabled=False,
    server_config=ServerConfig(
        num_workers=1, node_capacity=32,
        heartbeat_min_ttl=2.0, heartbeat_max_ttl=3.0,
    ),
))
agent.start()
print("ADDR", agent.rpc_addr, flush=True)
while True:
    time.sleep(1)
"""

CLIENT_SCRIPT = r"""
import sys, time
sys.path.insert(0, {repo!r})
from nomad_tpu.api.agent import Agent, AgentConfig
from nomad_tpu.client import ClientConfig

agent = Agent(AgentConfig(
    server_enabled=False,
    client_enabled=True,
    server_addr={addr!r},
    client_config=ClientConfig(data_dir={data_dir!r}),
))
agent.start()
print("NODE", agent.client.node.id, flush=True)
while True:
    time.sleep(1)
"""


def _spawn(code: str):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.Popen(
        [sys.executable, "-u", "-c", code],
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
        env=env,
        cwd=REPO,
    )


def _readline_tagged(proc, tag: str, timeout: float = 60.0) -> str:
    deadline = time.time() + timeout
    while time.time() < deadline:
        line = proc.stdout.readline()
        if not line:
            break
        if line.startswith(tag):
            return line.split(None, 1)[1].strip()
    err = proc.stderr.read() if proc.poll() is not None else ""
    raise AssertionError(f"never saw {tag!r}; stderr:\n{err}")


def _api(addr: str, path: str, body=None, method=None):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(
        addr + path, data=data,
        method=method or ("POST" if data else "GET"),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=30) as resp:
        return json.loads(resp.read() or b"null")


def _wait(pred, timeout=60.0, every=0.2):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(every)
    return pred()


@pytest.fixture
def two_process_cluster(tmp_path):
    server = _spawn(SERVER_SCRIPT.format(repo=REPO))
    procs = [server]
    try:
        addr = _readline_tagged(server, "ADDR")
        client = _spawn(CLIENT_SCRIPT.format(
            repo=REPO, addr=addr, data_dir=str(tmp_path / "client")
        ))
        procs.append(client)
        node_id = _readline_tagged(client, "NODE")
        yield addr, node_id, client
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
            p.wait(timeout=15)


def test_full_lifecycle_across_processes(two_process_cluster):
    addr, node_id, client_proc = two_process_cluster

    # Node registered + ready via the wire.
    assert _wait(lambda: _api(addr, f"/v1/node/{node_id}")["status"]
                 == "ready")

    # Submit a job through the public API; it must run on the remote client.
    job_payload = {
        "id": "wire-job",
        "name": "wire-job",
        "type": "service",
        "datacenters": ["dc1"],
        "task_groups": [{
            "name": "g",
            "count": 2,
            "tasks": [{
                "name": "t",
                "driver": "mock",
                "resources": {"cpu": 20, "memory_mb": 32},
            }],
            "ephemeral_disk": {"size_mb": 10},
        }],
    }
    out = _api(addr, "/v1/jobs", {"Job": job_payload})
    assert out["EvalID"]

    def running():
        allocs = _api(addr, "/v1/job/wire-job/allocations")
        return len([a for a in allocs
                    if a["client_status"] == "running"]) == 2
    assert _wait(running, timeout=90), _api(
        addr, "/v1/job/wire-job/allocations"
    )
    allocs = _api(addr, "/v1/job/wire-job/allocations")
    assert all(a["node_id"] == node_id for a in allocs)

    # Stop the job; the remote client must wind the tasks down.
    _api(addr, "/v1/job/wire-job", method="DELETE")

    def stopped():
        allocs = _api(addr, "/v1/job/wire-job/allocations")
        return all(a["client_status"] in ("complete", "failed")
                   for a in allocs)
    assert _wait(stopped, timeout=90)

    # Kill the client process: heartbeats stop; the server marks the node
    # down (TTL 2-3s) — failure detection over the wire.
    client_proc.kill()
    client_proc.wait(timeout=15)
    assert _wait(
        lambda: _api(addr, f"/v1/node/{node_id}")["status"] == "down",
        timeout=30,
    )


def test_rpc_proxy_blocking_query(two_process_cluster):
    """The alloc watch blocking query must actually block server-side
    (not poll): a no-change call with a short wait returns after ~wait."""
    addr, node_id, _ = two_process_cluster
    from nomad_tpu.api.rpc import HTTPServerRPC

    rpc = HTTPServerRPC(addr)
    allocs, index = rpc.get_client_allocs(node_id, min_index=0, timeout=1.0)
    assert allocs == []
    t0 = time.time()
    allocs2, index2 = rpc.get_client_allocs(
        node_id, min_index=index, timeout=2.0
    )
    elapsed = time.time() - t0
    assert elapsed >= 1.0, f"returned too fast ({elapsed:.2f}s) — not blocking"
    assert index2 >= index
