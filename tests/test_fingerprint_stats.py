"""Periodic re-fingerprint (client/fingerprint_manager.go) + client
host/device stats (ClientStats surface)."""

from __future__ import annotations

import time

import pytest

from helpers import _wait
from nomad_tpu.client import Client, ClientConfig
from nomad_tpu.server import Server, ServerConfig


@pytest.fixture
def server():
    s = Server(ServerConfig(
        num_workers=1, heartbeat_min_ttl=60, heartbeat_max_ttl=90
    ))
    s.start()
    yield s
    s.shutdown()


def test_refingerprint_pushes_changed_facts(server, tmp_path, monkeypatch):
    # Start WITHOUT an accelerator in the environment (the suite's env may
    # carry the TPU runtime's vars).
    monkeypatch.delenv("TPU_ACCELERATOR_TYPE", raising=False)
    c = Client(server, ClientConfig(
        data_dir=str(tmp_path / "c"), fingerprint_interval=0.2
    ))
    c.start()
    try:
        node_id = c.node.id
        assert "platform.tpu.type" not in (
            server.store.node_by_id(node_id).attributes
        )
        # An accelerator appears (env-fingerprinted TPU).
        monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5e-8")
        assert _wait(lambda: server.store.node_by_id(
            node_id
        ).attributes.get("platform.tpu.type") == "v5e", timeout=15)
        assert "tpu" in server.store.node_by_id(node_id).resources.devices
    finally:
        c.shutdown()


def test_client_stats_endpoint(tmp_path):
    from nomad_tpu.api import Agent, AgentConfig
    from nomad_tpu.api.client import APIClient

    a = Agent(AgentConfig(
        server_config=ServerConfig(
            num_workers=1, heartbeat_min_ttl=60, heartbeat_max_ttl=90
        ),
        client_config=ClientConfig(data_dir=str(tmp_path / "c")),
    ))
    a.start()
    try:
        out = APIClient(a.rpc_addr)._call("GET", "/v1/client/stats")
        assert out["CPU"]["Cores"] >= 1
        assert out["DataDir"]["Total"] > 0
        assert out["AllocCount"] == 0
        assert "Devices" in out
    finally:
        a.shutdown()


def test_reregistration_preserves_operator_state(server, tmp_path, monkeypatch):
    """A re-fingerprint re-registration must NOT wipe server-owned node
    state: a drain in progress (or markings like ineligibility) survives
    the client pushing refreshed facts (Node.Register semantics)."""
    from nomad_tpu.structs.types import DrainStrategy

    monkeypatch.delenv("TPU_ACCELERATOR_TYPE", raising=False)
    c = Client(server, ClientConfig(
        data_dir=str(tmp_path / "c"), fingerprint_interval=0.2
    ))
    c.start()
    try:
        node_id = c.node.id
        server.update_node_drain(
            node_id, DrainStrategy(deadline=300.0)
        )
        assert server.store.node_by_id(node_id).drain
        # Trigger a fact change -> re-registration.
        monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5p-4")
        assert _wait(lambda: server.store.node_by_id(
            node_id
        ).attributes.get("platform.tpu.type") == "v5p", timeout=15)
        node = server.store.node_by_id(node_id)
        assert node.drain  # drain survived the re-register
        assert node.scheduling_eligibility == "ineligible"
    finally:
        c.shutdown()
