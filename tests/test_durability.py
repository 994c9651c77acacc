"""Durability: WAL + snapshot/restore (VERDICT #3).

Reference behavior being matched: a server restart replays raft log +
FSM snapshot and loses nothing (nomad/fsm.go:1367 Persist, :1381 Restore,
raft-boltdb log store); the leader then rebuilds in-memory services from
state (nomad/leader.go:493 restoreEvals).
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import time

import pytest

from nomad_tpu import mock
from nomad_tpu.server.server import Server, ServerConfig
from nomad_tpu.state.wal import WriteAheadLog
from nomad_tpu.structs import serde
from nomad_tpu.structs.types import (
    Affinity,
    Allocation,
    Constraint,
    Evaluation,
    Job,
    Node,
    Spread,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg(tmp_path, **kw):
    kw.setdefault("num_workers", 1)
    kw.setdefault("node_capacity", 32)
    kw.setdefault("heartbeat_min_ttl", 600.0)
    kw.setdefault("heartbeat_max_ttl", 1200.0)
    kw.setdefault("data_dir", str(tmp_path / "data"))
    return ServerConfig(**kw)


# ----------------------------------------------------------------------
# serde
# ----------------------------------------------------------------------


def test_serde_roundtrip_job():
    job = mock.job()
    tg = job.task_groups[0]
    tg.constraints = [Constraint(l_target="${attr.kernel.name}",
                                 r_target="linux", operand="=")]
    tg.affinities = [Affinity(l_target="${attr.rack}", r_target="r1",
                              operand="=", weight=50)]
    tg.spreads = [Spread(attribute="${attr.rack}", weight=50)]
    wire = serde.to_wire(job)
    back = serde.from_wire(wire)
    assert isinstance(back, Job)
    assert back.id == job.id
    assert back.task_groups[0].constraints[0].r_target == "linux"
    assert back.task_groups[0].tasks[0].resources.cpu == tg.tasks[0].resources.cpu
    # Round-trip is a fixpoint.
    assert serde.to_wire(back) == wire


def test_serde_tolerates_schema_drift():
    node = mock.node()
    wire = serde.to_wire(node)
    wire["some_future_field"] = {"x": 1}
    back = serde.from_wire(wire)
    assert isinstance(back, Node)
    assert back.id == node.id


def test_serde_nested_containers():
    ev = Evaluation(job_id="j1", class_eligibility={"v1:abc": True})
    back = serde.from_wire(serde.to_wire(ev))
    assert back.class_eligibility == {"v1:abc": True}
    assert serde.from_wire(serde.to_wire({"__set": [1, 2]})) == {1, 2}


# ----------------------------------------------------------------------
# WAL mechanics
# ----------------------------------------------------------------------


def test_wal_append_and_load(tmp_path):
    wal = WriteAheadLog(str(tmp_path))
    wal.append(1, "op_a", {"args": [], "kwargs": {}})
    wal.append(2, "op_b", {"args": [1], "kwargs": {}})
    wal.close()
    snap, entries = WriteAheadLog(str(tmp_path)).load()
    assert snap is None
    assert [e["i"] for e in entries] == [1, 2]


def test_wal_discards_torn_final_line(tmp_path):
    wal = WriteAheadLog(str(tmp_path))
    wal.append(1, "op_a", {"args": [], "kwargs": {}})
    wal.close()
    with open(wal.log_path, "a") as fh:
        fh.write('{"i": 2, "op": "op_b", "a"')  # torn write
    snap, entries = WriteAheadLog(str(tmp_path)).load()
    assert [e["i"] for e in entries] == [1]


def test_wal_snapshot_rotates_and_skips_old_entries(tmp_path):
    wal = WriteAheadLog(str(tmp_path))
    wal.append(1, "op_a", {"args": [], "kwargs": {}})
    wal.write_snapshot({"latest_index": 1})
    wal.append(2, "op_b", {"args": [], "kwargs": {}})
    wal.close()
    snap, entries = WriteAheadLog(str(tmp_path)).load()
    assert snap["latest_index"] == 1
    assert [e["i"] for e in entries] == [2]
    # Crash between snapshot and rotation: stale low-index entries in the
    # log must be skipped, not double-applied.
    with open(wal.log_path, "a") as fh:
        fh.write('{"i": 1, "op": "op_a", "a": {"args": [], "kwargs": {}}}\n')
    snap, entries = WriteAheadLog(str(tmp_path)).load()
    assert [e["i"] for e in entries] == [2]


# ----------------------------------------------------------------------
# Server restart recovery
# ----------------------------------------------------------------------


def _boot_cluster(cfg, n_nodes=4):
    srv = Server(cfg)
    srv.start()
    for i in range(n_nodes):
        n = mock.node()
        n.attributes = dict(n.attributes)
        n.attributes["rack"] = f"r{i % 2}"
        srv.register_node(n)
    return srv


def test_restart_recovers_full_state(tmp_path):
    cfg = _cfg(tmp_path)
    srv = _boot_cluster(cfg)
    job = mock.job()
    job.task_groups[0].count = 3
    ev = srv.submit_job(job)
    done = srv.wait_for_eval(ev.id, timeout=60)
    assert done.status == "complete"
    live = {a.id for a in srv.store.allocs.values()
            if not a.terminal_status()}
    assert len(live) == 3
    nodes = set(srv.store.nodes)
    evals = set(srv.store.evals)
    latest = srv.store.latest_index
    # Crash-stop: abandon the server WITHOUT shutdown (no snapshot); the
    # WAL alone must carry everything.
    srv.heartbeater.set_enabled(False)
    for w in srv.workers:
        w.stop()
    srv.plan_applier.stop()

    srv2 = Server(cfg)
    assert set(srv2.store.nodes) == nodes
    assert set(srv2.store.evals) >= evals
    assert {a.id for a in srv2.store.allocs.values()
            if not a.terminal_status()} == live
    assert srv2.store.latest_index == latest
    assert srv2.store.job_by_id("default", job.id) is not None
    # Device matrix rebuilt: the restored cluster keeps scheduling.
    srv2.start()
    job2 = mock.job()
    job2.task_groups[0].count = 2
    ev2 = srv2.submit_job(job2)
    done2 = srv2.wait_for_eval(ev2.id, timeout=60)
    assert done2.status == "complete"
    allocs2 = [a for a in srv2.store.allocs.values()
               if a.job_id == job2.id and not a.terminal_status()]
    assert len(allocs2) == 2
    srv2.shutdown()


def test_restart_after_clean_shutdown_uses_snapshot(tmp_path):
    cfg = _cfg(tmp_path)
    srv = _boot_cluster(cfg)
    job = mock.job()
    job.task_groups[0].count = 2
    ev = srv.submit_job(job)
    assert srv.wait_for_eval(ev.id, timeout=60).status == "complete"
    srv.shutdown()  # writes a snapshot + rotates the log

    wal = WriteAheadLog(cfg.data_dir)
    snap, entries = wal.load()
    assert snap is not None
    assert entries == []  # compacted

    srv2 = Server(cfg)
    assert srv2.store.job_by_id("default", job.id) is not None
    assert len([a for a in srv2.store.allocs.values()
                if a.job_id == job.id]) == 2
    # matrix usage rebuilt from replayed allocs
    used = srv2.matrix.snapshot_host()["used"]
    assert used.sum() > 0
    srv2.shutdown()


def test_blocked_eval_restored_and_unblocks(tmp_path):
    """An eval blocked on capacity must survive restart and complete once
    capacity appears (restoreEvals + blocked-eval tracking)."""
    cfg = _cfg(tmp_path)
    srv = _boot_cluster(cfg, n_nodes=1)
    big = mock.job()
    big.task_groups[0].count = 1
    big.task_groups[0].tasks[0].resources.cpu = 100000
    ev = srv.submit_job(big)
    srv.wait_for_eval(ev.id, timeout=60)
    deadline = time.time() + 10
    while time.time() < deadline:
        blocked = [e for e in srv.store.evals.values()
                   if e.job_id == big.id and e.status == "blocked"]
        if blocked:
            break
        time.sleep(0.05)
    assert blocked, "expected a blocked eval"
    for w in srv.workers:
        w.stop()
    srv.plan_applier.stop()
    srv.heartbeater.set_enabled(False)

    srv2 = Server(cfg)
    srv2.start()
    restored = [e for e in srv2.store.evals.values()
                if e.job_id == big.id and e.status == "blocked"]
    assert restored, "blocked eval lost across restart"
    # Capacity arrives: a giant node unblocks and places the job.
    giant = mock.node()
    giant.resources.cpu = 200000
    giant.resources.memory_mb = 1 << 20
    srv2.register_node(giant)
    deadline = time.time() + 30
    placed = []
    while time.time() < deadline and not placed:
        placed = [a for a in srv2.store.allocs.values()
                  if a.job_id == big.id and not a.terminal_status()]
        time.sleep(0.05)
    assert placed, "blocked eval did not place after capacity arrived"
    srv2.shutdown()


def test_snapshot_every_compacts_log(tmp_path):
    cfg = _cfg(tmp_path, snapshot_every=10)
    srv = _boot_cluster(cfg)
    for i in range(12):
        srv.submit_job(mock.job())
    assert srv.store.wal.appends_since_snapshot < 10
    assert os.path.exists(srv.store.wal.snapshot_path)
    for w in srv.workers:
        w.stop()
    srv.plan_applier.stop()
    srv.heartbeater.set_enabled(False)
    srv2 = Server(cfg)
    assert len(srv2.store.jobs) == 12


KILL9_CHILD = r"""
import sys, time, os
sys.path.insert(0, {repo!r})
from nomad_tpu import mock
from nomad_tpu.server.server import Server, ServerConfig

cfg = ServerConfig(num_workers=1, node_capacity=32, data_dir={data!r},
                   heartbeat_min_ttl=600.0, heartbeat_max_ttl=1200.0)
srv = Server(cfg)
srv.start()
for i in range(4):
    srv.register_node(mock.node())
job = mock.job()
job.id = "kill9-job"
job.task_groups[0].count = 3
ev = srv.submit_job(job)
done = srv.wait_for_eval(ev.id, timeout=60)
assert done.status == "complete", done.status
print("READY", flush=True)
time.sleep(300)  # parent SIGKILLs us here
"""


def test_kill9_mid_workload_recovers(tmp_path):
    """The VERDICT's acceptance test: kill -9 a server mid-workload,
    restart, allocs/evals/jobs intact."""
    data = str(tmp_path / "data")
    code = KILL9_CHILD.format(repo=REPO, data=data)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [sys.executable, "-c", code],
        stdout=subprocess.PIPE,
        text=True,
        env=env,
        cwd=REPO,
    )
    try:
        line = proc.stdout.readline()
        assert "READY" in line, line
    finally:
        proc.kill()  # SIGKILL — no atexit, no shutdown snapshot
        proc.wait(timeout=30)

    cfg = ServerConfig(num_workers=1, node_capacity=32, data_dir=data,
                       heartbeat_min_ttl=600.0, heartbeat_max_ttl=1200.0)
    srv = Server(cfg)
    assert srv.store.job_by_id("default", "kill9-job") is not None
    live = [a for a in srv.store.allocs.values()
            if a.job_id == "kill9-job" and not a.terminal_status()]
    assert len(live) == 3
    assert len(srv.store.nodes) == 4
    # And it keeps scheduling on the rebuilt matrix.
    srv.start()
    ev = srv.submit_job(mock.job())
    assert srv.wait_for_eval(ev.id, timeout=60).status == "complete"
    srv.shutdown()
