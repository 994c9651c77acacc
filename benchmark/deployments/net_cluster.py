"""The set-up of ``c2m-10k-net``: a cluster whose nodes carry devices and
whose ports are in use.

After the seeded usage is installed and before the warm-up (README.md,
"Adding things"), each step a pure function of the node's index
(``net_reference``: ``cluster.devices``, ``cluster.resident``):

(i)  every node with a device group registers AGAIN, as a client does whose
     device plugin fingerprinted (``nvidia/gpu``, 4 instances on the nodes
     with ``i % 3 == 0``).  A re-registration keeps the node's matrix row
     and its usage; both are checked here (a row that moved fails the run),
     and the seeded usage is set again should it have been cleared;
(ii) the resident allocations: REAL ``Allocation`` objects of
     ``resident_jobs_per_kind`` service jobs a kind in a namespace of their
     own, each with the ports and device instances it holds, through
     ``store.upsert_job`` / ``store.upsert_allocs`` as a restored snapshot
     would put them there.  Their cpu, memory and disk come on top of the
     seeded usage;
(iii) what the matrix then holds (device instances, ports of the dynamic
     range in use) is read back and must equal the reference's sums.

Returns the reference's copy of what it installed: ``net_reference.
residents``' columns (plain data) and what it saw of rows and usage.
"""

from __future__ import annotations

import copy
import dataclasses

import numpy as np

import net_reference as net

CHUNK = 8192  # allocations per upsert (one raft-lite index each)


def _fail(msg):
    raise SystemExit(
        f"benchmark: set-up net_cluster: {msg}; nothing was measured")


def install(srv, cfg, seed, rows, seeded):
    from nomad_tpu import mock
    from nomad_tpu.structs import NetworkResource, RequestedDevice, Resources

    cluster, n = cfg["cluster"], cfg["nodes"]
    matrix = srv.matrix
    ids = [f"sim-node-{i:06d}" for i in range(n)]

    # (i) the device nodes fingerprint their devices
    gpu_nodes = 0
    for i, node_id in enumerate(ids):
        devices = net.node_devices(i, cluster)
        if not devices:
            continue
        node = copy.copy(srv.store.node_by_id(node_id))
        node.resources = dataclasses.replace(node.resources, devices={
            name: [f"{name}-{k}" for k in range(count)]
            for name, count in devices.items()})
        srv.register_node(node)
        gpu_nodes += 1
    moved = sum(matrix.row_of.get(ids[i]) != int(rows[i]) for i in range(n))
    if moved:
        _fail(f"{moved} nodes changed their matrix row on registering again")
    used = matrix.snapshot_host()["used"][rows]
    cleared = not np.array_equal(used, seeded.astype(used.dtype))
    if cleared:
        prio = matrix.snapshot_host()["prio_used"][rows].copy()
        matrix.set_usage(rows, seeded.astype(np.float32), prio)

    # (ii) the resident allocations
    state = net.residents(n, cluster)
    ns, per_kind = cluster["resident_namespace"], cluster["resident_jobs_per_kind"]
    srv.store.upsert_namespace(srv.next_index(), ns, "resident services")
    jobs, counts = {}, {}
    for spec in cluster.get("resident", []):
        for k in range(per_kind):
            job = mock.job(priority=50)
            job.id = job.name = f"{spec['name']}-{k:02d}"
            job.namespace = ns
            job.datacenters = [
                f"dc{d + 1}" for d in range(cluster["datacenters"])]
            tg = job.task_groups[0]
            tg.name = "g"
            tg.ephemeral_disk.size_mb = spec["disk_mb"]
            tg.networks = [NetworkResource(
                reserved_ports=list(spec["static_ports"]),
                dynamic_ports=list(spec["dynamic_ports"]))]
            tg.tasks[0].name = "t"
            tg.tasks[0].resources = Resources(
                cpu=spec["cpu"], memory_mb=spec["memory_mb"], devices=[
                    RequestedDevice(name=name, count=count)
                    for name, count in spec["devices"].items()])
            jobs[(spec["name"], k)], counts[(spec["name"], k)] = job, 0
    turn, placed = {}, []
    for kind in state["kind"]:
        key = (kind, turn.get(kind, 0) % per_kind)
        turn[kind] = turn.get(kind, 0) + 1
        placed.append((key, counts[key]))
        counts[key] += 1
    for key, job in jobs.items():
        job.task_groups[0].count = max(1, counts[key])
        srv.store.upsert_job(srv.next_index(), job)
    batch = []
    for k, (key, idx) in enumerate(placed):
        job, i = jobs[key], state["node"][k]
        a = mock.alloc(job, srv.store.nodes[ids[i]],
                       id=f"res-{i:05d}-{job.id}-{idx:05d}")
        a.name = f"{job.id}.g[{idx}]"
        a.resources = Resources(
            cpu=state["cpu"][k], memory_mb=state["memory_mb"][k],
            disk_mb=state["disk_mb"][k], devices=[
                RequestedDevice(name=name, count=count)
                for name, count in state["devices"][k].items()])
        a.assigned_ports = {"group": dict(
            zip(state["labels"][k], state["ports"][k]))}
        batch.append(a)
        if len(batch) == CHUNK:
            srv.store.upsert_allocs(srv.next_index(), batch)
            batch = []
    if batch:
        srv.store.upsert_allocs(srv.next_index(), batch)

    # (iii) what the matrix holds against the reference's sums
    host = matrix.snapshot_host()
    want = net.Tables(n, cluster).add_residents(state)
    for name, total in want.dev_total.items():
        slot = matrix.devices.lookup(name)
        if slot is None or not (
                np.array_equal(host["dev_total"][rows, slot], total)
                and np.array_equal(host["dev_used"][rows, slot],
                                   want.dev_used[name])):
            _fail(f"the matrix's instances of {name} are not the reference's")
    if not np.array_equal(host["dyn_used"][rows], want.dynamic_held()):
        _fail("the matrix's count of dynamic ports in use is not the "
              "reference's")
    extra = host["used"][rows].astype(np.float64) - seeded
    if not np.allclose(extra, net.resident_usage(n, state), atol=0.01):
        _fail("the matrix's usage is not seeded + resident")
    return dict(state, namespace=ns, jobs=len(jobs), gpu_nodes=gpu_nodes,
                usage_set_again=bool(cleared))
