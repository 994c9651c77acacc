"""The set-up of ``c2m-10k-preempt``: a full cluster in priority tiers.

After the seeded usage is installed and before the warm-up (README.md,
"Adding things"):

(i)  the seeded aggregates are set again with all of their usage in the
     bucket of the production tier (``tier.production_priority``, 70): a
     tier that nothing in the mix may evict, because nothing stands behind
     an aggregate to evict (PERF.md section 7);
(ii) a REAL best-effort tier: ``tier.jobs`` batch jobs at
     ``tier.priority`` (10) in a namespace of their own, each of one of the
     mix's shapes (``tier.shapes``), their allocations dealt from the seed
     onto every node until its free cpu is under ``tier.min_free_cpu`` (100
     MHz, the smallest ask of the mix): no node has room for any job of
     the window without an eviction.  Jobs and allocations go through
     ``store.upsert_job`` / ``store.upsert_allocs`` as a restored snapshot
     would put them there.

(iii) one job of the window's kind (``tier.probe``: a service of one
     instance at priority 50) is registered and must be placed, which on
     this cluster means by an eviction: a program that cannot do that
     (the parent of PR 37 names a full node and no eviction, attempt after
     attempt) ends here with "nothing was measured" in seconds, and not
     in its warm-up after 100 re-registrations of every job.

Returns the reference's copy of what it installed, as plain data in
columns (allocation ``k``: ``ids[k]``, on node ``node[k]``, of job
``job[k]``, asking ``cpu[k]`` / ``memory_mb[k]`` / ``disk_mb[k]``).
"""

from __future__ import annotations

import random

import numpy as np

import reference as ref

CHUNK = 8192  # allocations per upsert (one raft-lite index each)
EPHEMERAL_DISK_MB = 300  # the default of a task group: what a job asks
PROBE_WAIT_S = 90.0  # the probe's first launch compiles the program


def deal(seeded, totals, shapes, min_free_cpu, seed):
    """(node, shape) of every allocation of the tier: shapes drawn from
    the seed among those that still fit, node by node, until the node's
    free cpu is under ``min_free_cpu``.  Pure: the check calls it again."""
    rng = random.Random(f"{seed}:tier")
    out = []
    for i in range(len(seeded)):
        free = [float(totals[d] - seeded[i, d]) for d in range(3)]
        while free[0] >= min_free_cpu:
            fits = [s for s, (cpu, mem) in enumerate(shapes)
                    if cpu <= free[0] and mem <= free[1]
                    and EPHEMERAL_DISK_MB <= free[2]]
            if not fits:
                break
            s = fits[rng.randrange(len(fits))]
            out.append((i, s))
            free[0] -= shapes[s][0]
            free[1] -= shapes[s][1]
            free[2] -= EPHEMERAL_DISK_MB
    return out


def probe(srv, cfg):
    """Register ``tier.probe`` and see it placed; exit where it is not."""
    from nomad_tpu import mock
    from nomad_tpu.structs import Resources

    spec = cfg["tier"]["probe"]
    job = mock.job(priority=spec["priority"])
    job.id = job.name = "tier-probe"
    job.datacenters = [
        f"dc{d + 1}" for d in range(cfg["cluster"]["datacenters"])]
    tg = job.task_groups[0]
    tg.name, tg.count = "g", 1
    tg.tasks[0].name = "t"
    tg.tasks[0].resources = Resources(
        cpu=spec["cpu"], memory_mb=spec["memory_mb"])
    ev = srv.wait_for_eval(srv.submit_job(job).id, timeout=PROBE_WAIT_S)
    live = [a for a in srv.store.allocs_by_job(job.namespace, job.id)
            if not a.terminal_status()]
    if len(live) != 1:
        raise SystemExit(
            "benchmark: set-up preempt_tiers: a job of priority "
            f"{spec['priority']} was not placed on the full cluster (eval "
            f"{ev.status + ': ' + ev.status_description if ev else 'still open'}"
            "): this program cannot place by eviction; nothing was measured")


def install(srv, cfg, seed, rows, seeded):
    from nomad_tpu import mock
    from nomad_tpu.state.matrix import PRIORITY_BUCKETS, priority_bucket
    from nomad_tpu.structs import Resources

    tier = cfg["tier"]
    n, shapes = cfg["nodes"], [tuple(s) for s in tier["shapes"]]
    totals = ref.node_totals(cfg["cluster"])

    prio = np.zeros((n, PRIORITY_BUCKETS, 3), np.float32)
    prio[:, priority_bucket(tier["production_priority"])] = seeded
    srv.matrix.set_usage(rows, seeded.astype(np.float32), prio)

    ns = tier["namespace"]
    srv.store.upsert_namespace(srv.next_index(), ns, "best-effort tier")
    per_shape = max(1, tier["jobs"] // len(shapes))
    dealt = deal(seeded, totals, shapes, tier["min_free_cpu"], seed)
    jobs, counts = {}, {}
    for s, (cpu, mem) in enumerate(shapes):
        for k in range(per_shape):
            job = mock.batch_job(priority=tier["priority"])
            job.id = job.name = f"tier-{s}-{k:03d}"
            job.namespace = ns
            job.datacenters = [
                f"dc{d + 1}" for d in range(cfg["cluster"]["datacenters"])]
            tg = job.task_groups[0]
            tg.name = "g"
            tg.tasks[0].name = "t"
            tg.tasks[0].resources = Resources(cpu=cpu, memory_mb=mem)
            jobs[(s, k)], counts[(s, k)] = job, 0
    placed, turn = [], [0] * len(shapes)
    for i, s in dealt:
        key = (s, turn[s] % per_shape)
        turn[s] += 1
        placed.append((i, key, counts[key]))
        counts[key] += 1
    for key, job in jobs.items():
        job.task_groups[0].count = max(1, counts[key])
        srv.store.upsert_job(srv.next_index(), job)

    state = {"namespace": ns, "priority": tier["priority"], "ids": [],
             "node": [], "job": [], "cpu": [], "memory_mb": [], "disk_mb": []}
    batch = []
    for i, key, idx in placed:
        job = jobs[key]
        node = srv.store.nodes[f"sim-node-{i:06d}"]
        a = mock.alloc(job, node, id=f"tier-{i:05d}-{key[0]}-{key[1]:03d}-{idx:04d}")
        a.name = f"{job.id}.g[{idx}]"
        batch.append(a)
        state["ids"].append(a.id)
        state["node"].append(i)
        state["job"].append(job.id)
        state["cpu"].append(a.resources.cpu)
        state["memory_mb"].append(a.resources.memory_mb)
        state["disk_mb"].append(a.resources.disk_mb)
        if len(batch) == CHUNK:
            srv.store.upsert_allocs(srv.next_index(), batch)
            batch = []
    if batch:
        srv.store.upsert_allocs(srv.next_index(), batch)

    host = srv.matrix.snapshot_host()
    free_cpu = host["totals"][rows, 0] - host["used"][rows, 0]
    state["nodes_with_room"] = int((free_cpu >= tier["min_free_cpu"]).sum())
    state["cpu_fill"] = float(
        host["used"][rows, 0].sum() / host["totals"][rows, 0].sum())
    state["jobs"] = len(jobs)
    probe(srv, cfg)
    return state
