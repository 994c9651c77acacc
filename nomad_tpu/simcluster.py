"""Seeded synthetic clusters at the repo's headline deployment shape.

BASELINE.json's C2M configuration: N nodes in 4 datacenters, 6 node
classes and 32 racks, a third of them carrying the ``v5p`` accelerator
attribute and the rest ``v5e``, loaded with the aggregated usage of ~M
allocations (the matrix carries usage aggregates, the same thing AllocsFit
recomputes per call in the reference, funcs.go:97-150).  ``benchmark/run.py`` and
``chip_smoke.py`` both build their cluster here, always from the seed —
nothing is read from or written to disk.
"""

from __future__ import annotations

from typing import List, Tuple

import numpy as np

from . import mock
from .state.matrix import PRIORITY_BUCKETS, NodeMatrix, stable_hash
from .structs.types import Affinity, Constraint, Node, Op, Spread

DATACENTERS = 4
NODE_CLASSES = 6
RACKS = 32

# The attribute pattern of sim_node repeats every lcm(4, 6, 32, 3) = 96
# nodes; build_cluster encodes one period through the real upsert path and
# replicates it (only the node-unique columns differ per row).
SIM_PERIOD = 96

JOB_SHAPES = 8


def sim_node(i: int) -> Node:
    """Node ``i`` of the synthetic cluster (mock.node sized, attributes a
    pure function of ``i``)."""
    node = mock.node()
    node.datacenter = f"dc{i % DATACENTERS + 1}"
    node.node_class = f"class-{i % NODE_CLASSES}"
    node.attributes = dict(node.attributes)
    node.attributes["rack"] = f"r{i % RACKS}"
    node.attributes["platform.tpu.type"] = "v5e" if i % 3 else "v5p"
    return node


def sim_usage(
    totals: np.ndarray, n_allocs: int, seed: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Aggregated usage of ~``n_allocs`` allocations over the nodes whose
    comparable resources are ``totals`` (N, 3): returns ``used`` (N, 3) and
    ``prio_used`` (N, PRIORITY_BUCKETS, 3).

    Average alloc: ~100 MHz cpu / 128 MB mem / 30 MB disk, Poisson-many
    per node, capped at 75% of the node; spread over four priority buckets
    so the preemption tables see real data."""
    rng = np.random.default_rng(seed)
    n = totals.shape[0]
    usage = rng.poisson(n_allocs / n, n)[:, None] * np.array(
        [[100.0, 128.0, 30.0]]
    ) * rng.uniform(0.05, 0.12, (n, 1))
    usage = np.minimum(usage, totals * 0.75)
    shares = rng.dirichlet(np.ones(4), n)
    prio_used = np.zeros((n, PRIORITY_BUCKETS, 3), np.float32)
    for j, b in enumerate(rng.choice(PRIORITY_BUCKETS, 4, replace=False)):
        prio_used[:, b] = usage * shares[:, j : j + 1]
    return usage.astype(np.float32), prio_used


def build_cluster(
    n_nodes: int, capacity: int, n_allocs: int, seed: int = 42
) -> NodeMatrix:
    """The encoded cluster matrix, without a server around it: node ``i``
    sits in row ``i`` (rows past the first period under the id
    ``sim-node-<i>``)."""
    m = NodeMatrix(capacity=capacity)

    # Representatives go through the real upsert/encode path (correct
    # attribute slots, class ids, eligibility).
    reps = min(SIM_PERIOD, n_nodes)
    for i in range(reps):
        m.upsert_node(sim_node(i))

    host = m.snapshot_host()
    if n_nodes > reps:
        rows = np.arange(reps, n_nodes)
        src = rows % reps  # every modulus above divides SIM_PERIOD
        for key in (
            "totals", "used", "eligible", "attr_hash", "attr_num",
            "attr_ver", "class_id", "dev_total", "dev_used", "prio_used",
            "port_words", "dyn_used",
        ):
            host[key][rows] = host[key][src]
        # Node-unique columns must differ per row: re-hash the synthetic
        # node ids into the unique-attribute slots.
        ids = [f"sim-node-{int(r)}" for r in rows]
        id_hash = np.fromiter(
            (stable_hash(s) for s in ids), np.int32, len(ids)
        )
        m.value_of.update(zip(id_hash.tolist(), ids))
        for attr in ("node.unique.name", "node.unique.id"):
            slot = m.attrs.lookup(attr)
            if slot is not None:
                host["attr_hash"][rows, slot] = id_hash
        for r, node_id in zip(rows, ids):
            m.row_of[node_id] = int(r)
            m.node_of[int(r)] = node_id
        m._next_row = n_nodes

    rows = np.arange(n_nodes)
    m.set_usage(rows, *sim_usage(host["totals"][:n_nodes], n_allocs, seed))
    return m


def build_requests(m: NodeMatrix) -> List:
    """A mix of job shapes: plain binpack, affinity, spread, constrained."""
    from .ops.encode import RequestEncoder

    enc = RequestEncoder(m)
    shapes = []
    for i in range(JOB_SHAPES):
        job = mock.job()
        tg = job.task_groups[0]
        tg.tasks[0].resources.cpu = 100 + 50 * (i % 4)
        tg.tasks[0].resources.memory_mb = 128 + 64 * (i % 3)
        if i % 4 == 1:
            tg.affinities = [
                Affinity(l_target="${attr.platform.tpu.type}",
                         r_target="v5e", operand=Op.EQ.value, weight=50)
            ]
        if i % 4 == 2:
            tg.spreads = [Spread(attribute="${attr.rack}", weight=50)]
        if i % 4 == 3:
            tg.constraints = [
                Constraint(l_target="${attr.kernel.name}",
                           r_target="linux", operand=Op.EQ.value)
            ]
        shapes.append(enc.compile(job, tg).request)
    return shapes
