"""The plain reference: what a correct scheduler may answer, by plain sums.

Shares no code with ``nomad_tpu`` and imports nothing of it.  Everything
here works on what was read back over HTTP (allocations, node stubs, a
sample of whole nodes), on the configuration file's statement of the
cluster's shape, and on the seeded usage the harness installed.

* ``expected_node`` — node ``i`` of the cluster as the configuration states
  it (datacenter, class, rack, accelerator, resources).
* ``eligible`` — does a node satisfy a job's datacenters and constraints.
* ``overcommitted`` / ``has_room`` — per node, seeded usage + live
  allocations against the totals; each leaves the band that float32 sums
  cannot decide (``fit_slack``) to the side that raises no false alarm.
* ``binpack_score`` / ``final_score`` — Nomad's ScoreFit (20 - 10^freeCpu -
  10^freeMem, over 18), the job anti-affinity and node-affinity terms and
  their mean, in the precision asked for (float64 for the reference;
  float32 is what the program states; bfloat16 is the control).
"""

from __future__ import annotations

import numpy as np

DIMS = ("cpu", "memory_mb", "disk_mb")


# -- the cluster as the configuration states it ------------------------------

def expected_node(i: int, cluster: dict) -> dict:
    """Attributes of node ``i``: every attribute cycles with its own
    period, as the configuration's ``cluster`` block says."""
    return {
        "datacenter": f"dc{i % cluster['datacenters'] + 1}",
        "node_class": f"class-{i % cluster['node_classes']}",
        "attributes": {
            "rack": f"r{i % cluster['racks']}",
            "platform.tpu.type": "v5e" if i % 3 else "v5p",
        },
    }


def node_totals(cluster: dict) -> np.ndarray:
    """Schedulable (cpu, memory_mb, disk_mb) of one node: resources less
    what the node reserves for itself."""
    res, rsv = cluster["node_resources"], cluster["node_reserved"]
    return np.array([res[d] - rsv.get(d, 0) for d in DIMS], np.float64)


def attr_tables(n_nodes: int, cluster: dict) -> dict:
    """Column-wise attributes of all nodes, for full-width evaluation."""
    idx = np.arange(n_nodes)
    return {
        "${node.datacenter}": np.array(
            [f"dc{k + 1}" for k in range(cluster["datacenters"])]
        )[idx % cluster["datacenters"]],
        "${node.class}": np.array(
            [f"class-{k}" for k in range(cluster["node_classes"])]
        )[idx % cluster["node_classes"]],
        "${attr.rack}": np.array(
            [f"r{k}" for k in range(cluster["racks"])]
        )[idx % cluster["racks"]],
        "${attr.platform.tpu.type}": np.where(idx % 3 != 0, "v5e", "v5p"),
    }


def _match(values: np.ndarray, operand: str, want: str) -> np.ndarray:
    if operand in ("=", "==", "is"):
        return values == want
    if operand in ("!=", "not"):
        return values != want
    raise NotImplementedError(f"constraint operand {operand!r}")


def eligible(tables: dict, datacenters, constraints) -> np.ndarray:
    """(N,) bool: the nodes a job with these datacenters and hard
    constraints may be placed on."""
    ok = np.isin(tables["${node.datacenter}"], list(datacenters))
    for c in constraints:
        ok &= _match(tables[c["l_target"]], c["operand"], c["r_target"])
    return ok


def affinity_term(tables: dict, affinities) -> np.ndarray:
    """(N,) normalised affinity score: sum of matched weights over the sum
    of absolute weights; 0 where nothing matched (then it is no term)."""
    n = len(tables["${node.class}"])
    total = np.zeros(n, np.float64)
    norm = 0.0
    for a in affinities:
        total += a["weight"] * _match(
            tables[a["l_target"]], a["operand"], a["r_target"]
        )
        norm += abs(a["weight"])
    return total / norm if norm else total


# -- guarantees, by plain sums ---------------------------------------------------

def usage_after(used0: np.ndarray, allocs, row_of: dict) -> np.ndarray:
    """Seeded usage plus the resources of the live allocations."""
    used = used0.astype(np.float64).copy()
    for a in allocs:
        r = a["resources"]
        used[row_of[a["node_id"]]] += (r["cpu"], r["memory_mb"], r["disk_mb"])
    return used


def fit_slack(totals: np.ndarray) -> np.ndarray:
    """What float32 sums near a node's totals cannot decide: 64 roundings
    of a number of that size (0.03 MHz of 3,900; an allocation asks 100).
    The configuration states float32, so the program's own sums, which
    decide what fits, may differ from the float64 sums here by that."""
    return np.asarray(totals, np.float64) * 64 * 2.0 ** -23


def overcommitted(used: np.ndarray, totals: np.ndarray) -> np.ndarray:
    """Rows whose usage exceeds the node's totals in any dimension, beyond
    what float32 sums can decide."""
    return np.nonzero((used > totals + fit_slack(totals)).any(axis=1))[0]


def has_room(used: np.ndarray, ask, totals: np.ndarray, sure: bool = True):
    """(N,) bool: ``ask`` still fits on the node.  ``sure``: beyond what
    float32 sums can decide, so that a node the program rightly saw as full
    is never taken for one it passed over.  ``sure`` False: the loose
    reading (float64 sums + 1e-3) that PR 23's first check used, kept for
    the run's diagnostics."""
    ask = np.asarray(ask, np.float64)
    if sure:
        return (used + ask <= totals - fit_slack(totals)).all(axis=1)
    return (used + ask <= totals + 1e-3).all(axis=1)


# -- scores ------------------------------------------------------------------------

def binpack_score(used, ask, totals, dtype=np.float64):
    """ScoreFit of placing ``ask`` on nodes with usage ``used`` (..., 3)
    and schedulable ``totals``; only cpu and memory enter.  Every step is
    rounded to ``dtype``, as a kernel computing in it would."""
    used = np.asarray(used, dtype)
    ask = np.asarray(ask, dtype)
    totals = np.asarray(totals, dtype)
    one, ten = dtype(1.0), dtype(10.0)
    util = (used + ask).astype(dtype)
    free = (one - (util / np.maximum(totals, one)).astype(dtype)).astype(dtype)
    total = (
        np.power(ten, free[..., 0]).astype(dtype)
        + np.power(ten, free[..., 1]).astype(dtype)
    ).astype(dtype)
    score = np.clip((dtype(20.0) - total).astype(dtype), dtype(0), dtype(18))
    return (score / dtype(18.0)).astype(dtype)


def final_score(binpack, collisions, desired_count, affinity,
                dtype=np.float64):
    """Mean of the terms that apply: binpack always; job anti-affinity
    -(collisions + 1) / count where the job already has instances on the
    node; node affinity where something matched."""
    b = np.asarray(binpack, dtype)
    c = np.asarray(collisions, dtype)
    aff = np.asarray(affinity, dtype)
    aa = np.where(c > 0, -(c + dtype(1)) / dtype(desired_count), dtype(0))
    n = dtype(1) + (c > 0).astype(dtype) + (aff != 0).astype(dtype)
    return ((b + aa.astype(dtype) + aff) / n).astype(dtype)
