"""The one general traffic generator: a traffic file + a seed -> operations.

A traffic mix is a data file, ``traffic/<name>.json`` (see README.md).  This
module turns it into the seeded sequence of operations the client sends and
the checker re-derives.  It is a pure function of (file, seed, seconds): no
clock, no JAX, nothing of the program.

Every seed gets the SAME multiset of job widths, tenants, types, shapes and
inter-arrival gaps, in another order: each attribute is a deck dealt in
exact proportion (largest remainder) and shuffled by the seed.  So the work
of a window does not depend on the seed, only its order does — which is
what lets runs on different seeds be compared.

Whether an operation registers a new job or a resident one AGAIN, unchanged,
is data too (README.md, "What one operation is"): ``register_again_fraction``
and ``resident_jobs``.  So is what a job's body asks for beyond cpu and
memory: a shape's ``disk_mb``, ``networks`` and ``devices`` (``job_payload``).
A file without these keys gives the operations and bodies it gave before
there were any.
"""

from __future__ import annotations

import json
import math
import os
import random
from typing import Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
BLOCK = 512  # closed-loop sequences are dealt in blocks of this many ops


def load(name: str) -> Dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as fh:
        t = json.load(fh)
    t["name"] = name
    return t


def zipf_weights(n: int, s: float) -> List[float]:
    w = [1.0 / (k ** s) for k in range(1, n + 1)]
    total = sum(w)
    return [x / total for x in w]


def deal(weights: List[float], n: int) -> List[int]:
    """``n`` indices in exact proportion to ``weights`` (largest
    remainder), unshuffled."""
    exact = [w * n for w in weights]
    counts = [int(math.floor(x)) for x in exact]
    by_remainder = sorted(
        range(len(weights)), key=lambda i: (exact[i] - counts[i], -i),
        reverse=True,
    )
    for i in by_remainder[: n - sum(counts)]:
        counts[i] += 1
    out: List[int] = []
    for i, c in enumerate(counts):
        out.extend([i] * c)
    return out


def _rng(seed: int, salt: str) -> random.Random:
    # A str seed is hashed with sha512 by random.Random: stable across
    # processes and good for seeds past 2**31.
    return random.Random(f"{seed}:{salt}")


def _shuffled(items: List, seed: int, salt: str) -> List:
    items = list(items)
    _rng(seed, salt).shuffle(items)
    return items


def _decks(t: Dict, n: int, seed: int, salt: str) -> List[Dict]:
    """``n`` operations' attributes, each attribute dealt and shuffled on
    its own."""
    tenants = ["default"] + [f"tenant-{i}" for i in range(1, t["tenants"])]
    ns = _shuffled(deal(zipf_weights(len(tenants), t["tenant_zipf_s"]), n),
                   seed, salt + "ns")
    width = _shuffled(deal(zipf_weights(t["max_width"], t["width_zipf_s"]), n),
                      seed, salt + "width")
    batch = _shuffled(
        deal([1.0 - t["batch_fraction"], t["batch_fraction"]], n),
        seed, salt + "type")
    n_shapes = len(t["shapes"])
    shape = _shuffled([i % n_shapes for i in range(n)], seed, salt + "shape")
    return [
        {
            "namespace": tenants[ns[i]],
            "width": width[i] + 1,
            "type": "batch" if batch[i] else "service",
            "priority": t["batch_priority"] if batch[i]
            else t["service_priority"],
            "shape": shape[i],
        }
        for i in range(n)
    ]


def namespaces(t: Dict) -> List[str]:
    return ["default"] + [f"tenant-{i}" for i in range(1, t["tenants"])]


def again_fraction(t: Dict) -> float:
    return float(t.get("register_again_fraction", 0) or 0)


def kinds(t: Dict, n: int, seed: int) -> List[int]:
    """0 (register a new job) or 1 (register a resident job again) for each
    of ``n`` operations: dealt in exact proportion per block of ``BLOCK``
    and shuffled by the seed, so every seed has the same count of each kind
    in every block."""
    f = again_fraction(t)
    out: List[int] = []
    for b in range(int(math.ceil(n / BLOCK))):
        m = min(BLOCK, n - b * BLOCK)
        out.extend(_shuffled(deal([1.0 - f, f], m), seed, f"kind{b}"))
    return out


def resident_ops(t: Dict, seed: int) -> List[Dict]:
    """The resident set: ``resident_jobs`` jobs of the mix's own decks,
    registered and placed before the window (run.py), which the window's
    ``again`` operations register again, unchanged."""
    ops = _decks(t, int(t.get("resident_jobs", 0)), seed, "resident")
    for i, op in enumerate(ops):
        op.update(due=None, i=i, job_id=f"res-{i:06d}")
    return ops


def schedule(t: Dict, seed: int, seconds: float) -> List[Dict]:
    """The operations of one window.

    Open loop: exactly ``round(rate * seconds)`` operations, their gaps the
    quantiles of the exponential distribution (a Poisson process with its
    sampling noise taken out), shuffled by the seed; ``due`` is seconds
    from the window's start.  Closed loop: a sequence long enough for any
    sustainable rate, ``due`` None; the client begins the next as one ends.

    With ``register_again_fraction`` each operation has a ``kind``: ``new``
    ones are dealt the mix's attributes as above (the decks cover the new
    operations alone, so their multiset stays the same for every seed); the
    k-th ``again`` of the run names resident job ``perm[k mod
    resident_jobs]`` (``perm`` a seeded permutation) and carries that job's
    attributes, so two registrations of one job are ``resident_jobs``
    operations apart or more.
    """
    if t["loop"] == "open":
        n = int(round(t["rate_per_s"] * seconds))
        gaps = [-math.log(1.0 - (k + 0.5) / n) for k in range(n)]
        gaps = _shuffled(gaps, seed, "gaps")
        scale = seconds / (sum(gaps) + 1.0)  # the last gap runs to the end
        salts = ["open"]
    else:
        n_blocks = int(math.ceil(t["max_rate_per_s"] * seconds / BLOCK)) + 1
        n = n_blocks * BLOCK
        salts = [f"closed{b}" for b in range(n_blocks)]
    again = again_fraction(t) > 0
    if again:
        kind = kinds(t, n, seed)
        resident = resident_ops(t, seed)
        if not resident:
            raise ValueError("register_again_fraction needs resident_jobs")
        perm = _shuffled(range(len(resident)), seed, "again")
    else:
        kind = [0] * n
    # The new operations' decks: one for an open loop's whole window, one a
    # block for a closed loop.
    per = n if t["loop"] == "open" else BLOCK
    ops, k_again = [], 0
    for b, salt in enumerate(salts):
        block = kind[b * per:(b + 1) * per]
        fresh = iter(_decks(t, block.count(0), seed, salt))
        for k in block:
            if k:
                r = perm[k_again % len(perm)]
                k_again += 1
                ops.append(dict(resident[r], kind="again", resident=r))
            else:
                ops.append(next(fresh))
                if again:
                    ops[-1]["kind"] = "new"
    at = 0.0
    for i, op in enumerate(ops):
        if t["loop"] == "open":
            at += gaps[i] * scale
            op["due"] = at
        else:
            op["due"] = None
        op["i"] = i
        if op.get("kind") != "again":
            op["job_id"] = f"op-{i:06d}"
    return ops


def warmup_ops(t: Dict) -> List[Dict]:
    """One operation of every shape class of the mix: type x shape x the
    narrowest and the widest job (every launch has the same static shapes,
    so the widths between them add no program)."""
    widths = sorted({1, t["max_width"]})
    ops = []
    for jtype, prio in (("service", t["service_priority"]),
                        ("batch", t["batch_priority"])):
        for shape in range(len(t["shapes"])):
            for w in widths:
                ops.append({
                    "namespace": "default", "width": w, "type": jtype,
                    "priority": prio, "shape": shape, "due": None,
                })
    for i, op in enumerate(ops):
        op["i"] = i
        op["job_id"] = f"warm-{i:04d}"
    return ops


def job_payload(t: Dict, op: Dict, prefix: str = "") -> Dict:
    """The job as PUT to /v1/jobs (the server's snake_case wire form): one
    task group ``g`` of ``width`` instances of the op's shape.

    A shape may carry three optional keys beside ``cpu`` and ``memory_mb``,
    each in the API's wire form (README.md, "Adding things"): ``disk_mb``
    (the group's ``ephemeral_disk`` size), ``networks`` (the group's
    ``network`` stanzas: ``reserved_ports`` and ``dynamic_ports`` labels,
    where ``nomad job init``'s example job has its one) and ``devices`` (the
    task's ``device`` asks: ``name``, ``count``).  A shape without them
    gives, byte for byte, the body it gave before there were any."""
    s = t["shapes"][op["shape"]]
    jid = prefix + op["job_id"]
    resources = {"cpu": s["cpu"], "memory_mb": s["memory_mb"]}
    if "devices" in s:
        resources["devices"] = [
            {"name": d["name"], "count": d.get("count", 1)}
            for d in s["devices"]]
    group = {
        "name": "g",
        "count": op["width"],
        "constraints": [dict(c) for c in s.get("constraints", [])],
        "affinities": [dict(a) for a in s.get("affinities", [])],
        "spreads": [dict(x) for x in s.get("spreads", [])],
        "tasks": [{
            "name": "t",
            "driver": "mock",
            "config": {"run_for": 0},
            "resources": resources,
        }],
    }
    if "disk_mb" in s:
        group["ephemeral_disk"] = {"size_mb": s["disk_mb"]}
    if "networks" in s:
        group["networks"] = [
            {"reserved_ports": list(n.get("reserved_ports", [])),
             "dynamic_ports": list(n.get("dynamic_ports", []))}
            for n in s["networks"]]
    return {
        "id": jid,
        "name": jid,
        "namespace": op["namespace"],
        "type": op["type"],
        "priority": op["priority"],
        "datacenters": list(s["datacenters"]),
        "task_groups": [group],
    }
