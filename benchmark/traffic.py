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


def schedule(t: Dict, seed: int, seconds: float) -> List[Dict]:
    """The operations of one window.

    Open loop: exactly ``round(rate * seconds)`` operations, their gaps the
    quantiles of the exponential distribution (a Poisson process with its
    sampling noise taken out), shuffled by the seed; ``due`` is seconds
    from the window's start.  Closed loop: a sequence long enough for any
    sustainable rate, ``due`` None; the client begins the next as one ends.
    """
    if t["loop"] == "open":
        n = int(round(t["rate_per_s"] * seconds))
        gaps = [-math.log(1.0 - (k + 0.5) / n) for k in range(n)]
        gaps = _shuffled(gaps, seed, "gaps")
        scale = seconds / (sum(gaps) + 1.0)  # the last gap runs to the end
        ops = _decks(t, n, seed, "open")
        at = 0.0
        for op, g in zip(ops, gaps):
            at += g * scale
            op["due"] = at
    else:
        n_blocks = int(math.ceil(t["max_rate_per_s"] * seconds / BLOCK)) + 1
        ops = []
        for b in range(n_blocks):
            ops.extend(_decks(t, BLOCK, seed, f"closed{b}"))
        for op in ops:
            op["due"] = None
    for i, op in enumerate(ops):
        op["i"] = i
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
    task group ``g`` of ``width`` instances of the op's shape."""
    s = t["shapes"][op["shape"]]
    jid = prefix + op["job_id"]
    return {
        "id": jid,
        "name": jid,
        "namespace": op["namespace"],
        "type": op["type"],
        "priority": op["priority"],
        "datacenters": list(s["datacenters"]),
        "task_groups": [{
            "name": "g",
            "count": op["width"],
            "constraints": [dict(c) for c in s.get("constraints", [])],
            "affinities": [dict(a) for a in s.get("affinities", [])],
            "spreads": [dict(x) for x in s.get("spreads", [])],
            "tasks": [{
                "name": "t",
                "driver": "mock",
                "config": {"run_for": 0},
                "resources": {"cpu": s["cpu"], "memory_mb": s["memory_mb"]},
            }],
        }],
    }
