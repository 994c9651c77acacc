"""The plain reference for jobs that carry placement rules (PR 44).

numpy + ``re``, float64, nothing of ``nomad_tpu``: what Nomad v1.1.3's
``scheduler/feasible.go`` (checkConstraint :793-858, the operators to :1020,
DistinctHostsIterator :505, DistinctPropertyIterator :604) and
``scheduler/spread.go`` define, for every operator the ``rules-backlog``
traffic uses, on the configuration's own statement of the cluster
(``cluster.rule_attributes``).  ``reference.py`` keeps what it shares with
every cell: the node totals, ``has_room``, ScoreFit.

* ``attr_tables`` — every attribute a rule reads, column-wise, "" = the node
  does not have it.
* ``match`` / ``eligible`` — ``=``, ``!=``, ``is_set``, ``is_not_set``,
  ``version`` (comma-separated clauses), ``regexp``, ``set_contains``; a
  predicate is evaluated once per distinct value and broadcast.
* ``affinity_term`` — sum of matched weights over the sum of absolute
  weights, negative weights included (rank.go:698-728).
* ``spread_boost`` — the allocation-spread term of one node in one state of
  the job's own earlier allocations: ``percent`` targets, the implicit
  target, even spread (spread.go:110-230).
* ``final_score`` — the mean of the terms that apply (rank.go:737-771).
* ``distinct_hosts_violations`` / ``distinct_property_violations`` — over
  a job's live allocations as read back.

Departures from the reference implementation, each on purpose:

1. ``version`` compares (major, minor, patch) as integers; go-version's
   pre-release and metadata ordering is not modelled (no attribute of the
   configuration has either).
2. ``regexp`` is Python's ``re.search``; RE2 and ``re`` agree on the
   character classes and anchors the traffic uses.
3. A spread target's desired count (``percent`` / 100 x count) is rounded
   to the precision asked for before the difference to the used count is
   taken (the configuration states float32): a target met exactly then
   reads 0 in both, and the term is left out of the mean in both.
4. An attribute the configuration gives as "" counts as not set
   (Nomad's ``meta`` can hold an empty string; the clients of this cluster
   do not fingerprint one).
"""

from __future__ import annotations

import re
from typing import Dict, List, Sequence

import numpy as np

import reference as ref

DISTINCT = ("distinct_hosts", "distinct_property")


# -- the cluster as the configuration states it -------------------------------

def node_attribute(spec: Dict, i: int) -> str:
    """Value of one of ``cluster.rule_attributes`` on node ``i``: a
    constant (``value``), a cycle over the index (``cycle``), or a format
    of the index, modulo ``period`` where there is one."""
    if "value" in spec:
        return spec["value"]
    if "cycle" in spec:
        return spec["cycle"][i % len(spec["cycle"])]
    return spec["format"].format(i % spec["period"] if "period" in spec else i)


def expected_attributes(i: int, cluster: Dict) -> Dict[str, str]:
    """name -> value of every rule attribute node ``i`` has ("" left out)."""
    out = {s["name"]: node_attribute(s, i) for s in cluster["rule_attributes"]}
    return {k: v for k, v in out.items() if v != ""}


def attr_tables(n_nodes: int, cluster: Dict) -> Dict[str, np.ndarray]:
    """``${name}`` -> (N,) array of str, for ``reference.attr_tables``'s
    four and every rule attribute."""
    tables = dict(ref.attr_tables(n_nodes, cluster))
    for spec in cluster["rule_attributes"]:
        tables["${" + spec["name"] + "}"] = np.array(
            [node_attribute(spec, i) for i in range(n_nodes)], dtype=object
        ).astype(str)
    return tables


# -- constraint operators -------------------------------------------------------

_CLAUSE = re.compile(r"^\s*(>=|<=|!=|>|<|=)?\s*v?(\d+(?:\.\d+)*)\s*$")


def _version(text: str):
    m = re.match(r"^\s*v?(\d+(?:\.\d+)*)", text)
    if not m:
        return None
    parts = [int(p) for p in m.group(1).split(".")][:3]
    return tuple(parts + [0] * (3 - len(parts)))


def _version_ok(value: str, spec: str) -> bool:
    have = _version(value)
    if have is None:
        return False
    for clause in spec.split(","):
        m = _CLAUSE.match(clause)
        if not m:
            return False
        op, want = m.group(1) or "=", _version(m.group(2))
        if not {
            ">=": have >= want, "<=": have <= want, ">": have > want,
            "<": have < want, "=": have == want, "!=": have != want,
        }[op]:
            return False
    return True


def _holds(value: str, operand: str, want: str) -> bool:
    """One constraint on one value; "" = the node does not have the
    attribute (checkConstraint: only ``!=`` and ``is_not_set`` pass then)."""
    if operand == "is_set":
        return value != ""
    if operand == "is_not_set":
        return value == ""
    if operand in ("!=", "not"):
        return value == "" or value != want
    if value == "":
        return False
    if operand in ("=", "==", "is"):
        return value == want
    if operand in ("version", "semver"):
        return _version_ok(value, want)
    if operand == "regexp":
        return re.search(want, value) is not None
    if operand == "set_contains":
        have = {p.strip() for p in value.split(",")}
        return all(w.strip() in have for w in want.split(","))
    raise NotImplementedError(f"constraint operand {operand!r}")


def match(values: np.ndarray, operand: str, want: str) -> np.ndarray:
    """(N,) bool: the predicate on every node, once per distinct value."""
    distinct, inverse = np.unique(values, return_inverse=True)
    verdict = np.array([_holds(str(v), operand, want) for v in distinct], bool)
    return verdict[inverse]


def column(tables: Dict, target: str) -> np.ndarray:
    """The attribute column a target names; all "" where the configuration
    states no such attribute."""
    if target in tables:
        return tables[target]
    return np.full(len(tables["${node.class}"]), "", dtype=str)


def eligible(tables: Dict, datacenters, constraints) -> np.ndarray:
    """(N,) bool: the job's datacenters and every constraint that is a
    predicate on the node (``distinct_*`` are held on the allocations)."""
    ok = np.isin(tables["${node.datacenter}"], list(datacenters))
    for c in constraints:
        if c["operand"] not in DISTINCT:
            ok &= match(column(tables, c["l_target"]), c["operand"],
                        c["r_target"])
    return ok


def affinity_term(tables: Dict, affinities) -> np.ndarray:
    """(N,) sum of matched weights / sum of absolute weights; 0 where the
    sum is 0 (then it is no term of the mean)."""
    n = len(tables["${node.class}"])
    total, norm = np.zeros(n, np.float64), 0.0
    for a in affinities:
        total += a["weight"] * match(
            column(tables, a["l_target"]), a["operand"], a["r_target"])
        norm += abs(a["weight"])
    return total / norm if norm else total


# -- distinct_hosts, distinct_property ------------------------------------------

def distinct_limit(constraint: Dict) -> int:
    r = str(constraint.get("r_target", ""))
    return int(r) if r.isdigit() else 1


def distinct_hosts_violations(rows: Sequence[int]) -> int:
    """Live allocations of one group beyond the first on a node."""
    return len(rows) - len(set(rows))


def distinct_property_violations(tables: Dict, constraint: Dict,
                                 rows: Sequence[int]) -> int:
    """Live allocations of one job beyond ``limit`` on a value of the
    property, and those on a node without it."""
    values = column(tables, constraint["l_target"])[list(rows)]
    limit = distinct_limit(constraint)
    held: Dict[str, int] = {}
    for v in values:
        held[str(v)] = held.get(str(v), 0) + 1
    return held.pop("", 0) + sum(max(0, k - limit) for k in held.values())


def blocked_by_distinct_property(tables: Dict, constraints,
                                 other_rows: Sequence[int]) -> np.ndarray:
    """(N,) bool: nodes a distinct_property may have closed to one alloc of
    a job whose OTHER allocs sit on ``other_rows`` (had they all come
    first): a value held ``limit`` times or more, or no value at all."""
    n = len(tables["${node.class}"])
    blocked = np.zeros(n, bool)
    for c in constraints:
        if c["operand"] != "distinct_property":
            continue
        values = column(tables, c["l_target"])
        held, counts = np.unique(values[list(other_rows)], return_counts=True)
        full = held[counts >= distinct_limit(c)]
        blocked |= (values == "") | np.isin(values, full)
    return blocked


# -- spread ------------------------------------------------------------------------

def spread_boost(spreads: List[Dict], count: int, values: Sequence[str],
                 held: Sequence[Dict[str, int]], dtype=np.float64):
    """The allocation-spread term of one node (spread.go Next): ``values[k]``
    the node's value of spread k's attribute, ``held[k]`` value -> allocs
    of the group placed before this one."""
    sum_weights = dtype(sum(s["weight"] for s in spreads))
    total = dtype(0.0)
    for s, value, use in zip(spreads, values, held):
        if value == "":
            total = dtype(total - dtype(1.0))
            continue
        if not s.get("targets"):
            total = dtype(total + _even_boost(value, use, dtype))
            continue
        desired = {
            t["value"]: dtype(t["percent"] / 100.0 * count)
            for t in s["targets"]}
        wanted = float(sum(
            t["percent"] / 100.0 * count for t in s["targets"]))
        want = desired.get(value)
        if want is None and 0.0 < wanted < count:
            want = dtype(count - wanted)  # the implicit target
        if want is None:
            total = dtype(total - dtype(1.0))
            continue
        used = dtype(use.get(value, 0) + 1)
        weight = dtype(dtype(s["weight"]) / sum_weights)
        total = dtype(total + dtype(dtype(dtype(want - used) / want) * weight))
    return total


def _even_boost(value: str, use: Dict[str, int], dtype):
    """evenSpreadScoreBoost (spread.go:178-230)."""
    counts = [c for c in use.values() if c > 0]
    if not counts:
        return dtype(0.0)
    low, high = dtype(min(counts)), dtype(max(counts))
    current = dtype(use.get(value, 0))
    if current != low:
        return dtype(dtype(low - current) / low)
    if low == high:
        return dtype(-1.0)
    return dtype(dtype(high - low) / low)


# -- the mean of the terms ----------------------------------------------------------

def final_score(binpack, collisions, desired_count, affinity, spread,
                dtype=np.float64):
    """``reference.final_score`` with the allocation-spread term: binpack
    always; job anti-affinity where the job already has instances on the
    node; affinity and spread where they are not 0."""
    b = np.asarray(binpack, dtype)
    c = np.asarray(collisions, dtype)
    aff = np.asarray(affinity, dtype)
    spr = np.asarray(spread, dtype)
    aa = np.where(c > 0, -(c + dtype(1)) / dtype(desired_count), dtype(0))
    n = (dtype(1) + (c > 0).astype(dtype) + (aff != 0).astype(dtype)
         + (spr != 0).astype(dtype))
    return ((b + aa.astype(dtype) + aff + spr) / n).astype(dtype)
