"""Host-side constraint evaluation for non-vectorizable operators.

The kernels evaluate hash-equality, numeric and version predicates for every
node in one pass (ops/kernels.py). Operators that cannot vectorize — regexp,
set_contains, lexical ordering, multi-clause version ranges — escape here and
are evaluated **once per distinct value of the attribute's matrix column**
and broadcast over the nodes with numpy (``HostFeasibility``; the
reference's own optimization is the per-class cache: ComputedClass,
scheduler/feasible.go:1029, nomad/structs/node_class.go:28-37 — a value is
finer than a class and serves node-unique attributes too).  No eval walks
the matrix's nodes in Python; where an attribute has no column (the
registry is full) the stack falls back to a walk and counts it
(``nomad.sched.host_walk_nodes_total``).

Reference semantics: checkConstraint (feasible.go:793-858) and the operator
implementations at feasible.go:860-1020.
"""

from __future__ import annotations

import re
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from ..ops.encode import _resolve_attr_name
from ..state.matrix import node_attributes, stable_hash, version_value
from ..structs.types import Constraint, Node, Op

_regex_cache: Dict[str, Optional[re.Pattern]] = {}
_version_clause_re = re.compile(r"^\s*(>=|<=|>|<|=|!=|~>)?\s*v?([\d.]+)\s*$")


def attr_name(target: str) -> str:
    """``${attr.x}`` / ``${meta.y}`` / ``${node.class}`` -> the name the
    matrix's attribute registry knows it by (the encoder's resolution)."""
    return _resolve_attr_name(target) or ""


def _lookup_attr(node: Node, target: str) -> Optional[str]:
    """Resolve a constraint's target to a node's value
    (reference: resolveTarget, feasible.go:748-790)."""
    return node_attributes(node).get(attr_name(target)) or None


def _check_regexp(value: str, pattern: str) -> bool:
    compiled = _regex_cache.get(pattern)
    if pattern not in _regex_cache:
        try:
            compiled = re.compile(pattern)
        except re.error:
            compiled = None
        _regex_cache[pattern] = compiled
    return compiled is not None and compiled.search(value) is not None


def _check_version(value: str, spec: str) -> bool:
    """Constraint-style version check supporting comma-separated clauses
    (e.g. ``>= 1.0, < 2.0``). ``~>`` is pessimistic (same major, >= given)."""
    packed = version_value(value)
    if packed != packed:  # NaN
        return False
    for clause in spec.split(","):
        m = _version_clause_re.match(clause)
        if not m:
            return False
        op = m.group(1) or "="
        want = version_value(m.group(2))
        if want != want:
            return False
        if op == "~>":
            parts = m.group(2).split(".")
            major = float(int(parts[0]))
            if not (packed >= want and (packed // 1e6) == major):
                return False
        elif op == ">=" and not packed >= want:
            return False
        elif op == "<=" and not packed <= want:
            return False
        elif op == ">" and not packed > want:
            return False
        elif op == "<" and not packed < want:
            return False
        elif op == "=" and not packed == want:
            return False
        elif op == "!=" and not packed != want:
            return False
    return True


def check_constraint_host(con: Constraint, node: Node) -> bool:
    """Evaluate one escaped constraint against one node."""
    return check_constraint_value(con, _lookup_attr(node, con.l_target))


def check_constraint_value(con: Constraint, value: Optional[str]) -> bool:
    """Evaluate one escaped constraint against one value of its attribute
    (None = the node does not have it)."""
    operand = con.operand
    if operand == Op.IS_SET.value:
        return value is not None
    if operand == Op.IS_NOT_SET.value:
        return value is None

    if operand in (Op.NEQ.value, "not"):
        return value is None or value != con.r_target
    if value is None:
        return False

    if operand in (Op.EQ.value, "==", "is"):
        return value == con.r_target
    if operand == Op.REGEXP.value:
        return _check_regexp(value, con.r_target)
    if operand in (Op.VERSION.value, Op.SEMVER.value):
        return _check_version(value, con.r_target)
    if operand == Op.SET_CONTAINS.value:
        have = {p.strip() for p in value.split(",")}
        want = [p.strip() for p in con.r_target.split(",")]
        return all(w in have for w in want)
    if operand == Op.SET_CONTAINS_ANY.value:
        have = {p.strip() for p in value.split(",")}
        return any(p.strip() in have for p in con.r_target.split(","))
    # Lexical ordering fallback for non-numeric <, >, ... (feasible.go:918).
    if operand == Op.LT.value:
        return value < con.r_target
    if operand == Op.LTE.value:
        return value <= con.r_target
    if operand == Op.GT.value:
        return value > con.r_target
    if operand == Op.GTE.value:
        return value >= con.r_target
    return False


class HostFeasibility:
    """Escaped feasibility over the matrix's columns, for every node at
    once and cached across evals (one instance a matrix:
    ``NodeMatrix.host_feasibility``).

    A predicate is keyed by its CONTENT (target, operand, operand value),
    never by the job: every job of a deployment that carries the same rule
    shares one mask.  A mask is valid for one ``matrix.attr_version``; when
    that moves, only values not seen before are evaluated again (the
    verdicts per value id are kept), so a node registering costs a
    predicate one evaluation, not one per node.

    Counters (``server.py`` exposes them): ``predicates_evaluated`` — calls
    of ``check_constraint_value`` (one per distinct value on first sight);
    ``walked_nodes`` — nodes visited one by one in Python by the stack's
    fallback walk (``GenericStack._walk``): 0 where every attribute has a
    column; ``walked_classes`` — computed classes visited one by one in
    Python by a select (``GenericStack._class_eligibility``'s fallback over
    the classes' representatives): 0 likewise."""

    MAX_ENTRIES = 256

    def __init__(self, matrix):
        self.matrix = matrix
        self.predicates_evaluated = 0
        self.walked_nodes = 0
        self.walked_classes = 0
        self._masks: Dict[tuple, Tuple[int, np.ndarray]] = {}
        # predicate -> (value ids seen, sorted; their verdicts)
        self._verdicts: Dict[tuple, Tuple[np.ndarray, np.ndarray]] = {}

    def _cached(self, key: tuple, build) -> np.ndarray:
        version = self.matrix.attr_version  # read before the columns are
        hit = self._masks.get(key)
        if hit is not None and hit[0] == version:
            return hit[1]
        mask = build()
        mask.setflags(write=False)
        if len(self._masks) >= self.MAX_ENTRIES:
            self._masks.pop(next(iter(self._masks)), None)
        self._masks[key] = (version, mask)
        return mask

    def column(self, name: str) -> Optional[np.ndarray]:
        """(N,) i32 value id of every node for attribute ``name`` (0 = the
        node lacks it); None where the attribute has no column and nodes
        may still have it (the registry is full)."""
        attrs = self.matrix.attrs
        slot = attrs.lookup(name)
        host = self.matrix.snapshot_host()
        if slot is not None:
            return host["attr_hash"][:, slot]
        if len(attrs.slot_of) < attrs.slots:
            # No node ever registered it: absent everywhere.
            return np.zeros((host["attr_hash"].shape[0],), np.int32)
        return None

    def constraint_mask(self, con: Constraint) -> Optional[np.ndarray]:
        """(N,) bool — ``con`` on every node, evaluated once per distinct
        value of its column; None where the attribute has no column."""
        key = ("constraint", con.l_target, con.operand, con.r_target)
        if self.column(attr_name(con.l_target)) is None:
            return None
        return self._cached(key, lambda: self._evaluate(key, con))

    def _evaluate(self, key: tuple, con: Constraint) -> np.ndarray:
        col = self.column(attr_name(con.l_target))
        values, inverse = np.unique(col, return_inverse=True)
        seen, verdicts = self._verdicts.get(
            key, (np.zeros((0,), col.dtype), np.zeros((0,), bool))
        )
        new = values[~np.isin(values, seen)]
        if len(new):
            value_of = self.matrix.value_of
            fresh = np.fromiter(
                (check_constraint_value(
                    con, value_of.get(int(h)) if h else None) for h in new),
                bool, len(new),
            )
            self.predicates_evaluated += len(new)
            seen = np.concatenate([seen, new])
            order = np.argsort(seen, kind="stable")
            seen = seen[order]
            verdicts = np.concatenate([verdicts, fresh])[order]
            if len(self._verdicts) >= self.MAX_ENTRIES:
                self._verdicts.pop(next(iter(self._verdicts)), None)
            self._verdicts[key] = (seen, verdicts)
        return verdicts[np.searchsorted(seen, values)][inverse]

    def datacenter_mask(self, datacenters: Sequence[str]) -> np.ndarray:
        """(N,) bool — the node's datacenter is one of ``datacenters`` (a
        job with more of them than the request encodes)."""
        dcs = tuple(sorted(set(datacenters)))
        return self._cached(("dc",) + dcs, lambda: np.isin(
            self.column("node.datacenter"),
            np.array([stable_hash(dc) for dc in dcs], np.int32),
        ))

    def _rows_mask(self, rows: Dict[int, int], at_least: int) -> np.ndarray:
        mask = np.zeros((self.matrix.capacity,), bool)
        if rows:
            idx = np.fromiter(rows.keys(), np.int64, len(rows))
            cnt = np.fromiter(rows.values(), np.int64, len(rows))
            idx = idx[(cnt >= at_least) & (idx < mask.shape[0])]
            mask[idx] = True
        return mask

    def volume_mask(self, volumes: Sequence[str]) -> np.ndarray:
        """(N,) bool — the node exposes every host volume of ``volumes``
        (HostVolumeChecker, feasible.go:132)."""
        names = tuple(sorted(set(volumes)))
        index = self.matrix.volume_rows
        return self._cached(("volumes",) + names, lambda: np.logical_and.reduce(
            [self._rows_mask(index.get(v, {}), 1) for v in names]
        ))

    def device_mask(self, name: str, count: int) -> np.ndarray:
        """(N,) bool — the node carries ``count`` instances of device
        ``name`` (an ask the device registry had no slot for)."""
        index = self.matrix.device_rows
        return self._cached(
            ("device", name, int(count)),
            lambda: self._rows_mask(index.get(name, {}), int(count)),
        )

    def class_vector(self, cons: Sequence[Constraint], pad: int) -> Optional[np.ndarray]:
        """(pad,) bool by computed class id — every constraint of ``cons``
        holds on the class's nodes (their non-unique attributes are the
        class's); None where one of them has no column."""
        masks = [self.constraint_mask(c) for c in cons]
        if any(m is None for m in masks):
            return None
        key = ("classes", pad) + tuple(
            (c.l_target, c.operand, c.r_target) for c in cons
        )

        def build():
            ok = np.logical_and.reduce(masks)
            # (a registration can grow the matrix between the two reads)
            cid = self.matrix.snapshot_host()["class_id"][: len(ok)]
            live = (cid >= 0) & (cid < pad)
            elig = np.ones((pad,), bool)
            elig[cid[live]] = ok[: len(cid)][live]
            return elig

        return self._cached(key, build)
