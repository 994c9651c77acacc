"""Jaxpr-level semantic pass: prove the device-kernel contracts from the
traced program, not the source text.

The AST rules (J001–J005) pattern-match call sites, which a one-helper
refactor evades (tests/test_lint.py documents the known J005 miss).  This
pass closes that hole by tracing every registered entry point in
:mod:`.contracts` to a ClosedJaxpr under a declared configuration grid
and walking the result:

* **J101** — no host-callback primitive (``io_callback``,
  ``pure_callback``, ``debug_callback``) anywhere inside a fused
  program.  A callback re-introduces the per-eval host round trip the
  megakernel exists to amortize.
* **J102** — total device→host output bytes per launch within the
  declared budget, and *independent of the node count* (traced at two N
  values, byte counts must match): the O(B·P)-bytes fetch contract.
* **J103** — no node-axis-sized value crossing a collective
  (``psum``/``pmax``/``pmin``/``all_gather``/…) or leaving the
  ``shard_map`` boundary, except declared exemptions: nothing
  N-shaped may be replicated, reduced, or fetched across the mesh.
* **J104** — the declared donation set actually reaches XLA: every
  operand declared donated is donated after ``lower()`` (and no operand
  is donated undeclared), and donation survives to the compiled
  executable.  ``expect_alias`` additionally requires an
  ``input_output_alias`` in the HLO — off for the current entries
  because no donated lane-operand aval matches the packed (B, P, 8)
  output, so XLA can reuse the buffers as scratch but never alias them.
* **J105** — compile-cache cardinality, measured from the real cache:
  the contract's concrete sweep (occupancy fills, pow2 dirty-row
  buckets) may cost at most ``max_compiles`` new cache entries.

A contract whose harness itself breaks (entry won't trace, operands
mismatch) surfaces as **J100** so the gate fails loudly instead of
silently skipping the entry.

Findings flow through the same ``(rule, path, symbol)`` baseline ratchet
as the AST passes; ``symbol`` is the contract name.  Everything is
gated on JAX importability — :func:`run` returns ``[]`` (with a stderr
notice under ``--jaxpr``) when no backend is present.
"""

from __future__ import annotations

import os
import re
import warnings
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple

from . import Finding, repo_root

__all__ = ["available", "check_contract", "run"]

# Primitive names that punch through to the host mid-program.
CALLBACK_PRIMS = frozenset(
    {"io_callback", "pure_callback", "debug_callback", "callback"}
)

# Cross-shard collectives (psum traces as psum_invariant under shard_map's
# varying-axes typing).  pvary is deliberately absent: it is replication
# bookkeeping, not data movement.
COLLECTIVE_PRIMS = frozenset(
    {
        "psum",
        "psum_invariant",
        "pmax",
        "pmin",
        "all_gather",
        "all_to_all",
        "reduce_scatter",
        "ppermute",
        "pgather",
    }
)


def available() -> bool:
    """True when JAX imports and a backend initializes."""
    try:
        import jax

        jax.devices()
        return True
    except Exception:
        return False


# ---------------------------------------------------------------------------
# Jaxpr walking
# ---------------------------------------------------------------------------


def _subjaxprs(v: Any) -> Iterator[Any]:
    if hasattr(v, "jaxpr"):  # ClosedJaxpr
        yield v.jaxpr
    elif hasattr(v, "eqns"):  # raw Jaxpr (shard_map, custom_* params)
        yield v
    elif isinstance(v, (list, tuple)):
        for x in v:
            yield from _subjaxprs(x)


def iter_eqns(jaxpr: Any) -> Iterator[Any]:
    """Every equation, recursively through pjit/scan/cond/shard_map/… ."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _subjaxprs(v):
                yield from iter_eqns(sub)


def _aval_bytes(aval: Any) -> int:
    n = 1
    for d in aval.shape:
        n *= int(d)
    return n * aval.dtype.itemsize


def _shapes(eqn: Any) -> List[Tuple[int, ...]]:
    out = []
    for var in list(eqn.invars) + list(eqn.outvars):
        aval = getattr(var, "aval", None)
        shape = getattr(aval, "shape", None)
        if shape is not None:
            out.append(tuple(int(d) for d in shape))
    return out


# ---------------------------------------------------------------------------
# The checks
# ---------------------------------------------------------------------------


def _def_line(root: str, relpath: str, name: str) -> int:
    """Line of ``def name`` / ``name =`` so findings are clickable."""
    try:
        with open(os.path.join(root, relpath)) as fh:
            src = fh.read()
    except OSError:
        return 1
    m = re.search(
        rf"^(?:def {re.escape(name)}\b|{re.escape(name)}\s*=)", src, re.M
    )
    return src[: m.start()].count("\n") + 1 if m else 1


def _trace(entry: Callable[..., Any], args: Tuple[Any, ...],
           kwargs: Dict[str, Any]) -> Any:
    import functools

    import jax

    return jax.make_jaxpr(functools.partial(entry, **kwargs))(*args)


def _positional_args_info(lowered: Any, n_args: int) -> Sequence[Any]:
    """``lowered.args_info`` subtree per positional arg (statics are
    keyword-only for every registered entry, so positions line up)."""
    info = lowered.args_info
    if (
        isinstance(info, tuple)
        and len(info) == 2
        and isinstance(info[1], dict)
        and len(info[0]) == n_args
    ):
        return info[0]
    return info


def _check_traced(c: Any, g: Any, closed: Any, emit: Callable[[str, str], None]) -> int:
    """J101 + J103 on one traced grid point; returns the output bytes
    (J102 budget/independence is judged across grid points by the
    caller)."""
    callbacks = sorted(
        {e.primitive.name for e in iter_eqns(closed.jaxpr)
         if e.primitive.name in CALLBACK_PRIMS}
    )
    if callbacks:
        emit(
            "J101",
            f"host callback primitive(s) {callbacks} inside the fused "
            f"program at grid {g!r} — every launch would round-trip to "
            "the host",
        )

    marker = int(g.nodes)
    flagged: set = set()
    for eqn in iter_eqns(closed.jaxpr):
        name = eqn.primitive.name
        if name in COLLECTIVE_PRIMS:
            for shape in _shapes(eqn):
                if marker in shape and shape not in c.boundary_exempt_shapes:
                    key = (name, shape)
                    if key not in flagged:
                        flagged.add(key)
                        emit(
                            "J103",
                            f"collective '{name}' moves a node-axis value "
                            f"of shape {shape} (N={marker}) across the mesh "
                            f"at grid {g!r} — only scalars and lane-sized "
                            "values may cross (kernels.elect)",
                        )
        elif name == "shard_map" and not c.node_axis_outputs_ok:
            for var in eqn.outvars:
                aval = getattr(var, "aval", None)
                shape = tuple(int(d) for d in getattr(aval, "shape", ()))
                if marker in shape and shape not in c.boundary_exempt_shapes:
                    emit(
                        "J103",
                        f"shard_map output of shape {shape} (N={marker}) "
                        f"escapes the mesh boundary at grid {g!r}",
                    )
    return sum(_aval_bytes(a) for a in closed.out_avals)


def _check_donation(c: Any, emit: Callable[[str, str], None]) -> None:
    import jax

    g = c.compile_grid
    entry = c.build(g)
    args = c.operands(g)
    lowered = entry.lower(*args, **c.static_kwargs(g))
    declared = set(c.donated_args)
    pos_info = _positional_args_info(lowered, len(args))
    for i in range(len(args)):
        leaves = jax.tree_util.tree_leaves(pos_info[i])
        donated = [bool(getattr(leaf, "donated", False)) for leaf in leaves]
        if i in declared and not all(donated):
            emit(
                "J104",
                f"operand {i} is declared donated but lowered with "
                f"{donated.count(False)}/{len(donated)} leaves undonated — "
                "the donation was dropped before reaching XLA",
            )
        if i not in declared and any(donated):
            emit(
                "J104",
                f"operand {i} is donated but not declared in the contract "
                "— in-flight dispatches sharing that buffer would read "
                "freed memory",
            )
    kw_info = lowered.args_info[1] if (
        isinstance(lowered.args_info, tuple) and len(lowered.args_info) == 2
        and isinstance(lowered.args_info[1], dict)
    ) else {}
    for name, info in sorted(kw_info.items()):
        donated = [
            bool(getattr(leaf, "donated", False))
            for leaf in jax.tree_util.tree_leaves(info)
        ]
        if name in c.donated_kwargs and not all(donated):
            emit(
                "J104",
                f"keyword operand '{name}' is declared donated but lowered "
                f"with {donated.count(False)}/{len(donated)} leaves undonated",
            )
        if name not in c.donated_kwargs and any(donated):
            emit(
                "J104",
                f"keyword operand '{name}' is donated but not declared in "
                "the contract",
            )
    if not declared and not c.donated_kwargs:
        return
    with warnings.catch_warnings(record=True):
        warnings.simplefilter("always")
        compiled = lowered.compile()
    compiled_donated = tuple(getattr(compiled, "donate_argnums", ()) or ())
    if not compiled_donated:
        emit(
            "J104",
            "declared donation set vanished between lower() and compile() "
            "— XLA sees no donated operands",
        )
    if c.expect_alias and "input_output_alias" not in compiled.as_text():
        emit(
            "J104",
            "contract requires input_output_alias but the compiled HLO has "
            "none — every donated buffer fell back to copy",
        )


def check_contract(c: Any, root: Optional[str] = None) -> List[Finding]:
    """Run J101–J105 for one :class:`.contracts.DeviceContract` row."""
    root = root or repo_root()
    line = _def_line(root, c.path, c.name)
    findings: List[Finding] = []

    def emit(rule: str, msg: str) -> None:
        f = Finding(rule=rule, path=c.path, line=line, symbol=c.name, message=msg)
        if f not in findings:
            findings.append(f)

    try:
        bytes_by_nodes: Dict[Tuple[Any, ...], Dict[int, int]] = {}
        for g in c.trace_grids:
            entry = c.build(g)
            closed = _trace(entry, c.operands(g), c.static_kwargs(g))
            out_bytes = _check_traced(c, g, closed, emit)
            if c.out_budget is None:
                continue
            budget = int(c.out_budget(g))
            resident = int(c.resident_out(g)) if c.resident_out else 0
            if out_bytes - resident > budget:
                emit(
                    "J102",
                    f"launch returns {out_bytes - resident} B to the host at grid "
                    f"{g!r}, over the declared budget of {budget} B",
                )
            # Node-count independence: same grid modulo N must cost the
            # same bytes.
            key = (g.batch, g.placements, g.deltas, g.live, g.features)
            bytes_by_nodes.setdefault(key, {})[int(g.nodes)] = out_bytes
        for key, by_n in bytes_by_nodes.items():
            if len(set(by_n.values())) > 1:
                emit(
                    "J102",
                    "device→host bytes depend on the node count "
                    f"({ {n: b for n, b in sorted(by_n.items())} }) — an "
                    "O(N) value is being fetched to the host",
                )

        if c.compile_grid is not None:
            _check_donation(c, emit)

        if c.sweep is not None and c.max_compiles is not None:
            entry = c.build(c.compile_grid)
            measured = int(c.sweep(entry, c))
            if measured > c.max_compiles:
                emit(
                    "J105",
                    f"configuration sweep cost {measured} compile-cache "
                    f"entries, over the declared max of {c.max_compiles} — "
                    "a runtime value leaked into the static key",
                )
    except Exception as exc:  # noqa: BLE001 — surface as a finding, loudly
        emit(
            "J100",
            f"contract harness failed: {type(exc).__name__}: {exc}",
        )
    return findings


def run(root: Optional[str] = None) -> List[Finding]:
    """All contracts; ``[]`` when no JAX backend is importable."""
    if not available():
        return []
    from . import contracts

    root = root or repo_root()
    findings: List[Finding] = []
    for c in contracts.table():
        findings += check_contract(c, root=root)
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings
