"""JAX hot-path pass (rules J001–J005).

The live dispatch path stays fast only while two disciplines hold: no
implicit device→host sync outside the resolver thread (each one blocks
until the device is done and collapses the pipeline overlap), and no
recompilation surprises (jit tracing captures, static-arg hashing).
This pass enforces both lexically over ``ops/``, ``parallel/``,
``scheduler/coalescer.py`` and ``state/matrix.py``:

* **J001 host sync on a device value** — a name assigned from a
  device-producing call (``kernels.*``, ``jnp.*``, ``jax.jit``-wrapped
  fns, the sharded dispatch) later hits ``np.asarray``/``float``/
  ``int``/``.item()``/``.tolist()``/``.block_until_ready()`` — or a
  device-producing call is fed to one directly.  The designated
  resolver-thread fetch is a baseline exemption, not a rule carve-out,
  so moving it shows up in review.
* **J002 jit-captured mutable global** — a ``@jax.jit`` function reads a
  module-level name bound to a list/dict/set: tracing freezes its value
  at first call, so later mutation silently diverges (and a rebind
  retriggers a trace per identity).
* **J003 non-hashable static arg** — a call to a jit-with-
  ``static_argnames`` function passes a list/dict/set display (directly
  or via a local) to a static parameter, or the jitted function declares
  a mutable default for one: static args key the compile cache by
  hash/eq, so each call raises or recompiles.
* **J004 per-eval recompile trigger on the fused path** — a call to the
  mega-batched fused entry points (``fused_place_batch`` /
  ``fused_place_batch_live``) feeds them a shape-polymorphic operand
  (``np.stack``/``jnp.asarray`` over a comprehension, or a
  ``tree_map``-stacked pytree, whose leading dim tracks the batch
  occupancy) or derives a static arg from the batch (``len(batch)``,
  ``x.shape[...]``).  Either way the "one compile serves every
  occupancy" contract breaks and each distinct batch size pays a full
  XLA compile mid-dispatch.  Preallocate a ``(B, ...)`` operand slab
  (``ops.encode.RequestSlab``), mark dead lanes with ``lane_steps`` 0, and
  keep static args bound to configuration constants.
* **J005 node-axis fetch at a fused/sharded call site** — a function that
  drives the fused or node-sharded dispatch entry points
  (``fused_place_batch[_live]`` / ``sharded_fused_place_batch``) also
  fetches a node-axis-shaped value to host: a sync sink
  (``np.asarray``/``.block_until_ready()``/…) applied to a
  ``DeviceArrays`` leaf (``arrays.used``, ``.totals``, ``.attr_hash``,
  …) or a node-shaped ``PlacementResult`` field (``used_after``,
  ``tg_count_after``).  The sharded megabatch contract
  (parallel/sharding.py) is that only the packed (B, P, 8) winner block
  ever crosses the device→host boundary; an (…, N) fetch reintroduces
  O(nodes) host traffic per dispatch and scales with cluster size —
  exactly what the election across shards (``kernels.elect``) exists to
  prevent.
"""

from __future__ import annotations

import ast
import os
from typing import Dict, List, Optional, Set, Tuple

from . import Finding

SCAN_DIRS = ("ops", "parallel")
SCAN_FILES = (
    os.path.join("scheduler", "coalescer.py"),
    os.path.join("state", "matrix.py"),
)

# Dotted-prefix patterns whose call results live on device.
DEVICE_PRODUCER_PREFIXES = ("kernels.", "jnp.", "jax.numpy.")
DEVICE_PRODUCER_EXACT = {"jax.device_put"}
DEVICE_PRODUCER_NAMES = {
    "fused_place_batch_live", "sharded_fused_place_batch",
    "sharded_fused_place_batch_live", "_sharded_fused_fn",
}

# Sinks that force a device→host sync.
SYNC_CALL_NAMES = {"float", "int", "bool"}
SYNC_DOTTED = {"np.asarray", "numpy.asarray", "np.array", "numpy.array", "jax.device_get"}
SYNC_METHODS = {"item", "tolist", "block_until_ready"}

# J004: the mega-batched fused entry points whose one-compile-per-shape
# contract the rule protects.
FUSED_ENTRY_NAMES = {"fused_place_batch", "fused_place_batch_live"}
# Array constructors that stack per-dispatch Python sequences into a new
# leading dim — shape-polymorphic when fed a comprehension/starred seq.
STACKING_CALL_NAMES = {
    "stack", "vstack", "hstack", "concatenate", "asarray", "array",
}
# Static params of the fused entry points (mirrors ops/kernels.py); a
# batch-derived value here keys a fresh compile per occupancy.
FUSED_STATIC_PARAMS = ("n_placements", "features", "layouts")

# J005: the node-sharded dispatch builders — a function calling any of
# these (or the fused entries above) is "on the fused/sharded path" and
# must never fetch node-axis-shaped arrays to host.  ``_sharded_fused_fn``
# is the coalescer's bound callable built by
# ``sharded_fused_place_batch_live`` — the production dispatch site invokes
# the entry through it, so the bound name counts as an entry too.
SHARDED_ENTRY_NAMES = {
    "sharded_fused_place_batch",
    "sharded_fused_place_batch_live",
    "_sharded_fused_fn",
}
# Node-axis-shaped leaves: every DeviceArrays field (state/matrix.py) plus
# the node-shaped PlacementResult fields (ops/kernels.py).  An attribute
# access with one of these names is treated as (…, N)-shaped.
NODE_AXIS_ATTRS = {
    "totals", "used", "eligible", "attr_hash", "attr_num", "attr_ver",
    "class_id", "dev_total", "dev_used", "prio_used", "port_words",
    "dyn_used",
    "used_after", "tg_count_after",
}


def _dotted(node: ast.AST) -> Optional[str]:
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _is_device_call(node: ast.AST, jitted_names: Set[str]) -> bool:
    if not isinstance(node, ast.Call):
        return False
    d = _dotted(node.func)
    if d is None:
        return False
    short = d.rsplit(".", 1)[-1]
    if d in DEVICE_PRODUCER_EXACT or short in DEVICE_PRODUCER_NAMES:
        return True
    if d in jitted_names or short in jitted_names:
        return True
    return any(d.startswith(p) for p in DEVICE_PRODUCER_PREFIXES)


def _mutable_display(node: ast.AST) -> bool:
    return isinstance(
        node, (ast.List, ast.Dict, ast.Set, ast.ListComp, ast.DictComp, ast.SetComp)
    )


def _varlen_stack_call(node: ast.AST) -> bool:
    """``np.stack([... for ...])`` / ``jnp.asarray(x for ...)`` /
    ``tree_map(...)``: a call that materializes a per-dispatch Python
    sequence into a new leading dim, so the result's shape tracks the
    live batch occupancy instead of a preallocated (B, ...) slab."""
    if not isinstance(node, ast.Call):
        return False
    d = _dotted(node.func)
    short = (d or "").rsplit(".", 1)[-1]
    if short == "tree_map":
        return True
    if short not in STACKING_CALL_NAMES:
        return False
    for a in node.args:
        if isinstance(a, (ast.ListComp, ast.GeneratorExp)):
            return True
        if isinstance(a, (ast.List, ast.Tuple)) and any(
            isinstance(e, ast.Starred) for e in a.elts
        ):
            return True
    return False


def _batch_derived(node: ast.AST) -> bool:
    """True when the expression reads ``len(...)`` or ``.shape`` — a value
    that varies with the live batch rather than configuration."""
    for sub in ast.walk(node):
        if (
            isinstance(sub, ast.Call)
            and isinstance(sub.func, ast.Name)
            and sub.func.id == "len"
        ):
            return True
        if isinstance(sub, ast.Attribute) and sub.attr == "shape":
            return True
    return False


class _ModuleInfo:
    def __init__(self, path: str, tree: ast.Module):
        self.path = path
        self.tree = tree
        # module-level names bound to mutable containers
        self.mutable_globals: Dict[str, int] = {}
        # jit-wrapped callables visible in this module: name -> static params
        self.jitted: Dict[str, Tuple[str, ...]] = {}
        self._scan_module_scope()

    def _scan_module_scope(self) -> None:
        for node in self.tree.body:
            if isinstance(node, ast.Assign) and len(node.targets) == 1:
                t = node.targets[0]
                if isinstance(t, ast.Name):
                    if _mutable_display(node.value):
                        self.mutable_globals[t.id] = node.lineno
                    jc = _jit_call_info(node.value)
                    if jc is not None:
                        self.jitted[t.id] = jc
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                statics = _jit_decorator_statics(node)
                if statics is not None:
                    self.jitted[node.name] = statics


def _jit_call_info(node: ast.AST) -> Optional[Tuple[str, ...]]:
    """`jax.jit(f, static_argnames=(...))` -> static names ('' if none)."""
    if not isinstance(node, ast.Call):
        return None
    if _dotted(node.func) not in ("jax.jit", "jit"):
        return None
    return _static_names(node)


def _jit_decorator_statics(fn: ast.AST) -> Optional[Tuple[str, ...]]:
    """Static argnames for @jax.jit / @partial(jax.jit, ...) decorated
    functions; None when the function isn't jitted at all."""
    for dec in getattr(fn, "decorator_list", []):
        d = _dotted(dec) or (_dotted(dec.func) if isinstance(dec, ast.Call) else None)
        if d in ("jax.jit", "jit"):
            return _static_names(dec) if isinstance(dec, ast.Call) else ()
        if isinstance(dec, ast.Call) and _dotted(dec.func) in (
            "functools.partial", "partial",
        ):
            if dec.args and _dotted(dec.args[0]) in ("jax.jit", "jit"):
                return _static_names(dec)
    return None


def _static_names(call: ast.Call) -> Tuple[str, ...]:
    for kw in call.keywords:
        if kw.arg in ("static_argnames", "static_argnums"):
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, str):
                return (v.value,)
            if isinstance(v, (ast.Tuple, ast.List)):
                return tuple(
                    e.value for e in v.elts
                    if isinstance(e, ast.Constant) and isinstance(e.value, str)
                )
    return ()


# ----------------------------------------------------------------------


def _check_function(
    info: _ModuleInfo,
    fn: ast.AST,
    symbol: str,
    findings: List[Finding],
) -> None:
    jitted_names = set(info.jitted)
    device_vars: Set[str] = set()
    # locals bound to mutable displays (for J003 via a hop)
    mutable_locals: Dict[str, int] = {}
    # locals bound to per-dispatch stacked arrays (for J004 via a hop)
    stacked_locals: Dict[str, int] = {}
    # locals bound to node-axis-shaped attributes (for J005 via a hop)
    node_axis_vars: Dict[str, int] = {}

    # J005 scopes to functions that drive the fused/sharded dispatch path.
    fused_caller = any(
        isinstance(n, ast.Call)
        and (_dotted(n.func) or "").rsplit(".", 1)[-1]
        in (FUSED_ENTRY_NAMES | SHARDED_ENTRY_NAMES)
        and not (_dotted(n.func) or "").startswith("fake_device.")
        for n in ast.walk(fn)
    )

    def _node_axis_expr(expr: ast.AST) -> Optional[str]:
        if isinstance(expr, ast.Attribute) and expr.attr in NODE_AXIS_ATTRS:
            return _dotted(expr) or f".{expr.attr}"
        if isinstance(expr, ast.Name) and expr.id in node_axis_vars:
            return expr.id
        return None

    statics = _jit_decorator_statics(fn)
    if statics:
        # J003: mutable default on a static parameter.
        args = fn.args
        defaults = args.defaults
        params = [a.arg for a in args.args]
        for param, default in zip(params[len(params) - len(defaults):], defaults):
            if param in statics and _mutable_display(default):
                findings.append(Finding(
                    "J003", info.path, fn.lineno, symbol,
                    f"static arg '{param}' has a non-hashable (mutable) "
                    f"default — jit static args are cache keys and must "
                    f"hash",
                ))

    for node in ast.walk(fn):
        if isinstance(node, ast.Assign) and len(node.targets) == 1:
            t = node.targets[0]
            if isinstance(t, ast.Name):
                if _varlen_stack_call(node.value):
                    stacked_locals[t.id] = node.lineno
                if (
                    isinstance(node.value, ast.Attribute)
                    and node.value.attr in NODE_AXIS_ATTRS
                ):
                    node_axis_vars[t.id] = node.lineno
                if _is_device_call(node.value, jitted_names):
                    device_vars.add(t.id)
                elif _mutable_display(node.value):
                    mutable_locals[t.id] = node.lineno
                elif isinstance(node.value, ast.Name):
                    if node.value.id in device_vars:
                        device_vars.add(t.id)
        if not isinstance(node, ast.Call):
            continue
        d = _dotted(node.func)

        def _arg_is_device(c: ast.Call) -> Optional[str]:
            for a in c.args:
                if isinstance(a, ast.Name) and a.id in device_vars:
                    return a.id
                if _is_device_call(a, jitted_names):
                    return _dotted(a.func) or "<device call>"
            return None

        # J001 sinks.
        hit: Optional[str] = None
        if d in SYNC_DOTTED:
            hit = _arg_is_device(node)
        elif isinstance(node.func, ast.Name) and node.func.id in SYNC_CALL_NAMES:
            hit = _arg_is_device(node)
        elif isinstance(node.func, ast.Attribute) and node.func.attr in SYNC_METHODS:
            recv = node.func.value
            if isinstance(recv, ast.Name) and recv.id in device_vars:
                hit = recv.id
            elif _is_device_call(recv, jitted_names):
                hit = _dotted(recv.func) or "<device call>"
        if hit is not None:
            sink = d or (
                f".{node.func.attr}()" if isinstance(node.func, ast.Attribute)
                else getattr(node.func, "id", "?")
            )
            findings.append(Finding(
                "J001", info.path, node.lineno, symbol,
                f"implicit device->host sync: {sink} on device value "
                f"'{hit}' — each sync blocks until the device is done; route "
                f"fetches through the resolver thread",
            ))
            continue

        # J005: node-axis-shaped operand fetched to host in a function
        # that drives the fused/sharded dispatch path.
        if fused_caller:
            tgt: Optional[str] = None
            if d in SYNC_DOTTED or (
                isinstance(node.func, ast.Name)
                and node.func.id in SYNC_CALL_NAMES
            ):
                for a in node.args:
                    tgt = _node_axis_expr(a)
                    if tgt:
                        break
            elif (
                isinstance(node.func, ast.Attribute)
                and node.func.attr in SYNC_METHODS
            ):
                tgt = _node_axis_expr(node.func.value)
            if tgt is not None:
                findings.append(Finding(
                    "J005", info.path, node.lineno, symbol,
                    f"node-axis-shaped value '{tgt}' fetched to host at a "
                    f"fused/sharded call site — only the packed "
                    f"(B, P, 8) winner block may cross the device->host "
                    f"boundary; an (..., N) fetch is O(nodes) host "
                    f"traffic per dispatch (see ops/kernels.py "
                    f"elect)",
                ))
                continue

        # J004: per-eval recompile triggers at fused-megakernel call
        # sites. The fake-device twin has no compile cache, so its calls
        # are exempt.
        short_callee = d.rsplit(".", 1)[-1] if d else None
        if (
            short_callee in FUSED_ENTRY_NAMES
            and not (d or "").startswith("fake_device.")
        ):
            for a in node.args:
                if _varlen_stack_call(a) or (
                    isinstance(a, ast.Name) and a.id in stacked_locals
                ):
                    src = (
                        a.id if isinstance(a, ast.Name)
                        else _dotted(a.func) or "<stack call>"
                    )
                    findings.append(Finding(
                        "J004", info.path, node.lineno, symbol,
                        f"shape-polymorphic operand '{src}' fed to "
                        f"{short_callee}() — its leading dim tracks the "
                        f"batch occupancy, so every distinct batch size "
                        f"recompiles; preallocate a (B, ...) slab "
                        f"(ops.encode.RequestSlab) and mask dead lanes",
                    ))
            for kw in node.keywords:
                if kw.arg in FUSED_STATIC_PARAMS and _batch_derived(kw.value):
                    findings.append(Finding(
                        "J004", info.path, node.lineno, symbol,
                        f"static arg '{kw.arg}' of {short_callee}() is "
                        f"derived from the live batch (len()/.shape) — "
                        f"each occupancy keys a fresh XLA compile; bind "
                        f"static args to configuration constants and let "
                        f"lane_steps absorb occupancy",
                    ))

        # J003: mutable value into a static param of a known jitted fn.
        callee = d.rsplit(".", 1)[-1] if d else None
        if callee in info.jitted and info.jitted[callee]:
            statics_set = set(info.jitted[callee])
            for kw in node.keywords:
                if kw.arg in statics_set and (
                    _mutable_display(kw.value)
                    or (isinstance(kw.value, ast.Name) and kw.value.id in mutable_locals)
                ):
                    findings.append(Finding(
                        "J003", info.path, node.lineno, symbol,
                        f"non-hashable value passed to static arg "
                        f"'{kw.arg}' of jitted {callee}() — raises or "
                        f"poisons the compile cache",
                    ))

    # J002: jitted function reading a mutable module-level global.
    if statics is not None and info.mutable_globals:
        params = {a.arg for a in fn.args.args}
        assigned = {
            t.id
            for n in ast.walk(fn)
            if isinstance(n, ast.Assign)
            for t in n.targets
            if isinstance(t, ast.Name)
        }
        for node in ast.walk(fn):
            if (
                isinstance(node, ast.Name)
                and isinstance(node.ctx, ast.Load)
                and node.id in info.mutable_globals
                and node.id not in params
                and node.id not in assigned
            ):
                findings.append(Finding(
                    "J002", info.path, node.lineno, symbol,
                    f"jit-traced function captures mutable global "
                    f"'{node.id}' — tracing freezes its value; pass it as "
                    f"an argument or make it immutable",
                ))
                break


# ----------------------------------------------------------------------


def analyze_sources(sources: Dict[str, str]) -> List[Finding]:
    """Analyze {repo-relative path: source text} — the test fixture API."""
    findings: List[Finding] = []
    for path, src in sources.items():
        info = _ModuleInfo(path, ast.parse(src))
        _walk(info, findings)
    return findings


def _walk(info: _ModuleInfo, findings: List[Finding]) -> None:
    def walk_body(body, prefix: str) -> None:
        for node in body:
            if isinstance(node, ast.ClassDef):
                walk_body(node.body, f"{node.name}.")
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                _check_function(info, node, f"{prefix}{node.name}", findings)

    walk_body(info.tree.body, "")


def run(root: str) -> List[Finding]:
    pkg = os.path.join(root, "nomad_tpu")
    paths: List[str] = []
    for d in SCAN_DIRS:
        base = os.path.join(pkg, d)
        if not os.path.isdir(base):
            continue
        for dirpath, _dirnames, filenames in os.walk(base):
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    paths.append(os.path.join(dirpath, fn))
    for f in SCAN_FILES:
        p = os.path.join(pkg, f)
        if os.path.exists(p):
            paths.append(p)

    findings: List[Finding] = []
    for p in sorted(paths):
        with open(p) as fh:
            src = fh.read()
        rel = os.path.relpath(p, root).replace(os.sep, "/")
        info = _ModuleInfo(rel, ast.parse(src))
        _walk(info, findings)
    return findings
