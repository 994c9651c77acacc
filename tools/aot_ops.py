#!/usr/bin/env python3
"""aot_ops.py — the node-sharded placement program compiled for a v5e 2x2
that is described, not attached, and the table that turns a device op's name
into a source line without a chip (PR 47).

The ledger's ``breakdown`` of a traced run names device ops as the chip's
compiler named them (``while.121``, ``dynamic-update-slice.47``).  The same
compiler is installed here and names them the same, letter for letter, when
it compiles the same program at the same sizes: this tool compiles one named
``Features`` variant at the four-chip cells' timed sizes (64 lanes x 102,400
rows on a ``(2, 2)`` mesh, the overlay and the chain as the coalescer hands
them over) and prints, for every ``while`` and every op that is no part of a
fusion's body, its name, the computation that holds it, its trip count where
the compiler knows one, and its ``op_name``: the stack of ``jax.named_scope``
names (``place_scan/while/body/vmap(score)/feasibility/...``) down to the jax
primitive.  Nothing runs, so it says nothing of results or times.

    JAX_PLATFORMS=cpu python tools/aot_ops.py wide            # ~20 s
    JAX_PLATFORMS=cpu python tools/aot_ops.py plain --grep feasibility
    JAX_PLATFORMS=cpu python tools/aot_ops.py wide --text /root/scratch/wide.hlo

``compile_sharded`` is also what ``tests/test_compile_for_v5e.py`` compiles.
Only one process at a time may load the TPU's library: run it alone.
"""

from __future__ import annotations

import argparse
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

LANES, ROWS, SCAN, CLASS_PAD = 64, 102_400, 16, 8192

# The variants the four-chip cells launch most (``placement_programs`` of
# benchmark/configs/c2m-100k-rules.json and c2m-100k.json).
VARIANTS = {
    "wide": dict(c_width=8, a_width=2, s_width=2, dp_width=1),
    "plain": dict(c_width=4, a_width=1, s_width=1, dp_width=0),
}


def features(**widths):
    from nomad_tpu.ops import kernels

    return kernels.Features(preempt=False, ports=False, **widths)


def described_mesh():
    """A ``(2, 2)`` ('batch', 'node') mesh of a described v5e:2x2; raises
    what ``get_topology_desc`` raises where libtpu cannot be loaded."""
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh

    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2")
    return Mesh(np.array(topo.devices).reshape(2, 2), ("batch", "node"))


def launch_specs(feats, rows, class_pad, sharding_of):
    """What a launch of the server hands its one program, from shapes alone:
    ``(operands, static)`` of the live entries (``kernels.
    fused_place_batch_live`` / ``sharding.sharded_fused_place_batch_live``)
    at 64 lanes x ``rows`` nodes, the packs and their layouts the server's
    own (``lint/contracts.py``: ``RequestSlab``'s and ``_staging``'s).
    ``sharding_of(spec)`` lays an operand out, ``spec`` its
    ``PartitionSpec`` on a ('batch', 'node') mesh, None for the two packs
    (their split is the program's to ask)."""
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from nomad_tpu.lint import contracts
    from nomad_tpu.parallel import sharding

    g = contracts.Grid(nodes=8, batch=LANES, placements=SCAN, deltas=0,
                       live=LANES, features=feats)
    arrays, used, _rp, _lp, tg, pen, hm, carry = contracts.packed_operands(g)
    packs, layouts = contracts._unpack_packs(g, class_pad)

    def struct(x, spec, shape=None):
        return jax.ShapeDtypeStruct(
            shape or np.shape(x), np.asarray(x).dtype,
            sharding=sharding_of(spec))

    def node_rows(x, spec):
        return struct(x, spec, (rows,) + np.shape(x)[1:])

    operands = (
        type(arrays)(*map(node_rows, arrays, sharding._ARRAYS_SPEC)),
        node_rows(used, P("node", None)),
        *(struct(p, None) for p in packs),
        *(struct(x, P("batch", "node"), (LANES, rows)) for x in (tg, pen, hm)),
        struct(carry, P(None, "batch", None, None)),
    )
    return operands, {"layouts": layouts, "features": feats}


def _on(mesh):
    from jax.sharding import NamedSharding

    return lambda spec: None if spec is None else NamedSharding(mesh, spec)


def compile_sharded(mesh, feats):
    """``sharded_fused_place_batch_live`` (the one call a launch of the
    four-chip cells makes) lowered and compiled for ``mesh`` at their
    sizes, from shapes alone."""
    from nomad_tpu.parallel import sharding

    operands, static = launch_specs(feats, ROWS, CLASS_PAD, _on(mesh))
    fn = sharding.sharded_fused_place_batch_live(mesh, SCAN)
    return fn.lower(*operands, **static).compile()


def compile_sharded_plain(mesh, feats):
    """``sharded_fused_place_batch`` (every operand its own, each laid out
    as the ``shard_map`` asks: the placement program as it was before a
    launch handed it the packs) at the same sizes: what the live entry's
    collectives are held to."""
    import jax
    from jax.sharding import PartitionSpec as P

    from nomad_tpu.ops import kernels
    from nomad_tpu.parallel import sharding

    (arrays, used, request_pack, lane_pack, *rest), static = launch_specs(
        feats, ROWS, CLASS_PAD, _on(mesh))
    reqs, lane = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=_on(mesh)(P("batch"))),
        jax.eval_shape(
            lambda *packs: kernels.unpack_launch(
                *packs, static["layouts"], feats.dp_width),
            request_pack, lane_pack))
    fn = sharding.sharded_fused_place_batch(mesh, SCAN)
    return kernels.place_launch(
        fn.lower, arrays, used, reqs, lane, *rest, features=feats).compile()


def compile_one_chip(device, feats, rows=10_240, class_pad=512):
    """``kernels.fused_place_batch_live`` (the one call a launch of the
    one-chip cells makes) lowered and compiled for ``device`` at their
    sizes (64 lanes x 10,240 rows; ``c2m-10k-rules`` has 480 computed
    classes), from shapes alone."""
    from jax.sharding import SingleDeviceSharding

    from nomad_tpu.ops import kernels

    operands, static = launch_specs(
        feats, rows, class_pad, lambda _spec: SingleDeviceSharding(device))
    return kernels.fused_place_batch_live.lower(
        *operands, n_placements=SCAN, **static).compile()


_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$")
_OP = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = .*?[\]})] ([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_TRIP = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_INNER = re.compile(r"(?:calls|to_apply)=%?([\w.\-]+)")
_LOOP = re.compile(r"(condition|body)=%?([\w.\-]+)")
_NO_TIME = frozenset((
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
))


def op_table(text: str):
    """[(op, computation, trip count or '', op_name)] of an optimized HLO
    text: every op that stands in a computation in its own right (the
    entry, a loop's body or condition, a branch), where the profile times
    it under its own name; what a fusion or a reducer holds inside is the
    fusion's."""
    rows, inner, loop_of, comp = [], set(), {}, ""
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _OP.match(line)
        if not m:
            continue
        op, kind = m.groups()
        if kind not in ("while", "conditional", "call"):
            inner.update(_INNER.findall(line))
        if kind in _NO_TIME:
            continue
        name = _OP_NAME.search(line)
        trip = _TRIP.search(line) if kind == "while" else None
        if kind == "while":
            for part, callee in _LOOP.findall(line):
                loop_of[callee] = f"{part} of {op}"
        rows.append((op, comp, trip.group(1) if trip else "",
                     name.group(1) if name else ""))
    return [
        (op, f"{c} ({loop_of[c]})" if c in loop_of else c, trip, name)
        for op, c, trip, name in rows if c not in inner
    ]


def loops_under(text: str, *scopes: str):
    """The ``while`` ops whose ``op_name`` holds every one of ``scopes``."""
    return [
        r for r in op_table(text)
        if re.match(r"while(\.\d+)?$", r[0]) and all(s in r[3] for s in scopes)
    ]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("variant", choices=sorted(VARIANTS))
    ap.add_argument("--grep", default="", help="only op_names that hold this")
    ap.add_argument("--all", action="store_true",
                    help="every unfused op, not the loops alone")
    ap.add_argument("--text", default="", help="write the HLO text here too")
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax

    # Such a compile cannot be read back from the persistent cache without
    # a chip: keep it out.
    jax.config.update("jax_enable_compilation_cache", False)
    compiled = compile_sharded(
        described_mesh(), features(**VARIANTS[args.variant]))
    text = compiled.as_text()
    if args.text:
        with open(args.text, "w") as fh:
            fh.write(text)
    mem = compiled.memory_analysis()
    print(f"# {args.variant}: temp {mem.temp_size_in_bytes} B, arguments "
          f"{mem.argument_size_in_bytes} B, output {mem.output_size_in_bytes} B "
          "a device")
    print("# op\tcomputation\ttrip\top_name")
    for op, comp, trip, name in op_table(text):
        if not args.all and not op.startswith("while"):
            continue
        if args.grep in name:
            print(f"{op}\t{comp}\t{trip}\t{name}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
