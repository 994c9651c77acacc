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


def compile_sharded(mesh, feats):
    """``sharded_fused_place_batch`` lowered and compiled for ``mesh`` at
    the four-chip cells' sizes, from shapes alone."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from nomad_tpu.lint.contracts import Grid, fused_operands
    from nomad_tpu.ops import kernels
    from nomad_tpu.parallel import sharding
    from nomad_tpu.scheduler.claims import CHAIN_DEPTH
    from nomad_tpu.scheduler.coalescer import MAX_DELTA_ROWS

    def spec(shape, dtype, p):
        return jax.ShapeDtypeStruct(
            tuple(shape), dtype, sharding=NamedSharding(mesh, p))

    # Field shapes off a small grid; the node axis at the region's size.
    small = fused_operands(Grid(
        nodes=8, batch=LANES, placements=SCAN, deltas=MAX_DELTA_ROWS,
        live=LANES, features=feats))
    arrays = type(small[0])(*(
        spec((ROWS,) + np.shape(x)[1:], np.asarray(x).dtype, p)
        for x, p in zip(small[0], sharding._ARRAYS_SPEC)))
    reqs = kernels.device_request(small[7], feats.dp_width)
    reqs = type(reqs)(*(
        None if f is None else spec(np.shape(f), np.asarray(f).dtype, p)
        for f, p in zip(reqs, sharding._REQS_SPEC)))
    lanes, f32, i32 = P("batch", None, None), np.float32, np.int32
    k = MAX_DELTA_ROWS
    args = (
        arrays, spec((ROWS, 3), f32, P("node", None)),
        spec((LANES, k), i32, P("batch", None)), spec((LANES, k, 3), f32, lanes),
        spec((LANES, ROWS), i32, P("batch", "node")),
        spec(np.shape(small[5]), f32, lanes),
        spec((LANES, ROWS), bool, P("batch", "node")), reqs,
        spec((LANES, CLASS_PAD), bool, P("batch", None)),
        spec((LANES, ROWS), bool, P("batch", "node")),
        spec((LANES,), i32, P("batch")),
    )
    overlay = (spec((LANES, 64), i32, P("batch", None)),
               spec((LANES, 64, 3), f32, lanes))
    chain = (spec((CHAIN_DEPTH, LANES, k + SCAN, 4), f32,
                  P(None, "batch", None, None)),
             spec((LANES, 1 + CHAIN_DEPTH), bool, P("batch", None)),
             spec((LANES, k, 3), f32, lanes))
    fn = sharding.sharded_fused_place_batch(mesh, SCAN)
    return fn.lower(
        *args, features=feats, overlay=overlay, chain=chain).compile()


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
