"""The node-sharded placement program compiled for a v5e 2x2 that is
described, not attached (PR 46): the wide ``Features`` variant the cell
``c2m-100k-rules.rules-backlog-x4`` launches, at its timed sizes (64 lanes x
102,400 rows on a ``(2, 2)`` mesh, a class operand of 8,192 a lane, the
overlay and the chain as the coalescer hands them over).  What the chip's
compiler would refuse (a variant that does not partition, a program that
does not fit a chip's memory) it refuses here, at no chip time; nothing
runs, so this says nothing of results or times.

The topology is described inside a fixture, in this file alone: only one
process at a time may load the TPU's library, and a worker that cannot
skips these tests without touching any other.
"""

from __future__ import annotations

import re

import numpy as np
import pytest

LANES, ROWS, SCAN, CLASS_PAD = 64, 102_400, 16, 8192
HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def mesh():
    import jax
    from jax.experimental import topologies
    from jax.sharding import Mesh

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # Such a compile is written to the persistent cache and cannot be read
    # back without a chip: keep it out.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield Mesh(np.array(topo.devices).reshape(2, 2), ("batch", "node"))
    jax.config.update("jax_enable_compilation_cache", was)


def _compiled(mesh, feats):
    import jax
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


def _features(**widths):
    from nomad_tpu.ops import kernels

    return kernels.Features(preempt=False, ports=False, **widths)


@pytest.mark.parametrize("dp_width", [1, 2])
def test_the_wide_sharded_variant_compiles_for_four_chips(mesh, dp_width):
    compiled = _compiled(mesh, _features(
        c_width=8, a_width=2, s_width=2, dp_width=dp_width))
    mem = compiled.memory_analysis()
    # a chip's half of the matrix is resident beside it (244 MB a snapshot)
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < HBM_BYTES // 4
    names = set(re.findall(r'op_name="([^"]*)"', compiled.as_text()))
    under = {n for n in names if "/rules_exchange/" in n}
    # the chip's compiler keeps the stage's pmax an op of its own, so a
    # profile tells its time (XLA's CPU backend folds it into the step's
    # other pmax)
    assert any(n.endswith("rules_exchange/pmax") for n in under), under
    assert any(n.endswith("rules_exchange/gather") for n in under), under


def test_the_plain_sharded_variant_has_no_rules_exchange(mesh):
    compiled = _compiled(mesh, _features(
        c_width=4, a_width=1, s_width=1, dp_width=0))
    assert "rules_exchange" not in compiled.as_text()
