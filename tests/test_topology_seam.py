"""One placement body, two topologies (PR 50; PR 49 was the same change, not merged).

``kernels._fused_place_batch_impl`` is the placement step of one shard,
written once against ``kernels.Topology``: on one device every method of
the seam returns its argument, on a mesh each is a collective
(``sharding.MESH``).  Held here, on the CPU:

* the one-device programs trace to a jaxpr with no collective, no gather
  over an axis and no varying-axes cast at any depth, while the mesh's
  program holds them (the walk is not blind);
* a ``(1, 1)`` mesh runs the body with the mesh's side of the seam on one
  device, and its outputs are the one-device program's bit for bit, the
  overlay and the chained carry included;
* the seam is closed (the mesh overrides every method), nothing of the
  step is written in ``parallel/sharding.py``, and the leaks the seam
  replaced are gone.
"""

from __future__ import annotations

import ast
import functools
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers import random_launch

from nomad_tpu.lint.jaxprpass import iter_eqns
from nomad_tpu.ops import kernels
from nomad_tpu.ops.kernels import FULL_FEATURES, Features
from nomad_tpu.parallel import sharding

# Every primitive that names a mesh axis.
ACROSS_SHARDS = frozenset((
    "psum", "psum2", "psum_invariant", "pmax", "pmin", "all_gather",
    "all_gather_invariant", "all_to_all", "ppermute", "psum_scatter",
    "reduce_scatter", "pcast", "pvary", "axis_index", "shard_map",
))
RULES_PREEMPT = Features(c_width=2, a_width=1, s_width=1, preempt=True,
                         ports=False, dp_width=1)
NODES, LANES, STEPS, CLAIMS = 320, 4, 3, 3


def _across_shards(fn, *operands, **static):
    closed = jax.make_jaxpr(functools.partial(fn, **static))(*operands)
    names = [e.primitive.name for e in iter_eqns(closed.jaxpr)]
    assert len(names) > 500, "the walk lost the program"
    return sorted(set(names) & ACROSS_SHARDS)


def _in_flight(seed, ops):
    """A random overlay and chain for ``ops`` (``random_launch``): rows of
    the cluster or padding, two carried blocks of which one is live."""
    rng = np.random.default_rng(seed)
    rows = lambda *shape: np.where(
        rng.random(shape) < 0.6, rng.integers(0, NODES, shape), -1
    ).astype(np.int32)
    vals = lambda *shape: (rng.random(shape + (3,)) * 60).astype(np.float32)
    held = ops[2].shape[1] + STEPS
    carry = np.concatenate(
        [rows(2, LANES, held)[..., None].astype(np.float32),
         vals(2, LANES, held)], axis=3)
    flags = np.array([[True, True], [True, False]] * (LANES // 2))
    return dict(
        overlay=(rows(LANES, CLAIMS), vals(LANES, CLAIMS)),
        chain=(carry, flags, vals(*ops[2].shape)),
    )


@pytest.mark.parametrize("features", [FULL_FEATURES, RULES_PREEMPT],
                         ids=["full", "dp1_preempt"])
def test_the_batched_program_on_one_device_holds_no_collective(features):
    ops = random_launch(49, NODES, LANES, features, steps=STEPS)
    assert _across_shards(
        kernels.fused_place_batch, *ops, n_placements=STEPS,
        features=features, **_in_flight(49, ops)) == []


def test_the_solo_scan_holds_no_collective():
    arrays, used, _dr, _dv, tg, sc, pen, reqs, ce, hm, _ls = random_launch(
        49, NODES, LANES, FULL_FEATURES, steps=STEPS)
    req = jax.tree_util.tree_map(lambda x: x[0], reqs)
    assert _across_shards(
        kernels.place_task_group, arrays, req, used, tg[0], sc[0], pen[0],
        ce[0], hm[0], n_placements=STEPS, features=FULL_FEATURES) == []


def test_the_mesh_program_holds_them(eight_devices):
    """The same walk over the same body under the mesh's side of the seam
    finds every kind of exchange: the check above is not blind."""
    ops = random_launch(49, NODES, LANES, RULES_PREEMPT, steps=STEPS)
    fn = sharding.sharded_fused_place_batch(
        sharding.make_mesh(4, batch=2), STEPS)
    found = _across_shards(
        fn, *ops, features=RULES_PREEMPT, **_in_flight(49, ops))
    assert {"pmax", "pmin", "all_gather", "axis_index",
            "shard_map"} <= set(found), found
    assert {"psum", "psum_invariant"} & set(found), found
    assert {"pcast", "pvary"} & set(found), found


@pytest.mark.parametrize("features", [
    Features(c_width=4, a_width=1, s_width=1, preempt=False, ports=False,
             dp_width=0),
    RULES_PREEMPT, FULL_FEATURES,
], ids=["plain", "dp1_preempt", "full"])
def test_a_one_by_one_mesh_is_the_one_device_program_bit_for_bit(
        eight_devices, features):
    ops = random_launch(2 ** 31 + 49, NODES, LANES, features, steps=STEPS)
    in_flight = _in_flight(7, ops)
    one, one_carry = kernels.fused_place_batch(
        *ops, n_placements=STEPS, features=features, **in_flight)
    mesh = sharding.make_mesh(1, batch=1)
    arrays = sharding.shard_matrix_arrays(
        mesh, jax.tree_util.tree_map(jnp.asarray, ops[0]))
    many, many_carry = sharding.sharded_fused_place_batch(mesh, STEPS)(
        arrays, *ops[1:], features=features, **in_flight)
    assert np.asarray(one).tobytes() == np.asarray(many).tobytes()
    assert np.asarray(one_carry).tobytes() == np.asarray(many_carry).tobytes()
    placed = np.asarray(one)[:, :, kernels.PACKED_ROW] >= 0
    assert placed.sum() >= LANES, "the operands place too little to show it"


def _public(cls):
    return {n for n, v in vars(cls).items()
            if inspect.isfunction(v) and not n.startswith("_")}


def test_the_mesh_overrides_every_method_of_the_seam():
    seam = _public(kernels.Topology)
    assert len(seam) >= 8
    assert _public(type(sharding.MESH)) == seam
    assert isinstance(sharding.MESH, kernels.Topology)
    assert type(kernels.ONE_DEVICE) is kernels.Topology
    # One device: every method returns its argument, and holds everything.
    x = np.arange(3)
    one = kernels.ONE_DEVICE
    assert one.shard(NODES, LANES) == (0, 0)
    assert all(f(x) is x for f in (
        one.vary, one.all_lanes, one.max, one.min, one.sum, one.any, one.all))


def test_sharding_defines_no_placement_step():
    tree = ast.parse(inspect.getsource(sharding))
    defined = {n.name for n in ast.walk(tree)
               if isinstance(n, ast.FunctionDef)}
    assert not defined & {"score", "commit", "step", "take", "lane_step",
                          "p_step", "_fused_place_batch_local"}, defined
    # What it imports of the kernels is the body, not the parts of one.
    imported = {a.name for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)
                and n.module == "ops.kernels" for a in n.names}
    assert imported == {"FULL_FEATURES", "Topology", "_fused_place_batch_impl",
                        "place_launch", "unpack_launch"}, imported


@pytest.mark.parametrize("where,name", [
    (kernels, "resolved_pick"), (kernels, "claims_image"),
    (kernels, "overlay_usage"), (sharding, "TOPK_K"),
    (sharding, "_fused_place_batch_local"),
])
def test_what_the_seam_replaced_is_gone(where, name):
    assert not hasattr(where, name)


@pytest.mark.parametrize("fn,gone", [
    (kernels.score_nodes, "node_axis"), (kernels.rank_nodes, "node_axis"),
    (kernels.launch_invariants, "vary"),
])
def test_no_function_takes_the_topology_by_another_name(fn, gone):
    assert gone not in inspect.signature(fn).parameters


def test_no_jitted_entry_takes_the_seam_as_an_argument():
    """Whoever builds an entry binds it: it is no operand and no static."""
    for entry in (kernels.fused_place_batch, kernels.fused_place_batch_live,
                  kernels.place_task_group):
        assert "topo" not in inspect.signature(entry).parameters
