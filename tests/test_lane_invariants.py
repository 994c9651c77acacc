"""What a lane's placement steps all share is computed once a launch (PR 47).

``kernels.lane_invariants`` (the static feasibility mask, the affinity
term, the attribute columns of the spread and distinct_property stages, the
preemption tables' rows) is built before the placement scan, inside the
placement program, and ``rank_nodes`` ranks every step against it.  Two
things are held here, on the CPU:

* structure: in the traced fused entries the placement loop's body holds no
  equation of the feasibility stage but the distinct_property stage's (the
  one feasibility term a pick changes), and none that reads an attribute
  table whole but the picked row's read in ``update``;
* values: the program that computes them once equals, bit for bit, the one
  that computes them anew in every step (a loop of one-shot
  ``score_nodes`` calls, as the scan's step was before), solo and batched,
  on one device and on a mesh, on randomised operands.
"""

from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import Var

from helpers import random_launch, solo_reference

from nomad_tpu.lint import contracts
from nomad_tpu.lint.jaxprpass import _subjaxprs
from nomad_tpu.ops import kernels
from nomad_tpu.ops.kernels import FULL_FEATURES, Features
from nomad_tpu.parallel import sharding

FEATURES = {
    "none": Features(0, 0, 0, False, False, 0),
    "plain": Features(c_width=4, a_width=1, s_width=1, preempt=False,
                      ports=False, dp_width=0),
    "wide": Features(c_width=8, a_width=2, s_width=2, preempt=False,
                     ports=False, dp_width=1),
    "preempt": Features(c_width=2, a_width=0, s_width=1, preempt=True,
                        ports=True, dp_width=2),
    "full": FULL_FEATURES,
}
ATTR_TABLES = ("attr_hash", "attr_num", "attr_ver")
_WRAPPED = re.compile(r"\b\w+\(([\w/]*)\)")  # vmap(score) -> score


# -- structure ---------------------------------------------------------------


def _scopes(name_stack: str) -> tuple:
    return tuple(p for p in _WRAPPED.sub(r"\1", name_stack).split("/") if p)


def _inner(eqn, sub):
    """{an equation's operand: the sub-jaxpr's variable it arrives as}."""
    outer = list(eqn.invars)
    if eqn.primitive.name == "while":
        if sub is not eqn.params["body_jaxpr"].jaxpr:
            return {}
        outer = outer[eqn.params["cond_nconsts"]:]
    if len(outer) != len(sub.invars):
        return {}
    return {o: i for o, i in zip(outer, sub.invars)
            if isinstance(o, Var)}


def _walk(jaxpr, tables, stack=(), in_loop=False):
    """(scopes, in the placement loop's body, attribute tables read whole)
    of every equation that holds no other, through every sub-jaxpr;
    ``tables``: {variable: name} of the attribute tables in ``jaxpr``."""
    for eqn in jaxpr.eqns:
        scopes = stack + _scopes(str(eqn.source_info.name_stack))
        subs = [s for v in eqn.params.values() for s in _subjaxprs(v)]
        if not subs:
            yield scopes, in_loop, sorted(
                tables[v] for v in eqn.invars
                if isinstance(v, Var) and v in tables)
        for sub in subs:
            arrives = _inner(eqn, sub)
            yield from _walk(
                sub, {arrives[v]: n for v, n in tables.items() if v in arrives},
                scopes,
                in_loop or (eqn.primitive.name == "while"
                            and "place_scan" in scopes
                            and sub is eqn.params["body_jaxpr"].jaxpr),
            )


FUSED = ("fused_place_batch", "fused_place_batch_live",
         "sharded_fused_place_batch", "sharded_fused_place_batch_live")


@pytest.fixture(scope="module")
def traced():
    """{(entry, variant): the equations of the entry traced at the lint
    grid} for the four fused entries, wide and plain."""
    out = {}
    for name in FUSED:
        c = contracts.get(name)
        for variant in ("wide", "plain"):
            g = c.trace_grids[0]._replace(features=FEATURES[variant])
            operands = c.operands(g)
            closed = jax.make_jaxpr(functools.partial(
                c.build(g), **c.static_kwargs(g)))(*operands)
            fields = type(operands[0])._fields
            tables = {closed.jaxpr.invars[fields.index(f)]: f
                      for f in ATTR_TABLES}
            out[name, variant] = list(_walk(closed.jaxpr, tables))
    return out


ENTRIES = [(e, v) for e in FUSED for v in ("wide", "plain")]


@pytest.mark.parametrize("entry,variant", ENTRIES)
def test_the_loop_body_holds_no_static_feasibility(traced, entry, variant):
    eqns = traced[entry, variant]
    body = [s for s, in_loop, _ in eqns if in_loop]
    assert len(body) > 100, "the walk lost the placement loop's body"
    stray = {s for s in body
             if "feasibility" in s and "distinct_property" not in s}
    assert not stray, stray
    # ... and it did not vanish: it is computed before the loop.
    assert any("feasibility" in s and "place_scan" not in s
               for s, in_loop, _ in eqns if not in_loop)
    if FEATURES[variant].dp_width:
        assert any("distinct_property" in s for s in body)


@pytest.mark.parametrize("entry,variant", ENTRIES)
def test_the_loop_body_reads_no_attribute_table_whole(traced, entry, variant):
    eqns = traced[entry, variant]
    reads = [(s, t) for s, in_loop, t in eqns if in_loop and t]
    # the picked row's values, for the spread and distinct_property counts
    assert reads and all("update" in s for s, _ in reads), reads
    assert all(t == ["attr_hash"] for _, t in reads), reads
    before = {n for s, in_loop, t in eqns if not in_loop for n in t
              if "feasibility" in s or "affinity_spread" in s}
    assert before == set(ATTR_TABLES), before


# -- values ------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("features",))
def _step_anew(arrays, req, carry, penalty, class_elig, host_mask, features):
    """One placement step as the scan made it before PR 47: every term of
    the ranking from scratch (``score_nodes``), the arg-max, the commit."""
    used, tg_cnt, s_hash, s_counts, dp_cnt = carry
    req_step = req._replace(s_value_hash=s_hash)
    res = kernels.score_nodes(
        arrays, used, tg_cnt, s_counts, penalty, req_step, class_elig,
        host_mask, features, dp_cnt=dp_cnt)
    counts = (
        jnp.sum(res.feasible).astype(jnp.int32),
        jnp.sum(~res.feasible & arrays.eligible).astype(jnp.int32),
        jnp.sum(res.feasible & ~res.fits).astype(jnp.int32),
    )
    row = jnp.argmax(res.final).astype(jnp.int32)
    ok = res.final[row] > kernels.NEG_INF / 2
    inv = kernels.lane_invariants(
        arrays, req_step, class_elig, host_mask, features)
    return kernels._commit_step(
        kernels.ONE_DEVICE, arrays, inv, carry, req_step, res, counts,
        jnp.where(ok, row, -1), features, 0)


def _scan_anew(ops, lane, n_placements, features):
    """Lane ``lane`` of ``ops`` through ``n_placements`` steps of
    ``_step_anew``: (P, 7) in the ``PACKED_*`` column order."""
    arrays, used, drows, dvals, tg, sc, pen, reqs, ce, hm, _ = ops
    req = jax.tree_util.tree_map(lambda x: x[lane], reqs)
    live = drows[lane] >= 0
    used0 = jnp.asarray(used).at[drows[lane][live]].add(dvals[lane][live])
    inv = kernels.lane_invariants(arrays, req, ce[lane], hm[lane], features)
    carry = kernels.scan_carry(
        inv, req, used0, jnp.asarray(tg[lane]), jnp.asarray(sc[lane]),
        features)
    rows = []
    for _ in range(n_placements):
        carry, out = _step_anew(
            arrays, req, carry, pen[lane], ce[lane], hm[lane], features)
        rows.append([np.asarray(o, np.float32) for o in out[:7]])
    return np.array(rows, np.float32)


NODES, LANES, STEPS = 320, 4, 3


@pytest.mark.parametrize("seed", [47, 2 ** 31 + 47])
@pytest.mark.parametrize("variant", sorted(FEATURES))
def test_once_a_launch_equals_anew_in_every_step(variant, seed):
    features = FEATURES[variant]
    ops = random_launch(seed, NODES, LANES, features, steps=STEPS)
    arrays = jax.tree_util.tree_map(jnp.asarray, ops[0])
    once = solo_reference(arrays, ops[2:10], STEPS, features=features)
    placed = 0
    for lane in range(LANES):
        anew = _scan_anew((arrays,) + ops[1:], lane, STEPS, features)
        assert once[lane].tobytes() == anew.tobytes(), (lane, once[lane], anew)
        placed += int((anew[:, kernels.PACKED_ROW] >= 0).sum())
    assert placed >= LANES, "the operands place too little to show anything"


@pytest.fixture(scope="module")
def mesh(eight_devices):
    return sharding.make_mesh(4, batch=2)


@pytest.mark.parametrize("route", ["one_device", "mesh"])
@pytest.mark.parametrize("variant", ["plain", "wide", "full"])
def test_a_lane_of_the_batched_program_equals_its_solo_scan(
        variant, route, mesh):
    features = FEATURES[variant]
    ops = random_launch(47, NODES, LANES, features, steps=STEPS)
    arrays = jax.tree_util.tree_map(jnp.asarray, ops[0])
    solo = solo_reference(arrays, ops[2:10], STEPS, features=features)
    if route == "mesh":
        fn = sharding.sharded_fused_place_batch(mesh, STEPS)
        placed = sharding.shard_matrix_arrays(mesh, arrays)
        launch = lambda steps: fn(
            placed, *ops[1:10], steps, features=features)
    else:
        launch = lambda steps: kernels.fused_place_batch(
            arrays, *ops[1:10], steps, n_placements=STEPS, features=features)
    for lane in range(LANES):
        # One live lane: nothing of the launch contends with it, so it is
        # its solo scan bit for bit (every step asked for, so no inert row).
        steps = np.zeros(LANES, np.int32)
        steps[lane] = STEPS
        out = np.asarray(launch(steps))
        got = out[lane, :, :kernels.PACKED_WIDTH].copy()
        # (the batched program's FILTERED carries the limit's flag as + 0.5)
        got[:, kernels.PACKED_FILTERED] = np.floor(
            got[:, kernels.PACKED_FILTERED])
        assert got.tobytes() == solo[lane].tobytes(), (lane, got, solo[lane])
        assert (out[np.arange(LANES) != lane, :, kernels.PACKED_ROW] == -1).all()


@pytest.mark.parametrize("variant", ["plain", "wide", "full"])
def test_the_mesh_equals_one_device_with_every_lane_live(variant, mesh):
    features = FEATURES[variant]
    ops = random_launch(2 ** 31 + 47, NODES, LANES, features, steps=STEPS)
    one = np.asarray(kernels.fused_place_batch(
        *ops, n_placements=STEPS, features=features))
    fn = sharding.sharded_fused_place_batch(mesh, STEPS)
    arrays = sharding.shard_matrix_arrays(
        mesh, jax.tree_util.tree_map(jnp.asarray, ops[0]))
    many = np.asarray(fn(arrays, *ops[1:], features=features))
    assert one.tobytes() == many.tobytes()
    assert (one[:, :, kernels.PACKED_ROW] >= 0).sum() >= LANES
