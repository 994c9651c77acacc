"""The jaxpr-level contract gate, tested from both sides.

One half proves the analyzer itself: five mutant entry points — an
injected ``io_callback``, a full-score-vector return, a node-axis value
pushed through a collective, a dropped donation, an occupancy-keyed
static arg — each built to violate exactly ONE of J101–J105 while
honoring every other contract clause, so each test asserts the rule set
is precisely ``{its rule}``.  A clean twin asserts the empty set, so a
check that started firing spuriously is caught the same way as one that
went blind.

The other half is the live gate: the real contract table
(:mod:`nomad_tpu.lint.contracts`) runs against the real tree, riding
tier-1 alongside ``tests/test_lint_gate.py``, including the acceptance
claim that ONE compile of ``fused_place_batch_live`` serves every
occupancy fill (measured from the real compile cache, not inferred).
"""

from __future__ import annotations

import functools

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402

try:
    from jax import shard_map
except ImportError:  # older jax
    from jax.experimental.shard_map import shard_map
from jax.sharding import PartitionSpec as P  # noqa: E402

from nomad_tpu.lint import load_baseline, repo_root, split_baselined  # noqa: E402
from nomad_tpu.lint import contracts, jaxprpass  # noqa: E402
from nomad_tpu.lint.contracts import DeviceContract, Grid  # noqa: E402
from nomad_tpu.parallel.sharding import make_mesh  # noqa: E402

pytestmark = pytest.mark.skipif(
    not jaxprpass.available(), reason="no JAX backend"
)

# ---------------------------------------------------------------------------
# The mini entry-point family: same contract shape as the fused kernel
# (node-axis operand, per-lane operands, lane steps, (B, 1) packed result)
# at a fraction of the trace/compile cost.
# ---------------------------------------------------------------------------

N1, N2 = 37, 53  # prime markers: collide with no other dimension


def mini_operands(g: Grid):
    cols = np.ones((g.nodes, 3), np.float32)  # node-axis resident operand
    ops = np.ones((g.batch, 4), np.float32)  # per-lane operand (donated)
    lane_steps = np.zeros((g.batch,), np.int32)
    lane_steps[: g.live] = g.steps or g.placements
    return (cols, ops, lane_steps)


def _mini_body(cols, ops, lane_steps):
    w = jnp.where((lane_steps > 0)[:, None], ops, 0.0)
    return w.sum(axis=1, keepdims=True) + 0.0 * cols.sum()  # (B, 1)


TRACE_GRIDS = (
    Grid(nodes=N1, batch=4, placements=1, deltas=1, live=4),
    Grid(nodes=N2, batch=4, placements=1, deltas=1, live=4),
)
COMPILE_GRID = Grid(nodes=16, batch=4, placements=1, deltas=1, live=4)


def mini_contract(build, **over) -> DeviceContract:
    kw = dict(
        name="mini",
        path="tests/test_jaxprpass.py",
        build=build,
        operands=mini_operands,
        static_kwargs=lambda g: {},
        trace_grids=TRACE_GRIDS,
        out_budget=lambda g: g.batch * 4,  # the (B, 1) f32 verdict column
        donated_args=(1, 2),
        compile_grid=COMPILE_GRID,
        sweep=contracts.occupancy_sweep,
        max_compiles=1,
    )
    kw.update(over)
    return DeviceContract(**kw)


def rules(findings):
    return {f.rule for f in findings}


def test_clean_mini_entry_fires_nothing():
    entry = jax.jit(_mini_body, donate_argnums=(1, 2))
    fs = jaxprpass.check_contract(mini_contract(lambda g: entry))
    assert rules(fs) == set(), [f.render() for f in fs]


def test_j101_injected_io_callback_fires_only_j101():
    from jax.experimental import io_callback

    def body(cols, ops, lane_steps):
        io_callback(lambda a: None, None, ops)  # the host round trip
        return _mini_body(cols, ops, lane_steps)

    entry = jax.jit(body, donate_argnums=(1, 2))
    fs = jaxprpass.check_contract(mini_contract(lambda g: entry))
    assert rules(fs) == {"J101"}, [f.render() for f in fs]


def test_j102_full_score_vector_return_fires_only_j102():
    def body(cols, ops, lane_steps):
        # The classic regression: "just return the scores too" — an O(N)
        # value fetched from device to host, on every launch.
        return _mini_body(cols, ops, lane_steps), cols.sum(axis=1)

    entry = jax.jit(body, donate_argnums=(1, 2))
    fs = jaxprpass.check_contract(mini_contract(lambda g: entry))
    assert rules(fs) == {"J102"}, [f.render() for f in fs]
    # Both halves of J102 must have fired: over budget AND node-dependent.
    msgs = " | ".join(f.message for f in fs)
    assert "budget" in msgs and "node count" in msgs


def test_j103_node_axis_collective_fires_only_j103():
    mesh = make_mesh(1, batch=1)

    def local(cols, ops, lane_steps):
        # An (n_local,)-shaped value pushed through a collective: the
        # mesh moves O(N) bytes per launch however small the result.
        leak = jax.lax.psum(cols[:, 0], "batch")
        anchor = jax.lax.pmax(leak.sum(), "node")
        w = jnp.where((lane_steps > 0)[:, None], ops, 0.0)
        return w.sum(axis=1, keepdims=True) + 0.0 * anchor

    entry = jax.jit(
        shard_map(
            local,
            mesh=mesh,
            in_specs=(P("node", None), P("batch", None), P("batch")),
            out_specs=P("batch", None),
        )
    )
    fs = jaxprpass.check_contract(
        mini_contract(lambda g: entry, donated_args=())
    )
    assert rules(fs) == {"J103"}, [f.render() for f in fs]


def test_j104_dropped_donation_fires_only_j104():
    entry = jax.jit(_mini_body)  # donate_argnums went missing in a refactor
    fs = jaxprpass.check_contract(mini_contract(lambda g: entry))
    assert rules(fs) == {"J104"}, [f.render() for f in fs]


def test_j104_undeclared_donation_fires_only_j104():
    entry = jax.jit(_mini_body, donate_argnums=(0, 1, 2))  # cols is shared!
    fs = jaxprpass.check_contract(mini_contract(lambda g: entry))
    assert rules(fs) == {"J104"}, [f.render() for f in fs]


def test_j105_occupancy_keyed_static_arg_fires_only_j105():
    @functools.partial(
        jax.jit, static_argnames=("n_live",), donate_argnums=(1, 2)
    )
    def body(cols, ops, lane_steps, *, n_live):
        # Occupancy in the static key: every fill level recompiles.
        w = ops[:n_live]
        return _mini_body(cols, ops, lane_steps) + w.sum()

    fs = jaxprpass.check_contract(
        mini_contract(
            lambda g: body,
            static_kwargs=lambda g: {"n_live": int(g.live)},
        )
    )
    assert rules(fs) == {"J105"}, [f.render() for f in fs]


def test_j105_step_count_keyed_static_arg_fires_only_j105():
    """The loops' trip count leaked into the static key: the occupancy
    fills all share one compile, each step count 1..P costs its own."""
    @functools.partial(
        jax.jit, static_argnames=("trip",), donate_argnums=(1, 2)
    )
    def body(cols, ops, lane_steps, *, trip):
        return _mini_body(cols, ops, lane_steps) * jnp.arange(trip).sum()

    fs = jaxprpass.check_contract(
        mini_contract(
            lambda g: body,
            static_kwargs=lambda g: {"trip": int(g.steps or g.placements)},
            compile_grid=COMPILE_GRID._replace(placements=4),
            sweep=contracts.lane_steps_sweep,
        )
    )
    assert rules(fs) == {"J105"}, [f.render() for f in fs]
    assert any("cost 4 compile" in f.message for f in fs), fs


def test_j103_catches_the_j005_helper_evasion():
    """Companion to tests/test_lint.py (TestJ005NodeAxisFetch): threading
    the node-axis value through ONE helper function defeats the AST
    rule's local-variable tracking — but the traced program still shows
    an N-shaped output escaping the mesh boundary, whatever the call
    graph looked like.  This is why both layers exist."""
    mesh = make_mesh(1, batch=1)

    def _snapshot(x):  # the one-hop indirection J005 cannot see through
        return x * 2.0

    def local(cols, ops, lane_steps):
        w = jnp.where((lane_steps > 0)[:, None], ops, 0.0)
        verdict = w.sum(axis=1, keepdims=True) + 0.0 * jax.lax.pmax(
            cols.sum(), "node"
        )
        return verdict, _snapshot(cols)

    entry = jax.jit(
        shard_map(
            local,
            mesh=mesh,
            in_specs=(P("node", None), P("batch", None), P("batch")),
            out_specs=(P("batch", None), P("node", None)),
        )
    )
    fs = jaxprpass.check_contract(
        mini_contract(
            lambda g: entry,
            donated_args=(),
            out_budget=None,  # isolate the boundary check
            sweep=None,
            max_compiles=None,
            compile_grid=None,
        )
    )
    assert rules(fs) == {"J103"}, [f.render() for f in fs]
    assert any("escapes the mesh boundary" in f.message for f in fs)


def test_harness_breakage_surfaces_as_j100():
    def broken_build(g):
        raise RuntimeError("entry point renamed out from under the table")

    fs = jaxprpass.check_contract(mini_contract(broken_build))
    assert rules(fs) == {"J100"}


# ---------------------------------------------------------------------------
# The live gate: real contract table vs the real tree.
# ---------------------------------------------------------------------------


def test_live_tree_contracts_clean_against_baseline():
    findings = jaxprpass.run(repo_root())
    new, _suppressed, _stale = split_baselined(findings, load_baseline())
    assert new == [], "jaxpr contract findings:\n" + "\n".join(
        f.render() for f in new
    )


def test_j105_one_compile_serves_all_occupancy_fills():
    """The acceptance claim, asserted from the real compile cache: the
    live fused entry's occupancy sweep (fill 1..B) costs at most one new
    cache entry — lane occupancy is runtime data, never a static key."""
    c = contracts.get("fused_place_batch_live")
    assert c.max_compiles == 1
    entry = c.build(c.compile_grid)
    measured = contracts.occupancy_sweep(entry, c)
    assert measured <= 1, f"occupancy sweep cost {measured} compiles"


def test_j105_one_compile_serves_all_step_counts():
    """Per-lane step counts 1..16 are runtime data too: after the
    occupancy fills, the whole step sweep of the live fused entry (scan
    length 16) adds no compile-cache entry."""
    c = contracts.get("fused_place_batch_live")
    assert c.sweep is contracts.lane_steps_sweep
    assert c.compile_grid.placements == 16
    entry = c.build(c.compile_grid)
    contracts.occupancy_sweep(entry, c)  # the one compile, if still due
    before = contracts._cache_size(entry)
    assert contracts.lane_steps_sweep(entry, c) == 0
    assert contracts._cache_size(entry) == before


@pytest.mark.parametrize(
    "name", ["fused_place_batch_live", "sharded_fused_place_batch_live"]
)
def test_j105_the_packed_entries_compile_once_for_every_fill(name):
    """A live entry's operands are the server's own packs (``RequestSlab``'s
    and ``_staging``'s layouts), whose shapes do not know the fill, the
    three node-axis buffers and the carry; the packs are not donated (views
    of a staging slot, read until the launch resolves); fills 1..64 and
    every step count after the first launch cost no compile."""
    from nomad_tpu.ops.encode import SchedRequest
    from nomad_tpu.ops.kernels import LANE_FIELDS

    c = contracts.get(name)
    assert c.sweep is contracts.lane_steps_sweep and c.max_compiles == 1
    assert not {2, 3} & set(c.donated_args) and c.donated_kwargs == ()
    g = c.compile_grid
    packs, layouts = contracts._unpack_packs(g._replace(live=3, steps=2))
    assert [len(lay) for lay in layouts] == [
        len(SchedRequest._fields), len(LANE_FIELDS)]
    assert all(p.dtype == np.uint8 and p.shape[0] == g.batch for p in packs)
    operands = c.operands(g)
    assert len(operands) == 8 and all(
        a.tobytes() == b.tobytes()
        for a, b in zip(operands[2:4], contracts._unpack_packs(g)[0]))
    assert c.static_kwargs(g)["layouts"] == layouts
    entry = c.build(g)
    assert contracts.lane_steps_sweep(entry, c) <= 1
    assert contracts.lane_steps_sweep(entry, c) == 0


@pytest.mark.parametrize(
    "name", ["make_row_scatter", "make_sharded_row_scatter"]
)
def test_row_scatter_takes_one_packed_operand_and_a_program_a_bucket(name):
    """Both dirty-row scatters are registered, take the matrix and ONE
    packed uint8 operand (the rows' twelve fields and their index, as a
    sync of the server builds it), donate nothing (launches in flight read
    the old snapshot), and 1..4 dirty rows cost the two pow2 buckets
    {2, 4}, then nothing."""
    from nomad_tpu.state.matrix import DeviceArrays, scatter_bucket

    c = contracts.get(name)
    assert c.sweep is contracts.pow2_rows_sweep and c.max_compiles == 2
    assert c.donated_args == () and c.donated_kwargs == ()
    assert c.out_budget is None and c.node_axis_outputs_ok
    g = c.compile_grid
    for k in range(1, g.batch + 1):
        device, pack = c.operands(g._replace(deltas=scatter_bucket(k)))
        assert isinstance(device, DeviceArrays)
        assert pack.dtype == np.uint8 and pack.ndim == 2
        assert pack.shape[0] == scatter_bucket(k)
    assert jaxprpass.check_contract(c) == []
    entry = c.build(g)
    assert contracts.pow2_rows_sweep(entry, c) <= 2
    assert contracts.pow2_rows_sweep(entry, c) == 0
    out = entry(*c.operands(g))
    assert all(
        np.asarray(x).shape[0] == g.nodes for x in out
    ), "the scatter returns the resident matrix"


def test_contract_table_names_every_registered_entry():
    names = {c.name for c in contracts.table()}
    assert names == {
        "fused_place_batch",
        "fused_place_batch_live",
        "sharded_fused_place_batch",
        "sharded_fused_place_batch_live",
        "make_row_scatter",
        "make_sharded_row_scatter",
    }
