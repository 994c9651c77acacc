"""The device-contract table for the jaxpr-level semantic gate.

Each :class:`DeviceContract` row declares, for one registered device
entry point, the properties :mod:`nomad_tpu.lint.jaxprpass` proves from
the *traced program* (not the source text):

* which abstract configuration grid to trace under (two node counts so
  J102 can assert node-count independence of the device→host fetch);
* the device→host output-byte budget per launch (``None`` exempts an
  entry whose outputs are deliberately device-resident, e.g. the matrix
  scatter);
* the donation set — which positional operands the entry declares
  donated, checked against what actually survives ``lower()`` /
  ``compile()``;
* the compile-cache ratchet — a concrete sweep (occupancy fills, per-lane
  step counts, pow2-padded dirty-row counts) plus the max number of
  distinct cache entries it may cost.

New policy heads (ROADMAP item 4) register a row here instead of a new
lint rule: add the entry to :func:`table` with its budget/donation/sweep
declaration and the J101–J105 checks apply unchanged.  STATIC_ANALYSIS.md
("Semantic passes") documents the schema and the rule catalog.

Everything in this module is import-gated on JAX: importing
:mod:`nomad_tpu.lint` stays backend-free, and :func:`table` is only
called from :func:`jaxprpass.run` after an availability check.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import numpy as np


class Grid(NamedTuple):
    """One point of the trace/compile configuration grid.

    ``live`` is the occupancy (how many of the ``batch`` lanes carry a
    real eval); ``steps`` is how many placements each of them asks for
    (0 = all ``placements``) — together the ``lane_steps`` fill.
    ``deltas`` is the in-flight delta-row count K.  ``features`` is the
    static :class:`nomad_tpu.ops.kernels.Features` bucket (``None`` for
    entry points that take no feature switch, e.g. the row scatter).
    """

    nodes: int
    batch: int
    placements: int
    deltas: int
    live: int
    features: Any = None
    steps: int = 0


@dataclass(frozen=True)
class DeviceContract:
    """One registered device entry point and its proven properties.

    ``build(grid)`` returns the jitted entry (factories like
    ``sharded_fused_place_batch`` are rebuilt per grid; module-level
    jitted functions just get returned).  ``operands(grid)`` returns a
    FRESH tuple of concrete numpy operands every call — freshness
    matters because donated entries consume their buffers during the
    J105 sweep.  ``static_kwargs(grid)`` is the static keyword set
    (``n_placements``/``features``) for entries that take one.
    """

    name: str
    path: str  # repo-relative, forward slashes — Finding's path
    build: Callable[[Grid], Callable[..., Any]]
    operands: Callable[[Grid], Tuple[Any, ...]]
    static_kwargs: Callable[[Grid], Dict[str, Any]]
    trace_grids: Tuple[Grid, ...]
    # J102: device→host bytes per launch; None = outputs are
    # device-resident by design (budget and node-independence both skipped).
    out_budget: Optional[Callable[[Grid], int]] = None
    # J102: bytes of the outputs that stay on the device by design beside
    # a fetched one (the placement programs' carry of claims blocks: the
    # next launch's operand).  Left out of the budget, not out of the
    # node-count independence: no output may be node-axis shaped.
    resident_out: Optional[Callable[[Grid], int]] = None
    # J104: positional argnums declared donated. Checked BOTH ways — a
    # declared-donated operand lowered undonated fires, and so does an
    # undeclared donation.
    donated_args: Tuple[int, ...] = ()
    # J104: keyword operands declared donated, checked both ways too.
    donated_kwargs: Tuple[str, ...] = ()
    # J103: entry is ALLOWED to emit node-axis-shaped outputs across the
    # mesh boundary (the scatter returns the resident matrix itself).
    node_axis_outputs_ok: bool = False
    # J103: shapes exempt from the boundary check, if a node count ever
    # collides with a shape a program declares it may move.
    boundary_exempt_shapes: Tuple[Tuple[int, ...], ...] = ()
    # J104: require an explicit input_output_alias in the compiled HLO.
    # The fused kernel's donated lane operands are scratch-reusable but
    # never output-ALIASED (no donated aval matches the packed (B, P, 8)
    # output); the live entry's carry is: the carry a launch hands on
    # takes the buffer of the carry it was handed.
    expect_alias: bool = False
    # J104/J105 run at this (small) grid; None skips both.
    compile_grid: Optional[Grid] = None
    # J105: concrete sweep returning the measured compile count.
    sweep: Optional[Callable[[Callable[..., Any], "DeviceContract"], int]] = None
    max_compiles: Optional[int] = None


# ---------------------------------------------------------------------------
# Concrete operand builders (numpy; make_jaxpr abstracts them, calls use them)
# ---------------------------------------------------------------------------


def _concrete_arrays(n: int) -> Any:
    from ..state.matrix import (
        ATTR_SLOTS,
        DEVICE_SLOTS,
        PORT_WORDS,
        PRIORITY_BUCKETS,
        DeviceArrays,
    )

    return DeviceArrays(
        totals=np.full((n, 3), 100.0, np.float32),
        used=np.zeros((n, 3), np.float32),
        eligible=np.ones((n,), bool),
        attr_hash=np.zeros((n, ATTR_SLOTS), np.int32),
        attr_num=np.zeros((n, ATTR_SLOTS), np.float32),
        attr_ver=np.zeros((n, ATTR_SLOTS), np.float32),
        class_id=np.zeros((n,), np.int32),
        dev_total=np.zeros((n, DEVICE_SLOTS), np.int32),
        dev_used=np.zeros((n, DEVICE_SLOTS), np.int32),
        prio_used=np.zeros((n, PRIORITY_BUCKETS, 3), np.float32),
        port_words=np.zeros((n, PORT_WORDS), np.uint32),
        dyn_used=np.zeros((n,), np.int32),
    )


def _concrete_reqs(b: int) -> Any:
    from ..ops.encode import (
        MAX_AFFINITIES,
        MAX_CONSTRAINTS,
        MAX_DATACENTERS,
        MAX_DISTINCT_PROPS,
        MAX_DISTINCT_VALUES,
        MAX_SPREAD_VALUES,
        MAX_SPREADS,
        MAX_STATIC_PORTS,
        SchedRequest,
    )
    from ..state.matrix import DEVICE_SLOTS

    f32, i32 = np.float32, np.int32
    return SchedRequest(
        ask=np.ones((b, 3), f32),
        c_slot=np.full((b, MAX_CONSTRAINTS), -1, i32),
        c_op=np.zeros((b, MAX_CONSTRAINTS), i32),
        c_hash=np.zeros((b, MAX_CONSTRAINTS), i32),
        c_num=np.zeros((b, MAX_CONSTRAINTS), f32),
        dc_hash=np.full((b, MAX_DATACENTERS), -1, i32),
        dev_ask=np.zeros((b, DEVICE_SLOTS), i32),
        algorithm=np.zeros((b,), i32),
        desired_count=np.ones((b,), f32),
        a_slot=np.full((b, MAX_AFFINITIES), -1, i32),
        a_op=np.zeros((b, MAX_AFFINITIES), i32),
        a_hash=np.zeros((b, MAX_AFFINITIES), i32),
        a_num=np.zeros((b, MAX_AFFINITIES), f32),
        a_weight=np.zeros((b, MAX_AFFINITIES), f32),
        s_slot=np.full((b, MAX_SPREADS), -1, i32),
        s_weight=np.zeros((b, MAX_SPREADS), f32),
        s_even=np.zeros((b, MAX_SPREADS), bool),
        s_value_hash=np.zeros((b, MAX_SPREADS, MAX_SPREAD_VALUES), i32),
        s_desired=np.zeros((b, MAX_SPREADS, MAX_SPREAD_VALUES), f32),
        s_implicit=np.zeros((b, MAX_SPREADS), f32),
        s_sum_weights=np.zeros((b,), f32),
        preempt_bucket=np.full((b,), -1, i32),
        distinct_hosts=np.zeros((b,), bool),
        p_static=np.full((b, MAX_STATIC_PORTS), -1, i32),
        p_dyn=np.zeros((b,), i32),
        # Slot 0 live: the rows trace the distinct_property stage too.
        dp_slot=np.tile(np.array([1, -1], i32)[:MAX_DISTINCT_PROPS], (b, 1)),
        dp_limit=np.ones((b, MAX_DISTINCT_PROPS), f32),
        dp_value_hash=np.zeros(
            (b, MAX_DISTINCT_PROPS, MAX_DISTINCT_VALUES), i32),
        dp_count=np.zeros((b, MAX_DISTINCT_PROPS, MAX_DISTINCT_VALUES), f32),
    )


def fused_operands(g: Grid) -> Tuple[Any, ...]:
    """The 11-operand tuple shared by every fused_place_batch variant."""
    from ..ops.encode import MAX_SPREAD_VALUES, MAX_SPREADS

    n, b, k = g.nodes, g.batch, g.deltas
    lane_steps = np.zeros((b,), np.int32)
    lane_steps[: g.live] = g.steps or g.placements
    return (
        _concrete_arrays(n),
        np.zeros((n, 3), np.float32),  # used
        np.full((b, k), -1, np.int32),  # delta_rows (-1 = no delta)
        np.zeros((b, k, 3), np.float32),  # delta_vals
        np.zeros((b, n), np.int32),  # tg_counts
        np.zeros((b, MAX_SPREADS, MAX_SPREAD_VALUES), np.float32),
        np.zeros((b, n), bool),  # penalties
        _concrete_reqs(b),
        # class_eligs: live, (b, pow2_bucket(n_classes)): a width of the
        # cluster's class count, whatever its node count or the lanes.
        np.ones((b, 1), bool),
        np.ones((b, n), bool),  # host_masks
        lane_steps,
    )


def _unpack_packs(g: Grid, class_pad: int = 1) -> Tuple[Any, ...]:
    """((request pack, lane pack), their layouts) as a launch of the server
    hands them to its one program: ``RequestSlab``'s and ``_staging``'s own
    buffers (so ``MAX_DELTA_ROWS`` delta rows a lane whatever ``g.deltas``),
    every lane a valid request, the first ``g.live`` asking for ``g.steps``
    placements; the class operand ``class_pad`` wide (a width of the
    cluster's class count, whatever its node count or the lanes)."""
    import jax

    from ..ops.encode import MAX_SPREAD_VALUES, MAX_SPREADS
    from ..scheduler.coalescer import DeviceCoalescer
    from ..state.matrix import NodeMatrix

    coal = DeviceCoalescer(NodeMatrix(capacity=g.nodes), max_lanes=g.batch)
    st, slab = coal._staging(
        g.nodes, class_pad, (MAX_SPREADS, MAX_SPREAD_VALUES))
    slab.fill(0, jax.tree_util.tree_map(lambda f: f[0], _concrete_reqs(1)))
    st["lane_steps"][: g.live] = g.steps or g.placements
    return (slab.pack, st["pack"]), (slab.layout, st["layout"])


def _live_carry(g: Grid) -> Any:
    """The carry a live launch is handed: blocks of padding, a lane's block
    as long as the staging slot's delta rows and the scan."""
    from ..scheduler.claims import empty_carry
    from ..scheduler.coalescer import MAX_DELTA_ROWS

    return empty_carry(g.batch, MAX_DELTA_ROWS + g.placements)


def packed_operands(g: Grid) -> Tuple[Any, ...]:
    """The eight operands of a live launch (``fused_place_batch_live`` and
    its mesh twin): the matrix, ``used``, the server's two packs, the three
    node-axis lane buffers and the carry of the launch before."""
    n, b = g.nodes, g.batch
    return (
        _concrete_arrays(n),
        np.zeros((n, 3), np.float32),  # used
        *_unpack_packs(g)[0],
        np.zeros((b, n), np.int32),  # tg_counts
        np.zeros((b, n), bool),  # penalties
        np.ones((b, n), bool),  # host_masks
        _live_carry(g),
    )


def scatter_operands(g: Grid) -> Tuple[Any, ...]:
    """(device, pack) for the dirty-row scatter, the pack as a sync of the
    server builds it (``NodeMatrix._pack_rows``: the rows' twelve fields
    and their index in one buffer); ``g.deltas`` is the (already
    pow2-padded) dirty-row count."""
    from ..state.matrix import NodeMatrix

    m = NodeMatrix(capacity=g.nodes)
    rows = np.arange(g.deltas, dtype=np.int32) % g.nodes
    return (m.sync_host(), m._pack_rows(rows))


# ---------------------------------------------------------------------------
# J105 sweeps — concrete call sequences whose compile cost is ratcheted
# ---------------------------------------------------------------------------


def _cache_size(entry: Callable[..., Any]) -> int:
    size = getattr(entry, "_cache_size", None)
    return int(size()) if callable(size) else 0


def _compiles_over(entry: Callable[..., Any], c: DeviceContract, grids) -> int:
    """Call the entry at each grid point (fresh operands per call —
    donated buffers are consumed) and return how many NEW compile-cache
    entries that cost."""
    import jax

    before = _cache_size(entry)
    for g in grids:
        out = entry(*c.operands(g), **c.static_kwargs(g))
        jax.block_until_ready(out)  # the compile must have really happened
    return _cache_size(entry) - before


def occupancy_sweep(entry: Callable[..., Any], c: DeviceContract) -> int:
    """Every occupancy fill 1..batch.  The contract: occupancy is a
    runtime value, so ONE compile serves all fills."""
    g = c.compile_grid
    assert g is not None
    return _compiles_over(
        entry, c, (g._replace(live=k) for k in range(1, g.batch + 1))
    )


def lane_steps_sweep(entry: Callable[..., Any], c: DeviceContract) -> int:
    """:func:`occupancy_sweep`, then every per-lane step count
    1..placements.  The contract: the loops' trip counts are read from the
    ``lane_steps`` operand, so the SAME compile serves all of them — a
    count that leaked into a static argument shows as one compile each."""
    g = c.compile_grid
    assert g is not None
    return occupancy_sweep(entry, c) + _compiles_over(
        entry, c, (g._replace(steps=k) for k in range(1, g.placements + 1))
    )


def pow2_rows_sweep(entry: Callable[..., Any], c: DeviceContract) -> int:
    """Scatter sweep: dirty-row counts 1..batch, padded as
    ``NodeMatrix._sync_locked`` pads them, so the distinct idx shapes —
    and therefore compiles — stay logarithmic in the row count."""
    from ..state.matrix import scatter_bucket

    g = c.compile_grid
    assert g is not None
    return _compiles_over(
        entry, c,
        (g._replace(deltas=scatter_bucket(k))
         for k in range(1, g.batch + 1)),
    )


# ---------------------------------------------------------------------------
# The table
# ---------------------------------------------------------------------------

# Trace grids: two node counts (prime-ish, colliding with no slot width,
# batch, placement, or delta dimension) prove node-count independence;
# the third point swaps the static Features bucket.  Kept moderate —
# tracing cost is per-equation, not per-element.
_N_A, _N_B = 97, 159


def _fused_trace_grids() -> Tuple[Grid, ...]:
    from ..ops.kernels import FULL_FEATURES, Features

    narrow = Features(c_width=0, a_width=0, s_width=0, preempt=False,
                      ports=False, dp_width=0)
    base = Grid(nodes=_N_A, batch=6, placements=3, deltas=5, live=6,
                features=FULL_FEATURES)
    return (base, base._replace(nodes=_N_B), base._replace(features=narrow))


def _fused_compile_grid() -> Grid:
    from ..ops.kernels import Features

    narrow = Features(c_width=0, a_width=0, s_width=0, preempt=False,
                      ports=False, dp_width=0)
    # The live scan length, so the step-count sweep covers 1..16.
    return Grid(nodes=32, batch=4, placements=16, deltas=4, live=4, features=narrow)


def _fused_budget(g: Grid) -> int:
    # One packed (B, P, FUSED_PACKED_WIDTH) f32 fetch: 32 B per
    # placement-row per eval, whatever the node count.
    from ..ops.kernels import FUSED_PACKED_WIDTH

    return g.batch * g.placements * FUSED_PACKED_WIDTH * 4


def table() -> Tuple[DeviceContract, ...]:
    """The registered device entry points.  Built lazily (imports jax)."""
    from ..ops import kernels
    from ..parallel import sharding
    from ..state import matrix

    def overlay(g: Grid) -> Tuple[Any, Any]:
        # The in-flight claims overlay as the coalescer hands it over: a few
        # rows a lane, -1 padded (scheduler/claims.py).
        return (
            np.full((g.batch, 2), -1, np.int32),
            np.zeros((g.batch, 2, 3), np.float32),
        )

    def chain(g: Grid) -> Tuple[Any, Any, Any]:
        # The claims chained on the device as the coalescer hands them
        # over: the carry of the launch before (blocks of padding), the
        # flags and what each lane's plan advertises on its delta rows.
        from ..scheduler.claims import CHAIN_DEPTH, empty_carry

        return (
            empty_carry(g.batch, g.deltas + g.placements),
            np.zeros((g.batch, 1 - (-CHAIN_DEPTH // g.batch)), bool),
            np.zeros((g.batch, g.deltas, 3), np.float32),
        )

    def carry_bytes(g: Grid) -> int:
        return int(chain(g)[0].nbytes)

    def live_carry_bytes(g: Grid) -> int:
        return int(_live_carry(g).nbytes)

    fused_kwargs = lambda g: {
        "n_placements": g.placements, "features": g.features,
        "overlay": overlay(g), "chain": chain(g),
    }
    trace_grids = _fused_trace_grids()
    compile_grid = _fused_compile_grid()

    def build_sharded(g: Grid) -> Callable[..., Any]:
        # Deterministic 1-device (1, 1) mesh: collectives and the
        # shard_map boundary are present in the trace regardless of the
        # physical shard count, so the contract holds wherever it runs.
        mesh = sharding.make_mesh(1, batch=1)
        return sharding.sharded_fused_place_batch(mesh, g.placements)

    def build_sharded_live(g: Grid) -> Callable[..., Any]:
        mesh = sharding.make_mesh(1, batch=1)
        return sharding.sharded_fused_place_batch_live(mesh, g.placements)

    scatter_grid = Grid(nodes=_N_A, batch=4, placements=1, deltas=4, live=4)
    # Both dirty-row scatters, one chip's and the mesh's: one body
    # (``matrix.scatter_packed``), one packed host operand.
    scatter_contract: Dict[str, Any] = dict(
        operands=scatter_operands,
        static_kwargs=lambda g: {},
        trace_grids=(scatter_grid, scatter_grid._replace(nodes=_N_B)),
        out_budget=None,  # outputs ARE the device-resident matrix
        node_axis_outputs_ok=True,
        # launches in flight still read the old snapshot; the pack is a
        # host buffer with nothing to give back
        donated_args=(),
        compile_grid=scatter_grid._replace(nodes=32),
        sweep=pow2_rows_sweep,
        max_compiles=2,  # pow2 buckets of 1..4 dirty rows: {2, 4}
    )
    return (
        DeviceContract(
            name="fused_place_batch",
            path="nomad_tpu/ops/kernels.py",
            build=lambda g: kernels.fused_place_batch,
            operands=fused_operands,
            static_kwargs=fused_kwargs,
            trace_grids=trace_grids,
            out_budget=_fused_budget,
            resident_out=carry_bytes,
            donated_args=(),  # the un-donated entry: tests/tools reuse inputs
            compile_grid=compile_grid,
        ),
        DeviceContract(
            name="fused_place_batch_live",
            path="nomad_tpu/ops/kernels.py",
            build=lambda g: kernels.fused_place_batch_live,
            operands=packed_operands,
            static_kwargs=lambda g: {
                "layouts": _unpack_packs(g)[1],
                "n_placements": g.placements, "features": g.features,
            },
            trace_grids=trace_grids,
            out_budget=_fused_budget,
            resident_out=live_carry_bytes,
            # The node-axis lane buffers, and the carry: it has one reader.
            # The packs are views of a staging slot, read until the launch
            # resolves.
            donated_args=(4, 5, 6, 7),
            expect_alias=True,  # carry in -> carry out, in place
            compile_grid=compile_grid,
            sweep=lane_steps_sweep,
            # occupancy and step counts are runtime data, and the packs'
            # shapes do not know the fill: ONE compile
            max_compiles=1,
        ),
        DeviceContract(
            name="sharded_fused_place_batch",
            path="nomad_tpu/parallel/sharding.py",
            build=build_sharded,
            operands=fused_operands,
            static_kwargs=lambda g: {
                "features": g.features, "overlay": overlay(g),
                "chain": chain(g),
            },
            trace_grids=trace_grids,
            out_budget=_fused_budget,
            resident_out=carry_bytes,  # split over 'batch', never fetched
            donated_args=(),  # tests, the smoke and the tools reuse inputs
        ),
        DeviceContract(
            name="sharded_fused_place_batch_live",
            path="nomad_tpu/parallel/sharding.py",
            build=build_sharded_live,
            operands=packed_operands,
            static_kwargs=lambda g: {
                "layouts": _unpack_packs(g)[1], "features": g.features,
            },
            trace_grids=trace_grids,
            out_budget=_fused_budget,
            resident_out=live_carry_bytes,
            # matrix stays shared with in-flight dispatches; the carry is a
            # few hundred KB a device, not worth a donation of its own
            donated_args=(),
            compile_grid=compile_grid,
            sweep=lane_steps_sweep,
            max_compiles=1,
        ),
        DeviceContract(
            name="make_row_scatter",
            path="nomad_tpu/state/matrix.py",
            build=lambda g: matrix.make_row_scatter(),
            **scatter_contract,
        ),
        DeviceContract(
            name="make_sharded_row_scatter",
            path="nomad_tpu/parallel/sharding.py",
            # The (1, 1) mesh of ``build_sharded``: the out_shardings are
            # in the lowering whatever the physical shard count.
            build=lambda g: sharding.make_sharded_row_scatter(
                sharding.make_mesh(1, batch=1)
            ),
            **scatter_contract,
        ),
    )


def get(name: str) -> DeviceContract:
    for c in table():
        if c.name == name:
            return c
    raise KeyError(name)
