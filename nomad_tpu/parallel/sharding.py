"""Sharded scheduling step over a ``jax.sharding.Mesh``.

Mesh axes and their roles (the sharding design the scaling-book recipe
produces for this workload):

- ``node``  — the cluster matrix's node axis, sharded like sequence/tensor
  dims in an ML model. Every (N, ...) array in ``DeviceArrays`` plus the
  usage matrix splits along it. Feasibility/scoring is row-parallel, so each
  shard scores its own nodes with zero communication; only the final
  *argmax* crosses shards (one ``pmax`` pair over ICI — the analog of a
  ring-attention score reduction).
- ``batch`` — independent evaluations, sharded like data-parallel batches.
  Each batch shard picks winners locally; the resulting usage deltas are
  ``psum``-ed across the batch axis (the gradient-all-reduce analog) so every
  replica applies the same state update.

Reference behaviors preserved: the step scores all nodes per eval (replacing
stack.go:78-91's candidate sampling), applies proposed usage like
BinPackIterator's proposed-alloc accounting (rank.go:210-323), and leaves
conflict resolution to the serialized plan applier (plan_apply.go:49-69) —
batched picks are optimistic by design.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.encode import SchedRequest, pow2_bucket
from ..ops.kernels import (
    FULL_FEATURES,
    NEG_INF,
    apply_spread_values,
    fused_trip_counts,
    inert_step_outputs,
    pack_fused_lanes,
    scan_steps,
    score_nodes,
    spread_values_at,
)
from ..state.matrix import DeviceArrays

# Hierarchical top-k width: each node shard contributes its k best rows to
# the (shards, k) candidate table.  Any k >= 1 preserves exact argmax parity
# (the global winner is always some shard's per-shard maximum, and
# jax.lax.top_k is stable so the lowest-index occurrence of that maximum is
# always in the table); PARITY.md "Hierarchical top-k" documents the
# tie-break proof.  k = 1 is the fast path: XLA lowers top_k with k > 1
# inside the shard_map scan to a full sort of the (n_local,) scores —
# measured 2x end-to-end on the 100K-node sweep — while k = 1 stays the
# single-pass max+argmax.  Widen only for a future multi-winner selection
# that actually consumes the extra rows.
TOPK_K = 1


def make_mesh(
    n_devices: Optional[int] = None, batch: Optional[int] = None
) -> Mesh:
    """A 2-D ('batch', 'node') mesh over the first ``n_devices`` devices.

    ``batch`` defaults to 2 when the device count is even (so both axes get
    exercised), else 1.
    """
    devs = jax.devices()
    n = n_devices if n_devices is not None else len(devs)
    assert len(devs) >= n, (
        f"requested {n} devices but only {len(devs)} visible — set "
        "XLA_FLAGS=--xla_force_host_platform_device_count=N for a virtual mesh"
    )
    if batch is None:
        batch = 2 if n % 2 == 0 and n >= 2 else 1
    assert n % batch == 0, f"{n} devices not divisible by batch={batch}"
    arr = np.array(devs[:n]).reshape(batch, n // batch)
    return Mesh(arr, axis_names=("batch", "node"))


def node_shard_count(mesh: Mesh) -> int:
    """Width of the mesh's node axis — the number of home shards the
    matrix partitions rows across when this mesh is live.

    This is the number that shrinks on a shard evacuation: the
    coalescer drops its compiled entry points, rebuilds the mesh over
    the surviving devices (``make_mesh(survivors)``), and the matrix
    re-lays-out to this width (``relayout_shards``) so the sharded
    kernels' ``row_offset = shard * n_local`` arithmetic keeps every
    row owned by exactly one shard (scheduler/coalescer.py
    ``evacuate_shard`` / ``heal_shard_evacuations``).
    """
    return int(dict(zip(mesh.axis_names, mesh.devices.shape))["node"])


def stack_requests(reqs: Sequence[SchedRequest]) -> SchedRequest:
    """Stack B per-eval requests into one batched pytree (leading B axis).

    Trailing padding in the per-predicate dimensions (constraints,
    affinities, static ports, datacenters) is narrowed to the batch's
    actual maximum, pow2-bucketed so the jit cache stays bounded.  The
    per-predicate column gathers are the dominant HBM traffic of a batched
    dispatch (see kernels._check_predicate); typical jobs use 2-4 of the
    16 constraint slots, so this cuts the gather volume ~4x.
    """
    stacked = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *reqs)

    def width(active: np.ndarray, cap: int) -> int:
        count = int(active.sum(axis=1).max()) if len(active) else 0
        return min(cap, pow2_bucket(max(1, count)))

    cw = width(stacked.c_slot >= 0, stacked.c_slot.shape[1])
    aw = width(stacked.a_slot >= 0, stacked.a_slot.shape[1])
    pw = width(stacked.p_static >= 0, stacked.p_static.shape[1])
    dw = width(stacked.dc_hash != 0, stacked.dc_hash.shape[1])
    return stacked._replace(
        c_slot=stacked.c_slot[:, :cw],
        c_op=stacked.c_op[:, :cw],
        c_hash=stacked.c_hash[:, :cw],
        c_num=stacked.c_num[:, :cw],
        a_slot=stacked.a_slot[:, :aw],
        a_op=stacked.a_op[:, :aw],
        a_hash=stacked.a_hash[:, :aw],
        a_num=stacked.a_num[:, :aw],
        a_weight=stacked.a_weight[:, :aw],
        p_static=stacked.p_static[:, :pw],
        dc_hash=stacked.dc_hash[:, :dw],
    )


def build_batch_inputs(matrix, requests: Sequence[SchedRequest]) -> dict:
    """Assemble the batched tensors ``score_batch``/``sharded_schedule_step``
    consume, for B evals with no in-flight plan state: zero TG counts and
    spread counts, no penalties, all classes eligible, no host mask.

    Shared by bench.py, __graft_entry__, and tests — the shapes (class-count
    padding in particular) must stay in sync with the kernel.
    """
    reqs = jax.tree_util.tree_map(
        jnp.asarray, stack_requests(list(requests))
    )
    b = len(requests)
    n = matrix.capacity
    pad = pow2_bucket(max(1, len(matrix.class_ids)))
    return dict(
        reqs=reqs,
        tg_counts=jnp.zeros((b, n), jnp.int32),
        spread_counts=jnp.zeros(
            (b,) + requests[0].s_value_hash.shape, jnp.float32
        ),
        penalties=jnp.zeros((b, n), bool),
        class_eligs=jnp.ones((b, pad), bool),
        host_masks=jnp.ones((b, n), bool),
    )


# PartitionSpecs for the matrix arrays: every (N, ...) leaf splits on 'node'.
_ARRAYS_SPEC = DeviceArrays(
    totals=P("node", None),
    used=P("node", None),
    eligible=P("node"),
    attr_hash=P("node", None),
    attr_num=P("node", None),
    attr_ver=P("node", None),
    class_id=P("node"),
    dev_total=P("node", None),
    dev_used=P("node", None),
    prio_used=P("node", None, None),
    port_words=P("node", None),
    dyn_used=P("node"),
)

# Batched request: every leaf has a leading B axis, replicated over 'node'.
_REQS_SPEC = SchedRequest(
    ask=P("batch", None),
    c_slot=P("batch", None),
    c_op=P("batch", None),
    c_hash=P("batch", None),
    c_num=P("batch", None),
    dc_hash=P("batch", None),
    dev_ask=P("batch", None),
    algorithm=P("batch"),
    desired_count=P("batch"),
    a_slot=P("batch", None),
    a_op=P("batch", None),
    a_hash=P("batch", None),
    a_num=P("batch", None),
    a_weight=P("batch", None),
    s_slot=P("batch", None),
    s_weight=P("batch", None),
    s_even=P("batch", None),
    s_value_hash=P("batch", None, None),
    s_desired=P("batch", None, None),
    s_implicit=P("batch", None),
    s_sum_weights=P("batch"),
    preempt_bucket=P("batch"),
    distinct_hosts=P("batch"),
    p_static=P("batch", None),
    p_dyn=P("batch"),
)


def shard_matrix_arrays(mesh: Mesh, arrays: DeviceArrays) -> DeviceArrays:
    """Lay the matrix out across the mesh's 'node' axis."""
    # zip over NamedTuple fields — PartitionSpec is itself a tuple, so
    # tree_map would wrongly recurse into it.
    return DeviceArrays(
        *(
            jax.device_put(x, NamedSharding(mesh, spec))
            for x, spec in zip(arrays, _ARRAYS_SPEC)
        )
    )


def make_sharded_row_scatter(mesh: Mesh):
    """Build the jitted dirty-row scatter into a mesh-RESIDENT matrix.

    ``scatter(device, idx, *row_data) -> DeviceArrays`` updates rows
    ``idx`` of the sharded snapshot with fresh host values; out_shardings
    pins every output leaf to the same 'node' layout, so XLA routes each
    row to the shard that owns it — the incremental alternative to
    re-laying the full matrix through ``shard_matrix_arrays`` per dispatch
    (state/matrix.py sync_sharded).  No donation: in-flight pipelined
    dispatches may still be reading the previous snapshot's buffers.
    """
    out_shardings = DeviceArrays(
        *(NamedSharding(mesh, spec) for spec in _ARRAYS_SPEC)
    )

    def scat(d, i, *vals):
        return DeviceArrays(
            **{
                f: getattr(d, f).at[i].set(v)
                for f, v in zip(DeviceArrays._fields, vals)
            }
        )

    return jax.jit(scat, out_shardings=out_shardings)


def _step_local(arrays, used, tg_counts, spread_counts, penalties, reqs,
                class_eligs, host_masks):
    """Per-shard body. Local shapes: arrays/used are (N/n, ...); batched
    inputs are (B/b, ...) with node-sized trailing dims already (N/n)."""
    n_local = used.shape[0]
    shard = jax.lax.axis_index("node")
    row_offset = shard * n_local

    def one(tg, sc, pen, req, ce, hm):
        res = score_nodes(arrays, used, tg, sc, pen, req, ce, hm)
        local_row = jnp.argmax(res.final).astype(jnp.int32)
        local_ok = res.final[local_row] > NEG_INF / 2

        # Cross-shard argmax over the node axis: one pmax for the score, one
        # to elect the owning shard's global row (ties break to highest row).
        score = jnp.where(local_ok, res.final[local_row], NEG_INF)
        best = jax.lax.pmax(score, "node")
        candidate = jnp.where(
            local_ok & (score == best), row_offset + local_row, -1
        )
        row = jax.lax.pmax(candidate, "node")
        ok = best > NEG_INF / 2
        row = jnp.where(ok, row, -1)
        win = (row >= row_offset) & (row < row_offset + n_local)
        pre = jax.lax.pmax(
            jnp.where(
                win & ok, res.needs_preempt[local_row], False
            ).astype(jnp.int32),
            "node",
        ).astype(bool)
        evaluated = jax.lax.psum(
            jnp.sum(res.feasible.astype(jnp.int32)), "node"
        )
        # Failed placements report score 0.0, matching score_batch /
        # place_task_group so consumers can aggregate without re-masking.
        return row, jnp.where(ok, best, 0.0), pre, evaluated, req.ask

    rows, scores, pre, evaluated, asks = jax.vmap(one)(
        tg_counts, spread_counts, penalties, reqs, class_eligs, host_masks
    )

    # State update (the "optimizer step"): scatter each winner's ask into
    # this shard's usage rows, then psum the deltas across the batch axis so
    # every batch replica applies every pick.
    local_rows = rows - row_offset
    mine = (local_rows >= 0) & (local_rows < n_local)
    safe = jnp.clip(local_rows, 0, n_local - 1)
    delta = jnp.zeros_like(used).at[safe].add(
        jnp.where(mine[:, None], asks, 0.0)
    )
    delta = jax.lax.psum(delta, "batch")
    return rows, scores, pre, evaluated, used + delta


def sharded_schedule_step(mesh: Mesh):
    """Build the jitted SPMD scheduling step for ``mesh``.

    Returns ``step(arrays, used, tg_counts, spread_counts, penalties, reqs,
    class_eligs, host_masks) -> (rows, scores, preempted, nodes_evaluated,
    used_after)`` — B optimistic placements plus the updated (still sharded)
    usage matrix.
    """
    fn = shard_map(
        _step_local,
        mesh=mesh,
        in_specs=(
            _ARRAYS_SPEC,
            P("node", None),  # used
            P("batch", "node"),  # tg_counts
            P("batch", None, None),  # spread_counts
            P("batch", "node"),  # penalties
            _REQS_SPEC,
            P("batch", None),  # class_eligs
            P("batch", "node"),  # host_masks
        ),
        out_specs=(
            P("batch"),
            P("batch"),
            P("batch"),
            P("batch"),
            P("node", None),
        ),
    )
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# Sharded dispatch-coalescer kernel (the LIVE multi-chip path)
# ---------------------------------------------------------------------------


def _place_batch_local(
    arrays, used, delta_rows, delta_vals, tg_counts, spread_counts,
    penalties, reqs, class_eligs, host_masks, n_placements,
):
    """Per-shard body of the coalescer's ``place_batch`` (ops/kernels.py:659)
    under a ('batch', 'node') mesh: each shard scores its own node rows, the
    per-placement argmax crosses shards over ICI (pmax score + pmin row, so
    ties break to the lowest global row exactly like the single-device
    ``jnp.argmax``), and the winning shard alone applies the usage/tg-count
    update.  Spread-count updates need the winning node's attribute values,
    which live on one shard — the owner broadcasts them with a psum.
    """
    n_local = used.shape[0]
    shard = jax.lax.axis_index("node")
    row_offset = shard * n_local
    big = jnp.int32(2 ** 30)

    def one(drows, dvals, tg, sc, pen, req, ce, hm):
        # Sparse in-flight plan deltas arrive as GLOBAL rows; each shard
        # applies the slice it owns.
        local = drows - row_offset
        mine = (drows >= 0) & (local >= 0) & (local < n_local)
        safe = jnp.clip(local, 0, n_local - 1)
        used0 = used.at[safe].add(jnp.where(mine[:, None], dvals, 0.0))

        def step(carry, _):
            u, tg_cnt, s_hash, s_counts = carry
            req_step = req._replace(s_value_hash=s_hash)
            res = score_nodes(
                arrays, u, tg_cnt, s_counts, pen, req_step, ce, hm
            )
            lrow = jnp.argmax(res.final).astype(jnp.int32)
            lok = res.final[lrow] > NEG_INF / 2
            score = jnp.where(lok, res.final[lrow], NEG_INF)
            best = jax.lax.pmax(score, "node")
            cand = jnp.where(
                lok & (score == best), row_offset + lrow, big
            )
            grow = jax.lax.pmin(cand, "node")  # lowest row wins ties
            ok = best > NEG_INF / 2
            grow = jnp.where(ok, grow, -1)
            owner = ok & (grow >= row_offset) & (grow < row_offset + n_local)
            lwin = jnp.clip(grow - row_offset, 0, n_local - 1)

            n_eval = jax.lax.psum(
                jnp.sum(res.feasible.astype(jnp.int32)), "node"
            )
            n_filt = jax.lax.psum(
                jnp.sum((~res.feasible & arrays.eligible).astype(jnp.int32)),
                "node",
            )
            n_exh = jax.lax.psum(
                jnp.sum((res.feasible & ~res.fits).astype(jnp.int32)), "node"
            )

            u2 = jnp.where(owner, u.at[lwin].add(req.ask), u)
            tg2 = jnp.where(owner, tg_cnt.at[lwin].add(1), tg_cnt)

            # Winning node's per-stanza attr values: owner computes, psum
            # broadcasts (hash 0 = "no value", so non-owners contribute 0).
            nvals = jnp.where(
                owner, spread_values_at(arrays, req_step, lwin), 0
            )
            nvals = jax.lax.psum(nvals, "node")
            new_hash, new_counts = apply_spread_values(
                s_counts, req_step, nvals
            )
            s_hash2 = jnp.where(ok, new_hash, s_hash)
            s_counts2 = jnp.where(ok, new_counts, s_counts)

            binp = jax.lax.psum(
                jnp.where(owner, res.binpack[lwin], 0.0), "node"
            )
            pre = jax.lax.pmax(
                jnp.where(
                    owner, res.needs_preempt[lwin], False
                ).astype(jnp.int32),
                "node",
            ).astype(bool)
            out = (
                grow,
                jnp.where(ok, best, 0.0),
                jnp.where(ok, binp, 0.0),
                pre & ok,
                n_eval,
                n_filt,
                n_exh,
            )
            return (u2, tg2, s_hash2, s_counts2), out

        init = (used0, tg, req.s_value_hash, sc)
        _, outs = jax.lax.scan(step, init, None, length=n_placements)
        rows, scores, binpack, pre, ne, nf, nx = outs
        return jnp.stack(
            [
                rows.astype(jnp.float32),
                scores,
                binpack,
                pre.astype(jnp.float32),
                ne.astype(jnp.float32),
                nf.astype(jnp.float32),
                nx.astype(jnp.float32),
            ],
            axis=1,
        )  # (P, 7) — kernels.PACKED_* layout

    return jax.vmap(one)(
        delta_rows, delta_vals, tg_counts, spread_counts, penalties, reqs,
        class_eligs, host_masks,
    )


def sharded_place_batch(mesh: Mesh, n_placements: int):
    """Build the jitted SPMD twin of ``kernels.place_batch`` for ``mesh``.

    Same signature and packed (B, P, PACKED_WIDTH) result as the unsharded
    kernel, so the dispatch coalescer swaps it in transparently when the
    server runs on a multi-chip slice (scheduler/coalescer.py).  Placement
    parity with the single-device kernel is exact (tie-breaks included) —
    tests/test_parallel.py asserts it.
    """
    fn = shard_map(
        functools.partial(_place_batch_local, n_placements=n_placements),
        mesh=mesh,
        in_specs=(
            _ARRAYS_SPEC,
            P("node", None),  # used
            P("batch", None),  # delta_rows (global ids, replicated on node)
            P("batch", None, None),  # delta_vals
            P("batch", "node"),  # tg_counts
            P("batch", None, None),  # spread_counts
            P("batch", "node"),  # penalties
            _REQS_SPEC,
            P("batch", None),  # class_eligs
            P("batch", "node"),  # host_masks
        ),
        out_specs=P("batch", None, None),
    )
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# Sharded FUSED megakernel (hierarchical top-k + sharded AllocsFit verify)
# ---------------------------------------------------------------------------


def _fused_place_batch_local(
    arrays, used, delta_rows, delta_vals, tg_counts, spread_counts,
    penalties, reqs, class_eligs, host_masks, lane_steps, n_placements,
    features,
):
    """Per-shard body of ``kernels.fused_place_batch`` under a
    ('batch', 'node') mesh — the full megakernel (ranking scan + cross-lane
    AllocsFit re-verify) with the node axis partitioned.

    Ranking is a hierarchical top-k: each shard scores only its local node
    slice and contributes its ``k = min(TOPK_K, n_local)`` best rows via one
    ``all_gather`` over ICI, producing a tiny (shards, k) candidate table
    replicated on every shard.  The global winner is the table's max score,
    ties broken to the LOWEST global row — ``jax.lax.top_k`` is stable
    (lower index first on ties), so the per-shard maximum's lowest local
    occurrence is always in the table and the min-over-ties selection
    reproduces the single-device ``jnp.argmax`` bit-for-bit (PARITY.md
    "Hierarchical top-k").  No (B, N) score tensor ever exists globally:
    per-shard intermediates are (n_local,) and everything crossing the
    interconnect or reaching the host is O(B · P) or (shards, k).

    The cross-lane verify gathers only winner rows + asks + in-flight
    deltas over the batch axis (all O(B · P), node-shape-free), scans all B
    lanes against the LOCAL (n_local, 3) usage slice with non-owned rows
    vacuously fitting, and combines verdicts with a single ``pmin`` over
    the node axis — each row's owner alone decides.

    Both loops run as many iterations as the launch's live lanes asked for
    (``lane_steps``, as in the single-device kernel).  Every step holds
    collectives over 'node' and the verify replays all B lanes on every
    shard, so the trip counts are taken over the WHOLE batch (the counts
    are all_gathered over 'batch'): one number on every shard.
    """
    n_local = used.shape[0]
    shard = jax.lax.axis_index("node")
    row_offset = shard * n_local
    big = jnp.int32(2 ** 30)
    k = min(TOPK_K, n_local)
    live = lane_steps > 0  # (b_local,)
    g_steps = jax.lax.all_gather(lane_steps, "batch", tiled=True)  # (B,)
    trip, last_lane = fused_trip_counts(g_steps, n_placements)

    def vary(x, axes=("batch",)):
        # shard_map's varying-axes check wants a loop carry typed the same
        # going in as coming out: buffers that start as constants (or as
        # this shard's slice) and take per-lane values are cast to vary
        # over those axes up front — a typing formality.
        return jax.lax.pcast(x, axes, to="varying")

    def one(drows, dvals, tg, sc, pen, req, ce, hm, n_steps):
        local = drows - row_offset
        mine = (drows >= 0) & (local >= 0) & (local < n_local)
        safe = jnp.clip(local, 0, n_local - 1)
        used0 = used.at[safe].add(jnp.where(mine[:, None], dvals, 0.0))

        def step(carry, i):
            u, tg_cnt, s_hash, s_counts = carry
            active = i < n_steps
            req_step = req._replace(s_value_hash=s_hash)
            with jax.named_scope("score"):
                res = score_nodes(
                    arrays, u, tg_cnt, s_counts, pen, req_step, ce, hm,
                    features=features,
                )
            # Hierarchical top-k: (n_local,) -> per-shard (k,) candidates,
            # then a cross-shard reduce of the implicit (shards, k) table —
            # pmax elects the winning score, pmin the lowest owning row.
            with jax.named_scope("pick"):
                vals, idxs = jax.lax.top_k(res.final, k)
                best = jax.lax.pmax(vals[0], "node")
                ok = (best > NEG_INF / 2) & active
                cand = jnp.where(
                    vals == best, row_offset + idxs.astype(jnp.int32), big
                )
                # lowest row on ties
                grow = jax.lax.pmin(jnp.min(cand), "node")
                grow = jnp.where(ok, grow, -1)
                owner = (
                    ok & (grow >= row_offset) & (grow < row_offset + n_local)
                )
                lwin = jnp.clip(grow - row_offset, 0, n_local - 1)

                n_eval = jax.lax.psum(
                    jnp.sum(res.feasible.astype(jnp.int32)), "node"
                )
                n_filt = jax.lax.psum(
                    jnp.sum(
                        (~res.feasible & arrays.eligible).astype(jnp.int32)
                    ),
                    "node",
                )
                n_exh = jax.lax.psum(
                    jnp.sum((res.feasible & ~res.fits).astype(jnp.int32)),
                    "node",
                )

            with jax.named_scope("update"):
                u2 = jnp.where(owner, u.at[lwin].add(req.ask), u)
                tg2 = jnp.where(owner, tg_cnt.at[lwin].add(1), tg_cnt)

                nvals = jnp.where(
                    owner, spread_values_at(arrays, req_step, lwin), 0
                )
                nvals = jax.lax.psum(nvals, "node")
                new_hash, new_counts = apply_spread_values(
                    s_counts, req_step, nvals
                )
                s_hash2 = jnp.where(ok, new_hash, s_hash)
                s_counts2 = jnp.where(ok, new_counts, s_counts)

                binp = jax.lax.psum(
                    jnp.where(owner, res.binpack[lwin], 0.0), "node"
                )
                pre = jax.lax.pmax(
                    jnp.where(
                        owner, res.needs_preempt[lwin], False
                    ).astype(jnp.int32),
                    "node",
                ).astype(bool)
            out = (
                grow,
                jnp.where(ok, best, 0.0),
                jnp.where(ok, binp, 0.0),
                pre & ok,
                jnp.where(active, n_eval, 0),
                jnp.where(active, n_filt, 0),
                jnp.where(active, n_exh, 0),
            )
            return (u2, tg2, s_hash2, s_counts2), out

        init = (used0, tg, req.s_value_hash, sc)
        bufs = tuple(vary(o) for o in inert_step_outputs(n_placements))
        with jax.named_scope("place_scan"):
            _, outs = scan_steps(step, init, bufs, trip)
        return outs  # each (P,)

    rows, scores, binpack, pre, ne, nf, nx = jax.vmap(one)(
        delta_rows, delta_vals, tg_counts, spread_counts, penalties, reqs,
        class_eligs, host_masks, lane_steps,
    )
    rows = jnp.where(live[:, None], rows, -1)  # (b_local, P)

    # Cross-lane AllocsFit re-verify, sharded: every tensor gathered over
    # the batch axis is winner-row-shaped — (B, P) rows, (B, 3) asks,
    # (B, K) / (B, K, 3) in-flight deltas, (B,) liveness — never node-axis
    # shaped.  Each node shard then replays all B lanes in resolve order
    # against its local (n_local, 3) usage slice; rows it does not own fit
    # vacuously, and one pmin over 'node' lets each row's owner veto.
    g_rows = jax.lax.all_gather(rows, "batch", tiled=True)  # (B, P)
    g_ask = jax.lax.all_gather(reqs.ask, "batch", tiled=True)  # (B, 3)
    g_drows = jax.lax.all_gather(delta_rows, "batch", tiled=True)  # (B, K)
    g_dvals = jax.lax.all_gather(delta_vals, "batch", tiled=True)
    g_live = g_steps > 0  # (B,)

    def lane_step(b, state):
        cum_used, fits_all = state
        l_rows, l_ask, l_live = g_rows[b], g_ask[b], g_live[b]
        l_drows, l_dvals = g_drows[b], g_dvals[b]
        l_local = l_drows - row_offset
        l_mine = (
            (l_drows >= 0) & (l_local >= 0) & (l_local < n_local) & l_live
        )
        l_safe = jnp.clip(l_local, 0, n_local - 1)
        base = cum_used.at[l_safe].add(
            jnp.where(l_mine[:, None], l_dvals, 0.0)
        )

        def p_step(u, p):
            row = l_rows[p]
            p_local = row - row_offset
            p_mine = (
                (row >= 0) & (p_local >= 0) & (p_local < n_local) & l_live
            )
            p_safe = jnp.clip(p_local, 0, n_local - 1)
            u2 = u.at[p_safe].add(jnp.where(p_mine, l_ask, 0.0))
            fit = jnp.all(u2[p_safe] <= arrays.totals[p_safe]) | ~p_mine
            return u2, (fit,)

        after, (fits,) = scan_steps(p_step, base, (fits_all[b],), trip)
        return (
            jnp.where(l_live, after, cum_used),
            jax.lax.dynamic_update_index_in_dim(fits_all, fits, b, 0),
        )

    # The carry starts as this shard's usage slice (varying over 'node'
    # only) but accumulates lane data gathered over 'batch' (every batch
    # replica holds the same values).  Lanes past the last live one and
    # slots past the largest count are never visited and read "fits".
    with jax.named_scope("verify_scan"):
        _, fits_all = jax.lax.fori_loop(
            0, last_lane, lane_step,
            (vary(used), vary(jnp.ones(g_rows.shape, bool), ("batch", "node"))),
        )  # (B, P) bool, identical on every node shard only after the pmin:
        verified = jax.lax.pmin(fits_all.astype(jnp.int32), "node")  # (B, P)

    b_local = rows.shape[0]
    b_idx = jax.lax.axis_index("batch")
    v_local = jax.lax.dynamic_slice_in_dim(
        verified, b_idx * b_local, b_local, axis=0
    )  # (b_local, P)
    return pack_fused_lanes(
        rows, scores, binpack, pre, ne, nf, nx, v_local, live
    )


def sharded_fused_place_batch(mesh: Mesh, n_placements: int):
    """Build the jitted SPMD twin of ``kernels.fused_place_batch``.

    Same signature (``features`` keyword-static) and packed
    (B, P, FUSED_PACKED_WIDTH) result as the single-device fused kernel —
    the dispatch coalescer swaps it in when a mesh is configured and
    ``NOMAD_TPU_SHARDED_MEGABATCH`` is not disabled.  Placement AND
    verify-column parity with the unsharded kernel is exact (tie-breaks
    included) — tests/test_parallel.py asserts it across shard counts.
    """

    def entry(
        arrays, used, delta_rows, delta_vals, tg_counts, spread_counts,
        penalties, reqs, class_eligs, host_masks, lane_steps, *,
        features=FULL_FEATURES,
    ):
        fn = shard_map(
            functools.partial(
                _fused_place_batch_local,
                n_placements=n_placements,
                features=features,
            ),
            mesh=mesh,
            in_specs=(
                _ARRAYS_SPEC,
                P("node", None),  # used
                P("batch", None),  # delta_rows (global ids)
                P("batch", None, None),  # delta_vals
                P("batch", "node"),  # tg_counts
                P("batch", None, None),  # spread_counts
                P("batch", "node"),  # penalties
                _REQS_SPEC,
                P("batch", None),  # class_eligs
                P("batch", "node"),  # host_masks
                P("batch"),  # lane_steps
            ),
            out_specs=P("batch", None, None),
        )
        return fn(
            arrays, used, delta_rows, delta_vals, tg_counts, spread_counts,
            penalties, reqs, class_eligs, host_masks, lane_steps,
        )

    return jax.jit(entry, static_argnames=("features",))
