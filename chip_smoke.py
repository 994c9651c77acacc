#!/usr/bin/env python3
"""chip_smoke.py — prove the live scheduling path on the attached chip.

One process (the only one that touches JAX) drives, at the repo's headline
deployment size — 10,000 nodes (capacity 10,240) carrying the aggregated
usage of 2,000,000 allocations, seeded from ``--seed``:

1. **library level, full width** — one call each of ``fused_place_batch``
   (64 lanes x 10,240 rows x 16 placements), ``place_task_group``,
   ``system_feasible``, ``verify_plan_fit`` and the dirty-row scatter, each compared on the host with its numpy twin in
   ``ops/fake_device.py`` (rows, preempted flags, VERIFIED column and the
   node counters exactly equal); with several devices visible also
   ``sharded_fused_place_batch`` against the unsharded kernel, bit for bit;
2. **live level** — an ``Agent`` built exactly as ``nomad agent`` builds it
   (server only, ``node_capacity = 10240``, default lanes and pipeline
   depth, heartbeats armed and served), 10,000 registered nodes, and a few
   dozen jobs through the HTTP API: service binpack, batch with spread +
   affinity, constrained, one system job pinned to a rack, then a
   higher-priority job that must preempt.  Allocations are read back over
   HTTP and checked with plain numpy/Python against the seeded cluster;
3. **the device did the work** — ``/v1/health`` reads breaker closed, no
   degraded / slow / wedged dispatch; fused dispatches happened; the matrix
   was uploaded once per device mirror and scattered after; the resident
   ``used`` array is a ``jax.Array`` on the accelerator (on every device of
   the mesh when there are several) and equals the host mirror.

Exit code 0 and a last stdout line ``{"ok": true, "device": {"platform",
"kind", "count"}}`` (those keys and no others) only when every check passed
on an accelerator; the line before it, ``report: {...}``, carries sizes,
cold/warm seconds, counters and parity results.  With no accelerator (or
``NOMAD_TPU_FAKE_DEVICE`` set, or outside a checkout) it exits non-zero
and prints no result.  ``--region-scale 10`` is the 100,000-node region
(capacity 102,400); ``--sharded-only`` runs, on several chips, the
sharded entry against the unsharded one and nothing else (the four-chip
check of ``c2m-100k``: ``--sharded-only --region-scale 10``).
``--cpu-rehearsal`` is for debugging this script on a CPU: it establishes
nothing about the chip, says so, and reports ``"ok": false``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time
import traceback

NODES, CAPACITY, ALLOCS = 10_000, 10_240, 2_000_000
REHEARSAL_TINY = (480, 512, 96_000)
RACK = 5  # the system job's rack (nodes i with i % 32 == RACK)
HEARTBEAT_PERIOD_S = 3.0  # well inside the server's 10 s minimum TTL
EVAL_TIMEOUT_S = 900.0  # covers a chain of cold compiles


class SmokeFailure(Exception):
    """A check did not hold; the message says which."""


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(f"[{time.strftime('%H:%M:%S')}] {msg}", flush=True)


class CompileMeter:
    """Counts XLA backend compiles and persistent-cache traffic through
    jax.monitoring, so compile seconds are measured, not inferred."""

    def __init__(self):
        import jax.monitoring as mon

        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def mark(self):
        return (self.compiles, self.compile_s)

    def since(self, mark) -> dict:
        return {
            "compiles": self.compiles - mark[0],
            "compile_s": round(self.compile_s - mark[1], 2),
        }


# ---------------------------------------------------------------------------
# Library level
# ---------------------------------------------------------------------------


def _cold_warm(meter: CompileMeter, call):
    """Run ``call`` twice, fetching the result both times: (value, stats)
    with the first call's wall and compile seconds and the second's wall."""
    import numpy as np

    mark = meter.mark()
    t = time.perf_counter()
    out = np.asarray(call())
    cold = time.perf_counter() - t
    stats = meter.since(mark)
    t = time.perf_counter()
    np.asarray(call())
    stats.update(cold_s=round(cold, 2), warm_s=round(time.perf_counter() - t, 4))
    return out, stats


def _compare_packed(name: str, dev, twin, fused: bool) -> dict:
    """Exact agreement of everything that decides a placement."""
    import numpy as np

    from nomad_tpu.ops import kernels as K

    exact = {
        "rows": K.PACKED_ROW,
        "preempted": K.PACKED_PREEMPT,
        "nodes_evaluated": K.PACKED_EVALUATED,
        "nodes_filtered": K.PACKED_FILTERED,
        "nodes_exhausted": K.PACKED_EXHAUSTED,
    }
    if fused:
        exact["verified"] = K.FUSED_PACKED_VERIFIED
    check(dev.shape == twin.shape, f"{name}: shape {dev.shape} != {twin.shape}")
    check(bool(np.isfinite(dev).all()), f"{name}: non-finite output")
    for label, col in exact.items():
        bad = np.argwhere(dev[..., col] != twin[..., col])
        if bad.size:
            idx = tuple(bad[0])
            detail = ""
            if label == "rows":
                detail = (
                    f"; device score {dev[idx + (K.PACKED_SCORE,)]!r} vs "
                    f"twin score {twin[idx + (K.PACKED_SCORE,)]!r}"
                )
            raise SmokeFailure(
                f"{name}: {label} differ from the numpy twin at "
                f"{len(bad)} position(s), first {idx}: device "
                f"{dev[idx + (col,)]!r} vs twin {twin[idx + (col,)]!r}{detail}"
            )
    score_err = float(
        np.abs(
            dev[..., [K.PACKED_SCORE, K.PACKED_BINPACK]]
            - twin[..., [K.PACKED_SCORE, K.PACKED_BINPACK]]
        ).max()
    )
    check(score_err < 1e-4, f"{name}: scores off by {score_err}")
    stats = {
        "placed": int((dev[..., K.PACKED_ROW] >= 0).sum()),
        "preempting": int((dev[..., K.PACKED_PREEMPT] != 0).sum()),
        "max_score_err": score_err,
    }
    if fused:
        # The in-launch resolution at work: picks moved off a node that
        # earlier lanes had claimed, and conflicts left to the applier.
        placed = dev[..., K.PACKED_ROW] >= 0
        stats["repicked"] = int((dev[..., K.FUSED_PACKED_VERIFIED] == 2.0).sum())
        stats["unresolved"] = int(
            (placed & (dev[..., K.FUSED_PACKED_VERIFIED] == 0.0)).sum()
        )
    return stats


def _same_bits(a, b) -> bool:
    import numpy as np

    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and a.dtype == b.dtype and a.tobytes() == b.tobytes()


def library_level(meter: CompileMeter, sizes, seed: int,
                  sharded_only: bool = False) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from nomad_tpu import mock, simcluster
    from nomad_tpu.ops import fake_device, kernels
    from nomad_tpu.ops.encode import RequestEncoder, RequestSlab, pow2_bucket
    from nomad_tpu.scheduler.coalescer import MAX_DELTA_ROWS
    from nomad_tpu.scheduler.stack import PLACEMENT_CHUNK
    from nomad_tpu.server.server import ServerConfig
    from nomad_tpu.state.matrix import DeviceArrays
    from nomad_tpu.structs.types import Constraint, Op

    nodes, capacity, allocs = sizes
    lanes = ServerConfig().coalescer_lanes
    scan = PLACEMENT_CHUNK
    rng = np.random.default_rng(seed)
    out: dict = {"lanes": lanes, "placements": scan}

    t0 = time.perf_counter()
    m = simcluster.build_cluster(nodes, capacity, allocs, seed=seed)
    shapes = simcluster.build_requests(m)
    enc = RequestEncoder(m)
    # One request that needs preemption: high priority, and more cpu than
    # any node has left (the seeded usage is at least ~850 of 3,900 MHz;
    # with room anywhere there is no eviction).
    pjob = mock.job(priority=90)
    pjob.task_groups[0].tasks[0].resources.cpu = 3200
    pjob.task_groups[0].tasks[0].resources.memory_mb = 2600
    preempting = enc.compile(
        pjob, pjob.task_groups[0], preemption_enabled=True
    ).request
    sjob = mock.system_job(datacenters=["dc1", "dc2", "dc3", "dc4"])
    sjob.task_groups[0].constraints = [
        Constraint("${attr.rack}", f"r{RACK}", Op.EQ.value)
    ]
    system_req = enc.compile(sjob, sjob.task_groups[0]).request
    out["build_s"] = round(time.perf_counter() - t0, 2)

    reqs = [(shapes + [preempting])[i % (len(shapes) + 1)] for i in range(lanes)]
    live = lanes - 4  # the last lanes stay dead: masks must hold
    slab = RequestSlab(lanes)
    for i, r in enumerate(reqs):
        slab.fill(i, r)
    stacked = slab.batch()
    feats = kernels.features_of(slab.live_view(live))
    out["features"] = feats._asdict()
    n = capacity
    pad = pow2_bucket(max(1, len(m.class_ids)))
    dr = np.full((lanes, MAX_DELTA_ROWS), -1, np.int32)
    dv = np.zeros((lanes, MAX_DELTA_ROWS, 3), np.float32)
    for lane in (3, 7, 11):  # in-flight plan deltas on a few lanes
        picks = rng.choice(nodes, 4, replace=False)
        dr[lane, :4] = picks
        dv[lane, :4] = rng.uniform(50.0, 400.0, (4, 3)).astype(np.float32)
    tg = np.zeros((lanes, n), np.int32)
    sc = np.zeros((lanes,) + reqs[0].s_value_hash.shape, np.float32)
    pen = np.zeros((lanes, n), bool)
    ce = np.ones((lanes, pad), bool)
    hm = np.ones((lanes, n), bool)
    hm[:, nodes:] = False
    lm = np.arange(lanes) < live
    # Per-lane step counts: every live lane the whole scan (what a launch
    # costs at most), and the live traffic's mix (1-8 a lane, one dead
    # lane in between): the loops then stop at the widest lane's count.
    ls = np.where(lm, scan, 0).astype(np.int32)
    ls_mixed = np.where(lm, 1 + (np.arange(lanes) * 5) % 8, 0).astype(np.int32)
    ls_mixed[20] = 0

    arrays = m.sync()
    host = m.sync_host()
    check(m.full_uploads == 1, "library: first sync was not one full upload")
    out["platforms"] = sorted(d.platform for d in arrays.used.devices())
    out["matrix_bytes"] = int(sum(a.nbytes for a in host))
    lane_lists = (
        list(dr), list(dv), list(tg), list(sc), list(pen), reqs, list(ce),
        list(hm),
    )

    results = out["entry_points"] = {}
    n_dev = len(jax.devices())
    if sharded_only:
        # The sharded entry against the unsharded one and nothing else: the
        # numpy twins and the other entry points are the full run's.
        check(n_dev > 1, "--sharded-only needs several devices")
        results["sharded_fused_place_batch"] = _library_sharded(
            meter, m, arrays, (dr, dv, tg, sc, pen, stacked, ce, hm),
            (ls_mixed, ls), feats, scan, n_dev,
        )
        return out

    def run(name, call, twin, fused):
        log(f"library: {name} (cold)")
        dev, stats = _cold_warm(meter, call)
        stats.update(_compare_packed(name, dev, twin(), fused))
        results[name] = stats
        log(f"library: {name} {stats}")
        return dev

    fused = run(
        "fused_place_batch",
        lambda: kernels.fused_place_batch(
            arrays, arrays.used, dr, dv, tg, sc, pen, stacked, ce, hm, ls,
            n_placements=scan, features=feats,
        ),
        lambda: fake_device.fused_place_batch(
            host, host.used, *lane_lists, lane_mask=lm, n_placements=scan
        ),
        fused=True,
    )
    check(
        results["fused_place_batch"]["preempting"] > 0,
        "library: the preempting request never needed preemption",
    )
    mixed = run(
        "fused_place_batch_mixed_steps",
        lambda: kernels.fused_place_batch(
            arrays, arrays.used, dr, dv, tg, sc, pen, stacked, ce, hm,
            ls_mixed, n_placements=scan, features=feats,
        ),
        lambda: fake_device.fused_place_batch(
            host, host.used, *lane_lists, lane_mask=lm, n_placements=scan,
            live_counts=list(ls_mixed),
        ),
        fused=True,
    )
    check(
        not results["fused_place_batch_mixed_steps"]["compiles"],
        "library: step counts compiled the fused kernel again",
    )
    vcol = kernels.FUSED_PACKED_VERIFIED
    for lane, n in enumerate(ls_mixed):
        # Same work: the steps a lane asked for are bit for bit those of
        # the full-length launch, up to the step at which the lane's pick
        # first depends on the other lanes in either (VERIFIED 2.0: they
        # had claimed the room its own pick needed, and in the full-length
        # launch they claim more; 0.0: the same, and the node it moved to
        # does not verify, as a preempting pick never does).  The verdict
        # column alone may differ before that.
        for launch in (mixed, fused):
            hit = np.flatnonzero(launch[lane, :n, vcol] != 1.0)
            n = min(n, int(hit[0])) if len(hit) else n
        check(
            _same_bits(mixed[lane, :n, :7], fused[lane, :n, :7]),
            f"library: lane {lane}'s first {n} rows differ from the "
            "full-length launch",
        )

    # Solo entry: the preempting request alone, the way stack.py calls it.
    solo_feats = kernels.features_of(preempting)

    def pack(r, xp):  # a PlacementResult in the PACKED_* column layout
        f32 = xp.float32
        return xp.stack([
            r.rows.astype(f32), r.scores, r.binpack, r.preempted.astype(f32),
            r.nodes_evaluated.astype(f32), r.nodes_filtered.astype(f32),
            r.nodes_exhausted.astype(f32),
        ], axis=1)

    def solo():
        return pack(kernels.place_task_group(
            arrays, preempting, arrays.used, jnp.asarray(tg[0]),
            jnp.asarray(sc[0]), jnp.asarray(pen[0]), jnp.asarray(ce[0]),
            jnp.asarray(hm[0]), n_placements=scan, features=solo_feats,
        ), jnp)

    def solo_twin():
        return pack(fake_device.place_task_group(
            host, preempting, host.used, tg[0], sc[0], pen[0], ce[0], hm[0],
            n_placements=scan,
        ), np)

    run("place_task_group", solo, solo_twin, fused=False)

    log("library: system_feasible (cold)")
    mf, stats = _cold_warm(meter, lambda: kernels.system_feasible(
        arrays, arrays.used, system_req, jnp.asarray(ce[0]),
        jnp.asarray(hm[0]),
    ))
    twin_mf = fake_device.system_feasible(
        host, host.used, system_req, ce[0], hm[0]
    )
    check(_same_bits(mf, twin_mf), "system_feasible differs from its twin")
    expect_rack = sum(1 for i in range(nodes) if i % simcluster.RACKS == RACK)
    check(
        int(mf[0].sum()) == expect_rack,
        f"system_feasible: {int(mf[0].sum())} feasible, rack holds {expect_rack}",
    )
    stats["feasible"] = int(mf[0].sum())
    results["system_feasible"] = stats

    log("library: verify_plan_fit (cold)")
    k = 256
    vrows = np.full((k,), -1, np.int32)
    vrows[: k - 16] = rng.choice(nodes, k - 16, replace=False)
    vdelta = rng.uniform(0.0, 2500.0, (k, 3)).astype(np.float32)
    vreq = rng.random(k) < 0.5
    verdicts, stats = _cold_warm(meter, lambda: kernels.verify_plan_fit(
        arrays, vrows, vdelta, vreq
    ))
    check(
        _same_bits(
            verdicts, fake_device.verify_plan_fit(host, vrows, vdelta, vreq)
        ),
        "verify_plan_fit differs from its twin",
    )
    check(
        0 < int(verdicts.sum()) < k,
        "verify_plan_fit: the plan mix should both pass and fail",
    )
    stats["rejected"] = int((~verdicts).sum())
    results["verify_plan_fit"] = stats

    # Dirty-row scatter: three syncs, two pow2 buckets (64, 8, 64 again).
    log("library: dirty-row scatter")
    mark = meter.mark()
    t = time.perf_counter()
    for count in (37, 5, 64):
        rows = rng.choice(nodes, count, replace=False)
        used = rng.uniform(0.0, 900.0, (count, 3)).astype(np.float32)
        m.set_usage(rows, used, host.prio_used[rows])
        arrays = m.sync()
    dev_host = jax.device_get(arrays)
    mirror = m.snapshot_host()
    for f in DeviceArrays._fields:
        check(
            _same_bits(getattr(dev_host, f), mirror[f]),
            f"scatter: device field {f} differs from the host mirror",
        )
    stats = meter.since(mark)
    stats["wall_s"] = round(time.perf_counter() - t, 2)
    check(m.full_uploads == 1 and m.scatter_syncs == 3, "scatter: sync counts")
    check(
        m.scatter_operands_total == 3,
        "scatter: a sync hands the device one packed host operand",
    )
    check(stats["compiles"] <= 2, f"scatter compiled {stats['compiles']} > 2 buckets")
    results["row_scatter"] = stats

    if n_dev > 1:
        results["sharded_fused_place_batch"] = _library_sharded(
            meter, m, arrays, (dr, dv, tg, sc, pen, stacked, ce, hm),
            (ls_mixed, ls), feats, scan, n_dev,
        )
    return out


def _library_sharded(meter, m, arrays, operands, steps, feats, scan,
                     n_dev) -> dict:
    """The node-sharded fused kernel against the unsharded one on the same
    (post-scatter) matrix: all eight packed columns, bit for bit, under
    each of the per-lane step counts in ``steps`` (mixed first)."""
    import numpy as np

    from nomad_tpu.ops import kernels
    from nomad_tpu.parallel.sharding import (
        make_mesh,
        mesh_layout,
        node_shard_count,
        sharded_fused_place_batch,
    )
    from nomad_tpu.state.matrix import DeviceArrays

    # The layout a server with these devices and this matrix would choose.
    mesh = make_mesh(n_dev, batch=mesh_layout(n_dev, int(m.capacity))[0])
    sharded = m.sync_sharded(mesh)
    fn = sharded_fused_place_batch(mesh, scan)
    log(f"library: sharded_fused_place_batch over {dict(mesh.shape)} (cold)")
    stats = {}
    for name, ls in zip(("mixed_steps", "full_length"), steps):
        got, st = _cold_warm(meter, lambda: fn(
            sharded, sharded.used, *operands, ls, features=feats
        ))
        want = np.asarray(kernels.fused_place_batch(
            arrays, arrays.used, *operands, ls, n_placements=scan,
            features=feats,
        ))
        check(
            _same_bits(got, want),
            f"sharded_fused_place_batch ({name}) differs from the "
            "unsharded kernel",
        )
        stats[name] = st
    check(
        not stats["full_length"]["compiles"],
        "sharded: step counts compiled the fused kernel again",
    )
    stats["mesh"] = {k: int(v) for k, v in mesh.shape.items()}
    stats["resident"] = _residency(sharded.used, n_dev, node_shard_count(mesh))
    mirror = m.snapshot_host()
    for f in DeviceArrays._fields:
        check(
            _same_bits(getattr(sharded, f), mirror[f]),
            f"sharded matrix field {f} differs from the host mirror",
        )
    return stats


def _residency(used, n_dev: int, node_shards: int) -> list:
    """Where the ``used`` array lives: one entry per device; every device
    of the mesh must hold its 1/node_shards slice of the rows."""
    shards = [
        {"device": s.device.id, "rows": int(s.data.shape[0])}
        for s in used.addressable_shards
    ]
    check(
        len({s["device"] for s in shards}) == n_dev,
        f"matrix resident on {len(shards)} device(s), expected {n_dev}",
    )
    want = used.shape[0] // node_shards
    check(
        all(s["rows"] == want for s in shards),
        f"matrix shards hold {[s['rows'] for s in shards]} rows, expected {want}",
    )
    return shards


# ---------------------------------------------------------------------------
# Live level
# ---------------------------------------------------------------------------


def _sim_attr(i: int, name: str, target: str) -> str:
    """Value of a constraint target on sim node ``i`` (simcluster.sim_node)."""
    return {
        "${node.class}": f"class-{i % 6}",
        "${attr.rack}": f"r{i % 32}",
        "${attr.platform.tpu.type}": "v5e" if i % 3 else "v5p",
        "${node.unique.name}": name,
    }[target]


def _eligible(job, i: int, name: str) -> bool:
    from nomad_tpu.structs.types import Op

    if f"dc{i % 4 + 1}" not in job.datacenters:
        return False
    for c in job.task_groups[0].constraints:
        same = _sim_attr(i, name, c.l_target) == c.r_target
        if same != (c.operand == Op.EQ.value):
            return False
    return True


def _make_jobs(seed: int, target_name: str, target_dc: str):
    """(burst, filler, preemptor): the few dozen jobs of the live level."""
    import numpy as np

    from nomad_tpu import mock
    from nomad_tpu.structs.types import Affinity, Constraint, Op, Spread

    rng = np.random.default_rng(seed + 2)
    all_dcs = ["dc1", "dc2", "dc3", "dc4"]

    def job(jid, jtype, count, cpu, mem, dcs, priority=50, **stanzas):
        j = mock.job(
            id=jid, name=jid, type=jtype, priority=priority,
            datacenters=list(dcs),
        )
        tg = j.task_groups[0]
        tg.count = count
        tg.tasks[0].resources.cpu = int(cpu)
        tg.tasks[0].resources.memory_mb = int(mem)
        for key, value in stanzas.items():
            setattr(tg, key, value)
        return j

    burst = []
    for k in range(12):  # service, plain binpack, one datacenter each
        burst.append(job(
            f"smoke-svc-{k}", "service", 4, 100 + 50 * (k % 4),
            128 + 64 * (k % 3), [all_dcs[k % 4]],
        ))
    # Batch, spread over node classes + accelerator affinity.  Each on a
    # rack of its own: a batch eval gets two plan attempts (as in the
    # reference), and ten identically-ranking jobs herding onto the same
    # fullest nodes would spend them on each other's conflicts.
    for k in range(10):
        burst.append(job(
            f"smoke-batch-{k}", "batch", 6, int(rng.integers(60, 200)),
            int(rng.integers(64, 256)), all_dcs,
            constraints=[
                Constraint("${attr.rack}", f"r{(7 * k + 3) % 32}", Op.EQ.value)
            ],
            spreads=[Spread(attribute="${node.class}", weight=50)],
            affinities=[Affinity(
                "${attr.platform.tpu.type}", "v5e", Op.EQ.value, 50
            )],
        ))
    for k in range(8):  # service, hard constraints
        burst.append(job(
            f"smoke-con-{k}", "service", 3, 150, 200, all_dcs,
            constraints=[
                Constraint("${attr.rack}", f"r{(7 * k + 1) % 32}", Op.EQ.value),
                Constraint("${node.class}", f"class-{k % 6}", Op.NEQ.value),
                Constraint("${attr.platform.tpu.type}", "v5e", Op.EQ.value),
            ],
        ))
    system = mock.system_job(
        id="smoke-system", name="smoke-system", datacenters=all_dcs
    )
    system.task_groups[0].constraints = [
        Constraint("${attr.rack}", f"r{RACK}", Op.EQ.value)
    ]
    burst.append(system)

    pin = [Constraint("${node.unique.name}", target_name, Op.EQ.value)]
    # Seven 500 MHz fillers leave 400 MHz of the node's 3900: the 1000 MHz
    # preemptor cannot fit without evicting.
    filler = job(
        "smoke-filler", "batch", 7, 500, 1000, [target_dc], priority=20,
        constraints=pin,
    )
    preemptor = job(
        "smoke-preemptor", "service", 1, 1000, 1500, [target_dc],
        priority=80, constraints=pin,
    )
    return burst, filler, preemptor


class _Heartbeats(threading.Thread):
    """What 10,000 clients would do: renew every node's TTL."""

    def __init__(self, server, node_ids):
        super().__init__(name="smoke-heartbeats", daemon=True)
        self.server = server
        self.node_ids = node_ids
        self.stop = threading.Event()
        self.sweeps = 0
        self.slowest_sweep_s = 0.0
        self.error = None

    def run(self) -> None:
        while not self.stop.is_set():
            t = time.perf_counter()
            try:
                for nid in self.node_ids:
                    self.server.heartbeat_node(nid)
            except Exception as e:  # noqa: BLE001 — surfaced by the caller
                self.error = e
                return
            took = time.perf_counter() - t
            self.sweeps += 1
            self.slowest_sweep_s = max(self.slowest_sweep_s, took)
            self.stop.wait(max(0.1, HEARTBEAT_PERIOD_S - took))


def _await_evals(client, eval_ids, what: str) -> float:
    """Block until every eval is complete; returns the wall seconds."""
    t0 = time.perf_counter()
    pending = dict(eval_ids)
    deadline = time.time() + EVAL_TIMEOUT_S
    while pending:
        for jid, eid in list(pending.items()):
            ev = client.get_evaluation(eid)
            if ev["status"] == "complete":
                check(
                    not ev.get("failed_tg_allocs"),
                    f"{what}: eval of {jid} left placements failed: "
                    f"{ev['failed_tg_allocs']}",
                )
                del pending[jid]
            else:
                check(
                    ev["status"] in ("pending", "blocked"),
                    f"{what}: eval of {jid} ended {ev['status']}: "
                    f"{ev.get('status_description')}",
                )
        if pending:
            check(
                time.time() < deadline,
                f"{what}: {len(pending)} eval(s) still pending after "
                f"{EVAL_TIMEOUT_S:.0f}s: {sorted(pending)[:5]}",
            )
            time.sleep(0.25)
    return time.perf_counter() - t0


def live_level(meter: CompileMeter, sizes, seed: int) -> dict:
    import jax
    import numpy as np

    from nomad_tpu import cli, simcluster
    from nomad_tpu.api.client import APIClient
    from nomad_tpu.jobspec import job_to_api
    from nomad_tpu.state.matrix import DeviceArrays

    nodes, capacity, allocs = sizes
    out: dict = {}
    mark = meter.mark()

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        cfg = os.path.join(tmp, "agent.hcl")
        with open(cfg, "w") as fh:
            fh.write(f"server {{\n  node_capacity = {capacity}\n}}\n")
        args = cli.build_parser().parse_args([
            "agent", "--server-only", "--port", "0", "--workers", "16",
            "--config", cfg,
        ])
        agent = cli.build_agent(args)
    agent.start()
    srv = agent.server
    hb = None
    try:
        client = APIClient(agent.rpc_addr)
        log(f"live: agent up at {agent.rpc_addr}")

        t0 = time.perf_counter()
        sim_nodes = []
        for i in range(nodes):
            node = simcluster.sim_node(i)
            node.id = f"sim-node-{i:05d}"
            node.name = f"sim-{i:05d}"
            srv.register_node(node)
            sim_nodes.append(node)
        node_ids = [n.id for n in sim_nodes]
        index_of = {nid: i for i, nid in enumerate(node_ids)}
        hb = _Heartbeats(srv, node_ids)
        hb.start()

        # The aggregated usage of the 2M allocations, installed in bulk;
        # one node — the preemption target — holds only real allocations,
        # because choosing victims needs Allocation objects to evict.
        rows = np.fromiter(
            (srv.matrix.row_of[nid] for nid in node_ids), np.int64, nodes
        )
        totals = srv.matrix.snapshot_host()["totals"][rows].copy()
        used0, prio0 = simcluster.sim_usage(totals, allocs, seed + 1)
        target = int(np.random.default_rng(seed + 3).integers(nodes))
        while target % simcluster.RACKS == RACK:
            target = (target + 1) % nodes
        used0[target] = 0.0
        prio0[target] = 0.0
        srv.matrix.set_usage(rows, used0, prio0)
        out["setup_s"] = round(time.perf_counter() - t0, 2)
        out["preemption_target"] = node_ids[target]
        check(srv.matrix.full_uploads == 0, "live: matrix synced before any job")

        burst, filler, preemptor = _make_jobs(
            seed, sim_nodes[target].name, sim_nodes[target].datacenter
        )

        def submit(jobs):
            ids = {}
            for j in jobs:
                resp = client.register_job(job_to_api(j))
                check(resp.get("EvalID"), f"live: no eval for {j.id}: {resp}")
                ids[j.id] = resp["EvalID"]
            return ids

        log(f"live: submitting {len(burst)} jobs in one burst")
        out["burst_s"] = round(
            _await_evals(client, submit(burst), "burst"), 2
        )
        log(f"live: burst complete in {out['burst_s']}s; filler")
        out["filler_s"] = round(
            _await_evals(client, submit([filler]), "filler"), 2
        )
        check(
            client.set_scheduler_configuration(
                {"preemption_config": {"service_scheduler_enabled": True}}
            ).get("Updated"),
            "live: scheduler configuration not updated",
        )
        log("live: service preemption enabled; preemptor")
        out["preemptor_s"] = round(
            _await_evals(client, submit([preemptor]), "preemptor"), 2
        )

        # -- read back over HTTP and check against the seeded cluster ------
        jobs = burst + [filler, preemptor]
        placed = {}
        load = used0.astype(np.float64)
        evicted = {}
        for j in jobs:
            running = []
            for a in client.job_allocations(j.id):
                check(a["node_id"] in index_of, f"{j.id}: alloc on unknown node")
                i = index_of[a["node_id"]]
                if a["desired_status"] != "run":
                    evicted.setdefault(j.id, []).append(a)
                    continue
                check(
                    _eligible(j, i, sim_nodes[i].name),
                    f"{j.id}: alloc on {a['node_id']} breaks a constraint",
                )
                r = a["resources"]
                load[i] += (r["cpu"], r["memory_mb"], r["disk_mb"])
                running.append(i)
            placed[j.id] = running
        over = np.argwhere(load > totals.astype(np.float64) + 1e-3)
        check(
            over.size == 0,
            f"live: {len(over)} node resource(s) over-committed, first "
            f"{node_ids[over[0][0]] if over.size else None}",
        )
        for j in jobs:
            tg = j.task_groups[0]
            got = len(placed[j.id])
            if j.type == "system":
                want = sorted(
                    i for i in range(nodes) if _eligible(j, i, sim_nodes[i].name)
                )
                check(
                    sorted(placed[j.id]) == want,
                    f"{j.id}: on {got} nodes, rack r{RACK} has {len(want)} eligible",
                )
            elif j.id == filler.id:
                lost = len(evicted.get(j.id, []))
                check(
                    lost >= 1 and got + lost == tg.count,
                    f"{j.id}: {got} running + {lost} evicted of {tg.count}",
                )
                check(
                    all(
                        a["desired_status"] == "evict"
                        and "Preempted" in a.get("desired_description", "")
                        for a in evicted[j.id]
                    ),
                    f"{j.id}: evictions are not preemptions",
                )
            else:
                check(got == tg.count, f"{j.id}: {got} running of {tg.count}")
                check(j.id not in evicted, f"{j.id}: unexpected evictions")
        check(placed[preemptor.id] == [target], "preemptor not on its node")
        out["jobs"] = len(jobs)
        out["allocs_running"] = sum(len(v) for v in placed.values())
        out["allocs_preempted"] = len(evicted.get(filler.id, []))
        out["system_nodes"] = len(placed["smoke-system"])

        # -- the device did the work --------------------------------------
        device = client.health()["device"]
        out["device_health"] = device
        check(device["breaker"] == "closed", f"breaker {device['breaker']}")
        for key in ("degraded_dispatches", "wedged", "slow", "trips"):
            check(device[key] == 0, f"/v1/health device.{key} = {device[key]}")
        coal, mx = srv.coalescer, srv.matrix
        resident = coal.sync_arrays()
        n_shards = coal.n_device_shards
        counters = out["counters"] = {
            "n_device_shards": n_shards,
            "dispatches": coal.dispatches,
            "fused_dispatches": coal.fused_dispatches,
            "device_calls": coal.device_calls,
            "fused_lanes": coal.fused_lanes,
            "scan_steps_total": coal.scan_steps_total,
            "coalesced_requests": coal.coalesced_requests,
            "solo_ops": coal.solo_ops,
            "stale_dispatches": coal.stale_dispatches,
            "verify_conflicts": coal.verify_conflicts,
            "lane_repicks": coal.lane_repicks,
            "feature_recompiles": coal.feature_recompiles,
            "wedged_dispatches": coal.wedged_dispatches,
            "full_uploads": mx.full_uploads,
            "scatter_syncs": mx.scatter_syncs,
            "scatter_operands_total": mx.scatter_operands_total,
            "rows_scattered_total": mx.rows_scattered_total,
            "upload_bytes_total": mx.upload_bytes_total,
            "plans_applied": srv.plan_applier.plans_applied,
            "heartbeat_sweeps": hb.sweeps,
            "heartbeat_slowest_sweep_s": round(hb.slowest_sweep_s, 3),
            "heartbeats_missed": int(
                srv.metrics.snapshot().get("nomad.heartbeat.missed", 0)
            ),
        }
        check(hb.error is None, f"heartbeat thread died: {hb.error!r}")
        check(counters["heartbeats_missed"] == 0, "nodes missed heartbeats")
        check(coal.fused_dispatches > 0, "no fused dispatch happened")
        check(coal.dispatches == coal.fused_dispatches, "staged dispatches ran")
        check(
            coal.device_calls == coal.fused_dispatches,
            f"{coal.device_calls} jitted calls for "
            f"{coal.fused_dispatches} launches: a launch is one call",
        )
        # The server shards over every visible accelerator on its own (a
        # CPU backend, the rehearsal's, stays on one device by design).
        n_dev = len(jax.devices())
        accel = jax.devices()[0].platform != "cpu"
        check(
            n_shards == (n_dev if accel else 1),
            f"n_device_shards {n_shards} with {n_dev} device(s) visible",
        )
        # One upload per device mirror: the (sharded) dispatch mirror, plus
        # — on a mesh — the single-device mirror the solo entry points
        # (system_feasible here) still read.
        mirrors = 1 if n_shards == 1 else 2
        check(
            mx.full_uploads == mirrors,
            f"matrix uploaded in full {mx.full_uploads}x for {mirrors} mirror(s)",
        )
        check(mx.scatter_syncs > 0, "no dirty-row scatter happened")
        check(isinstance(resident.used, jax.Array), "resident used is not a jax.Array")
        platforms = {d.platform for d in resident.used.devices()}
        check(
            platforms == {jax.devices()[0].platform},
            f"resident matrix on {platforms}",
        )
        out["resident"] = _residency(
            resident.used, n_dev if n_shards > 1 else 1, mx.shard_count
        )
        mirror = mx.snapshot_host()
        for f in DeviceArrays._fields:
            check(
                _same_bits(getattr(resident, f), mirror[f]),
                f"live: resident field {f} differs from the host mirror",
            )
        # Phase timings the watchdog question rests on: compiles happen in
        # the launch (dispatch thread), never inside the watched fetch.
        snap = srv.metrics.snapshot()
        for phase in ("coalescer.launch", "coalescer.device", "eval.process"):
            hist = snap.get(f"nomad.phase.{phase}")
            if isinstance(hist, dict):
                out.setdefault("phase_ms", {})[phase] = {
                    k: hist[k] for k in ("count", "p50_ms", "p99_ms", "max_ms")
                    if k in hist
                }
        out.update(meter.since(mark))
    finally:
        if hb is not None:
            hb.stop.set()
            hb.join(timeout=10)
        agent.shutdown()
    return out


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0, help="data seed")
    ap.add_argument(
        "--cpu-rehearsal", nargs="?", const="tiny", choices=("tiny", "full"),
        help="debug this script on a CPU (tiny or full size); establishes "
             "nothing about the chip and never reports ok",
    )
    ap.add_argument(
        "--sharded-only", action="store_true",
        help="several chips: only the node-sharded fused entry against the "
             "unsharded one (mixed step counts, full length), no live agent",
    )
    ap.add_argument(
        "--region-scale", type=int, default=1,
        help="multiply the cluster: 10 is the 100,000-node region "
             "(capacity 102,400, usage of 20,000,000 allocations)",
    )
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    print(
        f"device: platform={device['platform']} kind={device['kind']} "
        f"count={device['count']}", flush=True,
    )
    if os.environ.get("NOMAD_TPU_FAKE_DEVICE"):
        print("chip_smoke: NOMAD_TPU_FAKE_DEVICE is set; refusing to run",
              file=sys.stderr)
        return 2
    rehearsal = args.cpu_rehearsal
    if device["platform"] == "cpu" and not rehearsal:
        print("chip_smoke: JAX found platform 'cpu', no accelerator; "
              "nothing was run", file=sys.stderr)
        return 2
    if rehearsal and device["platform"] != "cpu":
        print("chip_smoke: --cpu-rehearsal on an accelerator makes no sense",
              file=sys.stderr)
        return 2
    if rehearsal:
        log("CPU REHEARSAL: this run establishes nothing about the chip")
    sizes = REHEARSAL_TINY if rehearsal == "tiny" else (NODES, CAPACITY, ALLOCS)
    sizes = tuple(x * args.region_scale for x in sizes)

    try:
        import nomad_tpu
    except ImportError as e:
        print(f"chip_smoke: not inside a nomad_tpu checkout ({e})",
              file=sys.stderr)
        return 2

    meter = CompileMeter()
    result = {
        "ok": False,
        "device": device,
        "seed": args.seed,
        "sizes": dict(zip(("nodes", "capacity", "sim_allocs"), sizes)),
        "cache_dir": nomad_tpu.enable_compilation_cache(),
    }
    if rehearsal:
        result["rehearsal"] = rehearsal
    code = 1
    try:
        t = time.perf_counter()
        result["library"] = library_level(
            meter, sizes, args.seed, sharded_only=args.sharded_only
        )
        result["library"]["wall_s"] = round(time.perf_counter() - t, 1)
        if not args.sharded_only:
            t = time.perf_counter()
            result["live"] = live_level(meter, sizes, args.seed)
            result["live"]["wall_s"] = round(time.perf_counter() - t, 1)
        result["ok"] = not rehearsal
        code = 0
    except SmokeFailure as e:
        result["failed"] = str(e)
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
    except Exception as e:  # noqa: BLE001 — report, then exit non-zero
        traceback.print_exc()
        result["failed"] = f"{type(e).__name__}: {e}"
    result["compile"] = {
        "backend_compiles": meter.compiles,
        "backend_compile_s": round(meter.compile_s, 1),
        "persistent_cache_hits": meter.cache_hits,
        "persistent_cache_misses": meter.cache_misses,
    }
    result["wall_s"] = round(time.perf_counter() - t_start, 1)
    sys.stderr.flush()
    # The full report (sizes, cold/warm seconds, counters, parity) is the
    # second-to-last line; the last line is the verdict alone, exactly
    # {"ok", "device"}, which is what the driver parses.
    print("report: " + json.dumps(result), flush=True)
    print(json.dumps({"ok": result["ok"], "device": device}), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
