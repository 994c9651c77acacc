#!/usr/bin/env python3
"""chip_rules_mesh.py — the rule stages of the node-sharded placement
program against the one-device program and the numpy twin, at the sizes the
cell ``c2m-100k-rules.rules-backlog-x4`` runs at (PR 46).

One process.  It builds the region of ``benchmark/configs/c2m-100k-rules.json``
in a state store (100,000 nodes, capacity 102,400, the rule attributes and
the seeded usage; the matrix homed over the node shards as a server homes
it), compiles one job of each of the eight rule shapes of
``benchmark/traffic/rules-backlog-x4.json`` into lanes exactly as a select
does (``GenericStack._feasibility`` for the class vector and the host mask,
``_distinct_property_seed`` for the limit's counts), and sends ONE launch of
64 lanes (mixed 1-8 steps a lane, the wide ``Features`` variant) down three
routes: ``kernels.fused_place_batch`` on one device,
``sharded_fused_place_batch`` on the mesh a server would lay the visible
devices out as ((2, 2) on four chips), and ``fake_device.fused_place_batch``.
Rows, the VERIFIED column, the ``dp_moved`` flags and the node counters must
be equal; scores within ``--tol`` (1e-6).  It times nothing: a launch here
hands over its operands from the host (39 MB to four devices), which is no
kernel time; ``kernel_ms_per_launch`` of the cell's traced run is.

Exit 0 and a last stdout line ``{"ok": true, ...}`` only when every
comparison held on an accelerator; the report goes to
``chiprun_out/rules_mesh.json`` too.  ``--cpu-rehearsal`` debugs this script
on forced host devices at a tiny size and reports ``"ok": false``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "benchmark"),
          os.path.join(ROOT, "benchmark", "deployments")):
    if p not in sys.path:
        sys.path.insert(0, p)

T0 = time.time()


def log(msg: str) -> None:
    print(f"[{time.time() - T0:7.2f}s] {msg}", file=sys.stderr, flush=True)


def build_region(cfg, nodes, capacity, node_shards, seed):
    """The configuration's cluster in a state store, homed over
    ``node_shards`` as ``DeviceCoalescer._resolve_sharding`` homes it."""
    import numpy as np

    import rules_reference as rules
    from nomad_tpu import simcluster
    from nomad_tpu.state import NodeMatrix
    from nomad_tpu.state.store import StateStore

    matrix = NodeMatrix(capacity=capacity)
    matrix.set_shard_count(node_shards)
    store = StateStore(matrix=matrix)
    ids = []
    for i in range(nodes):
        node = simcluster.sim_node(i)
        node.id, node.name = f"sim-node-{i:06d}", f"sim-{i:06d}"
        node.meta = dict(node.meta)
        for name, value in rules.expected_attributes(i, cfg["cluster"]).items():
            kind, key = name.split(".", 1)
            (node.meta if kind == "meta" else node.attributes)[key] = value
        store.upsert_node(1000 + i, node)
        ids.append(node.id)
    rows = np.fromiter((matrix.row_of[n] for n in ids), np.int64, nodes)
    totals = matrix.snapshot_host()["totals"][rows].copy()
    used0, prio0 = simcluster.sim_usage(
        totals, cfg["sim_allocs"] * nodes // cfg["nodes"], seed % 2 ** 32)
    matrix.set_usage(rows, used0, prio0)
    return store, rows


def lanes_of(store, mix, lanes, held=None):
    """``lanes`` selects' operands: shape ``lane % 8`` at width 1-8, built
    by the stack as a worker builds them.  ``held``: {lane: matrix rows the
    job counts as its own already}, so that a limit has values at their
    count before the first pick."""
    import numpy as np

    import traffic
    from nomad_tpu.jobspec.parse import api_to_job
    from nomad_tpu.ops.encode import RequestSlab
    from nomad_tpu.scheduler.coalescer import MAX_DELTA_ROWS
    from nomad_tpu.scheduler.context import EvalContext
    from nomad_tpu.scheduler.stack import (
        GenericStack, _and_mask, _full_mask, _pad_width,
    )
    from nomad_tpu.structs.types import Plan

    m = store.matrix
    n = int(m.capacity)
    snap = store.snapshot()
    slab = RequestSlab(lanes)
    reqs, ces, hms, scs = [], [], [], []
    for lane in range(lanes):
        op = {"namespace": "default", "width": 1 + (lane * 5) % 8,
              "type": "service", "priority": 50, "shape": lane % 8,
              "job_id": f"lane-{lane:02d}"}
        job = api_to_job(traffic.job_payload(mix, op))
        tg = job.task_groups[0]
        stack = GenericStack(EvalContext(snap, Plan(job=job)), m)
        stack.set_job(job)
        compiled = stack.encoder.compile(job, tg)
        class_elig, host_mask = stack._feasibility(job, tg, compiled)
        request, dp_mask = stack._distinct_property_seed(
            job, compiled, (held or {}).get(lane, ()))
        host_mask = _and_mask(host_mask, dp_mask)
        scs.append(stack._spread_counts(job, tg, compiled))
        # (_spread_counts persists the values it found into the request)
        request = request._replace(
            s_value_hash=compiled.request.s_value_hash)
        slab.fill(lane, request)
        reqs.append(request)
        ces.append(class_elig)
        hms.append(_pad_width(_full_mask(n, host_mask), n, False))
    ops = (
        np.full((lanes, MAX_DELTA_ROWS), -1, np.int32),
        np.zeros((lanes, MAX_DELTA_ROWS, 3), np.float32),
        np.zeros((lanes, n), np.int32),
        np.stack(scs).astype(np.float32),
        np.zeros((lanes, n), bool),
        slab.batch(),
        np.stack(ces),
        np.stack(hms),
    )
    return slab, reqs, ops


def compare(name, got, want, tol):
    """Packed (B, P, 8): rows, PREEMPT, the node counters (the ``dp_moved``
    flag is FILTERED's fraction) and VERIFIED exact; the two scores within
    ``tol``.  Returns the widest score gap."""
    import numpy as np

    from nomad_tpu.ops import kernels as k

    exact = [k.PACKED_ROW, k.PACKED_PREEMPT, k.PACKED_EVALUATED,
             k.PACKED_FILTERED, k.PACKED_EXHAUSTED, k.FUSED_PACKED_VERIFIED]
    for col in exact:
        if not np.array_equal(got[:, :, col], want[:, :, col]):
            bad = np.argwhere(got[:, :, col] != want[:, :, col])[:5]
            raise SystemExit(
                f"{name}: column {col} differs at (lane, step) "
                f"{bad.tolist()}: {got[tuple(bad[0])][col]} != "
                f"{want[tuple(bad[0])][col]}")
    scores = [k.PACKED_SCORE, k.PACKED_BINPACK]
    gap = float(np.abs(got[:, :, scores] - want[:, :, scores]).max())
    if not gap <= tol:
        raise SystemExit(f"{name}: scores differ by {gap} (limit {tol})")
    return gap


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=4600000001)
    ap.add_argument("--lanes", type=int, default=64)
    ap.add_argument("--tol", type=float, default=1e-6)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args(argv)
    if args.cpu_rehearsal:
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=4")

    import jax
    import numpy as np

    import nomad_tpu
    import traffic
    from nomad_tpu.ops import fake_device, kernels
    from nomad_tpu.parallel.sharding import (
        make_mesh, mesh_layout, sharded_fused_place_batch,
    )

    devices = jax.devices()
    platform = devices[0].platform
    if (platform == "cpu") != args.cpu_rehearsal:
        raise SystemExit(f"platform {platform!r}: "
                         "--cpu-rehearsal is for a CPU, and only for one")
    if len(devices) < 4:
        raise SystemExit(f"needs four devices, JAX sees {len(devices)}")
    nomad_tpu.enable_compilation_cache()
    with open(os.path.join(ROOT, "benchmark/configs/c2m-100k-rules.json")) as fh:
        cfg = json.load(fh)
    nodes, capacity = (480, 512) if args.cpu_rehearsal else (
        cfg["nodes"], cfg["node_capacity"])
    lanes = 8 if args.cpu_rehearsal else args.lanes
    scan = 16
    batch, node_shards = mesh_layout(len(devices), capacity)
    log(f"device: {platform} {devices[0].device_kind} x{len(devices)}; "
        f"mesh (batch {batch}, node {node_shards})")

    t = time.time()
    store, rows = build_region(cfg, nodes, capacity, node_shards, args.seed)
    m = store.matrix
    log(f"region: {nodes} nodes, {len(m.class_ids)} class keys, "
        f"{time.time() - t:.1f}s")
    mix = traffic.load("rules-backlog-x4")
    ls = np.array([1 + (i * 5) % 8 for i in range(lanes)], np.int32)
    ls[lanes // 3] = 0  # a dead lane in between
    arrays = m.sync()
    mesh = make_mesh(len(devices), batch=batch)
    sharded = m.sync_sharded(mesh)
    fn = sharded_fused_place_batch(mesh, scan)
    host = m.sync_host()
    report = {
        "nodes": nodes, "capacity": capacity, "lanes": lanes,
        "class_keys": len(m.class_ids), "mesh": [batch, node_shards],
        "device": {"platform": platform, "kind": devices[0].device_kind,
                   "count": len(devices)},
    }

    def three_routes(tag, held):
        """One launch down the three routes, compared; the mesh's result."""
        slab, reqs, ops = lanes_of(store, mix, lanes, held)
        feats = kernels.features_of(slab.live_view(lanes))
        log(f"{tag}: features {tuple(feats)}, class operand {ops[6].shape}")
        if not (feats.dp_width >= 1 and feats.c_width >= 8
                and feats.s_width == 2):
            raise SystemExit(f"not the wide variant: {feats}")
        out = report[tag] = {"features": feats._asdict(),
                             "class_pad": int(ops[6].shape[1])}
        t = time.time()
        one = np.asarray(kernels.fused_place_batch(
            arrays, arrays.used, *ops, ls, n_placements=scan, features=feats))
        out["one_device_s"] = time.time() - t
        t = time.time()
        got = np.asarray(fn(sharded, sharded.used, *ops, ls, features=feats))
        out["sharded_s"] = time.time() - t
        log(f"{tag}: one device {out['one_device_s']:.1f}s, "
            f"mesh {out['sharded_s']:.1f}s")
        out["sharded_vs_one_device_score_gap"] = compare(
            f"{tag}: mesh against one device", got, one, args.tol)
        t = time.time()
        twin = fake_device.fused_place_batch(
            host, host.used, *[list(a) for a in ops[:5]], reqs,
            list(ops[6]), list(ops[7]), ls > 0, n_placements=scan,
            live_counts=list(ls))
        out["twin_s"] = time.time() - t
        out["sharded_vs_twin_score_gap"] = compare(
            f"{tag}: mesh against the numpy twin", got, twin, args.tol)
        out["one_device_vs_twin_score_gap"] = compare(
            f"{tag}: one device against the numpy twin", one, twin, args.tol)
        return got, ops, feats

    # At 2,560 racks a job's best nodes seldom share one, so the limit
    # seldom moves a pick.  The second launch counts the first one's picks
    # as allocations the jobs under a distinct_property hold already: their
    # best nodes' racks are at their count before the first step.
    first, _, _ = three_routes("free", None)
    held = {
        lane: first[lane, : ls[lane], kernels.PACKED_ROW].astype(np.int64)
        for lane in range(lanes)
        if any(c["operand"] == "distinct_property"
               for c in mix["shapes"][lane % 8]["constraints"])}
    got, ops, feats = three_routes("held", held)

    placed = got[:, :, kernels.PACKED_ROW] >= 0
    moved = got[:, :, kernels.PACKED_FILTERED] % 1 != 0
    repicked = got[:, :, kernels.FUSED_PACKED_VERIFIED] == 2.0
    report.update(placed=int(placed.sum()), asked=int(ls.sum()),
                  dp_moved=int(moved.sum()), repicked=int(repicked.sum()))
    if report["placed"] != report["asked"] or not report["dp_moved"]:
        raise SystemExit(f"the launch compared too little: {report}")
    # every pick holds its rules by the plain reference
    import rules_reference as rules

    tables = rules.attr_tables(nodes, dict(cfg["cluster"]))
    index_of = np.full((capacity,), -1, np.int64)
    index_of[rows] = np.arange(nodes)
    for lane in range(lanes):
        s = mix["shapes"][lane % 8]
        picks = np.concatenate([
            held.get(lane, np.zeros((0,), np.int64)),
            got[lane, : ls[lane], kernels.PACKED_ROW].astype(np.int64)])
        picks = index_of[picks]
        ok = rules.eligible(tables, s["datacenters"], s["constraints"])
        bad = not ok[picks].all()
        for c in s["constraints"]:
            if c["operand"] == "distinct_hosts":
                bad |= rules.distinct_hosts_violations(picks.tolist()) > 0
            if c["operand"] == "distinct_property":
                bad |= rules.distinct_property_violations(
                    tables, c, picks.tolist()) > 0
        if bad:
            raise SystemExit(f"lane {lane} ({s['name']}) breaks its rules: "
                             f"{picks.tolist()}")

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    report["peak_bytes_in_use"] = max(
        (s.get("peak_bytes_in_use", 0) for s in stats), default=0)
    report["ok"] = not args.cpu_rehearsal
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "rules_mesh.json"), "w") as fh:
        json.dump(report, fh, indent=1)
    print("report: " + json.dumps(report), flush=True)
    print(json.dumps({"ok": report["ok"], "device": report["device"]}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
