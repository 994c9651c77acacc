"""Shared test helpers (the tier-2 in-process agent pattern, SURVEY.md §4).

Kept in one module so wait/crash semantics can't drift between suites.
"""

from __future__ import annotations

import time

from nomad_tpu.client import Client, ClientConfig


def _wait(pred, timeout=30.0, every=0.05):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(every)
    return pred()


def _small(job):
    """Shrink a mock job's asks so many fit on one mock node."""
    for tg in job.task_groups:
        for t in tg.tasks:
            t.resources.cpu = 20
            t.resources.memory_mb = 32
        tg.ephemeral_disk.size_mb = 10
    return job


def _client(server, tmp_path, name, **cfg) -> Client:
    c = Client(server, ClientConfig(data_dir=str(tmp_path / name), **cfg))
    c.start()
    return c


def _crash_client(client):
    """Simulate an agent crash: stop loops WITHOUT destroying allocs or
    killing tasks (Client.shutdown would tear the tasks down)."""
    client._shutdown.set()
    with client._dirty_cond:
        client._dirty_cond.notify_all()


def _live(server, job):
    return [
        a for a in server.store.allocs_by_job(job.namespace, job.id)
        if not a.terminal_status()
    ]


def lane_operands(matrix, requests, deltas=None, penalties=None,
                  tg_counts=None, max_deltas=4):
    """The per-lane operands of ``fused_place_batch`` between ``used`` and
    ``lane_steps``, in the entry's order: (delta_rows, delta_vals,
    tg_counts, spread_counts, penalties, reqs, class_eligs, host_masks)
    for ``requests`` with no plan state but what the overrides say.

    ``ops.encode.RequestSlab`` stacks the requests, as the coalescer does;
    what it cannot give is the other seven operands, whose shapes (the
    class-count padding in particular) must stay in step with the kernel.

    deltas: {lane: [(row, (cpu, mem, disk)), ...]} in-flight deltas;
    penalties: {lane: [row, ...]}; tg_counts: {lane: {row: count}}.
    """
    import numpy as np

    from nomad_tpu.ops.encode import RequestSlab, pow2_bucket

    b, n = len(requests), int(matrix.capacity)
    slab = RequestSlab(b)
    for i, req in enumerate(requests):
        slab.fill(i, req)
    drows = np.full((b, max_deltas), -1, np.int32)
    dvals = np.zeros((b, max_deltas, 3), np.float32)
    for lane, items in (deltas or {}).items():
        for j, (row, vals) in enumerate(items):
            drows[lane, j] = row
            dvals[lane, j] = vals
    pen = np.zeros((b, n), bool)
    for lane, rows in (penalties or {}).items():
        pen[lane, list(rows)] = True
    tg = np.zeros((b, n), np.int32)
    for lane, counts in (tg_counts or {}).items():
        for row, c in counts.items():
            tg[lane, row] = c
    sc = np.zeros((b,) + np.asarray(requests[0].s_value_hash).shape,
                  np.float32)
    ce = np.ones((b, max(2, pow2_bucket(len(matrix.class_ids)))), bool)
    hm = np.ones((b, n), bool)
    return drows, dvals, tg, sc, pen, slab.batch(), ce, hm


def solo_reference(arrays, operands, n_placements, lanes=None,
                   features=None):
    """Each lane (or each of ``lanes``) of ``operands`` (``lane_operands``'
    tuple) alone through ``place_task_group`` (the static scan over the
    dense proposed usage), packed (B, P, 7) in the ``PACKED_*`` column
    order: what a lane of the batched program must read, sparse deltas
    included."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from nomad_tpu.ops import kernels

    drows, dvals, tg, sc, pen, reqs, ce, hm = operands
    features = features or kernels.FULL_FEATURES
    out = []
    for i in (range(len(drows)) if lanes is None else lanes):
        live = drows[i] >= 0
        used0 = arrays.used.at[drows[i][live]].add(dvals[i][live])
        r = kernels.place_task_group(
            arrays, jax.tree_util.tree_map(lambda x: x[i], reqs), used0,
            jnp.asarray(tg[i]), jnp.asarray(sc[i]), jnp.asarray(pen[i]),
            jnp.asarray(ce[i]), jnp.asarray(hm[i]), n_placements, features,
        )
        out.append(np.stack([
            np.asarray(c, np.float32) for c in (
                r.rows, r.scores, r.binpack, r.preempted, r.nodes_evaluated,
                r.nodes_filtered, r.nodes_exhausted,
            )
        ], axis=1))
    return np.stack(out)


def fill_frontier(matrix, nodes, picks, ask_cpu, ask_mem, seed=0):
    """Fill ``nodes[i]`` for i in ``picks`` until each holds room for
    exactly ONE (ask_cpu MHz, ask_mem MB) ask: the frontier of nearly full
    nodes that binpack ranks first and that the lanes of one launch all
    want.  Returns their matrix rows."""
    import numpy as np

    from nomad_tpu.structs import Allocation, Job, Resources

    rng = np.random.default_rng(seed)
    host = matrix.snapshot_host()
    rows = []
    for i in picks:
        row = matrix.row_of[nodes[i].id]
        free_cpu, free_mem = (host["totals"][row] - host["used"][row])[:2]
        assert free_cpu >= 2 * ask_cpu and free_mem >= 2 * ask_mem
        matrix.add_alloc(Allocation(
            node_id=nodes[i].id,
            job=Job(priority=50),
            resources=Resources(
                cpu=int(free_cpu - ask_cpu - rng.integers(0, ask_cpu)),
                memory_mb=int(free_mem - ask_mem - rng.integers(0, ask_mem)),
            ),
        ))
        rows.append(row)
    return rows


# Fills a launch is checked at: 1-3 lanes (steady), 8-16 (the backlog cells,
# 16 workers), past a batch shard of (2, 2) and up to every lane.
LAUNCH_FILLS = (1, 2, 8, 9, 16, 33, 57, 64)
NODE_AXIS = ("tg_count", "penalty", "host_mask")


def lane_batch(coal, k, seed=0, classes=2, rules=False):
    """``k`` lanes for ``coal._dispatch``: lanes whose node-axis operands
    differ (host masks with holes, job counts, penalties, seeded per lane,
    the same whatever the launch's width), asks that differ; with ``rules``
    every other lane's job carries two ``distinct_property`` limits
    (``dp_width`` 2: the matrix's nodes need ``meta.rack`` / ``meta.zone``)."""
    import numpy as np

    from nomad_tpu import mock
    from nomad_tpu.ops.encode import RequestEncoder
    from nomad_tpu.scheduler.coalescer import MAX_DELTA_ROWS, _Pending
    from nomad_tpu.structs.types import Constraint, Op

    m, n = coal.matrix, int(coal.matrix.capacity)
    enc = RequestEncoder(m)
    batch = []
    for i in range(k):
        rng = np.random.default_rng(1000 * seed + i)
        job = mock.job()
        job.task_groups[0].tasks[0].resources.cpu = 100 + 10 * (i % 7)
        if rules and i % 2 == 0:
            job.task_groups[0].constraints = [
                Constraint(l_target=f"${{meta.{prop}}}", r_target=limit,
                           operand=Op.DISTINCT_PROPERTY.value)
                for prop, limit in (("rack", "2"), ("zone", "9"))]
        req = enc.compile(job, job.task_groups[0]).request
        batch.append(_Pending(
            request=req,
            delta_rows=np.full((MAX_DELTA_ROWS,), -1, np.int32),
            delta_vals=np.zeros((MAX_DELTA_ROWS, 3), np.float32),
            tg_count=rng.integers(0, 3, n).astype(np.int32),
            spread_counts=np.zeros_like(req.s_desired),
            penalty=rng.random(n) < 0.2,
            class_elig=np.ones((classes,), bool),
            host_mask=rng.random(n) < 0.7,
            n_live=1 + i % 3,
        ))
    return batch


def launch_lanes(coal, k, degraded=False, **lanes):
    """One batch of ``k`` lanes (``lane_batch``) straight through
    ``coal._dispatch`` (no threads, so the launch holds exactly ``k``).
    Returns the fetched packed result, every lane of it."""
    import numpy as np

    packed, _version = coal._dispatch(
        lane_batch(coal, k, **lanes), degraded=degraded)
    return np.asarray(packed)


def spy_on_launch(monkeypatch, coal):
    """Record the jitted calls of the next launches: ``(operands, static)``
    of each call of the placement program (one device's or, where the
    coalescer has built it, the mesh's)."""
    from nomad_tpu.ops import kernels

    calls = []

    def spy(fn):
        def call(*operands, **static):
            calls.append((operands, static))
            return fn(*operands, **static)
        return call

    monkeypatch.setattr(
        kernels, "fused_place_batch_live", spy(kernels.fused_place_batch_live))
    if coal._sharded_fused_fn is not None:
        monkeypatch.setattr(
            coal, "_sharded_fused_fn", spy(coal._sharded_fused_fn))
    return calls


def collectives(hlo_text):
    """How many ops of each collective kind a compiled program's text holds
    (an asynchronous pair counts once, at its start)."""
    import re
    from collections import Counter

    return Counter(re.findall(
        r" (all-gather|all-reduce|all-to-all|collective-permute|"
        r"collective-broadcast|reduce-scatter)(?:-start)?\(", hlo_text))


def wide_coalescer(n_device_shards=1, nodes=40, capacity=64, lanes=64):
    from nomad_tpu import mock
    from nomad_tpu.scheduler.coalescer import DeviceCoalescer
    from nomad_tpu.state import NodeMatrix

    m = NodeMatrix(capacity=capacity)
    for i in range(nodes):
        node = mock.node()
        node.meta = {"rack": f"r{i % 8}", "zone": f"z{i % 2}"}
        m.upsert_node(node)
    return DeviceCoalescer(
        m, max_lanes=lanes, linger_s=0.0, pipeline_depth=1,
        n_device_shards=n_device_shards,
    )


_WIDE = {}  # (devices, rules) -> a 64-lane coalescer that has launched once


def launched_coalescer(n_device_shards=1, rules=False):
    """The file's wide coalescer for that route, kept for the whole file (a
    mesh compiles its placement program per coalescer): it has launched
    once, at ``dp_width`` 2 where ``rules``."""
    key = n_device_shards, rules
    if key not in _WIDE:
        _WIDE[key] = wide_coalescer(n_device_shards)
        launch_lanes(_WIDE[key], 1, rules=rules)
    return _WIDE[key]


def check_packed_launch(monkeypatch, k, n_device_shards=1, rules=False):
    """A launch of ``k`` lanes is ONE jitted call.  Its operands are the
    resident matrix, the two packs every small lane operand is a view of
    (the slot's own buffers, numpy: nothing unpacked them on the way), the
    three node-axis buffers at full width (the slot's, dead lanes
    all-False) and the carry the launch before handed on; the byte counter
    and the call counter say so; and every lane reads bit for bit what the
    plain placement program gives on the numpy operands as the slot holds
    them.  Returns the coalescer and the call's (operands, static)."""
    import jax
    import numpy as np

    from nomad_tpu.ops import kernels
    from nomad_tpu.scheduler.claims import OVERLAY_ROWS

    coal = launched_coalescer(n_device_shards, rules)
    assert coal._features.dp_width == (2 if rules else 0)
    n, lanes = int(coal.matrix.capacity), coal.max_lanes
    calls = spy_on_launch(monkeypatch, coal)
    bytes0, calls0, carry0 = (
        coal.operand_bytes_total, coal.device_calls, coal._carry)
    got = launch_lanes(coal, k, seed=1, rules=rules)

    st, slab = coal._stage[0], coal._req_slabs[0]
    small = kernels.LANE_FIELDS
    assert all(np.shares_memory(st[f], st["pack"]) for f in small)
    assert all(np.shares_memory(f, slab.pack) for f in slab.batch())
    fields = sum(st[f].nbytes for f in small)  # each padded to 4 bytes a lane
    assert fields <= st["pack"].nbytes <= fields + lanes * 4 * len(small)
    assert coal.operand_bytes_total - bytes0 == (
        lanes * n * (1 + 4 + 1) + st["pack"].nbytes + slab.pack.nbytes)
    ((operands, static),) = calls
    assert coal.device_calls == calls0 + 1
    resident, used, req_pack, lane_pack, tg, pen, hm, carry = operands
    mx = coal.matrix
    assert resident is (
        mx._device if n_device_shards == 1 else mx._sharded_device)
    assert used is resident.used
    assert req_pack is slab.pack and lane_pack is st["pack"]
    for x, field in zip((tg, pen, hm), NODE_AXIS):
        assert x is st[field] and x.shape == (lanes, n)
    assert not hm[k:].any()
    assert carry is carry0 and isinstance(carry, jax.Array)
    assert static["layouts"] == (slab.layout, st["layout"])
    assert static["features"] == coal._features
    assert st["overlay_rows"].size >= OVERLAY_ROWS
    assert (st["overlay_rows"] == -1).all()

    reqs = kernels.device_request(
        [f.copy() for f in slab.batch()], coal._features.dp_width)
    plain = [st[f].copy() for f in (
        "delta_rows", "delta_vals", "tg_count", "spread_counts", "penalty")]
    plain += [reqs] + [st[f].copy() for f in (
        "class_elig", "host_mask", "lane_steps")]
    if n_device_shards == 1:
        want = kernels.fused_place_batch(
            resident, used, *plain,
            n_placements=coal.scan_length, features=coal._features)
    else:
        want = plain_mesh_program(coal)(
            resident, used, *plain, features=coal._features)
    assert (got[:k, 0, kernels.PACKED_ROW] >= 0).any()
    np.testing.assert_array_equal(got, np.asarray(want))
    return coal, (operands, static)


_PLAIN = {}  # (mesh, scan length) -> the mesh's plain placement program


def plain_mesh_program(coal):
    """``sharded_fused_place_batch`` on the coalescer's mesh (every operand
    its own): what the packed entry is held to."""
    from nomad_tpu.parallel.sharding import sharded_fused_place_batch

    key = coal._mesh, coal.scan_length
    if key not in _PLAIN:
        _PLAIN[key] = sharded_fused_place_batch(*key)
    return _PLAIN[key]


_COMPILES = []


def backend_compiles() -> int:
    """XLA compiles this process has made since the first call (what the
    benchmark's ``compiles_in_window`` counts)."""
    if not _COMPILES:
        import jax.monitoring

        def on(event, _duration, **_kw):
            if event == "/jax/core/compile/backend_compile_duration":
                _COMPILES[0] += 1

        _COMPILES.append(0)
        jax.monitoring.register_event_duration_secs_listener(on)
    return _COMPILES[0]


def hold_gil(seconds: float) -> float:
    """Hold the interpreter for about ``seconds`` inside ONE C call
    (``sum`` over a ``range`` never reaches a bytecode boundary, so no
    other thread gets the GIL; ``time.sleep`` would release it).  Returns
    the seconds the call took."""
    n = 2_000_000
    t0 = time.perf_counter()
    sum(range(n))
    per_item = (time.perf_counter() - t0) / n
    t0 = time.perf_counter()
    sum(range(int(seconds / per_item)))
    return time.perf_counter() - t0


def dirty_hard_rows(matrix, rows):
    """Write the values a transfer is most likely to bend into ``rows`` of
    the matrix's host mirror, and mark them dirty for both device copies:
    NaN payloads in ``attr_num`` / ``attr_ver`` (a float round trip may
    quieten or canonicalise them), ``port_words`` with bit 31 set (a signed
    detour would flip it), ``eligible`` toggled (so both truth values
    cross), and a value of the row's own in every other field."""
    import numpy as np

    host = matrix.snapshot_host()
    for r in (int(r) for r in rows):
        payload = np.array([0x7FC01234 + r, 0xFFC0BEEF - r], np.uint32)
        host["attr_num"][r, :2] = payload.view(np.float32)
        host["attr_ver"][r, -2:] = payload[::-1].view(np.float32)
        host["port_words"][r, [0, -1]] = (0x80000000 | r, 0xFFFFFFFF - r)
        host["eligible"][r] = not host["eligible"][r]
        host["used"][r] = (r + 0.25, r + 0.5, -0.0)
        host["attr_hash"][r, 1] = -(r + 1)
        host["class_id"][r] = r % 7
        host["dev_used"][r, 0] = r + 3
        host["prio_used"][r, -1] = (1e-38, r, 3e38)
        host["dyn_used"][r] = 2**31 - 1 - r
        matrix._dirty.add(r)
        matrix._sharded_dirty.add(r)


def host_mirror(matrix):
    """The matrix's host arrays as a ``DeviceArrays`` (views, no copy):
    what a device copy must equal after a sync."""
    from nomad_tpu.state.matrix import DeviceArrays

    return DeviceArrays(
        **{f: matrix._alloc[f] for f in DeviceArrays._fields})


def assert_bits_equal(got, want, what=""):
    """Every field of two ``DeviceArrays`` (device or numpy) byte for
    byte: ``assert_array_equal`` would call two NaNs of different payload
    equal and -0.0 equal to 0.0."""
    import numpy as np

    for f, a, b in zip(type(want)._fields, got, want):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape, (what, f)
        assert a.tobytes() == b.tobytes(), f"{what}: field {f} differs"


def check_sync_span(n_device_shards=1):
    """A launch's ``coalescer.sync`` span says how many host operands the
    dirty-row scatter handed jax: 1 when rows were dirty (their twelve
    fields and the index in one pack), 0 when none were; the device copy
    the launch read is the host mirror bit for bit.  Uses the file's wide
    coalescer (``check_packed_launch``'s), which has launched once."""
    from nomad_tpu import trace

    coal = launched_coalescer(n_device_shards)
    m = coal.matrix
    claimed = sorted(m.node_of)
    rows = [claimed[0], claimed[1], claimed[-1]]  # both ends of the node axis

    def sync_args():
        trace.clear()
        launch_lanes(coal, 2)
        (rec,) = [r for r in trace.dump() if r["name"] == "coalescer.sync"]
        return rec["args"]

    dirty_hard_rows(m, rows)
    operands0, bytes0 = m.scatter_operands_total, m.upload_bytes_total
    args = sync_args()
    assert args["operands"] == 1 and args["rows"] == len(rows)
    assert m.scatter_operands_total == operands0 + 1
    assert args["bytes"] == m.upload_bytes_total - bytes0 == (
        m._pack_rows(rows).nbytes)
    assert args["shards"] == coal.mesh_shape()[1]
    dev = m._device if n_device_shards == 1 else m._sharded_device
    assert_bits_equal(dev, host_mirror(m), f"{n_device_shards} device(s)")
    clean = sync_args()
    assert (clean["operands"], clean["rows"], clean["bytes"]) == (0, 0, 0)
    return coal


def check_enqueue_span(n_device_shards=1):
    """A launch's ``coalescer.enqueue`` span says what it handed jax: ONE
    jitted call, and its operands buffer by buffer (the resident matrix's
    twelve fields and ``used``, the two packs, the three node-axis buffers,
    the carry: 19 whatever the lanes' requests hold); the counter the
    server exports as ``nomad.coalescer.device_calls_total`` grows by one a
    launch, as the launches' own counter does.  The numpy twin's route makes
    no jitted call and says nothing.  Uses the file's wide coalescer."""
    from nomad_tpu import trace

    coal = launched_coalescer(n_device_shards)
    calls0, launches0 = coal.device_calls, coal.fused_dispatches
    for k in (1, 9, 64):
        trace.clear()
        launch_lanes(coal, k)
        (rec,) = [r for r in trace.dump() if r["name"] == "coalescer.enqueue"]
        assert rec["args"]["calls"] == 1 and rec["args"]["lanes"] == k
        assert rec["args"]["operands"] == 12 + 1 + 2 + 3 + 1
    assert coal.device_calls - calls0 == coal.fused_dispatches - launches0 == 3
    twin = wide_coalescer(n_device_shards, nodes=10, capacity=16, lanes=8)
    trace.clear()
    launch_lanes(twin, 2, degraded=True, classes=16)  # an open breaker's route
    (rec,) = [r for r in trace.dump() if r["name"] == "coalescer.enqueue"]
    assert "calls" not in rec["args"]
    assert (twin.device_calls, twin.fused_dispatches) == (0, 1)
    return coal


def random_launch(seed, nodes, lanes, features, live=None, steps=4):
    """Randomised operands of one fused launch, every stage engaged as far
    as ``features`` goes: ``(arrays, used, delta_rows, delta_vals,
    tg_counts, spread_counts, penalties, reqs, class_eligs, host_masks,
    lane_steps)`` in ``fused_place_batch``'s order, numpy throughout.
    Attribute values come from a vocabulary of five (0 = unset), so
    constraints, affinities, spreads and limits all hit and miss; asks are
    a tenth of a node, so lanes contend for the emptier nodes."""
    import numpy as np

    from nomad_tpu.ops import encode as e
    from nomad_tpu.ops.kernels import device_request
    from nomad_tpu.state import matrix as mx

    rng = np.random.default_rng(seed)
    f32, i32 = np.float32, np.int32
    n, b = nodes, lanes
    vocab = lambda *shape: rng.choice(
        6, shape, p=[.05, .19, .19, .19, .19, .19]).astype(i32)
    small = lambda *shape: rng.integers(0, 4, shape).astype(f32)
    totals = np.tile(np.array([[4000.0, 8192.0, 1000.0]], f32), (n, 1))
    prio = (rng.random((n, mx.PRIORITY_BUCKETS, 3)) < 0.1) * totals[:, None] / 8
    num = small(n, mx.ATTR_SLOTS)
    num[rng.random(num.shape) < 0.05] = np.nan
    arrays = mx.DeviceArrays(
        totals=totals,
        used=(totals * rng.choice([0.2, 0.5, 0.95], (n, 1))).astype(f32),
        eligible=rng.random(n) < 0.9,
        attr_hash=vocab(n, mx.ATTR_SLOTS),
        attr_num=num,
        attr_ver=small(n, mx.ATTR_SLOTS),
        class_id=rng.choice(5, n, p=[.04, .24, .24, .24, .24]).astype(i32) - 1,
        dev_total=rng.integers(0, 3, (n, mx.DEVICE_SLOTS)).astype(i32),
        dev_used=rng.integers(0, 2, (n, mx.DEVICE_SLOTS)).astype(i32),
        prio_used=prio.astype(f32),
        port_words=rng.integers(0, 2 ** 32, (n, mx.PORT_WORDS), np.uint32),
        dyn_used=rng.integers(0, 100, n).astype(i32),
    )

    def slots(width, cap, holes=False):
        """(b, cap) slots: the first ``width`` mostly live, the rest -1."""
        s = rng.integers(0, mx.ATTR_SLOTS, (b, cap)).astype(i32)
        dead = np.arange(cap)[None, :] >= rng.integers(
            1, width + 1, (b, 1)) if width else np.ones((b, cap), bool)
        if holes and width:  # spread slots are positional
            dead = (np.arange(cap)[None, :] >= width) | (
                rng.random((b, cap)) < 0.3)
        return np.where(dead, -1, s).astype(i32)

    def ops(cap):
        """Mostly the ops most nodes pass, so that lanes place."""
        p = np.array([3, 35, 3, 6, 3, 6, 35, 1, 1, 1, 3, 1, 3], float)
        return rng.choice(13, (b, cap), p=p / p.sum()).astype(i32)

    f = features
    s_hash = vocab(b, e.MAX_SPREADS, e.MAX_SPREAD_VALUES)
    s_hash[:, :, 4:] = 0  # room for a value the scan sees first
    desired = small(b, e.MAX_SPREADS, e.MAX_SPREAD_VALUES) + 1
    desired[rng.random(desired.shape) < 0.4] = np.nan
    implicit = small(b, e.MAX_SPREADS)
    implicit[rng.random(implicit.shape) < 0.5] = np.nan
    dp_hash = vocab(b, e.MAX_DISTINCT_PROPS, e.MAX_DISTINCT_VALUES)
    dp_hash[:, :, 3:] = 0
    reqs = e.SchedRequest(
        ask=np.tile(np.array([[400.0, 800.0, 100.0]], f32), (b, 1)),
        c_slot=slots(f.c_width, e.MAX_CONSTRAINTS),
        c_op=ops(e.MAX_CONSTRAINTS),
        c_hash=vocab(b, e.MAX_CONSTRAINTS),
        c_num=small(b, e.MAX_CONSTRAINTS),
        dc_hash=np.where(
            rng.random((b, 1)) < 0.5, -1,
            rng.integers(1, 6, (b, e.MAX_DATACENTERS))).astype(i32),
        dev_ask=(rng.random((b, mx.DEVICE_SLOTS)) < 0.03).astype(i32),
        algorithm=rng.integers(0, 2, b).astype(i32),
        desired_count=rng.integers(1, 9, b).astype(f32),
        a_slot=slots(f.a_width, e.MAX_AFFINITIES),
        a_op=ops(e.MAX_AFFINITIES),
        a_hash=vocab(b, e.MAX_AFFINITIES),
        a_num=small(b, e.MAX_AFFINITIES),
        a_weight=rng.integers(-100, 101, (b, e.MAX_AFFINITIES)).astype(f32),
        s_slot=slots(f.s_width, e.MAX_SPREADS, holes=True),
        s_weight=rng.integers(1, 101, (b, e.MAX_SPREADS)).astype(f32),
        s_even=rng.random((b, e.MAX_SPREADS)) < 0.5,
        s_value_hash=s_hash,
        s_desired=desired,
        s_implicit=implicit,
        s_sum_weights=rng.integers(50, 201, b).astype(f32),
        preempt_bucket=(
            rng.integers(-1, mx.PRIORITY_BUCKETS + 1, b) if f.preempt
            else np.full(b, -1)).astype(i32),
        distinct_hosts=rng.random(b) < 0.5,
        p_static=np.where(
            f.ports & (rng.random((b, e.MAX_STATIC_PORTS)) < 0.2),
            rng.integers(0, 32 * mx.PORT_WORDS, (b, e.MAX_STATIC_PORTS)), -1
        ).astype(i32),
        p_dyn=(rng.integers(0, 3, b) * f.ports).astype(i32),
        dp_slot=slots(f.dp_width, e.MAX_DISTINCT_PROPS, holes=True),
        dp_limit=rng.integers(1, 4, (b, e.MAX_DISTINCT_PROPS)).astype(f32),
        dp_value_hash=dp_hash,
        dp_count=small(b, e.MAX_DISTINCT_PROPS, e.MAX_DISTINCT_VALUES),
    )
    k = 4
    delta_rows = np.where(
        rng.random((b, k)) < 0.5, rng.integers(0, n, (b, k)), -1).astype(i32)
    lane_steps = np.zeros(b, i32)
    lane_steps[: b if live is None else live] = rng.integers(
        1, steps + 1, b if live is None else live)
    return (
        arrays,
        arrays.used,
        delta_rows,
        (rng.random((b, k, 3)) * 200).astype(f32),
        (rng.random((b, n)) < 0.1).astype(i32),  # tg_counts
        small(b, e.MAX_SPREADS, e.MAX_SPREAD_VALUES),  # spread_counts
        rng.random((b, n)) < 0.05,  # penalties
        device_request(reqs, f.dp_width),
        rng.random((b, 4)) < 0.8,  # class_eligs
        rng.random((b, n)) < 0.9,  # host_masks
        lane_steps,
    )
