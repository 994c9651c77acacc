"""Tier-1 host-loop smoke: the LIVE server loop — broker dequeue → worker
snapshot-sync → stack select → coalescer → plan queue → batched applier —
must place a job burst above a conservative throughput floor under the
fake-device backend (NOMAD_TPU_FAKE_DEVICE=1).

The floor is deliberately ~10x below the measured rate (~600 evals/s at
2000 nodes, tools/host_loop_profile.txt) so the test never flakes on a
loaded CI box, while still catching a reversion to the pre-overhaul
regime (~5 evals/s through the real dispatch path, ~78 evals/s under the
fake device before the host-path work)."""

from __future__ import annotations

import time

import numpy as np

from nomad_tpu import mock
from nomad_tpu.server.server import Server, ServerConfig

N_NODES = 200
N_JOBS = 128
FLOOR_EVALS_PER_SEC = 50.0

MEGABATCH_B = 256
MEGABATCH_FLOOR = 3.0


def test_host_loop_burst_above_floor(monkeypatch):
    monkeypatch.setenv("NOMAD_TPU_FAKE_DEVICE", "1")
    srv = Server(ServerConfig(
        num_workers=4,
        node_capacity=256,
        heartbeat_min_ttl=3600.0,
        heartbeat_max_ttl=7200.0,
    ))
    srv.start()
    try:
        for i in range(N_NODES):
            node = mock.node()
            node.node_class = f"class-{i % 6}"
            srv.register_node(node)

        def make_job(i: int):
            job = mock.job()
            tg = job.task_groups[0]
            tg.count = 2
            tg.tasks[0].resources.cpu = 50 + 25 * (i % 4)
            tg.tasks[0].resources.memory_mb = 64 + 32 * (i % 3)
            return job

        # Warm the select path outside the timed region.
        ev = srv.submit_job(make_job(0))
        assert srv.wait_for_eval(ev.id, timeout=60.0)

        t0 = time.time()
        evals = [srv.submit_job(make_job(i)) for i in range(N_JOBS)]
        pending = {e.id for e in evals}
        deadline = time.time() + 60.0
        last_index = 0
        while pending and time.time() < deadline:
            pending = {
                eid for eid in pending
                if not (
                    (e := srv.store.eval_by_id(eid)) is not None
                    and e.terminal_status()
                )
            }
            if not pending:
                break
            last_index = srv.store.wait_for_table(
                "evals", last_index, timeout=0.25
            )
        wall = time.time() - t0

        assert not pending, f"{len(pending)} evals never went terminal"
        rate = N_JOBS / wall
        assert rate >= FLOOR_EVALS_PER_SEC, (
            f"host loop placed {N_JOBS} evals at {rate:.1f}/s — below the "
            f"{FLOOR_EVALS_PER_SEC}/s floor (pre-overhaul regression?)"
        )
        # The burst must have actually placed allocs, not failed them.
        n_allocs = len(srv.store.allocs)
        assert n_allocs >= N_JOBS, (
            f"only {n_allocs} allocs for {N_JOBS} jobs x count=2"
        )
    finally:
        srv.shutdown()


def test_megabatch_throughput_floor():
    """Tier-1 CI gate: the mega-batched fused kernel must process a B=256
    eval batch ≥ 3× faster than the staged per-eval dispatch path it
    replaced, on the CPU backend CI runs on.

    Measured on the real (JAX CPU) kernels because the win being gated is
    launch amortization — one fused launch vs 256 per-eval dispatches.
    The NOMAD_TPU_FAKE_DEVICE numpy twin is a per-lane loop by design
    (same compute either way — its parity is pinned in
    tests/test_megakernel.py), so it cannot observe this regression.
    Headroom is real: measured ~8× on an idle box; 3× is the flake-proof
    floor."""
    import jax
    import jax.numpy as jnp

    from nomad_tpu.ops import RequestEncoder, kernels, place_task_group
    from nomad_tpu.ops.encode import MAX_SPREADS, MAX_SPREAD_VALUES
    from nomad_tpu.state import NodeMatrix
    from nomad_tpu.structs import (
        DriverInfo, Job, Node, NodeResources, Resources, Task, TaskGroup,
    )

    m = NodeMatrix(capacity=256)
    for i in range(N_NODES):
        m.upsert_node(Node(
            datacenter="dc1",
            resources=NodeResources(cpu=4000 + 10 * i, memory_mb=8192,
                                    disk_mb=100 * 1024),
            drivers={"mock": DriverInfo()},
        ))

    def make_job(i: int) -> Job:
        tg = TaskGroup(name="web", count=1, tasks=[Task(resources=Resources(
            cpu=50 + 25 * (i % 4), memory_mb=64 + 32 * (i % 3)))])
        return Job(task_groups=[tg])

    enc = RequestEncoder(m)
    compiled = [
        enc.compile(make_job(i), make_job(i).task_groups[0])
        for i in range(MEGABATCH_B)
    ]
    arrays = m.sync()
    n = int(arrays.used.shape[0])
    feats = kernels.features_of(compiled[0].request)
    for c in compiled[1:]:
        feats = feats.widen(kernels.features_of(c.request))

    tg0 = jnp.zeros((n,), jnp.int32)
    sc0 = jnp.zeros((MAX_SPREADS, MAX_SPREAD_VALUES), jnp.float32)
    pen0 = jnp.zeros((n,), bool)
    ce0 = jnp.ones((2,), bool)
    hm0 = jnp.ones((n,), bool)

    def staged_per_eval():
        rows = []
        for c in compiled:
            r = place_task_group(arrays, c.request, arrays.used, tg0, sc0,
                                 pen0, ce0, hm0, 1, features=feats)
            rows.append(np.asarray(r.rows))
        return rows

    reqs = jax.tree_util.tree_map(
        lambda *xs: np.stack(xs), *[c.request for c in compiled]
    )
    B = MEGABATCH_B
    dr = jnp.full((B, 1), -1, jnp.int32)
    dv = jnp.zeros((B, 1, 3), jnp.float32)
    tgb = jnp.zeros((B, n), jnp.int32)
    scb = jnp.zeros((B, MAX_SPREADS, MAX_SPREAD_VALUES), jnp.float32)
    penb = jnp.zeros((B, n), bool)
    ceb = jnp.ones((B, 2), bool)
    hmb = jnp.ones((B, n), bool)
    ls = jnp.ones((B,), jnp.int32)  # every lane live, its one step

    def fused_batch():
        return np.asarray(kernels.fused_place_batch(
            arrays, arrays.used, dr, dv, tgb, scb, penb, reqs, ceb, hmb,
            ls, n_placements=1, features=feats,
        ))

    # Warm both paths out of the timed region (compile + first transfer),
    # then take the best of 3 so a CI scheduling hiccup on one rep can't
    # fail the gate.
    staged_rows = staged_per_eval()
    fused_out = fused_batch()

    staged_s = min(_timed(staged_per_eval) for _ in range(3))
    fused_s = min(_timed(fused_batch) for _ in range(3))
    ratio = staged_s / fused_s

    # Both paths placed every eval (sanity, not the gate): the first lane
    # where it lands alone, the later ones there or, once the lanes before
    # them have claimed that node's room, on their best node that is left
    # (the launch resolves its lanes' picks; the re-verify passes them).
    rows = fused_out[:, 0, 0].astype(np.int32)
    assert rows[0] == int(staged_rows[0][0]) and (rows >= 0).all()
    assert np.isin(fused_out[:, 0, kernels.FUSED_PACKED_VERIFIED],
                   (1.0, 2.0)).all()
    assert ratio >= MEGABATCH_FLOOR, (
        f"fused megakernel processed B={B} at only {ratio:.2f}x the staged "
        f"per-eval path ({staged_s * 1e6 / B:.0f} -> {fused_s * 1e6 / B:.0f} "
        f"us/eval) — below the {MEGABATCH_FLOOR}x floor"
    )


def _timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


# ---------------------------------------------------------------------------
# The route by which an operation can fail for good (ISSUE 30): a slot the
# lane asked for that comes back empty although the cluster has room reads
# "no node can take it" on the host (stack.py: options.append(None)), the
# eval completes with queued allocations and a blocked eval, and nothing in
# a cluster that stops and preempts nothing ever unblocks it.
# ---------------------------------------------------------------------------

HERD_NODES = 96
HERD_FRONTIER = 32
HERD_JOBS = 208
HERD_SPARE = 4


def _place_the_herd(srv):
    """HERD_JOBS identical binpack jobs of width 1-8 in one burst onto a
    frontier of nearly full nodes, failed evals (out of plan attempts)
    registered again as the benchmark's client does.  Returns (jobs, every
    eval seen)."""
    from nomad_tpu.structs import Resources

    def make_job(i):
        job = mock.batch_job() if i % 3 == 0 else mock.job()
        tg = job.task_groups[0]
        tg.count = 1 + i % 8
        tg.tasks[0].resources.cpu = 100
        tg.tasks[0].resources.memory_mb = 64
        return job

    jobs = [make_job(i) for i in range(HERD_JOBS)]
    demand = sum(j.task_groups[0].count for j in jobs)

    nodes = [mock.node() for _ in range(HERD_NODES)]
    for n in nodes:
        srv.register_node(n)
    # The frontier binpack ranks first: room for ONE ask (100 MHz / 64 MB
    # of the 3,900 / 7,936 a node offers), so the lanes of one launch all
    # want the same node, over and over.  The other nodes hold the rest of
    # the burst and HERD_SPARE asks more: the cluster has room for every
    # job at all times, and only just at the end.  (While it has, the
    # claims of one launch never exceed the room: the kernel's fallback,
    # a lane keeping its own pick, is what tests/test_megakernel.py's
    # few-free-nodes cases run.)
    rest = HERD_NODES - HERD_FRONTIER
    slots = [1] * HERD_FRONTIER + [
        (demand + HERD_SPARE - HERD_FRONTIER) // rest
        + (i < (demand + HERD_SPARE - HERD_FRONTIER) % rest)
        for i in range(rest)
    ]
    assert sum(slots) == demand + HERD_SPARE and max(slots) < 39
    fill = []
    for i, (n, k) in enumerate(zip(nodes, slots)):
        a = mock.alloc(n=n)
        a.resources = Resources(cpu=3900 - 100 * k - (i % 90),
                                memory_mb=1000 - (i % 60))
        fill.append(a)
    srv.store.upsert_allocs(srv.next_index(), fill)

    open_evals = {srv.submit_job(j).id: j for j in jobs}
    seen = {}
    deadline = time.time() + 90.0
    last_index = 0
    while open_evals and time.time() < deadline:
        for eid in list(open_evals):
            ev = srv.store.eval_by_id(eid)
            if ev is None or not ev.terminal_status():
                continue
            seen[eid] = ev
            job = open_evals.pop(eid)
            if ev.status == "failed":
                # Lost the plan race: the client registers the job again.
                open_evals[srv.submit_job(job).id] = job
        if open_evals:
            last_index = srv.store.wait_for_table(
                "evals", last_index, timeout=0.25
            )
    assert not open_evals, f"{len(open_evals)} evals never went terminal"
    return jobs, seen


def test_herd_on_a_frontier_leaves_no_eval_blocked(monkeypatch):
    """A cluster with room, 16 workers racing through one coalescer: every
    eval ends ``complete`` or ``failed`` (then registered again), none
    completes with allocations it could not place, nothing is blocked, and
    every job is placed in full."""
    monkeypatch.setenv("NOMAD_TPU_FAKE_DEVICE", "1")
    srv = Server(ServerConfig(
        num_workers=16,
        node_capacity=128,
        coalescer_lanes=8,
        heartbeat_min_ttl=3600.0,
        heartbeat_max_ttl=7200.0,
        slo_enabled=False,
    ))
    srv.start()
    try:
        jobs, seen = _place_the_herd(srv)
        assert {e.status for e in seen.values()} <= {"complete", "failed"}
        stuck = [
            e.id for e in seen.values()
            if e.queued_allocations and any(e.queued_allocations.values())
            or e.failed_tg_allocs
        ]
        assert not stuck, f"{len(stuck)} evals left allocations unplaced"
        assert srv.blocked_evals.blocked_count() == 0
        for job in jobs:
            live = [
                a for a in srv.store.allocs_by_job(job.namespace, job.id)
                if not a.terminal_status()
            ]
            assert len(live) == job.task_groups[0].count, job.id
        coal = srv.coalescer
        assert coal.fused_lanes > coal.fused_dispatches  # lanes did share
        assert coal.lane_repicks > 0  # and the resolution engaged
    finally:
        srv.shutdown()
