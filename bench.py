"""C2M-scale scheduler benchmark (driver entry).

Simulates the reference's headline scale — 10K nodes carrying ~2M
allocations (BASELINE.md / SURVEY.md §6) — and measures BOTH:

1. **Kernel dispatch throughput**: the batched TPU scheduler kernel (each
   eval scores EVERY node, no candidate sampling, B evals per dispatch).
2. **End-to-end server-loop throughput**: evals driven through
   broker → worker → snapshot-sync → stack → plan queue → serialized
   applier (the full optimistic-concurrency path), matching the
   reference's ``nomad.worker.invoke_scheduler`` + ``nomad.plan.*``
   timers (worker.go:245, plan_apply.go:185,370,401).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
Target (BASELINE.json): >= 50K evals/sec, p99 < 5 ms, on 1x TPU v5e.

The backend is whatever ``jax.devices()`` gives: there is no probe, no
retry and no CPU fallback — a run that cannot reach its device fails.
``platform`` / ``device_kind`` / ``device_count`` in the output say what
the numbers were measured on (an explicit ``JAX_PLATFORMS=cpu`` from the
caller is legal and is disclosed the same way).  The JSON line is printed
even when a phase raises (its ``*_error`` key says which), and the exit
code is then non-zero.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

N_NODES = int(os.environ.get("BENCH_NODES", "10000"))
# Capacity tracks the asked node count (pow2, min 256) so BENCH_NODES
# probes actually change the compiled shapes — round-3 probes at
# BENCH_NODES=512 silently kept the 10240-wide matrix and concluded
# "throughput is N-independent" from identical programs.
CAPACITY = int(os.environ.get(
    "BENCH_CAPACITY",
    10240 if 8192 < N_NODES <= 10240
    else max(256, 1 << (N_NODES - 1).bit_length()),
))
N_ALLOCS = int(os.environ.get("BENCH_ALLOCS", "2000000"))
BATCH = int(os.environ.get("BENCH_BATCH", "4096"))
# Enough samples that p99 is a real tail statistic, not the max.
DISPATCHES = int(os.environ.get("BENCH_DISPATCHES", "100"))
# In-flight dispatch depth for the pipelined (headline) throughput phase.
PIPELINE_DEPTH = int(os.environ.get("BENCH_PIPELINE", "8"))
# Interactive-batch phase size (one coalesced burst of user-facing evals).
INTERACTIVE_BATCH = int(os.environ.get("BENCH_INTERACTIVE_BATCH", "256"))

# End-to-end loop knobs.  Worker count is the in-flight eval bound: with
# the dispatch coalescer batching every in-flight select into one kernel
# call, throughput scales with workers until the host (GIL) saturates.
E2E = os.environ.get("BENCH_E2E", "1") != "0"
E2E_JOBS = int(os.environ.get("BENCH_E2E_JOBS", "512"))
E2E_GROUP_COUNT = int(os.environ.get("BENCH_E2E_COUNT", "2"))
E2E_PROBES = int(os.environ.get("BENCH_E2E_PROBES", "50"))
E2E_WORKERS = int(os.environ.get("BENCH_E2E_WORKERS", "32"))

# Host-only phase knobs (fake-device e2e burst; see bench_host_only).
HOST_ONLY = os.environ.get("BENCH_HOST_ONLY", "1") != "0"
HOST_ONLY_NODES = int(os.environ.get("BENCH_HOST_NODES", "2000"))
HOST_ONLY_JOBS = int(os.environ.get("BENCH_HOST_JOBS", "1024"))
HOST_ONLY_WORKERS = int(os.environ.get("BENCH_HOST_WORKERS", "8"))

# Live-pipeline phase knobs (see bench_live_pipeline): lane cap stays SMALL
# so pipeline depth — not lane coalescing — is the concurrency lever, and
# workers ≥ max_depth × lanes so every pipeline slot can fill.
LIVE_PIPELINE = os.environ.get("BENCH_LIVE_PIPELINE", "1") != "0"
LIVE_DEPTHS = tuple(
    int(d) for d in os.environ.get("BENCH_LIVE_DEPTHS", "1,4,8").split(",")
)
LIVE_LATENCY_MS = float(os.environ.get("BENCH_LIVE_LATENCY_MS", "65"))
LIVE_JOBS = int(os.environ.get("BENCH_LIVE_JOBS", "96"))
LIVE_NODES = int(os.environ.get("BENCH_LIVE_NODES", "256"))
LIVE_LANES = int(os.environ.get("BENCH_LIVE_LANES", "2"))
LIVE_WORKERS = int(os.environ.get("BENCH_LIVE_WORKERS", "16"))

# Overload phase knobs (see bench_overload): loadgen traffic shapes
# replayed against a fake-device server with the SLO control loop armed.
OVERLOAD = os.environ.get("BENCH_OVERLOAD", "1") != "0"
OVERLOAD_NODES = int(os.environ.get("BENCH_OVERLOAD_NODES", "512"))
OVERLOAD_WORKERS = int(os.environ.get("BENCH_OVERLOAD_WORKERS", "4"))
OVERLOAD_RATE = float(os.environ.get("BENCH_OVERLOAD_RATE", "120"))
OVERLOAD_DURATION = float(os.environ.get("BENCH_OVERLOAD_DURATION", "4"))
OVERLOAD_SEED = int(os.environ.get("BENCH_OVERLOAD_SEED", "11"))

# Sharded megabatch phase knobs (see bench_sharded): node-axis shard sweep
# of the fused placement kernel.  shards=1 runs the plain (unsharded)
# fused_place_batch at the SAME eval batch — the comparison baseline the
# ledger judges sharded_evals_per_sec against; shards>1 run the
# hierarchical-top-k shard_map entry on a (1, shards) mesh.
SHARDED = os.environ.get("BENCH_SHARDED", "1") != "0"
# 16 rides along with the issue's {1, 4, 8}: per-shard score intermediates
# are B*(N/s)*4 bytes, and on a CPU host the curve keeps improving until
# they drop under the last-level cache (~4MB at s=8, ~2MB at s=16 for
# B=64, N=100K) — s=16 is where it flattens.
SHARD_SWEEP = tuple(
    int(s) for s in os.environ.get("BENCH_SHARD_SWEEP", "1,4,8,16").split(",")
)
SHARDED_BATCH = int(os.environ.get("BENCH_SHARDED_BATCH", "64"))
SHARDED_DISPATCHES = int(os.environ.get("BENCH_SHARDED_DISPATCHES", "8"))
# Placements per fused lane in the sharded sweep (scan length).
SHARDED_SCAN = int(os.environ.get("BENCH_SHARDED_SCAN", "1"))

# E2E job count on a CPU run: the full 512 is device-paced and unbounded
# on a host backend, so cap it — but keep the cap a knob, not a constant
# (the old hard-coded 64 starved the host-path pipeline enough to distort
# evals/sec downward).
CPU_E2E_JOBS = int(os.environ.get("NOMAD_TPU_BENCH_E2E_JOBS", "256"))


def init_backend():
    """Initialize the jax backend and return its devices.  No probe, no
    retry, no fallback: a backend that cannot start fails the run."""
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        # Host backend exposes ONE device by default; the sharded sweep
        # needs max(SHARD_SWEEP) of them.  The flag only works before the
        # first backend init, which is exactly where we are.
        want = max(SHARD_SWEEP) if SHARDED and SHARD_SWEEP else 1
        flags = os.environ.get("XLA_FLAGS", "")
        if want > 1 and "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count={want}"
            ).strip()
    import jax

    return jax.devices()


def build_cluster():
    """(matrix, request shapes) of the seeded sim cluster at bench size."""
    from nomad_tpu import simcluster

    m = simcluster.build_cluster(N_NODES, CAPACITY, N_ALLOCS)
    return m, simcluster.build_requests(m)


def _phase_breakdown(registry) -> dict:
    """Fold a registry's ``nomad.phase.*`` trace histograms into the
    per-phase latency table the BENCH json reports: where an eval's wall
    clock went — queue-wait vs host orchestration vs device RTT."""
    from nomad_tpu.trace import PHASE_PREFIX

    out = {}
    for key, val in registry.snapshot().items():
        if not key.startswith(PHASE_PREFIX) or not isinstance(val, dict):
            continue
        out[key[len(PHASE_PREFIX):]] = {
            "count": val["count"],
            "p50_ms": val["p50_ms"],
            "p99_ms": val["p99_ms"],
            "total_ms": round(val["mean_ms"] * val["count"], 1),
        }
    return out


def bench_kernel(result: dict) -> None:
    """Kernel dispatch phase.

    Timing discipline: every timed region ends in a REAL device→host
    fetch (``np.asarray``) — dispatch is asynchronous, so anything less
    times the enqueue — and the sync round-trip floor of a trivial jitted
    op is measured separately (``rtt_floor_ms``) so the dispatch numbers
    can be read against it.

    Two throughput modes:
    - sync: one dispatch at a time, fetch each result (latency statistic);
    - pipelined (headline): PIPELINE_DEPTH dispatches in flight, results
      fetched as they drain — how the server's dispatch coalescer actually
      drives the chip, and the honest sustained rate.
    """
    import jax
    import jax.numpy as jnp

    from nomad_tpu.ops.kernels import (
        features_of,
        fused_place_batch,
        score_batch,
    )
    from nomad_tpu.parallel import build_batch_inputs

    def _mark(msg: str) -> None:
        # Progress breadcrumbs on stderr: a run that dies should be
        # diagnosable from where the trail stops.
        sys.stderr.write(f"bench: [{time.strftime('%H:%M:%S')}] {msg}\n")
        sys.stderr.flush()

    # Sync round-trip floor: a trivial jitted op, result fetched.
    _mark("rtt floor")
    trivial = jax.jit(lambda x: x + 1)
    x = jnp.zeros((8,), jnp.float32)
    np.asarray(trivial(x))
    rtts = []
    for _ in range(10):
        t = time.time()
        np.asarray(trivial(x))
        rtts.append(time.time() - t)
    result["rtt_floor_ms"] = round(float(np.median(rtts)) * 1000.0, 3)

    _mark(f"rtt_floor={result['rtt_floor_ms']}ms; building cluster")
    m, shapes = build_cluster()
    arrays = m.sync()
    inp = build_batch_inputs(
        m, [shapes[i % len(shapes)] for i in range(BATCH)]
    )
    # Occupancy bucketing: compile for the widths the request mix actually
    # uses (the live coalescer's Features ratchet does the same).
    feats = features_of(shapes[0])
    for s in shapes[1:]:
        feats = feats.widen(features_of(s))
    result["features"] = {
        "c_width": feats.c_width, "a_width": feats.a_width,
        "s_width": feats.s_width, "preempt": feats.preempt,
        "ports": feats.ports,
    }

    def dispatch():
        return score_batch(
            arrays, arrays.used, inp["tg_counts"], inp["spread_counts"],
            inp["penalties"], inp["reqs"], inp["class_eligs"],
            inp["host_masks"], features=feats,
        )

    # Warmup (compile + cache).
    _mark("warmup compile (first dispatch)")
    placed = int((np.asarray(dispatch().rows) >= 0).sum())
    _mark("warmup done")
    for _ in range(2):
        np.asarray(dispatch().rows)

    # Setup ends here: everything after this line is measurement.
    # (``setup_s`` used to be stamped at process exit, i.e. it reported the
    # WHOLE run — the r05 artifact's 103 s — which made "how long until the
    # bench starts measuring" unreadable from the JSON.)
    if "_t_setup" in result:
        result["setup_s"] = round(time.time() - result.pop("_t_setup"), 1)

    # Sync latency phase.
    _mark("sync latency phase")
    times = []
    for _ in range(DISPATCHES):
        t = time.time()
        np.asarray(dispatch().rows)
        times.append(time.time() - t)
    arr = np.array(times)
    sync_rate = DISPATCHES * BATCH / float(arr.sum())

    # Interactive-batch phase: B=256 (one coalesced burst of user-facing
    # evals, vs the 4096-deep bulk batch).  Each sample is
    # a full dispatch + device→host fetch; the net-of-RTT column is the
    # device-side time the 5 ms target judges.
    _mark("interactive B=256 phase")
    inp_i = build_batch_inputs(
        m, [shapes[i % len(shapes)] for i in range(INTERACTIVE_BATCH)]
    )

    def dispatch_interactive():
        return score_batch(
            arrays, arrays.used, inp_i["tg_counts"], inp_i["spread_counts"],
            inp_i["penalties"], inp_i["reqs"], inp_i["class_eligs"],
            inp_i["host_masks"], features=feats,
        )

    np.asarray(dispatch_interactive().rows)  # compile for the small shape
    from nomad_tpu import trace
    from nomad_tpu.metrics import MetricsRegistry

    reg_i = MetricsRegistry()
    it = []
    for _ in range(DISPATCHES):
        t = time.time()
        with trace.span("interactive.dispatch", metrics=reg_i):
            out_i = dispatch_interactive()
        with trace.span("interactive.fetch", metrics=reg_i):
            np.asarray(out_i.rows)
        it.append(time.time() - t)
    iarr = np.array(it)
    # Launch vs device→host fetch split for the interactive burst.
    result["interactive_phase_ms"] = _phase_breakdown(reg_i)
    result.update(
        interactive_batch=INTERACTIVE_BATCH,
        interactive_dispatch_p50_ms=round(
            float(np.percentile(iarr, 50) * 1000.0), 3
        ),
        interactive_dispatch_p99_ms=round(
            float(np.percentile(iarr, 99) * 1000.0), 3
        ),
        interactive_p99_net_of_rtt_ms=round(
            float(np.percentile(iarr, 99) * 1000.0)
            - result["rtt_floor_ms"],
            3,
        ),
    )

    # Pipelined throughput phase (the headline number).
    _mark(f"pipelined phase (sync rate {sync_rate:.0f}/s)")
    n_pipe = max(DISPATCHES, PIPELINE_DEPTH * 4)
    if result.get("platform") == "cpu":
        # CPU run: each 10K-node dispatch costs ~1s of host compute;
        # halve the pipelined sample count to keep the diagnostic run
        # bounded (the platform is disclosed, the numbers are not the
        # headline claim).
        n_pipe = max(DISPATCHES, PIPELINE_DEPTH * 2)
    t0 = time.time()
    inflight = []
    for _ in range(n_pipe):
        inflight.append(dispatch())
        if len(inflight) >= PIPELINE_DEPTH:
            np.asarray(inflight.pop(0).rows)
    for out in inflight:
        np.asarray(out.rows)
    pipe_total = time.time() - t0
    pipe_rate = n_pipe * BATCH / pipe_total

    # Fused megakernel phase: the WHOLE eval pipeline — feasibility →
    # binpack → spread/affinity → evict-set → cross-lane AllocsFit
    # re-verify — in ONE launch for a batch of B evals (vs one launch per
    # eval on the solo path).  Same pipelined discipline as the headline.
    _mark("fused megakernel phase")
    n = int(np.asarray(arrays.used).shape[0])
    f_dr = jnp.full((BATCH, 1), -1, jnp.int32)
    f_dv = jnp.zeros((BATCH, 1, 3), jnp.float32)
    f_ls = jnp.ones((BATCH,), jnp.int32)  # every lane live, one step

    def dispatch_fused():
        return fused_place_batch(
            arrays, arrays.used, f_dr, f_dv, inp["tg_counts"],
            inp["spread_counts"], inp["penalties"], inp["reqs"],
            inp["class_eligs"], inp["host_masks"], f_ls,
            n_placements=1, features=feats,
        )

    t_c = time.time()
    fused_first = np.asarray(dispatch_fused())
    fused_compile_s = time.time() - t_c
    fused_placed = int((fused_first[:, :, 0] >= 0).sum())
    fused_verified = int((fused_first[:, :, -1] > 0.5).sum())
    t0 = time.time()
    inflight = []
    for _ in range(n_pipe):
        inflight.append(dispatch_fused())
        if len(inflight) >= PIPELINE_DEPTH:
            np.asarray(inflight.pop(0))
    for out in inflight:
        np.asarray(out)
    fused_rate = n_pipe * BATCH / (time.time() - t0)

    # Host staging cost per eval on the fused path: encode-slab row fills
    # plus the per-lane staging-buffer writes the coalescer performs before
    # a launch — the host work that bounds eval admission into a batch.
    from nomad_tpu.ops.encode import RequestSlab
    from nomad_tpu.scheduler.coalescer import MAX_DELTA_ROWS

    slab = RequestSlab(BATCH)
    stage = {
        "host_mask": np.ones((BATCH, n), bool),
        "tg_count": np.zeros((BATCH, n), np.int32),
        "penalty": np.zeros((BATCH, n), bool),
        "delta_rows": np.full((BATCH, MAX_DELTA_ROWS), -1, np.int32),
        "lane_steps": np.zeros((BATCH,), np.int32),
    }
    ones_n = np.ones((n,), bool)
    zeros_n = np.zeros((n,), np.int32)
    zeros_b = np.zeros((n,), bool)
    drow = np.full((MAX_DELTA_ROWS,), -1, np.int32)
    t0 = time.time()
    for i in range(BATCH):
        slab.fill(i, shapes[i % len(shapes)])
        stage["host_mask"][i] = ones_n
        stage["tg_count"][i] = zeros_n
        stage["penalty"][i] = zeros_b
        stage["delta_rows"][i] = drow
        stage["lane_steps"][i] = 1
    host_us = (time.time() - t0) / BATCH * 1e6

    result.update(
        value=round(pipe_rate, 1),
        vs_baseline=round(pipe_rate / 50000.0, 3),
        sync_evals_per_sec=round(sync_rate, 1),
        p99_ms=round(float(np.percentile(arr, 99) * 1000.0), 3),
        # The sync round-trip floor is not software-addressable; the
        # net number is what the 5ms target judges.
        p99_net_of_rtt_ms=round(
            float(np.percentile(arr, 99) * 1000.0) - result["rtt_floor_ms"],
            3,
        ),
        max_ms=round(float(arr.max()) * 1000.0, 3),
        per_eval_us=round(1e6 / pipe_rate, 2),
        batch=BATCH,
        nodes=N_NODES,
        capacity=CAPACITY,
        sim_allocs=N_ALLOCS,
        placed_in_first_batch=placed,
        dispatches=DISPATCHES,
        pipeline_depth=PIPELINE_DEPTH,
        fused_evals_per_sec=round(fused_rate, 1),
        fused_per_eval_us=round(1e6 / fused_rate, 2),
        fused_speedup_vs_staged=round(fused_rate / pipe_rate, 3),
        fused_compile_s=round(fused_compile_s, 1),
        fused_placed_in_first_batch=fused_placed,
        fused_verified_in_first_batch=fused_verified,
        # One fused launch serves BATCH evals; the solo escape-hatch path
        # is one launch per eval — the ≥10× launches-per-eval claim.
        fused_launches_per_eval=round(1.0 / BATCH, 6),
        solo_launches_per_eval=1.0,
        host_us_per_eval=round(host_us, 2),
    )


def bench_sharded(result: dict) -> None:
    """Node-sharded fused placement sweep (hierarchical top-k).

    For each shard count in SHARD_SWEEP the fused placement megakernel is
    dispatched over the full cluster at the SAME eval batch.  shards=1 is
    the unsharded ``fused_place_batch`` baseline; shards>1 lay the matrix
    over a (1, shards) mesh and run the shard_map entry where each device
    scores only its node slice and the winner election is per-shard top-k
    → cross-shard reduce (parallel/sharding.py).  Per config the sweep
    records evals/s, per-shard HBM bytes of matrix residency, and HOST
    bytes fetched per eval — the sharded path's contract is that a fetch
    is O(lanes × scan), never O(nodes).

    Ledger contract: ``sharded_evals_per_sec`` is the headline the rolling
    baseline judges.  Runs with ``BENCH_SHARD_SWEEP=1`` record the
    unsharded rate under that name (the baseline population); sweep runs
    record the best sharded (>1) rate — an "improve" verdict therefore
    means node-sharding beat the unsharded fused path at equal batch.
    """
    import jax
    import jax.numpy as jnp

    from nomad_tpu.ops.kernels import features_of, fused_place_batch
    from nomad_tpu.parallel import (
        build_batch_inputs,
        make_mesh,
        shard_matrix_arrays,
        sharded_fused_place_batch,
    )

    def _mark(msg: str) -> None:
        sys.stderr.write(f"bench: [{time.strftime('%H:%M:%S')}] {msg}\n")
        sys.stderr.flush()

    m, shapes = build_cluster()
    arrays = m.sync()
    feats = features_of(shapes[0])
    for s in shapes[1:]:
        feats = feats.widen(features_of(s))

    b = SHARDED_BATCH
    inp = build_batch_inputs(m, [shapes[i % len(shapes)] for i in range(b)])
    dr = jnp.full((b, 1), -1, jnp.int32)
    dv = jnp.zeros((b, 1, 3), jnp.float32)
    ls = jnp.full((b,), SHARDED_SCAN, jnp.int32)  # every lane, whole scan
    # Matrix residency: every leaf of the DeviceArrays snapshot; a shard
    # holds 1/s of each node-axis leaf.
    matrix_bytes = int(sum(
        getattr(x, "nbytes", 0)
        for x in jax.tree_util.tree_leaves(arrays)
    ))
    n_rows = int(arrays.used.shape[0])
    n_dev = len(jax.devices())
    disp = SHARDED_DISPATCHES
    configs: dict = {}
    for s in SHARD_SWEEP:
        key = f"s{s}"
        if s > n_dev:
            _mark(f"sharded s={s}: skipped ({n_dev} devices visible)")
            configs[key] = {"skipped_devices": n_dev}
            continue
        if n_rows % s:
            _mark(f"sharded s={s}: skipped ({n_rows} rows not divisible)")
            configs[key] = {"skipped_rows": n_rows}
            continue
        if s == 1:
            def dispatch():
                return fused_place_batch(
                    arrays, arrays.used, dr, dv, inp["tg_counts"],
                    inp["spread_counts"], inp["penalties"], inp["reqs"],
                    inp["class_eligs"], inp["host_masks"], ls,
                    n_placements=SHARDED_SCAN, features=feats,
                )
        else:
            mesh = make_mesh(s, batch=1)
            arr_s = shard_matrix_arrays(mesh, arrays)
            fn = sharded_fused_place_batch(mesh, SHARDED_SCAN)

            def dispatch(fn=fn, arr_s=arr_s):
                return fn(
                    arr_s, arr_s.used, dr, dv, inp["tg_counts"],
                    inp["spread_counts"], inp["penalties"], inp["reqs"],
                    inp["class_eligs"], inp["host_masks"], ls,
                    features=feats,
                )

        _mark(f"sharded s={s}: compile")
        t_c = time.time()
        first = np.asarray(dispatch())
        compile_s = time.time() - t_c
        t0 = time.time()
        inflight: list = []
        for _ in range(disp):
            inflight.append(dispatch())
            if len(inflight) >= 4:
                np.asarray(inflight.pop(0))
        for out in inflight:
            np.asarray(out)
        rate = disp * b / (time.time() - t0)
        configs[key] = {
            "evals_per_sec": round(rate, 1),
            "per_shard_hbm_bytes": matrix_bytes // s,
            # The ONLY device→host traffic per dispatch is the packed
            # (B, scan, 8) winner block — never a node-axis array.
            "host_bytes_per_eval": round(first.nbytes / b, 1),
            "compile_s": round(compile_s, 1),
            "placed_in_first_batch": int((first[:, :, 0] >= 0).sum()),
            "verified_in_first_batch": int((first[:, :, -1] > 0.5).sum()),
        }
        _mark(f"sharded s={s}: {rate:.0f} evals/s")

    result["sharded"] = {
        "batch": b,
        "scan": SHARDED_SCAN,
        "dispatches": disp,
        "sweep": ",".join(str(s) for s in SHARD_SWEEP),
        "configs": configs,
    }
    ran = {
        s: configs[f"s{s}"]
        for s in SHARD_SWEEP
        if "evals_per_sec" in configs.get(f"s{s}", {})
    }
    if not ran:
        return
    multi = {s: c for s, c in ran.items() if s > 1}
    pick = (
        max(multi, key=lambda s: multi[s]["evals_per_sec"])
        if multi else max(ran)
    )
    result["sharded_evals_per_sec"] = ran[pick]["evals_per_sec"]
    result["sharded_shards"] = pick
    result["sharded_host_bytes_per_eval"] = ran[pick]["host_bytes_per_eval"]
    if multi and 1 in ran:
        result["sharded_speedup_vs_unsharded"] = round(
            ran[pick]["evals_per_sec"] / ran[1]["evals_per_sec"], 3
        )


def bench_e2e(result: dict) -> None:
    """Drive evals through the LIVE server loop on the same-scale cluster:
    broker dequeue → worker snapshot-sync → scheduler stack (kernel select
    per placement) → plan queue → serialized applier verify/commit."""
    from nomad_tpu.server.server import Server, ServerConfig

    cfg = ServerConfig(
        num_workers=E2E_WORKERS,
        node_capacity=CAPACITY,
        heartbeat_min_ttl=3600.0,
        heartbeat_max_ttl=7200.0,
    )
    srv = Server(cfg)
    srv.start()
    try:
        _run_e2e(srv, result)
    finally:
        srv.shutdown()


def _run_e2e(srv, result: dict) -> None:
    from nomad_tpu import mock

    # Heartbeats stay ARMED: the heap-driven wheel serves 10K nodes from
    # one thread (the old per-node threading.Timer design needed disarming
    # at this scale).
    rng = np.random.default_rng(7)
    for i in range(N_NODES):
        node = mock.node()
        node.datacenter = "dc1"
        node.node_class = f"class-{i % 6}"
        node.attributes = dict(node.attributes)
        node.attributes["rack"] = f"r{i % 32}"
        srv.register_node(node)
    # Pre-load usage so binpack sees a non-trivial cluster (under the host
    # lock — the coalescer's sync drain runs concurrently).
    with srv.matrix._host_lock:
        host = srv.matrix.snapshot_host()
        usage = rng.uniform(0.1, 0.6, (N_NODES, 3)) * host["totals"][:N_NODES]
        host["used"][:N_NODES] = usage
        srv.matrix._dirty.update(range(N_NODES))

    def make_job(i: int):
        job = mock.job()
        tg = job.task_groups[0]
        tg.count = E2E_GROUP_COUNT
        tg.tasks[0].resources.cpu = 50 + 25 * (i % 4)
        tg.tasks[0].resources.memory_mb = 64 + 32 * (i % 3)
        return job

    # Warm the select path (first place_batch compile — can take minutes on
    # a cold TPU cache) outside the timed region.
    ev = srv.submit_job(make_job(0))
    srv.wait_for_eval(ev.id, timeout=600.0)

    # Throughput: a burst of jobs, wall-clock until every eval terminal.
    evals = []
    t0 = time.time()
    for i in range(E2E_JOBS):
        evals.append(srv.submit_job(make_job(i)))
    deadline = time.time() + 300.0
    pending = {e.id for e in evals}
    while pending and time.time() < deadline:
        done = set()
        for eid in pending:
            e = srv.store.eval_by_id(eid)
            if e is not None and e.terminal_status():
                done.add(eid)
        pending -= done
        if pending:
            # Coarse poll: latency is measured by the probe phase below;
            # a fine poll here would contend with the workers' store locks
            # and depress the throughput being measured.
            time.sleep(0.01)
    t_burst = time.time() - t0
    completed = E2E_JOBS - len(pending)

    # Latency: sequential probes with a fine-grained poll (0.25ms).
    # Timed-out probes are excluded from the percentiles (they'd be
    # censored 10s artifacts, not completions) and disclosed separately;
    # two consecutive timeouts abort the phase — the condition persists.
    lat = []
    timeouts = 0
    consecutive_timeouts = 0
    for i in range(E2E_PROBES):
        t = time.time()
        e = srv.submit_job(make_job(i))
        timed_out = False
        while True:
            cur = srv.store.eval_by_id(e.id)
            if cur is not None and cur.terminal_status():
                break
            if time.time() - t > 10.0:
                timed_out = True
                break
            time.sleep(0.00025)
        if timed_out:
            timeouts += 1
            consecutive_timeouts += 1
            if consecutive_timeouts >= 2:
                break
        else:
            consecutive_timeouts = 0
            lat.append(time.time() - t)

    result.update(
        e2e_evals_per_sec=round(completed / t_burst, 1),
        e2e_completed=completed,
        e2e_jobs=E2E_JOBS,
        e2e_placements_per_eval=E2E_GROUP_COUNT,
        e2e_workers=E2E_WORKERS,
        e2e_coalescer_dispatches=srv.coalescer.dispatches,
        e2e_coalesced_selects=srv.coalescer.coalesced_requests,
    )
    if timeouts:
        result["e2e_probe_timeouts"] = timeouts
    if lat:
        arr = np.array(lat)
        result.update(
            e2e_p50_ms=round(float(np.percentile(arr, 50) * 1000.0), 3),
            e2e_p99_ms=round(float(np.percentile(arr, 99) * 1000.0), 3),
        )


def bench_host_only(result: dict) -> None:
    """The e2e burst under the fake-device backend (NOMAD_TPU_FAKE_DEVICE=1):
    every kernel answer comes from the instant numpy twins, so the number
    isolates HOST orchestration cost — broker, snapshot-sync, reconcile,
    encode, plan submit/apply — from device dispatch entirely.

    Runs at HOST_ONLY_NODES (default 2000): the numpy twin executes the
    device's O(N) scoring serially on the host, so at 10K nodes the twin —
    a stand-in for work the TPU does in parallel — dominates the wall clock
    and masks the host-path cost this phase exists to measure.  The scale
    is disclosed in the output keys."""
    from nomad_tpu.server.server import Server, ServerConfig

    prev = os.environ.get("NOMAD_TPU_FAKE_DEVICE")
    os.environ["NOMAD_TPU_FAKE_DEVICE"] = "1"
    srv = None
    try:
        from nomad_tpu import mock

        srv = Server(ServerConfig(
            num_workers=HOST_ONLY_WORKERS,
            node_capacity=max(256, 1 << (HOST_ONLY_NODES - 1).bit_length()),
            heartbeat_min_ttl=3600.0,
            heartbeat_max_ttl=7200.0,
        ))
        srv.start()
        rng = np.random.default_rng(7)
        for i in range(HOST_ONLY_NODES):
            node = mock.node()
            node.node_class = f"class-{i % 6}"
            srv.register_node(node)
        with srv.matrix._host_lock:
            host = srv.matrix.snapshot_host()
            host["used"][:HOST_ONLY_NODES] = (
                rng.uniform(0.1, 0.6, (HOST_ONLY_NODES, 3))
                * host["totals"][:HOST_ONLY_NODES]
            )
            srv.matrix._dirty.update(range(HOST_ONLY_NODES))

        def make_job(i: int):
            job = mock.job()
            tg = job.task_groups[0]
            tg.count = E2E_GROUP_COUNT
            tg.tasks[0].resources.cpu = 50 + 25 * (i % 4)
            tg.tasks[0].resources.memory_mb = 64 + 32 * (i % 3)
            return job

        ev = srv.submit_job(make_job(0))
        srv.wait_for_eval(ev.id, timeout=120.0)

        t0 = time.time()
        evals = [srv.submit_job(make_job(i)) for i in range(HOST_ONLY_JOBS)]
        pending = {e.id for e in evals}
        deadline = time.time() + 120.0
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
        completed = HOST_ONLY_JOBS - len(pending)
        coal = srv.coalescer
        result.update(
            e2e_host_only_evals_per_sec=round(completed / wall, 1),
            e2e_host_only_jobs=HOST_ONLY_JOBS,
            e2e_host_only_nodes=HOST_ONLY_NODES,
            e2e_host_only_workers=HOST_ONLY_WORKERS,
            e2e_host_only_phase_ms=_phase_breakdown(srv.metrics),
            # Launch accounting through the live coalescer: the fused path
            # amortizes one launch over every coalesced lane.
            e2e_host_only_fused_dispatches=coal.fused_dispatches,
            e2e_host_only_fused_lanes=coal.fused_lanes,
            e2e_host_only_launches_per_eval=round(
                coal.fused_dispatches / coal.fused_lanes, 4
            ) if coal.fused_lanes else None,
            e2e_host_only_verify_conflicts=coal.verify_conflicts,
        )
    finally:
        if srv is not None:
            srv.shutdown()
        if prev is None:
            os.environ.pop("NOMAD_TPU_FAKE_DEVICE", None)
        else:
            os.environ["NOMAD_TPU_FAKE_DEVICE"] = prev


def bench_live_pipeline(result: dict) -> None:
    """The LIVE server loop under a synthetic device→host fetch latency,
    swept over coalescer pipeline depths.

    Fake-device backend with NOMAD_TPU_FAKE_DEVICE_LATENCY_MS: every
    dispatch's RESULT arrives LIVE_LATENCY_MS after launch (the latency is
    charged at resolve time, like a real device→host fetch), so
    the phase proves — without a TPU — that the coalescer's pipelined
    producer/consumer loop overlaps in-flight dispatches: depth d sustains
    ~d×lanes evals per RTT where the old serial loop managed lanes per RTT
    regardless of depth.  Lane cap is deliberately small (LIVE_LANES) so
    lane coalescing can't mask the depth effect."""
    from nomad_tpu import mock
    from nomad_tpu.server.server import Server, ServerConfig

    prev_fake = os.environ.get("NOMAD_TPU_FAKE_DEVICE")
    prev_lat = os.environ.get("NOMAD_TPU_FAKE_DEVICE_LATENCY_MS")
    os.environ["NOMAD_TPU_FAKE_DEVICE"] = "1"
    os.environ["NOMAD_TPU_FAKE_DEVICE_LATENCY_MS"] = str(LIVE_LATENCY_MS)

    def make_job(i: int):
        job = mock.job()
        tg = job.task_groups[0]
        tg.count = E2E_GROUP_COUNT
        tg.tasks[0].resources.cpu = 50 + 25 * (i % 4)
        tg.tasks[0].resources.memory_mb = 64 + 32 * (i % 3)
        return job

    def one_depth(depth: int) -> float:
        srv = Server(ServerConfig(
            num_workers=LIVE_WORKERS,
            node_capacity=max(256, 1 << (LIVE_NODES - 1).bit_length()),
            coalescer_lanes=LIVE_LANES,
            pipeline_depth=depth,
            heartbeat_min_ttl=3600.0,
            heartbeat_max_ttl=7200.0,
        ))
        srv.start()
        try:
            rng = np.random.default_rng(7)
            for i in range(LIVE_NODES):
                node = mock.node()
                node.node_class = f"class-{i % 6}"
                srv.register_node(node)
            with srv.matrix._host_lock:
                host = srv.matrix.snapshot_host()
                host["used"][:LIVE_NODES] = (
                    rng.uniform(0.1, 0.6, (LIVE_NODES, 3))
                    * host["totals"][:LIVE_NODES]
                )
                srv.matrix._dirty.update(range(LIVE_NODES))
            ev = srv.submit_job(make_job(0))
            srv.wait_for_eval(ev.id, timeout=120.0)

            t0 = time.time()
            evals = [srv.submit_job(make_job(i)) for i in range(LIVE_JOBS)]
            pending = {e.id for e in evals}
            deadline = time.time() + 120.0
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
            # Per-depth phase split: deeper pipelines should move time
            # out of coalescer.device (overlapped) into queue phases.
            return (LIVE_JOBS - len(pending)) / wall, _phase_breakdown(
                srv.metrics
            )
        finally:
            srv.shutdown()

    try:
        rates = {}
        for depth in LIVE_DEPTHS:
            rate, phases = one_depth(depth)
            rates[depth] = round(rate, 1)
            result[f"live_pipeline_evals_per_sec_depth{depth}"] = rates[depth]
            result[f"live_pipeline_phase_ms_depth{depth}"] = phases
        result.update(
            live_pipeline_latency_ms=LIVE_LATENCY_MS,
            live_pipeline_jobs=LIVE_JOBS,
            live_pipeline_nodes=LIVE_NODES,
            live_pipeline_lanes=LIVE_LANES,
            live_pipeline_workers=LIVE_WORKERS,
        )
        base = rates.get(min(rates))
        if base:
            result["live_pipeline_speedup"] = round(
                rates[max(rates)] / base, 2
            )
    finally:
        for key, prev in (
            ("NOMAD_TPU_FAKE_DEVICE", prev_fake),
            ("NOMAD_TPU_FAKE_DEVICE_LATENCY_MS", prev_lat),
        ):
            if prev is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = prev


def bench_overload(result: dict) -> None:
    """Admission/shed behavior under synthetic traffic shapes.

    Replays each loadgen shape (poisson / diurnal / flash_crowd) against
    a fake-device server with the overload control loop armed on
    compressed thresholds and a deliberately small admission bucket, so
    a few seconds of traffic exercises the whole actuator chain:
    429s at submit, priority shedding in the broker, gate level moves.
    Records per-shape evals/s, latency percentiles (submit → terminal,
    over every admitted eval), and admit/reject/shed deltas — the ledger
    rows that catch an actuator regressing into over- or under-shedding.
    """
    from nomad_tpu import mock
    from nomad_tpu.obs.controller import OverloadConfig
    from nomad_tpu.server.server import Server, ServerConfig

    sys.path.insert(
        0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "tools")
    )
    from loadgen import LoadGen, LoadGenConfig, make_job_factory

    prev = os.environ.get("NOMAD_TPU_FAKE_DEVICE")
    os.environ["NOMAD_TPU_FAKE_DEVICE"] = "1"
    srv = None
    try:
        # Compressed control loop: same shape as the chaos scenarios —
        # host-scale pressure peaks far below production thresholds, so
        # enter/exit levels and windows shrink to match the phase length.
        srv = Server(ServerConfig(
            num_workers=OVERLOAD_WORKERS,
            node_capacity=max(256, 1 << (OVERLOAD_NODES - 1).bit_length()),
            heartbeat_min_ttl=3600.0,
            heartbeat_max_ttl=7200.0,
            slo_interval=0.15,
            overload_config=OverloadConfig(
                gate_enter=0.03, gate_exit=0.012,
                shed_enter=0.05, shed_exit=0.025,
                window_fast=0.6, window_slow=3.0,
                min_dwell=0.4, cooldown=0.2,
                max_flips=12, flip_window=30.0,
                shed_delay=0.3, shed_jitter=0.5,
                retry_after=0.5,
            ),
            admission_rate=OVERLOAD_RATE * 0.8,
            admission_burst=OVERLOAD_RATE * 0.5,
        ))
        srv.start()
        rng = np.random.default_rng(7)
        for i in range(OVERLOAD_NODES):
            node = mock.node()
            node.node_class = f"class-{i % 6}"
            srv.register_node(node)
        with srv.matrix._host_lock:
            host = srv.matrix.snapshot_host()
            host["used"][:OVERLOAD_NODES] = (
                rng.uniform(0.1, 0.6, (OVERLOAD_NODES, 3))
                * host["totals"][:OVERLOAD_NODES]
            )
            srv.matrix._dirty.update(range(OVERLOAD_NODES))

        ev = srv.submit_job(mock.job())
        srv.wait_for_eval(ev.id, timeout=120.0)

        gen = LoadGen(LoadGenConfig(
            seed=OVERLOAD_SEED, rate=OVERLOAD_RATE,
            duration=OVERLOAD_DURATION,
        ))
        factory = make_job_factory(mock)

        for shape in ("poisson", "diurnal", "flash_crowd"):
            gate0 = srv.admission_gate.stats()
            shed0 = srv.eval_broker.shed_stats()
            pending: dict = {}   # eval id -> submit time
            lat: list = []

            def submit(a, _p=pending):
                t = time.time()
                e = srv.submit_job(factory(a))
                _p[e.id] = t

            t_shape = time.time()
            stats = gen.run(submit, shape)

            # Drain: latency is stamped when the eval is OBSERVED
            # terminal, so the poll stays tight (wait_for_table wakes on
            # every eval transition).
            deadline = time.time() + 60.0
            last_index = 0
            while pending and time.time() < deadline:
                now = time.time()
                for eid in list(pending):
                    e = srv.store.eval_by_id(eid)
                    if e is not None and e.terminal_status():
                        lat.append(now - pending.pop(eid))
                if not pending:
                    break
                last_index = srv.store.wait_for_table(
                    "evals", last_index, timeout=0.1
                )

            gate1 = srv.admission_gate.stats()
            shed1 = srv.eval_broker.shed_stats()
            completed = len(lat)
            # Rate over replay + drain: completions trail arrivals, so
            # charging only the replay window would flatter the number.
            wall = max(time.time() - t_shape, 1e-6)
            result.update({
                f"overload_{shape}_offered": stats["offered"],
                f"overload_{shape}_admitted": stats["admitted"],
                f"overload_{shape}_rejected": stats["rejected"],
                f"overload_{shape}_evals_per_sec": round(completed / wall, 1),
                f"overload_{shape}_shed": int(
                    shed1["total_shed"] - shed0["total_shed"]
                ),
                f"overload_{shape}_gate_rejected": int(
                    gate1["rejected"] - gate0["rejected"]
                ),
            })
            if lat:
                arr = np.array(lat)
                result.update({
                    f"overload_{shape}_p50_ms": round(
                        float(np.percentile(arr, 50) * 1000.0), 3),
                    f"overload_{shape}_p99_ms": round(
                        float(np.percentile(arr, 99) * 1000.0), 3),
                })

            # Let the controller settle back to steady so each shape
            # starts from the same actuator state.
            settle = time.time() + 15.0
            while (srv.overload_controller.state != "steady"
                   and time.time() < settle):
                time.sleep(0.1)

        ctrl = srv.overload_controller
        result.update(
            overload_rate=OVERLOAD_RATE,
            overload_duration_s=OVERLOAD_DURATION,
            overload_nodes=OVERLOAD_NODES,
            overload_workers=OVERLOAD_WORKERS,
            overload_flips=ctrl.flips_total,
            overload_flips_suppressed=ctrl.flips_suppressed,
        )
    finally:
        if srv is not None:
            srv.shutdown()
        if prev is None:
            os.environ.pop("NOMAD_TPU_FAKE_DEVICE", None)
        else:
            os.environ["NOMAD_TPU_FAKE_DEVICE"] = prev


def main() -> None:
    t_setup = time.time()
    import nomad_tpu

    nomad_tpu.enable_compilation_cache()

    devices = init_backend()
    platform = devices[0].platform
    global BATCH, DISPATCHES, E2E_JOBS, E2E_PROBES
    if platform == "cpu" and "BENCH_DISPATCHES" not in os.environ:
        # CPU run: keep runtime bounded; the number is still honest
        # (platform is disclosed in the output).
        DISPATCHES = 20
    if platform == "cpu" and "BENCH_BATCH" not in os.environ:
        BATCH = 512
    if platform == "cpu" and "BENCH_E2E_JOBS" not in os.environ:
        E2E_JOBS = CPU_E2E_JOBS
    if platform == "cpu" and "BENCH_E2E_PROBES" not in os.environ:
        E2E_PROBES = 10

    result = {
        "metric": "eval_throughput",
        "value": 0.0,
        "unit": "evals/sec",
        "vs_baseline": 0.0,
        "platform": platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }
    # Free-form run annotation carried into the ledger entry's meta (e.g.
    # "100K-node sharded sweep") so off-default runs are self-describing.
    if os.environ.get("BENCH_NOTE"):
        result["note"] = os.environ["BENCH_NOTE"]
    result["_t_setup"] = t_setup  # consumed (and removed) by bench_kernel
    bench_kernel(result)
    result.pop("_t_setup", None)
    # A later phase that raises keeps the numbers already measured (its
    # *_error key lands in the JSON) but fails the run.
    failed = []
    for enabled, phase, error_key in (
        (SHARDED, bench_sharded, "sharded_error"),
        (E2E, bench_e2e, "e2e_error"),
        (HOST_ONLY, bench_host_only, "e2e_host_only_error"),
        (LIVE_PIPELINE, bench_live_pipeline, "live_pipeline_error"),
        (OVERLOAD, bench_overload, "overload_error"),
    ):
        if not enabled:
            continue
        try:
            phase(result)
        except Exception as e:  # noqa: BLE001 — reported, then exit non-zero
            import traceback

            traceback.print_exc()
            result[error_key] = f"{type(e).__name__}: {e}"
            failed.append(error_key)
    result["total_s"] = round(time.time() - t_setup, 1)
    print(json.dumps(result))
    _record_ledger(result)
    if failed:
        sys.exit(f"bench: phase(s) failed: {', '.join(failed)}")


def _record_ledger(result: dict) -> None:
    """Regression ledger: append this run to BENCH_LEDGER.jsonl and print
    improve/flat/regress verdicts vs the rolling baseline (stderr, so
    the stdout JSON-line contract stays parseable).
    NOMAD_TPU_BENCH_LEDGER redirects the ledger (tests point it at a
    tmp file so toy-cluster smokes don't pollute real baselines);
    "0"/"off" disables the hook entirely."""
    ledger_env = os.environ.get("NOMAD_TPU_BENCH_LEDGER", "")
    if ledger_env.lower() in ("0", "off", "no"):
        return
    try:
        sys.path.insert(
            0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "tools"))
        import bench_history

        kw = {"ledger": ledger_env} if ledger_env else {}
        entry = bench_history.record_run(result, source="bench.py", **kw)
        for line in bench_history.format_verdicts(entry):
            print(line, file=sys.stderr)
    except Exception as e:  # noqa: BLE001 — the ledger must never cost a run
        print(f"bench ledger skipped: {type(e).__name__}: {e}",
              file=sys.stderr)


if __name__ == "__main__":
    main()
