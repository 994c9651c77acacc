#!/usr/bin/env python3
"""Run one cell of the benchmark once, in a new process.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Boots the agent in-process on the cell's chips exactly as ``nomad agent
--server-only --workers 16`` does, registers the configuration's cluster,
installs the seeded usage of its allocations, warms up every shape of the
cell's traffic, measures for ``--seconds``, decides ``correct`` from the
HTTP read-back against the plain reference (check.py), and prints the
contract's one JSON line last.  The client and load generator run in a
process of their own (client.py), started before this one imports JAX.

It fails without an accelerator.  ``--rehearse`` runs a tiny cluster on a
CPU for the benchmark's own tests; such a line's device block says ``cpu``, and it is never a measurement.

Everything that belongs to one cell is data found by name: the workload in
BENCHMARK.json names a configuration (``configs/<name>.json`` through its
``file``) and a traffic mix (``traffic/<name>.json``); each per-layer metric
is read by ``readers/<metric>.py``.  A configuration may bring three things
of its own, each optional (README.md, "Adding things"): ``scheduler_config``
(set over the operator API before the first node registers), ``setup`` (a
module of ``deployments/`` whose ``install`` runs after the seeded usage)
and ``check`` (the module whose ``decide`` decides ``correct``).  A traffic
mix may have part of its operations register a resident job again
(``register_again_fraction``, ``resident_jobs``): the resident set is
registered and placed after the warm-up, untimed.
"""

from __future__ import annotations

import time

T_START = time.time()  # process start, as near as Python lets us see it

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import urllib.error  # noqa: E402
import urllib.request  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (HERE, os.path.join(HERE, "readers"),
          os.path.join(HERE, "deployments"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import check  # noqa: E402
import measure  # noqa: E402
import traffic as traffic_mod  # noqa: E402

REHEARSE = {"nodes": 480, "node_capacity": 512, "sim_allocs": 96_000,
            "workers": 4, "heartbeat_min_ttl": 10, "heartbeat_max_ttl": 20}
HEARTBEAT_TICK_S = 0.1    # the simulated clients renew in small batches
MAX_WARMUP_PASSES = 4
SCATTER_BUCKETS = 11      # dirty-row scatter compiles per pow2 bucket: 1..1024
TRACE_RING = 1 << 17      # program span records kept per thread when traced
TRACE_SECONDS = 2.0       # the profiler traces the window's last 2 s, or ...
TRACE_COLLECTIONS = 3.0   # ... this many of the heap's longest full collection
TRACE_LEAD_S = 0.5        # the profiler is armed this long before its slice


def log(msg: str) -> None:
    print(f"[{time.time() - T_START:7.2f}s] {msg}", file=sys.stderr, flush=True)


class Fail(Exception):
    """The run cannot produce a result; exit non-zero, print no line."""


# -- the cell, from data ---------------------------------------------------------

def load_cell(workload: str, root: str = ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Fail(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(root, entry["file"])) as fh:
        cfg = json.load(fh)
    return bench, cell, cfg, traffic_mod.load(cell["traffic"])


def metrics_of(bench, cell, kind, moved=None):
    """Metric entries of ``kind`` this cell reports: those that list it
    under ``workloads`` or have no such key (for per-layer metrics without
    the key: wherever the end-to-end metric they move is reported)."""
    out = []
    for m in bench[kind]:
        if "workloads" in m:
            if cell["name"] in m["workloads"]:
                out.append(m)
        elif moved is None or m["moves"] in moved:
            out.append(m)
    return out


# -- the operator's API ------------------------------------------------------------

SCHEDULER_CONFIG = "/v1/operator/scheduler/configuration"


def http_json(addr, path, body=None):
    """GET ``path`` of the agent, or PUT ``body`` to it; the decoded reply."""
    req = urllib.request.Request(
        addr + path, method="GET" if body is None else "PUT",
        data=None if body is None else json.dumps(body).encode())
    with urllib.request.urlopen(req, timeout=120) as r:
        return json.loads(r.read())


def holds(got, want) -> bool:
    """``got`` says what ``want`` asks: objects key by key, the rest equal."""
    if isinstance(want, dict):
        return isinstance(got, dict) and all(
            k in got and holds(got[k], v) for k, v in want.items())
    return got == want


def set_scheduler_config(addr, want) -> None:
    """As ``nomad operator scheduler set-config`` does, then read back: a
    key the server does not hand back as sent was not set."""
    try:
        http_json(addr, SCHEDULER_CONFIG, want)
    except urllib.error.HTTPError as e:
        raise Fail(f"scheduler_config: sent {want}, the server says {e}")
    got = http_json(addr, SCHEDULER_CONFIG)
    if not holds(got, want):
        raise Fail(f"scheduler_config: sent {want}, the server reads {got}")


def read_resident(addr, traffic, jobs):
    """The resident set as the server holds it before the window: job id ->
    its version and live allocations (id -> node).  The check holds the
    window's ``again`` operations to exactly this."""
    out = {jid: {"version": None, "allocs": {}} for jid in jobs}
    for ns in traffic_mod.namespaces(traffic):
        for j in http_json(addr, f"/v1/jobs?namespace={ns}&prefix=res-"):
            if j["id"] in out:
                out[j["id"]]["version"] = j["version"]
        for a in http_json(addr, f"/v1/allocations?namespace={ns}"):
            if a["job_id"] in out and a["desired_status"] == "run":
                out[a["job_id"]]["allocs"][a["id"]] = a["node_id"]
    return out


# -- the client process ----------------------------------------------------------

class ClientProc:
    def __init__(self):
        env = {k: v for k, v in os.environ.items()
               if not k.startswith(("JAX_", "XLA_", "TPU_"))}
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "client.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env, text=True,
        )

    def send(self, **msg):
        self.proc.stdin.write(json.dumps(msg) + "\n")
        self.proc.stdin.flush()

    def recv(self):
        line = self.proc.stdout.readline()
        if not line:
            raise Fail("the client process ended without a reply")
        reply = json.loads(line)
        if "error" in reply:
            raise Fail(f"client: {reply['error']}")
        return reply

    def ask(self, **msg):
        self.send(**msg)
        return self.recv()

    def close(self):
        try:
            if self.proc.poll() is None:
                self.send(cmd="exit")
                self.proc.wait(timeout=10)
        except (OSError, subprocess.TimeoutExpired):
            pass
        finally:
            if self.proc.poll() is None:
                self.proc.kill()
                self.proc.wait()


# -- what 10,000 clients would do ------------------------------------------------

class Heartbeats(threading.Thread):
    """What the cluster's clients would do: each renews its TTL at half the
    minimum the server grants, so the server sees nodes / (min_ttl / 2)
    heartbeats a second, evenly spread."""

    def __init__(self, server, node_ids, min_ttl):
        super().__init__(name="bench-heartbeats", daemon=True)
        self.server, self.node_ids = server, node_ids
        self.per_s = len(node_ids) / (min_ttl / 2.0)
        self.stop = threading.Event()
        self.error = None

    def run(self) -> None:
        t0, sent, n = time.perf_counter(), 0, len(self.node_ids)
        while not self.stop.wait(HEARTBEAT_TICK_S):
            due = int((time.perf_counter() - t0) * self.per_s)
            try:
                for k in range(sent, due):
                    self.server.heartbeat_node(self.node_ids[k % n])
            except Exception as e:  # noqa: BLE001 — surfaced by the caller
                self.error = e
                return
            sent = max(sent, due)


class GcPauses:
    """Full collections of the server's heap, with their pauses: a stall
    of the whole process that no span shows."""

    def __init__(self):
        self.pauses = []  # (wall time, generation, seconds)
        self._t = None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.time()
        elif self._t is not None:
            took = time.time() - self._t
            if took > 0.02:
                self.pauses.append((self._t, info["generation"], took))


def slice_seconds(seconds, collections) -> float:
    """How much of the window's end the device trace covers.  A full
    collection of the server's heap stops every thread, the launching one
    too, and a slice that lies inside one holds no launch (PR 49 was lost
    to a 0.5 s slice under a 1.0 s collection of the 100,000-node heap).
    So the slice outlasts the longest collection seen so far (the set-up's
    own, and those of the window up to now; each is longer than the last)
    three times over, on any number of chips, and launches stand on either
    side of one that falls inside it."""
    longest = max(collections, default=0.0)
    return min(seconds / 2, max(TRACE_SECONDS, TRACE_COLLECTIONS * longest))


class CompileCounter:
    """Backend compiles, from jax.monitoring."""

    def __init__(self):
        import jax.monitoring as mon

        self.n = 0
        mon.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


# -- one run -----------------------------------------------------------------------

def run(args) -> dict:
    bench, cell, cfg, traffic = load_cell(args.workload)
    overrides = {k: float(v) for k, v in (kv.split("=", 1) for kv in args.set)}
    traffic.update(overrides)
    if args.rehearse:
        cfg = {**cfg, **REHEARSE}
    seconds = args.seconds
    setup = {}

    client = ClientProc()  # before anything here touches JAX
    agent = hb = None
    try:
        import jax

        devices = jax.devices()
        platform = devices[0].platform
        if platform == "cpu" and not args.rehearse:
            raise Fail("JAX found no accelerator (platform 'cpu')")
        if platform != "cpu" and args.rehearse:
            raise Fail("--rehearse is for a CPU")
        if not args.rehearse and len(devices) < cell["chips"]:
            raise Fail(f"cell needs {cell['chips']} chips, JAX sees {len(devices)}")
        if os.environ.get("NOMAD_TPU_FAKE_DEVICE"):
            raise Fail("NOMAD_TPU_FAKE_DEVICE is set")
        try:
            import numpy as np

            import nomad_tpu
            from nomad_tpu import cli, simcluster, trace as ptrace
            from nomad_tpu.state.matrix import DeviceArrays
        except ImportError as e:
            raise Fail(f"not inside a nomad_tpu checkout ({e})")
        cache_dir = nomad_tpu.enable_compilation_cache()
        compiles = CompileCounter()
        gc_pauses = GcPauses()
        setup["import_s"] = time.time() - T_START
        log(f"device: {platform} {devices[0].device_kind} x{len(devices)}; "
            f"compile cache {cache_dir}")

        # -- the agent, as `nomad agent` builds it ---------------------------
        t = time.time()
        tmp = os.path.join(os.environ.get("TMPDIR") or "/tmp",
                           f"bench-agent-{os.getpid()}")
        os.makedirs(tmp, exist_ok=True)
        conf = os.path.join(tmp, "agent.hcl")
        with open(conf, "w") as fh:
            fh.write(
                "server {\n"
                f"  node_capacity = {cfg['node_capacity']}\n"
                f"  heartbeat_min_ttl = {cfg['heartbeat_min_ttl']}\n"
                f"  heartbeat_max_ttl = {cfg['heartbeat_max_ttl']}\n"
                "}\n")
        agent = cli.build_agent(cli.build_parser().parse_args([
            "agent", "--server-only", "--port", "0",
            "--workers", str(cfg["workers"]), "--config", conf,
        ]))
        shutil.rmtree(tmp, ignore_errors=True)
        agent.start()
        srv = agent.server
        if "scheduler_config" in cfg:
            set_scheduler_config(agent.rpc_addr, cfg["scheduler_config"])
        n = cfg["nodes"]
        node_ids = [check.node_id(i) for i in range(n)]
        for i, nid in enumerate(node_ids):
            node = simcluster.sim_node(i)
            node.id, node.name = nid, f"sim-{i:06d}"
            srv.register_node(node)
        hb = Heartbeats(srv, node_ids, cfg["heartbeat_min_ttl"])
        hb.start()
        rows = np.fromiter((srv.matrix.row_of[nid] for nid in node_ids),
                           np.int64, n)
        totals = srv.matrix.snapshot_host()["totals"][rows].copy()
        used0, prio0 = simcluster.sim_usage(totals, cfg["sim_allocs"],
                                            args.seed % (2 ** 32))
        seeded = used0.copy()  # the reference's copy; the program gets its own
        srv.matrix.set_usage(rows, used0, prio0)
        setup["register_s"] = time.time() - t
        log(f"agent at {agent.rpc_addr}: {n} nodes, usage of "
            f"{cfg['sim_allocs']} allocations")
        state = None
        if "setup" in cfg:
            # The deployment's own set-up, in the server's process as the
            # calls above are.  What it returns is the reference's copy of
            # what it installed: plain data, nothing of the program's.
            t = time.time()
            install = importlib.import_module(cfg["setup"]).install
            state = json.loads(json.dumps(
                install(srv, cfg, args.seed, rows, seeded)))
            setup["install_s"] = time.time() - t
            log(f"deployment set-up {cfg['setup']}: {setup['install_s']:.1f}s")

        # -- warm-up: every shape the window will use ---------------------------
        t = time.time()
        client.ask(cmd="init", addr=agent.rpc_addr, traffic=cell["traffic"],
                   seed=args.seed, seconds=seconds, overrides=overrides)
        coal = srv.coalescer
        for k in range(MAX_WARMUP_PASSES):
            before = compiles.n
            reply = client.ask(cmd="warmup", tag=f"w{k}")
            if reply["placed"] != reply["ops"]:
                raise Fail(f"warm-up pass {k}: {reply}")
            if k == 0:
                # The dirty-row scatter compiles once per pow2 row count.
                for b in range(SCATTER_BUCKETS):
                    some = rows[: min(1 << b, n)]
                    host = srv.matrix.snapshot_host()
                    srv.matrix.set_usage(some, host["used"][some].copy(),
                                         host["prio_used"][some].copy())
                    coal.sync_arrays()
            log(f"warm-up pass {k}: {reply['ops']} ops, "
                f"{compiles.n - before} compiles")
            if compiles.n == before:
                break
        setup["warmup_s"] = time.time() - t
        setup["warmup_passes"] = k + 1
        if traffic.get("resident_jobs"):
            # The jobs the window's ``again`` operations register again:
            # registered and placed now, untimed, and read back as the
            # check will want them (their versions and allocations).
            reply = client.ask(cmd="resident")
            if reply["placed"] != reply["ops"]:
                raise Fail(f"resident set: {reply}")
            state = dict(state or {}, resident=read_resident(
                agent.rpc_addr, traffic, reply["jobs"]))
            setup["resident_s"] = reply["seconds"]
            log(f"resident set: {reply['ops']} jobs placed in "
                f"{reply['seconds']:.1f}s")
        n_shards = coal.n_device_shards
        if not args.rehearse and n_shards != cell["chips"]:
            raise Fail(f"n_device_shards {n_shards}, cell has {cell['chips']} chips")

        # -- the window -----------------------------------------------------------
        tracing = bool(args.trace)
        trace_dir = os.path.join(ROOT, ".bench_trace")
        traced = {}
        if tracing:
            ptrace.configure(enabled=True, sample=1.0, ring=TRACE_RING)
            ptrace.clear()
            shutil.rmtree(trace_dir, ignore_errors=True)
        # A full collection now, as the last step of set-up: the server's
        # heap (10,000 nodes and their objects) takes seconds to traverse,
        # and where the next one falls should not depend on how much
        # garbage set-up happened to leave.
        t = time.time()
        gc.collect()
        setup["gc_s"] = time.time() - t
        m0 = agent.metrics()
        c0 = compiles.n
        t0 = time.time() + 0.25
        setup_s = t0 - T_START
        client.send(cmd="run", t0=t0, seconds=seconds)
        if tracing:
            # The device trace covers the window's last seconds: a whole
            # window of a 12 ms kernel is millions of events.  It is
            # stopped after the window, so writing it out disturbs nothing.
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            while True:
                length = slice_seconds(seconds, [setup["gc_s"]] + [
                    s for t, _g, s in gc_pauses.pauses if t >= t0])
                wait = t0 + seconds - length - TRACE_LEAD_S - time.time()
                if wait <= 0:
                    break
                time.sleep(min(wait, 0.25))
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            traced["marker_wall"] = time.time()
            with jax.profiler.TraceAnnotation("bench.marker"):
                time.sleep(0.001)
            traced["t0"] = time.time()
        time.sleep(max(0.0, t0 + seconds - time.time()))
        m1 = agent.metrics()
        compiles_in_window = compiles.n - c0
        spans = None
        if tracing:
            traced["seconds"] = t0 + seconds - traced["t0"]
            spans = [s for s in ptrace.dump()
                     if s["ph"] == "X" and s["ts"] + s["dur"] >= t0
                     and s["ts"] <= t0 + seconds]
            t = time.time()
            jax.profiler.stop_trace()
            log(f"trace: stop_trace took {time.time() - t:.1f}s")
        log(f"window over: {compiles_in_window} compiles inside it; draining")
        reply = client.recv()
        if hb.error is not None:
            raise Fail(f"heartbeat thread died: {hb.error!r}")

        # -- what the client saw ------------------------------------------------------
        t_end, limit = reply["t_end"], reply["limit_s"]
        attempted = []
        for r in reply["records"]:
            if (reply["loop"] == "closed" and r["status"] == "open"
                    and t_end - r["due"] <= limit):
                continue  # in flight when the window ended: left out
            late = r["placed"] is not None and r["placed"] - r["due"] > limit
            r["ok"] = r["status"] == "placed" and not (
                reply["loop"] == "open" and late)
            if not r["ok"] and not r["cause"]:
                r["cause"] = "placed_after_limit"
            attempted.append(r)
        done_in_window = sum(r["ok"] and r["placed"] <= t_end for r in attempted)
        failed = [r for r in attempted if not r["ok"]]
        causes = {}
        for r in failed:
            causes[r["cause"]] = causes.get(r["cause"], 0) + 1
        lat = measure.latencies_ms({"attempted": attempted})
        e2e = {
            "evals_per_s": done_in_window / seconds,
            "placement_p50_ms": measure.percentile(lat, 0.50),
            "placement_p95_ms": measure.percentile(lat, 0.95),
            "setup_s": setup_s,
        }
        log(f"began {len(reply['records'])} of {reply['scheduled']} dealt; "
            f"attempted {len(attempted)}, failed {len(failed)} {causes}; "
            f"evals ended {reply['evals_ended']}, failed evals "
            f"{reply['evals_failed']}, 429s "
            f"{sum(r['n429'] for r in attempted)}, stream gaps "
            f"{reply['stream_gaps']}, reconnects {reply['stream_reconnects']}")

        # -- correct: the read-back against the plain reference ------------------------
        def get(path):
            return http_json(agent.rpc_addr, path)

        t = time.time()
        checker = importlib.import_module(cfg.get("check", "check"))
        correct, numbers, lines = checker.decide(
            get, cfg, traffic, reply["records"], seeded, args.seed,
            dump=args.check_dump, state=state,
        )
        for line in lines:
            print(line, flush=True)
        if compiles_in_window:
            print(f"check: compiles_in_window = {compiles_in_window} (limit 0)",
                  flush=True)
        log(f"check took {time.time() - t:.1f}s: correct={correct}")

        stats = [d.memory_stats() or {} for d in jax.local_devices()]
        peak = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)
        device = {"platform": platform, "kind": devices[0].device_kind,
                  "count": len(devices), "memory_peak_bytes": peak}
        result = {
            "correct": correct, "attempted": len(attempted),
            "failed": len(failed), "metrics": {}, "device": device,
        }
        moved = reduced = None
        if not tracing:
            kind = "end_to_end"
            values = e2e
        else:
            kind = "per_layer"
            moved = {m["name"] for m in metrics_of(bench, cell, "end_to_end")}
            if platform != "cpu":
                import trace_reduce

                t = time.time()
                files = glob.glob(os.path.join(
                    trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
                events = trace_reduce.load(sorted(files)[-1]) if files else []
                reduced = trace_reduce.reduce(
                    events, traced["marker_wall"], traced["t0"],
                    traced["seconds"], kernels=tuple(cfg["placement_programs"]),
                    spans=spans)
                if reduced:
                    log(f"trace: programs {reduced['modules']}")
                    # What kernel_ms_per_launch is a mean of.
                    log(f"trace: launches {reduced['launches']:g} in a "
                        f"slice of {reduced['window_s']:.2f}s")
                lines = {}
                for e in events:
                    lines[(e[0], e[1])] = lines.get((e[0], e[1]), 0) + 1
                log(f"trace: {len(events)} events reduced in "
                    f"{time.time() - t:.1f}s; lines {sorted(lines.items())}")
                if reduced is None or reduced["busy_s"] <= 0:
                    raise Fail("the trace shows no operation on the device")
                device["busy_s"] = reduced["busy_s"]
                device["window_s"] = reduced["window_s"]
                result["breakdown"] = {
                    "device_ops": reduced["device_ops"],
                    "idle_gaps": reduced["idle_gaps"],
                }
            host = srv.matrix.snapshot_host()
            resident = sum(host[f].nbytes for f in DeviceArrays._fields)
            bitmap = host["port_words"].nbytes
            log(f"trace: the resident matrix is {resident} bytes, {bitmap} "
                f"of them the port bitmap")
            ctx = {
                "loop": reply["loop"], "attempted": attempted,
                "client": reply, "m0": m0, "m1": m1, "spans": spans,
                "device": reduced, "setup": setup, "cfg": cfg,
                "traffic": traffic, "seconds": seconds,
                "compiles_in_window": compiles_in_window,
                "memory_peak_bytes": peak,
                "device_kind": devices[0].device_kind,
                # What a launch has to read of the resident matrix: every
                # field but the port bitmap (4 KB a node), of which a launch
                # gathers a few words a node and lane, and only where its
                # traffic asks for ports (roofline_net.py counts those).
                "matrix_bytes": float(resident - bitmap),
            }
            values = {}
            for m in metrics_of(bench, cell, kind, moved):
                values[m["name"]] = importlib.import_module(m["name"]).read(ctx)
        for m in metrics_of(bench, cell, kind, moved):
            v = values.get(m["name"])
            if v is not None and v == v and abs(v) != float("inf"):
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        # Each number compared beside its limit: the line's last key.
        # (A gap of a decision with no recorded score is infinite: a word
        # there, so that the line stays JSON.)
        result["compared"] = {
            k: {"value": numbers[k] if math.isfinite(numbers[k])
                else str(numbers[k]), "limit": limit}
            for k, limit in checker.LIMITS.items() if k in numbers}
        print("detail: " + json.dumps({
            "causes": causes, "numbers": numbers, "setup": setup, "e2e": e2e,
            "compiles_in_window": compiles_in_window,
            # Launches of the placement program in the traced slice, a chip.
            "launches": reduced["launches"] if reduced else None,
            "evals_ended": reply["evals_ended"],
            "evals_failed": reply["evals_failed"],
            "eval_failed_causes": reply["eval_failed_causes"],
            # Operations the client began of those it was dealt: a closed
            # loop that uses up its deck reads its own ceiling.
            "deck_used": [len(reply["records"]), reply["scheduled"]],
            "reregistered_ops": sum(r["registers"] > 1 for r in attempted),
            "n429": sum(r["n429"] for r in attempted),
            "stream_gaps": reply["stream_gaps"],
            "client_stall_ms": reply["stall_ms"],
            "client_stall_at_s": reply["stall_at_s"],
            # What the program was doing when it stalled: its longest spans.
            "long_spans": sorted(
                ([sp["name"], round(sp["dur"], 3), round(sp["ts"] - t0, 2)]
                 for sp in ptrace.dump()
                 if sp["ph"] == "X" and sp["dur"] > 0.5
                 and t0 <= sp["ts"] <= t0 + seconds),
                key=lambda x: -x[1])[:8],
            "gc_pauses_ms": [[g, round(s * 1e3)] for t, g, s in gc_pauses.pauses
                             if t0 <= t <= t0 + seconds],
            "late_p95_ms": measure.percentile(
                [(r["sent"] - r["due"]) * 1e3 for r in attempted if r["sent"]],
                0.95),
        }), flush=True)
        return result
    finally:
        client.close()
        if hb is not None:
            hb.stop.set()
            hb.join(timeout=10)
        if agent is not None:
            agent.shutdown()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny cluster on a CPU, for the benchmark's tests")
    ap.add_argument("--set", action="append", default=[], metavar="KEY=VALUE",
                    help="override a number of the traffic file, for the "
                         "sweep that finds a cell's rate; never in a check")
    ap.add_argument("--check-dump", default=None,
                    help="write the sampled decisions here (for the control)")
    args = ap.parse_args(argv)
    try:
        result = run(args)
    except Fail as e:
        print(f"benchmark: {e}; nothing was measured", file=sys.stderr)
        return 2
    for k, c in result["compared"].items():
        value = c["value"] if isinstance(c["value"], str) else (
            f"{c['value']:.6g}")
        print(f"check: {k} = {value} (limit {c['limit']:g})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
