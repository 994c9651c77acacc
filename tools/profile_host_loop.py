"""Profile the e2e server loop's HOST side.

Runs bench.py's e2e phase shape — N nodes, a burst of jobs through
broker → worker → stack → coalescer → applier — under a SAMPLING
profiler that captures every thread's stack (the py-spy approach;
cProfile only sees the calling thread, and the server's work happens in
worker/applier/coalescer threads).  On the CPU backend: the question is
where HOST time goes, not device time.

Usage: JAX_PLATFORMS=cpu python tools/profile_host_loop.py [jobs] [nodes]
           [--latency-ms MS] [--out PATH]
Writes tools/host_loop_profile.txt (override with --out).

``--latency-ms`` turns on the fake-device backend with a synthetic
device→host fetch latency (NOMAD_TPU_FAKE_DEVICE_LATENCY_MS) — the knob
that makes the coalescer's dispatch/resolve overlap visible on a CPU-only
box: with the latency charged at resolve time, a profile shows exactly
which thread waits out the fetch.
"""

from __future__ import annotations

import argparse
import collections
import os
import sys
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

_ap = argparse.ArgumentParser(description="host-loop sampling profiler")
_ap.add_argument("jobs", nargs="?", type=int, default=256)
_ap.add_argument("nodes", nargs="?", type=int, default=2000)
_ap.add_argument(
    "--latency-ms", type=float, default=None,
    help="fake-device synthetic fetch latency; implies NOMAD_TPU_FAKE_DEVICE=1",
)
_ap.add_argument(
    "--out", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "host_loop_profile.txt"
    ),
    help="report path (default tools/host_loop_profile.txt)",
)
_ARGS = _ap.parse_args()

N_JOBS = _ARGS.jobs
N_NODES = _ARGS.nodes
if _ARGS.latency_ms is not None:
    os.environ["NOMAD_TPU_FAKE_DEVICE"] = "1"
    os.environ["NOMAD_TPU_FAKE_DEVICE_LATENCY_MS"] = str(_ARGS.latency_ms)
WORKERS = int(os.environ.get("PROFILE_WORKERS", "8"))
# Modest rate + raw-frame walking: traceback.extract_stack at high Hz
# reads source through linecache and hogs the GIL hard enough to starve
# the system under test to ~zero throughput (observed; self-poisoning).
SAMPLE_HZ = 25.0

_IDLE_LEAVES = ("wait", "_wait_for_tstate_lock", "select", "poll",
                "accept", "read", "recv_into")


class Sampler(threading.Thread):
    """Stack sampler over every live thread (sys._current_frames)."""

    def __init__(self):
        super().__init__(name="stack-sampler", daemon=True)
        self._halt = threading.Event()
        # (thread_name_prefix, leaf frame) -> samples
        self.leaf: collections.Counter = collections.Counter()
        # full-stack flame lines -> samples (for the report tail)
        self.stacks: collections.Counter = collections.Counter()
        self.samples = 0

    def run(self) -> None:
        me = threading.get_ident()
        interval = 1.0 / SAMPLE_HZ
        while not self._halt.wait(interval):
            frames = sys._current_frames()
            names = {
                t.ident: t.name for t in threading.enumerate()
            }
            self.samples += 1
            for tid, frame in frames.items():
                if tid == me:
                    continue
                name = names.get(tid, "?").split("-")[0]
                # Raw frame walk — no FrameSummary, no linecache.
                code = frame.f_code
                if code.co_name in _IDLE_LEAVES:
                    continue
                self.leaf[
                    f"{name}: {os.path.basename(code.co_filename)}:"
                    f"{frame.f_lineno} {code.co_name}"
                ] += 1
                sig = []
                f = frame
                depth = 0
                while f is not None and depth < 10:
                    sig.append(
                        f"{os.path.basename(f.f_code.co_filename)}:"
                        f"{f.f_code.co_name}"
                    )
                    f = f.f_back
                    depth += 1
                self.stacks[f"{name}: " + ";".join(reversed(sig))] += 1

    def stop(self) -> None:
        self._halt.set()
        self.join(timeout=5)


def main() -> None:
    from nomad_tpu import mock
    from nomad_tpu.server.server import Server, ServerConfig

    srv = Server(ServerConfig(
        num_workers=WORKERS,
        node_capacity=max(256, 1 << (N_NODES - 1).bit_length()),
        heartbeat_min_ttl=3600.0,
        heartbeat_max_ttl=7200.0,
    ))
    srv.start()
    rng = np.random.default_rng(7)
    for i in range(N_NODES):
        node = mock.node()
        node.node_class = f"class-{i % 6}"
        srv.register_node(node)
    with srv.matrix._host_lock:
        host = srv.matrix.snapshot_host()
        host["used"][:N_NODES] = (
            rng.uniform(0.1, 0.6, (N_NODES, 3)) * host["totals"][:N_NODES]
        )
        srv.matrix._dirty.update(range(N_NODES))

    def make_job(i: int):
        job = mock.job()
        tg = job.task_groups[0]
        tg.count = 2
        tg.tasks[0].resources.cpu = 50 + 25 * (i % 4)
        tg.tasks[0].resources.memory_mb = 64 + 32 * (i % 3)
        return job

    # Warm compiles outside the profile.
    ev = srv.submit_job(make_job(0))
    srv.wait_for_eval(ev.id, timeout=600.0)

    sampler = Sampler()
    sampler.start()
    t0 = time.time()
    evals = [srv.submit_job(make_job(i)) for i in range(N_JOBS)]
    pending = {e.id for e in evals}
    deadline = time.time() + 300.0
    last_index = 0
    while pending and time.time() < deadline:
        done = {
            eid for eid in pending
            if (e := srv.store.eval_by_id(eid)) is not None
            and e.terminal_status()
        }
        pending -= done
        if not pending:
            break
        # Condvar wait on the evals table instead of a 10ms sleep-poll:
        # wakes on the next eval write, so completion latency isn't
        # quantized to the poll period.
        last_index = srv.store.wait_for_table(
            "evals", last_index, timeout=0.25
        )
    wall = time.time() - t0
    sampler.stop()
    rate = (N_JOBS - len(pending)) / wall

    lat = os.environ.get("NOMAD_TPU_FAKE_DEVICE_LATENCY_MS", "0")
    lines = [
        f"e2e host profile: {N_JOBS} jobs, {N_NODES} nodes, "
        f"{WORKERS} workers, latency={lat}ms -> {rate:.1f} evals/s "
        f"wall={wall:.1f}s (pending={len(pending)})",
        f"coalescer: dispatches={srv.coalescer.dispatches} "
        f"coalesced={srv.coalescer.coalesced_requests}",
        f"samples: {sampler.samples} @ {SAMPLE_HZ:.0f}Hz "
        f"(busy-leaf samples below; idle waits dropped)",
        "",
        "==== top 40 busy leaf frames (thread: file:line fn  samples) ====",
    ]
    for key, n in sampler.leaf.most_common(40):
        lines.append(f"{n:6d}  {key}")
    lines.append("")
    lines.append("==== top 25 stacks ====")
    for key, n in sampler.stacks.most_common(25):
        lines.append(f"{n:6d}  {key}")
    srv.shutdown()

    report = "\n".join(lines) + "\n"
    path = _ARGS.out
    with open(path, "w") as fh:
        fh.write(report)
    print(report[:3000])
    print(f"... full profile -> {path}")


if __name__ == "__main__":
    main()
