"""The readers of the program's CPU clocks, lock waits, probe and claims
counters (PR 39): each on a hand-made ``run``, with spans (a traced run)
and without (the counters and gauges read in any run), and ``None`` from
the parent's run, which has none of their sources; and the overlap of the
device's idle time with the dispatch thread's launches, on
``small_trace_launches.json``: ``small_trace.json`` with the marker where
the harness writes it (just before the slice) and two ``coalescer.launch``
events in the host plane."""

import importlib
import json
import os

import pytest

import idle_overlap
from test_readers import MS, event, field, line, op_meta

HERE = os.path.dirname(os.path.abspath(__file__))
CPU = "nomad.runtime.cpu_seconds{group=%s}"


def read(name, run):
    return importlib.import_module(name).read(run)


def span(name, ts, dur, cpu=None, **args):
    s = {"name": name, "ph": "X", "ts": ts, "dur": dur, "span": 0,
         "parent": 0, "args": args}
    if cpu is not None:
        s["cpu"] = cpu
    return s


def groups(worker, applier, dispatch, api, other, native):
    g = {"worker": worker, "plan-applier": applier,
         "device-coalescer": dispatch, "http-api": api, "other": other,
         "worker-renew": 0.0}
    out = {CPU % k: v for k, v in g.items()}
    out[CPU % "native"] = native
    out[CPU % "process"] = sum(g.values()) + native
    return out


@pytest.fixture()
def run():
    """A 10 s window from t = 1000 in which 40 operations were placed (one
    more after its end, one failed) and 50 evals processed."""
    placed = [{"ok": True, "placed": 1000.0 + k / 4.0} for k in range(40)]
    return {
        "seconds": 10.0, "loop": "closed",
        "client": {"t0": 1000.0, "t_end": 1010.0},
        "attempted": placed + [{"ok": True, "placed": 1010.5},
                               {"ok": False, "placed": None}],
        "spans": [
            # (a CPU clock that ticks every 10 ms reads a launch so)
            span("coalescer.launch", 1001.0, 0.020, cpu=0.0, lanes=8),
            span("coalescer.launch", 1002.0, 0.024, cpu=0.010, lanes=8),
            span("coalescer.launch", 1003.0, 0.030, cpu=0.0, lanes=8),
            span("coalescer.launch", 1004.0, 0.030, cpu=0.010, lanes=8),
            span("coalescer.sync", 1001.0, 0.010, cpu=0.001, rows=3,
                 lock_wait=0.002),
            span("coalescer.sync", 1002.0, 0.014, cpu=0.001, rows=3,
                 lock_wait=0.003),
            span("coalescer.sync", 1003.0, 0.012, cpu=0.001, rows=3,
                 lock_wait=0.010),
        ],
        "m0": {**groups(10.0, 2.0, 3.0, 1.0, 4.0, 5.0),
               "nomad.worker.evals_processed": 100,
               "nomad.runtime.wakes_total": 1000,
               "nomad.runtime.wake_late_seconds_total": 1.0,
               "nomad.runtime.stall_seconds_total": 0.0,
               "nomad.coalescer.launches_unresolved_predecessor": 10,
               "nomad.kernel.launches{path=fused}": 100},
        "m1": {**groups(14.0, 3.5, 5.0, 2.2, 4.3, 7.0),
               "nomad.worker.evals_processed": 150,
               "nomad.runtime.wakes_total": 1800,
               "nomad.runtime.wake_late_seconds_total": 5.0,
               "nomad.runtime.stall_seconds_total": 0.5,
               "nomad.coalescer.launches_unresolved_predecessor": 40,
               "nomad.kernel.launches{path=fused}": 300},
    }


READERS = [
    # the process: 4 + 1.5 + 2 + 1.2 + 0.3 Python, 2 native = 11 s
    ("host_cpu_ms_per_op", 1e3 * 11.0 / 40, True),
    ("interpreter_busy_share", 90.0, True),      # 9 s of Python in 10 s
    ("worker_cpu_ms_per_eval", 1e3 * 4.0 / 50, True),
    ("dispatch_cpu_share", 20.0, True),
    ("applier_cpu_share", 15.0, True),
    ("api_cpu_ms_per_op", 1e3 * 1.2 / 40, True),
    ("gil_wait_ms", 5.0, True),                  # 4 s late over 800 wakes
    ("stall_share", 5.0, True),
    ("unresolved_predecessor_share", 15.0, True),  # 30 of 200 launches
    ("launch_cpu_ms", 5.0, False),               # the mean: 20 ms of 4
    ("sync_lock_wait_ms", 3.0, False),
]


@pytest.mark.parametrize("name,want,untraced", READERS)
def test_reader_on_a_hand_made_run(run, name, want, untraced):
    assert read(name, run) == pytest.approx(want)
    # What reads counters and gauges reads them in an untraced run too;
    # what reads spans has nothing to read there.
    run["spans"] = None
    got = read(name, run)
    assert got == (pytest.approx(want) if untraced else None)


@pytest.mark.parametrize(
    "name", [r[0] for r in READERS] + ["idle_while_launching_share"])
def test_reader_finds_nothing_in_the_parents_run(run, name, monkeypatch,
                                                 tmp_path):
    """The parent: no CPU gauge, no probe, no ``cpu`` on a span, no
    ``lock_wait`` arg; its claims counter (PR 38) is there."""
    monkeypatch.setattr(idle_overlap, "TRACE_DIR", str(tmp_path))
    for m in (run["m0"], run["m1"]):
        for k in [k for k in m if k.startswith("nomad.runtime.")]:
            del m[k]
    for s in run["spans"]:
        s.pop("cpu", None)
        s["args"].pop("lock_wait", None)
    run["device"] = None
    got = read(name, run)
    assert got == (pytest.approx(15.0)
                   if name == "unresolved_predecessor_share" else None)


def test_a_quiet_window_reads_zero_not_nothing(run):
    run["m1"]["nomad.runtime.stall_seconds_total"] = 0.0
    assert read("stall_share", run) == 0.0
    run["m1"]["nomad.coalescer.launches_unresolved_predecessor"] = 10
    assert read("unresolved_predecessor_share", run) == 0.0


def test_python_groups_are_whatever_the_program_names(run):
    """A group the program adds later is in the interpreter's share; the
    two that are not Python's never are."""
    run["m0"][CPU % "raft"] = 0.0
    run["m1"][CPU % "raft"] = 0.5
    assert read("interpreter_busy_share", run) == pytest.approx(95.0)


# -- idle while launching -----------------------------------------------------------

@pytest.fixture()
def launches():
    with open(os.path.join(HERE, "small_trace_launches.json")) as fh:
        d = json.load(fh)
    return [tuple(e) for e in d["events"]], d["seconds"]


def test_idle_overlap_on_the_small_trace(launches):
    events, seconds = launches
    o = idle_overlap.overlap(events, seconds)
    # The first device: busy 4 x 10 ms + 1 ms of 250 ms.
    assert o["idle_s"] == pytest.approx(0.209)
    # Launch 2.012-2.032 meets the gap 2.010-2.025 for 13 ms (the rest of
    # it runs beside the second program); launch 2.100-2.150 lies in the
    # long gap.
    assert o["launching_s"] == pytest.approx(0.013 + 0.050)


def test_idle_overlap_without_its_sources(launches):
    events, seconds = launches
    no_launch = [e for e in events if e[2] != idle_overlap.LAUNCH]
    assert idle_overlap.overlap(no_launch, seconds) is None
    no_marker = [e for e in events if e[2] != "bench.marker"]
    assert idle_overlap.overlap(no_marker, seconds) is None
    host_only = [e for e in events if e[0].startswith("/host")]
    assert idle_overlap.overlap(host_only, seconds) is None
    # A launch span on a device plane's line is no host span.
    moved = [("/device:TPU:0",) + e[1:] if e[2] == idle_overlap.LAUNCH else e
             for e in events]
    assert idle_overlap.overlap(moved, seconds) is None


def test_idle_while_launching_share_from_an_xplane(tmp_path, monkeypatch):
    """Through ``load``: a device plane with two 10 ms programs, a host
    plane with the marker and two launch annotations, one with its
    arguments riding its name."""
    metas = [op_meta(1, "jit__fused_place_batch_impl(77)"),
             op_meta(2, "bench.marker"), op_meta(3, "coalescer.launch"),
             op_meta(4, "coalescer.launch#lanes=8#"),
             op_meta(5, "coalescer.idle")]
    device = (field(2, "/device:TPU:0")
              + field(3, line("XLA Modules", [event(1, 10 * MS, 10 * MS),
                                              event(1, 40 * MS, 10 * MS)]))
              + field(4, metas[0]))
    other = (field(2, "/device:TPU:1")
             + field(3, line("XLA Modules", [event(1, 0, 100 * MS)]))
             + field(4, metas[0]))
    # (a v5e's trace has such a plane, sorted before the chips', empty)
    custom = field(2, "/device:CUSTOM:Megascale Trace")
    host = (field(2, "/host:CPU")
            + field(3, line("python", [
                event(2, 9 * MS, 1 * MS),      # the slice: 10 ms .. 60 ms
                event(3, 15 * MS, 10 * MS),    # 5 ms busy, 5 ms idle
                event(5, 25 * MS, 5 * MS),     # another state: not counted
                event(4, 30 * MS, 10 * MS),    # 10 ms idle
            ]))
            + b"".join(field(4, m) for m in metas[1:]))
    d = tmp_path / "plugins" / "profile" / "2026_01_01"
    d.mkdir(parents=True)
    (d / "vm.xplane.pb").write_bytes(
        field(1, custom) + field(1, host) + field(1, device) + field(1, other))
    monkeypatch.setattr(idle_overlap, "TRACE_DIR", str(tmp_path))
    run = {"device": {"busy_s": 0.02, "window_s": 0.05}}
    # Idle 20-40 and 50-60 ms = 30 ms; launching 20-25 and 30-40 = 15 ms.
    assert read("idle_while_launching_share", run) == pytest.approx(50.0)
    assert read("idle_while_launching_share", {"device": None}) is None
