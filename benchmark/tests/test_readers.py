"""The readers of the dispatcher-state spans, the plan-outcome counters and
the kernel's stage scopes: each on a hand-made ``run``, and ``None`` where
the program under test has no such span, counter or scope (the parent of
the PR that added them, or any program that drops them)."""

import importlib
import json
import os

import pytest

import stage_reduce
from conftest import ROOT

HERE = os.path.dirname(os.path.abspath(__file__))


def read(name, run):
    return importlib.import_module(name).read(run)


def span(name, ts, dur, span_id=0, parent=0):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur, "span": span_id,
            "parent": parent, "args": {}}


def timer(count, mean_ms):
    return {"count": count, "mean_ms": mean_ms}


@pytest.fixture()
def run():
    """A 10 s window from t = 1000."""
    spans = [
        span("coalescer.idle", 990.0, 11.0),      # 1 s of it in the window
        span("coalescer.idle", 1004.0, 2.0),
        span("coalescer.idle", 1009.5, 3.0),      # 0.5 s of it
        span("runtime.gc_pause", 1002.0, 0.25),
        span("runtime.gc_pause", 1020.0, 0.25),   # after the window
        span("coalescer.launch", 1001.0, 0.004, span_id=1),
        span("coalescer.launch", 1002.0, 0.006, span_id=2),
        span("coalescer.launch", 1003.0, 0.040, span_id=3),
        span("coalescer.sync", 1001.0, 0.001, parent=1),
        span("coalescer.sync", 1002.0, 0.003, parent=2),
        span("coalescer.device", 1001.0, 0.020),
        span("coalescer.device", 1002.0, 0.030),
    ]
    phases = {"nomad.phase." + n: timer(1, 1.0) for n in
              ("coalescer.idle", "runtime.gc_pause")}
    return {
        "spans": spans, "seconds": 10.0, "loop": "open",
        "client": {"t0": 1000.0, "t_end": 1010.0},
        "m0": {"nomad.plan.result{outcome=committed}": 10,
               "nomad.phase.coalescer.trace_variant": timer(3, 1500.0),
               **phases},
        "m1": {"nomad.plan.result{outcome=committed}": 70,
               "nomad.plan.result{outcome=rejected}": 30,
               "nomad.plan.result{outcome=partial}": 10,
               **phases},
    }


@pytest.mark.parametrize("name,want", [
    ("coalescer_idle_share", 100.0 * (1.0 + 2.0 + 0.5) / 10.0),
    ("gc_pause_share", 2.5),
    ("launch_host_ms", 6.0),
    ("matrix_sync_ms", 2.0),
    ("launch_to_result_ms", 25.0),
    ("plan_rejected_share", 30.0),   # 60 committed, 30 rejected, 10 partial
    ("plan_partial_share", 10.0),
    ("setup_variant_trace_s", 4.5),
])
def test_reader_on_a_hand_made_run(run, name, want):
    assert read(name, run) == pytest.approx(want)


@pytest.mark.parametrize("name", [
    "coalescer_idle_share", "gc_pause_share", "launch_host_ms",
    "matrix_sync_ms", "launch_to_result_ms", "plan_rejected_share",
    "plan_partial_share", "setup_variant_trace_s", "kernel_scan_share",
    "kernel_verify_share",
])
def test_reader_finds_nothing_in_a_program_without_its_source(name):
    """The parent's run: the spans it does record, none of the new ones,
    no plan-outcome counter, no phase timer of a new span."""
    with open(os.path.join(HERE, "small_trace.json")) as fh:
        spans = [s for s in json.load(fh)["spans"]
                 if s["name"] not in ("coalescer.launch", "coalescer.device")]
    old = {"spans": spans, "seconds": 0.25, "loop": "open", "device": None,
           "client": {"t0": 1002.0, "t_end": 1002.25},
           "cfg": {"placement_programs": ["fused_place_batch"]},
           "m0": {"nomad.plan.applied": 1}, "m1": {"nomad.plan.applied": 5}}
    assert read(name, old) is None


def test_a_window_without_a_pause_reads_zero_not_nothing(run):
    run["spans"] = [s for s in run["spans"] if s["name"] != "runtime.gc_pause"]
    assert read("gc_pause_share", run) == 0.0


def test_an_outcome_no_plan_had_yet_reads_zero(run):
    del run["m1"]["nomad.plan.result{outcome=partial}"]
    assert read("plan_partial_share", run) == 0.0
    assert read("plan_rejected_share", run) == pytest.approx(100 * 30 / 90)


# -- the kernel's stages, from an xplane -------------------------------------------

def varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(no, value):
    """One field of a protobuf message: an int as a varint, bytes or str
    length-delimited (the xplane.proto of XLA's profiler gives the numbers)."""
    if isinstance(value, int):
        return varint(no << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(no << 3 | 2) + varint(len(value)) + value


def entry(key, message):
    return field(1, key) + field(2, message)


def event(meta, offset_ps, duration_ps):
    # metadata_id, offset_ps, duration_ps, and an event's own stat (a double:
    # fixed 64-bit, which the reader has to step over)
    own_stat = field(1, 9) + varint(2 << 3 | 1) + bytes(8)
    return (field(1, meta) + field(2, offset_ps) + field(3, duration_ps)
            + field(4, own_stat))


def line(name, events):
    return field(2, name) + b"".join(field(4, e) for e in events)


def op_meta(mid, name, op_name=None, ref=None):
    stats = b""
    if op_name is not None:
        stats = field(5, field(1, 1) + field(5, op_name))
    if ref is not None:
        stats = field(5, field(1, 1) + field(7, ref))
    return entry(mid, field(1, mid) + field(2, name) + stats)


MS = 10 ** 9  # picoseconds


@pytest.fixture()
def xplane(tmp_path):
    """Two launches of the placement program and one of another, on one
    device plane; a host plane beside it."""
    scan = "jit(_fused_place_batch_impl)/vmap(place_scan)/while/body/closed_call/"
    metas = [
        op_meta(1, "jit__fused_place_batch_impl(77)"),
        op_meta(2, "jit_scatter_rows(9)"),
        op_meta(3, "%while.24 = ...", op_name=scan[:-1]),
        op_meta(4, "%fusion.1 = ...", op_name=scan + "score/feasibility/and"),
        op_meta(5, "%fusion.2 = ...", op_name=scan + "pick/argmax"),
        op_meta(6, "%fusion.3 = ...", ref=3),   # the string kept as a stat name
        op_meta(7, "%copy.1 = ..."),            # no op_name at all
    ]
    stat_names = [
        entry(1, field(1, 1) + field(2, "tf_op")),
        entry(3, field(1, 3) + field(
            2, "jit(_fused_place_batch_impl)/verify_scan/while/body/add")),
    ]
    ops = []
    for base in (0, 20 * MS):   # the two launches: 10 ms each
        ops += [
            event(3, base, 6 * MS),               # the while: not a leaf
            event(4, base, 3 * MS),               # feasibility 3 ms
            event(5, base + 3 * MS, 1 * MS),      # pick 1 ms
            event(6, base + 6 * MS, 2 * MS),      # verify_scan 2 ms
            event(7, base + 8 * MS, 2 * MS),      # no scope 2 ms
        ]
    ops.append(event(4, 50 * MS, 1 * MS))          # inside the other program
    ops.append(event(7, 70 * MS, 1 * MS))          # inside no launch
    modules = [event(1, 0, 10 * MS), event(1, 20 * MS, 10 * MS),
               event(2, 50 * MS, 2 * MS)]
    device = (field(2, "/device:TPU:0")
              + field(3, line("XLA Modules", modules))
              + field(3, line("XLA Ops", ops))
              + field(3, line("Async XLA Ops", [event(7, 0, 99 * MS)]))
              + b"".join(field(4, m) for m in metas)
              + b"".join(field(5, s) for s in stat_names))
    host = field(2, "/host:CPU") + field(3, line("python", [event(7, 0, MS)]))
    d = tmp_path / "plugins" / "profile" / "2026_01_01"
    d.mkdir(parents=True)
    path = d / "vm.xplane.pb"
    path.write_bytes(field(1, host) + field(1, device))
    return str(path)


def test_stage_reduce_groups_leaf_ops_by_scope(xplane):
    scopes = stage_reduce.by_scope(
        stage_reduce.load(xplane, ("fused_place_batch",)))
    assert scopes == pytest.approx({
        "place_scan/score/feasibility": 0.006, "place_scan/pick": 0.002,
        "verify_scan": 0.004, "": 0.004})


@pytest.mark.parametrize("name,want,in_the_other_program", [
    ("kernel_scan_share", 50.0, 100.0), ("kernel_verify_share", 25.0, 0.0)])
def test_kernel_shares(xplane, monkeypatch, name, want, in_the_other_program):
    monkeypatch.setattr(stage_reduce, "TRACE_DIR",
                        os.path.dirname(os.path.dirname(os.path.dirname(
                            os.path.dirname(xplane)))))
    run = {"device": {"busy_s": 1.0},
           "cfg": {"placement_programs": ["fused_place_batch"]}}
    assert read(name, run) == pytest.approx(want)
    run["cfg"] = {"placement_programs": ["scatter_rows"]}
    # Its one op is under place_scan: none under verify_scan is 0 %, not
    # nothing ...
    assert read(name, run) == in_the_other_program
    run["cfg"] = {"placement_programs": ["no_such_program"]}
    assert read(name, run) is None    # ... and no op at all: nothing


def test_scope_of():
    assert stage_reduce.scope_of(
        "jit(f)/vmap(place_scan)/while/body/closed_call/score/binpack/add"
    ) == "place_scan/score/binpack"
    assert stage_reduce.scope_of("jit(f)/jit(main)/mul") == ""
    assert stage_reduce.scope_of("") == ""


# -- the list-bound metrics in the cell PR 43 lists them for - --------------------------

NEW_CELL = "c2m-10k-preempt.tiers-backlog"
TEN = ("launch_host_ms", "matrix_sync_ms", "coalescer_idle_share",
       "gc_pause_share", "plan_rejected_share", "plan_partial_share",
       "kernel_scan_share", "kernel_verify_share", "scan_steps_per_launch",
       "deck_used_share")
# PR 51: the two rules cells enter the same eleven lists.
RULES_CELLS = ("c2m-10k-rules.rules-backlog", "c2m-100k-rules.rules-backlog-x4")
LISTED = [(m, cell) for cell in (NEW_CELL,) + RULES_CELLS
          for m in TEN + ("setup_variant_trace_s",)]


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def recorded(cell, run):
    """The hand-made window above as a traced run of ``cell`` hands it to
    the readers: a closed loop with its deck, the cell's own configuration
    file, the counters and the trace reduction of one chip."""
    bench = _bench()
    config = {w["name"]: w["config"] for w in bench["workloads"]}[cell]
    path = {c["name"]: c["file"] for c in bench["configs"]}[config]
    with open(os.path.join(ROOT, path)) as fh:
        cfg = json.load(fh)
    records = [
        {"i": i, "ok": i != 7,
         "due": 1000.0 + i * 0.01, "placed": 1000.3 + i * 0.012}
        for i in range(40)]
    run = dict(run, loop="closed", cfg=cfg, attempted=records,
               device_kind="TPU v5 lite", matrix_bytes=48.8e6,
               device={"busy_s": 0.3, "launches": 90, "kernel_s": 0.27,
                       "devices": 1})
    run["client"] = dict(run["client"], records=records, scheduled=512)
    for m, grown in (("nomad.kernel.launches{path=fused}", 400),
                     ("nomad.kernel.fused_lanes", 1700),
                     ("nomad.kernel.scan_steps_total", 1500),
                     ("nomad.kernel.overlay_rows_total", 900)):
        run["m0"][m], run["m1"][m] = 10, 10 + grown
    return run


def test_benchmark_json_lists_the_cell_where_pr_43_says():
    per_layer = {m["name"]: m for m in _bench()["per_layer"]}
    for name, cell in LISTED:
        assert cell in per_layer[name]["workloads"], (name, cell)
    # PR 51: the four-chip rules cell also where its control and the
    # one-chip rules cell stand; a list only ever grows at its end, and
    # ``overlay_rows_per_launch`` keeps the four cells tier-1 pins.
    for name in ("collective_share", "cross_shard_share",
                 "kernel_feasibility_share", "sched_feasibility_ms",
                 "host_walk_nodes_per_eval"):
        assert per_layer[name]["workloads"][-1] == RULES_CELLS[1], name
    cells = [w["name"] for w in _bench()["workloads"]]
    assert per_layer["overlay_rows_per_launch"]["workloads"] == cells[:4]
    for m in per_layer.values():
        assert set(m.get("workloads", [])) <= set(cells), m["name"]
    # ... and took no cell away from any list.
    for name in TEN[:-1]:
        assert set(per_layer[name]["workloads"]) >= {
            "c2m-10k.steady", "c2m-10k.backlog", "c2m-100k.backlog-x4"}


# The recorded trace below holds the one-chip program (``fused_place_batch``);
# a mesh cell's stage shares look for ``jit_entry`` (test_sharded_readers.py).
@pytest.mark.parametrize("name,cell", [
    (m, c) for m, c in LISTED
    if not (c == RULES_CELLS[1] and m.startswith("kernel_"))])
def test_list_bound_reader_reads_a_number_in_a_cell_it_now_lists(
        run, xplane, monkeypatch, name, cell):
    monkeypatch.setattr(stage_reduce, "TRACE_DIR",
                        os.path.dirname(os.path.dirname(os.path.dirname(
                            os.path.dirname(xplane)))))
    value = read(name, recorded(cell, run))
    assert isinstance(value, float) and value == value and value >= 0.0
    if name == "deck_used_share":
        assert value == pytest.approx(100.0 * 40 / 512)
