"""The ``c2m-100k-rules`` deployment and its cell (PR 46) on a CPU at a tiny
size: the cell from files alone through ``run.py --rehearse``; the traffic
file against ``rules-backlog.json``; the configuration's class count from its
periods; ``roofline_sharded_rules`` against a count by hand; the three new
readers on a recorded run, and nothing where their sources are absent (the
parent's metrics)."""

import importlib
import json
import math
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT

sys.path.insert(0, os.path.join(BENCH, "deployments"))

import roofline  # noqa: E402
import roofline_rules  # noqa: E402
import roofline_sharded  # noqa: E402
import roofline_sharded_rules  # noqa: E402
import rules_check  # noqa: E402
import rules_reference as rules  # noqa: E402
import stage_reduce  # noqa: E402
import traffic  # noqa: E402
from test_readers import MS, entry, event, field, line, op_meta  # noqa: E402
from test_traffic import _digest  # noqa: E402

CELL = "c2m-100k-rules.rules-backlog-x4"
ENV = dict(os.environ, JAX_PLATFORMS="cpu",
           JAX_COMPILATION_CACHE_DIR=os.path.join(ROOT, ".jax_cache"))
with open(os.path.join(BENCH, "configs", "c2m-100k-rules.json")) as _fh:
    CFG = json.load(_fh)
with open(os.path.join(ROOT, "BENCHMARK.json")) as _fh:
    BENCHMARK = json.load(_fh)
TRAFFIC = traffic.load("rules-backlog-x4")
NEW = ("sharded_rules_place_batch_roofline", "rules_exchange_share",
       "class_walk_per_eval")


# -- end to end ------------------------------------------------------------------

def test_the_cell_from_files_alone_reads_correct(tmp_path):
    dump = tmp_path / "dump.json"
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark/run.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 46), "--seconds", "5",
         "--trace", "1", "--rehearse", "--check-dump", str(dump)],
        capture_output=True, text=True, env=ENV, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    out = p.stdout.strip().splitlines()
    result = json.loads(out[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 100
    assert list(result["compared"]) == list(rules_check.LIMITS)
    for k in ("distinct_hosts_violations", "distinct_property_violations",
              "constraint_violations"):
        assert result["compared"][k]["value"] == 0
    compared = [l for l in out if l.startswith("check: compared")][0]
    assert int(compared.split("; ")[1].split(" jobs wider")[0]) > 5
    detail = json.loads(
        [l for l in out if l.startswith("detail: ")][-1][len("detail: "):])
    assert detail["compiles_in_window"] == 0
    # the counter is there and reads 0; the two trace metrics have no
    # device trace to read on a CPU and are left out, not raised
    assert result["metrics"]["class_walk_per_eval"]["value"] == 0.0
    assert "rules_exchange_share" not in result["metrics"]
    assert "sharded_rules_place_batch_roofline" not in result["metrics"]
    # the control: the reference in bfloat16 is not correct on this dump
    c = subprocess.run(
        [sys.executable, os.path.join(BENCH, "control.py"), str(dump)],
        capture_output=True, text=True, env=ENV, timeout=300)
    assert c.returncode == 0, c.stdout + c.stderr


# -- the entries and the files ------------------------------------------------------

def test_benchmark_json_has_the_cell_as_the_issue_names_it():
    cell = {w["name"]: w for w in BENCHMARK["workloads"]}[CELL]
    assert cell == dict(cell, config="c2m-100k-rules",
                        traffic="rules-backlog-x4", chips=4)
    entry_ = {c["name"]: c for c in BENCHMARK["configs"]}["c2m-100k-rules"]
    assert entry_["file"] == "benchmark/configs/c2m-100k-rules.json"
    assert entry_["source"] == CFG["source"] and len(entry_["source"]) <= 200
    assert entry_["reduced"] == ["sim_allocs", "clients", "servers", "wal"]
    assert sorted(entry_["reduced"]) == sorted(CFG["reduced"])
    for name in NEW:
        m = {m["name"]: m for m in BENCHMARK["per_layer"]}[name]
        assert m["workloads"] == [CELL] and m["moves"] == "evals_per_s"
    four = [w["name"] for w in BENCHMARK["workloads"] if w["chips"] == 4]
    assert four == ["c2m-100k.backlog-x4", CELL]
    assert len(four) <= len(BENCHMARK["workloads"]) // 2


def test_the_traffic_is_rules_backlogs_under_backlog_x4s_loop():
    base, x4 = traffic.load("rules-backlog"), traffic.load("backlog-x4")
    differ = {k for k in set(base) | set(TRAFFIC)
              if base.get(k) != TRAFFIC.get(k)}
    assert differ == {"outstanding", "max_rate_per_s", "why", "name"}
    for k in ("loop", "outstanding", "max_rate_per_s", "limit_s"):
        assert TRAFFIC[k] == x4[k]
    assert (TRAFFIC["loop"], TRAFFIC["outstanding"],
            TRAFFIC["max_rate_per_s"], TRAFFIC["limit_s"]) == (
                "closed", 64, 400, 30)
    # a deck of 41 blocks: 20,992 operations a 50 s window cannot exhaust
    assert len(traffic.schedule(TRAFFIC, 7, 50)) == 41 * traffic.BLOCK


@pytest.mark.parametrize("name,want", [
    ("steady", ["dbd4e0215117fca1", "9c5b4a374cff0773"]),
    ("backlog", ["4b2a222a08b12aeb", "0e35a2a754e91e60"]),
    ("backlog-x4", ["4b2a222a08b12aeb", "0e35a2a754e91e60"]),
    ("tiers-backlog", ["0f01b3959f660c85", "38f9281dd1cd4ab9"]),
])
def test_the_older_files_are_unchanged(name, want):
    assert [_digest(name, s) for s in (7, 2 ** 31 + 4300)] == want


def test_the_configuration_is_c2m_100ks_with_c2m_10k_rules_attributes():
    with open(os.path.join(BENCH, "configs", "c2m-100k.json")) as fh:
        big = json.load(fh)
    with open(os.path.join(BENCH, "configs", "c2m-10k-rules.json")) as fh:
        small = json.load(fh)
    for k in ("chips", "nodes", "node_capacity", "sim_allocs", "workers",
              "servers", "wal", "clients", "precision", "reduced",
              "heartbeat_min_ttl", "heartbeat_max_ttl", "placement_programs"):
        assert CFG[k] == big[k], k
    for k in ("guarantees", "setup", "check"):
        assert CFG[k] == small[k], k
    for k in ("datacenters", "node_classes", "racks", "accelerator",
              "node_resources", "node_reserved"):
        assert CFG["cluster"][k] == big["cluster"][k], k
    ours, theirs = (c["cluster"]["rule_attributes"] for c in (CFG, small))
    assert ours[0] == dict(theirs[0], period=2560) and ours[1:] == theirs[1:]
    assert CFG["nodes"] // ours[0]["period"] == 39  # 39-40 nodes a rack


def test_the_class_count_follows_from_the_periods():
    cluster = CFG["cluster"]
    t = rules.attr_tables(CFG["nodes"], cluster)
    keys = ["${node.datacenter}", "${node.class}", "${attr.rack}",
            "${attr.platform.tpu.type}", "${meta.rack}",
            "${attr.kernel.version}", "${meta.cached_binaries}"]
    # one string a node, then the distinct ones (a set of 100,000 tuples)
    joined = t[keys[0]].astype(object)
    for k in keys[1:]:
        joined = joined + "|" + t[k].astype(object)
    assert len(set(joined.tolist())) == cluster["computed_classes"] == 7680
    assert math.lcm(4, 6, 32, 3, 5, 4, 2560) == 7680
    assert len(set(t["${meta.rack}"].tolist())) == 2560
    assert len(set(t["${attr.rack}"].tolist())) == 32


# -- the work function, by hand -----------------------------------------------------

MATRIX_BYTES, ROWS = 488.0e6, 102_400


def test_the_work_function_by_hand():
    w = roofline_rules.widths(TRAFFIC)
    assert w == {"c": 8, "a": 2, "s": 2, "dp": 1}
    # (2, 2): 8 live lanes and 4 steps a launch: the chip scores 4 lanes
    # over 51,200 rows
    got = roofline_sharded_rules.launch_work(
        MATRIX_BYTES, ROWS, 8.0, 4.0, 2, 2, w, 8192)
    plain = roofline_sharded.launch_work(MATRIX_BYTES, ROWS, 8.0, 4.0, 2, 2)
    cells = 4 * 51_200
    assert got["bytes"] == pytest.approx(
        plain["bytes"]
        + cells * ((8 + 2) * 8 + 4)          # slots and the class id, once
        + cells * 4.0 * (2 * 4 + 1 * 12)     # spreads and the limit, a step
        + 4 * 8192)                          # the class table, once a lane
    assert got["flop"] == pytest.approx(
        plain["flop"] + cells * ((8 + 2) * 4 + 4.0 * (2 * 16 + 1 * 4)))
    # at no rule width it is the plain sharded launch and the class table
    none = roofline_sharded_rules.launch_work(
        MATRIX_BYTES, ROWS, 8.0, 4.0, 2, 2, dict.fromkeys(w, 0), 0)
    assert none["bytes"] == pytest.approx(plain["bytes"] + cells * 4)


@pytest.mark.parametrize("batch,node", [(2, 2), (1, 4), (4, 1)])
def test_least_time_is_far_under_any_launch(batch, node):
    """Whatever a launch carries (1-64 lanes, 1-16 steps) its least time on
    a v5e stays under 2 ms (64 lanes x 16 steps; 0.7 ms for 8 lanes), and
    the chip has run no sharded launch of this matrix under 4 ms, none of
    64 x 16 under 22 (PERF.md, the layout table, plain shapes): the share
    cannot pass 100."""
    flops, bw = roofline.PEAKS["TPU v5 lite"]
    def least(lanes, steps):
        w = roofline_sharded_rules.launch_work(
            MATRIX_BYTES, ROWS, lanes, steps, node, batch,
            roofline_rules.widths(TRAFFIC), 8192)
        return max(w["bytes"] / bw, w["flop"] / flops)

    assert max(least(n, s) for n in (1, 8) for s in (1, 16)) < 0.7e-3
    assert least(64, 16) < 2.0e-3


# -- the new readers, on a recorded run ---------------------------------------------------

def _span(name, ts, dur, **args):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur, "span": 0,
            "parent": 0, "args": args}


@pytest.fixture()
def run():
    """100 launches in the window, 8 live lanes and 4 steps a launch, 20 ms
    of kernel a launch on each chip of a (2, 2) mesh."""
    return {
        "seconds": 10.0, "loop": "closed", "traffic": TRAFFIC,
        "client": {"t0": 1000.0, "t_end": 1010.0},
        "device": {"launches": 50.0, "kernel_s": 1.0, "devices": 4},
        "device_kind": "TPU v5 lite", "matrix_bytes": MATRIX_BYTES,
        "cfg": {"node_capacity": ROWS, "placement_programs": ["jit_entry"]},
        "spans": [
            _span("sched.feasibility", 1001.0, 0.0004, classes=7776,
                  class_pad=8192, values=0),
            _span("sched.feasibility", 1002.0, 0.0002, classes=7776,
                  class_pad=8192, values=0),
            _span("sched.dispatch", 1001.0, 0.020)],
        "m0": {"nomad.sched.class_walk_total": 0,
               "nomad.kernel.distinct_property_lanes_total": 5,
               "nomad.worker.evals_processed": 40,
               "nomad.kernel.launches{path=fused}": 10,
               "nomad.kernel.fused_lanes": 40,
               "nomad.kernel.scan_steps_total": 20},
        "m1": {"nomad.sched.class_walk_total": 0,
               "nomad.kernel.distinct_property_lanes_total": 105,
               "nomad.worker.evals_processed": 840,
               "nomad.kernel.launches{path=fused}": 110,
               "nomad.kernel.fused_lanes": 840,
               "nomad.kernel.scan_steps_total": 420,
               "nomad.mesh.devices": 4, "nomad.mesh.node_shards": 2,
               "nomad.mesh.batch_shards": 2},
    }


def read(name, run):
    return importlib.import_module(name).read(run)


def test_class_walk_per_eval(run):
    assert read("class_walk_per_eval", run) == 0.0
    # the fallback over 7,776 representatives in one eval in ten
    run["m1"]["nomad.sched.class_walk_total"] = 80 * 7776
    assert read("class_walk_per_eval", run) == pytest.approx(777.6)


def test_sharded_rules_place_batch_roofline(run):
    work = roofline_sharded_rules.launch_work(
        MATRIX_BYTES, ROWS, 8.0, 4.0, 2, 2, roofline_rules.widths(TRAFFIC),
        8192)
    got = read("sharded_rules_place_batch_roofline", run)
    assert got == pytest.approx(100.0 * (work["bytes"] / 819e9) / 0.020)
    assert 0 < got < 100
    # above the plain sharded launch's share of the same kernel time
    plain = roofline_sharded.launch_work(MATRIX_BYTES, ROWS, 8.0, 4.0, 2, 2)
    assert got > 100.0 * (plain["bytes"] / 819e9) / 0.020
    # a window in which no lane carried a distinct_property: nothing to read
    run["m1"]["nomad.kernel.distinct_property_lanes_total"] = 5
    assert read("sharded_rules_place_batch_roofline", run) is None


@pytest.fixture()
def xplane(tmp_path, monkeypatch):
    """One sharded launch: 10 ms of leaf ops, 0.5 of them under
    ``rules_exchange`` (the values it adds to the broadcast, its pmax)."""
    scan = "jit(entry)/shard_map/place_scan/while/body/"
    metas = [
        op_meta(1, "jit_entry(77)"),
        op_meta(2, "%fusion.1 = ...", op_name=scan + "score/feasibility/and"),
        op_meta(3, "%gather.2 = ...",
                op_name=scan + "update/rules_exchange/gather"),
        op_meta(4, "%all-reduce.3 = ...",
                op_name=scan + "update/broadcast/rules_exchange/pmax"),
        op_meta(5, "%all-reduce.4 = ...",
                op_name=scan + "update/broadcast/psum"),
    ]
    stat_names = [entry(1, field(1, 1) + field(2, "tf_op"))]
    ops = [event(2, 0, 17 * MS // 2), event(3, 17 * MS // 2, MS // 4),
           event(4, 35 * MS // 4, MS // 4), event(5, 9 * MS, MS)]
    device = (field(2, "/device:TPU:0")
              + field(3, line("XLA Modules", [event(1, 0, 10 * MS)]))
              + field(3, line("XLA Ops", ops))
              + b"".join(field(4, m) for m in metas)
              + b"".join(field(5, s) for s in stat_names))
    d = tmp_path / "plugins" / "profile" / "2026_01_01"
    d.mkdir(parents=True)
    (d / "vm.xplane.pb").write_bytes(field(1, device))
    monkeypatch.setattr(stage_reduce, "TRACE_DIR", str(tmp_path))


def test_rules_exchange_share(run, xplane):
    assert read("rules_exchange_share", run) == pytest.approx(5.0)
    # the scopes those ops were under still read them
    assert read("cross_shard_share", run) == pytest.approx(12.5)
    run["cfg"] = {"placement_programs": ["no_such_program"]}
    assert read("rules_exchange_share", run) is None


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_on_a_program_without_the_sources(
        run, xplane, name, tmp_path):
    """The parent has neither the counter, nor the span's ``class_pad``, nor
    the scope; an untraced run no spans and no device block."""
    del run["m0"]["nomad.sched.class_walk_total"]
    del run["m1"]["nomad.sched.class_walk_total"]
    for s in run["spans"]:
        s["args"].pop("class_pad", None)
    # the parent's trace: the same ops under the scopes they had
    path = next((tmp_path / "plugins" / "profile" / "2026_01_01").iterdir())
    path.write_bytes(path.read_bytes().replace(
        b"update/rules_exchange/gather", b"update/gather" + b" " * 15).replace(
        b"broadcast/rules_exchange/pmax", b"broadcast/pmax" + b" " * 15))
    assert read(name, run) is None
    run["spans"] = None
    run["device"] = None
    assert read(name, run) is None
