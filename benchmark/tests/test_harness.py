"""The harness end to end, on a CPU at a tiny size (``--rehearse``).

* a dummy cell, configuration, traffic mix and per-layer metric are picked
  up as files alone: nothing that is there is edited;
* the last printed line has exactly the contract's keys;
* with the timed path broken underneath (the rows the placement dispatch
  returns are altered where they are produced), ``correct`` comes out
  false;
* the lower-precision control fails the limit on the run's own decisions.
"""

import json
import os
import subprocess
import sys

import pytest

import check
from conftest import BENCH, ROOT, checkout

KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}
# What a run compares whose traffic registers no job again (PR 43): the
# numbers it compared before there was such a key.
COMPARED = [k for k in check.LIMITS if k not in check.RESUBMIT]


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """A copy of the benchmark with a dummy cell ADDED to it."""
    root = tmp_path_factory.mktemp("checkout")
    bench = checkout(root)
    cfg = json.load(open(root / "benchmark/configs/c2m-10k.json"))
    cfg["name"] = "dummy-cluster"
    (root / "benchmark/configs/dummy-cluster.json").write_text(json.dumps(cfg))
    mix = json.load(open(root / "benchmark/traffic/steady.json"))
    mix.update(rate_per_s=40, why="dummy")
    (root / "benchmark/traffic/dummy-mix.json").write_text(json.dumps(mix))
    (root / "benchmark/readers/dummy_metric.py").write_text(
        "def read(run):\n    return float(len(run['attempted']))\n")
    bench["configs"].append({
        "name": "dummy-cluster", "source": "none",
        "file": "benchmark/configs/dummy-cluster.json", "reduced": [],
        "why": "dummy"})
    bench["workloads"].append({
        "name": "dummy.cell", "config": "dummy-cluster",
        "traffic": "dummy-mix", "chips": 1, "why": "dummy"})
    bench["per_layer"].append({
        "name": "dummy_metric", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "load generator",
        "moves": "evals_per_s", "workloads": ["dummy.cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def _run(tree, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=os.path.join(ROOT, ".jax_cache"))
    return subprocess.run(
        [sys.executable, str(tree / "benchmark/run.py"), "--workload",
         "dummy.cell", "--seed", str(2 ** 31 + 77), "--seconds", "3",
         "--rehearse", *extra],
        capture_output=True, text=True, env=env, timeout=600)


@pytest.fixture(scope="module")
def traced(tree):
    dump = tree / "dump.json"
    p = _run(tree, "--trace", "1", "--check-dump", str(dump))
    assert p.returncode == 0, p.stderr[-2000:]
    return p, dump


def test_dummy_cell_config_and_metric_are_picked_up_as_files(traced):
    p, _ = traced
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    assert line["attempted"] == 120 and line["failed"] == 0
    assert line["metrics"]["dummy_metric"] == {"value": 120.0, "unit": "count"}
    assert "lanes_per_launch" in line["metrics"]
    # It reports no latency end to end, so nothing that moves one either.
    assert "loadgen_late_ms" not in line["metrics"]
    assert "collective_share" not in line["metrics"]  # one chip
    assert "kernel_ms_per_launch" not in line["metrics"]  # no device trace
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    assert line["device"]["platform"] == "cpu"


def test_last_line_has_exactly_the_contracts_keys(traced, tree):
    p, _ = traced
    assert set(json.loads(p.stdout.strip().splitlines()[-1])) == KEYS
    q = _run(tree, "--trace", "0")
    assert q.returncode == 0, q.stderr[-2000:]
    line = json.loads(q.stdout.strip().splitlines()[-1])
    assert set(line) == KEYS
    # Each number compared beside its limit: the line's last key, and the
    # last lines on standard error.
    assert list(line)[-1] == "compared"
    assert list(line["compared"]) == COMPARED
    assert len(COMPARED) == 6
    assert all(set(c) == {"value", "limit"} for c in line["compared"].values())
    assert line["compared"]["score_gap"]["limit"] == check.LIMITS["score_gap"]
    tail = q.stderr.strip().splitlines()[-len(COMPARED):]
    assert [t.split()[1] for t in tail] == COMPARED
    assert all(t.startswith("check: ") and "(limit " in t for t in tail)
    assert set(line["metrics"]) == {"evals_per_s", "setup_s"}
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    checks = [l for l in q.stdout.splitlines() if l.startswith("check: ")]
    assert sum("limit" in l for l in checks) >= 6


def test_without_an_accelerator_nothing_is_printed(tree):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(tree / "benchmark/run.py"), "--workload",
         "dummy.cell", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_control_in_lower_precision_fails_on_the_runs_own_decisions(traced):
    _, dump = traced
    p = subprocess.run([sys.executable, os.path.join(BENCH, "control.py"),
                        str(dump)], capture_output=True, text=True)
    assert p.returncode == 0, p.stdout + p.stderr
    assert "bfloat16 control" in p.stdout


@pytest.mark.parametrize("what", ["rows", "scores"])
def test_broken_timed_path_is_not_correct(what, monkeypatch, capsys):
    """Drive the rest of a run in this process with the placement dispatch
    altered where its answer is produced: the rows of one plan handed out
    in reverse (each still a node the kernel chose, so the plan commits,
    but not for the placement it was scored for), or every score 0.1 %
    off."""
    import run as bench_run
    from nomad_tpu.scheduler import stack

    real = stack.GenericStack._dispatch_place

    def altered(self, *a, **kw):
        out = list(real(self, *a, **kw))
        if what == "rows":
            rows = out[0].copy()
            rows[rows >= 0] = rows[rows >= 0][::-1]
            out[0] = rows
        else:
            out[1], out[2] = out[1] * 1.001, out[2] * 1.001
        return tuple(out)

    monkeypatch.setattr(stack.GenericStack, "_dispatch_place", altered)
    rc = bench_run.main(["--workload", "c2m-10k.steady", "--seed", "9",
                         "--seconds", "2", "--trace", "0", "--rehearse"])
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is False
