"""``register_again_fraction`` end to end (``--rehearse``, a CPU at a tiny
size): a dummy cell from files alone whose traffic registers half its
operations' jobs again; and the same kind of traffic with the program broken
underneath where a registration of an unchanged job is answered."""

import json
import os
import subprocess
import sys

import pytest

import check
from conftest import ROOT, checkout

KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    bench = checkout(root)
    mix = json.load(open(root / "benchmark/traffic/steady.json"))
    mix.update(rate_per_s=40, why="dummy", register_again_fraction=0.5,
               resident_jobs=60)
    (root / "benchmark/traffic/kinds-mix.json").write_text(json.dumps(mix))
    bench["workloads"].append({
        "name": "kinds.cell", "config": "c2m-10k", "traffic": "kinds-mix",
        "chips": 1, "why": "dummy"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_a_cell_from_files_alone_with_jobs_registered_again(tree):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=os.path.join(ROOT, ".jax_cache"))
    p = subprocess.run(
        [sys.executable, str(tree / "benchmark/run.py"), "--workload",
         "kinds.cell", "--seed", str(2 ** 31 + 43), "--seconds", "3",
         "--trace", "0", "--rehearse"],
        capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    out = p.stdout.strip().splitlines()
    line = json.loads(out[-1])
    assert set(line) == KEYS and list(line)[-1] == "compared"
    assert line["correct"] is True, out[-30:]
    detail = json.loads(
        [l for l in out if l.startswith("detail: ")][-1][len("detail: "):])
    assert (line["attempted"], line["failed"]) == (120, 0), detail["causes"]
    assert list(line["compared"]) == list(check.LIMITS)
    assert line["compared"]["resubmit_version_bumped"] == {
        "value": 0, "limit": 0}
    assert line["compared"]["resubmit_allocs_replaced"] == {
        "value": 0, "limit": 0}
    assert set(line["metrics"]) == {"evals_per_s", "setup_s"}
    tail = p.stderr.strip().splitlines()[-len(check.LIMITS):]
    assert [t.split()[1] for t in tail] == list(check.LIMITS)
    assert detail["setup"]["resident_s"] > 0
    assert detail["e2e"]["setup_s"] > (
        detail["setup"]["import_s"] + detail["setup"]["warmup_s"]
        + detail["setup"]["resident_s"])
    said = [l for l in out if l.startswith("check: compared ")][-1]
    assert "60 of the operations registered 60 resident jobs again" in said
    assert "resident set: 60 jobs placed" in p.stderr


@pytest.mark.parametrize("what", ["sound", "bumped", "replaced"])
def test_broken_registration_of_an_unchanged_job_is_not_correct(
        what, monkeypatch, capsys):
    """Drive the rest of a run in this process (``c2m-10k.backlog``'s files
    with half the operations registering one of 48 resident jobs again) with
    the answer to an unchanged job's registration altered where it is
    produced: the store takes every registration for a changed spec (a new
    version; the allocations are updated in place), or the reconciler also
    takes the change for a destructive one (every allocation stopped and
    placed anew).  Left as it is, the same run is correct."""
    import run as bench_run
    from nomad_tpu.scheduler import reconcile
    from nomad_tpu.state.store import StateStore

    if what != "sound":
        monkeypatch.setattr(StateStore, "_job_spec_changed",
                            staticmethod(lambda a, b: True))
    if what == "replaced":
        # (the memo, not ``tasks_updated``: its verdicts are cached by job
        # id and version pair for the life of the process)
        monkeypatch.setattr(reconcile, "tasks_updated_memo",
                            lambda old, new, tg: True)
    rc = bench_run.main(["--workload", "c2m-10k.backlog", "--seed", "9",
                         "--seconds", "2", "--trace", "0", "--rehearse",
                         "--set", "register_again_fraction=0.5",
                         "--set", "resident_jobs=48",
                         "--set", "outstanding=16"])
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is (what == "sound") and line["failed"] == 0
    compared = {k: c["value"] for k, c in line["compared"].items()}
    assert (compared["resubmit_version_bumped"] > 0) == (what != "sound")
    assert (compared["resubmit_allocs_replaced"] > 0) == (what == "replaced")
