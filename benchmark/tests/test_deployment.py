"""What a configuration brings of its own: ``scheduler_config``, ``setup``
and ``check`` (README.md, "Adding things"), on a CPU at a tiny size.

* a fixture deployment, added as files alone, sets a key of the scheduler
  configuration over the operator API before the first node registers, installs one real
  allocation between the seeded usage and the warm-up, and its named check
  decides ``correct`` with exactly the ``state`` its ``install`` returned;
* a ``scheduler_config`` the server does not hand back fails the run;
* with none of the three keys the harness calls the server, the client and
  ``check.decide`` in today's order (a spy, not timings).
"""

import functools
import importlib
import json
import os
import subprocess
import sys
import urllib.request

import pytest

from conftest import ROOT, checkout

SCHEDULER_CONFIG = "/v1/operator/scheduler/configuration"
# The fixture turns system-job preemption OFF (the server's default is on):
# a key the server stores and hands back, and one that steers no placement
# of this traffic.  Service preemption, which the deployment this seam was
# built for will turn on, cannot stand here: on this tree a service job's
# plan then names a full node without a preemption and is rejected on every
# attempt (PERF.md section 7).
SCHEDULER = {"preemption_config": {"system_scheduler_enabled": False}}

INSTALL = '''\
"""Fixture set-up: one real allocation of a low-priority batch job on the
emptiest node, and what the store said about when things happened."""

import numpy as np

from nomad_tpu import mock


def install(srv, cfg, seed, rows, seeded):
    i = int(np.argmin(seeded[:, 0]))
    node = srv.store.nodes[f"sim-node-{i:06d}"]
    job = mock.batch_job(priority=10)
    job.id = "fixture-tier"
    tg = job.task_groups[0]
    tg.count, tg.tasks[0].resources.cpu = 1, 10
    tg.tasks[0].resources.memory_mb = 10
    srv.store.upsert_job(srv.next_index(), job)
    alloc = mock.alloc(job, node)
    srv.store.upsert_allocs(srv.next_index(), [alloc])
    return {
        "alloc": {"id": alloc.id, "node_id": node.id, "job_id": job.id},
        "seed": seed,
        "preemption": {
            "system": srv.store.scheduler_config.preemption_config
            .system_scheduler_enabled},
        "scheduler_config_index": srv.store.table_index("scheduler_config"),
        "first_node_index": min(
            n.create_index for n in srv.store.nodes.values()),
    }
'''

CHECK = '''\
"""Fixture check: check.py's comparisons, and the installed allocation read
back over HTTP as the ``state`` names it."""

import check

LIMITS = {**check.LIMITS, "state_mismatch": 0}


def decide(get, cfg, traffic, records, used0, seed, dump=None, state=None):
    correct, numbers, lines = check.decide(
        get, cfg, traffic, records, used0, seed, dump=dump)
    want = (state or {}).get("alloc") or {}
    got = get("/v1/allocation/" + want["id"]) if want else {}
    numbers["state_mismatch"] = int(
        not want or state["seed"] != seed
        or any(got.get(k) != v for k, v in want.items())
        or got.get("desired_status") != "run"
        or state["preemption"] != {"system": False}
        or not state["scheduler_config_index"] < state["first_node_index"])
    lines.append(f"check: state_mismatch = {numbers['state_mismatch']} "
                 f"(limit {LIMITS['state_mismatch']})")
    return correct and not numbers["state_mismatch"], numbers, lines
'''


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """A copy of the benchmark with the fixture deployment ADDED to it:
    two configurations, two set-up / check modules, three cells."""
    root = tmp_path_factory.mktemp("checkout")
    bench = checkout(root)
    base = json.load(open(root / "benchmark/configs/c2m-10k.json"))
    configs = {
        "fixture-deployment": {"scheduler_config": SCHEDULER,
                            "setup": "fixture_install",
                            "check": "fixture_check"},
        "fixture-refused": {"scheduler_config": {"no_such_key": True}},
        "fixture-plain": {},
    }
    for name, keys in configs.items():
        (root / f"benchmark/configs/{name}.json").write_text(
            json.dumps({**base, "name": name, **keys}))
        bench["configs"].append({
            "name": name, "source": "none",
            "file": f"benchmark/configs/{name}.json", "reduced": [],
            "why": "fixture"})
        bench["workloads"].append({
            "name": f"{name}.cell", "config": name, "traffic": "steady",
            "chips": 1, "why": "fixture"})
    os.makedirs(root / "benchmark/deployments", exist_ok=True)
    (root / "benchmark/deployments/fixture_install.py").write_text(INSTALL)
    (root / "benchmark/deployments/fixture_check.py").write_text(CHECK)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


RATE = ["--set", "rate_per_s=40"]  # what a CPU at this size holds


def _run(tree, workload):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=os.path.join(ROOT, ".jax_cache"))
    return subprocess.run(
        [sys.executable, str(tree / "benchmark/run.py"), "--workload",
         workload, "--seed", str(2 ** 31 + 36), "--seconds", "3", "--trace",
         "0", "--rehearse", *RATE],
        capture_output=True, text=True, env=env, timeout=600)


def test_fixture_deployment_end_to_end_from_files_alone(tree):
    p = _run(tree, "fixture-deployment.cell")
    assert p.returncode == 0, p.stderr[-2000:]
    out = p.stdout.strip().splitlines()
    line = json.loads(out[-1])
    assert line["correct"] is True and line["failed"] == 0
    # The named check decided, with the state the named set-up returned.
    assert line["compared"]["state_mismatch"] == {"value": 0, "limit": 0}
    assert list(line["compared"])[-1] == "state_mismatch"
    assert "check: state_mismatch = 0 (limit 0)" in out
    assert p.stderr.strip().splitlines()[-1] == (
        "check: state_mismatch = 0 (limit 0)")
    detail = json.loads(
        [l for l in out if l.startswith("detail: ")][-1][len("detail: "):])
    assert detail["setup"]["install_s"] > 0
    assert detail["e2e"]["setup_s"] > (
        detail["setup"]["import_s"] + detail["setup"]["install_s"])


def test_scheduler_config_the_server_does_not_hand_back_fails(tree):
    p = _run(tree, "fixture-refused.cell")
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "scheduler_config" in p.stderr
    assert "nothing was measured" in p.stderr


# -- the order of the harness's calls, by a spy ------------------------------------

def _spy(monkeypatch, tree, events):
    """Record the harness's calls into the server, the client, the
    operator API and the deployment's modules, in order."""
    import run as bench_run
    from nomad_tpu import cli

    monkeypatch.setattr(bench_run, "load_cell", functools.partial(
        bench_run.load_cell, root=str(tree)))
    monkeypatch.syspath_prepend(str(tree / "benchmark/deployments"))

    def recorded(name, fn):
        @functools.wraps(fn)
        def wrapper(*a, **kw):
            events.append(name)
            return fn(*a, **kw)
        return wrapper

    real_build = cli.build_agent

    def build_agent(args):
        agent = real_build(args)
        real_start = agent.start

        def start():
            events.append("agent.start")
            real_start()
            srv = agent.server
            srv.register_node = recorded("srv.register_node",
                                         srv.register_node)
            srv.matrix.set_usage = recorded("srv.matrix.set_usage",
                                            srv.matrix.set_usage)
        agent.start = start
        return agent

    monkeypatch.setattr(cli, "build_agent", build_agent)

    real_send = bench_run.ClientProc.send

    def send(self, **msg):
        events.append("client." + msg["cmd"])
        return real_send(self, **msg)

    monkeypatch.setattr(bench_run.ClientProc, "send", send)

    real_urlopen = urllib.request.urlopen

    def urlopen(req, *a, **kw):
        if isinstance(req, urllib.request.Request):
            method, url = req.get_method(), req.full_url
        else:
            method, url = "GET", req
        path = "/" + url.split("/", 3)[3]
        events.append(f"http {method} {path}" if path == SCHEDULER_CONFIG
                      else f"http {method}")
        return real_urlopen(req, *a, **kw)

    monkeypatch.setattr(urllib.request, "urlopen", urlopen)
    return bench_run


def _collapsed(events):
    """Consecutive repeats as one; warm-up passes after the first dropped
    (how many there are depends on the compile cache)."""
    out = []
    for e in events:
        if out and out[-1] == e:
            continue
        if e == "client.warmup" and e in out:
            continue
        out.append(e)
    return out


TODAY = [
    "agent.start", "srv.register_node", "srv.matrix.set_usage",
    "client.init", "client.warmup", "srv.matrix.set_usage", "client.run",
    "check.decide", "http GET", "client.exit",
]


def test_with_no_key_the_calls_are_todays(tree, monkeypatch, capsys):
    import check

    events, calls = [], []
    bench_run = _spy(monkeypatch, tree, events)
    real = check.decide

    def decide(*a, **kw):
        events.append("check.decide")
        calls.append((a, kw))
        return real(*a, **kw)

    monkeypatch.setattr(check, "decide", decide)
    rc = bench_run.main(["--workload", "fixture-plain.cell", "--seed", "11",
                         "--seconds", "2", "--trace", "0", "--rehearse",
                         *RATE])
    assert rc == 0
    assert _collapsed(events) == TODAY
    (args, kw), = calls
    assert len(args) == 6 and kw == {"dump": None, "state": None}
    assert not any(m.startswith("fixture_") for m in sys.modules)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True


def test_with_the_three_keys_each_runs_once_in_its_place(
        tree, monkeypatch, capsys):
    events, installed, decided = [], [], []
    bench_run = _spy(monkeypatch, tree, events)
    fixture_install = importlib.import_module("fixture_install")
    fixture_check = importlib.import_module("fixture_check")
    real_install, real_decide = fixture_install.install, fixture_check.decide

    def install(srv, cfg, seed, rows, seeded):
        events.append("fixture_install.install")
        state = real_install(srv, cfg, seed, rows, seeded)
        installed.append(state)
        return state

    def decide(*a, **kw):
        events.append("fixture_check.decide")
        decided.append(kw["state"])
        return real_decide(*a, **kw)

    monkeypatch.setattr(fixture_install, "install", install)
    monkeypatch.setattr(fixture_check, "decide", decide)
    try:
        rc = bench_run.main([
            "--workload", "fixture-deployment.cell", "--seed", "12", "--seconds",
            "2", "--trace", "0", "--rehearse", *RATE])
    finally:
        for m in ("fixture_install", "fixture_check"):
            sys.modules.pop(m, None)
    assert rc == 0
    assert _collapsed(events) == [
        "agent.start",
        "http PUT " + SCHEDULER_CONFIG, "http GET " + SCHEDULER_CONFIG,
        "srv.register_node", "srv.matrix.set_usage",
        "fixture_install.install",
        "client.init", "client.warmup", "srv.matrix.set_usage", "client.run",
        "fixture_check.decide", "http GET", "client.exit",
    ]
    assert events.count("fixture_install.install") == 1
    assert len(installed) == 1 and decided == installed
    assert installed[0]["preemption"] == {"system": False}
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True
    assert line["compared"]["state_mismatch"] == {"value": 0, "limit": 0}
