"""Driver-contract tests for the entry points: __graft_entry__,
chip_smoke.py and the compile-cache rule they share.

Round-1 postmortem (VERDICT.md Weak #9): nothing exercised the entry
points the way the driver does — a fresh process with the *default*
environment, importing the module and calling the functions directly.
That's exactly what hung the round-1 multichip dryrun. These tests spawn
fresh subprocesses with no CPU-forcing in the parent so the entry points
must prove they are self-contained.
"""

from __future__ import annotations

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _driver_like_env() -> dict:
    """The driver's default environment: no JAX_PLATFORMS, no forced
    virtual device count (conftest.py sets both for in-process tests;
    strip them so the child sees what the driver's child would)."""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    flags = env.get("XLA_FLAGS", "")
    flags = " ".join(
        f for f in flags.split()
        if "xla_force_host_platform_device_count" not in f
    )
    if flags:
        env["XLA_FLAGS"] = flags
    else:
        env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


def test_dryrun_multichip_fresh_process():
    """dryrun_multichip(8) must succeed when called exactly as the driver
    calls it: module import + direct function call, default env."""
    code = "import __graft_entry__ as g; g.dryrun_multichip(8)"
    p = subprocess.run(
        [sys.executable, "-c", code],
        cwd=REPO,
        env=_driver_like_env(),
        capture_output=True,
        text=True,
        timeout=560,
    )
    assert p.returncode == 0, f"stdout={p.stdout}\nstderr={p.stderr}"
    assert "dryrun_multichip ok" in p.stdout


def test_entry_compiles_fresh_process():
    """entry() must return a jittable (fn, args) pair in a fresh process.
    (CPU platform pinned: the test box has no real chip; the contract
    under test is import + build + jit-compile, not the backend.)"""
    code = (
        "import __graft_entry__ as g\n"
        "import jax, numpy as np\n"
        "fn, args = g.entry()\n"
        "out = np.asarray(jax.jit(fn)(*args))\n"
        "assert out.shape[0] == 4 and out.shape[2] == 8, out.shape\n"
        "assert (out[:, 0, 0] >= 0).all(), out[:, :, 0]\n"
        "print('entry-contract-ok')\n"
    )
    env = _driver_like_env()
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "-c", code],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=560,
    )
    assert p.returncode == 0, f"stdout={p.stdout}\nstderr={p.stderr}"
    assert "entry-contract-ok" in p.stdout


def _cache_dir_seen_by_child(env: dict, cwd: str) -> str:
    code = (
        "import nomad_tpu, jax\n"
        "d = nomad_tpu.enable_compilation_cache()\n"
        "assert d == jax.config.jax_compilation_cache_dir, d\n"
        "print(d)\n"
    )
    p = subprocess.run(
        [sys.executable, "-c", code], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert p.returncode == 0, p.stderr
    return p.stdout.strip()


def test_compilation_cache_directory_rule(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the directory is left to JAX;
    unset it is <checkout>/.jax_cache — the same from any process and any
    working directory, with no temporary name, pid or time in it."""
    env = _driver_like_env()
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "placed")
    assert _cache_dir_seen_by_child(env, REPO) == str(tmp_path / "placed")
    del env["JAX_COMPILATION_CACHE_DIR"]
    expected = os.path.join(REPO, ".jax_cache")
    assert _cache_dir_seen_by_child(env, REPO) == expected
    assert _cache_dir_seen_by_child(env, str(tmp_path)) == expected


def test_chip_smoke_refuses_without_an_accelerator(tmp_path):
    """No accelerator: names the platform it found, exits non-zero and
    prints no result line."""
    env = _driver_like_env()
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert p.returncode != 0
    assert "platform=cpu" in p.stdout
    assert "cpu" in p.stderr
    assert not any(l.startswith("{") for l in p.stdout.splitlines())


def test_chip_smoke_cpu_rehearsal_runs_every_phase(tmp_path):
    """The rehearsal keeps the smoke's own code honest between chip runs:
    every check passes on a tiny cluster, and the result still says it
    established nothing (ok stays false)."""
    import json

    env = _driver_like_env()
    env.update(JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path))
    p = subprocess.run(
        [sys.executable, "chip_smoke.py", "--cpu-rehearsal"], cwd=REPO,
        env=env, capture_output=True, text=True, timeout=560,
    )
    assert p.returncode == 0, f"stdout={p.stdout[-3000:]}\nstderr={p.stderr[-3000:]}"
    lines = p.stdout.strip().splitlines()
    # Last line: the verdict alone, exactly the keys the driver parses.
    verdict = json.loads(lines[-1])
    assert set(verdict) == {"ok", "device"} and verdict["ok"] is False
    assert set(verdict["device"]) == {"platform", "kind", "count"}
    assert isinstance(verdict["device"]["kind"], str)
    assert isinstance(verdict["device"]["count"], int)
    assert lines[-2].startswith("report: ")
    out = json.loads(lines[-2][len("report: "):])
    assert out["ok"] is False and out["rehearsal"] == "tiny"
    assert out["device"] == verdict["device"]
    assert "failed" not in out
    assert list(out["library"]["entry_points"]) == [
        "fused_place_batch", "fused_place_batch_mixed_steps",
        "place_task_group", "system_feasible", "verify_plan_fit",
        "row_scatter",
    ]
    assert out["live"]["counters"]["fused_dispatches"] > 0
    assert out["live"]["allocs_preempted"] >= 1
