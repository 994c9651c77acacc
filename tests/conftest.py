"""Test configuration: force an 8-device virtual CPU platform.

Multi-chip TPU hardware is not available in CI; sharding correctness is
validated on a virtual 8-device CPU mesh (the driver separately dry-run
compiles the multi-chip path via __graft_entry__.dryrun_multichip).
This must run before jax is imported anywhere.
"""

import os

# Force CPU even if the environment preset JAX_PLATFORMS: unit tests
# validate logic + sharding on the virtual mesh; chip_smoke.py and benchmark/run.py
# are what run on the real chip.
os.environ["JAX_PLATFORMS"] = "cpu"

# Widen every raft timer 2x: the defaults (0.15-0.5s elections, 50-80ms
# heartbeats) flap when a loaded CI machine delays scheduler threads past
# the election window (round-4 flake in test_writes_rejected_on_followers).
os.environ.setdefault("NOMAD_TPU_RAFT_TIMEOUT_SCALE", "2.0")

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pytest  # noqa: E402

import nomad_tpu  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: exhaustive chaos sweeps excluded from tier-1 (-m 'not slow')",
    )
    # fused_place_batch_live donates its lane operands; no output shares their
    # shape, so XLA cannot alias them and jax warns once per compile (on
    # every backend).
    config.addinivalue_line(
        "filterwarnings",
        "ignore:Some donated buffers were not usable:UserWarning",
    )

# Kernel first-compiles are tens of seconds; persist them across test runs.
nomad_tpu.enable_compilation_cache()


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    outcome = yield
    report = outcome.get_result()
    if report.when == "call" and report.failed:
        # Post-mortem: persist the flight recorder (span ring buffers +
        # active chaos seed) so the failed run's timeline survives.
        # Capped per process (trace._MAX_AUTO_DUMPS) so a cascading
        # failure doesn't flood the trace dir.
        from nomad_tpu import trace

        path = trace.auto_dump("test-failure", extra={"test": item.nodeid})
        if path:
            report.sections.append(
                ("flight record", f"span timeline dumped to {path}")
            )


@pytest.fixture(scope="session")
def eight_devices():
    import jax

    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs
