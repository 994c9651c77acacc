"""The two readers of the in-launch pick resolution's counters: the ratio
from a pair of counter snapshots, ``None`` where there was no fused launch
in the window or the program has no such counter (the parent of the PR
that added them)."""

import importlib

import pytest

LAUNCHES = "nomad.kernel.launches{path=fused}"
COUNTER = {
    "verify_conflicts_per_launch": "nomad.kernel.verify_conflicts",
    "lane_repicks_per_launch": "nomad.kernel.lane_repicks_total",
}


def read(name, run):
    return importlib.import_module(name).read(run)


@pytest.mark.parametrize("name", sorted(COUNTER))
def test_ratio_over_the_window(name):
    run = {"m0": {COUNTER[name]: 40, LAUNCHES: 100},
           "m1": {COUNTER[name]: 340, LAUNCHES: 300}}
    assert read(name, run) == pytest.approx(1.5)


@pytest.mark.parametrize("name", sorted(COUNTER))
def test_a_counter_that_stood_still_reads_zero(name):
    run = {"m0": {COUNTER[name]: 7, LAUNCHES: 100},
           "m1": {COUNTER[name]: 7, LAUNCHES: 300}}
    assert read(name, run) == 0.0


@pytest.mark.parametrize("name", sorted(COUNTER))
@pytest.mark.parametrize("run", [
    # no fused launch in the window
    {"m0": {LAUNCHES: 5}, "m1": {LAUNCHES: 5}},
    # the parent: launches, but no such counter
    {"m0": {LAUNCHES: 5}, "m1": {LAUNCHES: 50}},
    # no snapshots at all
    {},
], ids=["no_launch", "no_counter", "no_snapshots"])
def test_nothing_to_read_is_none(name, run):
    run = {k: dict(v) for k, v in run.items()}
    if "m1" in run and run["m1"][LAUNCHES] == run["m0"][LAUNCHES]:
        run["m0"][COUNTER[name]] = 0
        run["m1"][COUNTER[name]] = 3
    assert read(name, run) is None
