"""The trace reduction on a small recorded trace (tests/small_trace.json:
two device planes, four 10 ms launches of the placement program each, a
collective partly hidden by a fusion, and a 1 ms scatter)."""

import json
import os

import pytest

import roofline
import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture()
def small():
    with open(os.path.join(HERE, "small_trace.json")) as fh:
        d = json.load(fh)
    d["events"] = [tuple(e) for e in d["events"]]
    return d


def test_reduce(small):
    r = trace_reduce.reduce(small["events"], small["marker_wall"], small["t0"],
                            small["seconds"], spans=small["spans"])
    assert r["devices"] == 2
    assert r["window_s"] == 0.25
    # Each launch: while 0-6 ms, all-reduce 6-9, fusion.9 8-10: busy 10 ms.
    assert r["busy_s"] == pytest.approx(4 * 0.010 + 0.001)
    assert r["launches"] == 4
    assert r["kernel_s"] == pytest.approx(0.040)
    # all-reduce 6-9 ms, fusion.9 covers 8-9: 2 ms exposed per launch.
    assert r["collective_exposed_s"] == pytest.approx(4 * 0.002)
    assert r["device_ops"][0][0] == "while.24"
    assert r["device_ops"][0][1] == pytest.approx(0.024)
    # Longest gap: from the last launch (ends 2.085) to the scatter (2.2).
    name, length = r["idle_gaps"][0]
    assert length == pytest.approx(0.115)
    assert name == "worker.invoke_scheduler"
    assert len(r["idle_gaps"]) <= 10


def test_window_clips_events(small):
    r = trace_reduce.reduce(small["events"], small["marker_wall"],
                            small["t0"] + 0.005, 0.020)
    # 5 ms of the first launch are before the window, the second starts
    # at its end.
    assert r["busy_s"] == pytest.approx(0.005)


def test_no_marker_or_no_device_gives_nothing(small):
    assert trace_reduce.reduce(small["events"], None, 0, 1) is None
    host_only = [e for e in small["events"] if e[0].startswith("/host")]
    assert trace_reduce.reduce(host_only, 1001.0, 1002.0, 1) is None


def test_roofline_share_and_unknown_device():
    work = roofline.launch_work(48.8e6, 10240, 2.0)
    r = roofline.roofline_share("TPU v5 lite", work, 12.4e-3)
    assert r["bound"] == "memory"
    assert 0.3 < r["share_pct"] < 1.0
    with pytest.raises(KeyError):
        roofline.roofline_share("TPU v9", work, 1.0)
