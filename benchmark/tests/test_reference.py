"""The plain reference disagrees with a deliberately wrong read-back."""

import copy

import numpy as np
import pytest

import check
import reference as ref
import traffic

CLUSTER = {
    "datacenters": 4, "node_classes": 6, "racks": 32,
    "node_resources": {"cpu": 4000, "memory_mb": 8192, "disk_mb": 102400},
    "node_reserved": {"cpu": 100, "memory_mb": 256, "disk_mb": 0},
}
N = 96


def _world():
    """A read-back of two placed operations on a 96-node cluster, scored
    by the reference itself (so the sound read-back passes exactly)."""
    t = traffic.load("steady")
    cfg = {"cluster": CLUSTER, "nodes": N}
    used0 = np.tile(np.array([[1500.0, 3000.0, 900.0]], np.float32), (N, 1))
    used0[:, 0] += np.arange(N) * 7.0
    totals = ref.node_totals(CLUSTER)
    nodes = {}
    for i in range(N):
        e = ref.expected_node(i, CLUSTER)
        nodes[check.node_id(i)] = {
            "id": check.node_id(i), "status": "ready",
            "scheduling_eligibility": "eligible",
            "datacenter": e["datacenter"], "node_class": e["node_class"],
            "attributes": e["attributes"],
            "resources": CLUSTER["node_resources"],
            "reserved": CLUSTER["node_reserved"],
        }
    records, allocs = [], []
    seen = used0.astype(np.float64).copy()
    for k, (shape, row) in enumerate([(0, 95), (1, 95)]):
        s = t["shapes"][shape]
        ask = [s["cpu"], s["memory_mb"], 300]
        b = float(ref.binpack_score(seen[row], ask, totals))
        seen[row] += ask
        aff = float(ref.affinity_term(ref.attr_tables(N, CLUSTER),
                                      s["affinities"])[row])
        f = float(ref.final_score(b, 0, 1, aff))
        jid = f"op-{k:06d}"
        records.append({"i": k, "job_id": jid, "status": "placed", "width": 1,
                        "shape": shape, "namespace": "default"})
        allocs.append({
            "id": f"a{k}", "job_id": jid, "task_group": "g",
            "node_id": check.node_id(row), "desired_status": "run",
            "create_index": 200 + k,
            "resources": {"cpu": ask[0], "memory_mb": ask[1], "disk_mb": ask[2]},
            "metrics": {"scores": {check.node_id(row): {"binpack": b, "final": f}}},
        })

    def get(path):
        if path == "/v1/nodes":
            return list(nodes.values())
        if path.startswith("/v1/node/"):
            return nodes[path.rsplit("/", 1)[1]]
        if path == "/v1/allocations?namespace=default":
            return allocs
        return []

    return get, cfg, t, records, used0, allocs, nodes


def _decide(world):
    get, cfg, t, records, used0, _, _ = world
    return check.decide(get, cfg, t, records, used0, seed=1)


def test_sound_read_back_is_correct():
    correct, numbers, lines = _decide(_world())
    assert correct, numbers
    assert numbers["score_gap"] < 1e-12
    assert any("score_gap" in line and "limit" in line for line in lines)


def test_overcommitted_read_back_is_not_correct():
    w = _world()
    for k in range(40):  # 40 x 100 MHz more on a node with 3900
        a = copy.deepcopy(w[5][0])
        a["id"], a["create_index"] = f"x{k}", 300 + k
        a["job_id"] = "other"
        w[5].append(a)
    correct, numbers, _ = _decide(w)
    assert not correct
    assert numbers["overcommitted_nodes"] == 1


@pytest.mark.parametrize("what", [
    "missing_alloc", "wrong_datacenter", "score_off", "better_node_ignored",
    "node_down",
])
def test_each_broken_guarantee_is_caught(what):
    w = _world()
    get, cfg, t, records, used0, allocs, nodes = w
    if what == "missing_alloc":
        records[0]["width"] = 2
        key = "count_mismatch"
    elif what == "wrong_datacenter":
        records[0]["shape"] = 3  # dc1/dc2 only; node 95 is in dc4
        key = "constraint_violations"
    elif what == "score_off":
        sc = allocs[0]["metrics"]["scores"][allocs[0]["node_id"]]
        sc["binpack"] *= 1.001
        key = "score_gap"
    elif what == "better_node_ignored":
        # The alloc sits on an emptier node than the fullest one: the
        # score recorded there is below what node 95 offered.
        a = allocs[0]
        a["node_id"] = check.node_id(3)
        b = float(ref.binpack_score(used0[3], [100, 128, 300],
                                    ref.node_totals(CLUSTER)))
        a["metrics"]["scores"] = {a["node_id"]: {"binpack": b, "final": b}}
        key = "rank_gap"
    else:
        nodes[check.node_id(10)]["status"] = "down"
        key = "nodes_wrong"
    correct, numbers, _ = _decide(w)
    assert not correct
    assert numbers[key] > check.LIMITS[key]


def test_node_full_within_float32_rounding_is_not_a_node_passed_over(tmp_path):
    """Node 94 is fuller than the chosen node 95 and misses room for the
    ask by 0.0005 MHz: the program's float32 sums rightly saw it as full.
    The loose reading of room (float64 sums + 1e-3) takes it for a better
    node passed over; ``correct`` does not.  A node with room beyond doubt
    still is one (``better_node_ignored`` above)."""
    import json

    get, cfg, t, records, used0, _, _ = _world()
    used0[94, 0] = np.float32(3800.0005)  # + 100 asked = 3900.0005 of 3900
    dump = tmp_path / "dump.json"
    correct, numbers, lines = check.decide(
        get, cfg, t, records, used0, seed=1, dump=str(dump))
    assert correct, numbers
    samples = json.loads(dump.read_text())["samples"]
    _, loose = check.score_gaps(samples, floor="floor_loose")
    assert loose > check.LIMITS["rank_gap"] >= numbers["rank_gap"]
    assert any("1 (node, ask) pairs" in line for line in lines), lines
    used0[94, 0] = np.float32(3799.0)     # room by 1 MHz: beyond doubt
    correct, numbers, _ = check.decide(get, cfg, t, records, used0, seed=1)
    assert not correct and numbers["rank_gap"] > check.LIMITS["rank_gap"]


def test_lower_precision_control_fails_the_limit(tmp_path):
    import json

    import ml_dtypes

    get, cfg, t, records, used0, _, _ = _world()
    dump = tmp_path / "dump.json"
    check.decide(get, cfg, t, records, used0, seed=1, dump=str(dump))
    samples = json.loads(dump.read_text())["samples"]
    assert len(samples) == 2
    sound, _ = check.score_gaps(samples)
    control, _ = check.score_gaps(samples, recorded_dtype=ml_dtypes.bfloat16)
    assert sound <= check.LIMITS["score_gap"] < control
