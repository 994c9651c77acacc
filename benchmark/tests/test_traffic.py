import collections
import hashlib
import json

import pytest

import traffic


@pytest.mark.parametrize("name", ["steady", "backlog", "backlog-x4"])
def test_schedule_is_a_pure_function_of_seed(name):
    t = traffic.load(name)
    a = traffic.schedule(t, 2 ** 31 + 12345, 10)
    b = traffic.schedule(t, 2 ** 31 + 12345, 10)
    c = traffic.schedule(t, 7, 10)
    assert a == b
    assert a != c


def test_every_seed_gets_the_same_work_in_another_order():
    t = traffic.load("steady")
    a = traffic.schedule(t, 1, 30)
    b = traffic.schedule(t, 99, 30)
    assert len(a) == len(b) == round(t["rate_per_s"] * 30)
    for key in ("width", "namespace", "type", "shape"):
        assert (collections.Counter(o[key] for o in a)
                == collections.Counter(o[key] for o in b))
    gaps = lambda ops: sorted(round(y["due"] - x["due"], 9)
                              for x, y in zip(ops, ops[1:]))
    assert [o["width"] for o in a] != [o["width"] for o in b]
    assert all(0 <= o["due"] < 30 for o in a)
    assert abs(sum(gaps(a)) - sum(gaps(b))) < 0.5


def test_mix_follows_the_file():
    t = traffic.load("steady")
    ops = traffic.schedule(t, 3, 30)
    n = len(ops)
    assert abs(sum(o["type"] == "batch" for o in ops) / n - 0.3) < 0.01
    widths = collections.Counter(o["width"] for o in ops)
    assert widths[1] > widths[2] > widths[4] > widths[8] > 0
    assert {o["priority"] for o in ops} == {10, 50}
    payload = traffic.job_payload(t, ops[0])
    assert payload["task_groups"][0]["count"] == ops[0]["width"]
    assert len(traffic.warmup_ops(t)) == 2 * len(t["shapes"]) * 2


# -- what an operation is, as data (PR 43) -------------------------------------------

def _digest(name, seed, seconds=50):
    """Everything the generator gives for one file and seed: the window's
    operations, the warm-up's, and every payload as it is PUT."""
    t = traffic.load(name)
    ops = traffic.schedule(t, seed, seconds)
    warm = traffic.warmup_ops(t)
    h = hashlib.sha256()
    for part in (ops, warm, [traffic.job_payload(t, o) for o in ops],
                 [traffic.job_payload(t, o, "w0s-") for o in warm]):
        h.update(json.dumps(part, sort_keys=True).encode())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("name,want", [
    ("steady", ["dbd4e0215117fca1", "9c5b4a374cff0773"]),
    ("backlog", ["4b2a222a08b12aeb", "0e35a2a754e91e60"]),
    # backlog-x4 deals backlog's mix and as many blocks (41)
    ("backlog-x4", ["4b2a222a08b12aeb", "0e35a2a754e91e60"]),
    ("tiers-backlog", ["0f01b3959f660c85", "38f9281dd1cd4ab9"]),
    # taken from the parent tree of PR 51 (7026899), before ``job_payload``
    # learned ``disk_mb``, ``networks`` and ``devices``
    ("rules-backlog", ["f72d8149d8038aff", "672bb7e4abd5a69b"]),
    ("rules-backlog-x4", ["693520a2e45eb74e", "7407092a25d21114"]),
])
def test_the_files_that_were_there_give_the_operations_they_gave(name, want):
    """Digests taken from the parent tree (259c845) before ``traffic.py``
    learned the kinds (PR 43), and held again when a shape's body became
    data (PR 51): a file without the new keys gives, bit for bit, what it
    gave."""
    assert [_digest(name, s) for s in (7, 2 ** 31 + 4300)] == want
    t = traffic.load(name)
    assert all("kind" not in o for o in traffic.schedule(t, 7, 2))
    payload = traffic.job_payload(t, traffic.schedule(t, 7, 2)[0])
    assert list(payload) == ["id", "name", "namespace", "type", "priority",
                             "datacenters", "task_groups"]
    assert list(payload["task_groups"][0]) == [
        "name", "count", "constraints", "affinities", "spreads", "tasks"]
    assert list(payload["task_groups"][0]["tasks"][0]["resources"]) == [
        "cpu", "memory_mb"]


# -- the job body as data (PR 51) ----------------------------------------------------

OP = {"namespace": "default", "width": 3, "type": "service", "priority": 50,
      "shape": 0, "job_id": "op-000000"}


def _body(**keys):
    t = traffic.load("backlog")
    plain = traffic.job_payload(t, OP)
    t["shapes"][0] = dict(t["shapes"][0], **keys)
    return plain, traffic.job_payload(t, OP)


@pytest.mark.parametrize("keys,where,want", [
    ({"disk_mb": 300}, "group", {"ephemeral_disk": {"size_mb": 300}}),
    ({"disk_mb": 0}, "group", {"ephemeral_disk": {"size_mb": 0}}),
    ({"networks": [{"reserved_ports": [80, 443], "dynamic_ports": ["db"]}]},
     "group",
     {"networks": [{"reserved_ports": [80, 443], "dynamic_ports": ["db"]}]}),
    ({"networks": [{"dynamic_ports": ["http", "metrics"]}]}, "group",
     {"networks": [{"reserved_ports": [],
                    "dynamic_ports": ["http", "metrics"]}]}),
    ({"devices": [{"name": "nvidia/gpu", "count": 2}]}, "resources",
     {"devices": [{"name": "nvidia/gpu", "count": 2}]}),
    ({"devices": [{"name": "nvidia/gpu"}]}, "resources",
     {"devices": [{"name": "nvidia/gpu", "count": 1}]}),
])
def test_a_shape_may_carry_disk_networks_and_devices(keys, where, want):
    """Each key lands where the server's decoder reads it (the group's
    ``ephemeral_disk`` and ``networks``, the task's ``resources.devices``)
    and changes nothing else of the body."""
    plain, body = _body(**keys)
    group = body["task_groups"][0]
    got = group if where == "group" else group["tasks"][0]["resources"]
    for k, v in want.items():
        assert got.pop(k) == v
    assert body == plain


def test_net_backlog_is_backlogs_loop_with_a_port_a_disk_or_a_device_a_shape():
    base, t = traffic.load("backlog"), traffic.load("net-backlog")
    differ = {k for k in set(base) | set(t) if base.get(k) != t.get(k)}
    assert differ == {"shapes", "why", "name"}
    assert [s["name"] for s in t["shapes"]] == [f"n{i}" for i in range(8)]
    assert all(s.get("networks") or s.get("devices") or s["disk_mb"]
               for s in t["shapes"])
    n0 = traffic.job_payload(t, dict(OP, shape=0))["task_groups"][0]
    # the ``nomad job init`` example job: cpu 500, memory 256, port "db",
    # ephemeral_disk 300
    assert n0["tasks"][0]["resources"] == {"cpu": 500, "memory_mb": 256}
    assert n0["networks"] == [{"reserved_ports": [], "dynamic_ports": ["db"]}]
    assert n0["ephemeral_disk"] == {"size_mb": 300}
    counts = collections.Counter(
        o["shape"] for o in traffic.schedule(t, 7, 50)[:512])
    assert set(counts.values()) == {64}  # equal shares, block by block
    # one job a type, shape and extreme width: the warm-up meets every body
    assert len(traffic.warmup_ops(t)) == 2 * 8 * 2


def _mix(**keys):
    t = traffic.load("backlog")
    t.update(keys)
    return t


@pytest.mark.parametrize("fraction", [0.8, 0.5])
def test_kind_deck(fraction):
    """Exact proportion per block, the same multiset for every seed, and no
    resident job registered again within ``resident_jobs`` operations."""
    t = _mix(register_again_fraction=fraction, resident_jobs=2048,
             max_rate_per_s=400)
    want_again = traffic.deal([1 - fraction, fraction], traffic.BLOCK).count(1)
    runs = [traffic.schedule(t, seed, 50) for seed in (1, 2 ** 31 + 99)]
    for ops in runs:
        assert len(ops) == 41 * traffic.BLOCK
        for b in range(0, len(ops), traffic.BLOCK):
            kinds = collections.Counter(
                o["kind"] for o in ops[b:b + traffic.BLOCK])
            assert kinds == {"again": want_again,
                             "new": traffic.BLOCK - want_again}
        last = {}
        for o in ops:
            if o["kind"] == "again":
                assert o["job_id"] == f"res-{o['resident']:06d}"
                assert o["i"] - last.get(o["job_id"], -10 ** 9) >= 2048
                last[o["job_id"]] = o["i"]
            else:
                assert o["job_id"] == f"op-{o['i']:06d}"
        # An ``again`` operation carries its resident job's attributes: the
        # payload is the one first sent.
        resident = traffic.resident_ops(t, [1, 2 ** 31 + 99][runs.index(ops)])
        assert len(resident) == 2048
        for o in ops[:2000]:
            if o["kind"] == "again":
                assert traffic.job_payload(t, o) == traffic.job_payload(
                    t, resident[o["resident"]])
    a, b = runs
    assert [o["kind"] for o in a] != [o["kind"] for o in b]
    # The new jobs are the same multiset for every seed; the ``again``
    # operations go round the resident set, itself the same multiset for
    # every seed, in whole cycles (a run ends part way through one).
    again = [[o for o in ops if o["kind"] == "again"] for ops in runs]
    for key in ("width", "namespace", "type", "shape"):
        count = lambda ops: collections.Counter(o[key] for o in ops)
        assert (count(o for o in a if o["kind"] == "new")
                == count(o for o in b if o["kind"] == "new")), key
        assert count(traffic.resident_ops(t, 1)) == count(
            traffic.resident_ops(t, 2 ** 31 + 99)), key
        assert count(again[0][:4 * 2048]) == count(again[1][:4 * 2048]), key
    for cycle in (again[0][:2048], again[1][2048:4096]):
        assert sorted(o["resident"] for o in cycle) == list(range(2048))
