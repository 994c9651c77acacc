import collections

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
