"""The closed loops' deck: how much of it a window used (``deck_used_share``)
and that deepening ``backlog-x4``'s left the operations it dealt before
where they were."""

import importlib

import pytest

import traffic

SEED = 2 ** 31 + 36


def read(run):
    return importlib.import_module("deck_used_share").read(run)


def reply(begun, dealt):
    """What the client's reply to ``run`` carries of the deck."""
    return {"records": [{"i": i} for i in range(begun)], "scheduled": dealt}


@pytest.mark.parametrize("begun,dealt,want", [
    (10_050, 20_992, 100.0 * 10_050 / 20_992),  # c2m-10k.backlog at PR 33
    (6_627, 6_656, 100.0 * 6_627 / 6_656),      # the four-chip cell at PR 33
    (6_656, 6_656, 100.0),                       # the deck ran out
    (0, 20_992, 0.0),
])
def test_deck_used_share_on_a_recorded_reply(begun, dealt, want):
    assert read({"loop": "closed", "client": reply(begun, dealt)}) == (
        pytest.approx(want))


@pytest.mark.parametrize("run", [
    {"loop": "open", "client": reply(3050, 3050)},  # a schedule, not a deck
    {"loop": "closed", "client": {"records": []}},  # a client that says nothing
    {"loop": "closed"},
    {},
], ids=["open_loop", "no_count", "no_client", "nothing"])
def test_deck_used_share_with_nothing_to_read_is_none(run):
    assert read(run) is None


def test_backlog_x4_deals_todays_operations_first_then_more():
    t = traffic.load("backlog-x4")
    before = traffic.schedule(dict(t, max_rate_per_s=120), SEED, 50)
    now = traffic.schedule(t, SEED, 50)
    assert len(before) == 6_656
    assert now[:6_656] == before
    assert len(now) - len(before) == 14_336
    # 419.8 a second is the most the cell can read; the server places ~133.
    assert len(now) / 50 == pytest.approx(419.84)


@pytest.mark.parametrize("name", ["backlog", "backlog-x4"])
def test_closed_loops_deck_is_twice_what_the_server_places_or_more(name):
    """The rates of the ledger's PR 33 lines: 201.43 and 132.86 a second."""
    placed = {"backlog": 201.43, "backlog-x4": 132.86}[name]
    t = traffic.load(name)
    assert t["limit_s"] < 50  # a blocked operation fails inside the drain
    assert len(traffic.schedule(t, SEED, 50)) / 50 >= 2 * placed
