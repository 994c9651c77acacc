"""How much of the window's end a traced run traces (PR 51): the slice
outlasts a full collection of the server's heap, on any number of chips."""

import pytest

import run


@pytest.mark.parametrize("seconds,collections,want", [
    (50, [], 2.0),
    (50, [0.07, 0.095, 0.126, 0.58], 2.0),            # c2m-10k.backlog
    (50, [0.42, 0.556, 0.69], 3 * 0.69),              # tiers-backlog
    (50, [0.36, 0.512, 0.618, 0.739], 3 * 0.739),     # rules-backlog-x4
    (50, [0.36, 0.512, 0.618, 0.739, 1.0], 3.0),      # its fourth, ~49 s
    (50, [12.0], 25.0),                               # never past half
    (3, [0.1], 1.5),                                  # a rehearsal's window
])
def test_the_slice_outlasts_the_longest_collection_three_times(
        seconds, collections, want):
    assert run.slice_seconds(seconds, collections) == pytest.approx(want)


def test_a_collection_inside_the_slice_leaves_launches_on_either_side():
    """PR 49's loss: a 0.5 s slice under a 1.0 s collection.  The slice
    sized from the collections before it (each longer than the last, by a
    fifth or so) leaves over a second of launches around the next."""
    seen = [0.364, 0.512, 0.618, 0.739]
    length = run.slice_seconds(50, seen)
    assert length - 1.0 >= 1.0
    assert length >= run.TRACE_SECONDS
