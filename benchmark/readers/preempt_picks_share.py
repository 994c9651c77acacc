"""preempt_picks_share: picks of the placement program whose PREEMPT column is set (the node fits only after an eviction) / picks it placed, over the window, in %."""

import measure


def read(run):
    share = measure.ratio(
        measure.delta(run, "nomad.kernel.preempt_picks_total"),
        measure.delta(run, "nomad.kernel.picks_placed_total"))
    return None if share is None else 100.0 * share
