"""lane_repicks_per_launch: placements the in-launch resolution moved off a node earlier lanes of the same launch had claimed / fused launches over the window."""

import measure


def read(run):
    return measure.ratio(
        measure.delta(run, "nomad.kernel.lane_repicks_total"),
        measure.delta(run, "nomad.kernel.launches{path=fused}"))
