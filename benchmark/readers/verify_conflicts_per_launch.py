"""verify_conflicts_per_launch: placements the launch's re-verify column read 0.0 (conflicts within one launch handed to the applier) / fused launches over the window."""

import measure


def read(run):
    return measure.ratio(
        measure.delta(run, "nomad.kernel.verify_conflicts"),
        measure.delta(run, "nomad.kernel.launches{path=fused}"))
