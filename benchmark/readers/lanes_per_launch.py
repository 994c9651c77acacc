"""lanes_per_launch: fused lanes / fused launches over the window."""

import measure


def read(run):
    return measure.ratio(
        measure.delta(run, "nomad.kernel.fused_lanes"),
        measure.delta(run, "nomad.kernel.launches{path=fused}"))
