"""scan_steps_per_launch: placement-scan steps the fused launches ran / fused launches over the window."""

import measure


def read(run):
    return measure.ratio(
        measure.delta(run, "nomad.kernel.scan_steps_total"),
        measure.delta(run, "nomad.kernel.launches{path=fused}"))
