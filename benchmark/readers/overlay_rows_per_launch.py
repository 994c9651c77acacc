"""overlay_rows_per_launch: rows of the in-flight claims overlay (picks of earlier launches whose plans the applier had not decided, or had committed after the launch's snapshot) handed to the placement program / fused launches over the window."""

import measure


def read(run):
    return measure.ratio(
        measure.delta(run, "nomad.kernel.overlay_rows_total"),
        measure.delta(run, "nomad.kernel.launches{path=fused}"))
