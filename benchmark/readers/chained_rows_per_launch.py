"""chained_rows_per_launch: rows of the live carried claims blocks (nomad.kernel.chained_rows_total: what the launches whose result was not yet on the host told the launches after them on the device, counted on the host when the block's launch resolves) / fused launches over the window."""

import measure


def read(run):
    return measure.ratio(
        measure.delta(run, "nomad.kernel.chained_rows_total"),
        measure.delta(run, "nomad.kernel.launches{path=fused}"))
