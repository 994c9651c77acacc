"""chained_launch_share: fused launches enqueued with at least one live carried claims block (nomad.coalescer.chained_launches: a launch before them had not reached the host, so its picks rode to them in the device buffer) / fused launches over the window, in %: reads beside unresolved_predecessor_share."""

import measure


def read(run):
    n = measure.delta(run, "nomad.coalescer.chained_launches")
    return measure.ratio(
        None if n is None else 100.0 * n,
        measure.delta(run, "nomad.kernel.launches{path=fused}"))
