"""unresolved_predecessor_share: launches enqueued while an earlier launch's result was not yet on the host (nomad.coalescer.launches_unresolved_predecessor: its picks were in no claims ledger) / fused launches over the window, in %: the ceiling of carrying claims on the device."""

import measure


def read(run):
    n = measure.delta(run, "nomad.coalescer.launches_unresolved_predecessor")
    return measure.ratio(
        None if n is None else 100.0 * n,
        measure.delta(run, "nomad.kernel.launches{path=fused}"))
