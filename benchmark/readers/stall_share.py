"""stall_share: lateness of the probe's wakes that came more than 250 ms late (nomad.runtime.stall_seconds_total; each is a span runtime.stall) over the window / window, in %: the holes in which the process did not run."""

import host_cpu
import measure


def read(run):
    return host_cpu.share_pct(
        measure.delta(run, "nomad.runtime.stall_seconds_total"), run)
