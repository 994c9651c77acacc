"""coalescer_wait_ms: program span coalescer.queue_wait, median (open loop only)."""

import measure


def read(run):
    if run["loop"] != "open":
        return None
    return measure.span_median_ms(run, "coalescer.queue_wait")
