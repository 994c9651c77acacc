"""broker_wait_ms: program span broker.queue_wait, median (open loop only)."""

import measure


def read(run):
    if run["loop"] != "open":
        return None
    return measure.span_median_ms(run, "broker.queue_wait")
