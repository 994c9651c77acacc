"""launch_host_ms: program span coalescer.launch (sync + stage + enqueue on the one dispatch thread), median: launches/s <= 1 / this."""

import measure


def read(run):
    return measure.span_median_ms(run, "coalescer.launch")
