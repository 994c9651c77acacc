"""matrix_sync_ms: program span coalescer.sync (matrix dirty rows host -> device, under DEVICE_LOCK), median: the time beside h2d_bytes_per_eval's bytes."""

import measure


def read(run):
    return measure.span_median_ms(run, "coalescer.sync")
