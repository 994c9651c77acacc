"""placement_p99_ms: client records: due -> placed, 99th percentile (open loop only)."""

import measure


def read(run):
    if run["loop"] != "open":
        return None
    return measure.percentile(measure.latencies_ms(run), 0.99)
