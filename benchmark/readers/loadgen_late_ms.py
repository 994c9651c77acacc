"""loadgen_late_ms: client: first send - due, 95th percentile (open loop only)."""

import measure


def read(run):
    if run["loop"] != "open":
        return None
    late = [(r["sent"] - r["due"]) * 1e3 for r in run["attempted"] if r["sent"]]
    return measure.percentile(late, 0.95)
