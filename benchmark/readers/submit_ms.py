"""submit_ms: client: the register call, median (open loop only)."""

import statistics

import measure


def read(run):
    if run["loop"] != "open":
        return None
    v = [r["submit_s"] * 1e3 for r in run["attempted"] if r["submit_s"]]
    return statistics.median(v) if v else None
