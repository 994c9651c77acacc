"""reregistrations_per_op: client: re-registrations after a failed eval, per attempted operation."""

import measure


def read(run):
    n = len(run["attempted"])
    return measure.ratio(sum(r["registers"] - 1 for r in run["attempted"]
                             if r["registers"]), n)
