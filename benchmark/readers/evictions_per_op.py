"""evictions_per_op: allocations the applier evicted for committed plans (nomad.plan.preempted_allocs) / operations placed inside the window."""

import measure


def read(run):
    t_end = run["client"]["t_end"]
    placed = sum(1 for r in run["attempted"]
                 if r["ok"] and r["placed"] <= t_end)
    return measure.ratio(
        measure.delta(run, "nomad.plan.preempted_allocs"), placed)
