"""preempt_reentries_per_eval: launches re-entered after a preempting pick (nomad.sched.preempt_reentries) / evals processed in the window."""

import measure


def read(run):
    return measure.ratio(
        measure.delta(run, "nomad.sched.preempt_reentries"),
        measure.evals_in_window(run))
