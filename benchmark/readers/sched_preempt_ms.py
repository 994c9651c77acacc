"""sched_preempt_ms: self time of sched.preempt (the host's victim search and exact score of one preempting pick), per eval processed in the window."""

import measure


def read(run):
    if not measure.span_values_ms(run, "sched.preempt"):
        return None
    return measure.ratio(
        measure.self_time_ms(run, ("sched.preempt",)),
        measure.evals_in_window(run))
