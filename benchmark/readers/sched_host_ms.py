"""sched_host_ms: self time of sched.encode + sched.feasibility + sched.dispatch, per eval processed in the window."""

import measure


def read(run):
    return measure.ratio(
        measure.self_time_ms(
            run, ("sched.encode", "sched.feasibility", "sched.dispatch")),
        measure.evals_in_window(run))
