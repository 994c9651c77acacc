"""sched_feasibility_ms: median of the span sched.feasibility: the host's side of feasibility for one select (escaped predicates per distinct value, class vector, host mask)."""

import measure


def read(run):
    return measure.span_median_ms(run, "sched.feasibility")
