"""plan_apply_ms: program span plan.apply, median."""

import measure


def read(run):
    return measure.span_median_ms(run, "plan.apply")
