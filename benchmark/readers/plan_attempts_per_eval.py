"""plan_attempts_per_eval: plans the applier evaluated / evals processed, over the window."""

import measure


def read(run):
    return measure.ratio(measure.timer_count(run, "nomad.plan.evaluate"),
                         measure.evals_in_window(run))
