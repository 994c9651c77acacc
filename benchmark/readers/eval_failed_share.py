"""eval_failed_share: evals of the window's jobs that ended failed / evals ended, from the event stream, in %."""

import measure


def read(run):
    return measure.ratio(100.0 * run["client"]["evals_failed"],
                         run["client"]["evals_ended"])
