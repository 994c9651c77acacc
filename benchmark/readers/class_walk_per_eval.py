"""class_walk_per_eval: computed classes visited one by one in Python by a select (nomad.sched.class_walk_total) / evals processed in the window; a program without the counter reads nothing."""

import measure


def read(run):
    return measure.ratio(
        measure.delta(run, "nomad.sched.class_walk_total"),
        measure.evals_in_window(run))
