"""host_walk_nodes_per_eval: nodes visited one by one in Python on an eval's path (nomad.sched.host_walk_nodes_total) / evals processed in the window; a program without the counter reads nothing."""

import measure


def read(run):
    return measure.ratio(
        measure.delta(run, "nomad.sched.host_walk_nodes_total"),
        measure.evals_in_window(run))
