"""worker_cpu_ms_per_eval: CPU of the worker threads over the window (gauge nomad.runtime.cpu_seconds{group=worker}) / evals processed in it, in ms: beside sched_host_ms, which is wall clock."""

import host_cpu
import measure


def read(run):
    return host_cpu.ms_per(host_cpu.group_s(run, "worker"),
                           measure.evals_in_window(run))
