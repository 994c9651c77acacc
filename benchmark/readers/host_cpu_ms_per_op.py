"""host_cpu_ms_per_op: CPU the whole server process used over the window (gauge nomad.runtime.cpu_seconds{group=process}) / operations placed inside it, in ms: the cost of a placed job."""

import host_cpu


def read(run):
    return host_cpu.ms_per(host_cpu.group_s(run, "process"),
                           host_cpu.placed_in_window(run))
