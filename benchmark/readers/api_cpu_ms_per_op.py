"""api_cpu_ms_per_op: CPU of the HTTP server's accept and handler threads over the window (gauge nomad.runtime.cpu_seconds{group=http-api}: registrations, the event stream) / operations placed inside it, in ms."""

import host_cpu


def read(run):
    return host_cpu.ms_per(host_cpu.group_s(run, "http-api"),
                           host_cpu.placed_in_window(run))
