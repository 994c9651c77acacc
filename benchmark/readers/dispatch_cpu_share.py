"""dispatch_cpu_share: CPU of the one launching thread over the window (gauge nomad.runtime.cpu_seconds{group=device-coalescer}) / window, in %: beside 100 - coalescer_idle_share, how much of "never idle" is waiting."""

import host_cpu


def read(run):
    return host_cpu.share_pct(host_cpu.group_s(run, "device-coalescer"), run)
