"""applier_cpu_share: CPU of the serialized plan applier over the window (gauge nomad.runtime.cpu_seconds{group=plan-applier}) / window, in %."""

import host_cpu


def read(run):
    return host_cpu.share_pct(host_cpu.group_s(run, "plan-applier"), run)
