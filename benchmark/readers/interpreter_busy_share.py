"""interpreter_busy_share: CPU of all Python thread groups (gauges nomad.runtime.cpu_seconds{group=}, process and native left out) over the window / window, in %: near 100 the interpreter is one core's worth busy and the GIL binds (over 100 is possible: numpy and the jitted call run without it)."""

import host_cpu


def read(run):
    return host_cpu.share_pct(host_cpu.python_s(run), run)
