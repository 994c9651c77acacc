"""launch_cpu_ms: CPU the dispatch thread used inside program span coalescer.launch (the record's cpu, read from the thread's CPU clock), mean, in ms: beside launch_host_ms, the rest of which is waiting. A mean, not a median: where the kernel's CPU clocks tick (10 ms on the chip machines) one record reads 0 or a tick, and only a sum over many means anything."""

import statistics


def read(run):
    v = [s["cpu"] * 1e3 for s in run.get("spans") or []
         if s["name"] == "coalescer.launch" and "cpu" in s]
    return statistics.fmean(v) if v else None
