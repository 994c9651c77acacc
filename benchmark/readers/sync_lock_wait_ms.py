"""sync_lock_wait_ms: time program span coalescer.sync was blocked acquiring DEVICE_LOCK and the matrix's host lock (the span's lock_wait arg), median, in ms: beside matrix_sync_ms."""

import statistics


def read(run):
    v = [s["args"]["lock_wait"] * 1e3 for s in run.get("spans") or []
         if s["name"] == "coalescer.sync" and "lock_wait" in s["args"]]
    return statistics.median(v) if v else None
