"""device_idle_share: trace: 1 - union of device op intervals / traced window, in %."""

import measure


def read(run):
    d = run.get("device")
    return 100.0 * (1.0 - d["busy_s"] / d["window_s"]) if d else None
