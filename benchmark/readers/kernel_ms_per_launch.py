"""kernel_ms_per_launch: trace: device time of the placement program / its launches."""

import measure


def read(run):
    d = run.get("device")
    return measure.ratio(d["kernel_s"] * 1e3, d["launches"]) if d else None
