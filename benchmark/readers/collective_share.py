"""collective_share: trace: collective time not hidden by compute / window, in % (several chips only)."""

import measure


def read(run):
    d = run.get("device")
    if not d or d["devices"] < 2:
        return None
    return 100.0 * d["collective_exposed_s"] / d["window_s"]
