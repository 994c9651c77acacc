"""peak_hbm_bytes: memory_stats()['peak_bytes_in_use'] of the fullest chip."""

import measure


def read(run):
    return run["memory_peak_bytes"]
