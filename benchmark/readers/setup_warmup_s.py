"""setup_warmup_s: run.py clock: warm-up passes until one adds no compile."""

import measure


def read(run):
    return run["setup"].get("warmup_s")
