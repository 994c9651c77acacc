"""setup_register_s: run.py clock: agent boot, node registration and seeded usage."""

import measure


def read(run):
    return run["setup"].get("register_s")
