"""setup_import_s: run.py clock: process start -> JAX and the program imported."""

import measure


def read(run):
    return run["setup"].get("import_s")
