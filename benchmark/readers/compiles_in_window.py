"""compiles_in_window: jax.monitoring backend compiles inside the window; must read 0."""

import measure


def read(run):
    return float(run["compiles_in_window"])
