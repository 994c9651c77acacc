"""kernel_feasibility_share: trace: device time of the placement program's leaf ops under a feasibility scope (at any depth: constraints, datacenters, class and host masks, distinct_property) / of all its leaf ops, in %."""

import glob
import os

import stage_reduce


def read(run):
    if not run.get("device"):
        return None
    files = glob.glob(os.path.join(
        stage_reduce.TRACE_DIR, "plugins", "profile", "*", "*.xplane.pb"))
    if not files:
        return None
    # stage_reduce's own cached reduction: the xplane is parsed once for
    # all the readers of a run.
    scopes = stage_reduce._scopes_of_trace(
        sorted(files)[-1], tuple(run["cfg"]["placement_programs"]))
    total = sum(scopes.values())
    under = [v for k, v in scopes.items() if "feasibility" in k.split("/")]
    if total <= 0 or not under:
        return None
    return 100.0 * sum(under) / total
