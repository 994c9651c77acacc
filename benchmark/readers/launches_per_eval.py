"""launches_per_eval: times an eval entered the placement kernel (fused lanes + solo launches: nomad.kernel.fused_lanes, nomad.kernel.launches{path=solo}) / evals processed in the window; 1.0 where every select is one launch, more where a pick was refused on the host (a port taken, no victims) and the eval went round again."""

import measure


def read(run):
    lanes = measure.delta(run, "nomad.kernel.fused_lanes")
    solo = measure.delta(run, "nomad.kernel.launches{path=solo}")
    if lanes is None:
        return None
    return measure.ratio(lanes + (solo or 0.0), measure.evals_in_window(run))
