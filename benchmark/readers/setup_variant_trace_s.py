"""setup_variant_trace_s: program span coalescer.trace_variant (tracing + lowering + compile or cache read of each Features variant) summed up to the window's start."""

import measure


def read(run):
    t = (run.get("m0") or {}).get("nomad.phase.coalescer.trace_variant")
    return t["count"] * t["mean_ms"] / 1e3 if t else None
