"""fused_place_batch_roofline: least time for the launch's bytes and ops at the chip's peaks / kernel time, in %."""

import measure
import roofline


def read(run):
    d = run.get("device")
    lanes = measure.ratio(
        measure.delta(run, "nomad.kernel.fused_lanes"),
        measure.delta(run, "nomad.kernel.launches{path=fused}"))
    if not d or not d["launches"] or lanes is None:
        return None
    work = roofline.launch_work(
        run["matrix_bytes"] / d["devices"],
        run["cfg"]["node_capacity"] / d["devices"], lanes)
    return roofline.roofline_share(
        run["device_kind"], work, d["kernel_s"] / d["launches"])["share_pct"]
