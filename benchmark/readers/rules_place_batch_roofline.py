"""rules_place_batch_roofline: least time for one launch whose lanes carry placement rules (roofline_rules.launch_work: the launch's bytes and ops plus the attribute columns, the distinct_property stage and the class gather) at the chip's peaks / kernel time, in %."""

import measure
import roofline
import roofline_rules


def read(run):
    d = run.get("device")
    launches = measure.delta(run, "nomad.kernel.launches{path=fused}")
    lanes = measure.ratio(
        measure.delta(run, "nomad.kernel.fused_lanes"), launches)
    steps = measure.ratio(
        measure.delta(run, "nomad.kernel.scan_steps_total"), launches)
    # A program without the stage in its scan has no such counter.
    ruled = measure.delta(run, "nomad.kernel.distinct_property_lanes_total")
    if (not d or not d["launches"] or lanes is None or steps is None
            or not ruled):
        return None
    work = roofline_rules.launch_work(
        run["matrix_bytes"] / d["devices"],
        run["cfg"]["node_capacity"] / d["devices"], lanes, steps,
        roofline_rules.widths(run["traffic"]))
    return roofline.roofline_share(
        run["device_kind"], work, d["kernel_s"] / d["launches"])["share_pct"]
