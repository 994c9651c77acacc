"""preempt_place_batch_roofline: least time for one launch of the preempt variant (roofline_preempt.launch_work: the launch's bytes and ops plus the preemption stage's tables) at the chip's peaks / kernel time, in %."""

import measure
import roofline
import roofline_preempt


def read(run):
    d = run.get("device")
    launches = measure.delta(run, "nomad.kernel.launches{path=fused}")
    lanes = measure.ratio(
        measure.delta(run, "nomad.kernel.fused_lanes"), launches)
    steps = measure.ratio(
        measure.delta(run, "nomad.kernel.scan_steps_total"), launches)
    picks = measure.delta(run, "nomad.kernel.preempt_picks_total")
    if (not d or not d["launches"] or lanes is None or steps is None
            or not picks):
        return None
    work = roofline_preempt.launch_work(
        run["matrix_bytes"] / d["devices"],
        run["cfg"]["node_capacity"] / d["devices"], lanes, steps)
    return roofline.roofline_share(
        run["device_kind"], work, d["kernel_s"] / d["launches"])["share_pct"]
