"""sharded_rules_place_batch_roofline: one chip's least time for a sharded launch whose lanes carry placement rules (roofline_sharded_rules.launch_work: the sharded launch's bytes and ops, the rules' per-node terms for the chip's rows and lanes, the class table once a lane) at the chip's peaks / kernel time a launch, in %."""

import statistics

import measure
import roofline
import roofline_rules
import roofline_sharded_rules


def read(run):
    d, m1 = run.get("device"), run.get("m1") or {}
    node_shards = m1.get("nomad.mesh.node_shards")
    batch_shards = m1.get("nomad.mesh.batch_shards")
    launches = measure.delta(run, "nomad.kernel.launches{path=fused}")
    lanes = measure.ratio(
        measure.delta(run, "nomad.kernel.fused_lanes"), launches)
    steps = measure.ratio(
        measure.delta(run, "nomad.kernel.scan_steps_total"), launches)
    # The width of the class operand: the span's tag, which a program before
    # this metric does not carry.
    pads = [s["args"]["class_pad"] for s in run.get("spans") or []
            if s["name"] == "sched.feasibility" and "class_pad" in s["args"]]
    ruled = measure.delta(run, "nomad.kernel.distinct_property_lanes_total")
    if (not d or not d["launches"] or not node_shards or not batch_shards
            or lanes is None or steps is None or not pads or not ruled):
        return None
    work = roofline_sharded_rules.launch_work(
        run["matrix_bytes"], run["cfg"]["node_capacity"], lanes, steps,
        int(node_shards), int(batch_shards),
        roofline_rules.widths(run["traffic"]), statistics.median(pads))
    return roofline.roofline_share(
        run["device_kind"], work, d["kernel_s"] / d["launches"])["share_pct"]
