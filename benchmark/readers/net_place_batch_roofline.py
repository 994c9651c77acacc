"""net_place_batch_roofline: least time for one launch whose lanes ask for ports and devices (roofline_net.launch_work: the launch's bytes and ops plus the port words gathered, the dynamic count and the device columns, a node and live lane) at the chip's peaks / kernel time, in %; nothing where the traffic asks for no port."""

import measure
import roofline
import roofline_net


def read(run):
    d = run.get("device")
    lanes = measure.ratio(
        measure.delta(run, "nomad.kernel.fused_lanes"),
        measure.delta(run, "nomad.kernel.launches{path=fused}"))
    if (not d or not d["launches"] or lanes is None
            or not roofline_net.asks_ports(run["traffic"])):
        return None
    work = roofline_net.launch_work(
        run["matrix_bytes"] / d["devices"],
        run["cfg"]["node_capacity"] / d["devices"], lanes)
    return roofline.roofline_share(
        run["device_kind"], work, d["kernel_s"] / d["launches"])["share_pct"]
