"""The least work one placement launch needs when its lanes ask for ports
and devices: ``roofline.launch_work`` plus what those asks read
(nomad_tpu/ops/kernels.py: ``port_mask``, ``device_mask``).

``run.py``'s ``matrix_bytes`` leaves the port bitmap out (4 KB a node: a
launch never reads it whole).  Per node and live lane, once a launch: a
lane with a static-port ask gathers the bitmap's word of each of its
``MAX_STATIC_PORTS`` slots (8 x 4 B), reads the node's count of dynamic
ports in use (4 B) and the device columns, instances a node has and
instances taken, over the ``DEVICE_SLOTS`` slots (8 x 2 x 4 B).  The memory
roof binds.
"""

from __future__ import annotations

from typing import Dict

import roofline

STATIC_PORT_SLOTS = 8      # MAX_STATIC_PORTS: words gathered a node and lane
WORD_BYTES = 4
DYNAMIC_BYTES = 4          # dyn_used, i32
DEVICE_SLOTS = 8           # dev_total and dev_used, i32 each
FLOP_PER_PORT = 3          # shift, and, compare
FLOP_PER_DEVICE = 2        # add, compare


def asks_ports(traffic: Dict) -> bool:
    """Does any shape of the traffic ask for a port: the launches of such
    a mix run the placement program's ``ports`` variant."""
    return any(n.get("reserved_ports") or n.get("dynamic_ports")
               for s in traffic["shapes"] for n in s.get("networks", []))


def launch_work(matrix_bytes: float, rows: float, lanes: float) -> Dict:
    """Bytes and operations of ONE launch that ranks ``rows`` nodes for
    ``lanes`` live lanes whose asks carry ports and devices."""
    work = roofline.launch_work(matrix_bytes, rows, lanes)
    per_node = (STATIC_PORT_SLOTS * WORD_BYTES + DYNAMIC_BYTES
                + DEVICE_SLOTS * 2 * 4)
    return {
        "bytes": work["bytes"] + lanes * rows * per_node,
        "flop": work["flop"] + lanes * rows * (
            STATIC_PORT_SLOTS * FLOP_PER_PORT + 1
            + DEVICE_SLOTS * FLOP_PER_DEVICE),
    }
