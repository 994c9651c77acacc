"""The least work ONE chip of a mesh needs for one node-sharded placement
launch whose lanes carry placement rules.

``roofline_sharded.launch_work`` for the chip's rows and lanes (the layout
from the gauges ``nomad.mesh.*``, the steps from the counter
``nomad.kernel.scan_steps_total``), plus what the rules read there
(``roofline_rules``' per-node terms, for the ``1 / node_shards`` of the rows
the chip holds and the ``1 / batch_shards`` of the live lanes it scores, at
the widths ``roofline_rules.widths`` reads off the traffic file), plus the
class-eligibility table once a lane: ``class_pad`` bools, whatever the node
count (the operand is replicated over the node shards, so every chip reads
its lanes' tables whole).  The memory roof binds; peaks and the share are
``roofline.py``'s.
"""

from __future__ import annotations

from typing import Dict

import roofline_rules as rr
import roofline_sharded


def launch_work(matrix_bytes: float, rows: float, lanes: float, steps: float,
                node_shards: int, batch_shards: int, w: Dict[str, int],
                class_pad: float) -> Dict:
    """Bytes and operations of one chip for ONE launch that ranks ``rows``
    nodes for ``lanes`` live lanes over ``steps`` placement steps at the
    rule widths ``w``, each lane with a class table of ``class_pad``
    entries."""
    work = roofline_sharded.launch_work(
        matrix_bytes, rows, lanes, steps, node_shards, batch_shards)
    cells = (lanes / batch_shards) * (rows / node_shards)  # lane x node
    once = (w["c"] + w["a"]) * rr.SLOT_BYTES + rr.CLASS_BYTES
    per_step = w["s"] * rr.SPREAD_BYTES + w["dp"] * rr.DISTINCT_BYTES
    return {
        "bytes": work["bytes"] + cells * (once + steps * per_step)
        + (lanes / batch_shards) * class_pad,
        "flop": work["flop"] + cells * (
            (w["c"] + w["a"]) * rr.FLOP_PER_PREDICATE
            + steps * (w["s"] * rr.SPREAD_VALUES
                       + w["dp"] * rr.FLOP_PER_DISTINCT)),
    }
