"""The least work one placement launch needs when its lanes carry placement
rules: ``roofline.launch_work`` plus what the rules read
(nomad_tpu/ops/kernels.py: ``constraint_mask`` and ``affinity_score`` at
``c_width`` / ``a_width`` slots, ``spread_score`` at ``s_width``, the
``distinct_property`` stage, the per-class eligibility gather).

Per node and live lane, once a launch (they do not change from pick to
pick): each constraint or affinity slot reads one value id and one number
of the node's attribute row (2 x 4 B) and compares; the per-class
eligibility reads the node's class id (4 B).  Per node, lane AND step of the
placement loop (they change with every pick): each spread slot reads the
value id (4 B) and matches it against the lane's table of ``SPREAD_VALUES``
values; each distinct_property slot reads the value id and reads and writes
the count the scan carries (3 x 4 B).  The tables themselves (16 values a
slot) are bytes a lane, not a node, and are left out.  The memory roof
binds.

``widths`` reads the slots off the traffic file as the program's
``Features`` ratchet ends up after the warm-up: the widest shape of each
kind, constraints with the driver's own, bucketed to a power of two.
"""

from __future__ import annotations

from typing import Dict

import roofline

SLOT_BYTES = 2 * 4         # a predicate: value id + number, per node
CLASS_BYTES = 4            # the node's computed class id
SPREAD_BYTES = 4           # a spread slot: value id, per node per step
DISTINCT_BYTES = 3 * 4     # value id; count read and written
SPREAD_VALUES = 16         # MAX_SPREAD_VALUES: compares a node a slot a step
FLOP_PER_PREDICATE = 4     # present, compare, select, and
FLOP_PER_DISTINCT = 4      # two compares, or, and; and the add at a pick


def _pow2(n: int) -> int:
    return 1 << max(0, n - 1).bit_length() if n else 0


def widths(traffic: Dict) -> Dict[str, int]:
    """Slots of each kind the launches of this traffic run at."""
    shapes = traffic["shapes"]

    def kinds(shape):
        ops = [c["operand"] for c in shape.get("constraints", [])]
        distinct = sum(op == "distinct_property" for op in ops)
        plain = sum(op not in ("distinct_hosts", "distinct_property")
                    for op in ops)
        return plain + 1, distinct  # + the task driver's constraint

    return {
        "c": min(16, _pow2(max(kinds(s)[0] for s in shapes))),
        "a": min(8, _pow2(max(len(s.get("affinities", [])) for s in shapes))),
        "s": min(2, max(len(s.get("spreads", [])) for s in shapes)),
        "dp": min(2, max(kinds(s)[1] for s in shapes)),
    }


def launch_work(matrix_bytes: float, rows: float, lanes: float, steps: float,
                w: Dict[str, int]) -> Dict:
    """Bytes and operations of ONE launch that ranks ``rows`` nodes for
    ``lanes`` live lanes over ``steps`` steps of the placement loop at the
    widths ``w``."""
    work = roofline.launch_work(matrix_bytes, rows, lanes)
    once = (w["c"] + w["a"]) * SLOT_BYTES + CLASS_BYTES
    per_step = w["s"] * SPREAD_BYTES + w["dp"] * DISTINCT_BYTES
    return {
        "bytes": work["bytes"] + lanes * rows * (once + steps * per_step),
        "flop": work["flop"] + lanes * rows * (
            (w["c"] + w["a"]) * FLOP_PER_PREDICATE
            + steps * (w["s"] * SPREAD_VALUES + w["dp"] * FLOP_PER_DISTINCT)),
    }
