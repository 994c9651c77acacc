"""The least work one placement launch of the ``preempt`` variant needs:
``roofline.launch_work`` plus what the kernel's ``preemption`` stage reads
(nomad_tpu/ops/kernels.py, ``preemption_state`` and the ``preemption``
scope of ``score_nodes``).

The stage builds its prefix tables once a launch from ``prio_used``
(16 buckets x 3 dimensions x 4 B a node, written once and read back), and
every live lane reads one row of each table a node in every step of the
placement loop: the freeable usage (3 x 4 B) and the two priority sums
(4 B each).  Per node, lane and step it then takes the deficit, compares
it with what can be freed, and scores the utilisation after the least
eviction (ScoreFit again: two ``exp2``) and the logistic of the net
priority: ``FLOP_PER_NODE_STEP``.  The memory roof binds.
"""

from __future__ import annotations

from typing import Dict

import roofline

PRIORITY_BUCKETS = 16     # nomad_tpu/state/matrix.py
TABLE_BYTES = PRIORITY_BUCKETS * 3 * 4   # the prefix sums of one node
ROW_BYTES = 3 * 4 + 4 + 4  # one row of each table: freeable, max, sum
FLOP_PER_NODE_STEP = 30   # deficit, compare, min, ScoreFit, logistic


def launch_work(matrix_bytes: float, rows: float, lanes: float,
                steps: float) -> Dict:
    """Bytes and operations of ONE launch that ranks ``rows`` nodes for
    ``lanes`` live lanes over ``steps`` steps of the placement loop with
    preemption on."""
    work = roofline.launch_work(matrix_bytes, rows, lanes)
    return {
        "bytes": work["bytes"] + rows * TABLE_BYTES
        + lanes * steps * rows * ROW_BYTES,
        "flop": work["flop"] + lanes * steps * rows * FLOP_PER_NODE_STEP,
    }
