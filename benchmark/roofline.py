"""Peaks of the chips, and the least work one placement launch needs.

Peaks (Google Cloud documentation, "TPU v5e" system architecture page):
one v5e chip does 197 TFLOP/s in bf16 and moves 819 GB/s to and from its
16 GB of HBM.  A device kind that is not in the table is an error.
"""

from __future__ import annotations

from typing import Dict

PEAKS = {
    # device_kind: (FLOP/s, HBM bytes/s)
    "TPU v5 lite": (197e12, 819e9),
    "TPU v5e": (197e12, 819e9),
}

PLACEMENTS = 16      # scan length of one launch (PLACEMENT_CHUNK)
RESULT_COLS = 8      # packed result columns per placement
FLOP_PER_NODE = 24   # compare + fit + two exp2 + mean, per node per lane


def launch_work(matrix_bytes: float, rows: int, lanes: float) -> Dict:
    """Bytes and operations the algorithm needs for ONE launch that ranks
    ``rows`` nodes for ``lanes`` live lanes: the resident matrix is read
    once, each lane reads its per-node operands (tg_count i32, penalty and
    host mask bool = 6 bytes a node) and writes its packed result; each
    lane scores every node once and then re-ranks after each of its
    placements (one compare per node per placement)."""
    return {
        "bytes": matrix_bytes + lanes * rows * 6.0
        + lanes * PLACEMENTS * RESULT_COLS * 4.0,
        "flop": lanes * rows * (FLOP_PER_NODE + PLACEMENTS),
    }


def roofline_share(device_kind: str, work: Dict, kernel_s: float) -> Dict:
    """Least time the chip could take over the time it took, in %, and
    which roof binds."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}")
    flops, bw = PEAKS[device_kind]
    t_flop, t_mem = work["flop"] / flops, work["bytes"] / bw
    return {
        "share_pct": 100.0 * max(t_flop, t_mem) / kernel_s,
        "bound": "memory" if t_mem >= t_flop else "compute",
    }
