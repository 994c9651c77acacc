#!/usr/bin/env python3
"""The control of ``correct``: the reference in lower precision, put in the
program's place, has to come out as NOT correct.

    python benchmark/control.py <dump.json> [<dump.json> ...]

Each dump is what ``run.py --check-dump`` wrote after a run on the chip at
the cell's own size: the sampled placement decisions with the states their
nodes can have been in.  The configuration states float32 scores, so the
step that would tempt a later PR is bfloat16: for every sample the score a
bfloat16 kernel would have recorded is computed by reference.py in that
type and compared exactly as the program's own record is.  Prints, per
dump, the sound run's numbers and the control's, and exits 1 if any control
stayed inside the limit.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import check  # noqa: E402


def control_numbers(samples):
    import ml_dtypes

    return check.score_gaps(samples, recorded_dtype=ml_dtypes.bfloat16)


def main(argv) -> int:
    limit = check.LIMITS["score_gap"]
    passed_control = 0
    for path in argv:
        with open(path) as fh:
            d = json.load(fh)
        sound, rank = check.score_gaps(d["samples"])
        ctl, ctl_rank = control_numbers(d["samples"])
        print(f"{os.path.basename(path)}: seed {d['seed']} samples "
              f"{len(d['samples'])}: sound score_gap {sound:.3g} rank_gap "
              f"{rank:.3g} | bfloat16 control score_gap {ctl:.3g} rank_gap "
              f"{ctl_rank:.3g} (limit {limit:g})")
        passed_control += ctl <= limit
    return 1 if passed_control else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
