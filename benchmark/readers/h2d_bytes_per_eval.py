"""h2d_bytes_per_eval: (matrix upload bytes + kernel operand bytes) over the window / evals processed."""

import measure


def read(run):
    up = measure.delta(run, "nomad.matrix.upload_bytes_total")
    op = measure.delta(run, "nomad.kernel.operand_bytes_total")
    if up is None or op is None:
        return None
    return measure.ratio(up + op, measure.evals_in_window(run))
