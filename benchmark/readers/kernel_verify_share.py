"""kernel_verify_share: trace: device time of the placement program's leaf ops under the verify_scan scope / of all its leaf ops, in %."""

import stage_reduce


def read(run):
    return stage_reduce.scope_share_pct(run, "verify_scan")
