"""idle_while_launching_share: trace: of the first device's idle time in the traced slice, the part overlapped by the dispatch thread's annotated span coalescer.launch in the host plane, in %: idle while a launch is prepared, against idle with nothing to launch."""

import idle_overlap


def read(run):
    return idle_overlap.share_pct(run)
