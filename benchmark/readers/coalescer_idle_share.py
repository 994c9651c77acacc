"""coalescer_idle_share: program span coalescer.idle (dispatch thread: queue empty, nothing to launch) clipped to the window / window, in %."""

import span_window


def read(run):
    return span_window.share_pct(run, "coalescer.idle")
