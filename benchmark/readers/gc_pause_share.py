"""gc_pause_share: program span runtime.gc_pause (full collections, every thread stopped) clipped to the window / window, in %."""

import span_window


def read(run):
    return span_window.share_pct(run, "runtime.gc_pause")
