"""gil_wait_ms: how late the runtime probe woke from its 10 ms sleeps over the window, on average (nomad.runtime.wake_late_seconds_total / nomad.runtime.wakes_total), in ms: what a thread pays to get the interpreter back (sys.getswitchinterval() is 5 ms)."""

import measure


def read(run):
    late = measure.delta(run, "nomad.runtime.wake_late_seconds_total")
    return measure.ratio(None if late is None else 1e3 * late,
                         measure.delta(run, "nomad.runtime.wakes_total"))
