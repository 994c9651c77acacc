"""Eval-lifecycle tracing: spans, flight recorder, exporters.

See OBSERVABILITY.md for the span taxonomy and knob reference.
"""

from .core import (
    PHASE_PREFIX,
    FlightRecorder,
    SpanContext,
    add_args,
    clear,
    config,
    configure,
    count,
    current,
    dump,
    event,
    record_span,
    recorder,
    set_default_metrics,
    span,
    start_trace,
    traces_by_id,
)
from .export import auto_dump, chrome_trace, dump_flight_record, trace_dir

__all__ = [
    "PHASE_PREFIX",
    "FlightRecorder",
    "SpanContext",
    "add_args",
    "auto_dump",
    "chrome_trace",
    "clear",
    "config",
    "configure",
    "count",
    "current",
    "dump",
    "dump_flight_record",
    "event",
    "record_span",
    "recorder",
    "set_default_metrics",
    "span",
    "start_trace",
    "trace_dir",
    "traces_by_id",
]
