"""What the runtime does to the program behind its back, as spans: full
garbage collections (``runtime.gc_pause``) and XLA backend compiles
(``jit.backend_compile``).

Process-wide and reference-counted: ``install()`` when a ``Server``
starts, ``uninstall()`` when it shuts down; the hooks are in place while
any server runs (tests run several in one process) and gone after the
last one stops.
"""

from __future__ import annotations

import gc
import threading
import time
from typing import Any, Dict

from . import core

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_lock = threading.Lock()
_installed = 0
_gc_t0 = 0.0


def _on_gc(phase: str, info: Dict[str, Any]) -> None:
    # Generations 0-1 run thousands of times a second: nothing but this
    # comparison.  A full collection stops every thread for as long as the
    # heap takes to traverse; it is only queued here (core._gc_pauses says
    # why) and becomes a span at the next record.
    if info["generation"] < 2:
        return
    global _gc_t0
    if phase == "start":
        _gc_t0 = time.time()
    elif _gc_t0:
        core._gc_pauses.append((_gc_t0, time.time(), info["collected"]))
        _gc_t0 = 0.0


def _on_duration(event: str, duration: float, **_kw: Any) -> None:
    if event == _COMPILE_EVENT:
        now = time.time()
        core.record_span("jit.backend_compile", now - duration, now)


def install() -> None:
    global _installed
    with _lock:
        _installed += 1
        if _installed > 1:
            return
        gc.callbacks.append(_on_gc)
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(_on_duration)


def uninstall() -> None:
    global _installed
    with _lock:
        if _installed == 0:
            return
        _installed -= 1
        if _installed:
            return
        gc.callbacks.remove(_on_gc)
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(_on_duration)
