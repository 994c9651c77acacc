"""What the runtime does to the program behind its back, and what the
program's threads spend, as spans and pulled numbers:

* full garbage collections (``runtime.gc_pause``) and XLA backend
  compiles (``jit.backend_compile``), as spans;
* the CPU each group of threads has used (``cpu_seconds``: the gauges
  ``nomad.runtime.cpu_seconds{group=}``), read from the per-thread CPU
  clocks only when somebody asks, so that work can be told from waiting;
* a probe thread that asks for nothing but the interpreter: it sleeps
  ``PROBE_PERIOD_S`` and measures by how much it woke late.  That lateness
  is what every worker pays each time it comes back from a lock, the
  device or a socket (``wake_late_seconds_total`` / ``wakes_total``); a
  wake later than ``STALL_THRESHOLD_S`` is filed as ``runtime.stall`` with
  args that tell the causes apart.

Process-wide and reference-counted: ``install()`` when a ``Server``
starts, ``uninstall()`` when it shuts down; the hooks and the probe are in
place while any server runs (tests run several in one process) and gone
after the last one stops.  The totals are the process's and never reset.
"""

from __future__ import annotations

import gc
import os
import re
import resource
import threading
import time
import weakref
from typing import Any, Dict, Optional, Tuple

from . import core

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

PROBE_PERIOD_S = 0.010     # the probe's sleep
STALL_THRESHOLD_S = 0.250  # a wake later than this is a runtime.stall
_REFRESH_S = 1.0           # the probe re-reads the per-thread clocks
_FRESH_S = 0.05            # one /v1/metrics snapshot makes one pass

# The thread groups of ``nomad.runtime.cpu_seconds``: a thread's name with
# trailing digits stripped, if it is one of these, else ``other``.
PYTHON_GROUPS = (
    "worker", "worker-renew", "plan-applier", "device-coalescer",
    "resolver-coalescer", "device-fetch", "http-api", "heartbeat-wheel",
    "slo-observatory", "other",
)
_HANDLER = "(process_request_thread)"  # socketserver's name for a handler
_TRAILING_ID = re.compile(r"[-_ ]?\d+$")

_lock = threading.Lock()
_installed = 0
_gc_t0 = 0.0
_last_gc: Tuple[float, float] = (0.0, 0.0)  # the newest full collection


def _on_gc(phase: str, info: Dict[str, Any]) -> None:
    # Generations 0-1 run thousands of times a second: nothing but this
    # comparison.  A full collection stops every thread for as long as the
    # heap takes to traverse; it is only queued here (core._runtime_spans
    # says why) and becomes a span at the next record.
    if info["generation"] < 2:
        return
    global _gc_t0, _last_gc
    if phase == "start":
        _gc_t0 = time.time()
    elif _gc_t0:
        _last_gc = (_gc_t0, time.time())
        core._runtime_spans.append((
            "runtime.gc_pause", _gc_t0, _last_gc[1],
            {"generation": 2, "collected": info["collected"]},
        ))
        _gc_t0 = 0.0


def _on_duration(event: str, duration: float, **_kw: Any) -> None:
    if event == _COMPILE_EVENT:
        now = time.time()
        core.record_span("jit.backend_compile", now - duration, now)


# ----------------------------------------------------------------------
# CPU by thread group


def group_of(thread_name: str) -> str:
    if _HANDLER in thread_name:
        return "http-api"
    name = _TRAILING_ID.sub("", thread_name)
    return name if name in PYTHON_GROUPS else "other"


def _thread_clock(native_id: int) -> int:
    """The CPU clock of the thread with this kernel id, as Linux lays a
    clock id out (``MAKE_THREAD_CPUCLOCK(tid, CPUCLOCK_SCHED)``): what
    ``pthread_getcpuclockid`` returns, without handing glibc the pthread
    of a thread that may have ended since it was listed (undefined there;
    the kernel answers a stale id with EINVAL)."""
    return ((~native_id) << 3) | 6


def _have_thread_clocks() -> bool:
    try:
        mine = time.pthread_getcpuclockid(threading.get_ident())
        return mine == _thread_clock(threading.get_native_id())
    except (AttributeError, OSError):
        return False


class _ThreadCpu:
    """CPU seconds by thread group, monotone: a thread that has ended
    keeps its last reading in its group's total."""

    def __init__(self) -> None:
        self.available = _have_thread_clocks()
        self._lock = threading.Lock()
        self._live: Dict[threading.Thread, Tuple[str, float]] = {}
        self._ended = dict.fromkeys(PYTHON_GROUPS, 0.0)
        # Threads that added their own reading as they ended.
        self._self_counted: "weakref.WeakSet[threading.Thread]" = (
            weakref.WeakSet())
        self._totals: Dict[str, float] = {}
        self._at = 0.0

    def refresh(self) -> Dict[str, float]:
        with self._lock:
            seen: Dict[threading.Thread, Tuple[str, float]] = {}
            for t in threading.enumerate():
                if t.native_id is None or t in self._self_counted:
                    continue
                try:
                    secs = time.clock_gettime(_thread_clock(t.native_id))
                except OSError:
                    continue  # ended since it was listed
                known = self._live.get(t)
                seen[t] = (known[0] if known else group_of(t.name), secs)
            for t, (group, secs) in self._live.items():
                if t not in seen:  # ended: its last reading stays
                    self._ended[group] += secs
            self._live = seen
            totals = dict(self._ended)
            for group, secs in seen.values():
                totals[group] += secs
            # Last, so that it is no less than the threads' sum.
            totals["process"] = time.process_time()
            # (never falling: the clocks are read microseconds apart)
            totals["native"] = max(
                self._totals.get("native", 0.0),
                totals["process"] - sum(totals[g] for g in PYTHON_GROUPS))
            self._totals, self._at = totals, time.monotonic()
            return totals

    def thread_ended(self, group: str) -> None:
        """The calling thread is about to end: its whole CPU goes to
        ``group`` now (a handler thread lives for one connection, often
        shorter than the probe's refresh)."""
        secs = time.thread_time()
        me = threading.current_thread()
        with self._lock:
            self._live.pop(me, None)
            self._self_counted.add(me)
            self._ended[group] += secs

    def totals(self) -> Dict[str, float]:
        if time.monotonic() - self._at > _FRESH_S:
            return self.refresh()
        return self._totals


_cpu = _ThreadCpu()


def cpu_groups() -> Tuple[str, ...]:
    """Groups ``cpu_seconds`` reads: the Python thread groups, ``process``
    (``time.process_time()``) and ``native`` (the process less its Python
    threads: XLA's, the runtime's and libtpu's own).  Empty where the
    platform has no per-thread CPU clock."""
    if not _cpu.available:
        return ()
    return PYTHON_GROUPS + ("process", "native")


def cpu_seconds(group: str) -> float:
    return _cpu.totals()[group]


def thread_ended(group: str) -> None:
    if _cpu.available:
        _cpu.thread_ended(group)


# ----------------------------------------------------------------------
# Runnable but given no core: /proc/self/task/*/schedstat


class _RunDelay:
    """Seconds the process's threads, native ones included, were runnable
    and waited for a core (the second number of a task's ``schedstat``),
    monotone across threads that end."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._last: Dict[str, int] = {}
        self._ended = 0
        self._absent = False  # no schedstat here (gVisor): never asked again

    def read(self) -> Optional[float]:
        if self._absent:
            return None
        try:
            tids = os.listdir("/proc/self/task")
        except OSError:
            tids = []
        now: Dict[str, int] = {}
        for tid in tids:
            try:
                fd = os.open(f"/proc/self/task/{tid}/schedstat", os.O_RDONLY)
            except OSError:
                continue
            try:
                now[tid] = int(os.read(fd, 96).split()[1])
            except (OSError, IndexError, ValueError):
                continue
            finally:
                os.close(fd)
        if not now:
            self._absent = True
            return None
        with self._lock:
            self._ended += sum(
                ns for tid, ns in self._last.items() if tid not in now)
            self._last = now
            return (self._ended + sum(now.values())) / 1e9


_run_delay = _RunDelay()


def run_delay_seconds() -> Optional[float]:
    return _run_delay.read()


# ----------------------------------------------------------------------
# The probe


class _Probe(threading.Thread):
    def __init__(self) -> None:
        super().__init__(name="runtime-probe", daemon=True)
        self.halt = False
        # What a stall's machine args are deltas from, and when it was read.
        self._base: Tuple[Optional[float], int, int] = (None, 0, 0)
        self._base_at = 0.0

    def run(self) -> None:
        while not self.halt:
            mono = time.monotonic()
            if mono - self._base_at >= _REFRESH_S:
                # Once a second: the per-thread CPU readings (a thread
                # that ends keeps at most a second out of its group) and
                # the base of a stall's deltas.
                if _cpu.available:
                    _cpu.refresh()
                self._base, self._base_at = _machine_counters(), mono
                mono = time.monotonic()
            wall, cpu0 = time.time(), time.process_time()
            time.sleep(PROBE_PERIOD_S)
            self.woke(time.monotonic() - mono - PROBE_PERIOD_S, wall, cpu0)

    def woke(self, late: float, wall: float, cpu0: float) -> None:
        """One wake, ``late`` seconds after it was due; ``wall`` and
        ``cpu0`` are the clock and the process's CPU as the sleep began."""
        global wakes_total, wake_late_seconds_total, stall_seconds_total
        wakes_total += 1
        if late <= 0:
            return
        wake_late_seconds_total += late
        if late < STALL_THRESHOLD_S:
            return
        stall_seconds_total += late
        now = time.time()
        delay, vol, invol = _machine_counters()
        base_delay, base_vol, base_invol = self._base
        args: Dict[str, Any] = {
            "late": late,
            # One thread on a core the whole time reads ``late``: a C call
            # that held the GIL.  Near 0: the process did not run.
            "cpu": time.process_time() - cpu0,
            "vol_switches": vol - base_vol,
            "invol_switches": invol - base_invol,
            # The machine's deltas (switches, run_delay) run from this
            # long before the stall's end, not from its start.
            "since": time.monotonic() - self._base_at,
            # (the probe can get the interpreter inside the hook that
            # ends a collection, before the hook has filed it: _gc_t0)
            "gc_overlap": bool(
                0.0 < _gc_t0 <= now
                or (_last_gc[1] >= wall and _last_gc[0] <= now)),
        }
        if delay is not None and base_delay is not None:
            # Runnable but given no core, summed over every thread.
            args["run_delay"] = delay - base_delay
        core._runtime_spans.append((
            "runtime.stall", wall + PROBE_PERIOD_S, now, args))
        self._base, self._base_at = (delay, vol, invol), time.monotonic()


def _machine_counters() -> Tuple[Optional[float], int, int]:
    ru = resource.getrusage(resource.RUSAGE_SELF)
    return _run_delay.read(), ru.ru_nvcsw, ru.ru_nivcsw


wakes_total = 0
wake_late_seconds_total = 0.0
stall_seconds_total = 0.0
_probe: Optional[_Probe] = None


def install() -> None:
    global _installed, _probe
    with _lock:
        _installed += 1
        if _installed > 1:
            return
        gc.callbacks.append(_on_gc)
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _probe = _Probe()
        _probe.start()


def uninstall() -> None:
    global _installed, _probe
    with _lock:
        if _installed == 0:
            return
        _installed -= 1
        if _installed:
            return
        gc.callbacks.remove(_on_gc)
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(_on_duration)
        probe, _probe = _probe, None
    if probe is not None:
        probe.halt = True
        probe.join(timeout=5.0)
