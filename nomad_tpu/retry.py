"""Shared retry/backoff policy — the one recovery path for every seam.

Reference: the reference wraps each cross-component call in its own
retry discipline (``client/servers/manager.go`` server rotation,
``client/client.go:1550`` registerAndHeartbeat's ``retryIntv``/
``noServersErr`` backoff, raft's per-peer pipeline backoff).  This build
had the same logic hand-rolled at each seam — fixed ``time.sleep``
constants that chaos testing cannot reason about.  This module replaces
them all: a declarative :class:`RetryPolicy` (jittered exponential
backoff + hard deadline + attempt cap + per-attempt timeout), a stateful
:class:`Backoff` for long-lived loops that recover in place (heartbeat,
watch), and :func:`retry_call` for bounded call-until-success paths
(RPC failover, register, sidecar boot).

Every seam the chaos layer (``nomad_tpu/chaos``) can break routes its
recovery through here, so fault scenarios exercise one policy surface
instead of N copies of ``while True: sleep``.
"""

from __future__ import annotations

import os
import random
import threading
import time
from dataclasses import dataclass
from typing import Callable, Optional, Tuple, Type


def env_int(name: str, default: int) -> int:
    """Tolerant integer env knob: unset, empty, or unparsable → default.
    The one parser for every ``NOMAD_TPU_*`` tuning variable,
    so tools and product code agree on the failure mode (a typo'd knob
    degrades to the default instead of crashing an agent at import)."""
    raw = os.environ.get(name, "")
    try:
        return int(raw) if raw else default
    except ValueError:
        return default


def env_float(name: str, default: float) -> float:
    """Tolerant float env knob — see :func:`env_int`."""
    raw = os.environ.get(name, "")
    try:
        return float(raw) if raw else default
    except ValueError:
        return default


def env_defaults(**pairs: str) -> None:
    """``os.environ.setdefault`` for several knobs at once — the shared
    rig-setup helper for tools that must pin env before jax imports
    (tools/chaos_repro.py; tests/conftest.py force-sets instead)."""
    for name, value in pairs.items():
        os.environ.setdefault(name, value)


@dataclass(frozen=True)
class RetryPolicy:
    """Declarative backoff shape.

    ``base_delay`` grows by ``multiplier`` per failed attempt, capped at
    ``max_delay``; each sleep is jittered by ±``jitter`` fraction so herds
    of retriers decorrelate (heartbeat.go:93 applies the same jitter to
    TTLs).  ``deadline`` is a hard wall-clock budget from the first
    attempt; ``max_attempts`` a hard attempt cap; ``attempt_timeout`` the
    per-attempt I/O timeout callers should pass to the underlying call
    (the policy carries it so seam code has one source of truth).
    """

    base_delay: float = 0.05
    max_delay: float = 2.0
    multiplier: float = 2.0
    jitter: float = 0.25
    max_attempts: Optional[int] = None
    deadline: Optional[float] = None
    attempt_timeout: Optional[float] = None


class Backoff:
    """Stateful delay generator for long-lived recovery loops.

    ``next_delay()`` advances the exponential schedule; ``reset()`` snaps
    back to ``base_delay`` on success.  Thread-compatible: each loop owns
    its instance (a shared instance would interleave schedules).
    """

    def __init__(self, policy: RetryPolicy, rng: Optional[random.Random] = None):
        self.policy = policy
        self._rng = rng or random
        self._attempt = 0

    @property
    def attempt(self) -> int:
        return self._attempt

    def reset(self) -> None:
        self._attempt = 0

    def next_delay(self) -> float:
        p = self.policy
        raw = min(p.base_delay * (p.multiplier ** self._attempt), p.max_delay)
        self._attempt += 1
        if p.jitter:
            raw *= 1.0 + p.jitter * (2.0 * self._rng.random() - 1.0)
        return max(0.0, raw)


class RetryBudgetExceeded(Exception):
    """The policy's deadline or attempt cap ran out; ``__cause__`` carries
    the last underlying error."""


def retry_call(
    fn: Callable,
    policy: Optional[RetryPolicy] = None,
    retry_on: Tuple[Type[BaseException], ...] = (Exception,),
    stop: Optional[threading.Event] = None,
    on_retry: Optional[Callable[[int, BaseException, float], None]] = None,
    description: str = "",
):
    """Call ``fn()`` until it succeeds or the policy's budget runs out.

    - retries only exceptions in ``retry_on``; anything else propagates
    - raises :class:`RetryBudgetExceeded` (chained to the last error)
      when ``max_attempts`` or ``deadline`` is exhausted
    - ``stop`` aborts the wait early (agent shutdown) — the last error
      is re-raised so callers see a real failure, not a silent None
    - ``on_retry(attempt, exc, delay)`` observes each scheduled retry
    """
    pol = policy or RetryPolicy()
    backoff = Backoff(pol)
    start = time.monotonic()
    attempt = 0
    while True:
        attempt += 1
        try:
            return fn()
        except retry_on as exc:
            out_of_attempts = (
                pol.max_attempts is not None and attempt >= pol.max_attempts
            )
            delay = backoff.next_delay()
            out_of_time = (
                pol.deadline is not None
                and time.monotonic() - start + delay > pol.deadline
            )
            if out_of_attempts or out_of_time:
                raise RetryBudgetExceeded(
                    f"{description or getattr(fn, '__name__', 'call')}: "
                    f"gave up after {attempt} attempt(s) "
                    f"({'attempt cap' if out_of_attempts else 'deadline'})"
                ) from exc
            if on_retry is not None:
                on_retry(attempt, exc, delay)
            if stop is not None:
                if stop.wait(timeout=delay):
                    raise exc
            else:
                time.sleep(delay)
