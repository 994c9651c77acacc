"""In-flight claims ledger — what launches in flight tell one another.

A launch scores and resolves against the committed matrix plus the claims
of its own lanes (``claims0`` in ``ops/kernels.py``'s placement body).  The picks of the
launch before it, whose plans are still being built, queued or applied, are
in neither: two launches in flight named the same nodes and the applier
refused the second plan (a third of a closed loop's plans, PERF.md section
6, PR 38).  This ledger holds those undecided picks, keyed by the eval that
made them, and hands every launch the live ones as one small operand (the
**overlay**: rows and their (cpu, mem, disk)) that the placement program
adds to its claims image and to its verify pass, and to nothing else.

Life of an entry:

* **open / close** — a worker brackets an eval's processing.  Only an open
  eval can hold claims, and ``close`` (the worker's ``finally``) drops
  whatever it still holds: an eval that is nacked, fails or raises leaves
  nothing behind, and neither does a caller no worker brackets (a dry run).
* **register** — the resolver thread, as a launch's result reaches the
  host: the eval's whole proposed usage (what its plan held before the
  launch, evictions not credited, plus the launch's picks).  It replaces
  what the eval held before.
* **refuse** — the plan was refused whole (or never reached a verdict): the
  entry goes at once.
* **commit** — the applier committed the plan, in whole or in part, and
  says at which ``matrix.version`` the commit was complete and which rows
  it refused.  Those rows go at once; the rest stay until the first launch
  whose synced snapshot holds the commit (``overlay(version, ...)`` with
  ``version`` at or past it), so no launch counts a placement twice (in the
  ledger and in the matrix) and none misses one between its sync and its
  read of the ledger.
* a launch leaves out the entries of its own lanes' evals: a lane carries
  that usage itself, as its ``delta_rows``.

**Launches whose result is not on the host yet** are in no entry: the
resolver registers a launch's lanes when its packed result arrives, and a
launch enqueued before that (one in eight on one chip, one in two on four:
PERF.md section 6, PR 41) used to find its predecessor's picks nowhere.
Those picks ride from launch to launch in a device buffer instead
(``ops/kernels.py::claims_block``: every launch writes, lane for lane, what
the resolver will register, and hands the next launch its own block and the
``CHAIN_DEPTH - 1`` before it).  The ledger says which carried blocks count:
a ``Launch`` is the chain's record of one launch, ``register_launch`` enters
all its lanes and marks it resolved in one step, and ``overlay`` decides
under the same lock which carried blocks are **live** (launch not resolved)
and reads the entries, leaving out the evals of a live block's lanes (the
block carries their whole proposed usage).  So a pick is in the overlay or
in a live block, never in both and never in neither.  What the chain still
cannot see: a launch older than ``CHAIN_DEPTH`` launches that is still
unresolved (``chain_overflow`` counts them), and one that took another
route than the launch reading it (the numpy twin while the breaker is open,
another mesh after an evacuation, rows of another layout).

Advisory, like the kernel's VERIFIED column: the serialized applier
verifies every plan against committed state, whatever the ledger held.  A
departure from the reference, whose workers never see each other's plans.
"""

from __future__ import annotations

import threading
from typing import Dict, Iterable, List, NamedTuple, Sequence, Tuple

import numpy as np

# Rows of one launch's overlay operand (static: the program's shapes are):
# 16 workers x up to 8 placements a plan in the served mixes.  When the
# ledger holds more, the newest go and ``truncated`` counts the rest.
OVERLAY_ROWS = 256

# Launches whose claims block a launch carries to the next one.  Measured
# (PERF.md section 6, PR 41: eight chip runs, all four cells): a launch is
# enqueued with one predecessor unresolved on 8-18 % of launches on one chip
# and 50 % on four, and never with two (``nomad.coalescer.chain_overflow``
# 0 in every run), so one block is used and the second is slack for a
# machine whose fetch is slower.  A block costs the program 0.03 ms on a v5e
# whether live or not (64 lanes x 48 rows of its claims scatter), so the
# depth is kept near what is used; ``chain_overflow`` says when to deepen.
CHAIN_DEPTH = 2

EVENTS = (
    "registered", "released_committed", "released_refused",
    "dropped_reentry", "truncated",
)


class _Entry(NamedTuple):
    seq: int  # registration order: the newest rows win the operand
    layout: int  # matrix.version at registration (rows are of that layout)
    rows: np.ndarray  # (k,) i32
    vals: np.ndarray  # (k, 3) f32


def empty_carry(lanes: int, width: int) -> np.ndarray:
    """A carry of ``CHAIN_DEPTH`` blocks of padding ((D, lanes, width, 4)
    f32: row -1, no claim): what a chain starts from."""
    carry = np.zeros((CHAIN_DEPTH, lanes, width, 4), np.float32)
    carry[..., 0] = -1.0
    return carry


class Launch:
    """The chain's record of one placement launch: the evals of its lanes,
    the layout its rows are of (``matrix.version`` of its snapshot), whether
    the carry still holds its block (``block``: the launching thread's), and
    whether its result has been entered (``resolved``) and how many later
    launches took its block live (``carried``): both written under the
    ledger's lock."""

    __slots__ = ("evals", "layout", "block", "resolved", "carried")

    def __init__(self, evals: Iterable[str], layout: int) -> None:
        self.evals = tuple(evals)
        self.layout = layout
        self.block = True
        self.resolved = False
        self.carried = 0


def _entry(rows, vals, layout: int) -> _Entry:
    return _Entry(
        0, layout, np.asarray(rows, np.int32),
        np.asarray(vals, np.float32).reshape(-1, 3),
    )


_NO_ROWS = np.zeros((0,), np.int32)
_NO_VALS = np.zeros((0, 3), np.float32)


class ClaimsLedger:
    """The coalescer's ledger of undecided picks.  Every method is a few
    dictionary operations under one leaf lock (nothing is called while it
    is held).  ``counts`` holds rows by event: ``registered`` =
    ``released_committed`` + ``released_refused`` + ``dropped_reentry`` +
    the rows held now."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._open: set = set()
        self._live: Dict[str, _Entry] = {}
        # (matrix.version the commit was complete at, entry), oldest first.
        self._committed: List[Tuple[int, _Entry]] = []
        self._seq = 0
        self.counts: Dict[str, int] = dict.fromkeys(EVENTS, 0)

    # -- the worker's bracket ----------------------------------------------

    def open(self, eval_id: str) -> None:
        with self._lock:
            self._open.add(eval_id)

    def close(self, eval_id: str) -> None:
        with self._lock:
            self._open.discard(eval_id)
            self._drop_live(eval_id, "released_refused")

    # -- resolver ----------------------------------------------------------

    def register(self, eval_id: str, rows, vals, layout: int) -> None:
        """``eval_id``'s whole proposed usage, replacing what it held."""
        entry = _entry(rows, vals, layout)
        with self._lock:
            self._register(eval_id, entry)

    def register_launch(self, launch: Launch, lanes=()) -> int:
        """A launch's result is on the host: enter its ``lanes`` ((eval,
        rows, vals) each) and mark it resolved, in one step, so that a
        launch being enqueued finds these picks here or in the launch's
        carried block and never in both.  With no lanes (the launch failed,
        wedged or was abandoned) it only stops the block from counting.
        Returns how many launches took the block live."""
        entries = [
            (eval_id, _entry(rows, vals, launch.layout))
            for eval_id, rows, vals in lanes
        ]
        with self._lock:
            for eval_id, entry in entries:
                self._register(eval_id, entry)
            launch.resolved = True
            return launch.carried

    def _register(self, eval_id: str, entry: _Entry) -> None:
        self._drop_live(eval_id, "dropped_reentry")
        if eval_id not in self._open or not len(entry.rows):
            return
        self._seq += 1
        self._live[eval_id] = entry._replace(seq=self._seq)
        self.counts["registered"] += len(entry.rows)

    # -- applier -----------------------------------------------------------

    def refuse(self, eval_id: str) -> None:
        with self._lock:
            self._drop_live(eval_id, "released_refused")

    def commit(self, eval_id: str, version: int,
               refused_rows: Iterable[int] = ()) -> None:
        with self._lock:
            entry = self._live.pop(eval_id, None)
            if entry is None:
                return
            refused_rows = list(refused_rows)
            if refused_rows:
                kept = ~np.isin(entry.rows, refused_rows)
                self.counts["released_refused"] += int((~kept).sum())
                entry = entry._replace(
                    rows=entry.rows[kept], vals=entry.vals[kept]
                )
            if len(entry.rows):
                self._committed.append((version, entry))

    # -- launching thread --------------------------------------------------

    def overlay(self, version: int, lane_evals: Iterable[str] = (),
                stale_before: int = 0, chain: Sequence[Launch] = (),
                ) -> Tuple[np.ndarray, np.ndarray, Tuple[bool, ...]]:
        """The operand of a launch whose synced snapshot holds the matrix
        up to ``version``: (rows (k,) i32, vals (k, 3) f32), k <=
        ``OVERLAY_ROWS``, and which of the ``chain``'s launches' carried
        blocks are live (not resolved, still in the carry, rows of this
        layout), decided in the same step: the entries of a live block's
        evals are left out, as those of ``lane_evals`` (the evals of the
        launch's own lanes) are.  Committed entries the snapshot holds are
        released here.  ``stale_before``: the version of the matrix's last
        row relocation (entries registered before it name rows of another
        layout and go)."""
        with self._lock:
            live = tuple(
                not launch.resolved and launch.block
                and launch.layout >= stale_before
                for launch in chain
            )
            kept = []
            for at, entry in self._committed:
                if at <= version or entry.layout < stale_before:
                    self.counts["released_committed"] += len(entry.rows)
                else:
                    kept.append((at, entry))
            self._committed = kept
            for eval_id in [
                e for e, entry in self._live.items()
                if entry.layout < stale_before
            ]:
                self._drop_live(eval_id, "released_refused")
            skip = set(lane_evals)
            for launch, on in zip(chain, live):
                if on:
                    launch.carried += 1
                    skip.update(launch.evals)
            entries = [e for _, e in kept] + [
                e for eval_id, e in self._live.items() if eval_id not in skip
            ]
        if not entries:
            return _NO_ROWS, _NO_VALS, live
        entries.sort(key=lambda e: e.seq)
        rows = np.concatenate([e.rows for e in entries])
        vals = np.concatenate([e.vals for e in entries])
        if len(rows) > OVERLAY_ROWS:
            self.counts["truncated"] += len(rows) - OVERLAY_ROWS
            rows, vals = rows[-OVERLAY_ROWS:], vals[-OVERLAY_ROWS:]
        return rows, vals, live

    # -- reading -----------------------------------------------------------

    def held_rows(self) -> int:
        """Rows the ledger holds now (live and committed-not-yet-seen)."""
        with self._lock:
            return sum(len(e.rows) for e in self._live.values()) + sum(
                len(e.rows) for _, e in self._committed
            )

    def _drop_live(self, eval_id: str, event: str) -> None:
        entry = self._live.pop(eval_id, None)
        if entry is not None:
            self.counts[event] += len(entry.rows)
