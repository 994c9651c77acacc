"""Device-resident cluster matrix — the core TPU-native data structure.

The reference walks Go node objects per evaluation (BinPackIterator,
scheduler/rank.go:149-531) and bounds work via node sampling
(scheduler/stack.go:78-91) and a computed-class feasibility cache
(scheduler/feasible.go:1029). This framework inverts that design: the whole
cluster is encoded once into dense arrays resident in TPU HBM, and every
evaluation scores *all* nodes in one vectorized pass.

Encoding:
  totals    (N, 3) f32  — comparable resources (total − reserved): cpu/mem/disk
  used      (N, 3) f32  — sum over non-terminal allocs per node
  eligible  (N,)   bool — ready & eligible & not draining
  attr_hash (N, A) i32  — stable nonzero hash per registered attribute slot
                           (0 = attribute unset)
  attr_num  (N, A) f32  — numeric value of the attribute (NaN if non-numeric)
  attr_ver  (N, A) f32  — version packing major*1e6+minor*1e3+patch (NaN none)
  class_id  (N,)   i32  — computed-class id (reference: node_class.go:28-37);
                           host-side fallback constraint checks are evaluated
                           once per class and gathered per node
  dev_total (N, D) i32  — device instances per registered device-type slot
  dev_used  (N, D) i32
  prio_used (N, P, 3) f32 — per-priority-bucket resource usage, enabling the
                           vectorized preemption search (a prefix-sum over the
                           priority axis replaces the reference's greedy
                           candidate walk, scheduler/preemption.go:198-557)
  tg_count  (N,)   i32  — allocs of the *current* job+TG per node (scattered
                           before each eval batch; drives JobAntiAffinity)

Host-side, a mirror lives in numpy; mutations mark dirty rows and `sync()`
scatters only those rows to the device (SURVEY.md §7 hard-part a: bound
host↔device transfer per plan).
"""

from __future__ import annotations

import math
import threading
import time
import zlib
from contextlib import contextmanager
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from ..structs.types import Allocation, Node

# All device interactions funnel through this lock: one thread at a time
# syncs the matrix and launches kernels, so a dispatch always reads the
# snapshot its own sync produced. There is one chip per scheduler process,
# so serializing kernel dispatch costs nothing. Reentrant so sync() nests
# inside a locked select().
DEVICE_LOCK = threading.RLock()

# Fixed encoding widths. Attribute slots beyond ATTR_SLOTS fall back to
# host-side per-class evaluation (the reference's own escape hatch).
ATTR_SLOTS = 32
DEVICE_SLOTS = 8
PRIORITY_BUCKETS = 16  # job priorities 1..100 bucketed by 100/PRIORITY_BUCKETS
RESOURCE_DIMS = 3  # cpu, mem, disk

# Port occupancy encoding (NetworkIndex equivalent, structs/network.go:35):
# one bit per port in [0, PORT_BITS) as uint32 words — matrix columns the
# kernel reads to mask static-port collisions; ports beyond PORT_BITS are
# host-checked only (rare). Dynamic allocation draws from
# [MIN_DYNAMIC_PORT, MAX_DYNAMIC_PORT] (structs/network.go port range).
PORT_WORDS = 1024
PORT_BITS = PORT_WORDS * 32  # 32768
MIN_DYNAMIC_PORT = 20000
MAX_DYNAMIC_PORT = 32000
DYN_PORT_CAPACITY = MAX_DYNAMIC_PORT - MIN_DYNAMIC_PORT + 1


def stable_hash(value: str) -> int:
    """Stable nonzero 31-bit hash of a string attribute value."""
    h = zlib.crc32(value.encode("utf-8")) & 0x7FFFFFFF
    return h if h != 0 else 1


def numeric_value(value: str) -> float:
    """Plain numeric interpretation of an attribute value, NaN otherwise.
    Used for ordered comparisons (``<``, ``>=``, …)."""
    try:
        return float(value)
    except (TypeError, ValueError):
        return math.nan


def version_value(value: str) -> float:
    """Version interpretation: 1-3 dot-separated integer components packed as
    major*1e6 + minor*1e3 + patch (missing components are 0); NaN otherwise.

    Kept separate from :func:`numeric_value` because strings like ``"2.0"``
    are both a valid decimal and a valid version — ``version``-operand
    comparisons read this column, ordered numeric comparisons read the plain
    one, and both sides of a comparison always use the same encoding.
    """
    if not isinstance(value, str):
        return math.nan
    v = value.strip()
    if v.startswith("v"):
        v = v[1:]
    parts = v.split(".")
    if not 1 <= len(parts) <= 3:
        return math.nan
    try:
        nums = [int(p) for p in parts]
    except ValueError:
        return math.nan
    while len(nums) < 3:
        nums.append(0)
    major, minor, patch = nums
    if minor >= 1000 or patch >= 1000 or major < 0 or minor < 0 or patch < 0:
        return math.nan
    return major * 1e6 + minor * 1e3 + patch


def priority_bucket(priority: int) -> int:
    """Map a job priority (1..100) to a preemption bucket."""
    p = min(max(int(priority), 0), 100)
    return min(p * PRIORITY_BUCKETS // 101, PRIORITY_BUCKETS - 1)


# Attributes excluded from the computed class because they are node-unique
# (reference: nomad/structs/node_class.go EscapedConstraints / unique prefix).
UNIQUE_PREFIX = "unique."


class AttributeRegistry:
    """Maps attribute names to matrix column slots.

    Well-known scheduling attributes are pre-registered so every cluster gets
    identical encodings; fingerprinted attributes claim remaining slots on
    first sight. Constraints on unregistered attributes escape to the
    host-side per-class path.
    """

    WELL_KNOWN = [
        "node.datacenter",
        "node.class",
        "node.unique.name",
        "node.unique.id",
        "kernel.name",
        "cpu.arch",
        "cpu.numcores",
        "os.name",
        "os.version",
        "driver.mock",
        "driver.exec",
        "driver.raw_exec",
        "driver.docker",
        "driver.java",
        "driver.qemu",
        "platform.tpu.type",
    ]

    def __init__(self, slots: int = ATTR_SLOTS):
        self.slots = slots
        self.slot_of: Dict[str, int] = {}
        for name in self.WELL_KNOWN:
            if len(self.slot_of) < slots:
                self.slot_of[name] = len(self.slot_of)

    def lookup(self, name: str) -> Optional[int]:
        return self.slot_of.get(name)

    def register(self, name: str) -> Optional[int]:
        slot = self.slot_of.get(name)
        if slot is not None:
            return slot
        if len(self.slot_of) >= self.slots:
            return None  # escaped — host fallback
        slot = len(self.slot_of)
        self.slot_of[name] = slot
        return slot


class DeviceRegistry:
    """Maps device-type names (e.g. ``nvidia/gpu`` or ``gpu``) to slots."""

    def __init__(self, slots: int = DEVICE_SLOTS):
        self.slots = slots
        self.slot_of: Dict[str, int] = {}

    def lookup(self, name: str) -> Optional[int]:
        return self.slot_of.get(name)

    def register(self, name: str) -> Optional[int]:
        slot = self.slot_of.get(name)
        if slot is not None:
            return slot
        if len(self.slot_of) >= self.slots:
            return None
        slot = len(self.slot_of)
        self.slot_of[name] = slot
        return slot


def node_attributes(node: Node) -> Dict[str, str]:
    """Flatten a node into the attribute namespace used by constraints
    (reference: scheduler/feasible.go resolveTarget :748-790)."""
    attrs: Dict[str, str] = {}
    attrs["node.datacenter"] = node.datacenter
    attrs["node.class"] = node.node_class
    attrs["node.unique.name"] = node.name
    attrs["node.unique.id"] = node.id
    for k, v in node.attributes.items():
        attrs[k] = v
    for k, v in node.meta.items():
        attrs[f"meta.{k}"] = v
        attrs[f"node.meta.{k}"] = v
    for name, info in node.drivers.items():
        attrs[f"driver.{name}"] = "1" if (info.detected and info.healthy) else ""
    return attrs


def computed_class_key(attrs: Dict[str, str], node: Node) -> str:
    """Class key over non-unique attributes (reference: node_class.go:28-37)."""
    items = sorted(
        (k, v)
        for k, v in attrs.items()
        if UNIQUE_PREFIX not in k and not k.startswith("node.unique")
    )
    items.append(("node.class", node.node_class))
    return str(zlib.crc32(repr(items).encode()))


class DeviceArrays(NamedTuple):
    """The on-device snapshot consumed by kernels (all jax arrays)."""

    totals: "jax.Array"  # (N, 3) f32
    used: "jax.Array"  # (N, 3) f32
    eligible: "jax.Array"  # (N,) bool
    attr_hash: "jax.Array"  # (N, A) i32
    attr_num: "jax.Array"  # (N, A) f32
    attr_ver: "jax.Array"  # (N, A) f32 — version packing (see version_value)
    class_id: "jax.Array"  # (N,) i32
    dev_total: "jax.Array"  # (N, D) i32
    dev_used: "jax.Array"  # (N, D) i32
    prio_used: "jax.Array"  # (N, P, 3) f32
    port_words: "jax.Array"  # (N, PORT_WORDS) u32 — occupied-port bitmap
    dyn_used: "jax.Array"  # (N,) i32 — ports consumed in the dynamic range


_SCATTER_FN = None


def scatter_bucket(rows: int) -> int:
    """The row count a dirty-row scatter of ``rows`` rows is padded to: the
    next power of two, and at least 2.  A bucket of one row would be a
    program of its own that only a sync of exactly one dirty row compiles:
    a warm-up that commits several plans a launch (none is refused since
    PR 41) never meets it, and the first such sync of a window compiled it
    there (15 ms on a v5e: PERF.md section 6, PR 41)."""
    return max(2, 1 << max(0, rows - 1).bit_length())


def _row_specs(arrays):
    """``packed_rows`` specs of one matrix row: every field of
    ``DeviceArrays`` less its node axis, then the row's own index."""
    return [(a.shape[1:], a.dtype) for a in arrays] + [((), np.int32)]


def scatter_packed(d: "DeviceArrays", pack) -> "DeviceArrays":
    """The body of both dirty-row scatters (this module's and the mesh's,
    ``parallel/sharding.py``): take ``NodeMatrix._pack_rows``' buffer apart
    (bit for bit what the host wrote) and write each field's rows at the
    index that came with them.  The layout is worked out from the shapes of
    ``d`` at trace time, as the host works it out from its mirror's."""
    from ..ops.encode import packed_layout
    from ..ops.kernels import unpack_rows

    layout, width = packed_layout(_row_specs(d))
    assert pack.shape[1] == width, (pack.shape, width)
    *vals, i = unpack_rows(pack, layout)
    return DeviceArrays(*(x.at[i].set(v) for x, v in zip(d, vals)))


def make_row_scatter():
    """Build the jitted multi-field dirty-row scatter.

    ``scatter(device, pack) -> DeviceArrays`` writes the rows that
    ``pack`` holds (``NodeMatrix._pack_rows``: twelve fields and their
    index, ONE host operand, so one host->device buffer a sync where the
    index and a numpy array a field were thirteen) into every matrix
    field in one dispatch.  No donation: launches in flight still read the
    previous snapshot's buffers.  This factory is the registered device
    entry point for the scatter in ``lint/contracts.py`` (the jaxpr-level
    contract gate traces and sweeps it), so keep its signature stable;
    ``_scatter_rows`` below is the lazy process-wide instance the sync
    path actually calls.
    """
    import jax

    return jax.jit(scatter_packed)


def _scatter_rows(device: "DeviceArrays", pack) -> "DeviceArrays":
    """Jitted multi-field row scatter (lazy so importing nomad_tpu doesn't
    initialize a jax backend)."""
    global _SCATTER_FN
    if _SCATTER_FN is None:
        _SCATTER_FN = make_row_scatter()
    return _SCATTER_FN(device, pack)


class NodeMatrix:
    """Host mirror + device copy of the cluster matrix.

    Row lifecycle: nodes claim rows on upsert; removed nodes free their row
    (marked ineligible until reused). Capacity grows by doubling; growth
    invalidates the device copy entirely (rare).
    """

    def __init__(self, capacity: int = 1024):
        self.capacity = max(16, capacity)
        self.attrs = AttributeRegistry()
        self.devices = DeviceRegistry()
        self.row_of: Dict[str, int] = {}  # node_id -> row
        self.node_of: Dict[int, str] = {}  # row -> node_id
        self._free: List[int] = []
        self._next_row = 0
        # class bookkeeping
        self.class_ids: Dict[str, int] = {}  # class key -> id
        self.class_repr: Dict[int, str] = {}  # class id -> representative node
        # What the host's column-wise feasibility reads (scheduler/
        # feasible_host.py ``HostFeasibility``): the string behind each
        # value id of ``attr_hash`` (an escaped predicate is evaluated once
        # per distinct value of its column); the rows that expose a host
        # volume or carry instances of a device, by name ({row: count});
        # and a counter bumped whenever any of it, a node's attribute row,
        # class or position changes: what a cached mask is valid for.
        self.value_of: Dict[int, str] = {}
        self.volume_rows: Dict[str, Dict[int, int]] = {}
        self.device_rows: Dict[str, Dict[int, int]] = {}
        self.attr_version = 0
        self._host_feasibility = None
        self._alloc = self._allocate_arrays(self.capacity)
        self._dirty: set = set()
        self._device: Optional[DeviceArrays] = None
        self._device_valid = False
        # Monotonic mutation counter, bumped on every host-side row change.
        # Pipelined dispatches record it at launch; a mismatch at resolve
        # time means the dispatch scored a stale snapshot (counted by the
        # coalescer — the applier's re-verify is the correctness backstop).
        self.version = 0
        # ``version`` as the last sync read it under the host lock, with the
        # rows it drained: that snapshot holds every mutation up to it and
        # none after (the coalescer's claims ledger releases a committed
        # plan's claims by it).
        self.synced_version = 0
        # Transfer telemetry (exported via /v1/metrics): proves steady-state
        # syncs move O(dirty rows), not the whole matrix.
        self.full_uploads = 0
        self.scatter_syncs = 0
        # Host operands the scatter syncs handed the device: one a sync.
        self.scatter_operands_total = 0
        self.rows_scattered_total = 0
        self.upload_bytes_total = 0
        # Seconds the syncs spent blocked acquiring ``_host_lock`` (held by
        # the store's mutators): the coalescer attaches each launch's share
        # to its ``coalescer.sync`` span as ``lock_wait``.  Timed per sync,
        # never on the per-mutation acquires.
        self.sync_lock_wait_total = 0.0
        # Sharded residency (multi-chip dispatch path): a second device
        # mirror laid out across a mesh, with its own dirty set so the
        # single-device and sharded copies sync independently.
        self._sharded_device: Optional[DeviceArrays] = None
        self._sharded_valid = False
        self._sharded_dirty: set = set()
        self._sharded_mesh = None
        self._sharded_scatter = None
        # Node-axis sharding (parallel/sharding.py): the capacity splits
        # into shard_count equal row blocks, one per mesh 'node' shard.
        # Row claims balance across blocks and _grow relocates rows so a
        # node's (home_shard, local_offset) pair survives capacity growth —
        # the sharded device mirror never sees a row migrate between
        # shards.  shard_count == 1 is the exact legacy dense policy.
        self.shard_count = 1
        self._shard_next: List[int] = [0]
        self._shard_claimed: List[int] = [0]
        # Row-relocation history: (version_after_remap, mapping) pairs, so
        # in-flight dispatches that recorded GLOBAL rows against an older
        # version can translate them (translate_rows).  Bounded window;
        # anything older resolves to -1 (= placement failed, stack retries).
        self._remaps: List[Tuple[int, np.ndarray]] = []
        self._remap_floor = 0
        # Guards _alloc row writes + _dirty against the sync drain: store
        # mutators run under the store lock, sync under DEVICE_LOCK — with
        # no common lock, a row marked dirty while sync snapshots the set
        # was cleared WITHOUT ever reaching the device, leaving (e.g.) a
        # freshly registered node invisible to every subsequent dispatch.
        self._host_lock = threading.Lock()
        self._encoder = None
        self._shared_masks: Optional[Tuple[np.ndarray, np.ndarray]] = None
        self._shared_zero_i32: Optional[np.ndarray] = None
        # TSan-lite (lint/tsan.py): lockset checking on _alloc row writes
        # and the dirty sets when a test enabled the sanitizer.
        from ..lint.tsan import maybe_instrument

        maybe_instrument("matrix", self)

    def shared_encoder(self):
        """The matrix-wide RequestEncoder.  Scheduling stacks are built per
        eval; a per-stack encoder made the compile cache die with each eval,
        so steady-state evals recompiled every constraint set.  The shared
        instance is safe: per-job broker serialization means no two live
        evals compile/mutate the same (job, tg) entry concurrently."""
        enc = self._encoder
        if enc is None:
            from ..ops.encode import RequestEncoder

            enc = self._encoder = RequestEncoder(self)
        return enc

    def host_feasibility(self):
        """The matrix-wide ``HostFeasibility``: the masks of escaped
        predicates, cached across evals as the encoder's compilations are
        (a stack is built per eval)."""
        hf = self._host_feasibility
        if hf is None:
            from ..scheduler.feasible_host import HostFeasibility

            hf = self._host_feasibility = HostFeasibility(self)
        return hf

    def shared_masks(self) -> Tuple[np.ndarray, np.ndarray]:
        """(all-False, all-True) read-only (capacity,) bool masks — select
        assembly reuses them instead of allocating fresh vectors per eval.
        Rebuilt when capacity grows; marked non-writeable so an accidental
        in-place mutation raises instead of corrupting a neighbor select."""
        n = self.capacity
        m = self._shared_masks
        if m is None or m[0].shape[0] != n:
            zeros = np.zeros((n,), bool)
            ones = np.ones((n,), bool)
            zeros.setflags(write=False)
            ones.setflags(write=False)
            m = self._shared_masks = (zeros, ones)
        return m

    def shared_zero_i32(self) -> np.ndarray:
        """Read-only all-zero (capacity,) int32 — the tg_count vector for
        evals whose job has no proposed allocs yet (the common first pass)."""
        n = self.capacity
        z = self._shared_zero_i32
        if z is None or z.shape[0] != n:
            z = np.zeros((n,), np.int32)
            z.setflags(write=False)
            self._shared_zero_i32 = z
        return z

    # -- host arrays --------------------------------------------------------

    def _allocate_arrays(self, cap: int) -> Dict[str, np.ndarray]:
        return {
            "totals": np.zeros((cap, RESOURCE_DIMS), np.float32),
            "used": np.zeros((cap, RESOURCE_DIMS), np.float32),
            "eligible": np.zeros((cap,), bool),
            "attr_hash": np.zeros((cap, self.attrs.slots), np.int32),
            "attr_num": np.full((cap, self.attrs.slots), np.nan, np.float32),
            "attr_ver": np.full((cap, self.attrs.slots), np.nan, np.float32),
            "class_id": np.full((cap,), -1, np.int32),
            "dev_total": np.zeros((cap, self.devices.slots), np.int32),
            "dev_used": np.zeros((cap, self.devices.slots), np.int32),
            "prio_used": np.zeros(
                (cap, PRIORITY_BUCKETS, RESOURCE_DIMS), np.float32
            ),
            "port_words": np.zeros((cap, PORT_WORDS), np.uint32),
            "dyn_used": np.zeros((cap,), np.int32),
        }

    # How many row-relocation mappings translate_rows keeps.  A dispatch
    # outlives at most a handful of growth doublings; anything older maps
    # to -1 (failed placement, retried) rather than a silently wrong row.
    _REMAP_KEEP = 16

    def _grow(self, min_cap: int) -> None:
        new_cap = self.capacity
        while new_cap < min_cap:
            new_cap *= 2
        new = self._allocate_arrays(new_cap)
        if self.shard_count > 1:
            # Shard-preserving relocation: row r of shard s sits at offset
            # (r - s·old_blk) inside its block; it moves to the SAME offset
            # of the SAME shard's doubled block, so (home_shard, offset)
            # survives growth and the mesh layout never migrates a node
            # between shards.  The mapping is recorded so in-flight
            # dispatches can translate rows they scored pre-growth.
            old_blk = self.capacity // self.shard_count
            new_blk = new_cap // self.shard_count
            rows = np.arange(self.capacity, dtype=np.int64)
            mapping = ((rows // old_blk) * new_blk + rows % old_blk).astype(
                np.int32
            )
            for k, arr in self._alloc.items():
                new[k][mapping] = arr
            self.row_of = {
                nid: int(mapping[r]) for nid, r in self.row_of.items()
            }
            self.node_of = {r: nid for nid, r in self.row_of.items()}
            self._free = [int(mapping[r]) for r in self._free]
            self._relocate_index(mapping)
            self._dirty = {int(mapping[r]) for r in self._dirty}
            self._sharded_dirty = {
                int(mapping[r]) for r in self._sharded_dirty
            }
            self._shard_next = [
                s * new_blk + (nxt - s * old_blk)
                for s, nxt in enumerate(self._shard_next)
            ]
            self._next_row = max(
                (r + 1 for r in self.node_of), default=0
            )
            self.version += 1
            self._remaps.append((self.version, mapping))
            if len(self._remaps) > self._REMAP_KEEP:
                dropped = self._remaps[: -self._REMAP_KEEP]
                self._remap_floor = dropped[-1][0]
                del self._remaps[: -self._REMAP_KEEP]
        else:
            for k, arr in self._alloc.items():
                new[k][: self.capacity] = arr
        self._alloc = new
        self.capacity = new_cap
        self.attr_version += 1
        self._device_valid = False
        self._sharded_valid = False

    @property
    def n_rows(self) -> int:
        return self._next_row

    def set_shard_count(self, n: int) -> None:
        """Partition the row space into ``n`` equal home-shard blocks
        (block b = rows [b·capacity/n, (b+1)·capacity/n)), matching the
        mesh 'node' axis size.  Subsequent claims balance across blocks
        and growth preserves each row's home shard.  ``n`` must divide
        capacity; ``n == 1`` restores the dense legacy policy."""
        n = max(1, int(n))
        with self._host_lock:
            if n == self.shard_count:
                return
            if self.capacity % n:
                raise ValueError(
                    f"shard_count {n} does not divide capacity "
                    f"{self.capacity}"
                )
            self.shard_count = n
            blk = self.capacity // n
            self._shard_next = [s * blk for s in range(n)]
            self._shard_claimed = [0] * n
            for r in self.node_of:
                self._shard_claimed[r // blk] += 1

    def home_shard(self, row: int) -> int:
        """The mesh shard owning ``row`` under the current partition."""
        return row // (self.capacity // self.shard_count)

    def shard_row_counts(self) -> List[int]:
        """Claimed-row count per home shard (the shard-balance gauge)."""
        with self._host_lock:
            if self.shard_count == 1:
                return [len(self.node_of)]
            return list(self._shard_claimed)

    def shard_nodes(self, shard: int) -> List[str]:
        """Node ids homed on ``shard`` — the chaos ``shard.partition``
        seam's blast-radius surface (scheduler/coalescer.py)."""
        with self._host_lock:
            blk = self.capacity // self.shard_count
            return [
                nid for r, nid in self.node_of.items() if r // blk == shard
            ]

    @property
    def relocated_at(self) -> int:
        """``version`` after the last row relocation (growth of a sharded
        layout, a re-layout); 0 = no row ever moved.  Row ids recorded
        before it name rows of another layout."""
        remaps = self._remaps
        return remaps[-1][0] if remaps else 0

    def translate_rows(
        self, rows: np.ndarray, from_version: int
    ) -> np.ndarray:
        """Map GLOBAL row ids recorded at matrix ``from_version`` through
        every shard-preserving relocation since.  Rows whose provenance
        predates the tracked remap window become -1 (the caller treats
        that as a failed placement and retries); negative rows pass
        through untouched."""
        with self._host_lock:
            remaps = [
                (ver, mp) for ver, mp in self._remaps if ver > from_version
            ]
            floor = self._remap_floor
        if not remaps:
            return rows
        out = np.array(rows, np.int64, copy=True)
        pos = out >= 0
        if from_version < floor:
            out[pos] = -1
            return out.astype(rows.dtype, copy=False)
        for _ver, mapping in remaps:
            ok = pos & (out >= 0) & (out < len(mapping))
            out = np.where(
                ok,
                mapping[np.clip(out, 0, len(mapping) - 1)],
                np.where(pos, -1, out),
            )
        return out.astype(rows.dtype, copy=False)

    def relayout_shards(self, n: int) -> np.ndarray:
        """Re-home every claimed row under a fresh ``n``-shard partition
        by replaying the claim policy (least-claimed shard, lowest index
        on ties, per-shard cursor) over nodes in ascending old-row order.

        That replay is, by construction, bit-identical to inserting the
        same nodes in that order into an empty ``n``-shard matrix — the
        PARITY.md shard-evacuation proof.  Capacity is rounded up to the
        next multiple of ``n`` (so ``_grow``'s divisibility invariant
        holds); since every claimed node fits the old capacity, the
        balanced replay always fits the new blocks.

        The old→new mapping (−1 for unclaimed rows) is recorded in the
        remap window, so in-flight dispatches that scored the old layout
        translate their winner rows like any growth relocation — rows
        freed by the re-layout come back −1 (failed placement, retried).
        Both device mirrors invalidate; the next sync re-uploads in full.
        Returns the mapping."""
        n = max(1, int(n))
        with self._host_lock:
            old_cap = self.capacity
            new_cap = old_cap if old_cap % n == 0 else (
                (old_cap + n - 1) // n
            ) * n
            blk = new_cap // n
            mapping = np.full((old_cap,), -1, np.int32)
            claimed = [0] * n
            cursor = [s * blk for s in range(n)]
            new_row_of: Dict[str, int] = {}
            for old_row in sorted(self.node_of):
                s = min(range(n), key=lambda i: (claimed[i], i))
                r = cursor[s]
                cursor[s] = r + 1
                claimed[s] += 1
                mapping[old_row] = r
                new_row_of[self.node_of[old_row]] = r
            new = self._allocate_arrays(new_cap)
            src = mapping >= 0
            if src.any():
                dst = mapping[src]
                for k, arr in self._alloc.items():
                    new[k][dst] = arr[src]
            self._alloc = new
            self.capacity = new_cap
            self._relocate_index(mapping)
            self.shard_count = n
            self.row_of = new_row_of
            self.node_of = {r: nid for nid, r in new_row_of.items()}
            self._free = []
            self._shard_next = cursor
            self._shard_claimed = claimed
            self._next_row = max((r + 1 for r in self.node_of), default=0)
            self._dirty.clear()
            self._sharded_dirty.clear()
            self.version += 1
            self._remaps.append((self.version, mapping))
            if len(self._remaps) > self._REMAP_KEEP:
                dropped = self._remaps[: -self._REMAP_KEEP]
                self._remap_floor = dropped[-1][0]
                del self._remaps[: -self._REMAP_KEEP]
            self._device_valid = False
            self._sharded_valid = False
            self._shared_masks = None
            self._shared_zero_i32 = None
            return mapping

    def evacuate_shard(self, shard: int) -> np.ndarray:
        """Evacuate a lost home shard: re-lay every node across the
        surviving ``shard_count - 1`` shards (the host mirror is
        authoritative — only the device-resident representation was
        lost, so no node goes away, every row re-homes).  Returns the
        old→new row mapping from :meth:`relayout_shards`."""
        if self.shard_count <= 1:
            raise ValueError("evacuate_shard requires shard_count > 1")
        if not 0 <= shard < self.shard_count:
            raise ValueError(
                f"shard {shard} out of range 0..{self.shard_count - 1}"
            )
        return self.relayout_shards(self.shard_count - 1)

    def _claim_row(self, node_id: str) -> int:
        row = self.row_of.get(node_id)
        if row is not None:
            return row
        if self.shard_count > 1:
            row = self._claim_sharded_row_locked()
        elif self._free:
            row = self._free.pop()
        else:
            if self._next_row >= self.capacity:
                self._grow(self._next_row + 1)
            row = self._next_row
            self._next_row += 1
        self.row_of[node_id] = row
        self.node_of[row] = node_id
        return row

    def _claim_sharded_row_locked(self) -> int:
        """Claim a row on the least-occupied home shard: a freed row in
        that shard's block if any, else the block's claim cursor.  Falls
        through fuller shards before growing (doubling every block)."""
        blk = self.capacity // self.shard_count
        order = sorted(
            range(self.shard_count),
            key=lambda s: (self._shard_claimed[s], s),
        )
        for s in order:
            lo, hi = s * blk, (s + 1) * blk
            for i in range(len(self._free) - 1, -1, -1):
                r = self._free[i]
                if lo <= r < hi:
                    del self._free[i]
                    self._shard_claimed[s] += 1
                    self._next_row = max(self._next_row, r + 1)
                    return r
            nxt = max(self._shard_next[s], lo)
            while nxt < hi and nxt in self.node_of:
                nxt += 1
            if nxt < hi:
                self._shard_next[s] = nxt + 1
                self._shard_claimed[s] += 1
                self._next_row = max(self._next_row, nxt + 1)
                return nxt
        self._grow(self.capacity + 1)
        return self._claim_sharded_row_locked()

    # -- mutations ----------------------------------------------------------

    def _mark_dirty_locked(self, row: int) -> None:
        """Record a row mutation (caller holds _host_lock): both device
        mirrors resync it, and the version bump lets in-flight pipelined
        dispatches detect they scored a stale snapshot."""
        self._dirty.add(row)
        self._sharded_dirty.add(row)
        self.version += 1

    def clear(self) -> None:
        """Drop every row (snapshot install replaces all state). Registries
        persist — attribute slots are append-only by design."""
        with self._host_lock:
            self.row_of.clear()
            self.node_of.clear()
            self._free.clear()
            self._next_row = 0
            self.class_ids.clear()
            self.class_repr.clear()
            self.volume_rows.clear()
            self.device_rows.clear()
            self.attr_version += 1
            self._alloc = self._allocate_arrays(self.capacity)
            self._dirty.clear()
            self._device_valid = False
            self._sharded_dirty.clear()
            self._sharded_valid = False
            blk = self.capacity // self.shard_count
            self._shard_next = [s * blk for s in range(self.shard_count)]
            self._shard_claimed = [0] * self.shard_count
            self.version += 1

    def upsert_node(self, node: Node) -> int:
        """Insert or refresh a node's static columns (totals, attrs, class).

        Usage columns are owned by the alloc-delta path.
        """
        with self._host_lock:
            return self._upsert_node_locked(node)

    def _upsert_node_locked(self, node: Node) -> int:
        row = self._claim_row(node.id)
        a = self._alloc
        avail = node.comparable_resources()
        a["totals"][row] = (avail.cpu, avail.memory_mb, avail.disk_mb)
        a["eligible"][row] = node.ready()

        attrs = node_attributes(node)
        hash_row = np.zeros((self.attrs.slots,), np.int32)
        num_row = np.full((self.attrs.slots,), np.nan, np.float32)
        ver_row = np.full((self.attrs.slots,), np.nan, np.float32)
        for name, value in attrs.items():
            if value is None or value == "":
                continue
            slot = self.attrs.register(name)
            if slot is None:
                continue
            hash_row[slot] = h = stable_hash(str(value))
            self.value_of[h] = str(value)
            num_row[slot] = numeric_value(str(value))
            ver_row[slot] = version_value(str(value))
        changed = not np.array_equal(a["attr_hash"][row], hash_row)
        a["attr_hash"][row] = hash_row
        a["attr_num"][row] = num_row
        a["attr_ver"][row] = ver_row

        key = computed_class_key(attrs, node)
        cid = self.class_ids.get(key)
        if cid is None:
            cid = len(self.class_ids)
            self.class_ids[key] = cid
            self.class_repr[cid] = node.id
        changed |= int(a["class_id"][row]) != cid
        a["class_id"][row] = cid
        changed |= self._index_row(
            row, node.host_volumes,
            {k: len(v) for k, v in node.resources.devices.items()},
        )
        # A status or eligibility update re-enters here with the same
        # facts: the cached masks stay.
        self.attr_version += changed

        dev_row = np.zeros((self.devices.slots,), np.int32)
        for name, instances in node.resources.devices.items():
            slot = self.devices.register(name)
            if slot is not None:
                dev_row[slot] = len(instances)
        a["dev_total"][row] = dev_row

        # Node-reserved ports claim their bits up-front (bits are otherwise
        # owned by the alloc-delta path, so set-only here).
        for p in node.reserved.reserved_ports:
            if 0 <= p < PORT_BITS:
                a["port_words"][row, p >> 5] |= np.uint32(1 << (p & 31))

        self._mark_dirty_locked(row)
        return row

    def _index_row(self, row: int, volumes, devices: Dict[str, int]) -> bool:
        """Enter what node ``row`` exposes into ``volume_rows`` /
        ``device_rows`` (nothing = the row was freed); whether it changed."""
        changed = False
        for index, have in (
            (self.volume_rows, dict.fromkeys(volumes, 1)),
            (self.device_rows, {k: n for k, n in devices.items() if n}),
        ):
            for name, rows in index.items():
                if name not in have and rows.pop(row, None) is not None:
                    changed = True
            for name, n in have.items():
                rows = index.setdefault(name, {})
                if rows.get(row) != n:
                    rows[row] = n
                    changed = True
        return changed

    def _relocate_index(self, mapping: np.ndarray) -> None:
        """``volume_rows`` / ``device_rows`` after rows moved (a growth
        under sharding, a re-layout): old row -> ``mapping[row]``."""
        for index in (self.volume_rows, self.device_rows):
            for name, rows in index.items():
                index[name] = {
                    int(mapping[r]): n for r, n in rows.items()
                    if mapping[r] >= 0
                }
        self.attr_version += 1

    def set_eligibility(self, node_id: str, eligible: bool) -> None:
        with self._host_lock:
            row = self.row_of.get(node_id)
            if row is None:
                return
            self._alloc["eligible"][row] = eligible
            self._mark_dirty_locked(row)

    def remove_node(self, node_id: str) -> None:
        with self._host_lock:
            self._remove_node_locked(node_id)

    def _remove_node_locked(self, node_id: str) -> None:
        row = self.row_of.pop(node_id, None)
        if row is None:
            return
        del self.node_of[row]
        self._index_row(row, (), {})
        self.attr_version += 1
        # Re-seat the computed-class representative if this node held it:
        # escaped-constraint checks are evaluated against the representative
        # (stack._class_eligibility), so a stale id would skip them.
        cid = int(self._alloc["class_id"][row])
        if cid >= 0 and self.class_repr.get(cid) == node_id:
            replacement = None
            for other_row, other_id in self.node_of.items():
                if int(self._alloc["class_id"][other_row]) == cid:
                    replacement = other_id
                    break
            if replacement is None:
                self.class_repr.pop(cid, None)
            else:
                self.class_repr[cid] = replacement
        for k in ("totals", "used", "dev_total", "dev_used", "port_words",
                  "dyn_used"):
            self._alloc[k][row] = 0
        self._alloc["eligible"][row] = False
        self._alloc["class_id"][row] = -1
        self._alloc["prio_used"][row] = 0
        self._free.append(row)
        if self.shard_count > 1:
            self._shard_claimed[self.home_shard(row)] -= 1
        self._mark_dirty_locked(row)

    def _usage_of(self, alloc: Allocation) -> np.ndarray:
        r = alloc.resources
        return np.array([r.cpu, r.memory_mb, r.disk_mb], np.float32)

    @staticmethod
    def ports_of(alloc: Allocation) -> set:
        """Every port an allocation occupies on its node: assigned (static +
        dynamic) plus statically reserved in its network asks."""
        ports = set()
        for nets in alloc.assigned_ports.values():
            ports.update(nets.values())
        for net in alloc.resources.networks:
            ports.update(net.reserved_ports)
        return ports

    def _port_delta(self, row: int, alloc: Allocation, claim: bool) -> None:
        ports = self.ports_of(alloc)
        if not ports:
            return
        words = self._alloc["port_words"]
        dyn = 0
        for p in ports:
            if MIN_DYNAMIC_PORT <= p <= MAX_DYNAMIC_PORT:
                dyn += 1
            if not 0 <= p < PORT_BITS:
                continue  # beyond the bitmap — host-checked only
            w, b = p >> 5, np.uint32(1 << (p & 31))
            if claim:
                words[row, w] |= b
            else:
                words[row, w] &= ~b
        if dyn:
            cur = int(self._alloc["dyn_used"][row])
            self._alloc["dyn_used"][row] = max(0, cur + (dyn if claim else -dyn))

    def add_alloc(self, alloc: Allocation) -> None:
        """Account a (non-terminal) allocation's usage on its node."""
        with self._host_lock:
            self._add_alloc_locked(alloc)

    def remove_alloc(self, alloc: Allocation) -> None:
        with self._host_lock:
            self._remove_alloc_locked(alloc)

    def _add_alloc_locked(self, alloc: Allocation) -> None:
        row = self.row_of.get(alloc.node_id)
        if row is None:
            return
        usage = self._usage_of(alloc)
        self._alloc["used"][row] += usage
        self._alloc["prio_used"][row, priority_bucket(alloc.job_priority())] += usage
        for dev in alloc.resources.devices:
            slot = self.devices.register(dev.name)
            if slot is not None:
                self._alloc["dev_used"][row, slot] += dev.count
        self._port_delta(row, alloc, claim=True)
        self._mark_dirty_locked(row)

    def _remove_alloc_locked(self, alloc: Allocation) -> None:
        row = self.row_of.get(alloc.node_id)
        if row is None:
            return
        usage = self._usage_of(alloc)
        self._alloc["used"][row] = np.maximum(self._alloc["used"][row] - usage, 0)
        bucket = priority_bucket(alloc.job_priority())
        self._alloc["prio_used"][row, bucket] = np.maximum(
            self._alloc["prio_used"][row, bucket] - usage, 0
        )
        for dev in alloc.resources.devices:
            slot = self.devices.lookup(dev.name)
            if slot is not None:
                self._alloc["dev_used"][row, slot] = max(
                    0, self._alloc["dev_used"][row, slot] - dev.count
                )
        self._port_delta(row, alloc, claim=False)
        self._mark_dirty_locked(row)

    def set_usage(self, rows, used, prio_used) -> None:
        """Overwrite the usage aggregates of ``rows`` in bulk — how a
        simulation installs the usage of allocations it never
        materialises as Allocation objects (simcluster.py)."""
        touched = [int(r) for r in rows]
        with self._host_lock:
            self._alloc["used"][rows] = used
            self._alloc["prio_used"][rows] = prio_used
            self._dirty.update(touched)
            self._sharded_dirty.update(touched)
            self.version += 1

    # -- device sync --------------------------------------------------------

    def run_on_device(self, fn):
        """Execute a device-touching closure on THE device thread.

        The single invariant point for device access: with a coalescer
        attached (the live server) the closure runs on its dispatch
        thread; otherwise inline under DEVICE_LOCK.  Call sites must not
        take DEVICE_LOCK and dispatch themselves — the live server has
        exactly one device-launching thread."""
        coal = getattr(self, "coalescer", None)
        if coal is not None:
            return coal.run_device_op(fn)
        with DEVICE_LOCK:
            return fn()

    @contextmanager
    def _host_lock_timed(self):
        """``_host_lock`` for a sync, the time blocked acquiring it added
        to ``sync_lock_wait_total``."""
        t0 = time.time()
        with self._host_lock:
            self.sync_lock_wait_total += time.time() - t0
            yield

    def snapshot_host(self) -> Dict[str, np.ndarray]:
        """Host-side view (no copy) of the active arrays."""
        return self._alloc

    def sync_host(self) -> DeviceArrays:
        """Copy-consistent host snapshot as a :class:`DeviceArrays` of
        numpy arrays — the degraded dispatch path (device breaker open)
        feeds the fake-device twin from this without ever touching the
        device, so a wedged device cannot stall the fallback."""
        with self._host_lock_timed():
            self.synced_version = self.version
            return DeviceArrays(
                **{f: self._alloc[f].copy() for f in DeviceArrays._fields}
            )

    def sync(self) -> DeviceArrays:
        """Return the device snapshot, scattering dirty rows if needed.

        Full upload on first use or growth; per-row scatter otherwise
        (`.at[rows].set`) so steady-state transfer is O(dirty rows).
        """
        with DEVICE_LOCK:
            return self._sync_locked()

    def _sync_locked(self) -> DeviceArrays:
        from ..ops import fake_device

        fake = fake_device.enabled()
        if self._device is not None and (
            isinstance(self._device.used, np.ndarray) != fake
        ):
            # Backend flipped (tests toggle the env var): the cached
            # snapshot is the wrong flavor — rebuild from the host arrays.
            self._device_valid = False

        # Snapshot the dirty rows' data under the host lock (mutators may
        # run concurrently from the store); the device transfer itself
        # happens outside it.  `_alloc[f][rows]` fancy-indexing copies.
        if self._device is None or not self._device_valid:
            with self._host_lock_timed():
                host_copy = {
                    f: self._alloc[f].copy() for f in DeviceArrays._fields
                }
                self._dirty.clear()
                self.synced_version = self.version
                # Claim validity for THIS copy while still under the lock:
                # a concurrent _grow after this point flips it back to
                # False and the next sync re-uploads — setting it after
                # the transfer would clobber that invalidation and leave
                # post-growth rows silently out of device bounds.
                self._device_valid = True
            self.full_uploads += 1
            if fake:
                # Fake-device backend: the "device snapshot" is the host
                # copy itself; dispatches consume it synchronously on the
                # coalescer thread before the next sync can scatter into
                # it, so no further copies are needed.  (No transfer, so
                # upload_bytes_total doesn't move.)
                self._device = DeviceArrays(**host_copy)
                return self._device
            self.upload_bytes_total += sum(
                a.nbytes for a in host_copy.values()
            )
            try:
                import jax

                # One pytree transfer, not 12 per-field round-trips.
                dev = jax.device_put(host_copy)
                self._device = DeviceArrays(
                    **{f: dev[f] for f in DeviceArrays._fields}
                )
            except BaseException:
                # Failed transfer must not strand the cleared dirty set —
                # invalidate so the next sync re-uploads everything.
                self._device_valid = False
                raise
            return self._device

        with self._host_lock_timed():
            self.synced_version = self.version
            if not self._dirty:
                return self._device
            rows = np.fromiter(self._dirty, np.int32)
            self._dirty.clear()
            if fake and isinstance(self._device.used, np.ndarray):
                # Numpy snapshot: scatter the dirty rows in place (same
                # O(dirty rows) incremental cost as the device path).
                for f in DeviceArrays._fields:
                    getattr(self._device, f)[rows] = self._alloc[f][rows]
                self.scatter_syncs += 1
                self.rows_scattered_total += len(rows)
                return self._device
            pack = self._pack_rows(rows)
        self._device = self._scatter(
            _scatter_rows, self._device, pack, rows, self._dirty
        )
        return self._device

    def _pack_rows(self, rows: np.ndarray) -> np.ndarray:
        """Rows ``rows`` of every device field and ``rows`` itself as ONE
        host buffer (``ops/encode.py::packed_rows``, a row a dirty row):
        the scatter's only host operand, so a sync costs the calling
        thread one device buffer (four on a mesh of four), not one per
        field.  The row count is padded to a pow2 bucket so the jitted
        scatter compiles once per bucket; the tail repeats the first row
        (the duplicate writes carry identical data).  Call under the host
        lock: the gather reads the mirror.  A fresh buffer every sync: jax
        reads a numpy operand after the call returns."""
        from ..ops.encode import packed_rows

        fields = [self._alloc[f] for f in DeviceArrays._fields]
        pack, views, _ = packed_rows(
            scatter_bucket(len(rows)), _row_specs(fields)
        )
        idx = views[-1]
        idx[:] = rows[0]
        idx[: len(rows)] = rows
        for src, view in zip(fields, views):
            view[...] = src[idx]
        return pack

    def _scatter(self, scatter, device, pack, rows, dirty: set):
        """``scatter(device, pack)`` with the sync's accounting; a scatter
        that raises puts the drained ``rows`` back into ``dirty`` so a
        later sync retries them."""
        try:
            device = scatter(device, pack)
        except BaseException:
            with self._host_lock:
                dirty.update(int(r) for r in rows)
            raise
        self.scatter_syncs += 1
        self.scatter_operands_total += 1
        self.rows_scattered_total += len(rows)
        self.upload_bytes_total += pack.nbytes
        return device

    def invalidate(self) -> None:
        self._device_valid = False
        self._sharded_valid = False

    # -- sharded device sync ------------------------------------------------

    def sync_sharded(self, mesh) -> DeviceArrays:
        """Return the mesh-resident snapshot for multi-chip dispatch,
        scattering only dirty rows to their owning shard.

        The sharded mirror used to be re-laid in full (shard_matrix_arrays
        over the whole host matrix) before EVERY dispatch; now it stays
        resident across dispatches exactly like the single-device copy —
        full lay-out on first use/growth/mesh change, O(dirty rows)
        scatter otherwise (the jitted scatter is sharding-aware: each row
        lands on the shard that owns it).
        """
        with DEVICE_LOCK:
            return self._sync_sharded_locked(mesh)

    def _sync_sharded_locked(self, mesh) -> DeviceArrays:
        from ..parallel.sharding import (
            make_sharded_row_scatter,
            shard_matrix_arrays,
        )

        if self._sharded_mesh is not mesh:
            self._sharded_mesh = mesh
            self._sharded_scatter = make_sharded_row_scatter(mesh)
            self._sharded_valid = False

        if self._sharded_device is None or not self._sharded_valid:
            with self._host_lock_timed():
                host_copy = {
                    f: self._alloc[f].copy() for f in DeviceArrays._fields
                }
                self._sharded_dirty.clear()
                self.synced_version = self.version
                # Same ordering contract as _sync_locked: claim validity
                # under the lock so a concurrent _grow's invalidation wins.
                self._sharded_valid = True
            try:
                self._sharded_device = shard_matrix_arrays(
                    mesh, DeviceArrays(**host_copy)
                )
            except BaseException:
                self._sharded_valid = False
                raise
            self.full_uploads += 1
            self.upload_bytes_total += sum(
                a.nbytes for a in host_copy.values()
            )
            return self._sharded_device

        with self._host_lock_timed():
            self.synced_version = self.version
            if not self._sharded_dirty:
                return self._sharded_device
            rows = np.fromiter(self._sharded_dirty, np.int32)
            self._sharded_dirty.clear()
            # Per-shard scatter buckets: home-shard blocks are contiguous
            # row ranges, so an ascending sort groups each shard's updates
            # into one dense run of the index vector — the sharding-aware
            # scatter then issues one contiguous block per shard instead
            # of interleaved single-row transfers.
            rows.sort()
            pack = self._pack_rows(rows)
        self._sharded_device = self._scatter(
            self._sharded_scatter, self._sharded_device, pack, rows,
            self._sharded_dirty,
        )
        return self._sharded_device
