"""Claims chained on the device (ISSUE 41): a launch that leaves before its
predecessor's result is on the host reads that launch's picks in a device
buffer.

(a) the placement program, three writings (one device, a mesh, the numpy
    twin): with every carried block masked off nothing changes, a live
    block moves the same lanes to the same nodes in all three, and the block
    a launch writes is, lane for lane, what the resolver enters into the
    ledger for the same result;
(b) exactly once: the ledger decides in one step which carried blocks are
    live and which entries the overlay holds;
(c) the coalescer's chain without threads: what is dropped and counted;
(d) the live server on the fake device with launches held in flight.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from test_claims_overlay import (  # noqa: F401  (herd: a fixture)
    ASK_CPU, ASK_MEM, INT_COLS, LANES, MESHES, PARENT_DECISIONS, SCAN,
    _batch_job, _burst, _digest, _overcommitted, _overlay, _plan_results,
    _quiet, _server, herd,
)
from test_megakernel import host_view

from nomad_tpu import mock
from nomad_tpu.chaos import FaultSpec, injected
from nomad_tpu.ops import fake_device, kernels
from nomad_tpu.ops.encode import RequestEncoder
from nomad_tpu.ops.kernels import FUSED_PACKED_VERIFIED, fused_place_batch
from nomad_tpu.scheduler.claims import CHAIN_DEPTH, ClaimsLedger, Launch
from nomad_tpu.scheduler.coalescer import (
    MAX_DELTA_ROWS, DeviceCoalescer, _Pending,
)
from nomad_tpu.state import NodeMatrix

K = 4  # delta rows a lane in the herd's operands
V = (float(ASK_CPU), float(ASK_MEM), 0.0)


def _carry(blocks, lanes=LANES, width=K + SCAN, depth=CHAIN_DEPTH):
    """[[(row, (cpu, mem, disk)), ...] a block] as the (D, lanes, width, 4)
    carry, the pairs dealt over the lanes."""
    carry = np.zeros((depth, lanes, width, 4), np.float32)
    carry[..., 0] = -1.0
    for d, pairs in enumerate(blocks):
        flat = carry[d].reshape(-1, 4)
        for i, (row, vals) in enumerate(pairs):
            flat[i * 7 % len(flat)] = (row,) + tuple(vals)
    return carry


def _flags(holds, live, lanes=LANES):
    """``kernels.chain_flags``' layout: column 0 the lanes that hold
    claims, the rest, as one flat list, the live carried blocks."""
    w = -(-CHAIN_DEPTH // lanes)
    flags = np.zeros((lanes, 1 + w), bool)
    flags[:, 0] = holds
    flat = np.zeros((lanes * w,), bool)
    flat[: len(live)] = live
    flags[:, 1:] = flat.reshape(lanes, w)
    return flags


def _one_device(m, ops, ls, chain, overlay=None):
    arrays = m.sync()
    out = fused_place_batch(
        arrays, arrays.used, *ops, ls, n_placements=SCAN, overlay=overlay,
        chain=chain and (chain[0], _flags(chain[3], chain[1]), chain[2]),
    )
    return tuple(np.asarray(o) for o in out) if chain else np.asarray(out)


def _mesh(m, ops, ls, chain, devices, batch):
    from nomad_tpu.parallel import (
        make_mesh, shard_matrix_arrays, sharded_fused_place_batch,
    )

    mesh = make_mesh(devices, batch=batch)
    sharded = shard_matrix_arrays(mesh, m.sync())
    out = sharded_fused_place_batch(mesh, SCAN)(
        sharded, sharded.used, *ops, ls,
        chain=chain and (chain[0], _flags(chain[3], chain[1]), chain[2]),
    )
    return tuple(np.asarray(o) for o in out) if chain else np.asarray(out)


def _twin(m, req, ops, ls, chain):
    drows, dvals, tg, sc, pen, _reqs, ce, hm = ops
    host = host_view(m.sync())
    return fake_device.fused_place_batch(
        host, host.used, *[list(a) for a in (drows, dvals, tg, sc, pen)],
        [req] * len(ls), list(ce), list(hm), ls > 0, n_placements=SCAN,
        live_counts=list(ls),
        chain=chain and (chain[0], chain[1], list(chain[2]), list(chain[3])),
    )


def _chain(herd, live, holds=True, blocks=None):
    """(carry, live, claim_vals, holds): the frontier's even rows in block
    0, its odd rows in the last block, and what lane 2's plan advertises on
    its one delta row."""
    _m, rows, _req, ops, _ls = herd
    if blocks is None:
        blocks = [[(r, V) for r in rows[::2]]] + [
            [] for _ in range(CHAIN_DEPTH - 2)
        ] + [[(r, V) for r in rows[1::2]]][: CHAIN_DEPTH - 1]
    cv = np.maximum(ops[1], 0.0)
    return (
        _carry(blocks), np.asarray(live, bool), cv,
        np.broadcast_to(np.asarray(holds, bool), (LANES,)).copy(),
    )


NONE_LIVE = (False,) * CHAIN_DEPTH
FIRST_LIVE = (True,) + (False,) * (CHAIN_DEPTH - 1)


# ---------------------------------------------------------------------------
# (a) the placement program
# ---------------------------------------------------------------------------


class TestEveryBlockMaskedOffIsTheParent:
    """Carried blocks full of claims on the very nodes the lanes want, none
    of them live: the packed output is bit for bit the program's without
    the operand, which is the parent's."""

    def test_one_device(self, herd):
        m, _rows, _req, ops, ls = herd
        none = _one_device(m, ops, ls, None)
        packed, _carry_out = _one_device(m, ops, ls, _chain(herd, NONE_LIVE))
        np.testing.assert_array_equal(none, packed)  # floats too: bitwise
        assert _digest(packed) == PARENT_DECISIONS

    @pytest.mark.parametrize("devices,batch", MESHES)
    def test_mesh(self, herd, eight_devices, devices, batch):
        m, _rows, _req, ops, ls = herd
        none = _mesh(m, ops, ls, None, devices, batch)
        packed, _carry_out = _mesh(
            m, ops, ls, _chain(herd, NONE_LIVE), devices, batch
        )
        np.testing.assert_array_equal(none, packed)
        assert _digest(packed) == PARENT_DECISIONS

    def test_numpy_twin(self, herd):
        m, _rows, req, ops, ls = herd
        none = _twin(m, req, ops, ls, None)
        packed, _carry_out = _twin(m, req, ops, ls, _chain(herd, NONE_LIVE))
        np.testing.assert_array_equal(none, packed)
        assert _digest(packed) == PARENT_DECISIONS


class TestLiveBlocks:
    def test_three_writings_agree(self, herd, eight_devices):
        m, rows, req, ops, ls = herd
        chain = _chain(herd, FIRST_LIVE)
        one, one_carry = _one_device(m, ops, ls, chain)
        twin, twin_carry = _twin(m, req, ops, ls, chain)
        np.testing.assert_array_equal(
            one[:, :, INT_COLS], twin[:, :, INT_COLS]
        )
        np.testing.assert_allclose(
            one[:, :, 1:3], twin[:, :, 1:3], rtol=1e-5, atol=1e-5
        )
        np.testing.assert_array_equal(one_carry, twin_carry)
        for devices, batch in MESHES:
            mesh, mesh_carry = _mesh(m, ops, ls, chain, devices, batch)
            np.testing.assert_array_equal(
                one[:, :, INT_COLS], mesh[:, :, INT_COLS],
                err_msg=f"mesh ({devices}, {batch})",
            )
            np.testing.assert_allclose(
                one[:, :, 1:3], mesh[:, :, 1:3], rtol=1e-5, atol=1e-6
            )
            np.testing.assert_array_equal(one_carry, mesh_carry)
        # Teeth: the live block moved picks off the nodes it holds, the
        # masked one moved nothing, and every placement still verifies.
        base = _one_device(m, ops, ls, None)
        placed = one[:, :, 0] >= 0
        took = set(one[:, :, 0][placed].astype(int))
        assert (one[:, :, 0] != base[:, :, 0]).any()
        assert set(rows[::2]) & set(base[:, :, 0][placed].astype(int))
        assert not set(rows[::2]) & took
        if CHAIN_DEPTH > 1:
            assert set(rows[1::2]) & took
        assert np.isin(
            one[:, :, FUSED_PACKED_VERIFIED][placed], (1.0, 2.0)
        ).all()
        # What was handed on: this launch's block, then the carried ones
        # shifted by one, the oldest gone.
        np.testing.assert_array_equal(one_carry[1:], chain[0][:-1])

    def test_a_live_block_counts_as_the_same_rows_in_the_overlay(self, herd):
        """Where the overlay enters, and nowhere else: the decisions are
        those of the launch that is handed the same claims by the ledger."""
        m, rows, _req, ops, ls = herd
        packed, _c = _one_device(m, ops, ls, _chain(herd, FIRST_LIVE))
        overlaid = _one_device(
            m, ops, ls, None, overlay=_overlay([(r, V) for r in rows[::2]])
        )
        np.testing.assert_array_equal(packed, overlaid)

    def test_every_live_block_counts(self, herd):
        if CHAIN_DEPTH < 2:
            pytest.skip("one block carried")
        m, rows, _req, ops, ls = herd
        packed, _c = _one_device(
            m, ops, ls, _chain(herd, (True,) * CHAIN_DEPTH)
        )
        placed = packed[:, :, 0] >= 0
        assert not set(rows) & set(packed[:, :, 0][placed].astype(int))


def _pendings(req, ops, ls, holds, n_live=None):
    """The lanes of ``ops`` as the coalescer's ``_Pending``s."""
    drows, dvals = ops[0], ops[1]
    return [
        _Pending(
            request=req, delta_rows=drows[i], delta_vals=dvals[i],
            tg_count=None, spread_counts=None, penalty=None, class_elig=None,
            host_mask=None, n_live=int(ls[i]) if n_live is None else n_live,
            eval_id=f"e{i}" if holds[i] else "",
            claim_vals=np.maximum(dvals[i], 0.0),
        )
        for i in range(len(ls))
    ]


def _entered(block_lane):
    """A lane of a claims block as the ledger would hold it: padding and
    rows that claim nothing dropped."""
    keep = (block_lane[:, 0] >= 0) & block_lane[:, 1:].any(axis=1)
    return block_lane[keep, 0].astype(np.int32), block_lane[keep, 1:]


def _assert_block_is_what_the_resolver_enters(coal, pendings, packed, block):
    for i, p in enumerate(pendings):
        rows, vals = _entered(block[i])
        if not p.eval_id or p.n_live == 0:
            assert not len(rows), f"lane {i} holds no claims"
            continue
        want_rows, want_vals = coal._lane_claims(
            p, packed[i, :, kernels.PACKED_ROW].astype(np.int32),
            packed[i, :, kernels.PACKED_PREEMPT],
        )
        np.testing.assert_array_equal(rows, want_rows, err_msg=f"lane {i}")
        np.testing.assert_array_equal(vals, want_vals, err_msg=f"lane {i}")


class TestTheBlockALaunchWrites:
    def _coal(self):
        return DeviceCoalescer(NodeMatrix(capacity=64), max_lanes=LANES,
                               scan_length=SCAN)

    def test_is_lane_for_lane_what_the_resolver_enters(self, herd):
        m, _rows, req, ops, ls = herd
        holds = np.array([1, 1, 1, 1, 0, 1, 1, 1], bool)  # lane 4: a dry run
        chain = _chain(herd, NONE_LIVE, holds=holds)
        pendings = _pendings(req, ops, ls, holds)
        coal = self._coal()
        for packed, carry in (
            _one_device(m, ops, ls, chain), _twin(m, req, ops, ls, chain),
        ):
            _assert_block_is_what_the_resolver_enters(
                coal, pendings, packed, carry[0]
            )
            # Teeth: lane 2's delta row, every holding lane's picks, and
            # nothing of lane 3 (dead) or lane 4 (no eval behind it).
            assert len(_entered(carry[0][2])[0]) == 1 + 3
            assert len(_entered(carry[0][0])[0]) == 8
            assert not len(_entered(carry[0][3])[0])
            assert not len(_entered(carry[0][4])[0])

    @pytest.mark.parametrize("counted", (False, True),
                             ids=["flag", "count"])
    def test_is_cut_at_the_first_preempting_pick(self, counted):
        """The host drops the rows after a preempting pick and re-enters:
        so does the block.  Evictions are not credited: what rides is
        ``claim_vals``, not the deltas."""
        rng = np.random.default_rng(3)
        lanes, p_len = 6, 8
        rows = rng.integers(0, 50, (lanes, p_len)).astype(np.int32)
        rows[1, 5:] = -1  # a lane that asked for five
        rows[2, 2] = -1  # a failed placement between two picks
        pre = np.zeros((lanes, p_len), np.float32)
        pre[0, 3] = pre[0, 6] = 2.0  # cut after the fourth pick
        pre[2, 0] = 1.0  # the first pick preempts: one pick
        pre[3, 7] = 1.0  # the last
        drows = np.full((lanes, K), -1, np.int32)
        drows[0, :2], drows[4, 0] = (7, 9), 11
        dvals = np.zeros((lanes, K, 3), np.float32)
        dvals[0, 0], dvals[0, 1] = (300, 100, 0), (-500, -50, 0)  # evicted
        dvals[4, 0] = (0, 0, 0)  # claims nothing: not entered
        cvals = np.maximum(dvals, 0.0)
        cvals[0, 1] = (200, 20, 0)  # the placement under the eviction
        ask = np.tile(np.asarray(V, np.float32), (lanes, 1))
        holds = np.array([1, 1, 1, 1, 1, 0], bool)
        block = np.asarray(kernels.claims_block(
            drows, cvals, rows, pre if counted else pre != 0, ask, holds,
        ))
        assert block.shape == (lanes, K + p_len, 4)

        class Req:
            pass

        Req.ask = V
        coal = self._coal()
        packed = np.zeros((lanes, p_len, kernels.FUSED_PACKED_WIDTH))
        packed[:, :, kernels.PACKED_ROW] = rows
        packed[:, :, kernels.PACKED_PREEMPT] = pre
        pendings = [
            _Pending(
                request=Req, delta_rows=drows[i], delta_vals=dvals[i],
                tg_count=None, spread_counts=None, penalty=None,
                class_elig=None, host_mask=None, n_live=p_len,
                eval_id=f"e{i}" if holds[i] else "", claim_vals=cvals[i],
            )
            for i in range(lanes)
        ]
        _assert_block_is_what_the_resolver_enters(
            coal, pendings, packed, block
        )
        assert _entered(block[0])[0].tolist() == [7, 9] + rows[0, :4].tolist()
        np.testing.assert_array_equal(_entered(block[0])[1][1], (200, 20, 0))
        assert _entered(block[2])[0].tolist() == [int(rows[2, 0])]
        assert len(_entered(block[3])[0]) == p_len
        assert len(_entered(block[4])[0]) == p_len  # the zero delta is not


# ---------------------------------------------------------------------------
# (b) exactly once
# ---------------------------------------------------------------------------


def _rows_and_live(ledger, chain, **kw):
    rows, _vals, live = ledger.overlay(0, chain=chain, **kw)
    return sorted(rows.tolist()), live


class TestExactlyOnce:
    def test_resolved_is_in_the_overlay_and_unresolved_in_the_carry(self):
        led = ClaimsLedger()
        led.open("e1")
        led.open("e2")
        first, second = Launch(["e1"], 0), Launch(["e2"], 0)
        led.register_launch(first, [("e1", [3, 4], [V, V])])
        assert _rows_and_live(led, [second, first]) == ([3, 4], (True, False))
        assert (second.carried, first.carried) == (1, 0)
        assert led.register_launch(second, [("e2", [8], [V])]) == 1
        assert _rows_and_live(led, [second, first]) == (
            [3, 4, 8], (False, False)
        )

    def test_an_eval_in_a_live_block_is_left_out_of_the_overlay(self):
        """Its block carries its whole proposed usage, what it entered at
        its launch before included: the entry would count that twice."""
        led = ClaimsLedger()
        led.open("e1")
        led.open("e2")
        led.register("e1", [3], [V], layout=0)
        led.register("e2", [5], [V], layout=0)
        again = Launch(["e1"], 0)  # e1's next launch, its plan holds row 3
        assert _rows_and_live(led, [again]) == ([5], (True,))
        led.register_launch(again, [("e1", [3, 6], [V, V])])
        assert _rows_and_live(led, [again]) == ([3, 5, 6], (False,))

    def test_a_block_that_no_longer_counts(self):
        led = ClaimsLedger()
        moved, failed, fell_off = Launch([], 4), Launch([], 9), Launch([], 9)
        fell_off.block = False  # past the carry's depth, or another route
        led.register_launch(failed)  # wedged, raised, abandoned: no lanes
        # The matrix moved its rows at version 7: ``moved`` names another
        # layout.
        _rows, live = _rows_and_live(
            led, [moved, failed, fell_off], stale_before=7
        )
        assert live == (False, False, False)
        assert Launch([], 9).block and not Launch([], 9).resolved

    def test_under_a_resolver_that_registers_between_the_reads(self):
        """Launching thread and resolver at full tilt: every read finds a
        launch's picks in the overlay or its block live, never both, never
        neither, and the eval's entry of the launch before never beside a
        live block."""
        import sys

        led = ClaimsLedger()
        n, stop, seen = 100, threading.Event(), []  # under OVERLAY_ROWS
        launches = [Launch([f"e{i}"], 0) for i in range(n)]
        for i in range(n):
            led.open(f"e{i}")
            led.register(f"e{i}", [10_000 + i], [V], layout=0)  # before

        def resolver():
            for i, launch in enumerate(launches):
                fresh = len(seen) + 2  # reads fall between
                while len(seen) < fresh:
                    time.sleep(0)
                led.register_launch(
                    launch, [(f"e{i}", [10_000 + i, i], [V, V])]
                )
            stop.set()

        def launcher():
            while not stop.is_set():
                rows, _vals, live = led.overlay(0, chain=launches)
                seen.append((set(rows.tolist()), live))

        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            threads = [threading.Thread(target=f) for f in
                       (launcher, launcher, resolver)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in threads)
        finally:
            sys.setswitchinterval(interval)
        assert len(seen) > 10
        flips = 0
        for rows, live in seen:
            for i, on in enumerate(live):
                assert (i in rows) != on, (i, on)
                assert (10_000 + i in rows) != on, (i, on)
            flips += 0 < sum(live) < n
        assert flips, "no read fell between two registrations"


# ---------------------------------------------------------------------------
# (c) the coalescer's chain, without threads
# ---------------------------------------------------------------------------


def _coalescer(monkeypatch, fake=True, lanes=4, **kw):
    if fake:
        monkeypatch.setenv("NOMAD_TPU_FAKE_DEVICE", "1")
    m = NodeMatrix(capacity=64)
    for _ in range(40):
        m.upsert_node(mock.node())
    coal = DeviceCoalescer(
        m, max_lanes=lanes, linger_s=0.0, n_device_shards=1, **kw
    )
    job = mock.job()
    req = RequestEncoder(m).compile(job, job.task_groups[0]).request
    return coal, req


def _launch(coal, req, evals, degraded=False):
    """One batch straight through ``coal._dispatch``: the launch is in
    flight (nothing resolves it) until the test says otherwise."""
    n = int(coal.matrix.capacity)
    batch = []
    for e in evals:
        coal.claims.open(e)
        batch.append(_Pending(
            request=req,
            delta_rows=np.full((MAX_DELTA_ROWS,), -1, np.int32),
            delta_vals=np.zeros((MAX_DELTA_ROWS, 3), np.float32),
            tg_count=np.zeros((n,), np.int32),
            spread_counts=np.zeros_like(req.s_desired),
            penalty=np.zeros((n,), bool), class_elig=np.ones((2,), bool),
            host_mask=np.ones((n,), bool), n_live=2, eval_id=e,
            claim_vals=np.zeros((MAX_DELTA_ROWS, 3), np.float32),
        ))
    packed, _version = coal._dispatch(batch, degraded=degraded)
    return batch, np.asarray(packed), coal._launches[0]


def _resolve(coal, batch, packed, launch):
    return coal.claims.register_launch(launch, [
        (p.eval_id,) + coal._lane_claims(
            p, packed[i, :, kernels.PACKED_ROW].astype(np.int32),
            packed[i, :, kernels.PACKED_PREEMPT],
        )
        for i, p in enumerate(batch)
    ])


def _live_blocks_of_each_launch(monkeypatch):
    """Spy on the twin: the ``live`` flags every launch is handed."""
    seen, twin = [], fake_device.fused_place_batch

    def spy(*a, **kw):
        seen.append(tuple(bool(x) for x in kw["chain"][1]))
        return twin(*a, **kw)

    monkeypatch.setattr(fake_device, "fused_place_batch", spy)
    return seen


class TestTheCoalescersChain:
    def test_a_pick_moves_from_the_carry_to_the_overlay(self, monkeypatch):
        coal, req = _coalescer(monkeypatch)
        seen = _live_blocks_of_each_launch(monkeypatch)
        b1, p1, l1 = _launch(coal, req, ["a", "b"])
        assert seen[-1] == NONE_LIVE and coal.chained_launches == 0
        # In flight: the next launch is handed l1's block live, the ledger
        # holds nothing of it, and its lanes pass over what l1 took (the
        # same job, so without the block they would name the same nodes).
        b2, p2, l2 = _launch(coal, req, ["c", "d"])
        assert seen[-1] == FIRST_LIVE and coal.overlay_rows_total == 0
        assert coal.chained_launches == 1 and l1.carried == 1
        assert (p2[:2, :2, FUSED_PACKED_VERIFIED] >= 1.0).all()
        # l1's result reaches the host: its picks are the ledger's now, the
        # block is masked off, and the rows are counted once.
        assert _resolve(coal, b1, p1, l1) == 1
        b3, p3, l3 = _launch(coal, req, ["e"])
        assert seen[-1] == FIRST_LIVE  # l2 in flight, l1 resolved
        assert coal.overlay_rows_total == 4  # two lanes' two picks each
        assert coal.chained_launches == 2 and coal.chain_overflow == 0
        assert l1.carried == 1 and l2.carried == 1
        # One node holds seven of this ask: nobody was pushed off it yet,
        # and all three launches fit under the claims they were shown.
        assert (p3[:1, :2, FUSED_PACKED_VERIFIED] >= 1.0).all()

    def test_beyond_the_carrys_depth_is_dropped_and_counted(self, monkeypatch):
        coal, req = _coalescer(monkeypatch, pipeline_depth=CHAIN_DEPTH + 3)
        seen = _live_blocks_of_each_launch(monkeypatch)
        flown = [
            _launch(coal, req, [f"e{i}"]) for i in range(CHAIN_DEPTH + 2)
        ]
        # Launch i is handed min(i, D) live blocks; from the (D + 1)th on a
        # launch has an unresolved predecessor the carry no longer holds.
        assert [sum(s) for s in seen] == [
            min(i, CHAIN_DEPTH) for i in range(CHAIN_DEPTH + 2)
        ]
        assert coal.chain_overflow == 1
        assert coal.launches_unresolved_predecessor == 0  # no ticket here
        assert [launch.block for _b, _p, launch in flown] == (
            [False] * 2 + [True] * CHAIN_DEPTH
        )
        for b, p, launch in flown:
            _resolve(coal, b, p, launch)
        _launch(coal, req, ["z"])
        assert seen[-1] == NONE_LIVE and coal.chain_overflow == 1
        assert len(coal._launches) == CHAIN_DEPTH  # the resolved are let go

    def test_another_route_starts_a_carry_of_its_own(self, monkeypatch):
        """A launch degraded to the numpy twin (the breaker is open) reads
        no block of a device launch and the device launch after it none of
        the twin's: each program has its own buffer."""
        coal, req = _coalescer(monkeypatch, fake=False)
        b1, p1, l1 = _launch(coal, req, ["a"])
        import jax

        assert isinstance(coal._carry, jax.Array)
        assert coal._carry_route == "device"
        b2, p2, l2 = _launch(coal, req, ["b"], degraded=True)
        assert isinstance(coal._carry, np.ndarray)
        assert coal._carry_route == "twin"
        assert not l1.block and l2.block
        assert (coal.chained_launches, coal.chain_overflow) == (0, 1)
        b3, p3, l3 = _launch(coal, req, ["c"])
        assert not l2.block and coal.chain_overflow == 2

    def test_the_carry_goes_from_call_to_call_on_the_device(self, monkeypatch):
        import jax

        coal, req = _coalescer(monkeypatch, fake=False)
        handed = []
        live = kernels.fused_place_batch_live

        def spy(*a, **kw):
            handed.append(a[-1])
            return live(*a, **kw)

        monkeypatch.setattr(kernels, "fused_place_batch_live", spy)
        b1, p1, l1 = _launch(coal, req, ["a", "b"])
        first = coal._carry
        assert isinstance(first, jax.Array)
        b2, p2, l2 = _launch(coal, req, ["c", "d"])
        assert handed[1] is first and coal._carry is not first
        carry = np.asarray(coal._carry)
        assert carry.shape == (
            CHAIN_DEPTH, coal.max_lanes, MAX_DELTA_ROWS + coal.scan_length, 4
        )
        # The launch's own block first, the one it was handed after it.
        _assert_block_is_what_the_resolver_enters(coal, b2, p2, carry[0])
        if CHAIN_DEPTH > 1:
            _assert_block_is_what_the_resolver_enters(coal, b1, p1, carry[1])
        # The lanes of the second launch passed over what the first took:
        # everything fits under the claims (VERIFIED 1.0 or 2.0).
        assert (p2[:2, :2, FUSED_PACKED_VERIFIED] >= 1.0).all()
        assert coal.chained_launches == 1

    def test_a_mesh_hands_its_carry_on_and_compiles_once(
            self, monkeypatch, eight_devices):
        """The first carry is laid out as the program's own output is:
        another layout would be another program, compiled at the second
        launch (and inside a window, for every ``Features`` variant)."""
        import helpers

        coal, req = _coalescer(monkeypatch, fake=False)
        coal.n_device_shards = 4
        flown = [_launch(coal, req, ["e0"])]
        compiled = helpers.backend_compiles()
        flown += [_launch(coal, req, [f"e{i}"]) for i in (1, 2)]
        assert coal._carry_route is coal._mesh
        assert helpers.backend_compiles() == compiled
        assert coal.chained_launches == 2
        carry = np.asarray(coal._carry)
        for d, (b, p, _launch_d) in enumerate(flown[::-1][:CHAIN_DEPTH]):
            _assert_block_is_what_the_resolver_enters(coal, b, p, carry[d])

    def test_a_launch_that_raises_leaves_no_block(self, monkeypatch):
        coal, req = _coalescer(monkeypatch)
        _launch(coal, req, ["a"])

        def boom(*a, **kw):
            raise RuntimeError("the call failed")

        with monkeypatch.context() as mp:
            mp.setattr(fake_device, "fused_place_batch", boom)
            with pytest.raises(RuntimeError):
                _launch(coal, req, ["b"])
            coal._drop_carry()  # what _run does when _dispatch raises
        assert coal._carry is None and not coal._launches[0].block
        _launch(coal, req, ["c"])
        # The launch that raised had been handed a's block; c is handed
        # none, and a is an unresolved predecessor no block stands for.
        assert coal.chained_launches == 1 and coal.chain_overflow == 1


# ---------------------------------------------------------------------------
# (d) through the live server (fake device), launches held in flight
# ---------------------------------------------------------------------------


class TestLiveServer:
    N_JOBS = 96

    def _run(self, monkeypatch, masked):
        if masked:
            # The test hook: the chain is kept as it is, but no launch is
            # told of a live block (the program itself has no switch), and
            # the ledger is read as the parent read it.
            read = ClaimsLedger.overlay

            def parent(self, version, lane_evals=(), stale_before=0,
                       chain=()):
                rows, vals, _live = read(
                    self, version, lane_evals, stale_before
                )
                return rows, vals, (False,) * len(chain)

            monkeypatch.setattr(ClaimsLedger, "overlay", parent)
        # Depth 2: a launch leaves while its predecessor's result is still
        # on its way (the twin answers at once, the fetch pays the
        # latency), as on four chips, where half the launches do.
        srv = _server(
            monkeypatch, latency_ms=15, num_workers=16, coalescer_lanes=8,
            pipeline_depth=2,
        )
        try:
            assert _burst(srv, 1) == ["complete"]
            statuses = _burst(srv, self.N_JOBS)
            _quiet(srv)
            return (
                statuses, _plan_results(srv), _overcommitted(srv),
                srv.coalescer,
            )
        finally:
            srv.shutdown()

    def test_a_burst_with_launches_in_flight_is_refused_less(
            self, monkeypatch):
        statuses, plans, over, coal = self._run(monkeypatch, masked=False)
        with monkeypatch.context() as mp:
            statuses0, plans0, over0, coal0 = self._run(mp, masked=True)
        assert over == over0 == 0
        refused = plans["rejected"] + plans["partial"]
        refused0 = plans0["rejected"] + plans0["partial"]
        # Identical jobs score identically: a launch that cannot see its
        # unresolved predecessor's picks names the same nodes.
        assert coal0.launches_unresolved_predecessor >= 5
        assert refused0 >= 10, (plans0, "the race lost its teeth")
        assert refused * 2 <= refused0, (plans, plans0)
        assert statuses.count("failed") <= statuses0.count("failed")
        assert statuses.count("complete") >= self.N_JOBS - 2, statuses
        assert coal.chained_launches > 0 and coal0.chained_launches == 0
        assert coal.chained_rows_total > 0 and coal0.chained_rows_total == 0
        # Within a launch of one another: a chained launch had a
        # predecessor in flight.
        assert coal.chained_launches <= coal.launches_unresolved_predecessor
        assert coal.chain_overflow == 0  # one predecessor at most, D >= 1
        # Nothing is left behind: every launch resolved, the ledger empty
        # but for what the next launch will release.
        assert all(launch.resolved for launch in coal._launches)
        assert not coal.claims._live and not coal.claims._open
        coal.claims.overlay(coal.matrix.version)
        assert coal.claims.held_rows() == 0

    def test_the_counters_are_on_the_registry(self, monkeypatch):
        srv = _server(monkeypatch, num_workers=4, coalescer_lanes=4)
        try:
            assert _burst(srv, 8).count("complete") >= 7
            snap = srv.metrics.snapshot()
            for key in (
                "nomad.coalescer.chained_launches",
                "nomad.kernel.chained_rows_total",
                "nomad.coalescer.chain_overflow",
                "nomad.coalescer.launches_unresolved_predecessor",
            ):
                assert key in snap, key
        finally:
            srv.shutdown()

    def test_nothing_is_left_behind_after_a_faulted_launch(self, monkeypatch):
        """A wedged launch (the chaos seam ``device.wedge``: its lanes'
        futures raise, the workers nack) entered nothing: its block stops
        counting all the same, and the crowd drains."""
        monkeypatch.setenv("NOMAD_TPU_DEVICE_DEADLINE_MS", "100")
        monkeypatch.setenv("NOMAD_TPU_DEVICE_COLD_SCALE", "1")
        srv = _server(monkeypatch, latency_ms=5, num_workers=8,
                      coalescer_lanes=4, pipeline_depth=2)
        coal = srv.coalescer
        try:
            assert _burst(srv, 1) == ["complete"]
            schedule = [FaultSpec(
                "device.wedge", "wedge", p=1.0, count=1, duration=0.5
            )]
            with injected(seed=3, schedule=schedule):
                statuses = _burst(srv, 24)
            assert coal.wedged_dispatches >= 1
            assert statuses.count("complete") >= 20, statuses
            _quiet(srv, timeout=30.0)
            assert all(launch.resolved for launch in coal._launches)
            assert not coal.claims._live and not coal.claims._open
            coal.claims.overlay(coal.matrix.version)
            assert coal.claims.held_rows() == 0
            assert _overcommitted(srv) == 0
        finally:
            srv.shutdown()


# ---------------------------------------------------------------------------
# the benchmark's readers of the new counters
# ---------------------------------------------------------------------------


@pytest.fixture()
def readers(monkeypatch):
    import importlib
    import os

    bench = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                         "benchmark")
    monkeypatch.syspath_prepend(bench)
    monkeypatch.syspath_prepend(os.path.join(bench, "readers"))
    return {
        name: importlib.import_module(name).read
        for name in ("chained_launch_share", "chained_rows_per_launch")
    }


LAUNCHES = "nomad.kernel.launches{path=fused}"
CHAINED = "nomad.coalescer.chained_launches"
ROWS = "nomad.kernel.chained_rows_total"


@pytest.mark.parametrize("name,m0,m1,want", [
    ("chained_launch_share", {CHAINED: 10, LAUNCHES: 100},
     {CHAINED: 510, LAUNCHES: 1100}, 50.0),  # four chips: one in two
    ("chained_launch_share", {CHAINED: 5, LAUNCHES: 10},
     {CHAINED: 5, LAUNCHES: 30}, 0.0),  # nothing in flight
    ("chained_launch_share", {LAUNCHES: 10}, {LAUNCHES: 30}, None),  # parent
    ("chained_launch_share", {CHAINED: 0, LAUNCHES: 10},
     {CHAINED: 9, LAUNCHES: 10}, None),  # no launch
    ("chained_rows_per_launch", {ROWS: 100, LAUNCHES: 10},
     {ROWS: 700, LAUNCHES: 30}, 30.0),
    ("chained_rows_per_launch", {ROWS: 5, LAUNCHES: 10},
     {ROWS: 5, LAUNCHES: 30}, 0.0),
    ("chained_rows_per_launch", {LAUNCHES: 10}, {LAUNCHES: 30}, None),
    ("chained_rows_per_launch", {ROWS: 0, LAUNCHES: 10},
     {ROWS: 9, LAUNCHES: 10}, None),
], ids=["share-closed_loop", "share-nothing_in_flight", "share-parent",
        "share-no_launch", "rows-closed_loop", "rows-nothing_in_flight",
        "rows-parent", "rows-no_launch"])
def test_chain_readers(readers, name, m0, m1, want):
    assert readers[name]({"m0": m0, "m1": m1}) == want
    assert readers[name]({}) is None


def test_the_benchmark_lists_the_metrics_wherever_evals_per_s_is_read():
    import json
    import os

    root = os.path.dirname(os.path.dirname(__file__))
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    for name in ("chained_launch_share", "chained_rows_per_launch"):
        (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert entry["layer"] == "coalescer"
        assert entry["moves"] == "evals_per_s" and "workloads" not in entry
    # entries are appended, never inserted: the two stand together, after
    # everything older (PR 44's four came after them, PR 46's three after
    # those)
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index("chained_launch_share")
    assert names[at + 1] == "chained_rows_per_launch"
    assert names[at + 2:] == [
        "sched_feasibility_ms", "host_walk_nodes_per_eval",
        "kernel_feasibility_share", "rules_place_batch_roofline",
        "sharded_rules_place_batch_roofline", "rules_exchange_share",
        "class_walk_per_eval"]
