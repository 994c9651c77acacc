"""Fused ranking megakernel vs the solo scan.

The mega-batched fused kernel (ops/kernels.py fused_place_batch) runs B
eval pipelines — feasibility → binpack → spread/affinity → preemption
evict-set → placement scan — PLUS the cross-lane AllocsFit re-verify in
one launch. These tests pin it against each request alone through
``place_task_group`` (the static scan over the dense proposed usage):

* placement parity, lane by lane, on a seeded 1K-node cluster, across
  constraint/affinity/spread/preemption request shapes and in-flight
  deltas; each of the served traffic's shapes alone in a 64-lane launch;
* in-launch pick resolution (contract C1-C3 of ISSUE 30): a lane passes
  over a node only because lanes of the same launch claimed the room it
  needed and takes its best node that is left (VERIFIED 2.0); where no
  node is left it keeps its own pick (VERIFIED 0.0, the applier decides):
  never an empty slot, never a placement past what the lane asked for; a
  launch whose picks never overflow a node is every lane's solo scan;
* dead-lane masking: one compile serves every batch occupancy, and dead
  lanes can never perturb live lanes' outputs or verdicts;
* per-lane step counts: the loops stop at what the lanes asked for, the
  steps a lane asked for are bit for bit those of a full-length launch,
  and the rest is inert;
* the fake-device numpy twin is kernel-exact, ``live_counts`` included;
* the occupancy-bucketed ``Features`` fast path scores identically to
  the full decode.
"""

import numpy as np
import pytest

from nomad_tpu.ops import RequestEncoder, fake_device
from nomad_tpu.ops import kernels
from nomad_tpu.ops.kernels import (
    FUSED_PACKED_VERIFIED,
    FUSED_PACKED_WIDTH,
    fused_place_batch,
)
from nomad_tpu.state import NodeMatrix
from nomad_tpu.structs import (
    Affinity,
    Allocation,
    Constraint,
    DriverInfo,
    Job,
    Node,
    NodeResources,
    Resources,
    Spread,
    Task,
    TaskGroup,
)

from helpers import fill_frontier, lane_operands, solo_reference

SCAN = 4


def make_node(cpu=4000, mem=8192, dc="dc1", node_class="", attrs=None, **kw):
    return Node(
        datacenter=dc,
        node_class=node_class,
        attributes=attrs or {},
        resources=NodeResources(cpu=cpu, memory_mb=mem, disk_mb=100 * 1024),
        drivers={"mock": DriverInfo()},
        **kw,
    )


def make_job(cpu=500, mem=256, count=1, constraints=None, affinities=None,
             spreads=None, **kw):
    tg = TaskGroup(
        name="web",
        count=count,
        tasks=[Task(resources=Resources(cpu=cpu, memory_mb=mem))],
        constraints=constraints or [],
        affinities=affinities or [],
        spreads=spreads or [],
    )
    return Job(task_groups=[tg], **kw)


def host_view(arrays):
    """The device snapshot as numpy arrays, for the numpy twin."""
    return type(arrays)(*[np.asarray(x) for x in arrays])


def steps_of(lane_mask, scan):
    """The fused entry's ``lane_steps`` for lanes that all ask for the
    whole scan: ``scan`` where live, 0 where dead."""
    return np.where(np.asarray(lane_mask, bool), scan, 0).astype(np.int32)


def run_both(m, compiled, scan=SCAN, lane_mask=None, **lanes_kw):
    """Run each request alone (``solo_reference``) and the fused megakernel
    over the same operands; returns (solo (B,P,7), fused (B,P,8)) as numpy."""
    arrays = m.sync()
    ops = lane_operands(m, [c.request for c in compiled], **lanes_kw)
    b = len(compiled)
    lm = np.ones((b,), bool) if lane_mask is None else np.asarray(lane_mask)
    solo = solo_reference(arrays, ops, scan)
    fused = np.asarray(fused_place_batch(
        arrays, arrays.used, *ops, steps_of(lm, scan), n_placements=scan,
    ))
    return solo, fused


def assert_solo_columns_match(solo, fused, lane_mask=None):
    """The fused kernel's first 7 columns must equal the solo scan's on
    every live lane — same feasibility, scores, evict decisions."""
    b = solo.shape[0]
    live = np.ones((b,), bool) if lane_mask is None else np.asarray(lane_mask)
    assert fused.shape == (b, solo.shape[1], FUSED_PACKED_WIDTH)
    np.testing.assert_array_equal(
        fused[live, :, 0].astype(np.int32), solo[live, :, 0].astype(np.int32)
    )
    np.testing.assert_allclose(
        fused[live, :, 1:7], solo[live, :, 1:7], rtol=1e-6, atol=1e-6
    )


def build_cluster_1k():
    """Seeded 1K-node cluster with heterogeneous resources, datacenters,
    classes, attrs, and a population of existing allocations."""
    rng = np.random.default_rng(17)
    m = NodeMatrix(capacity=1024)
    nodes = []
    for i in range(1000):
        node = make_node(
            cpu=int(rng.integers(2000, 16000)),
            mem=int(rng.integers(2048, 32768)),
            dc="dc1" if i % 3 else "dc2",
            node_class=f"class-{i % 4}",
            attrs={
                "rack": f"r{i % 16}",
                "kernel.name": "linux" if i % 5 else "darwin",
                "cpu.numcores": str(int(rng.integers(2, 64))),
            },
        )
        nodes.append(node)
        m.upsert_node(node)
    for i in rng.choice(1000, size=250, replace=False):
        m.add_alloc(Allocation(
            node_id=nodes[i].id,
            job=Job(priority=int(rng.integers(10, 60))),
            resources=Resources(
                cpu=int(rng.integers(100, 1500)),
                memory_mb=int(rng.integers(64, 2048)),
            ),
        ))
    return m, nodes


@pytest.fixture(scope="module")
def cluster_1k():
    return build_cluster_1k()


def compile_lane_mix(m):
    """Six requests covering the pipeline's stages: plain binpack, spread
    algorithm, constraint filter, affinity scoring, spread block, and
    preemption-enabled."""
    enc = RequestEncoder(m)
    lanes = []
    j = make_job(cpu=400, mem=300)
    lanes.append(enc.compile(j, j.task_groups[0]))
    j = make_job(cpu=700, mem=512, count=SCAN)
    lanes.append(enc.compile(j, j.task_groups[0], algorithm="spread"))
    j = make_job(cpu=300, mem=256, constraints=[
        Constraint(l_target="${attr.kernel.name}", operand="=",
                   r_target="linux"),
        Constraint(l_target="${attr.cpu.numcores}", operand=">=",
                   r_target="16"),
    ])
    lanes.append(enc.compile(j, j.task_groups[0]))
    j = make_job(cpu=200, mem=128, affinities=[
        Affinity(l_target="${attr.rack}", operand="=", r_target="r3",
                 weight=80),
    ])
    lanes.append(enc.compile(j, j.task_groups[0]))
    j = make_job(cpu=250, mem=200, count=SCAN,
                 spreads=[Spread(attribute="${node.datacenter}")])
    j.datacenters = ["dc1", "dc2"]
    lanes.append(enc.compile(j, j.task_groups[0]))
    j = make_job(cpu=1500, mem=1024)
    j.priority = 80
    lanes.append(enc.compile(j, j.task_groups[0], preemption_enabled=True))
    return lanes


class TestFusedVsSolo1K:
    def test_parity_on_seeded_cluster(self, cluster_1k):
        m, _ = cluster_1k
        compiled = compile_lane_mix(m)
        solo, fused = run_both(
            m, compiled,
            deltas={1: [(7, (900.0, 512.0, 0.0)), (11, (400.0, 0.0, 0.0))]},
            penalties={0: [3, 5], 3: [40]},
            tg_counts={4: {2: 1, 9: 2}},
        )
        assert_solo_columns_match(solo, fused)
        # The mix must actually exercise the pipeline: placements landed...
        assert (fused[:, 0, 0] >= 0).all()
        # ...and every live placement carries a real verify verdict.
        placed = fused[:, :, 0] >= 0
        assert np.isin(
            fused[:, :, FUSED_PACKED_VERIFIED], [0.0, 1.0, 2.0]
        ).all()
        assert (fused[~placed][:, FUSED_PACKED_VERIFIED] == 1.0).all()

    def test_constraint_lane_filters_match(self, cluster_1k):
        m, nodes = cluster_1k
        _, fused = run_both(m, compile_lane_mix(m))
        # Lane 2's constraints (linux ∧ ≥16 cores) must place on a
        # satisfying node.
        for p in range(SCAN):
            row = int(fused[2, p, 0])
            if row < 0:
                continue
            node = nodes[row]
            assert node.attributes["kernel.name"] == "linux"
            assert int(node.attributes["cpu.numcores"]) >= 16


class TestPreemptionEvictSets:
    def test_fused_preempts_like_solo(self):
        # Nodes saturated by low-priority work: only the preemption lane
        # can place, by evicting — parity including the preempted column.
        m = NodeMatrix(capacity=16)
        nodes = [make_node(cpu=1000, mem=1024) for _ in range(4)]
        for n in nodes:
            m.upsert_node(n)
            m.add_alloc(Allocation(node_id=n.id, job=Job(priority=10),
                                   resources=Resources(cpu=900,
                                                       memory_mb=900)))
        enc = RequestEncoder(m)
        hi = make_job(cpu=500, mem=500)
        hi.priority = 70
        lo = make_job(cpu=500, mem=500)
        compiled = [
            enc.compile(lo, lo.task_groups[0]),
            enc.compile(hi, hi.task_groups[0], preemption_enabled=True),
        ]
        solo, fused = run_both(m, compiled, scan=2)
        assert_solo_columns_match(solo, fused)
        assert int(fused[0, 0, 0]) == -1  # no preemption → no room
        assert int(fused[1, 0, 0]) >= 0
        # placed by evicting: PREEMPT holds the terms of the mean (binpack,
        # preemption)
        assert fused[1, 0, 3] == 2.0
        # Preempted placements verify against *current* usage — the evict
        # set frees capacity only at apply time, so the device-resident
        # AllocsFit conservatively flags them for the applier to re-check.
        assert fused[1, 0, FUSED_PACKED_VERIFIED] == 0.0


class TestAllocsFitRejection:
    def setup_m(self):
        m = NodeMatrix(capacity=16)
        node = make_node(cpu=1000, mem=1024)
        m.upsert_node(node)
        return m, node

    # Two lanes rank against the same snapshot and both want the fuller
    # node, which holds one ask.  (nodes in the cluster, in-flight delta on
    # lane 0) -> what lanes 0 and 1 read: which node, VERIFIED.
    CONFLICTS = {
        # The second lane passes over the node the first one claimed and
        # takes the one that is left: resolved, and it verifies.
        "conflict_resolved": (2, False, ("full", 1.0), ("spare", 2.0)),
        # No node left: the second lane keeps its pick, exactly the
        # conflict plan_apply rejects a round-trip later (never row -1).
        "conflict_no_node_left": (1, False, ("full", 1.0), ("full", 0.0)),
        # Lane 0 carries an in-flight delta claiming the node: its own scan
        # sees it, and lane 1's resolution accounts for it even though
        # lane 1's scores cannot.
        "inflight_delta_resolved": (2, True, ("spare", 1.0), ("spare", 2.0)),
        "inflight_delta_no_node_left": (1, True, (None, 1.0), ("full", 0.0)),
    }

    @pytest.mark.parametrize("case", sorted(CONFLICTS))
    def test_cross_lane_conflict(self, case):
        n_nodes, inflight, want0, want1 = self.CONFLICTS[case]
        m, node = self.setup_m()
        names = {"full": m.row_of[node.id], None: -1}
        if n_nodes == 2:
            # Emptier, so binpack ranks it second; room for both lanes.
            spare = make_node(cpu=4000, mem=4096)
            m.upsert_node(spare)
            names["spare"] = m.row_of[spare.id]
        enc = RequestEncoder(m)
        j = make_job(cpu=600, mem=400)
        c = enc.compile(j, j.task_groups[0])
        deltas = (
            {0: [(names["full"], (600.0, 400.0, 0.0))]} if inflight else None
        )
        solo, fused = run_both(m, [c, c], scan=1, deltas=deltas)
        for lane, (where, verdict) in enumerate((want0, want1)):
            assert int(fused[lane, 0, 0]) == names[where], (case, lane)
            assert fused[lane, 0, FUSED_PACKED_VERIFIED] == verdict
        # C1: a slot is empty only where the lane's own scan leaves it so.
        np.testing.assert_array_equal(fused[:, :, 0] >= 0, solo[:, :, 0] >= 0)

    def test_disjoint_lanes_all_verify(self):
        m = NodeMatrix(capacity=16)
        for _ in range(4):
            m.upsert_node(make_node(cpu=4000, mem=8192))
        enc = RequestEncoder(m)
        compiled = []
        for i in range(3):
            j = make_job(cpu=300 + 50 * i, mem=256)
            compiled.append(enc.compile(j, j.task_groups[0]))
        _, fused = run_both(m, compiled, scan=2)
        assert (fused[:, :, FUSED_PACKED_VERIFIED] == 1.0).all()


ASK_CPU, ASK_MEM = 300, 200
FRONTIER = 40  # nearly full nodes, each with room for exactly one ask


@pytest.fixture(scope="module")
def frontier_1k():
    """``cluster_1k``'s nodes with a frontier: the matrix, its rows that
    hold one more (ASK_CPU, ASK_MEM) ask, and that ask's request (plain
    binpack: the herd)."""
    m, nodes = build_cluster_1k()
    rows = fill_frontier(
        m, nodes, np.random.default_rng(5).choice(1000, FRONTIER, False),
        ASK_CPU, ASK_MEM,
    )
    j = make_job(cpu=ASK_CPU, mem=ASK_MEM, count=8)
    return m, rows, RequestEncoder(m).compile(j, j.task_groups[0]).request


def _mixed_steps(lanes, live, seed):
    """``live`` lanes asking 1-8 each (a Zipf-like mix: mostly narrow),
    dead lanes among them when ``live`` < ``lanes``."""
    rng = np.random.default_rng(seed)
    ls = np.zeros((lanes,), np.int32)
    at = np.sort(rng.choice(lanes, live, replace=False))
    ls[at] = np.minimum(rng.zipf(1.5, live), 8)
    ls[at[0]] = 8  # the launch runs all eight steps
    return ls


class TestPickResolution:
    """ISSUE 30's contract for the lanes of one launch (C1-C3, C5)."""

    # name -> (lanes compiled, live lanes, nodes the lanes may use: None =
    # all, k = only k frontier nodes: fewer free nodes than claims, so the
    # fallback runs)
    CASES = {
        "2_live": (8, 2, None),
        "3_live": (8, 3, None),
        "8_live": (8, 8, None),
        "64_live": (64, 64, None),
        "8_live_4_free_nodes": (8, 8, 4),
    }

    def _launch(self, frontier, case, seed):
        m, frontier_rows, req = frontier
        lanes, n_live, free = self.CASES[case]
        ls = _mixed_steps(lanes, n_live, seed)
        ops = lane_operands(m, [req] * lanes)
        hm = ops[7]
        if free is not None:
            hm[:] = False
            hm[:, frontier_rows[:free]] = True
        arrays = m.sync()
        got = np.asarray(fused_place_batch(
            arrays, arrays.used, *ops, ls, n_placements=8,
        ))
        return m, arrays, ops, ls, got

    @pytest.mark.parametrize("seed", (1, 2))
    @pytest.mark.parametrize("case", sorted(CASES))
    def test_contract(self, frontier_1k, case, seed):
        m, arrays, ops, ls, got = self._launch(frontier_1k, case, seed)
        rows = got[:, :, 0].astype(np.int64)
        vcol = got[:, :, FUSED_PACKED_VERIFIED]
        asked = np.arange(8)[None, :] < ls[:, None]
        host = host_view(arrays)
        ask = np.asarray(ops[5].ask[0])
        free = self.CASES[case][2]

        # C1: no empty slot.  Every slot a lane asked for holds a node
        # exactly where the lane's own scan holds one (here: everywhere,
        # the cluster has room), and nothing lies past what it asked for.
        live = np.flatnonzero(ls > 0)
        solo = solo_reference(arrays, ops, 8, lanes=live[:8])
        for lane, ref in zip(live, solo):
            np.testing.assert_array_equal(
                rows[lane, :ls[lane]] >= 0, ref[:ls[lane], 0] >= 0
            )
        placed = asked & (rows >= 0)
        assert free is not None or placed[asked].all()
        assert (rows[~asked] == -1).all()
        assert (got[~asked][:, 1:7] == 0.0).all()

        # What the launch claims of every node, all lanes together.
        claims = host.used.copy()
        np.add.at(claims, rows[placed], ask)
        over = ~np.all(claims <= host.totals, axis=1)
        room = np.all(claims + ask <= host.totals, axis=1) & (
            fake_device.feasibility_mask(
                host, frontier_1k[2], ops[6][0], ops[7][0]
            )
        )
        if free is None:
            # C3: the frontier was contended (re-picks happened), every
            # placement verifies, and no node is over-committed.
            assert (vcol[placed] == 2.0).any(), "nobody re-picked: no teeth"
            assert np.isin(vcol[placed], (1.0, 2.0)).all()
            assert not over.any()
            # The first lane's first pick is never passed over.
            np.testing.assert_array_equal(got[live[0], 0, :7], solo[0][0])
        else:
            # The fallback: a slot of room on each of the few nodes the
            # lanes may use (a lane's own scan places one ask on each and
            # then runs out, as alone), many more claims.  Each node is
            # taken once for good; every other pick stays the lane's own (a
            # node, never -1), reads 0.0, and over-commits a node only
            # because no node the lane may use had room left.
            taken = len(set(rows[placed]))
            assert 1 < taken <= free < placed.sum()
            assert (np.isin(vcol[placed], (1.0, 2.0))).sum() == taken
            assert (vcol[placed] == 0.0).sum() == placed.sum() - taken
            assert over.sum() <= taken and not room.any()
        assert (vcol[~placed & (ls > 0)[:, None]] == 1.0).all()
        assert (vcol[ls == 0] == -1.0).all()

    @pytest.mark.parametrize("case", sorted(CASES))
    def test_twin_agrees_on_all_eight_columns(self, frontier_1k, case):
        """C5: the numpy twin resolves as the kernel does."""
        m, arrays, ops, ls, got = self._launch(frontier_1k, case, seed=3)
        drows, dvals, tg, sc, pen, reqs, ce, hm = ops
        host = host_view(arrays)
        twin = fake_device.fused_place_batch(
            host, host.used,
            *[list(a) for a in (drows, dvals, tg, sc, pen)],
            [frontier_1k[2]] * len(ls), list(ce), list(hm),
            ls > 0, n_placements=8, live_counts=list(ls),
        )
        for col in (0, 3, 4, 5, 6, FUSED_PACKED_VERIFIED):
            np.testing.assert_array_equal(
                got[:, :, col], twin[:, :, col], err_msg=f"column {col}"
            )
        np.testing.assert_allclose(
            got[:, :, 1:3], twin[:, :, 1:3], rtol=1e-5, atol=1e-5
        )

    # C2: launches whose picks never overflow a node are, bit for bit,
    # every lane's solo scan, with every verdict 1.0.  Each shape edits the
    # host masks and the step counts of eight lanes asking 8 in place.
    def _one_live_lane(hm, ls, frontier_rows):
        ls[:] = 0
        ls[3] = 8

    def _disjoint_lanes(hm, ls, frontier_rows):
        # Lane i may use the rows with row % 8 == i only.
        hm &= (np.arange(hm.shape[1])[None, :] % 8) == np.arange(8)[:, None]

    def _one_step_off_the_frontier(hm, ls, frontier_rows):
        # All eight want the same node, which holds all eight asks.
        hm[:, frontier_rows] = False
        ls[:] = 1

    NO_CONFLICT = {
        "one_live_lane": _one_live_lane,
        "disjoint_lanes": _disjoint_lanes,
        "one_step_off_the_frontier": _one_step_off_the_frontier,
    }

    @pytest.mark.parametrize("shape", sorted(NO_CONFLICT))
    def test_no_conflict_is_the_solo_scan(self, frontier_1k, shape):
        m, frontier_rows, req = frontier_1k
        ops = lane_operands(m, [req] * 8)
        ls = np.full((8,), 8, np.int32)
        self.NO_CONFLICT[shape](ops[7], ls, frontier_rows)
        arrays = m.sync()
        got = np.asarray(fused_place_batch(
            arrays, arrays.used, *ops, ls, n_placements=8,
        ))
        live = np.flatnonzero(ls)
        solo = solo_reference(arrays, ops, 8, lanes=live)
        for lane, ref in zip(live, solo):
            k = ls[lane]
            assert got[lane, :k, :7].tobytes() == ref[:k].tobytes(), lane
        assert (got[live, :, FUSED_PACKED_VERIFIED] == 1.0).all()
        if shape == "one_step_off_the_frontier":
            assert len(set(got[:, 0, 0])) == 1  # they did share the node


class TestDeadLaneMasking:
    def test_occupancy_masking_and_isolation(self):
        m = NodeMatrix(capacity=16)
        for i in range(6):
            m.upsert_node(make_node(cpu=2000 + 500 * i))
        enc = RequestEncoder(m)
        compiled = []
        for i in range(4):
            j = make_job(cpu=200 + 100 * i, mem=128)
            compiled.append(enc.compile(j, j.task_groups[0]))

        _, full = run_both(m, compiled, scan=2)
        for k in (1, 2, 3):
            lm = np.arange(4) < k
            _, part = run_both(m, compiled, scan=2, lane_mask=lm)
            # Dead lanes: inert rows, no verdicts.
            assert (part[k:, :, 0] == -1.0).all()
            assert (part[k:, :, 1:7] == 0.0).all()
            assert (part[k:, :, FUSED_PACKED_VERIFIED] == -1.0).all()
            # Live lanes bit-identical to the full-occupancy run: dead
            # lanes contribute nothing to placement OR verify.
            np.testing.assert_array_equal(part[:k], full[:k])

    def test_one_compile_serves_all_occupancies(self):
        # The whole point of lane masking: occupancy changes must not be
        # recompile triggers (lint rule J004 guards the call sites; this
        # guards the kernel itself).
        m = NodeMatrix(capacity=16)
        for i in range(4):
            m.upsert_node(make_node())
        enc = RequestEncoder(m)
        j = make_job()
        compiled = [enc.compile(j, j.task_groups[0])] * 3
        before = fused_place_batch._cache_size()
        for k in (1, 2, 3):
            run_both(m, compiled, scan=2, lane_mask=np.arange(3) < k)
        added = fused_place_batch._cache_size() - before
        assert added <= 1, (
            f"batch occupancy triggered {added} fused-kernel compiles"
        )


class TestFakeDeviceTwinParity:
    def test_twin_matches_kernel(self, cluster_1k):
        """The numpy twin must be bit-compatible with the jax megakernel
        across the full lane mix at full length, including a dead lane,
        in-flight deltas, and the verify column."""
        m, _ = cluster_1k
        compiled = compile_lane_mix(m)
        arrays = m.sync()
        b = len(compiled)
        lm = np.ones((b,), bool)
        lm[3] = False
        deltas = {1: [(7, (900.0, 512.0, 0.0))]}
        drows, dvals, tg, sc, pen, reqs, ce, hm = lane_operands(
            m, [c.request for c in compiled], deltas=deltas,
            penalties={0: [3, 5]},
        )
        kernel = np.asarray(fused_place_batch(
            arrays, arrays.used, drows, dvals, tg, sc, pen, reqs, ce, hm,
            steps_of(lm, SCAN), n_placements=SCAN,
        ))
        arrays_np = host_view(arrays)
        twin = fake_device.fused_place_batch(
            arrays_np, arrays_np.used,
            [drows[i] for i in range(b)], [dvals[i] for i in range(b)],
            [tg[i] for i in range(b)], [sc[i] for i in range(b)],
            [pen[i] for i in range(b)],
            [c.request for c in compiled],
            [ce[i] for i in range(b)], [hm[i] for i in range(b)],
            lm, n_placements=SCAN,
        )
        assert twin.shape == kernel.shape
        np.testing.assert_array_equal(
            twin[:, :, 0].astype(np.int32), kernel[:, :, 0].astype(np.int32)
        )
        np.testing.assert_allclose(twin[:, :, 1:7], kernel[:, :, 1:7],
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(
            twin[:, :, FUSED_PACKED_VERIFIED],
            kernel[:, :, FUSED_PACKED_VERIFIED],
        )


FULL = 16  # the live scan length (stack.PLACEMENT_CHUNK)


def _first_repick(lane_rows) -> int:
    """The first step at which a lane's pick can have depended on the other
    lanes: it took another node than its own arg-max (VERIFIED 2.0), or
    reads 0.0 (which hides whether it did); the lane's length where its
    picks were all its own."""
    hit = np.flatnonzero(lane_rows[:, FUSED_PACKED_VERIFIED] != 1.0)
    return int(hit[0]) if len(hit) else len(lane_rows)

# Placements each lane's caller consumes (what stack.py hands place() as
# n_live); None = a dead lane.
N_LIVE_CASES = {
    # ROADMAP R4's shape: every lane wants the whole scan — the launch
    # must give what the static 16-step program gave, bit for bit.
    "all_16": [16] * 8,
    "all_1": [1] * 8,
    "mixed_1_to_8": [1, 3, None, 8, 2, None, 5, None],
    # "not said" and "more than the scan holds" both mean all of it.
    "zero_and_40": [0, 40, 2, 0, None, 40, 1, 7],
}


class TestLaneStepCounts:
    """The fused kernel's loops take their trip counts from ``lane_steps``:
    same answers for the steps that were asked for, inert rows after."""

    def _launch(self, m, n_live):
        """(lane_steps, fused kernel as a function of lane_steps, and the
        twin / the solo static scan over the same operands, both lazy)."""
        from nomad_tpu.scheduler.coalescer import lane_step_count

        mix = compile_lane_mix(m)
        compiled = (mix + mix)[: len(n_live)]
        arrays = m.sync()
        steps = np.array(
            [0 if k is None else lane_step_count(k, FULL) for k in n_live],
            np.int32,
        )
        ops = lane_operands(
            m, [c.request for c in compiled],
            deltas={1: [(7, (900.0, 512.0, 0.0)), (11, (400.0, 0.0, 0.0))],
                    6: [(272, (300.0, 100.0, 0.0))]},
            penalties={0: [3, 5]}, tg_counts={4: {2: 1, 9: 2}},
        )
        drows, dvals, tg, sc, pen, reqs, ce, hm = ops

        def kernel(ls):
            return np.asarray(fused_place_batch(
                arrays, arrays.used, drows, dvals, tg, sc, pen, reqs, ce,
                hm, ls, n_placements=FULL,
            ))

        def twin():
            arrays_np = host_view(arrays)
            return fake_device.fused_place_batch(
                arrays_np, arrays_np.used,
                *[list(a) for a in (drows, dvals, tg, sc, pen)],
                [c.request for c in compiled], list(ce), list(hm),
                steps > 0, n_placements=FULL, live_counts=list(steps),
            )

        def solo():
            return solo_reference(arrays, ops, FULL)

        return steps, kernel, twin, solo

    @pytest.mark.parametrize("case", sorted(N_LIVE_CASES))
    def test_kernel_matches_twin_with_live_counts(self, cluster_1k, case):
        m, _ = cluster_1k
        steps, kernel, twin, solo = self._launch(m, N_LIVE_CASES[case])
        got, twin = kernel(steps), twin()
        assert got.shape == twin.shape == (len(steps), FULL, FUSED_PACKED_WIDTH)
        # All eight columns: everything that decides or describes a
        # placement exactly, the two scores to float32 rounding (numpy and
        # XLA order their sums differently).
        for col in (0, 3, 4, 5, 6, FUSED_PACKED_VERIFIED):
            np.testing.assert_array_equal(
                got[:, :, col], twin[:, :, col], err_msg=f"column {col}"
            )
        np.testing.assert_allclose(
            got[:, :, 1:3], twin[:, :, 1:3], rtol=1e-5, atol=1e-5
        )
        # The case must have teeth: live lanes placed something.
        assert (got[steps > 0, 0, 0] >= 0).all()
        for lane, k in enumerate(steps):
            # Rows past what the lane asked for are inert: row -1, zeros,
            # "fits" (dead lanes: -1.0, no verdict).
            tail = got[lane, k:]
            assert (tail[:, 0] == -1.0).all()
            assert (tail[:, 1:7] == 0.0).all()
            assert (
                tail[:, FUSED_PACKED_VERIFIED] == (1.0 if k else -1.0)
            ).all()
        if (steps == FULL).all():
            # Nothing to cut: until a lane first re-picks (an earlier lane
            # of the launch claimed the room its own pick needed), its
            # placement columns are bit for bit the static 16-step scan's
            # (place_task_group still runs one); the first lane's first
            # pick is always its own.
            assert (got[:, :, FUSED_PACKED_VERIFIED] == 2.0).any()
            solo = solo()
            for lane in range(len(steps)):
                n = _first_repick(got[lane])
                assert n > 0 or lane > 0
                np.testing.assert_array_equal(got[lane, :n, :7], solo[lane, :n])

    @pytest.mark.parametrize(
        "case", [c for c in sorted(N_LIVE_CASES) if c != "all_16"]
    )
    def test_asked_steps_are_the_full_launch_bitwise(self, cluster_1k, case):
        """Same work, not less: the rows a lane asked for are bit for bit
        that lane's rows in a launch where every lane runs all 16 (the
        later steps never fed back into the earlier ones), up to the step
        at which the lane first re-picks in either launch: from there its
        picks depend on what the other lanes claimed, and in the full
        launch they claim more.  The verdict column is left out: it reads
        the OTHER lanes' commits, and those no longer include placements
        nobody asked for."""
        m, _ = cluster_1k
        steps, kernel, _, _ = self._launch(m, N_LIVE_CASES[case])
        cut = kernel(steps)
        full = kernel(np.where(steps > 0, FULL, 0).astype(np.int32))
        compared = 0
        for lane, k in enumerate(steps):
            n = min(k, _first_repick(cut[lane]), _first_repick(full[lane]))
            assert cut[lane, :n, :7].tobytes() == full[lane, :n, :7].tobytes()
            compared += n
        assert compared >= steps.sum() // 2

    def test_short_lane_is_not_charged_beside_a_wide_one(self):
        """A lane that asked for 1 beside a lane that asked for 4 takes no
        phantom placement at steps 2-4: its usage stays out of the
        cross-lane verify image, so the next lane's verdict is true."""
        m = NodeMatrix(capacity=16)
        node = make_node(cpu=1000, mem=1024)
        m.upsert_node(node)
        enc = RequestEncoder(m)
        small = make_job(cpu=300, mem=100)
        c = enc.compile(small, small.task_groups[0])
        arrays = m.sync()
        out = np.asarray(fused_place_batch(
            arrays, arrays.used, *lane_operands(m, [c.request] * 3),
            np.array([1, 4, 1], np.int32), n_placements=4,
        ))
        row = m.row_of[node.id]
        assert out[0, :, 0].tolist() == [row, -1, -1, -1]
        assert out[1, :3, 0].tolist() == [row, row, row]  # 3 x 300 fit
        # Committed before lane 1: lane 0's ONE placement (300), so lane
        # 1's first two fit (600, 900) and its third does not (1200).
        assert out[1, :3, FUSED_PACKED_VERIFIED].tolist() == [1.0, 1.0, 0.0]

    def test_bool_mask_is_refused(self, cluster_1k):
        """A bool lane mask would silently read as one step a lane."""
        m, _ = cluster_1k
        _, kernel, _, _ = self._launch(m, [16] * 8)
        with pytest.raises(TypeError, match="lane_steps"):
            kernel(np.ones((8,), bool))


# ---------------------------------------------------------------------------
# Each shape of the served traffic alone in a launch of the server's width
# ---------------------------------------------------------------------------

SERVED_LANES = 64  # ServerConfig.coalescer_lanes
SERVED_SHAPES = [f"shape{i}" for i in range(8)] + ["preempting"]


@pytest.fixture(scope="module")
def served_shapes():
    """A seeded simcluster matrix, ``simcluster.build_requests``' eight
    shapes and one request that has to preempt (built as chip_smoke.py
    builds its own)."""
    from nomad_tpu import mock, simcluster

    m = simcluster.build_cluster(480, 512, 96_000, seed=29)
    shapes = simcluster.build_requests(m)
    assert len(shapes) == 8
    pjob = mock.job(priority=90)
    # More cpu than any node has left (the seeded usage is at least ~850
    # of 3,900 MHz): with room anywhere there is no eviction.
    pjob.task_groups[0].tasks[0].resources.cpu = 3200
    pjob.task_groups[0].tasks[0].resources.memory_mb = 2600
    preempting = RequestEncoder(m).compile(
        pjob, pjob.task_groups[0], preemption_enabled=True
    ).request
    return m, dict(zip(SERVED_SHAPES, shapes + [preempting]))


@pytest.mark.parametrize("shape", SERVED_SHAPES)
def test_each_shape_alone_matches_solo(served_shapes, shape):
    """One live lane among 63 dead ones, at FULL_FEATURES (one compile for
    all nine): all eight columns are the request's own solo scan and the
    numpy twin's.  A mix cannot show which shape a disagreement is in."""
    m, by_name = served_shapes
    req = by_name[shape]
    arrays = m.sync()
    # Lane 0 is the shape under test; the dead lanes hold another shape so
    # that a leak across lanes would show.
    other = by_name["shape0" if shape != "shape0" else "shape3"]
    ops = lane_operands(m, [req] + [other] * (SERVED_LANES - 1))
    ls = np.zeros((SERVED_LANES,), np.int32)
    ls[0] = FULL
    got = np.asarray(fused_place_batch(
        arrays, arrays.used, *ops, ls, n_placements=FULL,
        features=kernels.FULL_FEATURES,
    ))
    assert got.shape == (SERVED_LANES, FULL, FUSED_PACKED_WIDTH)
    assert (got[0, :, 0] >= 0).all(), "the shape placed nothing"
    if shape == "preempting":
        assert (got[0, :, 3] >= 2.0).all(), "placed without an eviction"

    solo = solo_reference(arrays, ops, FULL, lanes=[0])
    np.testing.assert_array_equal(got[0, :, :7], solo[0])

    drows, dvals, tg, sc, pen, _, ce, hm = ops
    arrays_np = host_view(arrays)
    twin = fake_device.fused_place_batch(
        arrays_np, arrays_np.used, [drows[0]], [dvals[0]], [tg[0]], [sc[0]],
        [pen[0]], [req], [ce[0]], [hm[0]], np.ones((1,), bool),
        n_placements=FULL,
    )
    for col in (0, 3, 4, 5, 6, FUSED_PACKED_VERIFIED):
        np.testing.assert_array_equal(
            got[0, :, col], twin[0, :, col], err_msg=f"column {col}"
        )
    np.testing.assert_allclose(
        got[0, :, 1:3], twin[0, :, 1:3], rtol=1e-5, atol=1e-5
    )
    # The dead lanes stayed dead.
    assert (got[1:, :, 0] == -1.0).all()
    assert (got[1:, :, FUSED_PACKED_VERIFIED] == -1.0).all()


class TestFeaturesBucketing:
    def test_measured_features_match_full_decode(self, cluster_1k):
        """The occupancy-bucketed slim decode must score identically to
        the full decode — features only prune provably-inert work."""
        m, _ = cluster_1k
        compiled = compile_lane_mix(m)
        arrays = m.sync()
        ops = lane_operands(m, [c.request for c in compiled])
        ls = np.full((len(compiled),), SCAN, np.int32)
        feats = kernels.features_of(ops[5])
        full = np.asarray(fused_place_batch(
            arrays, arrays.used, *ops, ls, n_placements=SCAN,
            features=kernels.FULL_FEATURES,
        ))
        slim = np.asarray(fused_place_batch(
            arrays, arrays.used, *ops, ls, n_placements=SCAN, features=feats,
        ))
        np.testing.assert_array_equal(
            slim[:, :, 0].astype(np.int32), full[:, :, 0].astype(np.int32)
        )
        np.testing.assert_allclose(slim[:, :, 1:], full[:, :, 1:],
                                   rtol=1e-6, atol=1e-6)

    def test_widen_is_monotone_union(self):
        m = NodeMatrix(capacity=16)
        m.upsert_node(make_node(attrs={"rack": "r1"}))
        enc = RequestEncoder(m)
        plain = make_job()
        fancy = make_job(
            constraints=[Constraint(l_target="${attr.rack}", operand="=",
                                    r_target="r1")],
            affinities=[Affinity(l_target="${attr.rack}", operand="=",
                                 r_target="r1", weight=50)],
            spreads=[Spread(attribute="${node.datacenter}")],
        )
        fa = kernels.features_of(enc.compile(plain,
                                             plain.task_groups[0]).request)
        fb = kernels.features_of(enc.compile(fancy,
                                             fancy.task_groups[0]).request)
        w = fa.widen(fb)
        assert w == fb.widen(fa)
        assert w.widen(fa) == w and w.widen(fb) == w
        assert w.c_width >= max(fa.c_width, fb.c_width)
        assert w.s_width >= max(fa.s_width, fb.s_width)
