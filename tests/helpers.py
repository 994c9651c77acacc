"""Shared test helpers (the tier-2 in-process agent pattern, SURVEY.md §4).

Kept in one module so wait/crash semantics can't drift between suites.
"""

from __future__ import annotations

import time

from nomad_tpu.client import Client, ClientConfig


def _wait(pred, timeout=30.0, every=0.05):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(every)
    return pred()


def _small(job):
    """Shrink a mock job's asks so many fit on one mock node."""
    for tg in job.task_groups:
        for t in tg.tasks:
            t.resources.cpu = 20
            t.resources.memory_mb = 32
        tg.ephemeral_disk.size_mb = 10
    return job


def _client(server, tmp_path, name, **cfg) -> Client:
    c = Client(server, ClientConfig(data_dir=str(tmp_path / name), **cfg))
    c.start()
    return c


def _crash_client(client):
    """Simulate an agent crash: stop loops WITHOUT destroying allocs or
    killing tasks (Client.shutdown would tear the tasks down)."""
    client._shutdown.set()
    with client._dirty_cond:
        client._dirty_cond.notify_all()


def _live(server, job):
    return [
        a for a in server.store.allocs_by_job(job.namespace, job.id)
        if not a.terminal_status()
    ]


def lane_operands(matrix, requests, deltas=None, penalties=None,
                  tg_counts=None, max_deltas=4):
    """The per-lane operands of ``fused_place_batch`` between ``used`` and
    ``lane_steps``, in the entry's order: (delta_rows, delta_vals,
    tg_counts, spread_counts, penalties, reqs, class_eligs, host_masks)
    for ``requests`` with no plan state but what the overrides say.

    ``ops.encode.RequestSlab`` stacks the requests, as the coalescer does;
    what it cannot give is the other seven operands, whose shapes (the
    class-count padding in particular) must stay in step with the kernel.

    deltas: {lane: [(row, (cpu, mem, disk)), ...]} in-flight deltas;
    penalties: {lane: [row, ...]}; tg_counts: {lane: {row: count}}.
    """
    import numpy as np

    from nomad_tpu.ops.encode import RequestSlab, pow2_bucket

    b, n = len(requests), int(matrix.capacity)
    slab = RequestSlab(b)
    for i, req in enumerate(requests):
        slab.fill(i, req)
    drows = np.full((b, max_deltas), -1, np.int32)
    dvals = np.zeros((b, max_deltas, 3), np.float32)
    for lane, items in (deltas or {}).items():
        for j, (row, vals) in enumerate(items):
            drows[lane, j] = row
            dvals[lane, j] = vals
    pen = np.zeros((b, n), bool)
    for lane, rows in (penalties or {}).items():
        pen[lane, list(rows)] = True
    tg = np.zeros((b, n), np.int32)
    for lane, counts in (tg_counts or {}).items():
        for row, c in counts.items():
            tg[lane, row] = c
    sc = np.zeros((b,) + np.asarray(requests[0].s_value_hash).shape,
                  np.float32)
    ce = np.ones((b, max(2, pow2_bucket(len(matrix.class_ids)))), bool)
    hm = np.ones((b, n), bool)
    return drows, dvals, tg, sc, pen, slab.batch(), ce, hm


def solo_reference(arrays, operands, n_placements, lanes=None):
    """Each lane (or each of ``lanes``) of ``operands`` (``lane_operands``'
    tuple) alone through ``place_task_group`` (the static scan over the
    dense proposed usage), packed (B, P, 7) in the ``PACKED_*`` column
    order: what a lane of the batched program must read, sparse deltas
    included."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from nomad_tpu.ops import kernels

    drows, dvals, tg, sc, pen, reqs, ce, hm = operands
    out = []
    for i in (range(len(drows)) if lanes is None else lanes):
        live = drows[i] >= 0
        used0 = arrays.used.at[drows[i][live]].add(dvals[i][live])
        r = kernels.place_task_group(
            arrays, jax.tree_util.tree_map(lambda x: x[i], reqs), used0,
            jnp.asarray(tg[i]), jnp.asarray(sc[i]), jnp.asarray(pen[i]),
            jnp.asarray(ce[i]), jnp.asarray(hm[i]), n_placements,
        )
        out.append(np.stack([
            np.asarray(c, np.float32) for c in (
                r.rows, r.scores, r.binpack, r.preempted, r.nodes_evaluated,
                r.nodes_filtered, r.nodes_exhausted,
            )
        ], axis=1))
    return np.stack(out)


def fill_frontier(matrix, nodes, picks, ask_cpu, ask_mem, seed=0):
    """Fill ``nodes[i]`` for i in ``picks`` until each holds room for
    exactly ONE (ask_cpu MHz, ask_mem MB) ask: the frontier of nearly full
    nodes that binpack ranks first and that the lanes of one launch all
    want.  Returns their matrix rows."""
    import numpy as np

    from nomad_tpu.structs import Allocation, Job, Resources

    rng = np.random.default_rng(seed)
    host = matrix.snapshot_host()
    rows = []
    for i in picks:
        row = matrix.row_of[nodes[i].id]
        free_cpu, free_mem = (host["totals"][row] - host["used"][row])[:2]
        assert free_cpu >= 2 * ask_cpu and free_mem >= 2 * ask_mem
        matrix.add_alloc(Allocation(
            node_id=nodes[i].id,
            job=Job(priority=50),
            resources=Resources(
                cpu=int(free_cpu - ask_cpu - rng.integers(0, ask_cpu)),
                memory_mb=int(free_mem - ask_mem - rng.integers(0, ask_mem)),
            ),
        ))
        rows.append(row)
    return rows
