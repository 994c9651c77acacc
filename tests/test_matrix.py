"""Tests for the device-resident NodeMatrix encoding."""

import numpy as np
import pytest

from nomad_tpu.state import NodeMatrix, priority_bucket, stable_hash, numeric_value
from nomad_tpu.state import matrix as matrix_mod
from nomad_tpu.state.matrix import DeviceArrays, scatter_bucket
from nomad_tpu.structs import (
    Allocation,
    DriverInfo,
    Job,
    Node,
    NodeReservedResources,
    NodeResources,
    Resources,
)
from helpers import assert_bits_equal, dirty_hard_rows, host_mirror


def make_node(**kw):
    defaults = dict(
        resources=NodeResources(cpu=4000, memory_mb=8192, disk_mb=100 * 1024),
        drivers={"mock": DriverInfo()},
    )
    defaults.update(kw)
    return Node(**defaults)


class TestEncoding:
    def test_stable_hash_nonzero(self):
        assert stable_hash("") != 0
        assert stable_hash("dc1") == stable_hash("dc1")
        assert stable_hash("dc1") != stable_hash("dc2")

    def test_numeric_value(self):
        assert numeric_value("42") == 42.0
        assert numeric_value("1.5") == 1.5
        assert np.isnan(numeric_value("1.2.3"))
        assert np.isnan(numeric_value("amd64"))

    def test_version_value(self):
        from nomad_tpu.state.matrix import version_value

        assert version_value("1.2.3") == 1e6 + 2e3 + 3
        assert version_value("2.0") == 2e6
        assert version_value("2") == 2e6
        assert version_value("v1.1.0") == 1e6 + 1e3
        assert np.isnan(version_value("amd64"))
        assert np.isnan(version_value("1.2.3.4"))

    def test_priority_bucket_bounds(self):
        assert priority_bucket(0) == 0
        assert priority_bucket(1) >= 0
        assert priority_bucket(100) == 15
        assert priority_bucket(50) < priority_bucket(90)


class TestNodeMatrix:
    def test_upsert_and_rows(self):
        m = NodeMatrix(capacity=16)
        n1, n2 = make_node(datacenter="dc1"), make_node(datacenter="dc2")
        r1, r2 = m.upsert_node(n1), m.upsert_node(n2)
        assert r1 != r2
        host = m.snapshot_host()
        assert host["eligible"][r1] and host["eligible"][r2]
        # totals = comparable resources
        assert host["totals"][r1][0] == 4000
        # datacenter is attr slot 0 (well-known registry order)
        assert host["attr_hash"][r1][0] == stable_hash("dc1")
        assert host["attr_hash"][r2][0] == stable_hash("dc2")

    def test_reserved_subtracted(self):
        m = NodeMatrix()
        node = make_node(reserved=NodeReservedResources(cpu=500, memory_mb=512))
        row = m.upsert_node(node)
        assert m.snapshot_host()["totals"][row][0] == 3500

    def test_alloc_accounting(self):
        m = NodeMatrix()
        node = make_node()
        row = m.upsert_node(node)
        job = Job(priority=50)
        alloc = Allocation(
            node_id=node.id, job=job, resources=Resources(cpu=1000, memory_mb=512)
        )
        m.add_alloc(alloc)
        host = m.snapshot_host()
        assert host["used"][row][0] == 1000
        assert host["prio_used"][row, priority_bucket(50), 0] == 1000
        m.remove_alloc(alloc)
        assert m.snapshot_host()["used"][row][0] == 0

    def test_class_dedup(self):
        m = NodeMatrix()
        a = make_node(node_class="web", attributes={"cpu.arch": "amd64"})
        b = make_node(node_class="web", attributes={"cpu.arch": "amd64"})
        c = make_node(node_class="db", attributes={"cpu.arch": "arm64"})
        ra, rb, rc = m.upsert_node(a), m.upsert_node(b), m.upsert_node(c)
        host = m.snapshot_host()
        # identical non-unique attrs → same computed class (node_class.go:28).
        assert host["class_id"][ra] == host["class_id"][rb]
        assert host["class_id"][ra] != host["class_id"][rc]

    def test_remove_and_reuse_row(self):
        m = NodeMatrix()
        n1 = make_node()
        r1 = m.upsert_node(n1)
        m.remove_node(n1.id)
        assert not m.snapshot_host()["eligible"][r1]
        n2 = make_node()
        r2 = m.upsert_node(n2)
        assert r2 == r1  # freed row reused

    def test_growth(self):
        m = NodeMatrix(capacity=16)
        nodes = [make_node() for _ in range(40)]
        for n in nodes:
            m.upsert_node(n)
        assert m.capacity >= 40
        assert m.snapshot_host()["eligible"][: m.n_rows].sum() == 40

    def test_device_sync_incremental(self):
        m = NodeMatrix()
        n1 = make_node()
        m.upsert_node(n1)
        d1 = m.sync()
        assert bool(d1.eligible[0])
        # Mutate and re-sync: scatter path.
        job = Job()
        m.add_alloc(
            Allocation(node_id=n1.id, job=job, resources=Resources(cpu=700, memory_mb=1))
        )
        d2 = m.sync()
        assert float(d2.used[0, 0]) == 700.0

    def test_gpu_devices(self):
        m = NodeMatrix()
        node = make_node()
        node.resources.devices = {"nvidia/gpu": ["a", "b"]}
        row = m.upsert_node(node)
        slot = m.devices.lookup("nvidia/gpu")
        assert m.snapshot_host()["dev_total"][row, slot] == 2


class TestPackedScatter:
    """A sync hands the device ONE host operand: the dirty rows' twelve
    fields and their index in one packed buffer (ROADMAP S1b1)."""

    @staticmethod
    def _synced(n=40):
        m = NodeMatrix(capacity=64)
        nodes = [make_node() for _ in range(n)]
        for node in nodes:
            m.upsert_node(node)
        return m, m.sync()

    @pytest.mark.parametrize("k", [1, 2, 3, 33])
    def test_bit_for_bit_against_a_per_field_set(self, k):
        m, before = self._synced()
        rows = np.arange(5, 5 + k, dtype=np.int32)[::-1].copy()
        dirty_hard_rows(m, rows)
        pack = m._pack_rows(rows)
        bucket = scatter_bucket(k)
        assert pack.dtype == np.uint8 and pack.shape[0] == bucket
        # The index rides in the pack's last field; the tail of a padded
        # bucket repeats the first row.
        idx = pack[:, -4:].copy().view(np.int32)[:, 0]
        assert idx[:k].tolist() == rows.tolist()
        assert idx[k:].tolist() == [int(rows[0])] * (bucket - k)
        got = matrix_mod.make_row_scatter()(before, pack)
        want = DeviceArrays(*(
            x.at[idx].set(m._alloc[f][idx])
            for f, x in zip(DeviceArrays._fields, before)
        ))
        assert_bits_equal(got, want, f"{k} rows against .at[idx].set")
        assert_bits_equal(got, host_mirror(m), f"{k} rows against the mirror")
        # ... and the snapshot it was handed is left as it was: launches
        # in flight still read it (no donation).
        assert np.asarray(before.eligible).tobytes() != (
            m._alloc["eligible"].tobytes())

    def test_a_sync_hands_over_exactly_one_operand(self, monkeypatch):
        m, _ = self._synced()
        calls = []
        real = matrix_mod._scatter_rows
        monkeypatch.setattr(
            matrix_mod, "_scatter_rows",
            lambda *operands: calls.append(operands) or real(*operands),
        )
        bytes0 = m.upload_bytes_total
        for n, k in enumerate([1, 2, 3, 33], start=1):
            dirty_hard_rows(m, range(k))
            dev = m.sync()
            assert_bits_equal(dev, host_mirror(m), f"sync of {k} rows")
            assert (m.scatter_syncs, m.scatter_operands_total) == (n, n)
            device, pack = calls[-1]  # the matrix and ONE host buffer
            assert isinstance(pack, np.ndarray) and pack.dtype == np.uint8
            assert m.upload_bytes_total - bytes0 == pack.nbytes
            bytes0 = m.upload_bytes_total
        assert m.rows_scattered_total == 1 + 2 + 3 + 33
        # A clean sync hands over nothing.
        m.sync()
        assert (m.scatter_syncs, m.scatter_operands_total) == (4, 4)
        assert len(calls) == 4

    def test_a_scatter_that_raises_puts_the_drained_rows_back(
            self, monkeypatch):
        m, before = self._synced()
        dirty_hard_rows(m, [3, 9, 17])

        def boom(device, pack):
            raise RuntimeError("device lost")

        monkeypatch.setattr(matrix_mod, "_scatter_rows", boom)
        with pytest.raises(RuntimeError, match="device lost"):
            m.sync()
        assert m._dirty == {3, 9, 17}
        assert m._device is before
        assert (m.scatter_syncs, m.scatter_operands_total,
                m.rows_scattered_total) == (0, 0, 0)
        monkeypatch.undo()
        assert_bits_equal(m.sync(), host_mirror(m), "the retry")
        assert m._dirty == set() and m.scatter_operands_total == 1
