"""Bench regression ledger gates: an injected 2x latency regression
must flag `regress`, noise within 1 MAD must stay `flat`, and both
BENCH_*.json shapes (driver wrapper, flat result) must ingest."""

from __future__ import annotations

import json
import os
import sys

import pytest

TOOLS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "tools")
sys.path.insert(0, TOOLS)

import bench_history  # noqa: E402


def _seed_ledger(path, metric, values):
    for v in values:
        bench_history.append_entry(str(path), {
            "ts": 0.0, "source": "seed", "ok": True,
            "metrics": {metric: v}, "meta": {},
        })


class TestVerdicts:
    def test_2x_latency_regression_flags_regress(self, tmp_path):
        ledger = tmp_path / "ledger.jsonl"
        # Tight baseline around 100ms (MAD 1ms), then a 2x run.
        _seed_ledger(ledger, "e2e_p99_ms",
                     [99.0, 100.0, 101.0, 100.0, 99.5, 100.5])
        entry = bench_history.record_run(
            {"e2e_p99_ms": 200.0}, source="test", ledger=str(ledger))
        v = entry["verdicts"]["e2e_p99_ms"]
        assert v["verdict"] == "regress", v
        assert v["deviation"] == pytest.approx(100.0)

    def test_2x_throughput_drop_flags_regress(self, tmp_path):
        ledger = tmp_path / "ledger.jsonl"
        _seed_ledger(ledger, "eval_throughput",
                     [980.0, 1000.0, 1020.0, 1000.0])
        entry = bench_history.record_run(
            {"eval_throughput": 500.0}, source="test", ledger=str(ledger))
        assert entry["verdicts"]["eval_throughput"]["verdict"] == "regress"

    def test_improvement_flags_improve(self, tmp_path):
        ledger = tmp_path / "ledger.jsonl"
        _seed_ledger(ledger, "e2e_p99_ms",
                     [99.0, 100.0, 101.0, 100.0])
        entry = bench_history.record_run(
            {"e2e_p99_ms": 50.0}, source="test", ledger=str(ledger))
        assert entry["verdicts"]["e2e_p99_ms"]["verdict"] == "improve"

    def test_noise_within_one_mad_is_flat(self, tmp_path):
        ledger = tmp_path / "ledger.jsonl"
        values = [95.0, 100.0, 105.0, 98.0, 102.0, 100.0]
        _seed_ledger(ledger, "e2e_p99_ms", values)
        med = bench_history._median(values)
        mad = bench_history._mad(values, med)
        assert mad > 0
        entry = bench_history.record_run(
            {"e2e_p99_ms": med + mad},  # one MAD above the median
            source="test", ledger=str(ledger))
        assert entry["verdicts"]["e2e_p99_ms"]["verdict"] == "flat"

    def test_short_history_is_new_not_judged(self, tmp_path):
        ledger = tmp_path / "ledger.jsonl"
        _seed_ledger(ledger, "e2e_p99_ms", [100.0])
        entry = bench_history.record_run(
            {"e2e_p99_ms": 500.0}, source="test", ledger=str(ledger))
        assert entry["verdicts"]["e2e_p99_ms"]["verdict"] == "new"

    def test_failed_runs_excluded_from_baseline(self, tmp_path):
        ledger = tmp_path / "ledger.jsonl"
        _seed_ledger(ledger, "e2e_p99_ms", [100.0, 100.0, 100.0])
        # A crashed run with a garbage number must not widen the gate.
        bench_history.append_entry(str(ledger), {
            "ts": 0.0, "source": "crash", "ok": False,
            "metrics": {"e2e_p99_ms": 9999.0}, "meta": {},
        })
        entry = bench_history.record_run(
            {"e2e_p99_ms": 200.0}, source="test", ledger=str(ledger))
        assert entry["verdicts"]["e2e_p99_ms"]["verdict"] == "regress"

    def test_undirected_metrics_never_judged(self, tmp_path):
        ledger = tmp_path / "ledger.jsonl"
        _seed_ledger(ledger, "nodes", [100.0, 100.0, 100.0, 100.0])
        entry = bench_history.record_run(
            {"nodes": 5000.0}, source="test", ledger=str(ledger))
        assert "nodes" not in entry["verdicts"]
        assert entry["metrics"]["nodes"] == 5000.0  # recorded regardless


class TestDirectionInference:
    def test_known_directions(self):
        d = bench_history.direction
        assert d("eval_throughput") == 1
        assert d("live_pipeline_evals_per_sec_depth8") == 1
        assert d("live_pipeline_speedup") == 1
        assert d("e2e_p99_ms") == -1
        assert d("setup_s") == -1
        assert d("live_pipeline_latency_ms") == -1
        assert d("nodes") is None
        assert d("batch") is None


class TestNormalization:
    def test_wrapper_shape_with_parsed(self):
        raw = {"n": 3, "cmd": "python bench.py", "rc": 0, "tail": "...",
               "parsed": {"metric": "eval_throughput", "value": 969.5,
                          "p99_ms": 266.0, "platform": "tpu"}}
        entry = bench_history.normalize(raw, source="BENCH_r03.json")
        assert entry["ok"] is True
        assert entry["metrics"]["eval_throughput"] == 969.5
        assert entry["metrics"]["p99_ms"] == 266.0
        assert "platform" not in entry["metrics"]  # strings are not metrics

    def test_wrapper_shape_crashed_run(self):
        raw = {"n": 1, "cmd": "python bench.py", "rc": 1,
               "tail": "Traceback ...", "parsed": None}
        entry = bench_history.normalize(raw, source="BENCH_r01.json")
        assert entry["ok"] is False
        assert entry["metrics"] == {}

    def test_flat_dict_shape(self):
        entry = bench_history.normalize(
            {"live_pipeline_evals_per_sec_depth8": 101.4,
             "phase": "live_pipeline"})
        assert entry["ok"] is True
        assert entry["metrics"]["live_pipeline_evals_per_sec_depth8"] == 101.4
        assert entry["meta"]["phase"] == "live_pipeline"

    def test_nested_dicts_flatten_to_dotted_keys(self):
        entry = bench_history.normalize(
            {"e2e_host_only_phase_ms": {"plan.apply": {"p99_ms": 2.5}}})
        assert entry["metrics"][
            "e2e_host_only_phase_ms.plan.apply.p99_ms"] == 2.5


def _write_bench_files(tmp_path):
    """One file per input shape the ledger ingests: two driver wrappers
    (a parsed run and a crashed one) and a flat bench.py result."""
    files = {
        "BENCH_a.json": {
            "n": 1, "cmd": "python bench.py", "rc": 1,
            "tail": "Traceback ...", "parsed": None,
        },
        "BENCH_b.json": {
            "n": 2, "cmd": "python bench.py", "rc": 0, "tail": "...",
            "parsed": {"metric": "eval_throughput", "value": 1245.5,
                       "p99_ms": 41.2, "platform": "cpu",
                       "device_kind": "cpu", "device_count": 1},
        },
        "BENCH_c.json": {
            "live_pipeline_evals_per_sec_depth8": 101.4,
            "live_pipeline_speedup": 6.1, "phase": "live_pipeline",
        },
    }
    paths = []
    for name, payload in sorted(files.items()):
        path = tmp_path / name
        path.write_text(json.dumps(payload))
        paths.append(str(path))
    return paths


class TestRealFiles:
    def test_bench_files_ingest(self, tmp_path):
        files = _write_bench_files(tmp_path)
        ledger = tmp_path / "ledger.jsonl"
        rc = bench_history.main(
            ["--ledger", str(ledger), "ingest"] + files)
        assert rc == 0
        entries = bench_history.read_ledger(str(ledger))
        assert len(entries) == len(files)
        ok = [e for e in entries if e["ok"]]
        assert len(ok) == len(files) - 1  # the crashed run, rest parsed
        assert all(e["metrics"] for e in ok)

    def test_ingested_ledger_parses(self, tmp_path):
        ledger = str(tmp_path / "ledger.jsonl")
        bench_history.main(
            ["--ledger", ledger, "ingest"] + _write_bench_files(tmp_path))
        entries = bench_history.read_ledger(ledger)
        sources = {e["source"] for e in entries}
        assert sources == {"BENCH_a.json", "BENCH_b.json", "BENCH_c.json"}

    def test_report_runs_on_ingested_ledger(self, tmp_path, capsys):
        ledger = str(tmp_path / "ledger.jsonl")
        bench_history.main(
            ["--ledger", ledger, "ingest"] + _write_bench_files(tmp_path))
        capsys.readouterr()
        rc = bench_history.main(
            ["--ledger", ledger, "report", "--last", "10"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "runs shown" in out
