"""`nomad top` dashboard: the render path is a pure function of two
successive metric snapshots + the SLO/health reports, so the layout is
unit-testable without a server; `run_top --count N` is exercised
against a stub client."""

from __future__ import annotations

import io

from nomad_tpu.obs.top import CLEAR, render, run_top


def _metrics(evals=100, uptime=42):
    return {
        "uptime_s": uptime,
        "nomad.worker.evals_processed": evals,
        "nomad.plan.applied": evals,
        "nomad.broker.total_ready": 2,
        "nomad.broker.total_unacked": 1,
        "nomad.broker.total_pending": 0,
        "nomad.blocked_evals.total_blocked": 3,
        "nomad.plan.queue_depth": 1,
        "nomad.coalescer.inflight_depth": 2,
        "nomad.coalescer.pipeline_depth": 8,
        "nomad.coalescer.lane_fill_ratio": 0.75,
        "nomad.coalescer.stale_dispatches": 0,
        "nomad.phase.plan.apply": {
            "count": 50, "p50_ms": 0.5, "p99_ms": 2.0,
        },
        "nomad.phase.coalescer.device": {
            "count": 50, "p50_ms": 1.0, "p99_ms": 9.0,
        },
        "version": "x",  # non-numeric entries must not crash rendering
    }


def _slo():
    return {"slos": [{
        "name": "placement_latency_p99_ms", "objective": "nomad.eval.latency",
        "kind": "timer", "op": "<", "target": 5.0, "value": 3.91,
        "status": "ok", "burn_rate_fast": 0.4, "burn_rate_slow": 0.2,
        "windows_s": [60.0, 300.0], "budget": 0.05, "samples": [12, 40],
        "breached_since": None, "description": "",
    }]}


def _health():
    return {"status": "ok", "score": 97.3, "pressure": 0.027,
            "inputs": {}, "breached_slos": []}


class TestRender:
    def test_headline_and_queues(self):
        out = render(_metrics(), _slo(), _health(),
                     address="http://x:4646", interval=2.0)
        assert "health: ok" in out
        assert "score 97.3" in out
        assert "uptime 42s" in out
        assert "broker r/u/p: 2/1/0" in out
        assert "blocked: 3" in out
        assert "2/8 in flight" in out
        assert "lane fill 0.75" in out

    def test_shard_balance_row(self):
        cur = _metrics()
        cur.update({
            "nomad.matrix.shard_rows{shard=0}": 3,
            "nomad.matrix.shard_rows{shard=1}": 5,
            "nomad.matrix.shard_rows{shard=2}": 4,
            "nomad.matrix.shard_rows{shard=3}": 4,
            "nomad.topk.host_bytes_total": 2048,
        })
        out = render(cur, None, None)
        assert "rows 3/5/4/4" in out
        assert "skew 1.25" in out  # max 5 / mean 4
        assert "topk host bytes 2048" in out
        # A single-shard (or unsharded) matrix renders no shard row.
        assert "shards  :" not in render(_metrics(), None, None)

    def test_runtime_row_reads_work_and_waiting_between_snapshots(self):
        def snap(worker, api, native, wakes, late, stall):
            g = "nomad.runtime.cpu_seconds{group=%s}"
            return dict(_metrics(), **{
                g % "worker": worker, g % "http-api": api,
                g % "native": native,
                g % "process": worker + api + native,
                "nomad.runtime.wakes_total": wakes,
                "nomad.runtime.wake_late_seconds_total": late,
                "nomad.runtime.stall_seconds_total": stall,
            })

        prev = snap(10.0, 2.0, 5.0, 1000, 1.0, 0.0)
        cur = snap(11.2, 2.6, 5.5, 1200, 2.0, 0.4)
        out = render(cur, None, None, prev_metrics=prev, interval=2.0)
        (row,) = [ln for ln in out.splitlines() if ln.startswith("runtime :")]
        assert "interpreter busy  90.0%" in row      # 1.8 s of 2 s
        assert "native  25.0%" in row
        assert "wake late 5.00 ms" in row            # 1 s over 200 wakes
        assert "stalls 0.4s" in row
        # No row on the first frame, or from a server without the gauges.
        assert "runtime :" not in render(cur, None, None)
        assert "runtime :" not in render(
            _metrics(), None, None, prev_metrics=_metrics(), interval=2.0)

    def test_rates_are_deltas_between_snapshots(self):
        prev = _metrics(evals=100)
        cur = _metrics(evals=300)
        out = render(cur, _slo(), _health(), prev_metrics=prev,
                     interval=2.0)
        assert "evals/s :    100.0" in out  # (300-100)/2s
        # First frame has no prev: rates read 0, never garbage.
        first = render(cur, _slo(), _health(), interval=2.0)
        assert "evals/s :      0.0" in first

    def test_phase_table_sorted_by_where_time_goes(self):
        out = render(_metrics(), None, None)
        lines = out.splitlines()
        dev = next(i for i, l in enumerate(lines)
                   if "coalescer.device" in l)
        apply_ = next(i for i, l in enumerate(lines) if "plan.apply" in l)
        assert dev < apply_  # 50×9.0 > 50×2.0: device row first

    def test_slo_row_and_missing_reports(self):
        out = render(_metrics(), _slo(), _health())
        assert "placement_latency_p99_ms" in out
        assert "<5.0" in out
        # A follower (or a 501) yields slo/health None — still renders.
        bare = render(_metrics(), None, None)
        assert "health: ?" in bare

    def test_events_footer(self):
        out = render(_metrics(), _slo(), _health(),
                     events=["12:02:11 SLO SLOBreached placement_latency_p99_ms"])
        assert "events:" in out
        assert "SLOBreached" in out


class _StubClient:
    address = "http://stub:4646"
    token = ""

    def __init__(self):
        self.calls = 0

    def metrics(self):
        self.calls += 1
        return _metrics(evals=self.calls * 100)

    def slo(self):
        return _slo()

    def health(self):
        return _health()


class TestRunTop:
    def test_count_frames_then_exit(self):
        client = _StubClient()
        out = io.StringIO()
        rc = run_top(client, interval=0.01, count=3, clear=False, out=out)
        assert rc == 0
        assert client.calls == 3
        text = out.getvalue()
        assert CLEAR not in text  # --no-clear honored
        assert text.count("nomad top — http://stub:4646") == 3

    def test_endpoint_errors_degrade_gracefully(self):
        client = _StubClient()
        client.slo = lambda: (_ for _ in ()).throw(RuntimeError("501"))
        out = io.StringIO()
        rc = run_top(client, interval=0.01, count=1, clear=True, out=out)
        assert rc == 0
        assert "health: ok" in out.getvalue()
