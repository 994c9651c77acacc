"""The analyzer's own test suite: every rule id fires on a minimal
fixture and stays quiet on the matching clean idiom, plus the baseline
machinery and the TSan-lite runtime half.

Fixture paths matter: lock resolution keys on the repo-relative module
suffix (lint/lock_order.py ALIASES), so fixtures masquerade as the real
modules they exercise rules against.
"""

from __future__ import annotations

import textwrap
import threading

from nomad_tpu.lint import Baseline, Finding, load_baseline, split_baselined
from nomad_tpu.lint import chaospass, jaxpass, lockpass, obspass, tsan

_dedent = textwrap.dedent


def _lock_findings(src: str, path: str = "nomad_tpu/state/matrix.py"):
    return lockpass.analyze_sources({path: textwrap.dedent(src)})


def _rules(findings):
    return [f.rule for f in findings]


# ----------------------------------------------------------------------
# L001 — lock-order inversion
# ----------------------------------------------------------------------

class TestL001:
    def test_direct_inversion_fires(self):
        fs = _lock_findings(
            """
            class NodeMatrix:
                def bad(self):
                    with self._host_lock:
                        with DEVICE_LOCK:
                            pass
            """
        )
        assert "L001" in _rules(fs), fs

    def test_declared_order_is_clean(self):
        fs = _lock_findings(
            """
            class NodeMatrix:
                def good(self):
                    with DEVICE_LOCK:
                        with self._host_lock:
                            pass
            """
        )
        assert "L001" not in _rules(fs), fs

    def test_inversion_via_call_fires(self):
        # bad() holds matrix.host and calls a method whose body acquires
        # the device lock — the one-level interprocedural walk sees it.
        fs = _lock_findings(
            """
            class NodeMatrix:
                def _grab_device(self):
                    with DEVICE_LOCK:
                        pass

                def bad(self):
                    with self._host_lock:
                        self._grab_device()
            """
        )
        assert "L001" in _rules(fs), fs

    def test_reentrant_reacquire_is_clean(self):
        # install_snapshot's shape: the outer frame already holds the
        # (reentrant) outermost lock; a callee re-acquiring it adds no
        # ordering edge.
        fs = lockpass.analyze_sources({
            "nomad_tpu/state/store.py": textwrap.dedent(
                """
                class StateStore:
                    def _inner(self):
                        with self._write_lock:
                            pass

                    def ok(self):
                        with self._write_lock, self._lock:
                            self._inner()
                """
            )
        })
        assert "L001" not in _rules(fs), fs


# ----------------------------------------------------------------------
# L002 — Condition.wait while holding a foreign lock
# ----------------------------------------------------------------------

class TestL002:
    def test_wait_with_foreign_lock_fires(self):
        fs = lockpass.analyze_sources({
            "nomad_tpu/state/store.py": textwrap.dedent(
                """
                class StateStore:
                    def bad(self):
                        with self._lock:
                            with self._watch_cond:
                                self._watch_cond.wait()
                """
            )
        })
        assert "L002" in _rules(fs), fs

    def test_wait_on_own_condvar_is_clean(self):
        fs = lockpass.analyze_sources({
            "nomad_tpu/state/store.py": textwrap.dedent(
                """
                class StateStore:
                    def good(self):
                        with self._watch_cond:
                            self._watch_cond.wait()
                """
            )
        })
        assert "L002" not in _rules(fs), fs


# ----------------------------------------------------------------------
# L003 — blocking call inside a critical section
# ----------------------------------------------------------------------

class TestL003:
    def test_sleep_under_lock_fires(self):
        fs = _lock_findings(
            """
            import time

            class NodeMatrix:
                def bad(self):
                    with self._host_lock:
                        time.sleep(0.1)
            """
        )
        assert "L003" in _rules(fs), fs

    def test_sleep_outside_lock_is_clean(self):
        fs = _lock_findings(
            """
            import time

            class NodeMatrix:
                def good(self):
                    with self._host_lock:
                        pass
                    time.sleep(0.1)
            """
        )
        assert "L003" not in _rules(fs), fs

    def test_device_fetch_under_lock_fires(self):
        fs = _lock_findings(
            """
            class NodeMatrix:
                def bad(self, x):
                    with self._host_lock:
                        return np.asarray(x)
            """
        )
        assert "L003" in _rules(fs), fs

    def test_device_ops_under_device_lock_are_exempt(self):
        # Launch/upload under DEVICE_LOCK is that lock's purpose.
        fs = _lock_findings(
            """
            class NodeMatrix:
                def good(self):
                    with DEVICE_LOCK:
                        self.sync()
            """
        )
        assert "L003" not in _rules(fs), fs


# ----------------------------------------------------------------------
# L004 — literal-bounded condvar wait
# ----------------------------------------------------------------------

class TestL004:
    def test_literal_timeout_fires(self):
        fs = lockpass.analyze_sources({
            "nomad_tpu/state/store.py": textwrap.dedent(
                """
                class StateStore:
                    def bad(self):
                        with self._watch_cond:
                            self._watch_cond.wait(0.2)
                """
            )
        })
        assert "L004" in _rules(fs), fs

    def test_literal_via_ifexp_assignment_fires(self):
        # The exact coalescer._next_batch shape this rule was built for.
        fs = lockpass.analyze_sources({
            "nomad_tpu/state/store.py": textwrap.dedent(
                """
                class StateStore:
                    def bad(self):
                        with self._watch_cond:
                            timeout = 0.2 if self.busy else None
                            self._watch_cond.wait_for(lambda: True, timeout=timeout)
                """
            )
        })
        assert "L004" in _rules(fs), fs

    def test_untimed_wait_is_clean(self):
        fs = lockpass.analyze_sources({
            "nomad_tpu/state/store.py": textwrap.dedent(
                """
                class StateStore:
                    def good(self):
                        with self._watch_cond:
                            self._watch_cond.wait()
                """
            )
        })
        assert "L004" not in _rules(fs), fs

    def test_parameter_timeout_is_clean(self):
        # Caller-supplied deadlines (wait_for_index) are an API contract,
        # not a lost-notify workaround.
        fs = lockpass.analyze_sources({
            "nomad_tpu/state/store.py": textwrap.dedent(
                """
                class StateStore:
                    def good(self, timeout=None):
                        with self._watch_cond:
                            self._watch_cond.wait(timeout)
                """
            )
        })
        assert "L004" not in _rules(fs), fs


# ----------------------------------------------------------------------
# J001–J003 — JAX hot path
# ----------------------------------------------------------------------

class TestJaxPass:
    def test_host_sync_on_device_value_fires(self):
        fs = jaxpass.analyze_sources({
            "nomad_tpu/ops/fixture.py": textwrap.dedent(
                """
                def bad(a, b):
                    x = jnp.dot(a, b)
                    return float(x)
                """
            )
        })
        assert "J001" in _rules(fs), fs

    def test_asarray_on_device_chain_fires(self):
        fs = jaxpass.analyze_sources({
            "nomad_tpu/ops/fixture.py": textwrap.dedent(
                """
                def bad(arrays):
                    packed = fused_place_batch_live(arrays)
                    return np.asarray(packed)
                """
            )
        })
        assert "J001" in _rules(fs), fs

    def test_host_value_sync_is_clean(self):
        fs = jaxpass.analyze_sources({
            "nomad_tpu/ops/fixture.py": textwrap.dedent(
                """
                def good(rows):
                    total = sum(rows)
                    return float(total)
                """
            )
        })
        assert "J001" not in _rules(fs), fs

    def test_jit_captured_mutable_global_fires(self):
        fs = jaxpass.analyze_sources({
            "nomad_tpu/ops/fixture.py": textwrap.dedent(
                """
                SCALE = [1.0, 2.0]

                @jax.jit
                def bad(x):
                    return x * SCALE[0]
                """
            )
        })
        assert "J002" in _rules(fs), fs

    def test_jit_reading_immutable_global_is_clean(self):
        fs = jaxpass.analyze_sources({
            "nomad_tpu/ops/fixture.py": textwrap.dedent(
                """
                SCALE = 2.0

                @jax.jit
                def good(x):
                    return x * SCALE
                """
            )
        })
        assert "J002" not in _rules(fs), fs

    def test_mutable_static_arg_fires(self):
        fs = jaxpass.analyze_sources({
            "nomad_tpu/ops/fixture.py": textwrap.dedent(
                """
                kernel = jax.jit(_impl, static_argnames=("shape",))

                def bad(x):
                    return kernel(x, shape=[4, 4])
                """
            )
        })
        assert "J003" in _rules(fs), fs

    def test_hashable_static_arg_is_clean(self):
        fs = jaxpass.analyze_sources({
            "nomad_tpu/ops/fixture.py": textwrap.dedent(
                """
                kernel = jax.jit(_impl, static_argnames=("shape",))

                def good(x):
                    return kernel(x, shape=(4, 4))
                """
            )
        })
        assert "J003" not in _rules(fs), fs


# ----------------------------------------------------------------------
# J004 — fused-path recompile triggers
# ----------------------------------------------------------------------

class TestJ004FusedRecompile:
    def test_stacked_comprehension_operand_fires(self):
        fs = jaxpass.analyze_sources({
            "nomad_tpu/scheduler/coalescer.py": textwrap.dedent(
                """
                def bad(self, arrays, batch):
                    return kernels.fused_place_batch(
                        arrays, arrays.used,
                        np.stack([p.delta_rows for p in batch]),
                        self.lane_steps, n_placements=4,
                    )
                """
            )
        })
        assert "J004" in _rules(fs), fs

    def test_tree_map_stacked_requests_fire(self):
        # The exact anti-pattern the RequestSlab replaced: restacking the
        # request pytree per dispatch.
        fs = jaxpass.analyze_sources({
            "nomad_tpu/scheduler/coalescer.py": textwrap.dedent(
                """
                def bad(self, arrays, batch, ls):
                    reqs = jax.tree_util.tree_map(
                        lambda *xs: np.stack(xs),
                        *[p.request for p in batch],
                    )
                    return kernels.fused_place_batch_live(
                        arrays, arrays.used, reqs, ls, n_placements=4,
                    )
                """
            )
        })
        assert "J004" in _rules(fs), fs

    def test_batch_derived_static_arg_fires(self):
        fs = jaxpass.analyze_sources({
            "nomad_tpu/scheduler/coalescer.py": textwrap.dedent(
                """
                def bad(self, arrays, batch, reqs, ls):
                    return kernels.fused_place_batch(
                        arrays, arrays.used, reqs, ls,
                        n_placements=len(batch),
                    )
                """
            )
        })
        assert "J004" in _rules(fs), fs

    def test_slab_operands_and_config_statics_are_clean(self):
        fs = jaxpass.analyze_sources({
            "nomad_tpu/scheduler/coalescer.py": textwrap.dedent(
                """
                def good(self, arrays, ls):
                    reqs = self._req_slab.batch()
                    return kernels.fused_place_batch_live(
                        arrays, arrays.used, reqs, ls,
                        n_placements=self.scan_length,
                        features=self._features,
                    )
                """
            )
        })
        assert "J004" not in _rules(fs), fs

    def test_fake_device_twin_is_exempt(self):
        # The numpy twin takes per-lane lists by design — no compile
        # cache to poison.
        fs = jaxpass.analyze_sources({
            "nomad_tpu/scheduler/coalescer.py": textwrap.dedent(
                """
                def good(self, arrays, batch):
                    return fake_device.fused_place_batch(
                        arrays, arrays.used,
                        np.stack([p.delta_rows for p in batch]),
                        n_placements=4,
                        live_counts=[p.n_live for p in batch],
                    )
                """
            )
        })
        assert "J004" not in _rules(fs), fs


class TestJ005NodeAxisFetch:
    def test_asarray_on_arrays_leaf_fires(self):
        fs = jaxpass.analyze_sources({
            "nomad_tpu/scheduler/coalescer.py": textwrap.dedent(
                """
                def bad(self, arrays, dr, dv, reqs, ls):
                    packed = self._sharded_fused_fn(
                        arrays, arrays.used, dr, dv, reqs, ls,
                    )
                    snapshot = np.asarray(arrays.used)
                    return packed, snapshot
                """
            )
        })
        assert "J005" in _rules(fs), fs

    def test_block_until_ready_via_local_hop_fires(self):
        fs = jaxpass.analyze_sources({
            "nomad_tpu/scheduler/coalescer.py": textwrap.dedent(
                """
                def bad(self, arrays, dr, dv, reqs, ls):
                    u = arrays.used
                    u.block_until_ready()
                    return kernels.fused_place_batch(
                        arrays, u, dr, dv, reqs, ls, n_placements=1,
                    )
                """
            )
        })
        assert "J005" in _rules(fs), fs

    def test_placement_result_node_field_fires(self):
        fs = jaxpass.analyze_sources({
            "nomad_tpu/scheduler/coalescer.py": textwrap.dedent(
                """
                def bad(self, arrays, dr, dv, reqs, ls):
                    res = self._sharded_fused_fn(arrays, reqs, ls)
                    return np.asarray(res.used_after)
                """
            )
        })
        assert "J005" in _rules(fs), fs

    def test_packed_winner_fetch_is_clean(self):
        # The contract-conformant fetch: only the (B, P, 8) packed winner
        # block crosses the boundary.
        fs = jaxpass.analyze_sources({
            "nomad_tpu/scheduler/coalescer.py": textwrap.dedent(
                """
                def good(self, arrays, dr, dv, reqs, ls):
                    packed = self._sharded_fused_fn(
                        arrays, arrays.used, dr, dv, reqs, ls,
                    )
                    return packed
                """
            )
        })
        assert "J005" not in _rules(fs), fs

    def test_node_fetch_off_the_fused_path_is_not_j005(self):
        # Fetching a node-axis array in a function that never drives the
        # fused/sharded entry points is sync discipline (J001 territory),
        # not a sharded-contract violation.
        fs = jaxpass.analyze_sources({
            "nomad_tpu/state/matrix.py": textwrap.dedent(
                """
                def snapshot_usage(self, arrays):
                    return np.asarray(arrays.used)
                """
            )
        })
        assert "J005" not in _rules(fs), fs

    def test_one_hop_helper_evasion_is_a_documented_miss(self):
        # KNOWN EVASION, kept as a pinned expected-miss: J005 tracks
        # node-axis leaves through LOCAL variables only, so threading the
        # fetch through one helper function defeats it — `_snapshot` is
        # an opaque call, and its np.asarray happens in a function that
        # never touches the fused entry points (exactly the shape
        # test_node_fetch_off_the_fused_path_is_not_j005 exempts).
        # Closing this lexically would mean whole-program dataflow; the
        # semantic layer covers it instead: the same leak traced to a
        # jaxpr is an N-shaped value crossing the mesh boundary, which
        # fires J103 whatever the Python call graph looked like
        # (tests/test_jaxprpass.py::test_j103_catches_the_j005_helper_evasion).
        # If this assertion ever flips, J005 grew dataflow tracking —
        # celebrate, then delete the J103 cross-reference above.
        fs = jaxpass.analyze_sources({
            "nomad_tpu/scheduler/coalescer.py": textwrap.dedent(
                """
                def _snapshot(x):
                    return np.asarray(x)

                def evades(self, arrays, dr, dv, reqs, ls):
                    packed = self._sharded_fused_fn(
                        arrays, arrays.used, dr, dv, reqs, ls,
                    )
                    return packed, _snapshot(arrays.used)
                """
            )
        })
        assert "J005" not in _rules(fs), (
            "J005 now sees through helper calls — update this fixture "
            "and the STATIC_ANALYSIS.md evasion note"
        )


# ----------------------------------------------------------------------
# C001–C004 — chaos seams
# ----------------------------------------------------------------------

_DOC = """
## Seam catalog

| Seam | Where | ctx keys | Kinds honored |
|---|---|---|---|
| `rpc.call` | `api/rpc.py` | path | drop |
| `ghost.seam` | `gone.py` | x | drop |
| `lonely.seam` | `real.py` | x | drop |

## Retry policy surface (`nomad_tpu/retry.py`)

RPC failover (`api/rpc.py`), bare loop (`client/naked.py`).
"""


class TestChaosPass:
    def _analyze(self, **over):
        kw = dict(
            doc=_DOC,
            code_seams={
                "rpc.call": [("nomad_tpu/api/rpc.py", 10)],
                "lonely.seam": [("nomad_tpu/real.py", 5)],
                "rogue.seam": [("nomad_tpu/rogue.py", 7)],
            },
            exercised={"rpc.call"},
            retry_sources={
                "api/rpc.py": "x = retry_call(fn, RetryPolicy())",
                "client/naked.py": "while True: time.sleep(1)",
            },
        )
        kw.update(over)
        return chaospass.analyze(**kw)

    def test_stale_documented_seam_fires_c001(self):
        fs = self._analyze()
        stale = [f for f in fs if f.rule == "C001"]
        assert len(stale) == 1 and stale[0].symbol == "ghost.seam", fs

    def test_undocumented_code_seam_fires_c002(self):
        fs = self._analyze()
        rogue = [f for f in fs if f.rule == "C002"]
        assert len(rogue) == 1 and rogue[0].symbol == "rogue.seam", fs

    def test_unexercised_seam_fires_c003(self):
        fs = self._analyze()
        dead = [f for f in fs if f.rule == "C003"]
        assert len(dead) == 1 and dead[0].symbol == "lonely.seam", fs

    def test_retry_drift_fires_c004(self):
        fs = self._analyze()
        drift = [f for f in fs if f.rule == "C004"]
        assert len(drift) == 1 and drift[0].symbol == "client/naked.py", fs

    def test_consistent_surface_is_clean(self):
        fs = self._analyze(
            code_seams={
                "rpc.call": [("nomad_tpu/api/rpc.py", 10)],
                "ghost.seam": [("nomad_tpu/gone.py", 3)],
                "lonely.seam": [("nomad_tpu/real.py", 5)],
            },
            exercised={"rpc.call", "ghost.seam", "lonely.seam"},
            retry_sources={
                "api/rpc.py": "retry_call(fn)",
                "client/naked.py": "RetryPolicy()",
            },
        )
        assert fs == [], fs

    def test_real_doc_parses(self):
        from nomad_tpu.lint import repo_root

        import os

        with open(os.path.join(repo_root(), "CHAOS.md")) as fh:
            seams, retry_mods = chaospass.parse_doc(fh.read())
        assert "rpc.call" in seams and "raft.send" in seams
        assert any(m.endswith("rpc.py") for m in retry_mods)


# ----------------------------------------------------------------------
# Observability pass (O001)
# ----------------------------------------------------------------------

class TestObsPass:
    def test_seam_without_trace_fires_o001(self):
        fs = obspass.analyze_module("nomad_tpu/m.py", _dedent('''
            from ..chaos import inject

            def hot_path():
                fault = inject("wal.write", op="x")
                return fault
        '''))
        assert len(fs) == 1 and fs[0].rule == "O001", fs
        assert fs[0].symbol == "hot_path"
        assert "wal.write" in fs[0].message

    def test_direct_emission_is_clean(self):
        fs = obspass.analyze_module("nomad_tpu/m.py", _dedent('''
            from .. import trace
            from ..chaos import inject

            def hot_path():
                fault = inject("wal.write", op="x")
                trace.event("seam.wal.write", op="x")
        '''))
        assert fs == [], fs

    def test_span_counts_as_emission(self):
        fs = obspass.analyze_module("nomad_tpu/m.py", _dedent('''
            from .. import trace
            from ..chaos import inject

            def hot_path():
                inject("rpc.call", path="/x")
                with trace.span("rpc.send"):
                    pass
        '''))
        assert fs == [], fs

    def test_emitting_wrapper_covers_callers(self):
        # driver.py's pattern: a local _chaos guard emits the event for
        # every caller, so call sites need no trace call of their own.
        fs = obspass.analyze_module("nomad_tpu/m.py", _dedent('''
            from .. import trace
            from ..chaos import inject

            def _chaos(point, **kw):
                f = inject(point, **kw)
                trace.event("seam." + point, **kw)
                return f

            def start_task():
                _chaos("driver.start", driver="d")
        '''))
        assert fs == [], fs

    def test_silent_wrapper_flags_callers(self):
        fs = obspass.analyze_module("nomad_tpu/m.py", _dedent('''
            from ..chaos import inject

            def _chaos(point, **kw):
                return inject(point, **kw)

            def start_task():
                _chaos("driver.start", driver="d")
        '''))
        assert any(f.symbol == "start_task" for f in fs), fs

    def test_nested_def_does_not_leak_emission(self):
        # A trace call inside an inner closure is not on the seam's path.
        fs = obspass.analyze_module("nomad_tpu/m.py", _dedent('''
            from .. import trace
            from ..chaos import inject

            def outer():
                inject("wal.write", op="x")
                def unrelated():
                    trace.event("elsewhere")
        '''))
        assert len(fs) == 1 and fs[0].symbol == "outer", fs

    def test_production_tree_is_clean(self):
        from nomad_tpu.lint import repo_root

        assert obspass.run(repo_root()) == []


class TestO002SloObjectives:
    def test_unregistered_objective_fires(self):
        reg = obspass.collect_metric_names(
            'm = metrics.timer("nomad.eval.latency")')
        fs = obspass.analyze_slo_objectives("nomad_tpu/m.py", _dedent('''
            from .obs import SLOSpec

            SPECS = [SLOSpec(name="lat", objective="nomad.evals.latency",
                             op="<", target=5.0)]
        '''), reg)
        assert len(fs) == 1 and fs[0].rule == "O002", fs
        assert fs[0].symbol == "lat"
        assert "nomad.evals.latency" in fs[0].message

    def test_registered_objective_is_clean(self):
        reg = obspass.collect_metric_names(
            'metrics.timer("nomad.eval.latency")')
        fs = obspass.analyze_slo_objectives("nomad_tpu/m.py", _dedent('''
            SPECS = [SLOSpec(name="lat", objective="nomad.eval.latency",
                             op="<", target=5.0)]
        '''), reg)
        assert fs == [], fs

    def test_name_universe_covers_all_registration_shapes(self):
        reg = obspass.collect_metric_names(_dedent('''
            def setup(metrics, trace, snap):
                metrics.timer("nomad.a.timer")
                metrics.incr("nomad.b.counter")
                metrics.gauge_fn("nomad.c.gauge", lambda: 0)
                with trace.span("plan.apply"):
                    pass
                snap["nomad.d.handrolled"] = 1
        '''))
        assert reg == {
            "nomad.a.timer", "nomad.b.counter", "nomad.c.gauge",
            "nomad.phase.plan.apply", "nomad.d.handrolled",
        }

    def test_positional_objective_checked(self):
        fs = obspass.analyze_slo_objectives(
            "nomad_tpu/m.py",
            'S = SLOSpec("lat", "nomad.bogus", "<", 5.0)',
            {"nomad.real"},
        )
        assert len(fs) == 1 and fs[0].symbol == "lat", fs

    def test_dynamic_objective_out_of_scope(self):
        # Only literals are checked — a computed name can't be resolved
        # statically and must not flag.
        fs = obspass.analyze_slo_objectives("nomad_tpu/m.py", _dedent('''
            def make(name):
                return SLOSpec(name="x", objective=name, op="<", target=1.0)
        '''), set())
        assert fs == [], fs

    def test_default_slos_resolve_in_production_tree(self):
        # The shipped specs must stay wired to real metrics: collect the
        # whole package's name universe, check obs/slo.py against it.
        from nomad_tpu.lint import repo_root

        root = repo_root()
        registered = set()
        for rel, src in obspass._walk_sources(root):
            registered |= obspass.collect_metric_names(src)
        import os as _os
        with open(_os.path.join(root, "nomad_tpu", "obs", "slo.py")) as fh:
            src = fh.read()
        assert obspass.analyze_slo_objectives(
            "nomad_tpu/obs/slo.py", src, registered) == []


class TestO003Actuators:
    def test_silent_actuator_fires(self):
        fs = obspass.analyze_actuators("nomad_tpu/m.py", _dedent('''
            def engage(self):
                self.server.admission_gate.set_gate_level(0.5)
        '''))
        assert len(fs) == 1 and fs[0].rule == "O003", fs
        assert fs[0].symbol == "engage"
        assert "set_gate_level" in fs[0].message

    def test_trace_and_counter_is_clean(self):
        fs = obspass.analyze_actuators("nomad_tpu/m.py", _dedent('''
            def engage(self):
                self.server.admission_gate.set_gate_level(0.5)
                self.server.eval_broker.set_shedding(True)
                trace.event("seam.controller.actuate", target="gating")
                self.server.metrics.incr("nomad.overload.actuations")
        '''))
        assert fs == [], fs

    def test_trace_without_counter_fires(self):
        fs = obspass.analyze_actuators("nomad_tpu/m.py", _dedent('''
            def engage(self):
                self.broker.set_shedding(True)
                trace.event("seam.controller.actuate")
        '''))
        assert len(fs) == 1, fs
        assert "counter" in fs[0].message
        assert "trace" not in fs[0].message.split("never emits")[1]

    def test_counter_without_trace_fires(self):
        fs = obspass.analyze_actuators("nomad_tpu/m.py", _dedent('''
            def engage(self):
                self.gate.set_gate_level(0.25)
                self.metrics.incr("nomad.overload.actuations")
        '''))
        assert len(fs) == 1, fs
        assert "trace event" in fs[0].message

    def test_non_nomad_counter_does_not_satisfy(self):
        # A dynamic or foreign counter name is not the registered-counter
        # contract — the dashboard row would not exist.
        fs = obspass.analyze_actuators("nomad_tpu/m.py", _dedent('''
            def engage(self, name):
                self.gate.set_gate_level(0.25)
                trace.event("seam.controller.actuate")
                self.metrics.incr(name)
        '''))
        assert len(fs) == 1 and "counter" in fs[0].message, fs

    def test_nested_def_does_not_leak(self):
        fs = obspass.analyze_actuators("nomad_tpu/m.py", _dedent('''
            def outer(self):
                self.gate.set_gate_level(1.0)
                def unrelated():
                    trace.event("elsewhere")
                    metrics.incr("nomad.x")
        '''))
        assert len(fs) == 1 and fs[0].symbol == "outer", fs

    def test_both_actuators_reported_per_site(self):
        fs = obspass.analyze_actuators("nomad_tpu/m.py", _dedent('''
            def engage(self):
                self.gate.set_gate_level(0.5)
                self.broker.set_shedding(True)
        '''))
        assert len(fs) == 2, fs
        assert {f.rule for f in fs} == {"O003"}

    def test_controller_actuators_comply_in_tree(self):
        # The real decision sites must stay compliant (O003's raison
        # d'être) — check the shipped controller module directly.
        import os

        from nomad_tpu.lint import repo_root

        with open(os.path.join(
            repo_root(), "nomad_tpu", "obs", "controller.py"
        )) as fh:
            src = fh.read()
        assert obspass.analyze_actuators(
            "nomad_tpu/obs/controller.py", src) == []


class TestO004Breaker:
    def test_silent_transition_fires(self):
        fs = obspass.analyze_breaker_transitions("nomad_tpu/m.py", _dedent('''
            def trip(self):
                self._apply_transition(2, now)
        '''))
        assert len(fs) == 1 and fs[0].rule == "O004", fs
        assert fs[0].symbol == "trip"
        assert "_apply_transition" in fs[0].message

    def test_trace_and_counter_is_clean(self):
        fs = obspass.analyze_breaker_transitions("nomad_tpu/m.py", _dedent('''
            def trip(self, now):
                self._apply_transition(2, now)
                trace.event("seam.breaker.transition", frm="closed", to="open")
                self.metrics.incr("nomad.breaker.transitions")
        '''))
        assert fs == [], fs

    def test_trace_without_counter_fires(self):
        fs = obspass.analyze_breaker_transitions("nomad_tpu/m.py", _dedent('''
            def trip(self, now):
                self._apply_transition(2, now)
                trace.event("seam.breaker.transition")
        '''))
        assert len(fs) == 1, fs
        assert "counter" in fs[0].message
        assert "trace" not in fs[0].message.split("never emits")[1]

    def test_counter_without_trace_fires(self):
        fs = obspass.analyze_breaker_transitions("nomad_tpu/m.py", _dedent('''
            def trip(self, now):
                self._apply_transition(2, now)
                self.metrics.incr("nomad.breaker.transitions")
        '''))
        assert len(fs) == 1, fs
        assert "trace event" in fs[0].message

    def test_mutator_definition_scope_is_skipped(self):
        # _apply_transition recursing into itself (or a wrapper that IS
        # the mutator) is not a call site that owes the emission.
        fs = obspass.analyze_breaker_transitions("nomad_tpu/m.py", _dedent('''
            class DeviceBreaker:
                def _apply_transition(self, target, now):
                    if target == 3:
                        self._apply_transition(0, now)
        '''))
        assert fs == [], fs

    def test_nested_def_does_not_leak(self):
        fs = obspass.analyze_breaker_transitions("nomad_tpu/m.py", _dedent('''
            def trip(self, now):
                self._apply_transition(2, now)
                def unrelated():
                    trace.event("seam.breaker.transition")
                    metrics.incr("nomad.breaker.transitions")
        '''))
        assert len(fs) == 1 and fs[0].symbol == "trip", fs

    def test_breaker_module_complies_in_tree(self):
        # The shipped breaker must stay compliant — every state flip has
        # a seam event and a counter to line up against placement latency.
        import os

        from nomad_tpu.lint import repo_root

        with open(os.path.join(
            repo_root(), "nomad_tpu", "obs", "breaker.py"
        )) as fh:
            src = fh.read()
        assert obspass.analyze_breaker_transitions(
            "nomad_tpu/obs/breaker.py", src) == []


# ----------------------------------------------------------------------
# Baseline machinery
# ----------------------------------------------------------------------

class TestBaseline:
    def test_suppression_and_stale_reporting(self):
        f1 = Finding("L003", "a.py", 10, "C.m", "x")
        f2 = Finding("L001", "b.py", 20, "D.n", "y")
        bl = Baseline(entries=[
            {"rule": "L003", "path": "a.py", "symbol": "C.m", "why": "ok"},
            {"rule": "L004", "path": "z.py", "symbol": "E.o", "why": "gone"},
        ])
        new, suppressed, stale = split_baselined([f1, f2], bl)
        assert [f.rule for f in new] == ["L001"]
        assert [f.rule for f in suppressed] == ["L003"]
        assert [e["rule"] for e in stale] == ["L004"]

    def test_symbol_keying_survives_line_churn(self):
        bl = Baseline(entries=[
            {"rule": "L003", "path": "a.py", "symbol": "C.m", "why": "ok"},
        ])
        moved = Finding("L003", "a.py", 999, "C.m", "x")
        assert bl.match(moved) is not None

    def test_committed_baseline_loads_with_justifications(self):
        bl = load_baseline()
        assert bl.entries, "committed baseline should not be empty"
        assert all(e.get("why") for e in bl.entries)


# ----------------------------------------------------------------------
# TSan-lite runtime half
# ----------------------------------------------------------------------

class TestTsan:
    def _locked_pair(self):
        tl = tsan.TrackedLock(threading.Lock(), "g")
        info = tsan._ObjInfo("obj", (tl,))
        return tl, info

    def test_unguarded_second_thread_reports(self):
        tl, info = self._locked_pair()
        d = tsan._wrap_container({}, info)
        tsan.enable()
        try:
            d["a"] = 1  # exclusive owner
            t = threading.Thread(target=lambda: d.update(b=2), name="rogue")
            t.start()
            t.join()
            reports = tsan.reports()
        finally:
            tsan.disable()
        assert len(reports) == 1
        assert reports[0]["label"] == "obj" and reports[0]["thread"] == "rogue"

    def test_guarded_access_is_clean(self):
        tl, info = self._locked_pair()
        d = tsan._wrap_container({}, info)
        tsan.enable()
        try:
            d["a"] = 1

            def guarded():
                with tl:
                    d["b"] = 2

            t = threading.Thread(target=guarded)
            t.start()
            t.join()
            with tl:
                d["c"] = 3
            reports = tsan.reports()
        finally:
            tsan.disable()
        assert reports == [], reports

    def test_single_thread_never_checked(self):
        _tl, info = self._locked_pair()
        d = tsan._wrap_container({}, info)
        tsan.enable()
        try:
            for i in range(10):
                d[i] = i  # no lock, one thread: exclusive = free
            reports = tsan.reports()
        finally:
            tsan.disable()
        assert reports == []

    def test_wrapped_condition_round_trips(self):
        import time

        lock = threading.RLock()
        cond = threading.Condition(lock)
        tl = tsan.TrackedLock(lock, "c")
        tsan._rebind_condition(cond, tl)
        box = []

        def waiter():
            with cond:
                cond.wait_for(lambda: box, timeout=2)
                box.append("woke")

        t = threading.Thread(target=waiter)
        t.start()
        time.sleep(0.05)
        with cond:
            box.append(1)
            cond.notify_all()
        t.join()
        assert box == [1, "woke"]
        assert tsan.held_names() == frozenset()

    def test_array_view_writes_checked_but_derived_copies_free(self):
        import numpy as np

        tl = tsan.TrackedLock(threading.Lock(), "g")
        info = tsan._ObjInfo("arr", (tl,), writes_only=True)
        a = tsan._wrap_container(np.zeros((4, 3)), info)
        tsan.enable()
        try:
            a[0] = 1.0  # exclusive
            view = a[1:]
            derived = a * 2  # fresh buffer — must NOT carry the monitor

            def rogue():
                view[0] = 2.0      # unguarded view write: reported
                derived[0] = 9.0   # scratch write: free

            t = threading.Thread(target=rogue)
            t.start()
            t.join()
            reports = tsan.reports()
        finally:
            tsan.disable()
        assert len(reports) == 1 and reports[0]["label"] == "arr", reports

    def test_disabled_is_noop(self):
        assert not tsan.enabled()
        _tl, info = self._locked_pair()
        d = tsan._wrap_container({}, info)
        d["a"] = 1
        t = threading.Thread(target=lambda: d.update(b=2))
        t.start()
        t.join()
        assert tsan.reports() == []
