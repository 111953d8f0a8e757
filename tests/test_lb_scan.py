"""Cross-engine bit-exactness for §6 load-balanced configs (the tentpole).

The fused ``jax.lax.scan`` engine now runs Algorithm 1 inside the scan
(:mod:`repro.lb.jit_optimizer` + the pre-allocated slot universe).  These
tests pin the load-bearing property: for §6 configs — margin on and off,
repartition-heavy traces, cache and non-cache methods, vector and matrix
iterates — the scan reproduces the batched host engine and the scalar
``TrainingSimulator`` bit for bit, including the repartition schedule and
the cache eviction/rejection telemetry.  They also pin the routing
contract: ``EngineConfig(kind="auto")`` sends §6 configs to the scan; a
slot universe above the budget routes through the tiled active-slot
cache (still bit-exact); and the one genuinely unsupported case (the
active-entry footprint itself exceeds the budget) raises a structured
``EngineCapabilityError`` instead of silently falling back.
"""

import numpy as np
import pytest

import repro.experiments.fused as fused
from repro.cluster.simulator import (
    MethodConfig,
    TraceLatencySource,
    TrainingSimulator,
)
from repro.core.problems import (
    LogisticRegressionProblem,
    PCAProblem,
    make_genomics_like_matrix,
    make_higgs_like,
)
from repro.experiments.convergence import run_convergence_batch
from repro.latency.model import (
    make_heterogeneous_cluster,
    make_paper_artificial_cluster,
    sample_fleet,
)


@pytest.fixture(scope="module")
def logreg_small():
    X, y = make_higgs_like(480, seed=0)
    return LogisticRegressionProblem(X=X, y=y)


@pytest.fixture(scope="module")
def pca_small():
    return PCAProblem(X=make_genomics_like_matrix(240, 48, seed=0), k=3)


def artificial_fleet(problem, n_workers=6, n_scenarios=3, horizon=40, seed=11):
    """Persistent per-worker slowdowns: the §7.2-style LB showcase."""
    sp = 4
    c_task = problem.compute_cost(
        1, max(problem.num_samples // (n_workers * sp), 1)
    )
    cluster = make_paper_artificial_cluster(
        num_workers=n_workers, load_unit=c_task, seed=1
    )
    return cluster, sample_fleet(cluster, n_scenarios, horizon, seed=seed)


def bursty_fleet(n_workers=6, n_scenarios=2, horizon=30, seed=3):
    cluster = make_heterogeneous_cluster(
        n_workers, seed=seed, burst_rate=0.0, comp_range=(1.1e-3, 2.5e-3)
    )
    traces = sample_fleet(
        cluster, n_scenarios, horizon,
        burst_rate=3.0, burst_factor_mean=3.0, burst_duration_mean=5e-3,
        seed=seed + 8,
    )
    return cluster, traces


def lb_config(name="dsag", w=3, sp=4, **kw):
    kw.setdefault("lb_startup_delay", 0.005)
    kw.setdefault("lb_interval", 0.01)
    return MethodConfig(
        name=name, w=w, eta=0.25, subpartitions=sp, load_balance=True, **kw
    )


def assert_results_equal(a, b):
    np.testing.assert_array_equal(a.times, b.times)
    np.testing.assert_array_equal(a.suboptimality, b.suboptimality)
    np.testing.assert_array_equal(a.fresh_counts, b.fresh_counts)
    np.testing.assert_array_equal(a.per_worker_latency, b.per_worker_latency)
    np.testing.assert_array_equal(a.evictions, b.evictions)
    np.testing.assert_array_equal(a.rejected_stale, b.rejected_stale)
    assert a.repartition_events == b.repartition_events


class TestScanVsHostLB:
    """scan == host for §6 configs, and the balancer really balances."""

    def test_dsag_margin_on(self, logreg_small):
        cluster, traces = artificial_fleet(logreg_small)
        cfg = lb_config("dsag", margin=0.02)
        host = run_convergence_batch(
            logreg_small, traces, cfg, 40, eval_every=2, seed=0, engine="host"
        )
        scan = run_convergence_batch(
            logreg_small, traces, cfg, 40, eval_every=2, seed=0, engine="scan"
        )
        assert_results_equal(host, scan)
        # vacuity guard: the balancer must publish on this fleet
        assert any(len(ev) > 0 for ev in host.repartition_events)

    def test_dsag_margin_off(self, logreg_small):
        cluster, traces = artificial_fleet(logreg_small)
        cfg = lb_config("dsag", margin=0.0)
        host = run_convergence_batch(
            logreg_small, traces, cfg, 40, seed=0, engine="host"
        )
        scan = run_convergence_batch(
            logreg_small, traces, cfg, 40, seed=0, engine="scan"
        )
        assert_results_equal(host, scan)

    @pytest.mark.parametrize("name,w", [("sag", 6), ("sgd", 3)])
    def test_other_methods_with_lb(self, logreg_small, name, w):
        cluster, traces = bursty_fleet()
        cfg = lb_config(name, w=w, sp=3, lb_startup_delay=0.002, lb_interval=0.005)
        host = run_convergence_batch(
            logreg_small, traces, cfg, 30, seed=0, engine="host"
        )
        scan = run_convergence_batch(
            logreg_small, traces, cfg, 30, seed=0, engine="scan"
        )
        assert_results_equal(host, scan)

    def test_repartition_heavy_trace(self, logreg_small):
        """An aggressive publication schedule: many repartitions per run, so
        the slot-universe eviction walk and Algorithm-2 alignment are
        exercised hard — and the engines still agree bit for bit."""
        cluster, traces = bursty_fleet()
        cfg = lb_config("dsag", w=2, sp=3, lb_startup_delay=0.002, lb_interval=0.005)
        host = run_convergence_batch(
            logreg_small, traces, cfg, 30, seed=0, engine="host"
        )
        scan = run_convergence_batch(
            logreg_small, traces, cfg, 30, seed=0, engine="scan"
        )
        assert_results_equal(host, scan)
        assert min(len(ev) for ev in host.repartition_events) >= 5
        # repartitions must actually evict overlapping cache entries
        assert (host.evictions > 0).any()

    def test_pca_matrix_iterate(self, pca_small):
        """Matrix-valued cache entries through the LB slot universe."""
        cluster, traces = bursty_fleet()
        cfg = MethodConfig(
            name="dsag", w=2, eta=0.9, subpartitions=3, load_balance=True,
            lb_startup_delay=0.002, lb_interval=0.005,
        )
        host = run_convergence_batch(
            pca_small, traces, cfg, 25, eval_every=2, seed=0, engine="host"
        )
        scan = run_convergence_batch(
            pca_small, traces, cfg, 25, eval_every=2, seed=0, engine="scan"
        )
        assert_results_equal(host, scan)

    def test_scan_matches_scalar_simulator(self, logreg_small):
        """Direct scan-vs-scalar check (not only via the host engine)."""
        cluster, traces = artificial_fleet(logreg_small)
        cfg = lb_config("dsag")
        scan = run_convergence_batch(
            logreg_small, traces, cfg, 40, eval_every=2, seed=0, engine="scan"
        )
        for s in range(traces.num_scenarios):
            sim = TrainingSimulator(
                logreg_small, cluster, cfg, eval_every=2, seed=0,
                latency_source=TraceLatencySource(traces, s),
            )
            h = sim.run(40)
            np.testing.assert_array_equal(h.times, scan.times[s])
            np.testing.assert_array_equal(h.suboptimality, scan.suboptimality[s])
            np.testing.assert_array_equal(
                h.per_worker_latency, scan.per_worker_latency[s]
            )
            assert list(h.repartition_events) == list(scan.repartition_events[s])
            assert h.evictions == scan.evictions[s]
            assert h.rejected_stale == scan.rejected_stale[s]


class TestRouting:
    """engine='auto' contract: scan by default, host only behind the
    documented slot-universe escape hatch, never silently."""

    def test_auto_routes_lb_to_scan(self, logreg_small, monkeypatch):
        cluster, traces = artificial_fleet(logreg_small)
        cfg = lb_config("dsag")
        calls = []
        orig = fused.run_convergence_scan

        def spy(*args, **kw):
            calls.append(1)
            return orig(*args, **kw)

        monkeypatch.setattr(fused, "run_convergence_scan", spy)
        res = run_convergence_batch(logreg_small, traces, cfg, 10, seed=0)
        assert calls, "auto must route §6 configs to the fused scan"
        assert np.isfinite(res.times).all()

    def test_oversized_universe_runs_tiled_bitexact(self, logreg_small):
        """Bugfix pin: a slot universe above the budget no longer raises
        from explicit ``kind="scan"`` — it routes through the tiled
        active-slot cache and stays bit-exact against the host engine."""
        from repro.experiments.engine import CAP_TILED, EngineConfig

        cluster, traces = artificial_fleet(logreg_small)
        cfg = lb_config("dsag")
        cap_dense = fused.scan_capability(logreg_small, cfg, traces.num_workers)
        budget = cap_dense.slots_total - 1  # forces the tiled layout
        cap = fused.scan_capability(
            logreg_small, cfg, traces.num_workers, slot_budget=budget
        )
        assert cap.supported and cap.code == CAP_TILED
        assert cap.slots_resident <= budget < cap.slots_total
        tiled = run_convergence_batch(
            logreg_small, traces, cfg, 20, seed=0,
            engine=EngineConfig(kind="scan", slot_budget=budget),
        )
        host = run_convergence_batch(
            logreg_small, traces, cfg, 20, seed=0, engine=EngineConfig(kind="host")
        )
        assert_results_equal(host, tiled)
        # the §7.2 showcase actually repartitions, so the tiled walk's
        # eviction path is exercised, not just the SAG fast path
        assert sum(len(ev) for ev in tiled.repartition_events) > 0
        assert tiled.evictions.sum() > 0

    def test_unsupported_config_raises_capability_error(self, logreg_small):
        """Explicit ``kind="scan"`` on a genuinely unsupported config (the
        active-entry footprint itself exceeds the budget) must raise a
        structured capability error — not quietly fall back."""
        from repro.experiments.engine import (
            CAP_ACTIVE_SET,
            EngineCapabilityError,
            EngineConfig,
        )

        cluster, traces = artificial_fleet(logreg_small)
        cfg = lb_config("dsag")
        with pytest.raises(EngineCapabilityError) as exc:
            run_convergence_batch(
                logreg_small, traces, cfg, 10, seed=0,
                engine=EngineConfig(kind="scan", slot_budget=3),
            )
        cap = exc.value.capability
        assert cap.code == CAP_ACTIVE_SET and not cap.supported
        assert cap.slots_resident > cap.slot_budget == 3
        # still a ValueError telling the operator what to do instead
        assert isinstance(exc.value, ValueError)
        assert "host" in str(exc.value)

    def test_unsupported_config_auto_falls_back_to_host(self, logreg_small):
        from repro.experiments.engine import EngineConfig

        cluster, traces = artificial_fleet(logreg_small)
        cfg = lb_config("dsag")
        auto = run_convergence_batch(
            logreg_small, traces, cfg, 20, seed=0,
            engine=EngineConfig(kind="auto", slot_budget=3),
        )
        host = run_convergence_batch(
            logreg_small, traces, cfg, 20, seed=0, engine=EngineConfig(kind="host")
        )
        assert_results_equal(auto, host)
        assert auto.engine == host.engine == "host"

    def test_unsupported_config_auto_refuses_host_on_accelerator(
        self, logreg_small, monkeypatch
    ):
        """Off the CPU, ``kind="auto"`` never runs the host engine unasked."""
        import jax

        from repro.experiments.engine import (
            CAP_AUTO_NO_HOST,
            EngineCapabilityError,
            EngineConfig,
        )

        cluster, traces = artificial_fleet(logreg_small)
        cfg = lb_config("dsag")
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(EngineCapabilityError) as exc:
            run_convergence_batch(
                logreg_small, traces, cfg, 10, seed=0,
                engine=EngineConfig(kind="auto", slot_budget=3),
            )
        assert exc.value.capability.code == CAP_AUTO_NO_HOST
        assert "kind='host'" in str(exc.value)

    def test_lb_scan_refused_on_accelerator(self, logreg_small, monkeypatch):
        """Off the CPU the scan refuses §6 configs with their own code (their
        body has not finished a run on a TPU), loudly from kind="scan" and
        from kind="auto"; configs without §6 keep the scan."""
        import jax

        from repro.experiments.engine import (
            CAP_AUTO_NO_HOST,
            CAP_LB_ACCELERATOR,
            EngineCapabilityError,
            EngineConfig,
        )

        cluster, traces = artificial_fleet(logreg_small)
        cfg = lb_config("dsag")
        plain = MethodConfig(name="dsag", w=cfg.w, eta=cfg.eta, subpartitions=3)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        cap = fused.scan_capability(logreg_small, cfg, traces.num_workers)
        assert not cap.supported and cap.code == CAP_LB_ACCELERATOR
        assert "ROADMAP 2.1" in cap.detail and "kind='host'" in cap.detail
        assert fused.scan_capability(logreg_small, plain, traces.num_workers).supported
        with pytest.raises(EngineCapabilityError) as exc:
            run_convergence_batch(
                logreg_small, traces, cfg, 10, seed=0,
                engine=EngineConfig(kind="scan"),
            )
        assert exc.value.capability.code == CAP_LB_ACCELERATOR
        with pytest.raises(EngineCapabilityError) as exc:
            run_convergence_batch(logreg_small, traces, cfg, 10, seed=0)
        assert exc.value.capability.code == CAP_AUTO_NO_HOST
        assert "ROADMAP 2.1" in str(exc.value)

    def test_legacy_lb_max_slots_monkeypatch_still_gates(
        self, logreg_small, monkeypatch
    ):
        """The module constant is still the default budget."""
        cluster, traces = artificial_fleet(logreg_small)
        cfg = lb_config("dsag")
        monkeypatch.setattr(fused, "LB_MAX_SLOTS", 3)
        with pytest.warns(DeprecationWarning, match="scan_capability"):
            reason = fused.scan_unsupported_reason(
                logreg_small, cfg, traces.num_workers
            )
        assert reason is not None


class TestJitOptimizerInvariances:
    """The empirical CPU properties the cross-engine contract rests on."""

    def test_estimate_h_row_independent_of_batch(self):
        """A scenario's h draws depend only on its own moments — not on its
        row position or on which scenarios share the batch."""
        from repro.lb.optimizer import LoadBalanceOptimizer, OptimizerInputs

        rng = np.random.default_rng(0)
        S, N = 3, 5
        e_comp = rng.uniform(1e-3, 3e-3, (S, N))
        e_comm = rng.uniform(1e-4, 3e-4, (S, N))

        def inputs(rows):
            return OptimizerInputs(
                e_comm=e_comm[rows],
                v_comm=(0.1 * e_comm[rows]) ** 2,
                e_comp=e_comp[rows],
                v_comp=(0.1 * e_comp[rows]) ** 2,
                samples_per_worker=np.full((len(rows), N), 80.0),
                w=3,
            )

        opt = LoadBalanceOptimizer(seed=0, sim_iterations=30, ladder=(2, 4, 8))
        p = np.full((S, N), 4, dtype=np.int64)
        full = opt.update_batch(p, inputs(range(S)))[0]
        sub = opt.update_batch(p[1:], inputs([1, 2]))[0]
        np.testing.assert_array_equal(full[1:], sub)

    def test_moment_buffer_batch_invariance(self):
        """Row s of the [S, N, T] moments kernel equals the [1, N, T] call."""
        from repro.latency.profiler import MomentBuffer

        rng = np.random.default_rng(1)
        S, N, T = 3, 4, 6
        buf = MomentBuffer(S, N, T)
        for s in range(S):
            for i in range(N):
                for t in range(T - 1):
                    buf.record(
                        s, i, t,
                        rng.uniform(0, 5), rng.uniform(0.1, 1), rng.uniform(0.01, 0.5),
                    )
        now = rng.uniform(4, 6, S)
        full = buf.moments(now)
        for s in range(S):
            one = MomentBuffer(1, N, T)
            one.t_rec[0] = buf.t_rec[s]
            one.comm[0] = buf.comm[s]
            one.comp[0] = buf.comp[s]
            one.valid[0] = buf.valid[s]
            single = one.moments(now[s : s + 1])
            for a, b in zip(full, single):
                np.testing.assert_array_equal(a[s], b[0])
