"""Cross-backend pins: ``kernel_backend="pallas"`` == ``"xla"``, bit for bit.

The fused scan's two hot paths — the §3 width-bucketed block-subgradient
gather and the §5 grid-cache event application — can route through the
``repro.kernels`` Pallas twins (``EngineConfig(kernel_backend="pallas")``,
interpret mode on CPU).  These tests pin that on the same platform the
Pallas path reproduces the XLA path bit for bit across the committed
method grids (logreg: dsag/sag/sgd/gd/coded; PCA: dsag/sag), the §6
load-balanced configs (dense universe and tiled active-slot cache — §3
only there, the §6 cache walks stay XLA), elastic-fleet churn, and the
scenario-sharded driver; plus the structured capability reasons for
configs that cannot take the Pallas path.
"""

import dataclasses

import jax
import numpy as np
import pytest

from repro.cluster.simulator import MethodConfig
from repro.core.problems import (
    LogisticRegressionProblem,
    PCAProblem,
    make_genomics_like_matrix,
    make_higgs_like,
)
from repro.experiments.convergence import run_convergence_batch
from repro.experiments.engine import (
    CAP_PALLAS_DTYPE,
    CAP_PALLAS_HOST,
    CAP_PALLAS_UNAVAILABLE,
    EngineCapabilityError,
    EngineConfig,
)
from repro.experiments.fused import kernel_backend_capability, scan_capability
from repro.latency.model import (
    ChurnSchedule,
    make_heterogeneous_cluster,
    make_paper_artificial_cluster,
    sample_fleet,
)


@pytest.fixture(scope="module")
def logreg_small():
    X, y = make_higgs_like(240, seed=0)
    return LogisticRegressionProblem(X=X, y=y)


@pytest.fixture(scope="module")
def pca_small():
    return PCAProblem(X=make_genomics_like_matrix(240, 48, seed=0), k=3)


def small_fleet(n_workers=6, n_scenarios=3, horizon=25, seed=3):
    cluster = make_heterogeneous_cluster(
        n_workers, seed=seed, burst_rate=0.0, comp_range=(1.1e-3, 2.5e-3)
    )
    traces = sample_fleet(
        cluster, n_scenarios, horizon,
        burst_rate=3.0, burst_factor_mean=3.0, burst_duration_mean=5e-3,
        seed=seed + 8,
    )
    return traces


def assert_results_equal(a, b):
    np.testing.assert_array_equal(a.times, b.times)
    np.testing.assert_array_equal(a.suboptimality, b.suboptimality)
    np.testing.assert_array_equal(a.fresh_counts, b.fresh_counts)
    np.testing.assert_array_equal(a.per_worker_latency, b.per_worker_latency)
    np.testing.assert_array_equal(a.evictions, b.evictions)
    np.testing.assert_array_equal(a.rejected_stale, b.rejected_stale)
    assert a.repartition_events == b.repartition_events


def run_both(problem, traces, cfg, T, **eng_kw):
    xla = run_convergence_batch(
        problem, traces, cfg, T, eval_every=2, seed=0,
        engine=EngineConfig(kind="scan", **eng_kw),
    )
    pal = run_convergence_batch(
        problem, traces, cfg, T, eval_every=2, seed=0,
        engine=EngineConfig(kind="scan", kernel_backend="pallas", **eng_kw),
    )
    return xla, pal


class TestPallasEqualsXla:
    @pytest.mark.parametrize(
        "name,w",
        [("dsag", 2), ("sag", 6), ("sgd", 3), ("gd", 0), ("coded", 0)],
    )
    def test_logreg_methods(self, logreg_small, name, w):
        traces = small_fleet()
        cfg = MethodConfig(name=name, w=w, eta=0.25, subpartitions=3)
        xla, pal = run_both(logreg_small, traces, cfg, 25)
        assert_results_equal(xla, pal)

    @pytest.mark.parametrize("name,w", [("dsag", 2), ("sag", 6)])
    def test_pca_methods(self, pca_small, name, w):
        traces = small_fleet()
        cfg = MethodConfig(name=name, w=w, eta=0.9, subpartitions=3)
        xla, pal = run_both(pca_small, traces, cfg, 25)
        assert_results_equal(xla, pal)

    def test_churn_config(self, logreg_small):
        """Worker death mid-run: the churn body's gather widths and §5
        events still route identically through the Pallas twins."""
        traces = small_fleet(n_scenarios=2, horizon=30)
        sd = np.asarray(traces.slowdown)
        alive0 = np.ones(traces.num_workers, bool)
        alive1 = alive0.copy()
        alive1[4] = False
        churned = traces.with_churn(ChurnSchedule(
            times=np.array([0.02]),
            slowdown=np.stack([sd, sd]),
            alive=np.stack([alive0, alive1]),
        ))
        cfg = MethodConfig(name="dsag", w=2, eta=0.25, subpartitions=3)
        xla, pal = run_both(logreg_small, churned, cfg, 30)
        assert_results_equal(xla, pal)


class TestPallasEqualsXlaLB:
    """§6 configs: Pallas covers the §3 gather only (the universe/tiled
    cache walks have no Pallas twin), but the full run must still match."""

    @pytest.fixture(scope="class")
    def lb_problem(self):
        X, y = make_higgs_like(480, seed=0)
        return LogisticRegressionProblem(X=X, y=y)

    def _lb_setup(self, problem):
        sp, nw = 4, 6
        c_task = problem.compute_cost(
            1, max(problem.num_samples // (nw * sp), 1)
        )
        cluster = make_paper_artificial_cluster(
            num_workers=nw, load_unit=c_task, seed=1
        )
        traces = sample_fleet(cluster, 3, 40, seed=11)
        cfg = MethodConfig(
            name="dsag", w=3, eta=0.25, subpartitions=sp, load_balance=True,
            lb_startup_delay=0.005, lb_interval=0.01, margin=0.02,
        )
        return traces, cfg

    def test_lb_universe(self, lb_problem):
        traces, cfg = self._lb_setup(lb_problem)
        xla, pal = run_both(lb_problem, traces, cfg, 40)
        assert_results_equal(xla, pal)
        # vacuity guard: the balancer must actually publish on this fleet
        assert any(len(ev) > 0 for ev in xla.repartition_events)

    def test_lb_tiled(self, lb_problem):
        traces, cfg = self._lb_setup(lb_problem)
        cap = scan_capability(lb_problem, cfg, traces.num_workers)
        budget = cap.slots_total - 1  # forces the tiled layout
        xla, pal = run_both(lb_problem, traces, cfg, 40, slot_budget=budget)
        assert_results_equal(xla, pal)


class TestShardedPallas:
    def test_one_device_mesh_is_bitexact(self, logreg_small):
        """shard_map + Pallas interpret compose (D=1 runs everywhere)."""
        traces = small_fleet()
        cfg = MethodConfig(name="dsag", w=2, eta=0.25, subpartitions=3)
        plain = run_convergence_batch(
            logreg_small, traces, cfg, 25, seed=0,
            engine=EngineConfig(kind="scan", kernel_backend="pallas"),
        )
        sharded = run_convergence_batch(
            logreg_small, traces, cfg, 25, seed=0,
            engine=EngineConfig(
                kind="scan", kernel_backend="pallas", num_devices=1
            ),
        )
        assert_results_equal(plain, sharded)

    @pytest.mark.skipif(
        len(jax.devices()) < 4,
        reason="needs >= 4 devices (CI re-runs with "
        "XLA_FLAGS=--xla_force_host_platform_device_count=4)",
    )
    def test_four_devices_vs_xla(self, logreg_small):
        traces = small_fleet(n_scenarios=4)
        cfg = MethodConfig(name="dsag", w=2, eta=0.25, subpartitions=3)
        xla, pal = run_both(logreg_small, traces, cfg, 25, num_devices=4)
        assert_results_equal(xla, pal)


class TestCapabilityReasons:
    def test_xla_always_supported(self, logreg_small):
        cfg = MethodConfig(name="dsag", w=2, eta=0.25, subpartitions=2)
        cap = kernel_backend_capability(logreg_small, "xla", cfg)
        assert cap.supported

    def test_pallas_supported_for_committed_problems(
        self, logreg_small, pca_small
    ):
        cfg = MethodConfig(name="dsag", w=2, eta=0.25, subpartitions=2)
        for prob in (logreg_small, pca_small):
            cap = kernel_backend_capability(prob, "pallas", cfg)
            assert cap.supported, cap.detail

    def test_problem_without_pallas_kernels(self):
        """A problem that publishes no Pallas twins reports the structured
        unavailable code instead of failing inside the trace."""
        X, y = make_higgs_like(60, seed=1)
        prob = LogisticRegressionProblem(X=X, y=y)
        kernels = prob.fused_kernels()
        prob._kernels = dataclasses.replace(kernels, sub_blocks_pallas=None)
        cfg = MethodConfig(name="dsag", w=2, eta=0.25, subpartitions=2)
        cap = kernel_backend_capability(prob, "pallas", cfg)
        assert not cap.supported
        assert cap.code == CAP_PALLAS_UNAVAILABLE
        traces = small_fleet(n_workers=4, n_scenarios=1, horizon=10)
        with pytest.raises(EngineCapabilityError) as ei:
            run_convergence_batch(
                prob, traces, cfg, 10, seed=0,
                engine=EngineConfig(kind="scan", kernel_backend="pallas"),
            )
        assert ei.value.capability.code == CAP_PALLAS_UNAVAILABLE

    def test_float64_problem_reports_dtype_code(self):
        prob = PCAProblem(
            X=make_genomics_like_matrix(60, 16, seed=2).astype(np.float64), k=2
        )
        cfg = MethodConfig(name="dsag", w=2, eta=0.25, subpartitions=2)
        cap = kernel_backend_capability(prob, "pallas", cfg)
        assert not cap.supported
        assert cap.code == CAP_PALLAS_DTYPE

    def test_host_engine_rejects_pallas(self, logreg_small):
        traces = small_fleet(n_workers=4, n_scenarios=1, horizon=10)
        cfg = MethodConfig(name="dsag", w=2, eta=0.25, subpartitions=2)
        with pytest.raises(EngineCapabilityError) as ei:
            run_convergence_batch(
                logreg_small, traces, cfg, 10, seed=0,
                engine=EngineConfig(kind="host", kernel_backend="pallas"),
            )
        assert ei.value.capability.code == CAP_PALLAS_HOST

    def test_unknown_backend_rejected_at_config(self):
        with pytest.raises(ValueError, match="kernel_backend"):
            EngineConfig(kernel_backend="cuda")
