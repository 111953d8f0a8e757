"""Tests for the fused ``jax.lax.scan`` convergence engine and the kernel
properties it rests on.

The load-bearing chain: problems expose one set of JAX kernels
(:class:`~repro.core.problems.FusedKernels`); the scalar simulator, the
batched host engine, and the fused scan all delegate to them; block
subgradients are evaluated on the static
:func:`~repro.core.problems.width_bucket` ladder so a given (iterate,
interval) always runs at the same static shape.  These tests pin (a) the
two empirical CPU properties the delegation needs — batch-size invariance
and mask-multiply neutrality — and (b) end-to-end bit-exactness of
scan == host == scalar, including the §5.1 margin and the §6
load-balancing routing.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cluster.simulator import MethodConfig, TraceLatencySource, TrainingSimulator
from repro.core.problems import (
    LogisticRegressionProblem,
    PCAProblem,
    make_genomics_like_matrix,
    make_higgs_like,
    width_bucket,
)
from repro.experiments.convergence import (
    PAPER_SCALE_PCA,
    paper_scale_pca_sweep,
    run_convergence_batch,
)
from repro.experiments import fused
from repro.experiments.fused import run_convergence_scan
from repro.experiments.results import convergence_ordering
from repro.latency.model import make_heterogeneous_cluster, sample_fleet
from repro.precision import x64


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
        cluster,
        n_scenarios,
        horizon,
        burst_rate=3.0,
        burst_factor_mean=3.0,
        burst_duration_mean=5e-3,
        seed=seed + 8,
    )
    return cluster, traces


def assert_results_equal(a, b):
    np.testing.assert_array_equal(a.times, b.times)
    np.testing.assert_array_equal(a.suboptimality, b.suboptimality)
    np.testing.assert_array_equal(a.fresh_counts, b.fresh_counts)
    np.testing.assert_array_equal(a.per_worker_latency, b.per_worker_latency)
    np.testing.assert_array_equal(a.evictions, b.evictions)
    np.testing.assert_array_equal(a.rejected_stale, b.rejected_stale)


class TestKernelProperties:
    def test_width_bucket_ladder(self):
        assert width_bucket(1, 100) == 1
        assert width_bucket(5, 100) == 8
        assert width_bucket(16, 100) == 16
        assert width_bucket(17, 100) == 32
        # the full range keeps its exact width (no 2x gather for gd/coded)
        assert width_bucket(100, 100) == 100

    @pytest.mark.parametrize("which", ["logreg", "pca"])
    def test_masked_matches_equal_width_kernel(
        self, which, logreg_small, pca_small
    ):
        """subgradient_blocks_masked rows == subgradient_blocks rows, even
        at widths where the padded reduction shape differs from the raw
        one — the bucket ladder routes both calls to the same shape."""
        prob = logreg_small if which == "logreg" else pca_small
        V = prob.init(0) + (0.01 if which == "logreg" else 0.0)
        for m in (5, 13, 17, 40):
            starts = np.array([1, 41, 81], dtype=np.int64)
            stops = starts + m - 1
            Vs = np.repeat(V[None], 3, axis=0)
            a = prob.subgradient_blocks(Vs, starts, stops)
            b = prob.subgradient_blocks_masked(Vs, starts, stops)
            np.testing.assert_array_equal(a, b)

    @pytest.mark.parametrize("which", ["logreg", "pca"])
    def test_mixed_width_masked_rows_match_scalar(
        self, which, logreg_small, pca_small
    ):
        prob = logreg_small if which == "logreg" else pca_small
        V = prob.init(0) + (0.01 if which == "logreg" else 0.0)
        starts = np.array([1, 31, 61, 101], dtype=np.int64)
        stops = np.array([13, 47, 77, 240], dtype=np.int64)  # widths 13/17/17/140
        out = prob.subgradient_blocks_masked(
            np.repeat(V[None], 4, axis=0), starts, stops
        )
        for g in range(4):
            np.testing.assert_array_equal(
                out[g], prob.subgradient(V, int(starts[g]), int(stops[g]))
            )

    @pytest.mark.parametrize("which", ["logreg", "pca"])
    def test_suboptimality_batch_invariant(self, which, logreg_small, pca_small):
        """Row s of the [S] kernel equals the S = 1 call bit-for-bit (the
        scalar simulator delegates at S = 1, so equivalence needs this)."""
        prob = logreg_small if which == "logreg" else pca_small
        rng = np.random.default_rng(0)
        Vs = np.stack(
            [prob.init(0) + rng.normal(scale=0.01, size=prob.init(0).shape)
             .astype(np.float32) for _ in range(4)]
        )
        batch = prob.suboptimality_batch(Vs)
        for s in range(4):
            assert batch[s] == prob.suboptimality(Vs[s])

    @pytest.mark.parametrize("B", [1, 4, 13])
    @pytest.mark.parametrize("which", ["logreg", "pca"])
    def test_suboptimality_stacked_matches_rows(self, which, B, logreg_small, pca_small):
        """The stacked form (one float64 contraction with every iterate,
        the fused scan's eval off the CPU) gives each row's gap to a
        relative 1e-12 of the per-iterate kernel."""
        prob = logreg_small if which == "logreg" else pca_small
        V0 = prob.init(0)
        rng = np.random.default_rng(B)
        Vs = np.stack(
            [V0 + rng.normal(scale=0.05, size=V0.shape).astype(np.float32)
             for _ in range(B)]
        )
        kernels = prob.fused_kernels()
        with x64():
            stacked = np.asarray(jax.jit(kernels.suboptimality_stacked)(jnp.asarray(Vs)))
        assert stacked.shape == (B,) and stacked.dtype == np.float64
        np.testing.assert_allclose(stacked, prob.suboptimality_batch(Vs), rtol=1e-12, atol=0)

    def test_pca_projection_batch_invariant(self, pca_small):
        rng = np.random.default_rng(1)
        Vs = rng.normal(size=(5, pca_small.dim, pca_small.k)).astype(np.float32)
        batch = pca_small.project_batch(Vs)
        for s in range(5):
            np.testing.assert_array_equal(batch[s], pca_small.project(Vs[s]))


class TestScanVsHost:
    """The tentpole gate: the lax.scan engine reproduces the host batched
    engine (and therefore the scalar simulator) bit for bit."""

    @pytest.mark.parametrize(
        "name,w",
        [("dsag", 2), ("sag", 6), ("sgd", 3), ("gd", 0), ("coded", 0)],
    )
    def test_logreg_methods(self, logreg_small, name, w):
        cluster, traces = small_fleet()
        cfg = MethodConfig(name=name, w=w, eta=0.25, subpartitions=3)
        host = run_convergence_batch(
            logreg_small, traces, cfg, 25, eval_every=2, seed=0, engine="host"
        )
        scan = run_convergence_batch(
            logreg_small, traces, cfg, 25, eval_every=2, seed=0, engine="scan"
        )
        assert_results_equal(host, scan)

    @pytest.mark.parametrize("name,w", [("dsag", 2), ("sag", 6)])
    def test_pca_methods(self, pca_small, name, w):
        cluster, traces = small_fleet()
        cfg = MethodConfig(name=name, w=w, eta=0.9, subpartitions=3)
        host = run_convergence_batch(
            pca_small, traces, cfg, 25, eval_every=2, seed=0, engine="host"
        )
        scan = run_convergence_batch(
            pca_small, traces, cfg, 25, eval_every=2, seed=0, engine="scan"
        )
        assert_results_equal(host, scan)

    def test_margin_case(self, logreg_small):
        """§5.1 margin: post-w collection window resolved inside the scan."""
        cluster, traces = small_fleet(horizon=30)
        cfg = MethodConfig(name="dsag", w=2, eta=0.25, subpartitions=3, margin=0.25)
        host = run_convergence_batch(
            logreg_small, traces, cfg, 30, seed=0, engine="host"
        )
        scan = run_convergence_batch(
            logreg_small, traces, cfg, 30, seed=0, engine="scan"
        )
        assert (host.fresh_counts > 2).any()
        assert_results_equal(host, scan)

    def test_scan_matches_scalar_simulator(self, logreg_small):
        """Direct scan-vs-scalar check (not only via the host engine)."""
        cluster, traces = small_fleet()
        cfg = MethodConfig(name="dsag", w=2, eta=0.25, subpartitions=3)
        scan = run_convergence_scan(logreg_small, traces, cfg, 25, eval_every=2, seed=0)
        for s in range(traces.num_scenarios):
            sim = TrainingSimulator(
                logreg_small,
                cluster,
                cfg,
                eval_every=2,
                seed=0,
                latency_source=TraceLatencySource(traces, s),
            )
            h = sim.run(25)
            np.testing.assert_array_equal(h.times, scan.times[s])
            np.testing.assert_array_equal(h.suboptimality, scan.suboptimality[s])
            np.testing.assert_array_equal(
                h.per_worker_latency, scan.per_worker_latency[s]
            )
            assert h.rejected_stale == scan.rejected_stale[s]

    def test_load_balance_runs_in_scan(self, logreg_small):
        """§6 configs now run inside the scan: engine='auto' keeps them on
        the fused path and the result stays bit-exact vs the scalar
        simulator on the same traces (the full cross-engine §6 suite lives
        in tests/test_lb_scan.py)."""
        cluster, traces = small_fleet(horizon=30)
        cfg = MethodConfig(
            name="dsag", w=2, eta=0.25, subpartitions=3,
            load_balance=True, lb_startup_delay=0.005, lb_interval=0.01,
        )
        scan = run_convergence_scan(logreg_small, traces, cfg, 30, seed=0)
        auto = run_convergence_batch(logreg_small, traces, cfg, 30, seed=0)
        np.testing.assert_array_equal(scan.times, auto.times)
        sim = TrainingSimulator(
            logreg_small, cluster, cfg, seed=0,
            latency_source=TraceLatencySource(traces, 0),
        )
        h = sim.run(30)
        np.testing.assert_array_equal(h.times, auto.times[0])
        np.testing.assert_array_equal(h.suboptimality, auto.suboptimality[0])
        assert list(h.repartition_events) == list(auto.repartition_events[0])

    @pytest.mark.parametrize("which", ["logreg", "pca"])
    def test_stacked_eval_after_the_scan(self, which, logreg_small, pca_small):
        """The scan with the stacked eval (the form it takes off the CPU)
        keeps every other output bit for bit, NaN where no eval step is,
        and each gap to a relative 1e-12 of the per-iterate map."""
        prob = logreg_small if which == "logreg" else pca_small
        _, traces = small_fleet()
        cfg = MethodConfig(name="dsag", w=2, eta=0.25, subpartitions=3)
        spec, kernels, args = fused.prepare_scan_inputs(prob, traces, cfg, 25, eval_every=4)
        assert not spec.eval_stacked  # the CPU keeps the bit-exact map
        assert spec.eval_steps == (0, 4, 8, 12, 16, 20, 24)
        run = fused._scan_jit_for(kernels)
        with x64():
            mapped = [np.asarray(o) for o in run(kernels, spec, *args)]
            stacked_spec = dataclasses.replace(spec, eval_stacked=True)
            stacked = [np.asarray(o) for o in run(kernels, stacked_spec, *args)]
        for i, (a, b) in enumerate(zip(mapped, stacked)):
            if i != 1:
                np.testing.assert_array_equal(a, b)
        sub_m, sub_s = mapped[1], stacked[1]
        assert sub_m.shape == (traces.num_scenarios, 25)
        evaluated = np.zeros(25, bool)
        evaluated[list(spec.eval_steps)] = True
        np.testing.assert_array_equal(np.isfinite(sub_s), np.broadcast_to(evaluated, sub_s.shape))
        np.testing.assert_array_equal(np.isnan(sub_m), np.isnan(sub_s))
        np.testing.assert_allclose(sub_s, sub_m, rtol=1e-12, atol=0)

    def test_unknown_engine_rejected(self, logreg_small):
        cluster, traces = small_fleet()
        cfg = MethodConfig(name="dsag", w=2, subpartitions=3)
        with pytest.raises(ValueError, match="unknown engine"):
            run_convergence_batch(logreg_small, traces, cfg, 5, engine="gpu")

    def test_float64_problem_matrix(self):
        """A float64 data matrix must not break the scan carry (the
        in-flight value buffer dtype follows the kernels' value dtype)."""
        X = make_genomics_like_matrix(240, 48, seed=0).astype(np.float64)
        prob = PCAProblem(X=X, k=3)
        cluster, traces = small_fleet()
        cfg = MethodConfig(name="dsag", w=2, eta=0.9, subpartitions=3)
        host = run_convergence_batch(prob, traces, cfg, 15, seed=0, engine="host")
        scan = run_convergence_batch(prob, traces, cfg, 15, seed=0, engine="scan")
        assert_results_equal(host, scan)


@pytest.mark.slow
class TestPaperScalePCA:
    def test_paper_scale_smoke(self):
        """Shrunk paper-scale PCA run (n=12.5k): the fused engine handles
        the genomics-like workload end to end and DSAG reaches the
        calibrated gap before SAG and the coded bound."""
        out, gap = paper_scale_pca_sweep(scale=0.25, seed=0)
        assert out.problem.num_samples == PAPER_SCALE_PCA["n_rows"] // 4
        for res in out.results.values():
            assert np.isfinite(res.times).all()
        # at 1/4 scale the full gap ladder is not guaranteed; use a looser
        # mid-range gap for the ordering check
        o = convergence_ordering(out, 1e-3)
        assert o["dsag_fastest_to_gap"] == 1.0, o
