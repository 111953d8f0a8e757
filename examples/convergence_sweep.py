"""Run a batched §7 *convergence* sweep (time-to-suboptimality) and print
each method's time-to-gap across scenarios.

  PYTHONPATH=src python examples/convergence_sweep.py
  PYTHONPATH=src python examples/convergence_sweep.py --workers 100 \
      --scenarios 10 --iters 60 --gap 0.2 --out BENCH_convergence.json \
      --check-scalar
  PYTHONPATH=src python examples/convergence_sweep.py --problem pca \
      --paper-scale                     # the n=50k genomics-like matrix

Runs DSAG, SAG (w = N), SGD, and the idealized coded bound through the full
training loop (gradient cache, §5.1 margin, stale integration) on one
shared heavy-burst trace draw — all scenarios resolved at once by the fused
``jax.lax.scan`` convergence engine (``--engine host`` selects the
numpy-driven batched loop instead), which is bit-exact against the scalar
``TrainingSimulator`` (``--check-scalar`` verifies one scenario end to end
and times the scalar loop for the speedup report).  ``--devices D`` shards
the scenario axis over a D-device mesh (bit-exact vs the single-device
scan); on CPU demo with ``XLA_FLAGS=--xla_force_host_platform_device_count=4``.

``--problem pca`` switches the workload to PCA of a synthetic genomics-like
matrix (paper §2); ``--paper-scale`` applies the calibrated paper-scale
configuration (n=50k rows, 50 workers, eta/gap per
``repro.experiments.convergence.PAPER_SCALE_PCA``) — the committed
``BENCH_convergence.json`` carries this run as its ``pca_paper_scale``
column.
"""

import argparse

import jax
import numpy as np

from repro.cluster.simulator import effective_w
from repro.compile_cache import enable_compile_cache
from repro.core.problems import (
    LogisticRegressionProblem,
    PCAProblem,
    make_genomics_like_matrix,
    make_higgs_like,
)
from repro.experiments import (
    PAPER_SCALE_PCA,
    EngineConfig,
    convergence_ordering,
    default_convergence_methods,
    paper_scale_pca_sweep,
    run_convergence_sweep,
    scalar_convergence_run,
    scalar_convergence_seconds,
    write_bench_convergence,
)
from repro.experiments.grid import HEAVY_BURSTS
from repro.latency.model import make_heterogeneous_cluster


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--problem", choices=("logreg", "pca"), default="logreg")
    ap.add_argument(
        "--paper-scale",
        action="store_true",
        help="run the calibrated paper-scale PCA sweep (implies --problem pca; "
        "n=50k rows, 50 workers, gap per PAPER_SCALE_PCA)",
    )
    ap.add_argument("--workers", type=int, default=40)
    ap.add_argument("--scenarios", type=int, default=6)
    ap.add_argument("--iters", type=int, default=40)
    ap.add_argument("--samples", type=int, default=4096)
    ap.add_argument("--cols", type=int, default=96,
                    help="columns of the PCA matrix (pca only)")
    ap.add_argument("--w-frac", type=float, default=0.8)
    ap.add_argument("--subpartitions", type=int, default=10)
    ap.add_argument("--eta", type=float, default=None,
                    help="step size (default 0.25 for logreg, 0.9 for pca)")
    ap.add_argument("--gap", type=float, default=None,
                    help="time-to-gap threshold (default 0.2 logreg, 1e-4 pca)")
    ap.add_argument("--eval-every", type=int, default=4)
    ap.add_argument("--engine", choices=("auto", "scan", "host"), default="auto",
                    help="fused jax.lax.scan engine (auto/scan) or the "
                    "numpy-driven batched host loop")
    ap.add_argument("--devices", type=int, default=None,
                    help="shard the scenario axis of the fused scan over "
                    "this many devices (CPU demo: set XLA_FLAGS="
                    "--xla_force_host_platform_device_count=4)")
    ap.add_argument("--slot-budget", type=int, default=None,
                    help="override the fused engine's §6 slot budget "
                    "(default repro.experiments.fused.LB_MAX_SLOTS)")
    ap.add_argument("--kernel-backend", choices=("xla", "pallas"),
                    default="xla",
                    help="route the fused scan's §3 block-subgradient and "
                    "§5 grid-cache hot paths through the Pallas kernel "
                    "twins (interpret mode on CPU; bit-exact vs xla)")
    ap.add_argument("--load-balance", action="store_true",
                    help="run DSAG with the §6 load balancer in the loop "
                    "(runs inside the fused scan; slot universes above the "
                    "budget use the tiled active-slot cache)")
    ap.add_argument("--out", default=None, help="write BENCH-style JSON here")
    ap.add_argument(
        "--check-scalar",
        action="store_true",
        help="verify one scenario against the scalar TrainingSimulator "
        "(bit-exact) and time the scalar loop (slow)",
    )
    args = ap.parse_args()
    enable_compile_cache()
    dev = jax.devices()[0]
    print(
        f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(jax.devices())}"
    )
    if args.paper_scale:
        args.problem = "pca"
    engine = EngineConfig(
        kind=args.engine,
        num_devices=args.devices,
        slot_budget=args.slot_budget,
        eval_every=args.eval_every,
        kernel_backend=args.kernel_backend,
    )

    if args.paper_scale:
        out, default_gap = paper_scale_pca_sweep(seed=0, engine=engine)
        N = out.traces.num_workers
        print(
            f"paper-scale PCA: n={out.problem.num_samples} rows, {N} workers, "
            f"{out.traces.num_scenarios} scenarios, {out.num_iterations} iters "
            f"(PAPER_SCALE_PCA={PAPER_SCALE_PCA})"
        )
    else:
        if args.problem == "pca":
            prob = PCAProblem(
                X=make_genomics_like_matrix(args.samples, args.cols, seed=0), k=3
            )
            eta = 0.9 if args.eta is None else args.eta
            default_gap = 1e-4
        else:
            X, y = make_higgs_like(args.samples, seed=0)
            prob = LogisticRegressionProblem(X=X, y=y)
            eta = 0.25 if args.eta is None else args.eta
            default_gap = 0.2
        N, sp = args.workers, args.subpartitions
        c_task = prob.compute_cost(1, max(prob.num_samples // (N * sp), 1))
        cluster = make_heterogeneous_cluster(
            N, seed=0, burst_rate=0.0, load_unit=c_task
        )
        w = min(max(round(args.w_frac * N), 1), N)
        methods = default_convergence_methods(
            N, w=w, eta=eta, subpartitions=sp,
            load_balance_dsag=args.load_balance,
        )
        out = run_convergence_sweep(
            prob, cluster, methods,
            n_scenarios=args.scenarios, num_iterations=args.iters,
            eval_every=args.eval_every, regime=HEAVY_BURSTS, seed=0,
            engine=engine,
        )
    gap = default_gap if args.gap is None else args.gap
    print(
        f"{len(out.methods)} methods x {out.traces.num_scenarios} scenarios x "
        f"{out.num_iterations} iterations in {out.engine_seconds:.2f}s "
        f"({args.engine} engine"
        + (f", {args.devices}-device grid" if args.devices else "")
        + (", pallas kernels" if args.kernel_backend == "pallas" else "")
        + ")"
    )

    scalar_s = measured = None
    if args.check_scalar:
        h = scalar_convergence_run(out, "dsag", 0)
        res = out.results["dsag"]
        assert np.array_equal(h.times, res.times[0])
        assert np.array_equal(h.suboptimality, res.suboptimality[0], equal_nan=True)
        print("scalar TrainingSimulator replay of scenario 0: bit-exact")
        measured, scalar_s = scalar_convergence_seconds(
            out, methods=("dsag", "sag"), max_scenarios=2
        )
        print(f"scalar loop (dsag+sag pair, extrapolated): {scalar_s:.1f}s")

    header = (
        f"{'method':>6} {'w':>4} {'engine':>6} {'median t->gap (s)':>18} "
        f"{'final gap':>11} {'total t (s)':>12}"
    )
    print(header)
    print("-" * len(header))
    for name, res in out.results.items():
        ttg = res.time_to_gap(gap)
        print(
            f"{name:>6} {effective_w(out.methods[name], N):>4} {res.engine:>6} "
            f"{np.median(ttg):>18.4f} "
            f"{np.nanmean(res.suboptimality[:, -1]):>11.2e} "
            f"{res.times[:, -1].mean():>12.3f}"
        )
    o = convergence_ordering(out, gap)
    print(
        f"gap={gap}: sag/dsag={o['sag_over_dsag']:.2f}x "
        f"coded/dsag={o['coded_over_dsag']:.2f}x "
        f"dsag_fastest={bool(o['dsag_fastest_to_gap'])}"
    )

    if args.out:
        write_bench_convergence(
            out, args.out, gap=gap,
            scalar_seconds=scalar_s, scalar_seconds_measured=measured,
            scalar_methods=["dsag", "sag"] if scalar_s is not None else None,
        )
        print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
