"""Smoke run of the DSAG system's main paths on one TPU chip.

Drives, through the entry points a user calls, in one process:

  (a) the fused-scan simulator on the calibrated paper-scale PCA sweep
      (``paper_scale_pca_sweep``: n=50,000 x 96, 50 workers, 4 scenarios,
      80 iterations), xla kernel backend, then sgd on the pallas backend;
  (b) the simulator on the 100-worker x 10-scenario logreg grid (16,384
      samples, 60 iterations), xla backend, then sgd on the pallas backend.
      DSAG under the §6 load balancer (the committed lb_scan cadence) must
      be refused up front with ``CAP_LB_ACCELERATOR``: its scan body
      compiles for the chip but has not finished a run there (ROADMAP 2.1);
  (c) the live trainer (``launch/train.py``'s ``Trainer``) on the paper's
      logreg and PCA problems: 4 groups, 16,384 samples, 20 steps with
      injected stragglers.

Each simulator phase is checked against the scalar ``TrainingSimulator``
replay of scenario 0 for dsag and sag: integer streams equal, times and
suboptimality within the tolerances below.  Each must keep the paper's
dsag < sag < coded time-to-gap ordering, and every median time to gap
must equal the CPU value committed in ``BENCH_convergence.json``.  Each
trainer run must pass the
``--check`` conditions (the loss falls and xi reaches 1).  Per phase it
prints the first-run wall time (compile included), a warm second run
(ending in a host sync) and the device's peak bytes in use so far.

    python chip_smoke.py               # one chip: phases (a)-(c)
    python chip_smoke.py --four-chips  # four chips: the paper-scale PCA
                                       # sweep at 10 scenarios on a 4-device
                                       # scenario mesh vs one device

The last line of standard output is ``{"ok": true, "device": {...}}``; it
is printed only when every check passed.  Without a TPU, or outside a
checkout of this repository, the script exits non-zero before any phase.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

#: scan vs the scalar replay, and the median times to gap vs the CPU values
#: committed in BENCH_convergence.json: event times are float64 everywhere
#: (a v5e run agreed with the scalar replay to 1.2e-14 relative)
TIME_RTOL = 1e-6
#: suboptimality vs the scalar replay (a v5e run: PCA bit-equal, logreg
#: within 7.5e-10 absolute at values above 0.05)
SUBOPT_RTOL, SUBOPT_ATOL = 1e-6, 1e-12
#: suboptimality, pallas vs xla backend on the chip.  One call of either
#: form gives the same bits there, but over a whole scan the two drift
#: apart in the last bits (a lane reduction rounds differently under
#: Mosaic and XLA:TPU), magnified where the suboptimality is a small
#: difference of large explained variances (a v5e run: PCA sgd 3.6e-4)
PALLAS_SUBOPT_RTOL = 1e-3
#: the methods the pallas pass runs: sgd's gathers reach the block kernels,
#: dsag and sag are refused on the chip (their grid-cache kernel holds
#: 64-bit state) and coded's full-data gather takes the XLA form
PALLAS_METHODS = ("sgd",)

#: the 100-worker logreg grid (the committed BENCH_convergence.json grid)
LOGREG_GRID = dict(
    n_workers=100, n_scenarios=10, num_iterations=60, num_samples=16_384,
    w=80, eta=0.25, subpartitions=10, eval_every=5, gap=0.2,
)
#: the §6 optimizer's cadence in the committed lb_scan column
LB_CADENCE = dict(lb_startup_delay=0.05, lb_interval=0.1)
#: live trainer runs (phase c)
LIVE = dict(groups=4, samples=16_384, steps=20)

FAILURES: list[str] = []
REPO = Path(__file__).resolve().parent


def check(ok: bool, what: str) -> None:
    print(f"  [{'ok' if ok else 'FAIL'}] {what}")
    if not ok:
        FAILURES.append(what)


def peak_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def max_rel(a, b) -> float:
    import numpy as np

    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    fa = np.isfinite(a)
    if not np.array_equal(fa, np.isfinite(b)):
        return float("inf")
    if not fa.any():
        return 0.0
    return float(np.max(np.abs(a[fa] - b[fa]) / np.maximum(np.abs(b[fa]), 1e-300)))


def compare_to_scalar(outcome, methods=("dsag", "sag")) -> None:
    """Scenario 0 of the scan against the scalar TrainingSimulator."""
    import numpy as np

    from repro.experiments import scalar_convergence_run

    for name in methods:
        t0 = time.perf_counter()
        res, h = outcome.results[name], scalar_convergence_run(outcome, name, 0)
        print(f"  {name}: scalar replay of scenario 0 took {time.perf_counter() - t0:.2f} s")
        check(
            np.array_equal(res.fresh_counts[0], h.fresh_counts)
            and int(res.evictions[0]) == h.evictions
            and int(res.rejected_stale[0]) == h.rejected_stale,
            f"{name}: fresh counts, evictions and rejected-stale equal the scalar replay",
        )
        check(
            len(res.repartition_events[0]) == len(h.repartition_events)
            and max_rel(res.repartition_events[0], h.repartition_events) <= TIME_RTOL,
            f"{name}: {len(h.repartition_events)} repartition events match",
        )
        dt = max_rel(res.times[0], h.times)
        check(dt <= TIME_RTOL, f"{name}: times max rel diff {dt:.3e} <= {TIME_RTOL}")
        a, b = res.suboptimality[0], np.asarray(h.suboptimality)
        same_nan = np.array_equal(np.isnan(a), np.isnan(b))
        fin = ~np.isnan(a)
        ok = same_nan and np.allclose(a[fin], b[fin], rtol=SUBOPT_RTOL, atol=SUBOPT_ATOL)
        err = float(np.max(np.abs(a[fin] - b[fin]))) if same_nan and fin.any() else float("inf")
        check(
            ok,
            f"{name}: suboptimality max abs diff {err:.3e} "
            f"(rtol {SUBOPT_RTOL}, atol {SUBOPT_ATOL})",
        )


def compare_runs(a, b, label: str, subopt_rtol: float = SUBOPT_RTOL) -> None:
    """Two batch results of the same method on the same traces: integer
    streams equal, times and suboptimality within the stated tolerances."""
    import numpy as np

    ints = np.array_equal(a.fresh_counts, b.fresh_counts) and np.array_equal(
        a.evictions, b.evictions
    )
    dt, ds = max_rel(a.times, b.times), max_rel(a.suboptimality, b.suboptimality)
    close = np.allclose(
        np.nan_to_num(a.suboptimality), np.nan_to_num(b.suboptimality),
        rtol=subopt_rtol, atol=SUBOPT_ATOL,
    )
    exact = ints and dt == 0.0 and ds == 0.0
    check(
        ints and dt <= TIME_RTOL and close,
        f"{label}: integer streams equal, max rel diff times {dt:.1e}, "
        f"suboptimality {ds:.1e} ({'bit-exact' if exact else 'within tolerance'})",
    )


def committed_medians(section: str | None = None) -> dict[str, float]:
    """Median times to gap that a CPU run committed to BENCH_convergence.json
    (the top-level grid, or one column; ``None`` there: never reached)."""
    bench = json.loads((REPO / "BENCH_convergence.json").read_text())
    ordering = (bench if section is None else bench[section])["ordering"]
    prefix = "median_time_to_gap_"
    return {
        k[len(prefix):]: float("inf") if v is None else float(v)
        for k, v in ordering.items()
        if k.startswith(prefix)
    }


def check_ordering(outcome, gap: float, cpu: dict[str, float]) -> None:
    """dsag < sag < coded, and every median time to gap equal to the CPU's."""
    import math

    from repro.experiments import convergence_ordering

    o = convergence_ordering(outcome, gap)
    t = {m: o[f"median_time_to_gap_{m}"] for m in outcome.results}
    print("  median time to gap " + ", ".join(f"{m} {v:.4f}" for m, v in t.items()))
    check(o.get("ordering_dsag_sag_coded") == 1.0, "ordering dsag < sag < coded holds")
    for m, ref in cpu.items():
        got = t[m]
        same = got == ref or (
            math.isfinite(ref) and abs(got - ref) <= TIME_RTOL * abs(ref)
        )
        check(same, f"{m}: median time to gap {got!r} equals the CPU's {ref!r}")


def rerun(outcome, engine, regime):
    """The same sweep again, on the same problem (warm executables)."""
    from repro.experiments import run_convergence_sweep

    return run_convergence_sweep(
        outcome.problem, outcome.cluster, outcome.methods,
        n_scenarios=outcome.traces.num_scenarios,
        num_iterations=outcome.num_iterations, cost_scale=outcome.cost_scale,
        eval_every=outcome.eval_every, regime=regime, seed=outcome.seed,
        engine=engine,
    )


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def report(dev, first_s: float, warm_s: float) -> None:
    print(
        f"  first run {first_s:.2f} s (compile included), warm run {warm_s:.2f} s, "
        f"compile ~{max(first_s - warm_s, 0.0):.2f} s; "
        f"peak bytes in use so far {peak_bytes(dev)}"
    )


def pallas_pass(outcome, regime) -> None:
    """``PALLAS_METHODS`` again on the pallas backend, against the xla run."""
    from repro.experiments import EngineConfig, run_convergence_sweep
    from repro.experiments.fused import kernel_backend_capability

    for name, cfg in outcome.methods.items():
        cap = kernel_backend_capability(outcome.problem, "pallas", cfg)
        print(f"  pallas capability for {name}: {cap.code}")
    admitted = {name: outcome.methods[name] for name in PALLAS_METHODS}
    for name, cfg in admitted.items():
        cap = kernel_backend_capability(outcome.problem, "pallas", cfg)
        check(cap.supported, f"pallas admitted for {name}")
    pal, first_s = timed(lambda: run_convergence_sweep(
        outcome.problem, outcome.cluster, admitted,
        n_scenarios=outcome.traces.num_scenarios,
        num_iterations=outcome.num_iterations, cost_scale=outcome.cost_scale,
        eval_every=outcome.eval_every, regime=regime, seed=outcome.seed,
        engine=EngineConfig(kind="scan", kernel_backend="pallas"),
    ))
    print(f"  pallas backend ran {sorted(admitted)} in {first_s:.2f} s (compile included)")
    for name in admitted:
        compare_runs(
            pal.results[name], outcome.results[name], f"pallas {name} vs xla",
            subopt_rtol=PALLAS_SUBOPT_RTOL,
        )


def phase_pca(dev) -> None:
    from repro.experiments import EngineConfig, paper_scale_pca_sweep
    from repro.experiments.grid import HEAVY_BURSTS

    print("(a) simulator: paper-scale PCA sweep (fused scan, xla backend)")
    engine = EngineConfig(kind="scan")
    (out, gap), first_s = timed(lambda: paper_scale_pca_sweep(engine=engine))
    _, warm_s = timed(lambda: rerun(out, engine, HEAVY_BURSTS))
    print(
        f"  n={out.problem.num_samples} workers={out.traces.num_workers} "
        f"scenarios={out.traces.num_scenarios} iterations={out.num_iterations}; "
        f"engines {sorted({r.engine for r in out.results.values()})}"
    )
    report(dev, first_s, warm_s)
    check(
        all(r.engine == "scan" for r in out.results.values()),
        "every method ran on the scan engine",
    )
    compare_to_scalar(out)
    check_ordering(out, gap, committed_medians("pca_paper_scale"))
    pallas_pass(out, HEAVY_BURSTS)


def phase_logreg_grid(dev) -> None:
    from repro.core.problems import LogisticRegressionProblem, make_higgs_like
    from repro.experiments import (
        EngineConfig,
        default_convergence_methods,
        run_convergence_sweep,
    )
    from repro.experiments.engine import CAP_LB_ACCELERATOR
    from repro.experiments.fused import scan_capability
    from repro.experiments.grid import HEAVY_BURSTS
    from repro.latency.model import make_heterogeneous_cluster

    g = LOGREG_GRID
    print("(b) simulator: 100-worker x 10-scenario logreg grid (fused scan, xla backend)")
    X, y = make_higgs_like(g["num_samples"], seed=0)
    prob = LogisticRegressionProblem(X=X, y=y)
    N, sp = g["n_workers"], g["subpartitions"]
    c_task = prob.compute_cost(1, max(prob.num_samples // (N * sp), 1))
    cluster = make_heterogeneous_cluster(N, seed=0, burst_rate=0.0, load_unit=c_task)
    methods = default_convergence_methods(N, w=g["w"], eta=g["eta"], subpartitions=sp)
    dsag_lb = dataclasses.replace(methods["dsag"], load_balance=True, **LB_CADENCE)
    cap = scan_capability(prob, dsag_lb, N)
    print(f"  dsag under §6: {cap.code}: {cap.detail}")
    check(
        not cap.supported and cap.code == CAP_LB_ACCELERATOR,
        "dsag under §6 is refused up front on the chip",
    )
    engine = EngineConfig(kind="scan")
    out, first_s = timed(lambda: run_convergence_sweep(
        prob, cluster, methods, n_scenarios=g["n_scenarios"],
        num_iterations=g["num_iterations"], eval_every=g["eval_every"],
        regime=HEAVY_BURSTS, seed=0, engine=engine,
    ))
    _, warm_s = timed(lambda: rerun(out, engine, HEAVY_BURSTS))
    report(dev, first_s, warm_s)
    check(
        all(r.engine == "scan" for r in out.results.values()),
        "every method ran on the scan engine",
    )
    compare_to_scalar(out)
    check_ordering(out, g["gap"], committed_medians())
    pallas_pass(out, HEAVY_BURSTS)


def phase_live(dev) -> None:
    import numpy as np

    from repro.launch.paper_jobs import paper_train_config
    from repro.launch.train import Trainer, TrainerOptions

    for arch in ("logreg", "pca"):
        print(f"(c) live trainer: --arch {arch}")
        trainer = Trainer(TrainerOptions(
            arch=arch, samples=LIVE["samples"], num_groups=LIVE["groups"],
            steps=LIVE["steps"], train_config=paper_train_config(0.25, dsag=True),
            log_every=10_000,
        ))
        hist, first_s = timed(trainer.run)
        _, warm_s = timed(trainer.run)
        report(dev, first_s, warm_s)
        loss = hist["loss"]
        q = max(1, len(loss) // 4)
        first, last = float(np.mean(loss[:q])), float(np.mean(loss[-q:]))
        stragglers = sum(int(c) < LIVE["groups"] for c in hist["mask_count"])
        check(stragglers > 0, f"stragglers were injected on {stragglers} of {len(loss)} steps")
        check(last < first, f"loss falls {first:.4f} -> {last:.4f}")
        check(max(hist["xi"]) >= 1.0 - 1e-6, f"xi reaches 1 (max {max(hist['xi']):.3f})")


def phase_four_chips(devices) -> None:
    from repro.experiments import EngineConfig, paper_scale_pca_sweep
    from repro.experiments.grid import HEAVY_BURSTS

    print("four chips: paper-scale PCA sweep, 10 scenarios, scenario mesh of 4 vs 1 device")
    sharded_engine = EngineConfig(kind="scan", num_devices=4)
    (sharded, gap), first_s = timed(
        lambda: paper_scale_pca_sweep(engine=sharded_engine, n_scenarios=10)
    )
    _, warm_s = timed(lambda: rerun(sharded, sharded_engine, HEAVY_BURSTS))
    print(f"  4-device mesh: first run {first_s:.2f} s (compile included), warm {warm_s:.2f} s")
    peaks = [peak_bytes(d) for d in devices[:4]]
    print(f"  peak bytes in use per device {peaks}")
    check(all(p > 0 for p in peaks), "all four devices hold work (nonzero peak bytes)")
    single_engine = EngineConfig(kind="scan")
    single, first_1 = timed(lambda: rerun(sharded, single_engine, HEAVY_BURSTS))
    _, warm_1 = timed(lambda: rerun(sharded, single_engine, HEAVY_BURSTS))
    print(f"  1 device: first run {first_1:.2f} s (compile included), warm {warm_1:.2f} s")
    for name in sharded.results:
        compare_runs(sharded.results[name], single.results[name], f"{name} sharded vs single")
    check_ordering(sharded, gap, {})


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--four-chips", action="store_true",
        help="run only the 4-device scenario-mesh sweep and its 1-device comparison",
    )
    args = ap.parse_args(argv)
    sys.stdout.reconfigure(line_buffering=True)  # progress shows even if cut
    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro").is_dir():
        print(f"chip_smoke: no repro sources under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}", file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} chips, found {len(devices)}", file=sys.stderr)
        return 1

    from repro.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")
    print(f"device: platform={dev.platform} kind={dev.device_kind} count={len(devices)}")
    t0 = time.perf_counter()
    if args.four_chips:
        phase_four_chips(devices)
    else:
        phase_pca(dev)
        phase_logreg_grid(dev)
        phase_live(dev)
    print(f"total {time.perf_counter() - t0:.1f} s")
    if FAILURES:
        print(f"chip_smoke: {len(FAILURES)} check(s) failed:", file=sys.stderr)
        for f in FAILURES:
            print(f"  {f}", file=sys.stderr)
        return 1
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
