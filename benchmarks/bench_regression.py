"""Benchmark-regression gate: rerun the committed grids and diff the
``BENCH_sweep.json`` / ``BENCH_convergence.json`` artifacts.

The engines are deterministic given their seeds, so a rerun of a committed
grid must reproduce the artifact's *orderings* exactly; drift means a
semantic change to an engine or the latency model.  The gate:

* **fail** when an ordering changes — a sweep regime's method ranking (by
  best-w mean iteration time), the ``dsag_beats_sag_and_coded`` verdict,
  the convergence grid's time-to-gap ranking or
  ``dsag_fastest_to_gap`` / ``ordering_dsag_sag_coded`` verdicts, the
  ``lb_scan`` column's DSAG-with-LB verdict, the §6 scan-vs-host
  bit-exactness, the ``churn`` column's elastic-fleet pins (scan-vs-
  host bit-exactness under worker death/rejoin and the dsag < sag <
  coded ordering surviving churn), or the ``kernel_backend`` column's
  per-backend pins (Pallas-vs-XLA bit-exactness on the artifact's
  platform, per-backend trajectory digests, per-backend method
  rankings; cross-platform the Pallas-vs-XLA diff is gated by a
  relative tolerance instead), or the ``live_validation`` column's
  sim-to-live pins (the *live* trainer's (mask, flush, evict) streams
  must match the scalar simulator bit-for-bit on the shared trace, and
  the measured wall-clock dsag-before-sag time-to-gap ordering under
  injected stragglers must survive);
* **warn** (exit 0) when speedup ratios drift by more than 15% — both
  the deterministic DSAG-over-baseline ratios and the wall-clock
  ``lb_scan`` scan-vs-host speedup (machine-dependent by nature, so a
  flip of ``lb_scan_faster_than_host`` on a noisy runner also only
  warns).

The convergence artifact's ``pca_paper_scale`` column is *not* re-run
here (it takes minutes by design); its orderings are covered at reduced
scale by the slow-marked tests.  The ``pca_grid_sharded`` column *is*
re-run: the 10x scenario grid goes through the sharded scan (however many
devices the runner exposes — CI sets
``XLA_FLAGS=--xla_force_host_platform_device_count=4``) and through the
single-device scan; ordering flips and any sharded-vs-unsharded
bit-exactness break fail, while the wall-clock device-scaling ratio only
warns (fake host devices timeslice a single core).

Run from the repo root:

    PYTHONPATH=src python benchmarks/bench_regression.py [BENCH_sweep.json]
    PYTHONPATH=src python benchmarks/bench_regression.py BENCH_convergence.json --kind convergence
"""

from __future__ import annotations

import dataclasses
import json
import sys
import time

SPEEDUP_DRIFT_TOLERANCE = 0.15
SPEEDUP_KEYS = ("sag_over_dsag", "coded_over_dsag")
CONV_SPEEDUP_KEYS = ("sag_over_dsag", "coded_over_dsag", "sgd_over_dsag")


class GridMismatch(RuntimeError):
    """The committed artifact's grid cannot be reproduced by the rerun."""


def method_ranking(cells: dict[str, dict], regime: str) -> list[str]:
    """Methods sorted fastest-first by their best-w mean iteration time."""
    best: dict[str, float] = {}
    for key, cell in cells.items():
        reg, method, _w = key.split("/")
        if reg != regime:
            continue
        t = cell["mean_iter_time"]
        if method not in best or t < best[method]:
            best[method] = t
    return sorted(best, key=best.get)


def compare_sweep(committed: dict, fresh: dict) -> tuple[list[str], list[str]]:
    """Diff two BENCH_sweep payloads; returns (failures, warnings)."""
    failures: list[str] = []
    warnings: list[str] = []
    for regime in committed["grid"]["regimes"]:
        if regime not in fresh["grid"]["regimes"]:
            failures.append(f"{regime}: regime missing from rerun")
            continue
        old_rank = method_ranking(committed["cells"], regime)
        new_rank = method_ranking(fresh["cells"], regime)
        if old_rank != new_rank:
            failures.append(
                f"{regime}: method ordering flipped {old_rank} -> {new_rank}"
            )
        old_o = committed["ordering"].get(regime, {})
        new_o = fresh["ordering"].get(regime, {})
        old_verdict = old_o.get("dsag_beats_sag_and_coded")
        new_verdict = new_o.get("dsag_beats_sag_and_coded")
        if old_verdict != new_verdict:
            failures.append(
                f"{regime}: dsag_beats_sag_and_coded flipped "
                f"{old_verdict} -> {new_verdict}"
            )
        for key in SPEEDUP_KEYS:
            if key in old_o and key in new_o and old_o[key] > 0:
                drift = abs(new_o[key] / old_o[key] - 1.0)
                if drift > SPEEDUP_DRIFT_TOLERANCE:
                    warnings.append(
                        f"{regime}: {key} drifted {drift:.0%} "
                        f"({old_o[key]:.2f} -> {new_o[key]:.2f})"
                    )
    return failures, warnings


def rerun_grid(committed: dict) -> dict:
    """Re-execute the committed artifact's grid (engine only, no scalar
    timing) and summarize it with the same results layer.

    The artifact's ``grid`` section does not record every sweep parameter,
    so the swept w values are reconstructed from the cell keys, the regimes
    are matched by name against the known regime presets, and any cell-key
    mismatch between the rerun and the artifact is an explicit failure
    (raised as ``GridMismatch``) rather than a silent comparison of
    different grids.
    """
    from repro.experiments import outcome_to_dict, run_sweep
    from repro.experiments.grid import DEFAULT_REGIMES

    grid = committed["grid"]
    known_regimes = {r.name: r for r in DEFAULT_REGIMES}
    regimes = []
    for name in grid["regimes"]:
        if name not in known_regimes:
            raise GridMismatch(
                f"regime {name!r} in the committed artifact is not a known "
                "preset; rerun cannot reproduce the grid"
            )
        regimes.append(known_regimes[name])
    # swept w values: the w cells of the w-swept methods (sgd / dsag)
    w_values = sorted(
        {
            int(key.split("/")[2][1:])
            for key in committed["cells"]
            if key.split("/")[1] in ("sgd", "dsag")
        }
    )
    outcome = run_sweep(
        n_workers=grid["n_workers"],
        n_seeds=grid["n_seeds"],
        num_iterations=grid["num_iterations"],
        w_values=w_values,
        w_fracs=(),
        regimes=regimes,
        seed=grid.get("seed", 0),
    )
    fresh = outcome_to_dict(outcome)
    if set(fresh["cells"]) != set(committed["cells"]):
        missing = set(committed["cells"]) - set(fresh["cells"])
        added = set(fresh["cells"]) - set(committed["cells"])
        raise GridMismatch(
            f"rerun produced different grid cells (missing {sorted(missing)}, "
            f"unexpected {sorted(added)}); the artifact was generated with "
            "parameters the rerun cannot reconstruct — regenerate it"
        )
    return fresh


# ---------------------------------------------------------------------------
# BENCH_convergence.json (time-to-suboptimality grid + the lb_scan column)
# ---------------------------------------------------------------------------


def convergence_ranking(methods: dict[str, dict]) -> list[str]:
    """Methods sorted fastest-first by median time-to-gap (None/inf last).

    Ties (e.g. two methods that both never reach the gap) break by method
    name: the committed artifact is key-sorted JSON while a fresh payload
    is insertion-ordered, so a dict-order tie-break would flip spuriously.
    """

    def key(name: str):
        t = methods[name].get("median_time_to_gap")
        return (float("inf") if t is None else float(t), name)

    return sorted(methods, key=key)


def compare_convergence(committed: dict, fresh: dict) -> tuple[list[str], list[str]]:
    """Diff two BENCH_convergence payloads; returns (failures, warnings)."""
    failures: list[str] = []
    warnings: list[str] = []
    old_rank = convergence_ranking(committed["methods"])
    new_rank = convergence_ranking(fresh["methods"])
    if old_rank != new_rank:
        failures.append(
            f"convergence: time-to-gap ranking flipped {old_rank} -> {new_rank}"
        )
    old_o, new_o = committed["ordering"], fresh["ordering"]
    for verdict in ("dsag_fastest_to_gap", "ordering_dsag_sag_coded"):
        if old_o.get(verdict) != new_o.get(verdict):
            failures.append(
                f"convergence: {verdict} flipped "
                f"{old_o.get(verdict)} -> {new_o.get(verdict)}"
            )
    for key in CONV_SPEEDUP_KEYS:
        if key in old_o and key in new_o and old_o[key] and old_o[key] > 0:
            drift = abs(new_o[key] / old_o[key] - 1.0)
            if drift > SPEEDUP_DRIFT_TOLERANCE:
                warnings.append(
                    f"convergence: {key} drifted {drift:.0%} "
                    f"({old_o[key]:.2f} -> {new_o[key]:.2f})"
                )
    old_lb = committed.get("lb_scan")
    new_lb = fresh.get("lb_scan")
    if old_lb is not None and new_lb is not None:
        if not new_lb.get("bitexact_scan_vs_host", False):
            failures.append(
                "lb_scan: fused scan no longer bit-exact vs the host engine"
            )
        olo, nlo = old_lb.get("ordering", {}), new_lb.get("ordering", {})
        if olo.get("dsag_lb_fastest_to_gap") != nlo.get("dsag_lb_fastest_to_gap"):
            failures.append(
                f"lb_scan: dsag_lb_fastest_to_gap flipped "
                f"{olo.get('dsag_lb_fastest_to_gap')} -> "
                f"{nlo.get('dsag_lb_fastest_to_gap')}"
            )
        # wall-clock properties only warn: CI runners are noisy by nature
        # (and the gate's single-run rerun omits them entirely)
        if (
            "lb_scan_faster_than_host" in old_lb
            and "lb_scan_faster_than_host" in new_lb
            and bool(old_lb["lb_scan_faster_than_host"])
            != bool(new_lb["lb_scan_faster_than_host"])
        ):
            warnings.append(
                f"lb_scan: lb_scan_faster_than_host flipped "
                f"{old_lb.get('lb_scan_faster_than_host')} -> "
                f"{new_lb.get('lb_scan_faster_than_host')} (wall clock)"
            )
        os_, ns_ = old_lb.get("speedup_scan_over_host"), new_lb.get(
            "speedup_scan_over_host"
        )
        if os_ and ns_ and os_ > 0:
            drift = abs(ns_ / os_ - 1.0)
            if drift > SPEEDUP_DRIFT_TOLERANCE:
                warnings.append(
                    f"lb_scan: speedup_scan_over_host drifted {drift:.0%} "
                    f"({os_:.2f} -> {ns_:.2f})"
                )
    old_ps = committed.get("pca_grid_sharded")
    new_ps = fresh.get("pca_grid_sharded")
    if old_ps is not None and new_ps is not None:
        ps_failures, ps_warnings = compare_pca_grid_sharded(old_ps, new_ps)
        failures.extend(ps_failures)
        warnings.extend(ps_warnings)
    old_ch = committed.get("churn")
    new_ch = fresh.get("churn")
    if old_ch is not None and new_ch is not None:
        ch_failures, ch_warnings = compare_churn_column(old_ch, new_ch)
        failures.extend(ch_failures)
        warnings.extend(ch_warnings)
    old_kb = committed.get("kernel_backend")
    new_kb = fresh.get("kernel_backend")
    if old_kb is not None and new_kb is not None:
        kb_failures, kb_warnings = compare_kernel_backend_column(old_kb, new_kb)
        failures.extend(kb_failures)
        warnings.extend(kb_warnings)
    old_lv = committed.get("live_validation")
    new_lv = fresh.get("live_validation")
    if old_lv is not None and new_lv is not None:
        lv_failures, lv_warnings = compare_live_validation_column(old_lv, new_lv)
        failures.extend(lv_failures)
        warnings.extend(lv_warnings)
    return failures, warnings


def run_lb_scan_column(
    problem,
    traces,
    dsag_config,
    *,
    num_iterations: int,
    eval_every: int,
    seed: int,
    gap: float,
    base_medians: dict[str, float] | None = None,
    warm_timings: bool = True,
) -> dict:
    """Run the §6 DSAG config through both engines; build the lb_scan column.

    With ``warm_timings`` (artifact generation) each engine runs twice —
    cold runs carry one-time jit compiles, and the headline speedup
    compares warm against warm.  The regression gate passes
    ``warm_timings=False``: one run per engine suffices for everything
    that can *fail* (bit-exactness, the DSAG-with-LB verdict), and the
    wall-clock fields are then omitted instead of emitting
    apples-to-oranges cold numbers (their drift checks skip on absence).
    Always asserts bit-exactness and records the DSAG-with-LB time-to-gap
    verdict against the non-LB baselines' medians from the main grid
    (same traces, common random numbers).
    """
    import numpy as np

    from repro.experiments import EngineConfig, run_convergence_batch

    cfg = dataclasses.replace(dsag_config, load_balance=True)

    def run(kind: str):
        t0 = time.perf_counter()
        res = run_convergence_batch(
            problem, traces, cfg, num_iterations,
            eval_every=eval_every, seed=seed, engine=EngineConfig(kind=kind),
        )
        return res, time.perf_counter() - t0

    host, host_cold_s = run("host")
    scan, scan_cold_s = run("scan")
    if warm_timings:
        _, host_s = run("host")
        _, scan_s = run("scan")
    else:
        host_s = scan_s = None
    bitexact = bool(
        np.array_equal(host.times, scan.times)
        and np.array_equal(host.suboptimality, scan.suboptimality, equal_nan=True)
        and np.array_equal(host.fresh_counts, scan.fresh_counts)
        and np.array_equal(
            host.per_worker_latency, scan.per_worker_latency, equal_nan=True
        )
        and host.repartition_events == scan.repartition_events
        and np.array_equal(host.evictions, scan.evictions)
        and np.array_equal(host.rejected_stale, scan.rejected_stale)
    )
    ttg = scan.time_to_gap(gap)
    t_lb = float(np.median(ttg))
    ordering = {
        "gap": gap,
        "median_time_to_gap_dsag_lb": t_lb,
        "reached_gap_frac_dsag_lb": float(np.isfinite(ttg).mean()),
    }
    if base_medians:
        for name, t in base_medians.items():
            if name != "dsag" and t and t > 0:
                ordering[f"{name}_over_dsag_lb"] = t / t_lb
        sag_t = base_medians.get("sag")
        coded_t = base_medians.get("coded")
        if sag_t is not None and coded_t is not None:
            ordering["dsag_lb_fastest_to_gap"] = float(
                t_lb < sag_t and t_lb < coded_t
            )
    out = {
        "config": {
            "w": cfg.w,
            "subpartitions": cfg.subpartitions,
            "eta": cfg.eta,
            "lb_startup_delay": cfg.lb_startup_delay,
            "lb_interval": cfg.lb_interval,
        },
        "host_seconds_cold": host_cold_s,
        "scan_seconds_cold": scan_cold_s,
        "bitexact_scan_vs_host": bitexact,
        "repartitions_mean": float(
            np.mean([len(ev) for ev in scan.repartition_events])
        ),
        "ordering": ordering,
    }
    if warm_timings:
        out.update(
            host_seconds=host_s,
            scan_seconds=scan_s,
            speedup_scan_over_host=host_s / max(scan_s, 1e-12),
            lb_scan_faster_than_host=bool(scan_s < host_s),
        )
    return out


#: every parameter of the churn column's run — stored inside the column
#: itself so the gate rerun reproduces it without guessing
CHURN_RECIPE = {
    "problem": "logreg_higgs",
    "num_samples": 4096,
    "n_workers": 40,
    "subpartitions": 4,
    "w": 32,
    "eta": 0.25,
    "n_scenarios": 5,
    "num_iterations": 40,
    "eval_every": 5,
    "regime": "heavy_bursts",
    "seed": 0,
    "gap": 0.2,
    # elastic-fleet schedule, as fractions of the churn-free run length:
    # the slowest fifth of the fleet dies at 30% of the run and half of
    # the dead workers rejoin at 70%
    "death_frac": 0.2,
    "death_at_frac": 0.3,
    "revive_frac": 0.5,
    "revive_at_frac": 0.7,
}


def run_churn_column(recipe: dict | None = None) -> dict:
    """DSAG/SAG/coded through an elastic-fleet churn schedule, both engines.

    Builds the same kind of heterogeneous heavy-burst fleet as the main
    convergence grid (smaller: the recipe's N/S/T), derives a
    death-then-partial-rejoin :class:`~repro.latency.model.ChurnSchedule`
    from a churn-free latency replay (deterministic given the seed, so the
    gate rerun lands on the identical schedule), and runs each method
    through the host loop AND the fused scan on the churned traces.
    Fail-able outputs: per-field scan-vs-host bit-exactness under churn
    and the dsag < sag < coded time-to-gap ordering (the paper's §7
    straggler-resilience claim must survive workers dying mid-run).
    """
    import numpy as np

    from repro.core.problems import LogisticRegressionProblem, make_higgs_like
    from repro.experiments import (
        EngineConfig,
        default_convergence_methods,
        run_convergence_batch,
    )
    from repro.experiments.grid import DEFAULT_REGIMES
    from repro.experiments.sweep import replay_batch
    from repro.latency.model import (
        ChurnSchedule,
        make_heterogeneous_cluster,
        sample_fleet,
    )

    r = dict(CHURN_RECIPE)
    if recipe:
        r.update(recipe)
    if r["problem"] != "logreg_higgs":
        raise GridMismatch(
            f"churn recipe problem {r['problem']!r} is not reproducible here"
        )
    regimes = {reg.name: reg for reg in DEFAULT_REGIMES}
    if r["regime"] not in regimes:
        raise GridMismatch(f"unknown regime {r['regime']!r} in churn recipe")
    regime = regimes[r["regime"]]
    X, y = make_higgs_like(r["num_samples"], seed=r["seed"])
    prob = LogisticRegressionProblem(X=X, y=y)
    N, sp, T = r["n_workers"], r["subpartitions"], r["num_iterations"]
    c_task = prob.compute_cost(1, max(prob.num_samples // (N * sp), 1))
    cluster = make_heterogeneous_cluster(
        N, seed=r["seed"], burst_rate=0.0, load_unit=c_task
    )
    traces = sample_fleet(
        cluster,
        r["n_scenarios"],
        T,
        burst_rate=regime.rate,
        burst_factor_mean=regime.factor_mean,
        burst_duration_mean=regime.duration_mean,
        seed=r["seed"] + 1,
    )
    # anchor the schedule to the churn-free run length (latency replay
    # only — no gradients), then kill the slowest workers and revive half
    base = replay_batch(traces, r["w"], T)
    total = float(np.median(base.iteration_times[:, -1]))
    death_at = r["death_at_frac"] * total
    revive_at = r["revive_at_frac"] * total
    sd = np.asarray(traces.slowdown)
    n_dead = max(1, int(round(r["death_frac"] * N)))
    dead = np.argsort(-sd, kind="stable")[:n_dead]
    n_back = int(round(r["revive_frac"] * n_dead))
    revived = dead[:n_back]
    alive0 = np.ones(N, bool)
    alive1 = alive0.copy()
    alive1[dead] = False
    alive2 = alive1.copy()
    alive2[revived] = True
    churn = ChurnSchedule(
        times=np.array([death_at, revive_at]),
        slowdown=np.stack([sd, sd, sd]),
        alive=np.stack([alive0, alive1, alive2]),
    )
    churned = traces.with_churn(churn)
    methods = default_convergence_methods(
        N, w=r["w"], eta=r["eta"], subpartitions=sp
    )
    bitexact = True
    cols: dict[str, dict] = {}
    for name in ("dsag", "sag", "coded"):
        host = run_convergence_batch(
            prob, churned, methods[name], T,
            eval_every=r["eval_every"], seed=r["seed"],
            engine=EngineConfig(kind="host"),
        )
        scan = run_convergence_batch(
            prob, churned, methods[name], T,
            eval_every=r["eval_every"], seed=r["seed"],
            engine=EngineConfig(kind="scan"),
        )
        bitexact = bitexact and bool(
            np.array_equal(host.times, scan.times)
            and np.array_equal(
                host.suboptimality, scan.suboptimality, equal_nan=True
            )
            and np.array_equal(host.fresh_counts, scan.fresh_counts)
            and np.array_equal(
                host.per_worker_latency, scan.per_worker_latency,
                equal_nan=True,
            )
            and host.repartition_events == scan.repartition_events
            and np.array_equal(host.evictions, scan.evictions)
            and np.array_equal(host.rejected_stale, scan.rejected_stale)
        )
        ttg = scan.time_to_gap(r["gap"])
        med = float(np.median(ttg))
        cols[name] = {
            "median_time_to_gap": med if np.isfinite(med) else None,
            "reached_gap_frac": float(np.isfinite(ttg).mean()),
        }
    t_dsag = cols["dsag"]["median_time_to_gap"]
    t_sag = cols["sag"]["median_time_to_gap"]
    t_coded = cols["coded"]["median_time_to_gap"]
    finite = (
        t_dsag is not None and t_sag is not None and t_coded is not None
    )
    ordering = {
        "gap": r["gap"],
        "ordering_dsag_sag_coded": float(
            finite and t_dsag < t_sag < t_coded
        ),
    }
    if finite and t_dsag > 0:
        ordering["sag_over_dsag"] = t_sag / t_dsag
        ordering["coded_over_dsag"] = t_coded / t_dsag
    return {
        "recipe": r,
        "schedule": {
            "death_at": death_at,
            "revive_at": revive_at,
            "dead_workers": [int(i) for i in dead],
            "revived_workers": [int(i) for i in revived],
        },
        "bitexact_scan_vs_host": bitexact,
        "methods": cols,
        "ordering": ordering,
    }


def compare_churn_column(committed: dict, fresh: dict) -> tuple[list[str], list[str]]:
    """Diff the ``churn`` columns; returns (failures, warnings)."""
    failures: list[str] = []
    warnings: list[str] = []
    if not fresh.get("bitexact_scan_vs_host", False):
        failures.append(
            "churn: fused scan no longer bit-exact vs the host engine "
            "under fleet churn"
        )
    old_rank = convergence_ranking(committed["methods"])
    new_rank = convergence_ranking(fresh["methods"])
    if old_rank != new_rank:
        failures.append(
            f"churn: time-to-gap ranking flipped {old_rank} -> {new_rank}"
        )
    old_o, new_o = committed["ordering"], fresh["ordering"]
    if old_o.get("ordering_dsag_sag_coded") != new_o.get(
        "ordering_dsag_sag_coded"
    ):
        failures.append(
            f"churn: ordering_dsag_sag_coded flipped "
            f"{old_o.get('ordering_dsag_sag_coded')} -> "
            f"{new_o.get('ordering_dsag_sag_coded')}"
        )
    for key in SPEEDUP_KEYS:
        if key in old_o and key in new_o and old_o[key] > 0:
            drift = abs(new_o[key] / old_o[key] - 1.0)
            if drift > SPEEDUP_DRIFT_TOLERANCE:
                warnings.append(
                    f"churn: {key} drifted {drift:.0%} "
                    f"({old_o[key]:.2f} -> {new_o[key]:.2f})"
                )
    return failures, warnings


#: every parameter of the live_validation column's run — stored inside the
#: column itself so the gate rerun reproduces it without guessing.  margin
#: is 0 so dsag and sag share masks (identical collection windows) and the
#: comparison isolates the §5 stale-acceptance semantics; the §5.1 margin
#: rule is pinned separately by the test suite.
LIVE_VALIDATION_RECIPE = {
    "problem": "logreg_higgs",
    "num_samples": 512,
    "n_workers": 8,
    "w": 6,
    "eta": 0.25,
    "margin": 0.0,
    "n_scenarios": 2,
    "scenario": 0,
    "num_iterations": 80,
    "eval_every": 5,
    "regime": "heavy_bursts",
    "seed": 0,
    "gap": 0.05,
    #: real seconds slept per unit of virtual straggler time — large enough
    #: that the dsag/sag collection-time difference dominates step compute
    "time_scale": 25.0,
}


def run_live_validation_column(recipe: dict | None = None) -> dict:
    """Run the *live* trainer under injected stragglers; validate it against
    the scalar convergence engine on the same trace.

    The sim-to-live gap, closed twice over:

    * **streams**: the trainer's Tier-2 controller must log exactly the
      (mask, flush, evict) step inputs the scalar simulator records for
      the shared ``FleetTraces`` scenario (the cross-layer pin — fails the
      gate if the live control plane drifts from §5/§6.3 semantics);
    * **wall clock**: ``time_scale`` turns virtual straggler waits into
      real sleeps, so the measured wall time-to-gap per method must
      reproduce the simulator's *predicted* time-to-gap (drift warns) and
      the paper's dsag-before-sag ordering must survive on real hardware
      (a flip fails).
    """
    import numpy as np

    from repro.cluster.simulator import MethodConfig
    from repro.core.problems import LogisticRegressionProblem, make_higgs_like
    from repro.experiments.grid import DEFAULT_REGIMES
    from repro.ft.validation import pin_streams
    from repro.latency.model import make_heterogeneous_cluster, sample_fleet
    from repro.launch.paper_jobs import paper_train_config
    from repro.launch.train import Trainer, TrainerOptions

    r = dict(LIVE_VALIDATION_RECIPE)
    if recipe:
        r.update(recipe)
    if r["problem"] != "logreg_higgs":
        raise GridMismatch(
            f"live_validation recipe problem {r['problem']!r} is not "
            "reproducible here"
        )
    regimes = {reg.name: reg for reg in DEFAULT_REGIMES}
    if r["regime"] not in regimes:
        raise GridMismatch(
            f"unknown regime {r['regime']!r} in live_validation recipe"
        )
    regime = regimes[r["regime"]]
    X, y = make_higgs_like(r["num_samples"], seed=r["seed"])
    prob = LogisticRegressionProblem(X=X, y=y)
    N, T = r["n_workers"], r["num_iterations"]
    c_task = prob.compute_cost(1, max(prob.num_samples // N, 1))
    cluster = make_heterogeneous_cluster(
        N, seed=r["seed"] + 3, burst_rate=0.0, load_unit=c_task
    )
    traces = sample_fleet(
        cluster,
        r["n_scenarios"],
        4 * T,
        burst_rate=regime.rate,
        burst_factor_mean=regime.factor_mean,
        burst_duration_mean=regime.duration_mean,
        seed=r["seed"] + 7,
    )
    methods: dict[str, dict] = {}
    for name in ("dsag", "sag"):
        cfg = MethodConfig(
            name=name, w=r["w"], eta=r["eta"], margin=r["margin"],
            subpartitions=1,
        )
        ctrl, sim, hist = pin_streams(
            prob, cluster, traces, r["scenario"], cfg, T, seed=r["seed"]
        )
        tc = dataclasses.replace(
            paper_train_config(r["eta"]), dsag_margin=r["margin"]
        )
        opts = TrainerOptions(
            arch="logreg",
            steps=T,
            samples=r["num_samples"],
            num_groups=N,
            dsag_w=r["w"],
            method=name,
            traces=traces,
            scenario=r["scenario"],
            train_config=tc,
            simulate_stragglers=False,
            # the detector must not perturb the pin: persistent stragglers
            # are the *subject* here, not failures
            failure_max_misses=10**6,
            time_scale=r["time_scale"],
            eval_every=r["eval_every"],
            log_every=10**6,
            seed=r["seed"],
        )
        live = Trainer(opts).run()
        streams_match = bool(
            ctrl == sim
            and np.array_equal(np.stack(live["mask_stream"]), sim.mask)
            and np.array_equal(np.stack(live["flush_stream"]), sim.flush)
            and np.array_equal(np.stack(live["evict_stream"]), sim.evict)
        )
        virtual_ttg = hist.time_to_gap(r["gap"])
        measured = next(
            (wall for (_s, wall, _v, g) in live["eval"] if g <= r["gap"]), None
        )
        methods[name] = {
            "streams_match_simulator": streams_match,
            "virtual_time_to_gap": (
                float(virtual_ttg) if np.isfinite(virtual_ttg) else None
            ),
            "predicted_time_to_gap_s": (
                float(virtual_ttg * r["time_scale"])
                if np.isfinite(virtual_ttg)
                else None
            ),
            "measured_wall_to_gap_s": (
                float(measured) if measured is not None else None
            ),
            "final_gap_live": float(live["eval"][-1][3]),
            "wall_seconds": float(live["wall_seconds"][0]),
        }
    d, s = methods["dsag"], methods["sag"]
    ordering: dict = {"gap": r["gap"]}
    if (
        d["virtual_time_to_gap"] is not None
        and s["virtual_time_to_gap"] is not None
    ):
        ordering["predicted_dsag_faster_than_sag"] = float(
            d["virtual_time_to_gap"] <= s["virtual_time_to_gap"]
        )
    if (
        d["measured_wall_to_gap_s"] is not None
        and s["measured_wall_to_gap_s"] is not None
    ):
        ordering["live_dsag_faster_than_sag"] = float(
            d["measured_wall_to_gap_s"] < s["measured_wall_to_gap_s"]
        )
        ordering["sag_over_dsag_wall"] = (
            s["measured_wall_to_gap_s"] / d["measured_wall_to_gap_s"]
        )
    for name, m in methods.items():
        if m["predicted_time_to_gap_s"] and m["measured_wall_to_gap_s"]:
            m["measured_over_predicted"] = (
                m["measured_wall_to_gap_s"] / m["predicted_time_to_gap_s"]
            )
    return {"recipe": r, "methods": methods, "ordering": ordering}


def compare_live_validation_column(
    committed: dict, fresh: dict
) -> tuple[list[str], list[str]]:
    """Diff the ``live_validation`` columns; returns (failures, warnings)."""
    failures: list[str] = []
    warnings: list[str] = []
    for name, m in fresh.get("methods", {}).items():
        if not m.get("streams_match_simulator", False):
            failures.append(
                f"live_validation: {name} live trainer streams no longer "
                "match the scalar simulator (sim-to-live pin broken)"
            )
        if m.get("measured_wall_to_gap_s") is None:
            failures.append(
                f"live_validation: live {name} run never reached the gap"
            )
    old_o, new_o = committed.get("ordering", {}), fresh.get("ordering", {})
    # the deterministic (virtual) ordering and the measured wall-clock
    # ordering must both survive — the latter is the paper's actual claim
    for verdict in ("predicted_dsag_faster_than_sag", "live_dsag_faster_than_sag"):
        if old_o.get(verdict) != new_o.get(verdict):
            failures.append(
                f"live_validation: {verdict} flipped "
                f"{old_o.get(verdict)} -> {new_o.get(verdict)}"
            )
    os_, ns_ = old_o.get("sag_over_dsag_wall"), new_o.get("sag_over_dsag_wall")
    if os_ and ns_ and os_ > 0:
        drift = abs(ns_ / os_ - 1.0)
        if drift > SPEEDUP_DRIFT_TOLERANCE:
            warnings.append(
                f"live_validation: sag_over_dsag_wall drifted {drift:.0%} "
                f"({os_:.2f} -> {ns_:.2f}) (wall clock)"
            )
    for name, m in fresh.get("methods", {}).items():
        om = committed.get("methods", {}).get(name, {})
        ov, nv = om.get("measured_over_predicted"), m.get("measured_over_predicted")
        if ov and nv and ov > 0:
            drift = abs(nv / ov - 1.0)
            if drift > SPEEDUP_DRIFT_TOLERANCE:
                warnings.append(
                    f"live_validation: {name} measured_over_predicted drifted "
                    f"{drift:.0%} ({ov:.2f} -> {nv:.2f}) (wall clock)"
                )
    return failures, warnings


#: cross-backend tolerance on the Pallas-vs-XLA suboptimality trajectories.
#: On one platform the comparison must be *bit-exact* (CPU CI runs the
#: Pallas twins in interpret mode against the same jitted arithmetic); the
#: relative tolerance only applies when the artifact and the rerun disagree
#: on platform, where a real Pallas compile may round differently.
KERNEL_BACKEND_REL_TOL = 1e-3

#: every parameter of the kernel_backend column's run — stored inside the
#: column itself so the gate rerun reproduces it without guessing
KERNEL_BACKEND_RECIPE = {
    "seed": 0,
    "n_scenarios": 3,
    "num_iterations": 30,
    "eval_every": 5,
    "n_workers": 8,
    "subpartitions": 3,
    "regime": "heavy_bursts",
    "logreg": {"num_samples": 1024, "w": 6, "eta": 0.25,
               "methods": ["dsag", "sag", "coded"]},
    "pca": {"n_rows": 512, "n_cols": 64, "k": 4, "w": 6, "eta": 0.9,
            "methods": ["dsag", "sag"]},
}


def _trajectory_digest(res) -> str:
    """Short sha256 over a result's deterministic trajectory arrays.

    The artifact stores digests instead of the arrays themselves, so the
    gate rerun can check "bit-exact within a backend" (same platform, same
    backend, same bits) without committing megabytes of trajectories.
    """
    import hashlib

    import numpy as np

    h = hashlib.sha256()
    for arr in (res.times, res.suboptimality, res.fresh_counts):
        h.update(np.ascontiguousarray(np.asarray(arr)).tobytes())
    return h.hexdigest()[:16]


def run_kernel_backend_column(recipe: dict | None = None) -> dict:
    """Pin ``kernel_backend="pallas"`` against ``"xla"`` on both problems.

    Runs the recipe's logreg and PCA method grids through the fused scan
    twice — once per kernel backend — on identical fleets (common random
    numbers).  Fail-able outputs: same-platform Pallas-vs-XLA
    bit-exactness across every result field, per-backend trajectory
    digests (a rerun on the artifact's platform must reproduce each
    backend's bits exactly), and the per-backend method rankings by median
    final suboptimality.  Cross-platform, the digest check is skipped and
    the Pallas-vs-XLA diff is gated by :data:`KERNEL_BACKEND_REL_TOL`
    instead.
    """
    import jax
    import numpy as np

    from repro.core.problems import (
        LogisticRegressionProblem,
        PCAProblem,
        make_genomics_like_matrix,
        make_higgs_like,
    )
    from repro.experiments import (
        EngineConfig,
        default_convergence_methods,
        run_convergence_batch,
    )
    from repro.experiments.grid import DEFAULT_REGIMES
    from repro.latency.model import make_heterogeneous_cluster, sample_fleet

    r = dict(KERNEL_BACKEND_RECIPE)
    if recipe:
        r.update(recipe)
    regimes = {reg.name: reg for reg in DEFAULT_REGIMES}
    if r["regime"] not in regimes:
        raise GridMismatch(
            f"unknown regime {r['regime']!r} in kernel_backend recipe"
        )
    regime = regimes[r["regime"]]
    lr, pc = r["logreg"], r["pca"]
    X, y = make_higgs_like(lr["num_samples"], seed=r["seed"])
    problems = {
        "logreg": (LogisticRegressionProblem(X=X, y=y), lr),
        "pca": (
            PCAProblem(
                X=make_genomics_like_matrix(
                    pc["n_rows"], pc["n_cols"], seed=r["seed"]
                ),
                k=pc["k"],
            ),
            pc,
        ),
    }
    N, sp, T = r["n_workers"], r["subpartitions"], r["num_iterations"]
    bitexact = True
    max_rel = 0.0
    cols: dict[str, dict] = {}
    for pname, (prob, pr) in problems.items():
        c_task = prob.compute_cost(1, max(prob.num_samples // (N * sp), 1))
        cluster = make_heterogeneous_cluster(
            N, seed=r["seed"], burst_rate=0.0, load_unit=c_task
        )
        traces = sample_fleet(
            cluster,
            r["n_scenarios"],
            T,
            burst_rate=regime.rate,
            burst_factor_mean=regime.factor_mean,
            burst_duration_mean=regime.duration_mean,
            seed=r["seed"] + 1,
        )
        methods: dict[str, dict] = {}
        for name in pr["methods"]:
            cfg = default_convergence_methods(
                N, w=pr["w"], eta=pr["eta"], subpartitions=sp
            )[name]
            runs = {}
            for backend in ("xla", "pallas"):
                runs[backend] = run_convergence_batch(
                    prob, traces, cfg, T,
                    eval_every=r["eval_every"], seed=r["seed"],
                    engine=EngineConfig(kind="scan", kernel_backend=backend),
                )
            xla, pal = runs["xla"], runs["pallas"]
            bitexact = bitexact and bool(
                np.array_equal(xla.times, pal.times)
                and np.array_equal(
                    xla.suboptimality, pal.suboptimality, equal_nan=True
                )
                and np.array_equal(xla.fresh_counts, pal.fresh_counts)
                and np.array_equal(
                    xla.per_worker_latency, pal.per_worker_latency,
                    equal_nan=True,
                )
                and xla.repartition_events == pal.repartition_events
                and np.array_equal(xla.evictions, pal.evictions)
                and np.array_equal(xla.rejected_stale, pal.rejected_stale)
            )
            a = np.asarray(xla.suboptimality)
            b = np.asarray(pal.suboptimality)
            fa, fb = np.isfinite(a), np.isfinite(b)
            if not np.array_equal(fa, fb):
                max_rel = float("inf")
            elif fa.any():
                rel = np.abs(a[fa] - b[fa]) / np.maximum(np.abs(a[fa]), 1e-12)
                max_rel = max(max_rel, float(np.max(rel)))
            entry = {}
            for backend, res in runs.items():
                entry[f"median_final_subopt_{backend}"] = float(
                    np.median(np.asarray(res.suboptimality)[:, -1])
                )
                entry[f"digest_{backend}"] = _trajectory_digest(res)
            methods[name] = entry
        rankings = {}
        for backend in ("xla", "pallas"):
            col = f"median_final_subopt_{backend}"
            rankings[backend] = sorted(
                methods, key=lambda m, c=col: (methods[m][c], m)
            )
        cols[pname] = {
            "methods": methods,
            "ranking_xla": rankings["xla"],
            "ranking_pallas": rankings["pallas"],
        }
    return {
        "recipe": r,
        "platform": jax.default_backend(),
        "bitexact_pallas_vs_xla": bitexact,
        "max_rel_diff_pallas_vs_xla": max_rel,
        "problems": cols,
    }


def compare_kernel_backend_column(
    committed: dict, fresh: dict
) -> tuple[list[str], list[str]]:
    """Diff the ``kernel_backend`` columns; returns (failures, warnings)."""
    failures: list[str] = []
    warnings: list[str] = []
    same_platform = committed.get("platform") == fresh.get("platform")
    if not fresh.get("bitexact_pallas_vs_xla", False):
        rel = fresh.get("max_rel_diff_pallas_vs_xla")
        if fresh.get("platform") == "cpu":
            failures.append(
                "kernel_backend: pallas (interpret) no longer bit-exact vs "
                "xla on cpu"
            )
        elif rel is None or rel > KERNEL_BACKEND_REL_TOL:
            failures.append(
                f"kernel_backend: pallas vs xla max relative diff {rel} "
                f"exceeds tolerance {KERNEL_BACKEND_REL_TOL}"
            )
        else:
            warnings.append(
                f"kernel_backend: pallas vs xla not bit-exact on "
                f"{fresh.get('platform')} (max rel diff {rel:.1e}, within "
                "cross-backend tolerance)"
            )
    for pname, old_p in committed.get("problems", {}).items():
        new_p = fresh.get("problems", {}).get(pname)
        if new_p is None:
            failures.append(
                f"kernel_backend: problem column {pname!r} missing from rerun"
            )
            continue
        for backend in ("xla", "pallas"):
            ork = old_p.get(f"ranking_{backend}")
            nrk = new_p.get(f"ranking_{backend}")
            if ork != nrk:
                failures.append(
                    f"kernel_backend: {pname} {backend} final-suboptimality "
                    f"ranking flipped {ork} -> {nrk}"
                )
            for m, om in old_p.get("methods", {}).items():
                nm = new_p.get("methods", {}).get(m, {})
                if same_platform and om.get(f"digest_{backend}") != nm.get(
                    f"digest_{backend}"
                ):
                    failures.append(
                        f"kernel_backend: {pname}/{m} {backend} trajectory "
                        "digest changed (no longer bit-exact within backend)"
                    )
                ov = om.get(f"median_final_subopt_{backend}")
                nv = nm.get(f"median_final_subopt_{backend}")
                if ov and nv and ov > 0:
                    drift = abs(nv / ov - 1.0)
                    if drift > SPEEDUP_DRIFT_TOLERANCE:
                        warnings.append(
                            f"kernel_backend: {pname}/{m} {backend} "
                            f"median_final_subopt drifted {drift:.0%} "
                            f"({ov:.3g} -> {nv:.3g})"
                        )
    return failures, warnings


def run_pca_grid_sharded_column(
    *,
    n_scenarios: int = 40,
    num_devices: int | None = None,
    seed: int = 0,
) -> dict:
    """10x the calibrated paper-scale PCA grid through the *sharded* scan.

    Runs the grid twice — once on a ``num_devices``-wide scenario mesh
    (clamped to the devices actually present; CPU demo via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=4``) and once on the
    single-device scan — and records per-method bit-exactness between the
    two plus the wall-clock device-scaling ratio.  Orderings and
    bit-exactness are deterministic (gate failures); the scaling ratio is
    wall clock and only ever warns (a single-core runner timeslices its
    fake host devices, so ~1x there is expected).
    """
    import jax
    import numpy as np

    from repro.experiments import (
        EngineConfig,
        convergence_payload,
        paper_scale_pca_sweep,
    )

    avail = len(jax.devices())
    D = min(num_devices if num_devices is not None else 4, avail)
    sharded_out, gap = paper_scale_pca_sweep(
        seed=seed,
        n_scenarios=n_scenarios,
        engine=EngineConfig(kind="scan", num_devices=D),
    )
    plain_out, _ = paper_scale_pca_sweep(
        seed=seed, n_scenarios=n_scenarios, engine=EngineConfig(kind="scan")
    )
    bitexact = all(
        np.array_equal(
            sharded_out.results[m].times, plain_out.results[m].times
        )
        and np.array_equal(
            sharded_out.results[m].suboptimality,
            plain_out.results[m].suboptimality,
            equal_nan=True,
        )
        for m in sharded_out.results
    )
    payload = convergence_payload(sharded_out, gap)
    payload.update(
        num_devices=D,
        seed=seed,
        bitexact_sharded_vs_unsharded=bool(bitexact),
        sharded_seconds=sharded_out.engine_seconds,
        unsharded_seconds=plain_out.engine_seconds,
        device_scaling=plain_out.engine_seconds
        / max(sharded_out.engine_seconds, 1e-12),
    )
    return payload


def compare_pca_grid_sharded(committed: dict, fresh: dict) -> tuple[list[str], list[str]]:
    """Diff the ``pca_grid_sharded`` columns; returns (failures, warnings)."""
    failures: list[str] = []
    warnings: list[str] = []
    if not fresh.get("bitexact_sharded_vs_unsharded", False):
        failures.append(
            "pca_grid_sharded: sharded grid no longer bit-exact vs the "
            "single-device scan"
        )
    old_rank = convergence_ranking(committed["methods"])
    new_rank = convergence_ranking(fresh["methods"])
    if old_rank != new_rank:
        failures.append(
            f"pca_grid_sharded: time-to-gap ranking flipped "
            f"{old_rank} -> {new_rank}"
        )
    old_o, new_o = committed["ordering"], fresh["ordering"]
    for verdict in ("dsag_fastest_to_gap", "ordering_dsag_sag_coded"):
        if old_o.get(verdict) != new_o.get(verdict):
            failures.append(
                f"pca_grid_sharded: {verdict} flipped "
                f"{old_o.get(verdict)} -> {new_o.get(verdict)}"
            )
    for key in CONV_SPEEDUP_KEYS:
        if key in old_o and key in new_o and old_o[key] and old_o[key] > 0:
            drift = abs(new_o[key] / old_o[key] - 1.0)
            if drift > SPEEDUP_DRIFT_TOLERANCE:
                warnings.append(
                    f"pca_grid_sharded: {key} drifted {drift:.0%} "
                    f"({old_o[key]:.2f} -> {new_o[key]:.2f})"
                )
    # the device-scaling ratio is wall clock (and ~1x on a single-core
    # runner timeslicing fake host devices) — drift only warns
    os_, ns_ = committed.get("device_scaling"), fresh.get("device_scaling")
    if os_ and ns_ and os_ > 0:
        drift = abs(ns_ / os_ - 1.0)
        if drift > SPEEDUP_DRIFT_TOLERANCE:
            warnings.append(
                f"pca_grid_sharded: device_scaling drifted {drift:.0%} "
                f"({os_:.2f} -> {ns_:.2f}) on "
                f"{fresh.get('num_devices')} device(s) (wall clock)"
            )
    return failures, warnings


def rerun_convergence(committed: dict) -> dict:
    """Re-execute the committed convergence grid from its ``recipe``.

    The recipe section records every parameter of the committed run
    (problem constructor, cluster, methods, LB schedule); artifacts
    without one predate the gate and must be regenerated
    (:class:`GridMismatch`).  The scalar-timing and ``pca_paper_scale``
    sections are not re-run.
    """
    import numpy as np

    from repro.core.problems import LogisticRegressionProblem, make_higgs_like
    from repro.experiments import (
        convergence_payload,
        default_convergence_methods,
        run_convergence_sweep,
    )
    from repro.experiments.grid import DEFAULT_REGIMES
    from repro.latency.model import make_heterogeneous_cluster

    recipe = committed.get("recipe")
    if recipe is None:
        raise GridMismatch(
            "the committed BENCH_convergence.json has no recipe section; "
            "regenerate it with benchmarks.paper_figs.fig10_12_convergence_sweep"
        )
    if recipe["problem"] != "logreg_higgs":
        raise GridMismatch(
            f"recipe problem {recipe['problem']!r} is not reproducible here"
        )
    regimes = {r.name: r for r in DEFAULT_REGIMES}
    if recipe["regime"] not in regimes:
        raise GridMismatch(f"unknown regime {recipe['regime']!r} in recipe")
    X, y = make_higgs_like(recipe["num_samples"], seed=recipe["seed"])
    prob = LogisticRegressionProblem(X=X, y=y)
    N, sp = recipe["n_workers"], recipe["subpartitions"]
    c_task = prob.compute_cost(1, max(prob.num_samples // (N * sp), 1))
    cluster = make_heterogeneous_cluster(
        N, seed=recipe["seed"], burst_rate=0.0, load_unit=c_task
    )
    methods = default_convergence_methods(
        N, w=recipe["w"], eta=recipe["eta"], subpartitions=sp
    )
    out = run_convergence_sweep(
        prob,
        cluster,
        methods,
        n_scenarios=recipe["n_scenarios"],
        num_iterations=recipe["num_iterations"],
        eval_every=recipe["eval_every"],
        regime=regimes[recipe["regime"]],
        seed=recipe["seed"],
    )
    payload = convergence_payload(out, recipe["gap"])
    if "lb_scan" in committed:
        lb_cfg = dataclasses.replace(
            methods["dsag"],
            lb_startup_delay=recipe["lb"]["lb_startup_delay"],
            lb_interval=recipe["lb"]["lb_interval"],
        )
        base_medians = {
            name: float(np.median(res.time_to_gap(recipe["gap"])))
            for name, res in out.results.items()
        }
        payload["lb_scan"] = run_lb_scan_column(
            prob,
            out.traces,
            lb_cfg,
            num_iterations=recipe["num_iterations"],
            eval_every=recipe["eval_every"],
            seed=recipe["seed"],
            gap=recipe["gap"],
            base_medians=base_medians,
            # gate mode: one run per engine covers every fail-able check;
            # the warn-only wall-clock fields are left out
            warm_timings=False,
        )
    if "pca_grid_sharded" in committed:
        ps = committed["pca_grid_sharded"]
        payload["pca_grid_sharded"] = run_pca_grid_sharded_column(
            n_scenarios=ps["grid"]["n_scenarios"],
            num_devices=ps.get("num_devices"),
            seed=ps.get("seed", 0),
        )
    if "churn" in committed:
        payload["churn"] = run_churn_column(committed["churn"].get("recipe"))
    if "kernel_backend" in committed:
        payload["kernel_backend"] = run_kernel_backend_column(
            committed["kernel_backend"].get("recipe")
        )
    if "live_validation" in committed:
        payload["live_validation"] = run_live_validation_column(
            committed["live_validation"].get("recipe")
        )
    return payload


def main(argv: list[str]) -> int:
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    args = [a for a in argv[1:] if not a.startswith("--")]
    path = args[0] if args else "BENCH_sweep.json"
    kind = "sweep"
    if "--kind" in argv:
        kind = argv[argv.index("--kind") + 1]
    elif "convergence" in path:
        kind = "convergence"
    if kind == "tracelint":
        # gate mode over the static-analysis registry: any non-baselined
        # finding fails; a suppression that no longer matches anything
        # warns (stale documented debt — delete it)
        from repro.analysis.lint import load_baseline, run_lint

        report = run_lint("all", baseline_path="tracelint.toml")
        used = [s for _, s in report.suppressed]
        for supp in load_baseline("tracelint.toml"):
            if supp not in used:
                print(f"WARN: stale suppression {supp.code} ({supp.entry})")
        for f in report.findings:
            print(f"FAIL: {f.render()}")
        if report.findings:
            print(f"tracelint regression: {len(report.findings)} finding(s)")
            return 1
        print(
            f"tracelint: clean across {len(report.entries_run)} entries "
            f"({len(report.suppressed)} baselined finding(s))"
        )
        return 0
    try:
        with open(path) as fh:
            committed = json.load(fh)
    except FileNotFoundError:
        print(f"FAIL: committed artifact {path} not found")
        return 1
    try:
        if kind == "convergence":
            fresh = rerun_convergence(committed)
            failures, warnings = compare_convergence(committed, fresh)
            scope = "convergence grid + lb_scan column"
            if "pca_grid_sharded" in committed:
                scope += " + pca_grid_sharded column"
            if "churn" in committed:
                scope += " + churn column"
            if "kernel_backend" in committed:
                scope += " + kernel_backend column"
            if "live_validation" in committed:
                scope += " + live_validation column"
        else:
            fresh = rerun_grid(committed)
            failures, warnings = compare_sweep(committed, fresh)
            scope = f"{len(committed['grid']['regimes'])} regimes"
    except GridMismatch as exc:
        print(f"FAIL: {exc}")
        return 1
    for w in warnings:
        print(f"WARN: {w}")
    for f in failures:
        print(f"FAIL: {f}")
    if failures:
        print(f"benchmark regression: {len(failures)} ordering flip(s)")
        return 1
    print(
        f"benchmark regression: ordering stable across {scope}"
        + (f" ({len(warnings)} drift warning(s))" if warnings else "")
    )
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
