"""The simulator form: a closed loop of ``run_convergence_sweep`` calls.

Set-up makes the configuration's data and fleet, builds the program's
problem once (so its compiled scans are reused) and runs one warm-up
sweep, which compiles or loads every scan body from the persistent cache.
The window then runs sweeps back to back for ``--seconds``; sweep j draws
its latency scenarios and initial iterate from ``SeedSequence([seed, j])``
(j = 0 is the warm-up).  The traffic file names the methods, the burst
regime, how many answers to check, and optionally the sweep's sizes
(``"sweep": {"scenarios": ...}``) and the kernel backend
(``"engine": {"kernel_backend": ...}``).  The scenario axis is sharded over
as many devices as the cell's ``chips``.  After the window, sweeps drawn
from the seed are replayed by the plain reference
(``reference/eventsim.py``) and compared.
"""

from __future__ import annotations

import shutil
import time

import numpy as np

from chipbench import compare, trace_reduce
from chipbench.harness import WORK
from chipbench.kinds import load_kind
from chipbench.reference import eventsim

SPAN = "chipbench.sweep"


def sweep_seed(seed: int, j: int) -> int:
    return int(np.random.SeedSequence([seed % (1 << 64), j]).generate_state(1)[0])


def method_specs(cfg: dict, names) -> dict[str, eventsim.Method]:
    """The paper's section 7 columns (the program's default_convergence_methods)."""
    spec = {
        "dsag": eventsim.Method("dsag", cfg["w"], cfg["eta"], cfg["subpartitions"], cfg["margin"]),
        "sag": eventsim.Method("sag", cfg["workers"], cfg["eta"], cfg["subpartitions"]),
        "sgd": eventsim.Method("sgd", cfg["w"], cfg["eta"], cfg["subpartitions"]),
        "coded": eventsim.Method("coded", 0, cfg["coded_eta"], cfg["subpartitions"],
                                 code_rate=cfg["code_rate"]),
    }
    return {n: spec[n] for n in names}


def make_fleet(cfg: dict, kind) -> eventsim.Fleet:
    task_rows = max(int(cfg["rows"]) // (int(cfg["workers"]) * int(cfg["subpartitions"])), 1)
    return eventsim.make_fleet(
        int(cfg["workers"]), tuple(cfg["comm_range"]), tuple(cfg["comp_range"]),
        float(cfg["cv_comm"]), float(cfg["cv_comp"]),
        load_unit=kind.cost_per_row(cfg) * task_rows, seed=int(cfg["fleet_seed"]),
    )


class Program:
    """The system under test, built once: problem, cluster, methods."""

    def __init__(self, cfg: dict, traffic: dict, data: dict, fleet: eventsim.Fleet, kind,
                 chips: int):
        from repro.cluster.simulator import MethodConfig
        from repro.experiments import EngineConfig
        from repro.latency.model import ClusterLatencyModel, GammaParams, WorkerLatencyModel

        self.cfg, self.traffic = cfg, traffic
        self.problem = kind.program_problem(data, cfg)
        self.cluster = ClusterLatencyModel(workers=[
            WorkerLatencyModel(
                comm=GammaParams(float(fleet.comm_shape[i]), float(fleet.comm_scale[i])),
                comp_per_unit=GammaParams(float(fleet.comp_shape[i]), float(fleet.comp_scale[i])),
                burst_rate=0.0,
                slowdown=float(fleet.slowdown[i]),
            )
            for i in range(fleet.num_workers)
        ])
        self.methods = {
            n: MethodConfig(name=m.name, w=m.w, eta=m.eta, margin=m.margin,
                            subpartitions=m.subpartitions, code_rate=m.code_rate)
            for n, m in method_specs(cfg, traffic["methods"]).items()
        }
        # the scan engine: the scenario axis on a mesh of the cell's chips
        # (unsharded on one), and the kernel backend the traffic may name
        engine = traffic.get("engine", {})
        if set(engine) - {"kernel_backend"}:
            raise ValueError(f"unknown engine options {sorted(set(engine) - {'kernel_backend'})}")
        self.engine = EngineConfig(kind="scan", num_devices=None if chips == 1 else chips,
                                   **engine)

    def sweep(self, seed: int) -> dict:
        from repro.experiments import run_convergence_sweep

        t = self.traffic
        out = run_convergence_sweep(
            self.problem, self.cluster, self.methods,
            n_scenarios=int(self.cfg["scenarios"]), num_iterations=int(self.cfg["iterations"]),
            eval_every=int(self.cfg["eval_every"]), burst_rate=t["burst_rate"],
            burst_factor_mean=t["burst_factor_mean"],
            burst_duration_mean=t["burst_duration_mean"], seed=seed, engine=self.engine,
        )
        return {
            name: {
                "times": r.times, "suboptimality": r.suboptimality,
                "fresh_counts": r.fresh_counts, "latency": r.per_worker_latency,
                "evictions": r.evictions, "rejected_stale": r.rejected_stale,
            }
            for name, r in out.results.items()
        }


def sweep_traces(cfg: dict, traffic: dict, fleet, seed: int) -> eventsim.Traces:
    """The latency draws of sweep ``seed`` (the program's sweep samples its
    traces from ``seed + 1``)."""
    return eventsim.sample_traces(
        fleet, int(cfg["scenarios"]), int(cfg["iterations"]),
        burst_rate=traffic["burst_rate"], burst_factor_mean=traffic["burst_factor_mean"],
        burst_duration_mean=traffic["burst_duration_mean"], seed=seed + 1,
    )


def reference_sweep(cfg: dict, traffic: dict, ref_problem, fleet, seed: int, hi=np.float64) -> dict:
    """Every method and scenario of sweep ``seed`` through the reference."""
    S, T = int(cfg["scenarios"]), int(cfg["iterations"])
    traces = sweep_traces(cfg, traffic, fleet, seed)
    return {
        name: [
            eventsim.simulate(ref_problem, fleet, traces, s, m, T, int(cfg["eval_every"]),
                              seed, hi=hi)
            for s in range(S)
        ]
        for name, m in method_specs(cfg, traffic["methods"]).items()
    }


def useful_flops(cfg: dict, kind, results: dict) -> float:
    """FLOPs a sweep needs, from its shapes and what came back: each
    returned block subgradient, coded's full gradient per iteration, each
    evaluated suboptimality and each iterate update."""
    rows, workers = int(cfg["rows"]), int(cfg["workers"])
    block_rows = rows / (workers * int(cfg["subpartitions"]))
    total = 0.0
    for name, r in results.items():
        S, T = np.shape(r["times"])
        if name == "coded":
            total += S * T * kind.task_flops(cfg, rows)
        else:
            total += np.isfinite(r["latency"]).sum() * kind.task_flops(cfg, block_rows)
        total += np.isfinite(r["suboptimality"]).sum() * kind.eval_flops(cfg)
        total += S * T * kind.update_flops(cfg)
    return float(total)


def sweep_iters(cfg: dict, traffic: dict) -> int:
    return len(traffic["methods"]) * int(cfg["scenarios"]) * int(cfg["iterations"])


def run(ctx) -> dict:
    """One run of a sweep cell; returns what ``run.py`` prints."""
    import jax

    # a traffic mix may set the sweep's own sizes (scenarios, iterations)
    cfg = {**ctx.config, **ctx.traffic.get("sweep", {})}
    traffic = ctx.traffic
    t_in = time.perf_counter()
    kind = load_kind(cfg["kind"])
    data = kind.make_data(cfg)
    fleet = make_fleet(cfg, kind)
    t_data = time.perf_counter()
    program = Program(cfg, traffic, data, fleet, kind, int(ctx.cell["chips"]))
    t_prog = time.perf_counter()
    program.sweep(sweep_seed(ctx.seed, 0))  # warm-up: compile or load every scan
    compiles_before = ctx.compiles.count
    setup_s = time.perf_counter() - ctx.t0
    print(f"set-up {setup_s:.3f} s: start and JAX {t_in - ctx.t0:.3f} s, data and fleet "
          f"{t_data - t_in:.3f} s, program problem {t_prog - t_data:.3f} s, warm-up sweep "
          f"{ctx.t0 + setup_s - t_prog:.3f} s ({compiles_before} compilations)", file=ctx.err)

    kept: list[tuple[int, dict]] = []
    durations: list[float] = []
    failed = 0
    trace_dir = WORK / "trace" / ctx.cell["name"]
    if ctx.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    t_start = t_prev = time.perf_counter()
    wall_start = time.time()
    j = 0
    while True:
        j += 1
        try:
            with jax.profiler.TraceAnnotation(SPAN):
                kept.append((j, program.sweep(sweep_seed(ctx.seed, j))))
        except Exception as e:  # a sweep that raises is a failed request
            failed += 1
            print(f"sweep {j} failed: {e!r}", file=ctx.err)
        t_end = time.perf_counter()
        durations.append(t_end - t_prev)
        t_prev = t_end
        if (ctx.trace and j >= int(traffic["trace_sweeps"])) or (
            not ctx.trace and t_end - t_start >= ctx.seconds
        ):
            break
    if ctx.trace:
        jax.profiler.stop_trace()
    window_compiles = ctx.compiles.count - compiles_before
    device = ctx.device_block()
    print(f"window: {j} sweeps in {t_end - t_start:.3f} s from {time.strftime('%H:%M:%S', time.gmtime(wall_start))} UTC, "
          f"{window_compiles} compilations inside it; each sweep's seconds "
          f"{[round(d, 4) for d in durations]}", file=ctx.err)

    metrics = {}
    if not ctx.trace:
        metrics["setup_s"] = setup_s
        metrics["sim_iters_per_s"] = len(kept) * sweep_iters(cfg, traffic) / (t_end - t_start)
    del program

    # correctness: answers (a method on a scenario of a sweep) drawn from
    # the seed, ``check_per_method`` for each method, replayed by the
    # reference once the window has closed and the program's state is gone
    ref_problem = kind.reference(data, cfg)
    specs = method_specs(cfg, traffic["methods"])
    rng = np.random.default_rng([ctx.seed % (1 << 64), 7])
    pairs = [(k, s) for k in range(len(kept)) for s in range(int(cfg["scenarios"]))]
    n = min(int(traffic["check_per_method"]), len(pairs))
    picks = sorted((*pairs[i], name) for name in traffic["methods"]
                   for i in rng.choice(len(pairs), size=n, replace=False))
    t_ref = time.perf_counter()
    numbers, wrong, traces_of = None, set(), (None, None)
    for k, s, name in picks:
        j_k, prog = kept[k]
        seed_k = sweep_seed(ctx.seed, j_k)
        if traces_of[0] != k:
            traces_of = (k, sweep_traces(cfg, traffic, fleet, seed_k))
        ref = eventsim.simulate(ref_problem, fleet, traces_of[1], s, specs[name],
                                int(cfg["iterations"]), int(cfg["eval_every"]), seed_k)
        got = compare.answer_numbers(prog[name], s, ref, float(cfg["gap"]))
        if not compare.judge(got, ctx.limits)[0]:
            wrong.add(k)
        numbers = got if numbers is None else compare.merge(numbers, got)
    failed += len(wrong)
    print(f"reference: {len(picks)} answers in {time.perf_counter() - t_ref:.1f} s",
          file=ctx.err)
    if numbers is None:
        numbers = {k: float("inf") for k in ctx.limits}
    ok, checks = compare.judge(numbers, ctx.limits)

    breakdown = None
    if ctx.trace:
        red = trace_reduce.reduce_dir(trace_dir, span=SPAN)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        breakdown = {"device_ops": red["device_ops"], "idle_gaps": red["idle_gaps"]}
        from chipbench.peaks import peaks_for

        reading = {
            "trace": red,
            "requests": len(kept),
            "useful_flops": sum(useful_flops(cfg, kind, r) for _, r in kept),
            "peak_flops": (peaks_for(device["kind"]).flops * device["count"]
                           if device["platform"] == "tpu" else None),
        }
        metrics = ctx.read_per_layer(reading)
    return {
        "correct": bool(ok and failed == 0),
        "attempted": j,
        "failed": failed,
        "metrics": metrics,
        "device": device,
        "breakdown": breakdown,
        "checks": checks,
    }
