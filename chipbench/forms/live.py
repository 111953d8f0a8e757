"""The live form: a closed loop of live DSAG training jobs through the
program's ``Trainer`` (``repro.launch.train``).

Set-up makes the configuration's data and fleet (with ``subpartitions``
from the traffic: a group's task is its whole partition), the program's
job over that data (``kinds/<kind>_live.py``), and runs one warm-up job,
which compiles the step or loads it from the persistent cache.  Every job
gets a ``Trainer`` of its own, built by the program's constructor around
that job and the job's latency traces, and all of them run the warm-up's
compiled step, so nothing compiles in the window.  Set-up also prepares
the window's jobs, ``jobs_ready`` of them: each job's traces and its
``Trainer``, so that the window holds what a trainer's user runs, the jobs
themselves.  Job j draws its traces (one scenario of the fleet, made by the
benchmark's generator, ``reference/eventsim.py``, in the traffic's burst
regime) and its initial iterate from ``SeedSequence([seed, j])`` (j = 0 is
the warm-up), runs ``steps_per_job`` steps with the Tier-2 controller
replaying the traces in virtual time, and ends in a host copy of its final
parameters.  After the window, jobs drawn from the seed are replayed by the
plain reference (``reference/live.py``) and compared (``compare_live.py``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import shutil
import time

import numpy as np

from chipbench import compare, compare_live, trace_reduce
from chipbench.forms.sweep import sweep_seed as job_seed
from chipbench.harness import WORK
from chipbench.kinds import load_kind
from chipbench.reference import eventsim, live

SPAN = "chipbench.job"


def live_config(config: dict, traffic: dict) -> dict:
    return {**config, "subpartitions": int(traffic["subpartitions"])}


def make_fleet(cfg: dict, kind) -> tuple[eventsim.Fleet, np.ndarray]:
    """The fleet, scaled so that a group's task takes the configuration's
    ``comp_range`` on average, and each group's load."""
    loads = kind.group_loads(cfg, int(cfg["workers"]))
    fleet = eventsim.make_fleet(
        int(cfg["workers"]), tuple(cfg["comm_range"]), tuple(cfg["comp_range"]),
        float(cfg["cv_comm"]), float(cfg["cv_comp"]), load_unit=float(np.mean(loads)),
        seed=int(cfg["fleet_seed"]),
    )
    return fleet, loads


def job_traces(cfg: dict, traffic: dict, fleet: eventsim.Fleet, seed: int) -> eventsim.Traces:
    """The latency draws of job ``seed``: one scenario, a draw per step."""
    return eventsim.sample_traces(
        fleet, 1, int(traffic["steps_per_job"]), burst_rate=traffic["burst_rate"],
        burst_factor_mean=traffic["burst_factor_mean"],
        burst_duration_mean=traffic["burst_duration_mean"], seed=seed,
    )


class Recorder:
    """The shared compiled step, keeping the state it last returned and,
    on the device, the norm of each step's update direction."""

    def __init__(self, step_fn):
        self.step_fn, self.state, self.grad_norms = step_fn, None, []

    def __call__(self, *args):
        self.state, metrics = self.step_fn(*args)
        self.grad_norms.append(metrics["grad_norm"])
        return self.state, metrics


class Program:
    """The system under test: the program's job over the benchmark's data,
    and a ``Trainer`` per job, all on one compiled step."""

    def __init__(self, cfg: dict, traffic: dict, data: dict, fleet: eventsim.Fleet, kind):
        from repro.launch.paper_jobs import paper_train_config
        from repro.launch.train import TrainerOptions

        G, steps = int(cfg["workers"]), int(traffic["steps_per_job"])
        self.cfg, self.traffic, self.fleet = cfg, traffic, fleet
        self.job = kind.program_job(data, cfg, G)
        tc = dataclasses.replace(paper_train_config(float(cfg["eta"])),
                                 dsag_margin=float(cfg["margin"]))
        self.options = TrainerOptions(
            arch=kind.ARCH, num_groups=G, samples=int(cfg["rows"]), method=traffic["method"],
            dsag_w=int(cfg["w"]), train_config=tc, steps=steps,
            time_scale=float(traffic["time_scale"]), eval_every=0, log_every=steps + 1,
            failure_max_misses=int(traffic["max_misses"]), simulate_stragglers=False,
        )
        self.step_fn = None

    def prepare(self, seed: int):
        """Job ``seed``'s ``Trainer``, built by the program's constructor
        around this job (handed in where the constructor makes its paper
        job) and the job's traces."""
        from repro.latency.model import FleetTraces
        from repro.launch import train

        traces = job_traces(self.cfg, self.traffic, self.fleet, seed)
        fleet_traces = FleetTraces(
            comm=traces.comm, comp_unit=traces.comp_unit, slowdown=self.fleet.slowdown,
            burst_start=traces.burst_start, burst_end=traces.burst_end,
            burst_factor=traces.burst_factor, seed=seed,
        )
        make_paper_job = train.make_paper_job
        train.make_paper_job = lambda *_a, **_kw: self.job
        try:
            trainer = train.Trainer(dataclasses.replace(
                self.options, seed=seed, traces=fleet_traces, scenario=0))
        finally:
            train.make_paper_job = make_paper_job
        if self.step_fn is None:
            self.step_fn = trainer.step_fn
        trainer.step_fn = Recorder(self.step_fn)
        return trainer

    @staticmethod
    def run_job(trainer) -> dict:
        history = trainer.run()
        params = np.asarray(trainer.step_fn.state["params"])
        return {"history": history, "grad_norms": trainer.step_fn.grad_norms, "params": params}


def answer(out: dict) -> dict:
    """One job's output as ``compare_live.job_numbers`` reads it."""
    h = out["history"]
    return {
        "mask": np.array(h["mask_stream"]), "flush": np.array(h["flush_stream"]),
        "evict": np.array(h["evict_stream"]), "virtual": np.array(h["virtual"]),
        "loss": np.array(h["loss"]), "grad_norm": np.array([float(g) for g in out["grad_norms"]]),
        "params": out["params"],
    }


def reference_job(cfg: dict, traffic: dict, kind, ref_problem, fleet, loads, seed: int,
                  hi=np.float64, lo=np.float32):
    """Job ``seed`` through the reference: its streams, its trajectory and
    its initial iterate."""
    streams = live.control_streams(
        fleet, job_traces(cfg, traffic, fleet, seed), loads, int(cfg["w"]),
        float(cfg["margin"]), int(traffic["steps_per_job"]), int(traffic["max_misses"]), hi=hi)
    V0 = ref_problem.init(seed)
    return streams, live.replay(ref_problem, streams, V0, float(cfg["eta"]), hi=hi, lo=lo), V0


@contextlib.contextmanager
def quiet():
    """The trainer's log lines go nowhere: standard output ends in the result."""
    with open(os.devnull, "w") as null, contextlib.redirect_stdout(null):
        yield


def run(ctx) -> dict:
    """One run of a live cell; returns what ``run.py`` prints."""
    import jax

    traffic = ctx.traffic
    cfg = live_config(ctx.config, traffic)
    steps = int(traffic["steps_per_job"])
    t_in = time.perf_counter()
    kind = load_kind(f"{cfg['kind']}_live")
    data = kind.make_data(cfg)
    fleet, loads = make_fleet(cfg, kind)
    t_data = time.perf_counter()
    program = Program(cfg, traffic, data, fleet, kind)
    t_prog = time.perf_counter()
    with quiet():  # warm-up: compile or load the step, make the state's shapes
        program.run_job(program.prepare(job_seed(ctx.seed, 0)))
    t_warm = time.perf_counter()
    # the window's jobs, each its traces and its Trainer (job j at [j - 1])
    n_ready = int(traffic["trace_jobs"] if ctx.trace else traffic["jobs_ready"])
    ready = [program.prepare(job_seed(ctx.seed, j)) for j in range(1, n_ready + 1)]
    compiles_before = ctx.compiles.count
    setup_s = time.perf_counter() - ctx.t0
    print(f"set-up {setup_s:.3f} s: start and JAX {t_in - ctx.t0:.3f} s, data and fleet "
          f"{t_data - t_in:.3f} s, program job {t_prog - t_data:.3f} s, warm-up job "
          f"{t_warm - t_prog:.3f} s, {len(ready)} jobs prepared {ctx.t0 + setup_s - t_warm:.3f} s "
          f"({compiles_before} compilations or persistent-cache loads)", file=ctx.err)

    kept: list[tuple[int, dict]] = []
    durations: list[float] = []
    failed = 0
    trace_dir = WORK / "trace" / ctx.cell["name"]
    if ctx.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    t_start = t_prev = time.perf_counter()
    t_done = t_start  # end of the last completed job
    wall_start = time.time()
    j = 0
    with quiet():
        while True:
            j += 1
            # past the prepared jobs (a window longer than set-up planned
            # for), a job is prepared inside the window
            if j <= n_ready:
                trainer, ready[j - 1] = ready[j - 1], None
            else:
                trainer = program.prepare(job_seed(ctx.seed, j))
            try:
                with jax.profiler.TraceAnnotation(SPAN):
                    kept.append((j, program.run_job(trainer)))
                t_done = time.perf_counter()
            except Exception as e:  # a job that raises is a failed request
                failed += 1
                print(f"job {j} failed: {e!r}", file=ctx.err)
            t_end = time.perf_counter()
            durations.append(t_end - t_prev)
            t_prev = t_end
            if (ctx.trace and j >= int(traffic["trace_jobs"])) or (
                not ctx.trace and t_end - t_start >= ctx.seconds
            ):
                break
    if ctx.trace:
        jax.profiler.stop_trace()
    window_compiles = ctx.compiles.count - compiles_before
    device = ctx.device_block()
    print(f"window: {j} jobs in {t_end - t_start:.3f} s from "
          f"{time.strftime('%H:%M:%S', time.gmtime(wall_start))} UTC, {window_compiles} "
          f"compilations inside it, {max(j - n_ready, 0)} jobs prepared inside it; each "
          f"job's seconds: median {float(np.median(durations)):.4f}, max "
          f"{max(durations):.4f}", file=ctx.err)

    metrics = {}
    if not ctx.trace:
        metrics["setup_s"] = setup_s
        metrics["train_steps_per_s"] = len(kept) * steps / (t_done - t_start) if kept else 0.0

    # correctness: jobs drawn from the seed, ``check_per_job`` of them,
    # replayed by the reference once the window has closed
    rng = np.random.default_rng([ctx.seed % (1 << 64), 7])
    picks = sorted(rng.choice(len(kept), size=min(int(traffic["check_per_job"]), len(kept)),
                              replace=False))
    checked = [(kept[k][0], answer(kept[k][1])) for k in picks]
    done = len(kept)
    del program, kept, ready, trainer
    ref_problem = kind.reference(data, cfg, int(cfg["workers"]))
    t_ref = time.perf_counter()
    numbers = None
    for j_k, got in checked:
        got = compare_live.job_numbers(got, *reference_job(
            cfg, traffic, kind, ref_problem, fleet, loads, job_seed(ctx.seed, j_k)))
        if not compare.judge(got, ctx.limits)[0]:
            failed += 1
        numbers = got if numbers is None else compare.merge(numbers, got)
    print(f"reference: {len(picks)} jobs in {time.perf_counter() - t_ref:.1f} s", file=ctx.err)
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

        done_steps = done * steps
        reading = {
            "trace": red,
            "requests": done,
            "steps": done_steps,
            "useful_flops": done_steps * kind.step_flops(cfg, int(cfg["workers"])),
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
