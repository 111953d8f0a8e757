"""Readings the limits of ``correct`` are set from (see PERF.md section 2).

    python3 chipbench/calibrate.py --workload <cell> --seeds 12 --control-seeds 3 \
        --fault-seeds 3 --base-seed <n> [--out <file.json>]

Sweep cells: for each seed it runs the cell's first window sweep through
the program, on the machine it is started on, and compares it with the
plain reference (the lower readings).  For each fault seed it plants each
fault of ``faults.py`` in the program, builds it anew and compares its sweep
(the fault readings).  For each control seed it puts the reference in the
program's place, computed one precision step lower (float64 parts in
float32, float32 parts in bfloat16: ``upper``), and with the
suboptimality alone in float32 (``upper_eval``), and compares that.  The
reference's sweeps (numpy alone) run in a pool of worker processes that
import no JAX, one scenario of one method a task; everything that touches
JAX stays in this process.

Live cells: the same for the first ``check_per_job`` window jobs of each
seed, with the live faults; the controls are the reference one precision
step lower (``upper``) and the program's own lower-precision path, its
gradient cache in bfloat16 (``upper_program``).  A number's upper reading
(``upper_min``) is the least of these and of the faults' that reads ten
times its lower one or more (a state left unchanged: three times).

Prints one JSON object; ``--out`` also writes it to a file.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import dataclasses
import json
import multiprocessing
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def as_program(runs_by_method: dict) -> dict:
    """Reference runs shaped like the program's [S, ...] outputs."""
    import numpy as np

    return {
        name: {
            "times": np.stack([r.times for r in runs]),
            "suboptimality": np.stack([r.suboptimality for r in runs]),
            "fresh_counts": np.stack([r.fresh_counts for r in runs]),
            "latency": np.stack([r.latency for r in runs]),
            "evictions": [r.evictions for r in runs],
            "rejected_stale": [r.rejected_stale for r in runs],
        }
        for name, runs in runs_by_method.items()
    }


#: per worker process: the reference problems built so far, by precision
_BUILT: dict = {}


def _replay(cfg: dict, precision: tuple, fleet, traces, method, seed: int, hi: str):
    """One method on a one-scenario slice of a sweep's traces, in a worker;
    the reference is built from the configuration, once per precision."""
    from chipbench.kinds import load_kind
    from chipbench.reference import eventsim

    if precision not in _BUILT:
        kind = load_kind(cfg["kind"])
        _BUILT[precision] = kind.reference(kind.make_data(cfg), cfg, **dict(precision))
    return eventsim.simulate(_BUILT[precision], fleet, traces, 0, method, int(cfg["iterations"]),
                             int(cfg["eval_every"]), seed, hi=hi)


def _one_thread() -> None:
    """A worker's BLAS runs one thread (set before the worker imports numpy)."""
    for var in ("OMP_NUM_THREADS", "OPENBLAS_NUM_THREADS", "MKL_NUM_THREADS"):
        os.environ[var] = "1"


class ReferencePool:
    """``forms.sweep.reference_sweep`` over worker processes: the same
    replays, one task per scenario of each method."""

    def __init__(self, workers: int | None = None):
        workers = workers or max((os.cpu_count() or 2) - 2, 1)
        self.pool = concurrent.futures.ProcessPoolExecutor(
            workers, mp_context=multiprocessing.get_context("spawn"), initializer=_one_thread)

    def sweep(self, cfg: dict, traffic: dict, fleet, seed: int, precision: dict | None = None,
              hi: str = "float64") -> dict:
        """Every method and scenario of sweep ``seed`` through the
        reference of ``kind.reference(data, cfg, **precision)``, its event
        times in ``hi``."""
        from chipbench.forms import sweep as form
        from chipbench.reference import eventsim

        tr = form.sweep_traces(cfg, traffic, fleet, seed)
        spec = tuple(sorted((precision or {}).items()))
        futures = {
            name: [
                self.pool.submit(
                    _replay, cfg, spec, fleet,
                    eventsim.Traces(*(a[s : s + 1] for a in (
                        tr.comm, tr.comp_unit, tr.burst_start, tr.burst_end, tr.burst_factor))),
                    m, seed, hi)
                for s in range(int(cfg["scenarios"]))
            ]
            for name, m in form.method_specs(cfg, traffic["methods"]).items()
        }
        return {name: [f.result() for f in fs] for name, fs in futures.items()}

    def close(self) -> None:
        self.pool.shutdown()


def show(rec: dict) -> dict:
    print(json.dumps(rec), file=sys.stderr, flush=True)
    return rec


def calibrate_sweep(args, cell: dict, entry: dict, traffic: dict, devs) -> dict:
    from chipbench import compare, harness
    from chipbench.faults import FAULTS, Patches
    from chipbench.forms import sweep as form
    from chipbench.kinds import load_kind
    from chipbench.reference.precision import BF16

    cfg = {**harness.load_json(entry["file"]), **traffic.get("sweep", {})}
    kind = load_kind(cfg["kind"])
    data = kind.make_data(cfg)
    fleet = form.make_fleet(cfg, kind)
    gap = float(cfg["gap"])
    seeds = [args.base_seed + i for i in range(args.seeds)]
    pool = ReferencePool()
    refs: dict[int, dict] = {}

    def reference(s: int) -> dict:
        if s not in refs:
            refs[s] = pool.sweep(cfg, traffic, fleet, s)
        return refs[s]

    try:
        program = form.Program(cfg, traffic, data, fleet, kind, int(cell["chips"]))
        program.sweep(form.sweep_seed(seeds[0], 0))
        lower = []
        for seed in seeds:
            s = form.sweep_seed(seed, 1)
            t0 = time.perf_counter()
            got = program.sweep(s)
            t1 = time.perf_counter()
            ref = reference(s)
            t2 = time.perf_counter()
            lower.append(show({"seed": seed, **compare.sweep_numbers(got, ref, gap),
                               "sweep_s": t1 - t0, "reference_s": t2 - t1}))
        device = harness.device_block(devs)
        del program

        faults = {}
        for name, plant in FAULTS.items():
            patches = Patches()
            plant(patches.setattr)
            try:
                program = form.Program(cfg, traffic, data, fleet, kind, int(cell["chips"]))
                faults[name] = [
                    show({"fault": name, "seed": seed,
                          **compare.sweep_numbers(program.sweep(form.sweep_seed(seed, 1)),
                                                  reference(form.sweep_seed(seed, 1)), gap)})
                    for seed in seeds[: args.fault_seeds]
                ]
            finally:
                patches.undo()
                program = None

        controls = {
            "upper": ({"hi": "float32", "lo": BF16}, "float32"),
            "upper_eval": ({"ev": "float32"}, "float64"),
        }
        out = {"workload": args.workload, "device": device, "lower": lower}
        for key, (precision, hi) in controls.items():
            out[key] = []
            for seed in seeds[: args.control_seeds]:
                s = form.sweep_seed(seed, 1)
                ctl = pool.sweep(cfg, traffic, fleet, s, precision, hi=hi)
                out[key].append(show({"seed": seed, **compare.sweep_numbers(
                    as_program(ctl), reference(s), gap)}))
    finally:
        pool.close()
    out["faults"] = faults
    numbers = ("event_rel", "subopt_rel")
    out["lower_max"] = {k: max(r[k] for r in lower) for k in numbers}
    for key in controls:
        out[f"{key}_min"] = {k: min(r[k] for r in out[key]) for k in numbers}
    out["faults_min"] = {f: {k: min(r[k] for r in rs) for k in numbers}
                         for f, rs in faults.items()}
    return out


def calibrate_live(args, cell: dict, entry: dict, traffic: dict, devs) -> dict:
    from chipbench import compare, harness
    from chipbench.compare_live import NUMBERS, job_numbers
    from chipbench.faults import LIVE_FAULTS, Patches
    from chipbench.forms import live as form
    from chipbench.kinds import load_kind
    from chipbench.reference.precision import BF16

    cfg = form.live_config(harness.load_json(entry["file"]), traffic)
    kind = load_kind(f"{cfg['kind']}_live")
    data = kind.make_data(cfg)
    fleet, loads = form.make_fleet(cfg, kind)
    G = int(cfg["workers"])
    ref_problem = kind.reference(data, cfg, G)
    seeds = [args.base_seed + i for i in range(args.seeds)]
    per_seed = int(traffic["check_per_job"])
    jobs = [(seed, form.job_seed(seed, j)) for seed in seeds for j in range(1, per_seed + 1)]
    refs: dict[int, tuple] = {}

    def reference(s: int) -> tuple:
        if s not in refs:
            refs[s] = form.reference_job(cfg, traffic, kind, ref_problem, fleet, loads, s)
        return refs[s]

    def program_readings(seed_jobs, cache_dtype=None, **tag) -> list[dict]:
        program = form.Program(cfg, traffic, data, fleet, kind)
        if cache_dtype is not None:
            tc = dataclasses.replace(program.options.train_config, dsag_cache_dtype=cache_dtype)
            program.options = dataclasses.replace(program.options, train_config=tc)
        out = []
        with form.quiet():
            warm = form.job_seed(seed_jobs[0][0], 0)
            program.run_job(program.prepare(warm))
            for seed, s in seed_jobs:
                t0 = time.perf_counter()
                got = form.answer(program.run_job(program.prepare(s)))
                t1 = time.perf_counter()
                numbers = job_numbers(got, *reference(s))
                out.append(show({**tag, "seed": seed, "job_seed": s, **numbers,
                                 "job_s": t1 - t0, "reference_s": time.perf_counter() - t1}))
        return out

    lower = program_readings(jobs)
    device = harness.device_block(devs)

    faults = {}
    fault_jobs = [js for js in jobs if js[0] in seeds[: args.fault_seeds]]
    for name, plant in LIVE_FAULTS.items():
        patches = Patches()
        plant(patches.setattr)
        try:
            faults[name] = program_readings(fault_jobs, fault=name)
        finally:
            patches.undo()

    control_jobs = [js for js in jobs if js[0] in seeds[: args.control_seeds]]
    upper_program = program_readings(control_jobs, cache_dtype="bfloat16", control="cache_bf16")
    ctl_problem = kind.reference(data, cfg, G, hi="float32", lo=BF16)
    upper = []
    for seed, s in control_jobs:
        streams, traj, V0 = form.reference_job(cfg, traffic, kind, ctl_problem, fleet, loads, s,
                                               hi="float32", lo=BF16)
        ctl = {"mask": streams.mask, "flush": streams.flush, "evict": streams.evict,
               "virtual": streams.times, "loss": traj.loss, "grad_norm": traj.grad_norm,
               "params": traj.params}
        upper.append(show({"seed": seed, "job_seed": s, **job_numbers(ctl, *reference(s))}))

    out = {"workload": args.workload, "device": device, "lower": lower, "upper": upper,
           "upper_program": upper_program, "faults": faults,
           "lower_max": {k: max(r[k] for r in lower) for k in NUMBERS},
           "upper_reference_min": {k: min(r[k] for r in upper) for k in NUMBERS},
           "upper_program_min": {k: min(r[k] for r in upper_program) for k in NUMBERS},
           "faults_min": {f: {k: min(r[k] for r in rs) for k in NUMBERS}
                          for f, rs in faults.items()}}
    out["upper_min"] = {}
    for k in NUMBERS:
        low = out["lower_max"][k]
        tops = [(out["upper_reference_min"][k], 10), (out["upper_program_min"][k], 10),
                *((m[k], 3 if f == "state_unchanged" else 10)
                  for f, m in out["faults_min"].items())]
        out["upper_min"][k] = min((v for v, times in tops if v > low and v >= times * low),
                                  default=None)
    limits = harness.load_json(f"chipbench/limits/{cell['name']}.json")["limits"]
    out["fails_now"] = {f: not compare.judge(m, limits)[0] for f, m in
                        [("upper_program", out["upper_program_min"]), *out["faults_min"].items()]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--base-seed", type=int, required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

    from chipbench import harness

    cell, entry = harness.find_cell(harness.load_benchmark(), args.workload)
    traffic = harness.load_json(f"chipbench/traffic/{cell['traffic']}.json")
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    devs = harness.devices(int(cell["chips"]), allow_cpu=True)
    by_form = {"sweep": calibrate_sweep, "live": calibrate_live}
    out = by_form[traffic["form"]](args, cell, entry, traffic, devs)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(out, indent=1))
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
