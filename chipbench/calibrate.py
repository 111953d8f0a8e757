"""Readings the limits of ``correct`` are set from (see PERF.md section 2).

    python3 chipbench/calibrate.py --workload <cell> --seeds 12 --control-seeds 3 \
        --fault-seeds 3 --base-seed <n> [--out <file.json>]

For each seed it runs the cell's first window sweep through the program,
on the machine it is started on, and compares it with the plain reference
(the lower readings).  For each fault seed it plants each fault of
``faults.py`` in the program, builds it anew and compares its sweep (the
fault readings).  For each control seed it puts the reference in the
program's place, computed one precision step lower (float64 parts in
float32, float32 parts in bfloat16: ``upper``), and with the
suboptimality alone in float32 (``upper_eval``), and compares that.
Prints one JSON object; ``--out`` also writes it to a file.
"""

from __future__ import annotations

import argparse
import json
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

    from chipbench import compare, harness
    from chipbench.faults import FAULTS, Patches
    from chipbench.forms import sweep as form
    from chipbench.kinds import load_kind
    from chipbench.reference.precision import BF16

    bench = harness.load_benchmark()
    cell, entry = harness.find_cell(bench, args.workload)
    traffic = harness.load_json(f"chipbench/traffic/{cell['traffic']}.json")
    cfg = {**harness.load_json(entry["file"]), **traffic.get("sweep", {})}
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    devs = harness.devices(int(cell["chips"]), allow_cpu=True)
    kind = load_kind(cfg["kind"])
    data = kind.make_data(cfg)
    fleet = form.make_fleet(cfg, kind)
    ref_problem = kind.reference(data, cfg)
    gap = float(cfg["gap"])
    seeds = [args.base_seed + i for i in range(args.seeds)]
    refs: dict[int, dict] = {}

    def reference(s: int) -> dict:
        if s not in refs:
            refs[s] = form.reference_sweep(cfg, traffic, ref_problem, fleet, s)
        return refs[s]

    def show(rec: dict) -> dict:
        print(json.dumps(rec), file=sys.stderr, flush=True)
        return rec

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
        "upper": (kind.reference(data, cfg, hi="float32", lo=BF16), "float32"),
        "upper_eval": (kind.reference(data, cfg, ev="float32"), "float64"),
    }
    out = {"workload": args.workload, "device": device, "lower": lower}
    for key, (ctl_problem, hi) in controls.items():
        out[key] = []
        for seed in seeds[: args.control_seeds]:
            s = form.sweep_seed(seed, 1)
            ctl = form.reference_sweep(cfg, traffic, ctl_problem, fleet, s, hi=hi)
            out[key].append(show({"seed": seed, **compare.sweep_numbers(as_program(ctl),
                                                                        reference(s), gap)}))
    out["faults"] = faults
    numbers = ("event_rel", "subopt_rel")
    out["lower_max"] = {k: max(r[k] for r in lower) for k in numbers}
    for key in controls:
        out[f"{key}_min"] = {k: min(r[k] for r in out[key]) for k in numbers}
    out["faults_min"] = {f: {k: min(r[k] for r in rs) for k in numbers}
                         for f, rs in faults.items()}
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(out, indent=1))
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
