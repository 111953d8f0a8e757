"""Where a sweep's time goes, by phase of the scan body and by host span.

    python3 chipbench/phase_split.py --workload <cell> --seed <n> \
        [--untraced 4] [--traced <n>] [--slice <file.json>]

Needs the cell's chips, as ``run.py`` does.  Sets the cell up as its form
does (data, fleet, the program's problem, one warm-up sweep), runs
``--untraced`` sweeps with the profiler off, then ``--traced`` sweeps (the
traffic's ``trace_sweeps`` by default) under the profiler, each inside the
benchmark's ``chipbench.sweep`` span.  It prints one JSON object: the
seconds of each sweep, traced and untraced (the tracing cost); what the
benchmark's own readers give for the trace (``scan_ms.sim``,
``driver_ms.sim``, ``idle_share.sim``); what ``trace_scopes.reduce`` gives
(device time per phase scope, by the nesting rule and by each op's own
path, device-idle time inside the program's spans, the work counters,
each sweep by its seed and each method) and the readings made from it;
and the ops with most device time, each placed in its phase.  ``--slice``
writes a few milliseconds of the trace around the end of one method's
scan (see ``cut``) with the numbers the reduction gave for them.
"""

from __future__ import annotations

import argparse
import json
import shutil
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def cut(rep: dict, span: str, before_ns: float = 2.5e6, after_ns: float = 1e6) -> dict:
    """The trace from ``before_ns`` before the end of the first
    ``repro.fetch`` to ``after_ns`` after the next ``repro.dispatch``: the
    end of one method's scan, its copies and counters, the next method's
    preparation and the start of its scan.  Host events other than the
    program's spans are left out; the benchmark's span is the slice, and
    the program's spans and the modules it crosses are cut to it."""
    host = max(rep["host"], key=lambda h: sum(1 for e in h["events"] if e[0] == span))
    ev = sorted(host["events"], key=lambda e: e[1])
    fetch = next(e for e in ev if e[0] == "repro.fetch")
    dispatch = next(e for e in ev if e[0] == "repro.dispatch" and e[1] > fetch[1])
    lo, hi = fetch[1] + fetch[2] - before_ns, dispatch[1] + dispatch[2] + after_ns

    def clipped(events):
        return [[e[0], max(e[1], lo), min(e[1] + e[2], hi) - max(e[1], lo), *e[3:]]
                for e in events if e[1] < hi and e[1] + e[2] > lo]

    kept = clipped(e for e in ev if e[0].startswith("repro."))
    return {
        "devices": [{"name": d["name"],
                     "ops": [o for o in d["ops"] if lo <= o[1] and o[1] + o[2] <= hi],
                     "modules": clipped(d["modules"])}
                    for d in rep["devices"]],
        "host": [{"line": host["line"], "events": [[span, lo, hi - lo, {}]] + kept}],
    }


def leading_ops(buf: bytes, w0: float, w1: float, top: int = 12) -> dict:
    """The device ops with most self-time inside [w0, w1] (ns): each with
    its HLO name, the phase its own path names, the phase it is counted in
    (``trace_scopes.phase_times``) and its path; overall, among the ops
    whose own path names no phase but that are counted in one
    (``inherited``), and among those counted in none.  Also how many ops
    carry a path stat, how many have a path from the stat or the HLO, and
    the lines of each device plane."""
    from chipbench import trace_scopes as ts

    all_planes = ts.planes(buf, ())
    hlo = ts.hlo_protos(all_planes)
    total: dict[tuple, float] = {}
    n_ops = n_stat = n_path = 0
    lines = {}
    for plane in all_planes:
        if not (plane["name"].startswith(ts.DEVICE_PREFIX) and "TPU" in plane["name"]):
            continue
        lines[plane["name"]] = [[name, len(evs)] for name, evs in plane["lines"]]
        for lname, evs in plane["lines"]:
            if lname == ts.OPS_LINE:
                n_stat += sum(1 for m, _, _, st in evs
                              if ts.op_path(st, plane["meta"].get(m, ("", {}))[1]))
        ops = sorted(ts.device_ops(plane, hlo)[0], key=lambda e: (e[2], -e[3]))
        n_ops += len(ops)
        n_path += sum(1 for o in ops if o[1])
        counted = ts.phase_times([o[1:] for o in ops])
        for (name, path, _, _), (own, phase, s, t) in zip(ops, counted):
            if w0 <= s < w1 and t > 0:
                key = (name.split(" = ")[0], own, phase, path)
                total[key] = total.get(key, 0.0) + t * 1e-9

    def rows(items):
        return [[*k, v] for k, v in sorted(items, key=lambda kv: -kv[1])[:top]]

    return {"all": rows(total.items()),
            "inherited": rows((k, v) for k, v in total.items()
                              if k[1] == "none" and k[2] != "none"),
            "none": rows((k, v) for k, v in total.items() if k[2] == "none"),
            "ops": n_ops, "ops_with_path_stat": n_stat, "ops_with_path": n_path,
            "lines": lines}


def split(scoped: dict, scan_ms: float, n: int) -> dict:
    """Per phase, device milliseconds per sweep and percent of ``scan_ms``,
    by the nesting rule (``counted``) and by each op's own path (``own``)."""
    return {
        rule: {p: [1e3 * sec / n, 100.0 * 1e3 * sec / n / scan_ms] for p, sec in times.items()}
        for rule, times in (("counted", scoped["phase_s"]), ("own", scoped["own_phase_s"]))
    } if scan_ms else {}


def main(argv=None, *, allow_cpu: bool = False) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--untraced", type=int, default=4)
    ap.add_argument("--traced", type=int)
    ap.add_argument("--slice")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

    import glob

    import jax

    from chipbench import harness, trace_reduce, trace_scopes
    from chipbench.forms import sweep as form
    from chipbench.kinds import load_kind

    bench = harness.load_benchmark()
    cell, entry = harness.find_cell(bench, args.workload)
    traffic = harness.load_json(f"chipbench/traffic/{cell['traffic']}.json")
    cfg = {**harness.load_json(entry["file"]), **traffic.get("sweep", {})}
    try:
        devs = harness.devices(int(cell["chips"]), allow_cpu=allow_cpu)
    except harness.NoChip as e:
        print(f"phase_split: {e}", file=sys.stderr)
        return 3
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    kind = load_kind(cfg["kind"])
    program = form.Program(cfg, traffic, kind.make_data(cfg), form.make_fleet(cfg, kind),
                           kind, int(cell["chips"]))
    program.sweep(form.sweep_seed(args.seed, 0))

    def timed(j: int) -> float:
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(form.SPAN):
            program.sweep(form.sweep_seed(args.seed, j))
        return time.perf_counter() - t

    untraced = [timed(j) for j in range(1, 1 + args.untraced)]
    trace_dir = harness.WORK / "phase_split" / cell["name"]
    shutil.rmtree(trace_dir, ignore_errors=True)
    n = args.traced or int(traffic["trace_sweeps"])
    jax.profiler.start_trace(str(trace_dir))
    try:
        traced = [timed(j) for j in range(1 + args.untraced, 1 + args.untraced + n)]
    finally:
        jax.profiler.stop_trace()
    (pb,) = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
    buf = Path(pb).read_bytes()
    t = time.perf_counter()
    rep = trace_scopes.extract(buf)
    decode_s = time.perf_counter() - t
    red = trace_reduce.reduce_dir(trace_dir, span=form.SPAN)
    scoped = trace_scopes.reduce(rep, form.SPAN)
    readers = {e["name"]: r for e, r in harness.per_layer_readers(bench, cell)}
    accepted = {name: readers[name].read({"trace": red, "requests": n})
                for name in ("scan_ms.sim", "driver_ms.sim", "idle_share.sim")
                if name in readers}
    out = {
        "cell": cell["name"], "seed": args.seed, "device": devs[0].device_kind,
        "sweep_s": {"untraced": untraced, "traced": traced,
                    "untraced_median": statistics.median(untraced) if untraced else None,
                    "traced_median": statistics.median(traced)},
        "trace_bytes": len(buf), "decode_s": decode_s,
        "accepted": accepted,
        "window_s": red["window_s"], "busy_s": red["busy_s"],
        "device_ops": red["device_ops"],
        "scoped": scoped,
        "split": split(scoped, accepted.get("scan_ms.sim") or 0.0, n),
        "readings": trace_scopes.readings(scoped, n),
    }
    spans = [e for h in rep["host"] for e in h["events"] if e[0] == form.SPAN]
    out["leading_ops"] = leading_ops(buf, min(e[1] for e in spans),
                                     max(e[1] + e[2] for e in spans))
    if args.slice:
        piece = cut(rep, form.SPAN)
        Path(args.slice).write_text(json.dumps({
            "about": f"a few ms of a traced run of {cell['name']} on one {devs[0].device_kind}: "
                     "the end of one method's scan, its copies and counters, the next "
                     "method's preparation; reduced: what trace_scopes.reduce gave for it",
            "trace": piece,
            "reduced": trace_scopes.reduce(piece, form.SPAN),
        }))
    shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
