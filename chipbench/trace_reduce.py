"""From a JAX profiler trace to the numbers the per-layer metrics read.

``extract`` turns a trace into plain lists: per device plane the events
of its op line and of its module line (name, start ns, duration ns), and
the host events of the benchmark's spans together with the host events
nested in them on the same thread.  ``reduce`` then computes, inside the
traced window (first span start to last span end):

* busy time: the union of the device-op intervals, per device, and its
  mean over the devices used;
* executable self-time by name: the module events' durations summed per
  executable name (the ``(id)`` suffix dropped);
* the spans' host time and the device-busy time inside them;
* ``device_ops``: the ten ops that took most device self-time (an op's
  duration less that of the ops nested in it on the same line), named by
  their HLO instruction name and the executable they ran in;
* ``idle_gaps``: the ten longest gaps between device-op intervals,
  labelled by the innermost host event the main thread was in at the
  gap's midpoint (the span's name where nothing is nested deeper).

Busy time counts the compute ops line only ("XLA Ops"): the asynchronous
copies of "Async XLA Ops" overlap compute and do not make the cores busy.
"""

from __future__ import annotations

import bisect
import glob
import json
import re
from pathlib import Path

DEVICE_PREFIX = "/device:"
HOST_PREFIX = "/host:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_ID_SUFFIX = re.compile(r"\(\d+\)$")


def load(path) -> dict:
    """``extract`` of the one ``*.xplane.pb`` under ``path`` (a trace dir)."""
    from jax.profiler import ProfileData

    files = glob.glob(str(Path(path) / "**" / "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"expected one xplane.pb under {path}, found {files}")
    return extract(ProfileData.from_file(files[0]))


def extract(pd, span_prefix: str = "chipbench.") -> dict:
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX) and "TPU" in plane.name:
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(e.name, e.start_ns, e.duration_ns) for e in line.events]
                elif line.name == MODULES_LINE:
                    modules = [(e.name, e.start_ns, e.duration_ns) for e in line.events]
            devices.append({"name": plane.name, "ops": ops, "modules": modules})
        elif plane.name.startswith(HOST_PREFIX):
            for line in plane.lines:
                events = [(e.name, e.start_ns, e.duration_ns) for e in line.events]
                spans = [e for e in events if e[0].startswith(span_prefix)]
                if not spans:
                    continue
                lo = min(s[1] for s in spans)
                hi = max(s[1] + s[2] for s in spans)
                host.append({
                    "line": line.name,
                    "events": [e for e in events if e[1] >= lo and e[1] + e[2] <= hi],
                })
    return {"devices": devices, "host": host}


def union(intervals) -> list[tuple[float, float]]:
    """Merge (start, end) intervals into sorted disjoint ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo, hi) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def covered(merged, lo, hi) -> float:
    """Length of [lo, hi] that the merged intervals cover."""
    return sum(e - s for s, e in clip(merged, lo, hi))


def self_times(events) -> list[tuple[str, float, float]]:
    """(name, start, self time) of each event of one line, where events
    nest: each event's duration less its direct children's."""
    out: list[list] = []
    stack: list[int] = []
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and out[stack[-1]][1] + out[stack[-1]][3] <= s:
            stack.pop()
        if stack:
            out[stack[-1]][2] -= d
        out.append([name, s, d, d])
        stack.append(len(out) - 1)
    return [(n, s, self_t) for n, s, self_t, _ in out]


def _short(op: str) -> str:
    """HLO instruction name of an op event (``%while.12 = (...) while(...)``)."""
    return op.split(" = ", 1)[0]


def _label(host_events, t) -> str:
    """Innermost host event containing time ``t``."""
    best, best_dur = "outside the spans", float("inf")
    for name, s, d in host_events:
        if s <= t <= s + d and d < best_dur:
            best, best_dur = name, d
    return best


def reduce(rep: dict, span: str, top: int = 10) -> dict:
    spans = [e for h in rep["host"] for e in h["events"] if e[0] == span]
    if not spans or not rep["devices"]:
        return {"window_s": 0.0, "busy_s": 0.0, "spans": 0, "span_s": 0.0,
                "span_busy_s": 0.0, "modules": {}, "device_ops": [], "idle_gaps": []}
    w0 = min(s for _, s, _ in spans)
    w1 = max(s + d for _, s, d in spans)
    main = max(rep["host"], key=lambda h: sum(1 for e in h["events"] if e[0] == span))
    busy, span_busy, op_time, modules, gaps = [], [], {}, {}, []
    for k, dev in enumerate(rep["devices"]):
        source = dev["ops"] or dev["modules"]
        merged = union((s, s + d) for _, s, d in source)
        merged = clip(merged, w0, w1)
        busy.append(sum(e - s for s, e in merged))
        span_busy.append(sum(covered(merged, s, s + d) for _, s, d in spans))
        mods = sorted(dev["modules"], key=lambda e: e[1])
        mod_starts = [e[1] for e in mods]
        for name, s, t in self_times(dev["ops"]):
            if not (w0 <= s < w1) or t <= 0:
                continue
            i = bisect.bisect_right(mod_starts, s) - 1
            where = mods[i][0] if i >= 0 and s < mods[i][1] + mods[i][2] else "?"
            key = f"{_short(name)} in {where}"
            op_time[key] = op_time.get(key, 0.0) + t / len(rep["devices"])
        for name, s, d in dev["modules"]:
            t = covered([(s, s + d)], w0, w1)
            if t > 0:
                key = _ID_SUFFIX.sub("", name)
                modules[key] = modules.get(key, 0.0) + t / len(rep["devices"])
        if k == 0:
            edges = [w0] + [x for iv in merged for x in iv] + [w1]
            gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    host_events = sorted(main["events"], key=lambda e: e[1])
    starts = [e[1] for e in host_events]
    idle = []
    for s, e in longest:
        mid = 0.5 * (s + e)
        i = bisect.bisect_right(starts, mid)
        idle.append([_label(host_events[:i], mid), (e - s) * 1e-9])
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(busy) / len(busy) * 1e-9,
        "spans": len(spans),
        "span_s": sum(d for _, _, d in spans) * 1e-9,
        "span_busy_s": sum(span_busy) / len(span_busy) * 1e-9,
        "modules": {k: v * 1e-9 for k, v in modules.items()},
        "device_ops": [[n, t * 1e-9] for n, t in
                       sorted(op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": idle,
    }


def reduce_dir(path, span: str) -> dict:
    return reduce(load(path), span)


def save(rep: dict, path) -> None:
    Path(path).write_text(json.dumps(rep))
