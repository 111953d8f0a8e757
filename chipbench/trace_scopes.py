"""Program spans, span arguments and device-op scope paths, read from a
JAX profiler trace, and the per-layer readings made from them.

The program marks its work in the trace it is profiled into:

* a named scope per phase of the fused scan body (``phase_events``,
  ``phase_subgrad``, ``phase_cache``, ``phase_update``, ``phase_eval``,
  ``phase_lb``), which XLA keeps as each op's ``op_name`` metadata, so a
  device op's path reads ``.../<method>/while/body/<phase>/...``;
* host spans ``repro.sweep``, ``repro.sample_fleet``, ``repro.method``,
  ``repro.prepare``, ``repro.dispatch``, ``repro.fetch``;
* a zero-length ``repro.counts`` span per method whose arguments are the
  scan's work counters (``subgrad_rows``, ``events``, ``rejected``,
  ``walk_ranks``).

``jax.profiler.ProfileData`` gives an event's own stats but not those of
its event metadata, which is where a TPU trace keeps an op's properties
(the op path among them).  So ``extract`` walks the XSpace protobuf
itself: the wire format needs no generated code, and only the fields named
in ``tsl/profiler/protobuf/xplane.proto`` are read.  The v5e trace has no
such path for control-flow ops (``while``, ``conditional``); for them, and
any other op without one, the ``op_name`` comes from the module's HLO,
which the trace keeps on its ``/host:metadata`` plane.

``reduce`` then computes, inside the window of the benchmark's spans (as
``trace_reduce.reduce`` does):

* ``phase_s``: device self-time of the ops inside the fused scan's
  executables (``_run_scan``), by the innermost phase scope in each op's
  path; a fusion goes by its own path, which XLA takes from the fusion's
  root op; an op whose path names no phase goes by the op it runs inside
  (``phase_times``), and ``"none"`` is what is left (the scan's own loop,
  the set-up of its state);
* ``own_phase_s``: the same time by each op's own path alone, so that
  ``"none"`` there holds every op without a phase scope;
* ``idle_s``: device-idle time inside the program's host spans, for the
  set that prepares and dispatches a scan and for the one that fetches it;
* ``counts``: the counters of every ``repro.counts`` span, summed per
  method;
* ``sweeps``: each ``repro.sweep`` span's ``seed``, seconds and
  device-idle seconds, which place a slow sweep and say whether the device
  or the host held it up;
* ``methods``: per ``method`` of the ``repro.method`` spans, their count,
  seconds and device-idle seconds.
"""

from __future__ import annotations

import bisect
import struct

from chipbench.trace_reduce import DEVICE_PREFIX, HOST_PREFIX, MODULES_LINE, OPS_LINE
from chipbench.trace_reduce import clip, covered, union

#: the scan body's phase scopes, without their ``phase_`` prefix
PHASES = ("events", "subgrad", "cache", "update", "eval", "lb")
PREFIX = "phase_"
#: the op stat that carries an op's scope path in a TPU trace
PATH_STAT = "tf_op"
#: name prefixes of the benchmark's host spans and of the program's
SPAN_PREFIX, PROGRAM_PREFIX = "chipbench.", "repro."
SCAN_MODULE = "_run_scan"
#: host spans whose device-idle time ``prepare_ms.sim`` and
#: ``fetch_ms.sim`` read
PREPARE_SPANS = ("repro.sample_fleet", "repro.prepare", "repro.dispatch")
FETCH_SPANS = ("repro.fetch",)
COUNTS_SPAN = "repro.counts"
#: spans whose seconds and device-idle time ``reduce`` gives one by one
#: (a sweep, by its ``seed``) and summed by their ``method``
SWEEP_SPAN, METHOD_SPAN = "repro.sweep", "repro.method"
COUNTERS = ("subgrad_rows", "events", "rejected", "walk_ranks")


# -- the XSpace wire format ---------------------------------------------------


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, i: int = 0, end: int | None = None):
    """(field number, value) of one message; a length-delimited value is
    its (start, end) in ``buf``, a fixed 64-bit one its raw 8 bytes."""
    end = len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            v = (i, i + n)
            i += n
        elif kind == 1:
            v = buf[i:i + 8]
            i += 8
        elif kind == 5:
            v = buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"wire type {kind} at byte {i}")
        yield key >> 3, v


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _str(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _stat(buf: bytes, span, names: dict):
    """(name, value) of one XStat; a ``ref_value`` is the name of another
    stat metadata entry, the plane's way of storing a repeated string."""
    mid, value = 0, None
    for f, v in _fields(buf, *span):
        if f == 1:
            mid = v
        elif f == 2:
            value = struct.unpack("<d", v)[0]
        elif f in (3, 4):
            value = _signed(v) if f == 4 else v
        elif f == 5:
            value = _str(buf, v)
        elif f == 6:
            value = buf[v[0]:v[1]]
        elif f == 7:
            value = names.get(v, "")
    return names.get(mid, str(mid)), value


def _map_entry(buf: bytes, span) -> tuple[int, tuple[int, int] | None]:
    key, value = 0, None
    for f, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _plane(buf: bytes, span, host_prefixes: tuple[str, ...] | None) -> dict:
    """One XPlane: its name, its event metadata (name, stats) and its
    lines with their events (metadata id, start ns, duration ns, stats).
    Of a host plane, with ``host_prefixes``, only the events whose name
    starts with one of them (the rest are skipped undecoded)."""
    name, lines, ev_meta, stat_names = "", [], {}, {}
    for f, v in _fields(buf, *span):
        if f == 2:
            name = _str(buf, v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            ev_meta.update([_map_entry(buf, v)])
        elif f == 5:
            k, m = _map_entry(buf, v)
            for g, w in _fields(buf, *m):
                if g == 2:
                    stat_names[k] = _str(buf, w)
    meta = {}
    for k, m in ev_meta.items():
        mname, stats = "", {}
        for f, v in _fields(buf, *m):
            if f == 2:
                mname = _str(buf, v)
            elif f == 5:
                sk, sv = _stat(buf, v, stat_names)
                stats[sk] = sv
        meta[k] = (mname, stats)
    wanted = None
    if host_prefixes is not None and name.startswith(HOST_PREFIX):
        wanted = {k for k, (n, _) in meta.items() if n.startswith(host_prefixes)}
    out_lines = []
    for span_l in lines:
        lname, ts, events = "", 0, []
        for f, v in _fields(buf, *span_l):
            if f == 2:
                lname = _str(buf, v)
            elif f == 3:
                ts = _signed(v)
            elif f == 4:
                events.append(v)
        evs = []
        for span_e in events:
            if wanted is not None:
                # metadata_id is written first: skip the event on it alone
                key, i = _varint(buf, span_e[0])
                if key != 8 or _varint(buf, i)[0] not in wanted:
                    continue
            mid, off, dur, stats = 0, 0, 0, {}
            for f, v in _fields(buf, *span_e):
                if f == 1:
                    mid = v
                elif f == 2:
                    off = _signed(v)
                elif f == 3:
                    dur = _signed(v)
                elif f == 4:
                    sk, sv = _stat(buf, v, stat_names)
                    stats[sk] = sv
            # whole ns, as jax.profiler.ProfileData gives them
            evs.append((mid, float(ts + off // 1000), float(dur // 1000), stats))
        out_lines.append((lname, evs))
    return {"name": name, "meta": meta, "lines": out_lines}


def planes(buf: bytes, host_prefixes: tuple[str, ...] | None = None) -> list[dict]:
    """Every plane of a serialized XSpace (see ``_plane``)."""
    return [_plane(buf, v, host_prefixes) for f, v in _fields(buf) if f == 1]


# -- from a trace to plain lists ----------------------------------------------


def op_path(stats: dict, meta_stats: dict) -> str:
    """An op's ``PATH_STAT``: the event's own, else its metadata's."""
    path = stats.get(PATH_STAT) or meta_stats.get(PATH_STAT)
    return path if isinstance(path, str) else ""


def _sub(buf: bytes, span, field: int) -> list:
    return [v for f, v in _fields(buf, *span) if f == field]


def hlo_op_names(proto: bytes) -> dict[str, str]:
    """Instruction name -> ``op_name`` metadata of one serialized HloProto
    (``hlo_module`` 1; module ``computations`` 3; computation
    ``instructions`` 2; instruction ``name`` 1, ``metadata`` 7; metadata
    ``op_name`` 2)."""
    out = {}
    for module in _sub(proto, (0, len(proto)), 1):
        for comp in _sub(proto, module, 3):
            for ins in _sub(proto, comp, 2):
                name = [_str(proto, v) for v in _sub(proto, ins, 1)]
                meta = _sub(proto, ins, 7)
                op_name = [_str(proto, v) for m in meta for v in _sub(proto, m, 2)]
                if name and op_name:
                    out[name[0]] = op_name[0]
    return out


def _instruction(op: str) -> str:
    """HLO instruction name of an op event (``%fusion.3 = f32[...] ...`` or
    ``fusion.3``)."""
    return op.split(" = ", 1)[0].lstrip("%")


def hlo_protos(all_planes: list[dict]) -> dict[str, bytes]:
    """Module name -> serialized HloProto, from the ``/host:metadata`` plane."""
    return {
        name: stats["Hlo Proto"]
        for plane in all_planes if plane["name"] == HOST_PREFIX + "metadata"
        for name, stats in plane["meta"].values()
        if isinstance(stats.get("Hlo Proto"), bytes)
    }


def device_ops(plane: dict, hlo: dict[str, bytes]) -> tuple[list, list]:
    """(name, path, start, duration) of each op of a device plane's "XLA
    Ops" line, and its modules (name, start, duration).  An op's path is
    its ``PATH_STAT`` where the trace has one, else the ``op_name`` that its
    module's HLO gives the instruction: the v5e trace gives no
    ``PATH_STAT`` to control-flow ops (``while``, ``conditional``), and the
    ops XLA adds inside them take their phase through them
    (``phase_times``).  An op with neither has the path ``""``."""
    meta = plane["meta"]
    raw, modules = [], []
    for lname, evs in plane["lines"]:
        if lname == OPS_LINE:
            raw = evs
        elif lname == MODULES_LINE:
            modules = [(meta.get(m, (str(m), {}))[0], s, d) for m, s, d, _ in evs]
    mods = sorted(modules, key=lambda e: e[1])
    starts = [e[1] for e in mods]
    names: dict[str, dict[str, str]] = {}
    ops = []
    for m, s, d, st in raw:
        name, meta_stats = meta.get(m, (str(m), {}))
        path = op_path(st, meta_stats)
        if not path:
            i = bisect.bisect_right(starts, s) - 1
            module = mods[i][0] if i >= 0 else ""
            if module in hlo and module not in names:
                names[module] = hlo_op_names(hlo[module])
            path = names.get(module, {}).get(_instruction(name), "")
        ops.append((name, path, s, d))
    return ops, modules


def extract(buf: bytes) -> dict:
    """Per device plane its ops (path, start ns, duration ns, see
    ``device_ops``) and modules (name, start, duration); per host line that
    holds a benchmark span, the benchmark's and the program's spans inside
    the benchmark's (name, start, duration, arguments)."""
    devices, host = [], []
    all_planes = planes(buf, (SPAN_PREFIX, PROGRAM_PREFIX))
    hlo = hlo_protos(all_planes)
    for plane in all_planes:
        if plane["name"].startswith(DEVICE_PREFIX) and "TPU" in plane["name"]:
            ops, modules = device_ops(plane, hlo)
            devices.append({"name": plane["name"], "ops": [o[1:] for o in ops],
                            "modules": modules})
        elif plane["name"].startswith(HOST_PREFIX):
            meta = plane["meta"]
            for lname, evs in plane["lines"]:
                named = [(meta.get(m, (str(m), {}))[0], s, d, st) for m, s, d, st in evs]
                spans = [e for e in named if e[0].startswith(SPAN_PREFIX)]
                if not spans:
                    continue
                lo = min(e[1] for e in spans)
                hi = max(e[1] + e[2] for e in spans)
                host.append({
                    "line": lname,
                    "events": [e for e in named if e[1] >= lo and e[1] + e[2] <= hi],
                })
    return {"devices": devices, "host": host}


# -- from plain lists to the numbers the metrics read -------------------------


def phase_of(path: str) -> str:
    """The innermost phase scope named in an op path, or ``"none"``.  A TPU
    trace's ``tf_op`` is the path with ``:<op type>`` after it."""
    for part in reversed(path.split("/")):
        name = part.partition(":")[0]
        if name.startswith(PREFIX) and name[len(PREFIX):] in PHASES:
            return name[len(PREFIX):]
    return "none"


def phase_times(ops) -> list[tuple[str, str, float, float]]:
    """(own phase, counted phase, start, self time) of each op of one line,
    where ops nest as in ``trace_reduce.self_times``.  The own phase is the
    one the op's path names; an op whose path names none is counted in the
    phase of the innermost op it runs inside: XLA leaves the ops it adds
    itself (the float64 emulation's splits, layout converts) without an op
    path, and they run inside a loop op of the phase that needs them."""
    out: list[list] = []
    stack: list[int] = []
    for path, s, d in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and out[stack[-1]][2] + out[stack[-1]][4] <= s:
            stack.pop()
        own = counted = phase_of(path)
        if stack:
            out[stack[-1]][3] -= d
            if own == "none":
                counted = out[stack[-1]][1]
        out.append([own, counted, s, d, d])
        stack.append(len(out) - 1)
    return [(own, p, s, t) for own, p, s, t, _ in out]


def _idle_in(merged, spans) -> float:
    """Length of the union of ``spans`` that no merged busy interval covers."""
    return sum(e - s - covered(merged, s, e) for s, e in union(spans))


def reduce(rep: dict, span: str) -> dict:
    spans = [e for h in rep["host"] for e in h["events"] if e[0] == span]
    out = {"spans": len(spans), "phase_s": {}, "own_phase_s": {}, "idle_s": {},
           "counts": {}, "sweeps": [], "methods": {}}
    if not spans or not rep["devices"]:
        return out
    w0 = min(e[1] for e in spans)
    w1 = max(e[1] + e[2] for e in spans)
    main = max(rep["host"], key=lambda h: sum(1 for e in h["events"] if e[0] == span))
    inside = [(e[0], e[1], e[1] + e[2], e[3] if len(e) > 3 else {})
              for e in main["events"] if w0 <= e[1] and e[1] + e[2] <= w1]
    by_name: dict[str, list] = {}
    for name, s, e, _ in inside:
        by_name.setdefault(name, []).append((s, e))
    n_dev = len(rep["devices"])
    phase = {p: 0.0 for p in PHASES + ("none",)}
    own_phase = dict(phase)
    busy = []
    for dev in rep["devices"]:
        mods = sorted(dev["modules"], key=lambda e: e[1])
        starts = [e[1] for e in mods]
        for own, p, s, t in phase_times(dev["ops"]):
            if not (w0 <= s < w1) or t <= 0:
                continue
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < mods[i][1] + mods[i][2] and SCAN_MODULE in mods[i][0]:
                phase[p] += t / n_dev
                own_phase[own] += t / n_dev
        busy.append(clip(union((s, s + d) for _, s, d in dev["ops"] or dev["modules"]), w0, w1))

    def idle(intervals) -> float:
        return sum(_idle_in(merged, intervals) for merged in busy) / n_dev * 1e-9

    groups = {"prepare": PREPARE_SPANS, "fetch": FETCH_SPANS}
    counts: dict[str, dict[str, int]] = {}
    for name, s, e, args in inside:
        if name == COUNTS_SPAN:
            c = counts.setdefault(str(args.get("method", "?")), dict.fromkeys(COUNTERS, 0))
            for k in COUNTERS:
                c[k] += int(args.get(k, 0))
        elif name == SWEEP_SPAN:
            out["sweeps"].append({"seed": args.get("seed"), "s": (e - s) * 1e-9,
                                  "idle_s": idle([(s, e)])})
        elif name == METHOD_SPAN:
            m = out["methods"].setdefault(str(args.get("method", "?")),
                                          {"runs": 0, "s": 0.0, "idle_s": 0.0})
            m["runs"] += 1
            m["s"] += (e - s) * 1e-9
            m["idle_s"] += idle([(s, e)])
    out["phase_s"] = {k: v * 1e-9 for k, v in phase.items()}
    out["own_phase_s"] = {k: v * 1e-9 for k, v in own_phase.items()}
    out["idle_s"] = {g: idle([iv for n in names for iv in by_name.get(n, ())])
                     for g, names in groups.items() if any(n in by_name for n in names)}
    out["counts"] = counts
    return out


def readings(red: dict, requests: int) -> dict:
    """The per-layer readings of one traced run: each phase's device
    milliseconds per sweep, the device-idle milliseconds per sweep inside
    the preparing and the fetching spans, and two yields in percent.  A
    reading whose inputs the trace lacks is left out."""
    out: dict[str, float] = {}
    if not requests or not red.get("spans"):
        return out
    phase = red["phase_s"]
    if sum(phase.get(p, 0.0) for p in PHASES) > 0:
        for p in ("events", "subgrad", "cache", "update", "eval"):
            out[f"{p}_ms.sim"] = 1e3 * phase[p] / requests
    for g, seconds in red["idle_s"].items():
        out[f"{g}_ms.sim"] = 1e3 * seconds / requests
    rows = [c for c in red["counts"].values() if c["subgrad_rows"] > 0]
    if rows:
        done = sum(c["events"] - c["rejected"] for c in rows)
        out["subgrad_yield.sim"] = 100.0 * done / sum(c["subgrad_rows"] for c in rows)
    walks = [c for c in red["counts"].values() if c["walk_ranks"] > 0]
    if walks:
        out["walk_yield.sim"] = (
            100.0 * sum(c["events"] for c in walks) / sum(c["walk_ranks"] for c in walks)
        )
    return out
