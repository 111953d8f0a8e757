"""Scope paths, program spans and counters read from a trace: a hand-made
XSpace whose ops carry their op path as a stat, one whose paths come from
the executable's HLO, a tiny sweep traced on this machine, a recorded
slice of a chip trace, and ``phase_split.py``."""

from __future__ import annotations

import json
from pathlib import Path

import pytest

from chipbench import trace_scopes

DATA = Path(__file__).resolve().parent / "data"
SPAN = "chipbench.sweep"
BODY = "jit(_run_scan)/dsag/while/body"


def _ev(meta_id: int, start_ns: float, dur_ns: float, stats: str = "") -> str:
    return (f"events {{ metadata_id: {meta_id} offset_ps: {int(start_ns * 1000)} "
            f"duration_ps: {int(dur_ns * 1000)} {stats} }}")


def _str_stat(mid: int, value: str) -> str:
    return f'stats {{ metadata_id: {mid} str_value: "{value}" }}'


def _int_stat(mid: int, value: int) -> str:
    return f"stats {{ metadata_id: {mid} int64_value: {value} }}"


# Times in ns from the lines' timestamp.  Device: a _run_scan module
# [20, 120] whose while op [20, 115] holds one op per phase and one under
# no phase; another module [150, 160].  Host: the benchmark's span around
# the program's spans and two counter spans.
OPS = [  # (metadata id, start, duration, path) in _run_scan
    (10, 20, 95, "jit(_run_scan)/dsag/while"),
    (11, 20, 10, f"{BODY}/phase_events/sort"),
    (12, 30, 20, f"{BODY}/phase_cache/phase_subgrad/dot_general"),
    (13, 50, 20, f"{BODY}/closed_call/phase_cache/dynamic_update_slice"),
    (14, 70, 5, f"{BODY}/phase_update/sub"),
    (15, 75, 25, f"{BODY}/phase_eval/cond/branch_1_fun/log"),
    (16, 100, 5, f"{BODY}/add"),
    (18, 80, 5, ""),  # an op XLA added, inside the eval's op
]
HOST = [  # (name, start, duration, stats)
    (SPAN, 0, 200, ""),
    ("repro.sweep", 1, 198, _int_stat(3, 9)),
    ("repro.sample_fleet", 2, 8, ""),
    ("repro.method", 12, 120, _str_stat(2, "dsag")),
    ("repro.prepare", 12, 4, ""),
    ("repro.dispatch", 16, 2, ""),
    ("repro.fetch", 18, 112, ""),
    ("repro.counts", 131, 1, " ".join([_str_stat(2, "dsag"), _int_stat(4, 100),
                                         _int_stat(5, 80), _int_stat(6, 5),
                                         _int_stat(7, 200)])),
    ("repro.method", 140, 50, _str_stat(2, "coded")),
    ("repro.prepare", 141, 4, ""),
    ("repro.dispatch", 145, 2, ""),
    ("repro.fetch", 147, 23, ""),
    ("repro.counts", 185, 1, " ".join([_str_stat(2, "sag"), _int_stat(4, 50),
                                         _int_stat(5, 50), _int_stat(6, 0),
                                         _int_stat(7, 100)])),
]
STAT_NAMES = {1: "tf_op", 2: "method", 3: "seed", 4: "subgrad_rows", 5: "events",
              6: "rejected", 7: "walk_ranks", 20: OPS[3][3]}


def _xspace() -> str:
    ops, meta = [], []
    for mid, s, d, path in OPS:
        if mid == 11:  # the path as the event's own stat
            ops.append(_ev(mid, s, d, _str_stat(1, path)))
            meta.append(f'event_metadata {{ key: {mid} value {{ id: {mid} name: "op{mid}" }} }}')
            continue
        ops.append(_ev(mid, s, d))
        if not path:
            meta.append(f'event_metadata {{ key: {mid} value {{ id: {mid} name: "op{mid}" }} }}')
            continue
        # the path on the op's metadata; one of them by reference
        stat = (f"stats {{ metadata_id: 1 ref_value: 20 }}" if mid == 13
                else _str_stat(1, path))
        meta.append(f'event_metadata {{ key: {mid} value {{ id: {mid} name: "op{mid}" {stat} }} }}')
    ops.append(_ev(17, 150, 10))
    other = _str_stat(1, "jit(other)/mul")
    meta.append(f'event_metadata {{ key: 17 value {{ id: 17 name: "op17" {other} }} }}')
    meta += ['event_metadata { key: 1 value { id: 1 name: "jit__run_scan(7)" } }',
             'event_metadata { key: 2 value { id: 2 name: "jit_other(3)" } }']
    stat_meta = [f'stat_metadata {{ key: {k} value {{ id: {k} name: "{v}" }} }}'
                 for k, v in STAT_NAMES.items()]
    host_meta, host_ev = {}, []
    for name, s, d, stats in HOST:
        k = host_meta.setdefault(name, len(host_meta) + 1)
        host_ev.append(_ev(k, s, d, stats))
    host_ev.append(_ev(99, 60, 1))  # a Python frame, not decoded
    host_meta_txt = [f'event_metadata {{ key: {k} value {{ id: {k} name: "{n}" }} }}'
                     for n, k in host_meta.items()]
    host_meta_txt.append('event_metadata { key: 99 value { id: 99 name: "_array.py:631 _value" } }')
    return f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 1000
    {_ev(1, 20, 100)} {_ev(2, 150, 10)} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 1000 {" ".join(ops)} }}
  {" ".join(meta)} {" ".join(stat_meta)} }}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 3 name: "python" timestamp_ns: 1000 {" ".join(host_ev)} }}
  {" ".join(host_meta_txt)} {" ".join(stat_meta)} }}
"""


def _serialized(text: str) -> bytes:
    from jax.profiler import ProfileData

    return ProfileData.text_proto_to_serialized_xspace(text)


def test_wire_walk_matches_jax_reader():
    """Names, times and event stats as ``ProfileData`` reads them."""
    from jax.profiler import ProfileData

    buf = _serialized(_xspace())
    pd = ProfileData.from_serialized_xspace(buf)
    for plane, mine in zip(pd.planes, trace_scopes.planes(buf)):
        assert plane.name == mine["name"]
        for line, (name, events) in zip(plane.lines, mine["lines"]):
            assert line.name == name
            want = [(e.name, e.start_ns, e.duration_ns, dict(e.stats)) for e in line.events]
            got = [(mine["meta"][m][0], s, d, st) for m, s, d, st in events]
            assert got == want


def test_extract_paths_and_spans():
    rep = trace_scopes.extract(_serialized(_xspace()))
    (dev,) = rep["devices"]
    assert [o[0] for o in dev["ops"]] == [p for *_, p in OPS] + ["jit(other)/mul"]
    assert dev["ops"][1] == (OPS[1][3], 1020.0, 10.0)
    assert dev["modules"] == [("jit__run_scan(7)", 1020.0, 100.0), ("jit_other(3)", 1150.0, 10.0)]
    (host,) = rep["host"]
    # only the benchmark's and the program's spans, with their arguments
    assert [e[0] for e in host["events"]] == [h[0] for h in HOST]
    assert host["events"][1][3] == {"seed": 9}
    assert host["events"][7][3] == {"method": "dsag", "subgrad_rows": 100, "events": 80,
                                    "rejected": 5, "walk_ranks": 200}


@pytest.mark.parametrize("path,phase", [
    (f"{BODY}/phase_cache/phase_subgrad/dot_general", "subgrad"),
    (f"{BODY}/closed_call/phase_cache/while/body/closed_call/gather:", "cache"),
    (f"{BODY}/phase_update:", "update"),
    (f"{BODY}/phase_eval/cond/branch_1_fun/while/body/closed_call/exp", "eval"),
    ("phase_events/reduce_max", "events"),
    (f"{BODY}/phase_lb/cond", "lb"),
    ("jit(_run_scan)/dsag/while", "none"),
    (f"{BODY}/phase_walk/add", "none"),
    ("", "none"),
])
def test_phase_of(path, phase):
    assert trace_scopes.phase_of(path) == phase


def test_an_op_without_a_phase_takes_the_one_it_runs_inside():
    ops = [("a/phase_eval/while", 0, 10), ("", 2, 3),
           ("a/phase_eval/while/body/phase_cache/x", 5, 2), ("", 5, 1), ("", 20, 4),
           ("a/while", 30, 10), ("", 31, 2)]
    assert trace_scopes.phase_times(ops) == [
        ("eval", "eval", 0, 5), ("none", "eval", 2, 3), ("cache", "cache", 5, 1),
        ("none", "cache", 5, 1), ("none", "none", 20, 4), ("none", "none", 30, 8),
        ("none", "none", 31, 2)]


def test_reduce_and_readings():
    red = trace_scopes.reduce(trace_scopes.extract(_serialized(_xspace())), SPAN)
    assert red["spans"] == 1
    # the while op's self time (95 less its children's 85) and the op under
    # no phase are "none"; the op without a path counts in the eval it runs
    # inside; the other module's op is not counted
    assert red["phase_s"] == pytest.approx({
        "events": 10e-9, "subgrad": 20e-9, "cache": 20e-9, "update": 5e-9,
        "eval": 25e-9, "lb": 0.0, "none": 15e-9,
    })
    # device busy [20, 115] and [150, 160]: idle in sample_fleet 8, the two
    # prepares 4 + 4, the two dispatches 2 + 2; in the fetches 2 + 15 and 13
    # by its own path, the op without one is "none"
    assert red["own_phase_s"] == pytest.approx({
        "events": 10e-9, "subgrad": 20e-9, "cache": 20e-9, "update": 5e-9,
        "eval": 20e-9, "lb": 0.0, "none": 20e-9,
    })
    assert red["idle_s"] == pytest.approx({"prepare": 20e-9, "fetch": 30e-9})
    # the sweep [1, 199] and the methods [12, 132] and [140, 190] less the
    # busy [20, 115] and [150, 160]
    assert red["sweeps"] == [{"seed": 9, "s": pytest.approx(198e-9),
                              "idle_s": pytest.approx(93e-9)}]
    assert red["methods"] == {
        "dsag": {"runs": 1, "s": pytest.approx(120e-9), "idle_s": pytest.approx(25e-9)},
        "coded": {"runs": 1, "s": pytest.approx(50e-9), "idle_s": pytest.approx(40e-9)},
    }
    assert red["counts"] == {
        "dsag": {"subgrad_rows": 100, "events": 80, "rejected": 5, "walk_ranks": 200},
        "sag": {"subgrad_rows": 50, "events": 50, "rejected": 0, "walk_ranks": 100},
    }
    got = trace_scopes.readings(red, requests=1)
    assert got == pytest.approx({
        "events_ms.sim": 10e-6, "subgrad_ms.sim": 20e-6, "cache_ms.sim": 20e-6,
        "update_ms.sim": 5e-6, "eval_ms.sim": 25e-6,
        "prepare_ms.sim": 20e-6, "fetch_ms.sim": 30e-6,
        "subgrad_yield.sim": 100.0 * (75 + 50) / 150,
        "walk_yield.sim": 100.0 * (80 + 50) / 300,
    })
    assert trace_scopes.readings(red, requests=2)["cache_ms.sim"] == pytest.approx(10e-6)


def test_a_trace_without_the_program_reads_nothing():
    """The parent program has no scopes, spans or counters: no reading, and
    nothing raises."""
    text = _xspace().replace("phase_", "step_").replace('name: "repro.', 'name: "other.')
    red = trace_scopes.reduce(trace_scopes.extract(_serialized(text)), SPAN)
    assert red["counts"] == {} and red["idle_s"] == {}
    assert red["sweeps"] == [] and red["methods"] == {}
    assert trace_scopes.readings(red, requests=1) == {}
    empty = trace_scopes.reduce({"devices": [], "host": []}, SPAN)
    assert trace_scopes.readings(empty, requests=1) == {}


def _len(n: int) -> bytes:
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def test_paths_from_the_executables_hlo():
    """Where the ops carry no path, the ``Hlo Proto`` the trace keeps for
    each module gives it: here a real CPU executable's.  An op the HLO does
    not name either takes its phase from the op it runs inside."""
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("phase_subgrad"):
            y = jnp.sin(x) * 3.0
        with jax.named_scope("phase_eval"):
            return jnp.sum(y * y)

    compiled = jax.jit(f).lower(jnp.ones((64,), jnp.float32)).compile()
    (module,) = compiled.runtime_executable().hlo_modules()
    raw = module.as_serialized_hlo_module_proto()
    names = trace_scopes.hlo_op_names(b"\n" + _len(len(raw)) + raw)  # HloProto.hlo_module
    scoped = {n: p for n, p in names.items() if "phase_" in p}
    assert {trace_scopes.phase_of(p) for p in scoped.values()} == {"subgrad", "eval"}
    inst = sorted(scoped)[0]
    escaped = "".join(f"\\{b:03o}" for b in b"\n" + _len(len(raw)) + raw)
    text = f"""
planes {{ id: 1 name: "/host:metadata"
  event_metadata {{ key: 5 value {{ id: 5 name: "jit__run_scan(5)"
    stats {{ metadata_id: 1 bytes_value: "{escaped}" }} }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "Hlo Proto" }} }} }}
planes {{ id: 2 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0 {_ev(1, 0, 10)} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0 {_ev(2, 1, 5)} {_ev(4, 2, 1)} {_ev(3, 20, 1)} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "jit__run_scan(5)" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "%{inst} = f32[64] fusion(f32[64] %p)" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "%convert.9 = f32[64] convert(f64[64] %q)" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "copy.1" }} }} }}
"""
    (dev,) = trace_scopes.extract(_serialized(text))["devices"]
    assert dev["ops"] == [(scoped[inst], 1.0, 5.0), ("", 2.0, 1.0), ("", 20.0, 1.0)]
    phase = trace_scopes.phase_of(scoped[inst])
    assert [p[:2] for p in trace_scopes.phase_times(dev["ops"])] == [
        (phase, phase), ("none", phase), ("none", "none")]


def test_a_real_trace(tmp_path):
    """A tiny sweep traced on this machine: the program's spans and the
    benchmark's come out with their arguments, as ``ProfileData`` reads
    them."""
    import glob

    import jax
    from jax.profiler import ProfileData

    from repro.core.problems import LogisticRegressionProblem, make_higgs_like
    from repro.experiments import EngineConfig, run_convergence_sweep
    from repro.experiments.convergence import default_convergence_methods
    from repro.latency.model import make_heterogeneous_cluster

    X, y = make_higgs_like(240, seed=0)
    problem = LogisticRegressionProblem(X=X, y=y)
    cluster = make_heterogeneous_cluster(6, seed=3, burst_rate=0.0)
    methods = default_convergence_methods(6, w=4, eta=0.2, subpartitions=2)
    kw = dict(n_scenarios=2, num_iterations=8, eval_every=4, burst_rate=0.05,
              burst_factor_mean=4.0, burst_duration_mean=30.0, engine=EngineConfig(kind="scan"))
    run_convergence_sweep(problem, cluster, methods, seed=1, **kw)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(SPAN):
            run_convergence_sweep(problem, cluster, methods, seed=2, **kw)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    rep = trace_scopes.extract(Path(path).read_bytes())
    want = [(e.name, e.start_ns, e.duration_ns, dict(e.stats))
            for p in ProfileData.from_file(path).planes for line in p.lines
            for e in line.events if e.name.startswith(("chipbench.", "repro."))]
    (host,) = rep["host"]
    assert [tuple(e) for e in host["events"]] == want
    names = [e[0] for e in want]
    assert names.count("repro.method") == 4 and names.count("repro.counts") == 3
    assert {e[3]["method"] for e in want if e[0] == "repro.counts"} == {"dsag", "sag", "sgd"}


def test_phase_names_are_the_programs():
    from repro.experiments.fused import SCAN_PHASES

    assert SCAN_PHASES == tuple(trace_scopes.PREFIX + p for p in trace_scopes.PHASES)


def test_recorded_scoped_chip_slice():
    """A few ms of a traced pca_genomics_50w run on a TPU v5e, and the
    numbers ``trace_scopes.reduce`` gave for it when it was recorded."""
    rec = json.loads((DATA / "chip_slice_scoped.json").read_text())
    red = trace_scopes.reduce(rec["trace"], SPAN)
    want = rec["reduced"]
    assert red["spans"] == want["spans"] == 1
    for k in ("phase_s", "own_phase_s", "idle_s"):
        assert red[k] == pytest.approx(want[k]), k
    assert red["counts"] == want["counts"]
    assert red["sweeps"] == [pytest.approx(s) for s in want["sweeps"]]
    assert red["methods"] == {m: pytest.approx(v) for m, v in want["methods"].items()}
    assert sum(red["phase_s"][p] for p in trace_scopes.PHASES) > 0
    assert set(red["idle_s"]) == {"prepare", "fetch"}
    assert set(red["methods"]) >= {"dsag"}


def test_phase_split_refuses_without_a_chip():
    """Like ``run.py``, the script measures on the cell's chips or not at all."""
    import os
    import subprocess
    import sys

    root = DATA.parents[2]
    p = subprocess.run(
        [sys.executable, "chipbench/phase_split.py", "--workload", "logreg_higgs_100w.sweep",
         "--seed", "1"], cwd=root, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_phase_split_on_the_cpu(capsys):
    """The whole script at the logreg cell's real size, the look for a chip
    skipped: its sweeps, the benchmark's readers and the program's spans."""
    from chipbench import phase_split

    assert phase_split.main(["--workload", "logreg_higgs_100w.sweep", "--seed",
                             str(2**31 + 5), "--untraced", "1", "--traced", "1"],
                            allow_cpu=True) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert len(out["sweep_s"]["untraced"]) == len(out["sweep_s"]["traced"]) == 1
    assert set(out["accepted"]) == {"scan_ms.sim", "driver_ms.sim", "idle_share.sim"}
    assert out["scoped"]["spans"] == 1
    assert {"all", "inherited", "none", "ops"} <= set(out["leading_ops"])
