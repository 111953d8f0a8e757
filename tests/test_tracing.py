"""The simulator's tracing: a named scope per phase of the fused scan body,
host spans in the sweep driver, and the work counters carried as span
arguments.

The scopes are HLO metadata only: the optimized program with metadata
stripped is the same with and without them.  The spans and their
arguments are read back from a real profiler trace, and the counters are
held to an independent count on the scalar simulator.
"""

import contextlib
import glob
import re

import jax
import numpy as np
import pytest

from repro.cluster.simulator import TraceLatencySource, TrainingSimulator
from repro.core.problems import LogisticRegressionProblem, make_higgs_like
from repro.experiments import EngineConfig, fused, run_convergence_sweep
from repro.experiments.convergence import default_convergence_methods
from repro.latency.model import make_heterogeneous_cluster, sample_fleet
from repro.precision import x64

N, S, T, EVAL_EVERY = 6, 2, 12, 3
METHODS = ("dsag", "sag", "sgd", "coded")
#: the phases every default method's scan body has (``lb`` is §6 only);
#: ``phase_eval`` follows the scan
BODY_PHASES = ("phase_events", "phase_subgrad", "phase_cache", "phase_update")
EVAL_PHASE = "phase_eval"
_META = re.compile(r", metadata=\{[^}]*\}")
_TABLES = re.compile(r"\n(FileNames|FunctionNames|FileLocations|StackFrames)\n(?:[^\n]+\n)*")


@pytest.fixture(scope="module")
def setup():
    X, y = make_higgs_like(240, seed=0)
    problem = LogisticRegressionProblem(X=X, y=y)
    cluster = make_heterogeneous_cluster(
        N, seed=3, burst_rate=0.0, comp_range=(1.1e-3, 2.5e-3)
    )
    traces = sample_fleet(
        cluster, S, T, burst_rate=3.0, burst_factor_mean=3.0,
        burst_duration_mean=5e-3, seed=11,
    )
    methods = default_convergence_methods(N, w=4, eta=0.2, subpartitions=2)
    return problem, cluster, traces, methods


def _optimized_hlo(setup, name: str) -> str:
    problem, _, traces, methods = setup
    spec, kernels, args = fused.prepare_scan_inputs(
        problem, traces, methods[name], T, eval_every=EVAL_EVERY
    )
    jax.clear_caches()  # trace anew: the jit cache would hand back the last body
    with x64():
        fn = jax.jit(fused._run_scan, static_argnums=(0, 1))
        return fn.lower(kernels, spec, *args).compile().as_text()


@pytest.fixture(scope="module")
def hlo(setup):
    """Each method's optimized HLO, with the scopes and with a no-op
    ``named_scope`` standing for the code without them."""
    out = {}
    for name in METHODS:
        scoped = _optimized_hlo(setup, name)
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(jax, "named_scope", lambda _name: contextlib.nullcontext())
            plain = _optimized_hlo(setup, name)
        out[name] = (scoped, plain)
    jax.clear_caches()
    return out


def _assert_eval_after_scan(op_names, method_path: str):
    """The eval runs once, after the scan, inside the method's scope: its
    ops read ``<method_path>/phase_eval/...``, and none lies in a loop
    outside the scope (the scan's ``while/body``)."""
    evals = [p for p in op_names if EVAL_PHASE in p.split("/")]
    assert any(p.startswith(f"{method_path}/{EVAL_PHASE}/") for p in evals)
    assert not any("while" in p.split(EVAL_PHASE)[0] for p in evals)


@pytest.mark.parametrize("name", METHODS)
def test_every_phase_scope_is_in_the_hlo(hlo, name):
    scoped, plain = hlo[name]
    assert scoped.startswith("HloModule jit__run_scan")
    op_names = re.findall(r'op_name="([^"]*)"', scoped)
    for phase in BODY_PHASES:
        paths = [p for p in op_names if phase in p.split("/")]
        assert paths, phase
        # the method's scope sits around the scan, the phase inside its body
        assert any(p.startswith(f"jit(_run_scan)/{name}/while/body/") for p in paths), phase
    _assert_eval_after_scan(op_names, f"jit(_run_scan)/{name}")
    assert not any(phase in plain for phase in fused.SCAN_PHASES)


@pytest.mark.parametrize("name", METHODS)
def test_scopes_change_no_computation(hlo, name):
    scoped, plain = hlo[name]

    def strip(text):
        return _META.sub("", _TABLES.sub("\n", text))

    assert "metadata=" not in strip(scoped)
    assert strip(scoped) == strip(plain)


def test_scopes_under_shard_map(setup):
    """The scenario-sharded scan keeps the phase scopes and ``_run_scan`` in
    its executable's name."""
    if len(jax.devices()) < 2:
        pytest.skip(
            "needs >= 2 devices (CI re-runs with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=4)"
        )
    from repro.launch.mesh import make_scenario_mesh

    problem, _, traces, methods = setup
    spec, kernels, args = fused.prepare_scan_inputs(problem, traces, methods["dsag"], T)
    with x64():
        fn = fused._scan_jit_for(kernels, make_scenario_mesh(2))
        text = fn.lower(kernels, spec, *args).compile().as_text()
    assert text.startswith("HloModule jit__run_scan_sharded")
    op_names = re.findall(r'op_name="([^"]*)"', text)
    for phase in BODY_PHASES:
        assert any(p.startswith("jit(_run_scan_sharded)/shard_map/dsag/while/body/")
                   and phase in p.split("/") for p in op_names), phase
    _assert_eval_after_scan(op_names, "jit(_run_scan_sharded)/shard_map/dsag")


def test_scope_names_are_apart_from_jax_names():
    import jax.numpy as jnp
    from jax import lax

    names = [p.removeprefix("phase_") for p in fused.SCAN_PHASES]
    assert names == ["events", "subgrad", "cache", "update", "eval", "lb"]
    for scope in fused.SCAN_PHASES:
        assert not any(hasattr(m, scope) for m in (jax, jnp, lax))
    with pytest.raises(AssertionError):
        fused._phase("walk")


def _host_events(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        for line in plane.lines:
            events = [
                (e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
                for e in line.events if e.name.startswith("repro.")
            ]
            if events:
                return events
    return []


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_sweep_spans_nest_with_their_arguments(setup, tmp_path):
    problem, cluster, _, methods = setup
    kw = dict(n_scenarios=S, num_iterations=T, eval_every=EVAL_EVERY, burst_rate=3.0,
              burst_factor_mean=3.0, burst_duration_mean=5e-3,
              engine=EngineConfig(kind="scan"))
    run_convergence_sweep(problem, cluster, methods, seed=5, **kw)  # compile first
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = run_convergence_sweep(problem, cluster, methods, seed=6, **kw)
    finally:
        jax.profiler.stop_trace()
    events = _host_events(tmp_path)
    by_name = {}
    for e in events:
        by_name.setdefault(e[0], []).append(e)
    (sweep,) = by_name["repro.sweep"]
    assert sweep[3] == {"seed": 6}
    (sample,) = by_name["repro.sample_fleet"]
    assert _inside(sample, sweep)
    per_method = by_name["repro.method"]
    assert [m[3]["method"] for m in per_method] == list(methods)
    for phase in ("repro.prepare", "repro.dispatch", "repro.fetch"):
        assert len(by_name[phase]) == len(methods)
    counts = {c[3]["method"]: c[3] for c in by_name["repro.counts"]}
    assert set(counts) == {"dsag", "sag", "sgd"}  # coded has no block subgradients
    for k, m in enumerate(per_method):
        assert _inside(m, sweep) and m[1] >= sample[2]
        prep, disp, fetch = (by_name[p][k] for p in ("repro.prepare", "repro.dispatch",
                                                     "repro.fetch"))
        assert _inside(prep, m) and _inside(disp, m) and _inside(fetch, m)
        assert prep[2] <= disp[1] and disp[2] <= fetch[1]
        name = m[3]["method"]
        if name in counts:
            (c,) = [c for c in by_name["repro.counts"] if c[3]["method"] == name]
            assert _inside(c, m) and c[1] >= fetch[2]
            spec, _, _ = fused.prepare_scan_inputs(
                problem, out.traces, methods[name], T, eval_every=EVAL_EVERY
            )
            assert {k: v for k, v in c[3].items() if k != "method"} == fused.scan_counts(
                spec, out.results[name])
            # the post-scan eval's counters: on the CPU one pass per iterate
            n_evals = len(range(0, T, EVAL_EVERY)) + ((T - 1) % EVAL_EVERY != 0)
            assert c[3]["eval_iterates"] == c[3]["eval_passes"] == S * n_evals


@pytest.mark.parametrize("name", ["dsag", "sag", "sgd"])
def test_counters_match_the_scalar_simulator(setup, name):
    """Each counter against a count made on the scalar simulator's own
    decision streams, scenario by scenario, on the same traces."""
    problem, cluster, traces, methods = setup
    cfg = methods[name]
    res = fused.run_convergence_scan(problem, traces, cfg, T, eval_every=EVAL_EVERY)
    spec, _, _ = fused.prepare_scan_inputs(problem, traces, cfg, T, eval_every=EVAL_EVERY)
    events = rejected = evals = 0
    for s in range(S):
        h = TrainingSimulator(
            problem, cluster, cfg, eval_every=EVAL_EVERY,
            latency_source=TraceLatencySource(traces, s),
        ).run(T)
        # fresh results, plus (with stale acceptance) stale ones the cache
        # took (flush) or refused (rejected)
        events += int(h.mask_stream.sum())
        if cfg.accepts_stale:
            events += int(h.flush_stream.sum()) + h.rejected_stale
        rejected += h.rejected_stale
        evals += int(np.isfinite(h.suboptimality).sum())
    counts = fused.scan_counts(spec, res)
    n_buckets = len(spec.buckets)
    assert n_buckets >= 1
    assert counts["subgrad_rows"] == S * T * N * n_buckets
    assert counts["events"] == events
    assert counts["rejected"] == rejected
    walk = {"dsag": 2 * N, "sag": N, "sgd": 0}[name]
    assert counts["walk_ranks"] == S * T * walk
    assert 0 < counts["events"] <= counts["subgrad_rows"]
    if walk:
        assert counts["events"] <= counts["walk_ranks"]
    # the scalar simulator evaluates the same iterations
    assert counts["eval_iterates"] == evals == int(np.isfinite(res.suboptimality).sum())
    assert counts["eval_passes"] == evals  # the per-iterate map on the CPU
