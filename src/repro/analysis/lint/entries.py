"""Registered entry points the tracelint rules run against.

Each entry builds a small but *production-shaped* probe: the fused-scan
entries trace the real ``_run_scan`` body through
:func:`repro.experiments.fused.prepare_scan_inputs` (the same operand
builder ``run_convergence_scan`` uses), the kernel entries trace the real
``FusedKernels.sub_blocks`` closures, and so on — the analyzer never
audits a hand-maintained replica of the code it guards.

The registry is shared infrastructure: ``benchmarks/tracelint_bench.py``
times these same probes and ``benchmarks/bench_regression.py --kind
tracelint`` gates on them.
"""

from __future__ import annotations

import dataclasses
import functools
from collections.abc import Callable
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.cluster.simulator import MethodConfig, task_finish_time
from repro.core.problems import (
    LogisticRegressionProblem,
    PCAProblem,
    make_genomics_like_matrix,
    make_higgs_like,
)
from repro.experiments import fused
from repro.latency.model import make_heterogeneous_cluster, sample_fleet
from repro.precision import x64


@dataclasses.dataclass
class EntryProbe:
    """One registered entry point, traced and annotated for the rules.

    ``cond_depth_threshold`` marks how many enclosing loops are "batching"
    loops whose body-level conditionals are legitimate (the fused training
    scan); TL005 audits conds strictly deeper.  ``padded_axis_sizes`` are
    the width-bucket pad lengths TL003 audits reductions over.
    ``declared_output_dtypes`` is the kernel output contract TL004 checks,
    and ``hlo_fn_args`` lets TL002 compile the entry and attach
    HLO-derived copy-traffic evidence to a confirmed finding.
    """

    name: str
    description: str
    jaxpr: Any = None  # ClosedJaxpr for the structural rules
    latency_probe: tuple | None = None  # (fn, [args, ...]) for TL001
    cond_depth_threshold: int = 0
    padded_axis_sizes: tuple = ()
    declared_output_dtypes: tuple | None = None
    hlo_fn_args: tuple | None = None  # (fn, args) lowered on demand


# --------------------------------------------------------------------------
# shared probe fixtures (small, deterministic, CPU-cheap)
# --------------------------------------------------------------------------

_PROBE_WORKERS = 4
_PROBE_SCENARIOS = 2
_PROBE_ITERS = 6


@functools.lru_cache(maxsize=None)
def _probe_logreg():
    X, y = make_higgs_like(64, seed=0)
    return LogisticRegressionProblem(X=X, y=y)


@functools.lru_cache(maxsize=None)
def _probe_pca():
    return PCAProblem(X=make_genomics_like_matrix(64, 24, seed=0), k=2)


@functools.lru_cache(maxsize=None)
def _probe_traces():
    cluster = make_heterogeneous_cluster(
        _PROBE_WORKERS, seed=3, burst_rate=0.0, comp_range=(1.1e-3, 2.5e-3)
    )
    return sample_fleet(cluster, _PROBE_SCENARIOS, 10, burst_rate=0.0, seed=11)


@functools.lru_cache(maxsize=None)
def _probe_churn_traces():
    """The probe fleet under elastic churn: one death inside the probe
    horizon plus a slowdown drift, so ``spec.has_churn`` compiles the
    liveness mask, per-start slowdown rows, and dead-entry cache clears
    into the audited jaxpr."""
    from repro.latency.model import ChurnSchedule

    traces = _probe_traces()
    sd = np.asarray(traces.slowdown)
    alive0 = np.ones(_PROBE_WORKERS, bool)
    alive1 = alive0.copy()
    alive1[3] = False
    return traces.with_churn(
        ChurnSchedule(
            times=np.array([0.004]),
            slowdown=np.stack([sd, sd * 1.2]),
            alive=np.stack([alive0, alive1]),
        )
    )


def _fused_probe(
    problem, config, *, slot_budget=None, traces=None, kernel_backend="xla"
) -> EntryProbe:
    """Trace the production scan body with production-built operands."""
    if traces is None:
        traces = _probe_traces()
    spec, kernels, scan_args = fused.prepare_scan_inputs(
        problem, traces, config, _PROBE_ITERS, slot_budget=slot_budget,
        kernel_backend=kernel_backend,
    )
    fn = functools.partial(fused._run_scan, kernels, spec)
    with x64():
        jaxpr = jax.make_jaxpr(fn)(*scan_args)
    return EntryProbe(
        name="",
        description="",
        jaxpr=jaxpr,
        cond_depth_threshold=1,  # the training scan itself
        hlo_fn_args=(fn, scan_args),
    )


def _latency_chain(unit, cost, slowdown, factor, start, comm):
    # looked up through the module so the TL001 regression test can
    # monkeypatch the seam away and watch the rule fire
    comp = fused.guarded_comp_latency(unit, cost, slowdown, factor)
    return task_finish_time(start, comp, comm)


def _build_latency() -> EntryProbe:
    """TL001 probe: the §3 product feeding ``task_finish_time``.

    The rule compiles this chain and diffs against op-by-op evaluation;
    random strictly-positive draws make any FMA contraction of the final
    multiply-add visible in the last ULP.
    """
    with x64():
        batches = []
        for seed in (0, 1, 2, 3):
            rng = np.random.default_rng(seed)
            batches.append(
                tuple(
                    jnp.asarray(rng.uniform(0.1, 3.0, size=64), dtype=jnp.float64)
                    for _ in range(6)
                )
            )
        jaxpr = jax.make_jaxpr(_latency_chain)(*batches[0])
    return EntryProbe(
        name="latency",
        description="§3 latency product -> task_finish_time (FMA seam)",
        jaxpr=jaxpr,
        latency_probe=(_latency_chain, batches),
    )


def _build_fused_logreg_grid() -> EntryProbe:
    cfg = MethodConfig(name="dsag", w=3, subpartitions=2)
    probe = _fused_probe(_probe_logreg(), cfg)
    probe.name = "fused_logreg_grid"
    probe.description = "fused scan body, logreg, grid §5 cache"
    return probe


def _build_fused_logreg_lb() -> EntryProbe:
    cfg = MethodConfig(name="dsag", w=3, subpartitions=2, load_balance=True)
    probe = _fused_probe(_probe_logreg(), cfg)
    probe.name = "fused_logreg_lb"
    probe.description = "fused scan body, logreg, §6 LB slot-universe cache"
    return probe


def _build_fused_logreg_tiled() -> EntryProbe:
    cfg = MethodConfig(name="dsag", w=3, subpartitions=2, load_balance=True)
    prob = _probe_logreg()
    cap = fused.scan_capability(prob, cfg, _PROBE_WORKERS)
    # a budget of one slot less than the full universe forces the tiled
    # active-slot cache while staying supported
    probe = _fused_probe(prob, cfg, slot_budget=cap.slots_total - 1)
    probe.name = "fused_logreg_tiled"
    probe.description = "fused scan body, logreg, tiled active-slot cache"
    return probe


def _build_fused_logreg_churn() -> EntryProbe:
    cfg = MethodConfig(name="dsag", w=3, subpartitions=2, load_balance=True)
    probe = _fused_probe(_probe_logreg(), cfg, traces=_probe_churn_traces())
    probe.name = "fused_logreg_churn"
    probe.description = (
        "fused scan body, logreg, §6 LB universe cache under fleet churn"
    )
    return probe


def _build_fused_pca_grid() -> EntryProbe:
    cfg = MethodConfig(name="dsag", w=3, subpartitions=2)
    probe = _fused_probe(_probe_pca(), cfg)
    probe.name = "fused_pca_grid"
    probe.description = "fused scan body, PCA, grid §5 cache"
    return probe


def _build_fused_logreg_grid_pallas() -> EntryProbe:
    """The Pallas-backed scan body: the structural walkers recurse into
    ``pallas_call`` kernel jaxprs, so TL002-TL005 audit the §3
    ``block_sub`` and §5 ``cache_events`` kernels in their production
    surroundings (interpret mode traces identically to compiled)."""
    cfg = MethodConfig(name="dsag", w=3, subpartitions=2)
    probe = _fused_probe(_probe_logreg(), cfg, kernel_backend="pallas")
    probe.name = "fused_logreg_grid_pallas"
    probe.description = (
        "fused scan body, logreg, grid §5 cache, Pallas kernel backend"
    )
    return probe


def _build_fused_pca_grid_pallas() -> EntryProbe:
    cfg = MethodConfig(name="dsag", w=3, subpartitions=2)
    probe = _fused_probe(_probe_pca(), cfg, kernel_backend="pallas")
    probe.name = "fused_pca_grid_pallas"
    probe.description = (
        "fused scan body, PCA, grid §5 cache, Pallas kernel backend"
    )
    return probe


def _kernels_probe(problem, name: str, description: str) -> EntryProbe:
    kernels = problem.fused_kernels()
    pad_w = 16  # width_bucket(m, n) for 8 < m <= 16 at n=64
    with x64():
        starts = jnp.asarray([1, 17, 33], dtype=jnp.int64)
        widths = jnp.asarray([11, 16, 13], dtype=jnp.int64)
        Vb = jnp.zeros(
            (3,) + kernels.value_shape, dtype=kernels.value_dtype
        )
        jaxpr = jax.make_jaxpr(
            functools.partial(kernels.sub_blocks, pad_width=pad_w)
        )(Vb, starts, widths)
    return EntryProbe(
        name=name,
        description=description,
        jaxpr=jaxpr,
        padded_axis_sizes=(pad_w,),
        declared_output_dtypes=(np.dtype(kernels.value_dtype),),
    )


def _build_kernels_logreg() -> EntryProbe:
    return _kernels_probe(
        _probe_logreg(),
        "kernels_logreg",
        "FusedKernels.sub_blocks, logreg (width-bucket masked reduce)",
    )


def _build_kernels_pca() -> EntryProbe:
    return _kernels_probe(
        _probe_pca(),
        "kernels_pca",
        "FusedKernels.sub_blocks, PCA (width-bucket masked matmul)",
    )


def _build_lb_update() -> EntryProbe:
    from repro.lb import jit_optimizer as jlb

    S, N = _PROBE_SCENARIOS, _PROBE_WORKERS
    ladder = (1, 2, 4, 8, 16)
    with x64():
        rng = np.random.default_rng(7)
        args = (
            jnp.asarray(np.full((S, N), 2.0)),  # p_cur
            jnp.asarray(rng.uniform(1e-3, 5e-3, (S, N))),  # e_comm
            jnp.asarray(rng.uniform(1e-7, 1e-6, (S, N))),  # v_comm
            jnp.asarray(rng.uniform(1e-2, 5e-2, (S, N))),  # e_comp
            jnp.asarray(rng.uniform(1e-5, 1e-4, (S, N))),  # v_comp
            jnp.asarray(np.full((S, N), 16.0)),  # n_j
            jnp.asarray(np.full((S,), np.nan)),  # h_min
            jnp.asarray(np.ones((S,), bool)),  # active
        )
        fn = functools.partial(
            jlb.lb_update,
            ladder=ladder,
            w=3,
            margin=0.02,
            key=jax.random.PRNGKey(0),
        )
        jaxpr = jax.make_jaxpr(fn)(*args)
    return EntryProbe(
        name="lb_update",
        description="§6 optimizer round (Algorithm 1 + publication gate)",
        jaxpr=jaxpr,
    )


def _build_kernels_ops() -> EntryProbe:
    from repro.kernels import ops

    def probe(x, v, g, c, h, mask):
        gram = ops.gram_matvec_op(x, v, interpret=True)
        new_c, new_h = ops.dsag_cache_update_op(g, c, h, mask, interpret=True)
        return gram, new_c, new_h

    args = (
        jnp.zeros((32, 8), jnp.float32),
        jnp.zeros((8, 4), jnp.float32),
        jnp.zeros((4, 64), jnp.float32),
        jnp.zeros((4, 64), jnp.float32),
        jnp.zeros((64,), jnp.float32),
        jnp.zeros((4,), jnp.bool_),
    )
    jaxpr = jax.make_jaxpr(probe)(*args)
    return EntryProbe(
        name="kernels_ops",
        description="Pallas kernel wrappers (gram_matvec, dsag_cache_update)",
        jaxpr=jaxpr,
    )


def _build_dsag_pjit() -> EntryProbe:
    from repro.configs.base import TrainConfig
    from repro.core.dsag_pjit import GroupSpec, dsag_update, init_dsag_state

    tc = TrainConfig()
    gs = GroupSpec(num_groups=4, axes=())
    params_like = {"w": jax.ShapeDtypeStruct((8, 16), jnp.float32)}
    dsag0 = init_dsag_state(params_like, gs, tc)
    grads = {"w": jnp.zeros((4, 8, 16), jnp.float32)}
    mask = jnp.ones((4,), jnp.bool_)
    flush = jnp.zeros((4,), jnp.bool_)
    jaxpr = jax.make_jaxpr(dsag_update)(dsag0, grads, mask, flush)
    return EntryProbe(
        name="dsag_pjit",
        description="live-system DSAG cache rule (core/dsag_pjit.dsag_update)",
        jaxpr=jaxpr,
    )


#: name -> builder.  Names are stable API (baselines and CI artifacts key
#: on them); keep additions append-only.
ENTRIES: dict[str, Callable[[], EntryProbe]] = {
    "latency": _build_latency,
    "fused_logreg_grid": _build_fused_logreg_grid,
    "fused_logreg_lb": _build_fused_logreg_lb,
    "fused_logreg_tiled": _build_fused_logreg_tiled,
    "fused_logreg_churn": _build_fused_logreg_churn,
    "fused_pca_grid": _build_fused_pca_grid,
    "fused_logreg_grid_pallas": _build_fused_logreg_grid_pallas,
    "fused_pca_grid_pallas": _build_fused_pca_grid_pallas,
    "kernels_logreg": _build_kernels_logreg,
    "kernels_pca": _build_kernels_pca,
    "lb_update": _build_lb_update,
    "kernels_ops": _build_kernels_ops,
    "dsag_pjit": _build_dsag_pjit,
}


def build_entries(names) -> list:
    """Build the named probes ('all' or an iterable of registry keys)."""
    if names == "all" or names == ["all"]:
        names = list(ENTRIES)
    unknown = [n for n in names if n not in ENTRIES]
    if unknown:
        raise KeyError(
            f"unknown lint entries {unknown}; known: {sorted(ENTRIES)}"
        )
    return [ENTRIES[n]() for n in names]
