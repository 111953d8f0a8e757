"""Compile the main path for a described TPU v5e, without a chip.

The TPU compiler is installed with jaxlib, and it compiles for a
topology that is described rather than attached.  These tests hand it the
Pallas block-subgradient kernels at the paper-scale shapes with their real
``width_bucket`` pads, the grid-cache kernel at the 100-worker logreg grid
shapes (which XLA:TPU refuses, so the engine refuses it first), the
whole fused scan of each problem with the eval it takes on the chip, and
the §6 optimizer round (Algorithm 1) at the 100-worker grid's shapes.
What Mosaic or XLA:TPU would refuse on the chip fails here.  Nothing
runs: a compile says nothing about results or times.

The topology is described inside a module fixture, never at import time:
only one process at a time may load the TPU library, and the test workers
all import this file.
"""

import dataclasses
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.cluster.simulator import MethodConfig
from repro.core.problems import (
    LogisticRegressionProblem,
    PCAProblem,
    make_genomics_like_matrix,
    make_higgs_like,
)
from repro.experiments import default_convergence_methods, fused
from repro.experiments.engine import CAP_PALLAS_X64_STATE
from repro.experiments.grid import HEAVY_BURSTS
from repro.kernels.block_sub import logreg_block_sub, pca_block_sub
from repro.kernels.cache_events import grid_cache_update
from repro.latency.model import make_heterogeneous_cluster, sample_fleet
from repro.lb import jit_optimizer as jlb
from repro.lb.partitioner import build_p_ladder
from repro.precision import x64

#: the paper-scale PCA matrix and the logreg grid's data, as packed rows
PCA_ROWS, PCA_D, PCA_K = 50_000, 96, 3
LR_ROWS, LR_D = 16_384, 29
LANES = 128


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache, so keep the cache out of these compiles
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def _compile_in_engine_context(fn, *shapes):
    """The engines trace these kernels under x64, so compile them there."""
    with x64():
        return jax.jit(fn).lower(*shapes).compile()


@pytest.mark.parametrize("pad", [256, 1024])  # sag/dsag and gd widths, N=50
def test_pca_block_sub_compiles_at_paper_scale(one_chip, pad):
    G = 256  # 4 scenarios x 50 workers, padded to a power of two
    compiled = _compile_in_engine_context(
        lambda rows, vb, s, w: pca_block_sub(rows, vb, s, w, pad),
        _sds((PCA_ROWS, LANES), jnp.float32, one_chip),
        _sds((G, PCA_D, PCA_K), jnp.float32, one_chip),
        _sds((G,), jnp.int64, one_chip),
        _sds((G,), jnp.int64, one_chip),
    )
    assert _has_kernel(compiled)


@pytest.mark.parametrize("pad", [32, 256])  # sag/dsag and gd widths, N=100
def test_logreg_block_sub_compiles_at_grid_scale(one_chip, pad):
    G = 1024  # 10 scenarios x 100 workers, padded to a power of two
    compiled = _compile_in_engine_context(
        lambda rows, vb, s, w: logreg_block_sub(rows, vb, s, w, pad),
        _sds((LR_ROWS, LANES), jnp.float32, one_chip),
        _sds((G, LR_D), jnp.float32, one_chip),
        _sds((G,), jnp.int64, one_chip),
        _sds((G,), jnp.int64, one_chip),
    )
    assert _has_kernel(compiled)


def test_grid_cache_update_is_refused(one_chip, monkeypatch):
    """The grid-cache kernel does not compile for the chip (its state is
    float64/int64 and its blocks are not tile-aligned), so off the CPU the
    engine refuses the Pallas backend for grid-cache configs up front."""
    S, R, E, F = 10, 200, 100, LR_D  # the 100-worker grid, subpartitions 1
    with x64():
        args = [
            _sds((S, R), jnp.bool_, one_chip),
            _sds((S, R), jnp.int64, one_chip),
            _sds((S, R), jnp.int64, one_chip),
            _sds((S, R, F), jnp.float64, one_chip),
            _sds((S, F), jnp.float64, one_chip),
            _sds((S, E, F), jnp.float64, one_chip),
            _sds((S, E), jnp.int64, one_chip),
            _sds((S,), jnp.int64, one_chip),
            _sds((S,), jnp.int64, one_chip),
            _sds((E,), jnp.int64, one_chip),
        ]
        with pytest.raises(Exception):  # noqa: B017 - any compiler refusal
            jax.jit(grid_cache_update).lower(*args).compile()
    X, y = make_higgs_like(256, seed=0)
    prob = LogisticRegressionProblem(X=X, y=y)
    dsag = MethodConfig(name="dsag", w=8, eta=0.25, subpartitions=2)
    assert fused.kernel_backend_capability(prob, "pallas", dsag).supported
    monkeypatch.setattr(fused.jax, "default_backend", lambda: "tpu")
    cap = fused.kernel_backend_capability(prob, "pallas", dsag)
    assert not cap.supported and cap.code == CAP_PALLAS_X64_STATE
    # configs without the grid cache keep the Pallas backend
    sgd = MethodConfig(name="sgd", w=8, eta=0.25, subpartitions=2)
    assert fused.kernel_backend_capability(prob, "pallas", sgd).supported


def _compile_scan_for_chip(one_chip, prob, N, S, T, sp, eta, eval_every):
    """The DSAG scan (grid cache, xla backend) as the chip would run it:
    the spec made here takes the CPU's forms, so they are set to the
    chip's (compiled kernels, the stacked eval).  Returns the compiled
    scan and the ``op_name`` of each of its ops."""
    c_task = prob.compute_cost(1, max(prob.num_samples // (N * sp), 1))
    cluster = make_heterogeneous_cluster(N, seed=0, burst_rate=0.0, load_unit=c_task)
    traces = sample_fleet(
        cluster, S, T, burst_rate=HEAVY_BURSTS.rate,
        burst_factor_mean=HEAVY_BURSTS.factor_mean,
        burst_duration_mean=HEAVY_BURSTS.duration_mean, seed=1,
    )
    cfg = default_convergence_methods(N, w=int(0.8 * N), eta=eta, subpartitions=sp)["dsag"]
    spec, kernels, scan_args = fused.prepare_scan_inputs(
        prob, traces, cfg, T, eval_every=eval_every
    )
    spec = dataclasses.replace(spec, kernel_interpret=False, eval_stacked=True)
    with x64():
        shapes = [_sds(np.shape(a), a.dtype, one_chip) for a in scan_args]
        compiled = (
            jax.jit(fused._run_scan, static_argnums=(0, 1))
            .lower(kernels, spec, *shapes)
            .compile()
        )
    assert compiled.memory_analysis() is not None
    return compiled, re.findall(r'op_name="([^"]*)"', compiled.as_text())


def _assert_eval_after_the_scan(op_names):
    """The stacked eval is one pass after the scan: its ops read
    ``jit(_run_scan)/dsag/phase_eval/...`` and none lies in a loop."""
    evals = [p for p in op_names if "phase_eval" in p.split("/")]
    assert any(p.startswith("jit(_run_scan)/dsag/phase_eval/dot_general") for p in evals)
    assert not any("while" in p.split("/") for p in evals)
    assert any("/while/body/" in p and "phase_subgrad" in p.split("/") for p in op_names)


def test_fused_scan_body_compiles_for_logreg_grid(one_chip):
    """The whole float64 scan of the 100-worker logreg grid (DSAG, grid
    cache, xla backend, stacked eval), at a reduced iteration count."""
    X, y = make_higgs_like(LR_ROWS, seed=0)
    prob = LogisticRegressionProblem(X=X, y=y)
    _, op_names = _compile_scan_for_chip(
        one_chip, prob, N=100, S=10, T=10, sp=10, eta=0.25, eval_every=5
    )
    _assert_eval_after_the_scan(op_names)


def test_fused_scan_compiles_for_pca(one_chip):
    """The same for the 50-worker PCA job (5 subpartitions, k = 3), at a
    reduced size: the stacked eval's one ``[n, d] @ [d, B k]`` float64
    contraction sits after the scan."""
    prob = PCAProblem(X=make_genomics_like_matrix(2_000, 256, seed=0), k=PCA_K)
    _, op_names = _compile_scan_for_chip(
        one_chip, prob, N=50, S=4, T=8, sp=5, eta=0.9, eval_every=4
    )
    _assert_eval_after_the_scan(op_names)


def test_lb_update_compiles_for_logreg_grid(one_chip):
    """Algorithm 1 with the publication gate (its restore and slack
    ``while_loop``s over the what-if replay) for 10 scenarios x 100 workers,
    on the §6 ladder of the grid's 10 subpartitions over ~163 rows each."""
    S, N = 10, 100
    with x64():
        f64 = [_sds((S, N), jnp.float64, one_chip)] * 6
        upd = functools.partial(
            jlb.lb_update, ladder=build_p_ladder(10, LR_ROWS // N), w=80,
            margin=0.02, key=jax.random.PRNGKey(0),
        )
        compiled = jax.jit(upd).lower(
            *f64, _sds((S,), jnp.float64, one_chip), _sds((S,), jnp.bool_, one_chip)
        ).compile()
    assert compiled.memory_analysis() is not None
