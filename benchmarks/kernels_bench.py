"""Kernel microbenchmarks: the jnp reference path's time on the host this
runs on, plus each kernel's analytic roofline ratio (naive over fused
lower bound, from the published v5e peaks — a projection, not a
measurement of any chip)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import record, time_fn
from repro.analysis.roofline import V5E, peaks_for
from repro.kernels.ops import (
    dsag_cache_update_op,
    dsag_update_ref,
    flash_attention_op,
    flash_attention_ref,
    gram_matvec_op,
    gram_matvec_ref,
)

_PEAK = peaks_for(V5E)
PEAK_FLOPS, HBM_BW = _PEAK.flops, _PEAK.hbm_bw


def bench_gram_matvec() -> None:
    n, d, k = 4096, 512, 8
    x = jax.random.normal(jax.random.key(0), (n, d), jnp.float32)
    v = jax.random.normal(jax.random.key(1), (d, k), jnp.float32)
    ref = jax.jit(gram_matvec_ref)
    us_ref = time_fn(lambda: jax.block_until_ready(ref(x, v)))
    # TPU projection: 1 HBM pass over X vs 2 for the two-einsum form
    flops = 4.0 * n * d * k
    bytes_one_pass = n * d * 4 + 2 * d * k * 4
    bytes_two_pass = 2 * n * d * 4 + n * k * 8 + 2 * d * k * 4
    t_kernel = max(flops / PEAK_FLOPS, bytes_one_pass / HBM_BW) * 1e6
    t_naive = max(flops / PEAK_FLOPS, bytes_two_pass / HBM_BW) * 1e6
    record(
        "kernel_gram_matvec",
        us_ref,
        f"v5e_roofline_ratio={t_naive / t_kernel:.2f};host_ref_us={us_ref:.0f}",
    )


def bench_dsag_update() -> None:
    p, n = 8, 1 << 20
    g = jax.random.normal(jax.random.key(2), (p, n), jnp.bfloat16)
    c = jax.random.normal(jax.random.key(3), (p, n), jnp.bfloat16)
    h = jnp.zeros((n,), jnp.float32)
    mask = jnp.ones((p,))
    ref = jax.jit(dsag_update_ref)
    us_ref = time_fn(lambda: jax.block_until_ready(ref(g, c, h, mask)))
    # memory-bound: fused = read g+c+h, write c+h; naive adds a second c pass
    fused = (2 * p * n * 2 + 2 * n * 4) + (p * n * 2 + n * 4)
    naive = fused + p * n * 2 * 2
    record(
        "kernel_dsag_update",
        us_ref,
        f"v5e_roofline_ratio={naive / fused:.2f};host_ref_us={us_ref:.0f}",
    )


def bench_flash_attention() -> None:
    b, h, s, d = 1, 4, 1024, 128
    q = jax.random.normal(jax.random.key(4), (b, h, s, d), jnp.bfloat16)
    k = jax.random.normal(jax.random.key(5), (b, h, s, d), jnp.bfloat16)
    v = jax.random.normal(jax.random.key(6), (b, h, s, d), jnp.bfloat16)
    ref = jax.jit(lambda q, k, v: flash_attention_ref(q, k, v, causal=True))
    us_ref = time_fn(lambda: jax.block_until_ready(ref(q, k, v)))
    flops = 4.0 * b * h * s * s * d
    bytes_flash = 3 * b * h * s * d * 2 + b * h * s * d * 2
    bytes_naive = bytes_flash + 2 * b * h * s * s * 4  # S^2 scores round-trip
    t_flash = max(flops / PEAK_FLOPS, bytes_flash / HBM_BW)
    t_naive = max(flops / PEAK_FLOPS, bytes_naive / HBM_BW)
    record(
        "kernel_flash_attention",
        us_ref,
        f"v5e_roofline_ratio={t_naive / t_flash:.2f};host_ref_us={us_ref:.0f}",
    )


def run_all() -> None:
    bench_gram_matvec()
    bench_dsag_update()
    bench_flash_attention()
