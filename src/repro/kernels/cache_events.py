"""Pallas kernel for the §5 grid-cache event application.

Backs ``fused._apply_cache_events`` (cache mode ``"grid"`` — disjoint
fixed partitions, no §6 ladder) when the fused engine runs with
``EngineConfig(kernel_backend="pallas")``.  The XLA form walks event
ranks with a ``fori_loop`` whose every trip scatters into the full
``[S, E, ...]`` value table and re-reads it; here the whole walk is one
``pallas_call`` with grid ``(S,)`` — per scenario, the value/iteration
tables live in the program's output block, the running sums ride a
``fori_loop`` carry, and each rank touches exactly one table row via
dynamic load/store.  That fuses the §5 value-table write and
running-sum update into a single pass over the tables (the
``dsag_update.py`` fusion, generalized to rank-ordered events).

Bit-exactness: events arrive pre-sorted (the caller ranks them with the
same stable argsort + gathers ``_apply_cache_events_lb`` uses — pure
data movement), and each rank applies the literally identical float
expressions as the XLA loop body in the same per-scenario order, so the
results match the XLA path bit for bit (pinned by tests and the bench
kernel-backend tier).

Dtypes are taken from the operands (the engine's cache state is
float64/int64); interpret mode on the CPU executes them exactly.  XLA:TPU
refuses 64-bit operands in a Pallas call (and the ``(1, R)`` / ``(1,)``
blocks are not tile-aligned), so off the CPU the engine refuses this
kernel (``CAP_PALLAS_X64_STATE``) until the f32/i32 regime of ROADMAP 1.3
lands.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _grid_cache_kernel(
    valid_ref,  # [1, R] bool, rank-ordered event validity
    slot_ref,  # [1, R] int, rank-ordered slots (pre-clipped to [0, E))
    tag_ref,  # [1, R] int, rank-ordered iteration tags
    vals_ref,  # [1, R, F] float, rank-ordered event values
    sums0_ref,  # [1, F] running-sum input
    values0_ref,  # [1, E, F] value-table input
    iters0_ref,  # [1, E] iteration-table input (-1 = inactive)
    width_ref,  # [E] per-slot interval widths
    cov0_ref,  # [1] covered-rows input
    rej0_ref,  # [1] rejected-events input
    sums_ref,  # [1, F] out
    values_ref,  # [1, E, F] out
    iters_ref,  # [1, E] out
    cov_ref,  # [1] out
    rej_ref,  # [1] out
):
    R = valid_ref.shape[1]
    # seed the output tables; the rank loop then updates them in place,
    # so "current value/iteration" reads below always see the latest write
    values_ref[...] = values0_ref[...]
    iters_ref[...] = iters0_ref[...]

    def rank_body(j, carry):
        sums, covered, rejected = carry
        valid = valid_ref[0, j]
        slot = slot_ref[0, j]
        tag = tag_ref[0, j]
        v = vals_ref[0, j, :]
        cur_it = iters_ref[0, slot]
        old = values_ref[0, slot, :]
        # staleness dominance + in-place update — the same expressions as
        # the XLA rank_body in fused._apply_cache_events, scenario-local
        active = cur_it >= 0
        dom = active & (cur_it >= tag)
        acc = valid & ~dom
        rej = valid & dom
        delta = v - jnp.where(active, old, 0.0)
        sums = jnp.where(acc, sums + delta, sums)
        values_ref[0, slot, :] = jnp.where(acc, v, old)
        iters_ref[0, slot] = jnp.where(acc, tag, cur_it)
        sw = width_ref[slot]
        covered = covered + jnp.where(acc & ~active, sw, 0)
        rejected = rejected + rej.astype(rejected.dtype)
        return sums, covered, rejected

    sums, covered, rejected = jax.lax.fori_loop(
        0,
        R,
        rank_body,
        (sums0_ref[...][0], cov0_ref[...][0], rej0_ref[...][0]),
    )
    sums_ref[...] = sums[None]
    cov_ref[...] = covered[None]
    rej_ref[...] = rejected[None]


def grid_cache_update(
    valid_r: jnp.ndarray,  # [S, R] bool
    slot_r: jnp.ndarray,  # [S, R] int64, pre-clipped to [0, E)
    tag_r: jnp.ndarray,  # [S, R] int64
    vals_r: jnp.ndarray,  # [S, R, F] float64
    sums: jnp.ndarray,  # [S, F] float64
    values: jnp.ndarray,  # [S, E, F] float64
    iters: jnp.ndarray,  # [S, E] int64
    covered: jnp.ndarray,  # [S] int64
    rejected: jnp.ndarray,  # [S] int64
    slot_width: jnp.ndarray,  # [E] int64
    *,
    interpret: bool = False,
):
    """Apply rank-ordered §5 events to the grid cache in one table pass.

    Returns ``(sums, values, iters, covered, rejected)`` bit-identical to
    the XLA rank ``fori_loop`` on the same rank-ordered inputs.
    """
    S, R = valid_r.shape
    _, E, F = values.shape
    assert vals_r.shape == (S, R, F) and sums.shape == (S, F)
    row = lambda s: (s, 0)  # noqa: E731
    cube = lambda s: (s, 0, 0)  # noqa: E731
    return pl.pallas_call(
        _grid_cache_kernel,
        grid=(S,),
        in_specs=[
            pl.BlockSpec((1, R), row),
            pl.BlockSpec((1, R), row),
            pl.BlockSpec((1, R), row),
            pl.BlockSpec((1, R, F), cube),
            pl.BlockSpec((1, F), row),
            pl.BlockSpec((1, E, F), cube),
            pl.BlockSpec((1, E), row),
            pl.BlockSpec((E,), lambda s: (0,)),
            pl.BlockSpec((1,), lambda s: (s,)),
            pl.BlockSpec((1,), lambda s: (s,)),
        ],
        out_specs=[
            pl.BlockSpec((1, F), row),
            pl.BlockSpec((1, E, F), cube),
            pl.BlockSpec((1, E), row),
            pl.BlockSpec((1,), lambda s: (s,)),
            pl.BlockSpec((1,), lambda s: (s,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((S, F), sums.dtype),
            jax.ShapeDtypeStruct((S, E, F), values.dtype),
            jax.ShapeDtypeStruct((S, E), iters.dtype),
            jax.ShapeDtypeStruct((S,), covered.dtype),
            jax.ShapeDtypeStruct((S,), rejected.dtype),
        ],
        interpret=interpret,
    )(
        valid_r,
        slot_r,
        tag_r,
        vals_r,
        sums,
        values,
        iters,
        slot_width,
        covered,
        rejected,
    )
