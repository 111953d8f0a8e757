"""Pallas kernels for the §3 width-bucketed block-subgradient gather.

These back ``FusedKernels.sub_blocks`` when the fused engine runs with
``EngineConfig(kernel_backend="pallas")``: one kernel dispatch per pow2
``width_bucket``, grid ``(G,)`` over the task batch.  The data matrix
stays in ``ANY`` (HBM) space; each program DMAs its task's ``pad``-row
window into a VMEM scratch buffer once and reduces it there.

Bit-exactness contract (the reason these twins exist at all): the fused
engine's scan == host == scalar pins rest on every engine evaluating a
given width at the same static ``width_bucket`` pad with the same float
expressions.  So each program computes the same expression as the XLA
form in ``kernels/ref.py`` (per task, per output column: a lane
reduction over ``d``, then ``ref.tree_sum`` over the pad rows, whose
association is fixed by construction), and in interpret mode that
traces to the same CPU XLA ops.  Three consequences:

* the XLA path's clip-gather ``X[clip(start-1+arange(pad), 0, n-1)]``
  is replaced by a *contiguous* window DMA: within-width rows never
  clip (``stop <= n``) and rows past the width are mask-zeroed, so a
  clamped window offset plus a roll moves the same bits into place
  (``off = min(start-1, n-rows)``, roll left by ``start-1-off``, where
  ``rows`` is the pad rounded up to whole sublane tiles);
* the mask is a real ``iota < width`` comparison inside the kernel, so
  the tracelint TL003 mask-evidence walk (which recurses into
  ``pallas_call`` jaxprs) sees the same discipline as the XLA form;
* every block is tile-legal for Mosaic: the data (and logreg's labels,
  as one more column) sit in a row table padded to whole lane tiles
  (:func:`pack_rows`), operands travel as ``[G, r, d]`` with
  ``(1, r, d)`` blocks (the trailing two dims are full), and the window
  table is a flat int32 scalar prefetch.  The wrappers do the
  transposes/reshapes in XLA (bitwise no-ops).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import tree_sum


#: lane width of a TPU vreg; the window DMA needs row slices whose
#: minor dim is a whole number of lane tiles
_LANES = 128
#: sublane count of a 32-bit vreg; the in-VMEM roll needs whole sublane tiles
_SUBLANES = 8
#: largest gather width the problems route through these kernels.  Wider
#: windows (the coded bound's full-data gradient, whose width is n) take
#: the XLA form, which gives the same bits: a window of tens of thousands
#: of rows exceeds one program's VMEM and takes minutes to compile.
MAX_WINDOW_ROWS = 4096


def pack_rows(X: jnp.ndarray, y: jnp.ndarray | None = None) -> jnp.ndarray:
    """``[n, dp]`` row table the kernels DMA from: ``X``, then ``y`` as one
    more column when given, zero-padded to a multiple of 128 lanes.

    Built once per problem (the engines cache it); a bitwise copy of the
    data, so the kernels see exactly the XLA form's values.
    """
    cols = [X] if y is None else [X, y[:, None].astype(X.dtype)]
    used = sum(c.shape[1] for c in cols)
    dp = -(-used // _LANES) * _LANES
    return jnp.concatenate(cols + [jnp.zeros((X.shape[0], dp - used), X.dtype)], axis=1)


def _window_rows(n: int, pad_width: int) -> int:
    """Rows each program DMAs: the pad rounded up to whole sublane tiles
    (never more than ``n``); the kernel keeps the first ``pad_width``."""
    return min(-(-pad_width // _SUBLANES) * _SUBLANES, n)


def _window_table(starts, widths, n: int, rows: int):
    """Flat ``[3 G]`` int32 (offset, shift, width) scalar-prefetch table.

    ``offset`` is the clamped contiguous window start, ``shift`` how far
    the roll must move row 0 back into place (0 whenever the window fits
    without clamping; at most ``rows - width`` otherwise, so rolled rows
    always land in the masked tail).
    """
    starts_m1 = (starts - 1).astype(jnp.int32)
    off = jnp.minimum(starts_m1, jnp.int32(n - rows))
    shift = starts_m1 - off
    return jnp.stack([off, shift, widths.astype(jnp.int32)], axis=1).reshape(-1)


def _window(tab_ref, rows_hbm, win_ref, sem, pad_width: int):
    """DMA one task's row window into VMEM and roll it into place.

    Returns the task's ``[pad, dp]`` rows (row 0 = its first sample) and
    its ``[pad, 1]`` 1.0/0.0 mask of the rows inside its width.
    """
    g = pl.program_id(0)
    off = tab_ref[3 * g]
    shift = tab_ref[3 * g + 1]
    width = tab_ref[3 * g + 2]
    rows = win_ref.shape[0]
    copy = pltpu.make_async_copy(rows_hbm.at[pl.ds(off, rows)], win_ref, sem)
    copy.start()
    copy.wait()
    # roll left by `shift` == roll right by (rows - shift) mod rows (an
    # int32 literal: under x64 a bare 0 would be i64, which Mosaic refuses)
    right = jnp.where(shift == 0, jnp.int32(0), rows - shift)
    win = pltpu.roll(win_ref[...], right, 0)[:pad_width]
    iota = jax.lax.broadcasted_iota(jnp.int32, (pad_width, 1), 0)
    return win, (iota < width).astype(win.dtype)


def _pca_kernel(tab_ref, rows_hbm, vt_ref, o_ref, win_ref, sem, *, pad_width: int):
    win, mask = _window(tab_ref, rows_hbm, win_ref, sem, pad_width)
    vt = vt_ref[0]  # [k, d]
    xg = win[:, : vt.shape[1]] * mask
    # identical per-column reduce form to ref.block_sub_pca_ref
    cols = []
    for j in range(vt.shape[0]):
        xv = jnp.sum(xg * vt[j : j + 1, :], axis=1, keepdims=True)  # [pad, 1]
        cols.append(-tree_sum(xg * xv, axis=0))  # [1, d]
    o_ref[0] = jnp.concatenate(cols, axis=0)


def _logreg_kernel(tab_ref, rows_hbm, v_ref, o_ref, win_ref, sem, *, pad_width: int):
    win, mask = _window(tab_ref, rows_hbm, win_ref, sem, pad_width)
    v = v_ref[0]  # [1, d]
    d = v.shape[1]
    xg = win[:, :d]
    yg = win[:, d : d + 1] * mask  # labels ride in column d
    # identical reduce-based expression to ref.block_sub_logreg_ref
    z = yg * jnp.sum(xg * v, axis=1, keepdims=True)
    s = jax.nn.sigmoid(-z)
    o_ref[0] = -tree_sum(xg * (yg * s), axis=0) / rows_hbm.shape[0]


def _check_pad(n: int, pad_width: int):
    if not 1 <= pad_width <= n:
        raise ValueError(
            f"pad_width must satisfy 1 <= pad_width <= num_samples "
            f"({pad_width} vs n={n}); width_bucket never exceeds n, so this "
            f"is a caller bug"
        )


def _block_spec(rows: int, d: int):
    # int32 block indices even when traced under x64, where a bare 0 would
    # be i64 (Mosaic takes no i64)
    return pl.BlockSpec((1, rows, d), lambda g, tab: (g, g * 0, g * 0))


def _call(kernel, rows, operand, starts, widths, pad_width, interpret, name):
    """One program per task: the packed rows stay in ANY space, ``operand``
    (``[G, r, d]``) and the result travel in ``(1, r, d)`` blocks."""
    n, dp = rows.shape
    G, r, d = operand.shape
    _check_pad(n, pad_width)
    assert dp % _LANES == 0 and d < dp, (rows.shape, operand.shape)
    win = _window_rows(n, pad_width)
    return pl.pallas_call(
        functools.partial(kernel, pad_width=pad_width),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(G,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY), _block_spec(r, d)],
            out_specs=_block_spec(r, d),
            scratch_shapes=[pltpu.VMEM((win, dp), rows.dtype), pltpu.SemaphoreType.DMA],
        ),
        out_shape=jax.ShapeDtypeStruct((G, r, d), operand.dtype),
        interpret=interpret,
        name=name,
    )(_window_table(starts, widths, n, win), rows, operand)


def pca_block_sub(
    rows: jnp.ndarray,  # [n, dp] pack_rows(X): data matrix, stays in ANY/HBM
    Vb: jnp.ndarray,  # [G, d, k] per-task iterates
    starts: jnp.ndarray,  # [G] 1-indexed interval starts
    widths: jnp.ndarray,  # [G] interval widths (rows past each are masked)
    pad_width: int,
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """§3 PCA block subgradients ``-X_b^T (X_b V)`` at a static gather width.

    Pallas twin of ``PCAProblem.sub_blocks``'s body (pre-``_pad_pow2``):
    returns ``[G, d, k]`` with row ``g`` bit-identical to the XLA form.
    """
    out = _call(
        _pca_kernel, rows, jnp.swapaxes(Vb, 1, 2), starts, widths, pad_width,
        interpret, "pca_block_sub",
    )
    return jnp.swapaxes(out, 1, 2)


def logreg_block_sub(
    rows: jnp.ndarray,  # [n, dp] pack_rows(X, y): features, then labels
    Vb: jnp.ndarray,  # [G, d]
    starts: jnp.ndarray,  # [G]
    widths: jnp.ndarray,  # [G]
    pad_width: int,
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """§3 logistic-regression block subgradients at a static gather width.

    Pallas twin of ``LogisticRegressionProblem.sub_blocks``'s body
    (pre-``_pad_pow2``), keeping its reduce-based (batch-invariant) form:
    returns ``[G, d]`` with row ``g`` bit-identical to the XLA form.
    """
    out = _call(
        _logreg_kernel, rows, Vb[:, None, :], starts, widths, pad_width,
        interpret, "logreg_block_sub",
    )
    return out[:, 0, :]
