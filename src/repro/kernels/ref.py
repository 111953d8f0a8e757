"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax


def gram_matvec_ref(x: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """X^T (X V) in fp32 accumulation.  x: [n, d], v: [d, k] -> [d, k]."""
    xv = jnp.einsum("nd,dk->nk", x.astype(jnp.float32), v.astype(jnp.float32))
    return jnp.einsum("nd,nk->dk", x.astype(jnp.float32), xv)


def dsag_update_ref(
    g: jnp.ndarray,  # [p, n] fresh per-group gradients
    c: jnp.ndarray,  # [p, n] cache slots
    h: jnp.ndarray,  # [n] running sum
    mask: jnp.ndarray,  # [p] float (0/1)
):
    """Fused DSAG cache update:  h += Σ_i m_i (g_i - c_i);  c_i <- m_i?g_i:c_i.
    Returns (new_c, new_h)."""
    gf = g.astype(jnp.float32)
    cf = c.astype(jnp.float32)
    m = mask.astype(jnp.float32)[:, None]
    new_c = m * gf + (1.0 - m) * cf
    new_h = h.astype(jnp.float32) + (m * (gf - cf)).sum(axis=0)
    return new_c.astype(c.dtype), new_h


def tree_sum(a, axis: int):
    """Sum over ``axis`` in a fixed pairwise order; the axis is kept at
    length 1.

    XLA:CPU picks the association of a reduction over a non-minor axis
    per shape and per fusion: the pad rows of a ``[G, pad, d]`` batch and
    the same task's rows reduced alone inside a Pallas program can round
    differently.  So the block subgradients sum their rows with an
    explicit halving tree of elementwise adds, which fixes every output's
    association whatever the batch, the fusion or the backend.  (The
    reductions over the minor ``d`` axis stay ``jnp.sum``: XLA rewrites a
    halving tree of lane slices back into a reduce in some fusions and not
    in others, while its minor-axis reduce is batch-invariant as is.)  An
    odd length folds its last element into element 0 (adding 0.0
    elsewhere).
    """
    m = a.shape[axis]
    while m > 1:
        h = m // 2
        s = lax.slice_in_dim(a, 0, h, axis=axis) + lax.slice_in_dim(a, h, 2 * h, axis=axis)
        if m % 2:
            first = lax.broadcasted_iota(jnp.int32, s.shape, axis) == 0
            s = s + jnp.where(first, lax.slice_in_dim(a, 2 * h, m, axis=axis), 0.0)
        a, m = s, h
    return a


def block_sub_pca_ref(x, Vb, starts, widths, pad_width: int):
    """§3 PCA block subgradients, clip-gather jnp form (block_sub twin).

    x: [n, d], Vb: [G, d, k], starts/widths: [G] -> [G, d, k].  The
    expression ``PCAProblem.sub_blocks`` evaluates (pre-batch-padding).
    Each of the k output columns is one elementwise multiply and
    reduction over ``d``, then a :func:`tree_sum` over the pad rows (the
    logreg form below), rather than a batched matmul: XLA lowers a ``[G, pad, d]``
    batched product with an accumulation order that depends on G, whereas
    this form gives every row the same bits whatever else shares the
    batch.
    """
    n = x.shape[0]
    idx = jnp.clip(starts[:, None] - 1 + jnp.arange(pad_width)[None, :], 0, n - 1)
    xg = x[idx]  # [G, pad, d]
    mask = (jnp.arange(pad_width)[None, :] < widths[:, None]).astype(x.dtype)
    xg = xg * mask[:, :, None]
    cols = []
    for j in range(Vb.shape[2]):
        xv = jnp.sum(xg * Vb[:, None, :, j], axis=2, keepdims=True)  # [G, pad, 1]
        cols.append(-tree_sum(xg * xv, axis=1)[:, 0])  # [G, d]
    return jnp.stack(cols, axis=2)


def block_sub_logreg_ref(x, y, Vb, starts, widths, pad_width: int):
    """§3 logreg block subgradients, clip-gather jnp form (block_sub twin).

    x: [n, d], y: [n], Vb: [G, d] -> [G, d].  The reduce-based
    (batch-invariant) expression ``LogisticRegressionProblem.sub_blocks``
    evaluates (pre-batch-padding).
    """
    n = x.shape[0]
    idx = jnp.clip(starts[:, None] - 1 + jnp.arange(pad_width)[None, :], 0, n - 1)
    xg = x[idx]  # [G, pad, d]
    yg = y[idx] * (jnp.arange(pad_width)[None, :] < widths[:, None]).astype(y.dtype)
    z = yg * jnp.sum(xg * Vb[:, None, :], axis=2)
    s = jax.nn.sigmoid(-z)
    return -tree_sum(xg * (yg * s)[:, :, None], axis=1)[:, 0] / n


def grid_cache_update_ref(
    valid_r, slot_r, tag_r, vals_r, sums, values, iters, covered, rejected,
    slot_width,
):
    """§5 grid-cache rank walk, pure-jnp form (cache_events twin).

    Rank-ordered ``[S, R]`` event tables applied to ``[S, E, F]`` cache
    state via the masked-scatter ``fori_loop`` the fused engine's XLA
    path uses; returns ``(sums, values, iters, covered, rejected)``.
    """
    S, R = valid_r.shape
    s_idx = jnp.arange(S)

    def rank_body(j, state):
        sums, values, iters, covered, rejected = state
        valid = valid_r[:, j]
        slot = slot_r[:, j]
        tag = tag_r[:, j]
        v = vals_r[:, j]
        cur_it = iters[s_idx, slot]
        active = cur_it >= 0
        dom = active & (cur_it >= tag)
        acc = valid & ~dom
        rej = valid & dom
        old = values[s_idx, slot]
        delta = v - jnp.where(active[:, None], old, 0.0)
        sums = jnp.where(acc[:, None], sums + delta, sums)
        values = values.at[s_idx, slot].set(jnp.where(acc[:, None], v, old))
        iters = iters.at[s_idx, slot].set(jnp.where(acc, tag, cur_it))
        covered = covered + jnp.where(acc & ~active, slot_width[slot], 0)
        rejected = rejected + rej.astype(rejected.dtype)
        return sums, values, iters, covered, rejected

    return jax.lax.fori_loop(
        0, R, rank_body, (sums, values, iters, covered, rejected)
    )


def flash_attention_ref(
    q: jnp.ndarray,  # [b, h, sq, d]
    k: jnp.ndarray,  # [b, h, sk, d]
    v: jnp.ndarray,  # [b, h, sk, d]
    *,
    causal: bool = True,
) -> jnp.ndarray:
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
    s = s * scale
    if causal:
        sq, sk = q.shape[2], k.shape[2]
        mask = jnp.arange(sk)[None, :] <= (jnp.arange(sq)[:, None] + (sk - sq))
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)
