"""Jittable §6 load balancing: Algorithm 1 as pure JAX, shared by engines.

Everything the load balancer computes with floats lives in this module as
*traceable* functions — profiler window moments (§6.1), the gamma what-if
draws and batched trace replay behind the contribution estimate ``h``
(§6.2), the equalize / restore / slack hill-climb of Algorithm 1, the
§6.3 publication gate, and the Algorithm-2 alignment walk.  The host
:class:`~repro.lb.optimizer.LoadBalanceOptimizer` (used by the scalar
``TrainingSimulator`` and the batched host convergence engine) calls
jitted wrappers of these functions; the fused ``jax.lax.scan`` engine
(:mod:`repro.experiments.fused`) traces the same functions inline in its
scan body.  Bit-exactness of ``scan == host == scalar`` for §6 configs
rests on that sharing plus the CPU batch-invariance of row-independent
kernels that the repo already pins empirically (``tests/test_fused.py``,
``tests/test_lb_scan.py``).

Two deliberate reformulations versus the pre-jittable optimizer:

* **The p-ladder.**  Algorithm 1 no longer takes ±1% steps over all of
  ``[1, n_j]``; it climbs a finite geometric ladder of subpartition
  counts (:func:`repro.lb.partitioner.build_p_ladder`).  That bounds the
  set of intervals any repartition can produce, which is what lets the
  fused engine pre-allocate the §5 cache's slot universe at static
  shapes.  The equalize phase snaps its continuous solution down to the
  ladder; comm-bound workers get the ladder's top rung (least work)
  instead of exactly ``n_j`` subpartitions.
* **Wilson–Hilferty what-if draws.**  The what-if traces behind ``h``
  are gamma draws via the Wilson–Hilferty cube transform of one fixed
  ``[N, K]`` standard-normal draw per optimizer call (key derived from
  the optimizer seed), every scenario transforming the same base draw
  with its own moments — mirroring the host implementation that
  re-seeded ``default_rng(seed)`` per scenario, making a scenario's
  draws depend only on its own moments (never on its row position or on
  which scenarios share the batch), and keeping the estimator a fixed
  elementwise expression instead of a rejection loop (``jax.random.gamma``
  is ~1000x slower than the transform at the 100-worker scale, and
  Algorithm 1 re-estimates h every hill-climb round).

All hill-climb state updates are masked by per-scenario ``active`` flags,
so a whole ``[S]`` batch balances in one call and inactive rows pass
through untouched — the scalar path is literally the ``S = 1`` slice.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# Algorithm-1 constants shared by the host optimizer defaults and the
# fused-scan static spec (both must agree for cross-engine bit-exactness).
H_TOLERANCE = 0.01
SIM_ITERATIONS = 100
MAX_ROUNDS = 200
IMPROVEMENT_THRESHOLD = 0.10
#: §6.1 moving-window width (seconds) used by every engine's profiler view
PROFILER_WINDOW = 10.0


# ---------------------------------------------------------------------------
# §6.1 — profiler window moments
# ---------------------------------------------------------------------------


def window_moments(t_rec, comm, comp, valid, now, window, since=None):
    """Moving-window mean/variance per worker (the §6.1 profiler view).

    ``t_rec``/``comm``/``comp``/``valid`` are ``[..., N, T]`` buffers
    indexed by the *iteration that started the task* (one slot per task,
    written when the task's completion is observed); ``now`` is ``[...]``
    per scenario.  A sample is in-window iff ``t_rec >= now - window`` —
    identical to the deque profiler's front eviction because per-worker
    completion times are monotone in the task's iteration.  ``since``
    (``[...]`` per scenario, optional) additionally drops samples recorded
    before it — the churn re-profiling cutoff: after a fleet change the
    optimizer must not mix moments from the previous regime, so engines
    pass the latest churn-boundary time.  ``since = -inf`` is the static
    behaviour.  Returns ``(e_comm, v_comm, e_comp, v_comp, counts)`` with
    the single-sample variance floored to 1e-12 like
    ``LatencyProfiler.stats``.
    """
    cutoff = now[..., None, None] - window
    if since is not None:
        cutoff = jnp.maximum(cutoff, since[..., None, None])
    in_win = valid & (t_rec >= cutoff)
    cnt = jnp.sum(in_win, axis=-1)
    cnt_f = jnp.maximum(cnt, 1).astype(comm.dtype)

    def mean_var(x):
        mean = jnp.sum(jnp.where(in_win, x, 0.0), axis=-1) / cnt_f
        d = x - mean[..., None]
        var = jnp.sum(jnp.where(in_win, d * d, 0.0), axis=-1) / cnt_f
        return mean, jnp.where(cnt > 1, var, 1e-12)

    e_comm, v_comm = mean_var(comm)
    e_comp, v_comp = mean_var(comp)
    return e_comm, v_comm, e_comp, v_comp, cnt


# ---------------------------------------------------------------------------
# §6.2 — objective and the h(p') contribution estimate
# ---------------------------------------------------------------------------


def e_total(e_comm, e_comp, p, p_new):
    """Linearised expected total latency e'_{X,i} (paper §6.2)."""
    return e_comm + e_comp * p / p_new


def objective(e_x):
    """max/min ratio of expected per-worker total latency (Eq. 7)."""
    lo = jnp.maximum(e_x.min(axis=-1), 1e-12)
    return e_x.max(axis=-1) / lo


def _wilson_hilferty_gamma(z, shape, scale):
    """Gamma(shape, scale) draws from standard-normal draws ``z``.

    The Wilson–Hilferty cube transform: X ≈ shape·scale·(1 − 1/(9·shape)
    + z·sqrt(1/(9·shape)))³ — excellent for the moderate-to-large shapes
    the profiler produces (shape = 1/cv² ≈ 10–100) and, unlike rejection
    sampling, a fixed elementwise expression: cheap inside the scan, and
    the draw for a given (worker, iteration) position depends only on
    that position's normal draw and the scenario's own moments.  Clamped
    to a small positive floor (the cube can graze zero for tiny shapes).
    """
    c = 1.0 / (9.0 * shape)
    x = shape * scale * (1.0 - c + z * jnp.sqrt(c)) ** 3
    return jnp.maximum(x, 1e-12)


def _normal_base(key, N: int, K: int, dtype):
    """The two ``[N, K]`` standard-normal base draws (comm, comp).

    They depend only on the key and the shape, so Algorithm 1 draws them
    once per call and every h(p') round reuses them: the float64 normal
    sampler is most of what one h evaluation costs to compile on a TPU,
    where float64 is emulated, and the hill-climb holds four evaluations.
    """
    k_comm, k_comp = jax.random.split(key)
    return (
        jax.random.normal(k_comm, (N, K), dtype=dtype),
        jax.random.normal(k_comp, (N, K), dtype=dtype),
    )


def _draw_what_if(z, e_y, v_y, e_z, v_z):
    """[S, N, K] what-if latency draws (comm, comp).

    One ``[N, K]`` standard-normal base draw per component (``z``, from
    :func:`_normal_base`), shared by every scenario (the batched
    counterpart of the host optimizer's historical per-scenario
    ``default_rng(seed)`` streams, which also shared one underlying
    uniform stream), pushed through the Wilson–Hilferty gamma transform
    with each scenario's own moments.  A scenario's draws therefore depend
    only on its parameters — never on its row position or on which
    scenarios share the batch.
    """
    z_comm, z_comp = z
    comm = _wilson_hilferty_gamma(
        z_comm[None], (e_y * e_y / v_y)[:, :, None], (v_y / e_y)[:, :, None]
    )
    comp = _wilson_hilferty_gamma(
        z_comp[None], (e_z * e_z / v_z)[:, :, None], (v_z / e_z)[:, :, None]
    )
    return comm, comp


def _what_if_replay(comm, comp, w: int, K: int, margin: float, alive=None):
    """Participation of each worker over K what-if §4.2 iterations.

    The same idle/busy + w-th order statistic + margin-deadline algebra as
    :func:`repro.experiments.sweep.replay_batch`, traced in jnp (no
    bursts, unit loads — the what-if draws already carry the load).
    ``alive`` ([S, N] bool, optional) is the churn liveness mask at the
    optimizer call: dead workers' draws arrive pre-masked to +inf (see
    :func:`estimate_h`) so their participation is 0, and the order
    statistic waits for ``w_eff = min(w, #alive)`` of the living fleet —
    the what-if mirror of the engines' churn algebra."""
    # deferred: repro.cluster.simulator imports repro.lb.optimizer, which
    # imports this module — a top-level import would be circular
    from repro.cluster.simulator import margin_deadline, task_finish_time

    S, N, _ = comm.shape
    if alive is not None:
        w_eff = jnp.minimum(w, jnp.sum(alive, axis=1)).astype(jnp.int64)

    def body(carry, _):
        free_at, iter_end, draw_idx, part = carry
        idle = free_at <= iter_end[:, None]
        start = jnp.where(idle, iter_end[:, None], free_at)
        comm_d = jnp.take_along_axis(comm, draw_idx[:, :, None], axis=2)[:, :, 0]
        comp_d = jnp.take_along_axis(comp, draw_idx[:, :, None], axis=2)[:, :, 0]
        finish = task_finish_time(start, comp_d, comm_d)
        if alive is None:
            tau_w = jnp.sort(finish, axis=1)[:, w - 1]
        else:
            tau_w = jnp.take_along_axis(
                jnp.sort(finish, axis=1), w_eff[:, None] - 1, axis=1
            )[:, 0]
        if margin > 0.0:
            deadline = margin_deadline(tau_w, iter_end, margin)
        else:
            deadline = tau_w
        started = idle | (free_at <= deadline[:, None])
        fresh = started & (finish <= deadline[:, None])
        stale_ev = jnp.where((~idle) & (free_at <= deadline[:, None]), free_at, -jnp.inf)
        fresh_ev = jnp.where(fresh, finish, -jnp.inf)
        iter_end = jnp.maximum(
            jnp.maximum(stale_ev.max(axis=1), fresh_ev.max(axis=1)), tau_w
        )
        free_at = jnp.where(started, finish, free_at)
        draw_idx = draw_idx + started
        part = part + fresh
        return (free_at, iter_end, draw_idx, part), None

    carry0 = (
        jnp.zeros((S, N), dtype=comm.dtype),
        jnp.zeros((S,), dtype=comm.dtype),
        jnp.zeros((S, N), dtype=jnp.int64),
        jnp.zeros((S, N), dtype=jnp.int64),
    )
    (_, _, _, part), _ = jax.lax.scan(body, carry0, None, length=K)
    return part / max(K, 1)


def estimate_h(
    e_comm, v_comm, e_comp, v_comp, n_j, p_cur, p_new, *, w: int, margin: float,
    key, K: int, alive=None,
):
    """h(p') for every scenario via linearised what-if trace replay.

    With ``alive`` ([S, N] bool), dead workers' what-if comm draws are
    masked to +inf before the replay: they never finish, contribute u = 0,
    and the order statistic waits for ``w_eff`` of the living fleet.  The
    denominator keeps the full dataset size n — a death lowers h (its data
    really is uncovered), which is exactly the signal Algorithm 1 reacts
    to.  An all-True mask is value-identical to ``alive=None``.
    """
    z = _normal_base(key, e_comm.shape[-1], K, e_comm.dtype)
    return _estimate_h_from(
        z, e_comm, v_comm, e_comp, v_comp, n_j, p_cur, p_new,
        w=w, margin=margin, K=K, alive=alive,
    )


def _estimate_h_from(
    z, e_comm, v_comm, e_comp, v_comp, n_j, p_cur, p_new, *, w: int,
    margin: float, K: int, alive=None,
):
    """:func:`estimate_h` on given base draws ``z`` (see :func:`_normal_base`)."""
    e_y = jnp.maximum(e_comm, 1e-12)
    v_y = jnp.maximum(v_comm, 1e-18)
    ratio = p_cur / p_new
    e_z = jnp.maximum(e_comp * ratio, 1e-12)
    v_z = jnp.maximum(v_comp * ratio * ratio, 1e-18)
    comm, comp = _draw_what_if(z, e_y, v_y, e_z, v_z)
    if alive is not None:
        comm = jnp.where(alive[:, :, None], comm, jnp.inf)
    u = _what_if_replay(comm, comp, w, K, margin, alive=alive)
    n_tot = jnp.sum(n_j, axis=1)
    return jnp.sum(u * n_j / (p_new * n_tot[:, None]), axis=1)


# ---------------------------------------------------------------------------
# The p-ladder view
# ---------------------------------------------------------------------------


def ladder_tables(ladder: tuple[int, ...], n_j):
    """(eff [.., N, L], idx_cap [.., N]) — the per-worker effective ladder.

    ``eff[.., i, l] = min(ladder[l], n_j[.., i])`` is strictly increasing
    up to ``idx_cap`` (the last index before the ladder saturates at the
    worker's sample count); hill-climb indices are clipped to
    ``[0, idx_cap]`` so every move changes the value.
    """
    raw = jnp.asarray(ladder, dtype=n_j.dtype)
    eff = jnp.minimum(raw[..., None, :], n_j[..., None])
    idx_cap = jnp.minimum(
        jnp.sum(raw[..., None, :] < n_j[..., None], axis=-1), len(ladder) - 1
    )
    return eff, idx_cap


def ladder_value(eff, idx):
    """eff[.., i, idx[.., i]] — the p value at each worker's ladder index."""
    return jnp.take_along_axis(eff, idx[..., None], axis=-1)[..., 0]


def snap_to_ladder(eff, idx_cap, v):
    """Index of the largest ladder value <= v (clipped into [0, idx_cap])."""
    cnt = jnp.sum(eff <= v[..., None], axis=-1)
    return jnp.clip(cnt - 1, 0, idx_cap)


# ---------------------------------------------------------------------------
# Algorithm 1 on the ladder
# ---------------------------------------------------------------------------


def algorithm1(
    p_cur, e_comm, v_comm, e_comp, v_comp, n_j, h_min, active, *,
    ladder: tuple[int, ...], w: int, margin: float, key,
    K: int = SIM_ITERATIONS, h_tol: float = H_TOLERANCE,
    max_rounds: int = MAX_ROUNDS, alive=None,
):
    """Equalize / restore-contribution / spend-slack (paper Algorithm 1).

    All arrays are ``[S, N]`` (``h_min``/``active`` are ``[S]``); rows
    with ``active`` False pass through untouched.  Returns
    ``(idx_new, p_new, h_min, last_h)`` where ``idx_new`` are ladder
    indices, ``p_new`` their float values, and ``last_h`` is h at the
    returned vector (the slack phase backs violating steps out together
    with their h, so the report always describes the returned p').

    ``alive`` ([S, N] bool, optional) restricts the hill-climb to the
    living fleet: dead workers are excluded from the equalize target and
    the restore/slack argmax/argmin (±inf masks), their p is frozen at
    ``p_cur``, and the what-if h treats them as never finishing.  An
    all-True mask takes the same float path as ``alive=None``; passing
    ``None`` keeps the traced jaxpr byte-identical to the static one.
    """
    S, N = p_cur.shape
    rows = jnp.arange(S)
    eff, idx_cap = ladder_tables(ladder, n_j)
    z = _normal_base(key, N, K, e_comm.dtype)

    def h_of(p_new):
        return _estimate_h_from(
            z, e_comm, v_comm, e_comp, v_comp, n_j, p_cur, p_new,
            w=w, margin=margin, K=K, alive=alive,
        )

    def only_alive(x):  # mask for max/argmax reductions
        return x if alive is None else jnp.where(alive, x, -jnp.inf)

    # h_min = h(p_0) where not yet established (NaN)
    unset = jnp.isnan(h_min) & active
    h0 = jax.lax.cond(
        jnp.any(unset), h_of, lambda p: jnp.zeros((S,), p_cur.dtype), p_cur
    )
    h_min = jnp.where(unset, h0, h_min)

    # --- equalize total latency against the slowest worker ---
    e_x = e_total(e_comm, e_comp, p_cur, p_cur)
    slowest = jnp.argmax(only_alive(e_x), axis=1)
    target = (
        e_comm[rows, slowest]
        + e_comp[rows, slowest] * p_cur[rows, slowest] / p_cur[rows, slowest]
    )
    denom = target[:, None] - e_comm
    safe = jnp.where(denom > 0, denom, 1.0)
    balanced = jnp.maximum(jnp.floor(e_comp * p_cur / safe), 1.0)
    # comm-bound workers (denom <= 0) get the ladder's least-work rung
    cand = jnp.where(denom <= 0, ladder_value(eff, idx_cap), balanced)
    cand = jnp.clip(cand, 1.0, n_j)
    idx = snap_to_ladder(eff, idx_cap, cand)
    if alive is not None:
        # dead workers keep their current rung (their p is frozen)
        idx = jnp.where(alive, idx, snap_to_ladder(eff, idx_cap, p_cur))
    h = h_of(ladder_value(eff, idx))

    # --- restore contribution: give the fastest workers more work ---
    def restore_cond(st):
        _, _, act, r = st
        return jnp.any(act) & (r < max_rounds)

    def restore_body(st):
        idx, h, act, r = st
        e_now = e_total(e_comm, e_comp, p_cur, ladder_value(eff, idx))
        valid = idx > 0  # one rung down = strictly more work per task
        if alive is not None:
            valid = valid & alive
        order = jnp.argsort(e_now, axis=1, stable=True)
        valid_ord = jnp.take_along_axis(valid, order, axis=1)
        movable = valid_ord.any(axis=1)
        pick = order[rows, jnp.argmax(valid_ord, axis=1)]
        act = act & movable
        idx = idx.at[rows, pick].add(jnp.where(act, -1, 0))
        h_step = h_of(ladder_value(eff, idx))
        h = jnp.where(act, h_step, h)
        act = act & (h < h_min * (1.0 - h_tol))
        return idx, h, act, r + 1

    act0 = active & (h < h_min * (1.0 - h_tol))
    idx, h, _, _ = jax.lax.while_loop(restore_cond, restore_body, (idx, h, act0, 0))

    # --- spend slack: reduce the slowest workers' load while h holds ---
    def slack_cond(st):
        _, _, act, r = st
        return jnp.any(act) & (r < max_rounds)

    def slack_body(st):
        idx, h, act, r = st
        e_now = e_total(e_comm, e_comp, p_cur, ladder_value(eff, idx))
        slowest = jnp.argmax(only_alive(e_now), axis=1)
        act = act & (idx[rows, slowest] < idx_cap[rows, slowest])
        prev_idx, prev_h = idx, h
        idx = idx.at[rows, slowest].add(jnp.where(act, 1, 0))
        h_step = h_of(ladder_value(eff, idx))
        h = jnp.where(act, h_step, h)
        viol = act & (h < 0.99 * h_min)
        # back out the violating step — and its h with it, so the reported
        # h describes the returned p', not the rejected candidate
        idx = jnp.where(viol[:, None], prev_idx, idx)
        h = jnp.where(viol, prev_h, h)
        act = act & ~viol
        return idx, h, act, r + 1

    act0 = active & (h >= 0.99 * h_min)
    idx, h, _, _ = jax.lax.while_loop(slack_cond, slack_body, (idx, h, act0, 0))
    return idx, ladder_value(eff, idx), h_min, h


def should_publish(p_cur, p_new, e_comm, e_comp, threshold: float, alive=None):
    """[S] bool: Eq.-(7) objective improves by > threshold (paper §6.3).

    With ``alive``, the max/min latency ratio is taken over the living
    fleet only — a dead worker's (frozen) expected latency must not gate
    publication for the workers that can still act on it."""
    ex_cur = e_total(e_comm, e_comp, p_cur, p_cur)
    ex_new = e_total(e_comm, e_comp, p_cur, p_new)
    if alive is not None:
        hi = jnp.where(alive, ex_cur, -jnp.inf)
        lo = jnp.where(alive, ex_cur, jnp.inf)
        cur = hi.max(axis=-1) / jnp.maximum(lo.min(axis=-1), 1e-12)
        hi = jnp.where(alive, ex_new, -jnp.inf)
        lo = jnp.where(alive, ex_new, jnp.inf)
        new = hi.max(axis=-1) / jnp.maximum(lo.min(axis=-1), 1e-12)
    else:
        cur = objective(ex_cur)
        new = objective(ex_new)
    return new < cur * (1.0 - threshold)


def lb_update(
    p_cur, e_comm, v_comm, e_comp, v_comp, n_j, h_min, active, *,
    ladder: tuple[int, ...], w: int, margin: float, key,
    K: int = SIM_ITERATIONS, h_tol: float = H_TOLERANCE,
    max_rounds: int = MAX_ROUNDS, threshold: float = IMPROVEMENT_THRESHOLD,
    alive=None,
):
    """One §6 optimizer round: Algorithm 1 + the publication gate.

    Returns ``(p_new [S, N] int64, h_min [S], last_h [S], publish [S])``
    with ``h_min`` updated only for active rows and ``publish`` False for
    inactive ones.  ``alive`` applies the churn masking described on
    :func:`algorithm1`; dead workers' published p equals their current p.
    """
    idx, p_new_f, h_min_out, last_h = algorithm1(
        p_cur, e_comm, v_comm, e_comp, v_comp, n_j, h_min, active,
        ladder=ladder, w=w, margin=margin, key=key, K=K, h_tol=h_tol,
        max_rounds=max_rounds, alive=alive,
    )
    h_min_out = jnp.where(active, h_min_out, h_min)
    pub = should_publish(p_cur, p_new_f, e_comm, e_comp, threshold, alive=alive) & active
    p_out = jnp.maximum(p_new_f, 1.0).astype(jnp.int64)
    p_out = jnp.where(active[:, None], p_out, p_cur.astype(jnp.int64))
    if alive is not None:
        p_out = jnp.where(alive, p_out, p_cur.astype(jnp.int64))
    return p_out, h_min_out, last_h, pub


# ---------------------------------------------------------------------------
# Algorithm 2 — vectorized alignment walk (exact integer arithmetic)
# ---------------------------------------------------------------------------


def _p_start_j(n, p, i):
    return (i - 1) * n // p + 1


def _p_trans_j(n, p, p_new, k):
    s = _p_start_j(n, p, k) * p_new
    return (s + n - 1) // n  # ceil for positive ints


def align_batch(n, p, p_new, k, needs):
    """Vectorized Algorithm-2 walk (``repro.lb.partitioner._align``).

    ``n``/``p``/``p_new``/``k`` are int arrays (``n`` broadcastable);
    entries with ``needs`` False are returned unchanged.  Integer
    arithmetic only, so the result is exactly the scalar walk's.
    """
    one = jnp.ones_like(k)
    n = jnp.broadcast_to(n, k.shape)
    k_new = jnp.where(needs, _p_trans_j(n, p, p_new, k), k)

    def aligned(kk, kn):
        return _p_start_j(n, p_new, kn) == _p_start_j(n, p, kk)

    done = (~needs) | aligned(k, k_new)

    def cond(st):
        return jnp.any(~st[2])

    def body(st):
        kk, kn, dn = st
        kn2 = jnp.where(dn, kn, kn - 1)
        fb = (~dn) & (kn2 < 1)  # guaranteed-aligned (1, 1) fallback
        kk2 = jnp.where(fb, one, jnp.where(dn, kk, _p_trans_j(n, p_new, p, kn2)))
        kn3 = jnp.where(fb, one, kn2)
        dn2 = dn | fb | aligned(kk2, kn3)
        return kk2, kn3, dn2

    k, k_new, _ = jax.lax.while_loop(cond, body, (k, k_new, done))
    return k, k_new


# ---------------------------------------------------------------------------
# Jitted entry points for the host paths
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=64)
def _lb_update_jitted(ladder, w, K, h_tol, max_rounds, threshold, margin,
                      with_alive=False):
    if with_alive:

        def f(p_cur, e_comm, v_comm, e_comp, v_comp, n_j, h_min, active, key,
              alive):
            return lb_update(
                p_cur, e_comm, v_comm, e_comp, v_comp, n_j, h_min, active,
                ladder=ladder, w=w, margin=margin, key=key, K=K, h_tol=h_tol,
                max_rounds=max_rounds, threshold=threshold, alive=alive,
            )

    else:

        def f(p_cur, e_comm, v_comm, e_comp, v_comp, n_j, h_min, active, key):
            return lb_update(
                p_cur, e_comm, v_comm, e_comp, v_comp, n_j, h_min, active,
                ladder=ladder, w=w, margin=margin, key=key, K=K, h_tol=h_tol,
                max_rounds=max_rounds, threshold=threshold,
            )

    return jax.jit(f)


@functools.lru_cache(maxsize=8)
def _window_moments_jitted(window, with_since=False):
    if with_since:

        def f(t_rec, comm, comp, valid, now, since):
            return window_moments(t_rec, comm, comp, valid, now, window, since)

    else:

        def f(t_rec, comm, comp, valid, now):
            return window_moments(t_rec, comm, comp, valid, now, window)

    return jax.jit(f)


@functools.lru_cache(maxsize=64)
def _estimate_h_jitted(w, K, margin):
    def f(e_comm, v_comm, e_comp, v_comp, n_j, p_cur, p_new, key):
        return estimate_h(
            e_comm, v_comm, e_comp, v_comp, n_j, p_cur, p_new,
            w=w, margin=margin, key=key, K=K,
        )

    return jax.jit(f)


@functools.lru_cache(maxsize=8)
def _should_publish_jitted(threshold):
    def f(p_cur, p_new, e_comm, e_comp):
        return should_publish(p_cur, p_new, e_comm, e_comp, threshold)

    return jax.jit(f)
