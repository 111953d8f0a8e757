"""Fused ``jax.lax.scan`` convergence engine.

The host engine (:func:`repro.experiments.convergence.run_convergence_batch`
with ``EngineConfig(kind="host")``) runs one Python iteration per training
iteration and dispatches batched kernels from it.  This module compiles the
*entire* iteration body — §4.2 event algebra, §3 trace replay, block
subgradients, the §5 cache update as masked scatters, the iterate update,
and the suboptimality evaluation — into one jittable function and scans it
over the whole run: a single XLA dispatch for a complete ``[S]``-scenario
training sweep, ready for accelerators.

Bit-exactness contract (pinned by ``tests/test_fused.py``): for every
scenario, the scan produces the same bits as the host engine and the scalar
:class:`~repro.cluster.simulator.TrainingSimulator` replaying the same
trace.  Three ingredients make that possible:

* every float expression is shared: the problems'
  :class:`~repro.core.problems.FusedKernels` are called from all three
  engines, and the event algebra mirrors
  :func:`~repro.cluster.simulator.task_finish_time` /
  :func:`~repro.cluster.simulator.margin_deadline` term by term;
* block subgradients are evaluated at the static
  :func:`~repro.core.problems.width_bucket` ladder — one kernel call per
  possible bucket, rows selected by their actual width — so a given
  (iterate, interval) is always computed at the same static shape;
* the §5 cache applies events rank by rank in per-scenario event-time
  order (an inner ``fori_loop``), preserving the host cache's float
  accumulation order bit for bit.

There is ONE per-iteration scan body (:func:`_run_scan`), parameterized by
the static :class:`_StaticSpec` along two axes:

* the **(lo, hi, slot) source** — the fixed subpartition grid for plain
  configs, or the §6 candidate after the Algorithm-2 alignment walk for
  load-balanced ones (which also carry the profiler buffers, ladder
  indices, ``h_min``/schedule state, and run the jittable Algorithm 1 of
  :mod:`repro.lb.jit_optimizer` inside the scan);
* the **cache layout** (``spec.cache_mode``):

  - ``"grid"`` — no §6: the interval set is exactly the initial
    subpartition grid, state is dense ``[S, E]``, an active exact-match
    slot is the only possible overlap (the SAG fast path).
  - ``"universe"`` — §6 with the pre-allocated ladder universe
    (:func:`repro.core.gradient_cache.build_slot_universe`): dense
    ``[S, E]`` state over every interval the p-ladder can reach, with
    the statically tabulated overlap lists driving the scalar cache's
    eviction walk.
  - ``"tiled"`` — §6 universes above the slot budget: per-worker
    *active-entry* tables of capacity
    :func:`repro.core.gradient_cache.active_slot_capacity` (the greedy
    interval-scheduling bound on simultaneously active disjoint
    intervals).  Overlaps are computed against the small active set at
    runtime from the universe's start/stop tables, so memory drops from
    ``E ≈ N * sum(ladder)`` to ``N * A`` value buffers while keeping the
    scalar walk's float order.  This is how arbitrarily large §6 configs
    stay on the scan path instead of tripping :data:`LB_MAX_SLOTS`.

Multi-device: :func:`run_convergence_scan` shards the scenario axis over a
1-D ``"data"`` mesh (:func:`repro.launch.mesh.make_scenario_mesh`) with
``shard_map`` when the :class:`~repro.experiments.engine.EngineConfig`
names devices.  Every per-scenario quantity is row-independent; the only
cross-scenario values are dynamic trip counts and ``lax.cond`` decisions
whose skipped work is an exact no-op, so per-device shards produce the
same bits as the single-device scan (pinned by ``tests/test_sharded.py``).
Uneven ``S % num_devices`` batches are edge-padded and sliced back.

Capability: :func:`scan_capability` reports whether a config runs (and
with which cache layout) as a structured
:class:`~repro.experiments.engine.EngineCapability` with stable reason
codes; the one genuinely unsupported case — an *active-entry* footprint
above the slot budget — raises
:class:`~repro.experiments.engine.EngineCapabilityError`.
"""

from __future__ import annotations

import dataclasses
import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.cluster.simulator import (
    MethodConfig,
    effective_w,
    lb_ladder_for,
    margin_deadline,
    task_finish_time,
)
from repro.core.gradient_cache import (
    SlotUniverse,
    active_slot_capacity,
    build_slot_universe,
)
from repro.core.problems import FiniteSumProblem, FusedKernels, width_bucket
from repro.experiments.engine import (
    CAP_ACTIVE_SET,
    CAP_OK,
    CAP_PALLAS_DTYPE,
    CAP_PALLAS_UNAVAILABLE,
    CAP_LB_ACCELERATOR,
    CAP_PALLAS_X64_STATE,
    CAP_TILED,
    EngineCapability,
    EngineCapabilityError,
    EngineConfig,
    as_engine_config,
)
from repro.kernels.cache_events import grid_cache_update
from repro.latency.model import FleetTraces, comp_latency_expr
from repro.lb import jit_optimizer as jlb
from repro.lb.partitioner import p_start, p_stop
from repro.precision import x64

#: default budget on densely resident §6 slot-universe entries (per-slot
#: float64 value buffers are the fused engine's memory trade-off).
#: Universes above it run with the tiled active-slot cache; only configs
#: whose *active-entry* footprint also exceeds the budget are unsupported.
#: Override per run via ``EngineConfig(slot_budget=...)``.
LB_MAX_SLOTS = 250_000

#: named scopes of the scan body's phases, so device ops read
#: ``.../<method>/while/body/<phase>/...`` in a profiler trace.  The fixed
#: prefix keeps every name apart from JAX's primitive and function names.
SCAN_PHASES = tuple(
    f"phase_{p}" for p in ("events", "subgrad", "cache", "update", "eval", "lb")
)


def _phase(name: str):
    """The named scope of one phase of the scan body (HLO metadata only)."""
    scope = f"phase_{name}"
    assert scope in SCAN_PHASES, scope
    return jax.named_scope(scope)


def guarded_comp_latency(comp_unit_draw, load, slowdown, factor):
    """The §3 latency product with its FMA-contraction seam (tracelint TL001).

    Finalizes the §3 product before the event algebra consumes it: the
    LLVM backend otherwise contracts the last multiply into the
    ``task_finish_time`` add as an FMA (skipping the intermediate
    rounding the host engine's numpy performs), which changes the final
    ULP whenever slowdown/burst factors are not exactly 1.0.
    ``max(x, 0)`` is exact for the positive latencies and is a pattern
    the contraction cannot see through (``lax.optimization_barrier`` is
    erased before LLVM and does NOT prevent this).

    Kept as a module-level function so the tracelint TL001 probe
    (``repro.analysis.lint``) exercises the exact production expression:
    it compiles this chain with and without the seam and diffs against
    an op-by-op evaluation.
    """
    return jnp.maximum(
        comp_latency_expr(comp_unit_draw, load, slowdown, factor), 0.0
    )


@dataclasses.dataclass(frozen=True)
class _StaticSpec:
    """Hashable static configuration of one fused-scan compilation."""

    name: str
    w_wait: int
    eta: float
    margin: float  # effective margin (0.0 when unused)
    comp_scale: float
    process_full: bool
    uses_cache: bool
    accepts_stale: bool
    num_iterations: int
    base_start: tuple[int, ...]
    base_stop: tuple[int, ...]
    sub_p: tuple[int, ...]  # initial (and, without §6, permanent) p_i
    buckets: tuple[int, ...]  # static width_bucket ladder, ascending
    slot_offsets: tuple[int, ...]  # per-worker first slot (grid cache)
    num_slots: int
    cache_mode: str = "none"  # "none" | "grid" | "universe" | "tiled"
    active_cap: int = 0  # per-worker entry capacity of the tiled cache
    # §6 load balancing (empty/zero for non-LB specs)
    load_balance: bool = False
    ladder: tuple[int, ...] = ()  # the p-ladder Algorithm 1 climbs
    lb_interval: float = 0.0
    lb_startup_delay: float = 0.0
    lb_margin: float = 0.0  # optimizer-input margin (= config.margin)
    lb_p0: int = 0  # the optimizer-facing initial p (config.subpartitions)
    # elastic-fleet churn (traces carry a ChurnSchedule): time-varying
    # slowdown rows + a per-iteration liveness mask.  False compiles the
    # exact pre-churn body — the churn operands are then unused.
    has_churn: bool = False
    # hot-path kernel backend: "xla" (jnp forms) or "pallas" (the
    # repro.kernels twins).  kernel_interpret is resolved eagerly by
    # prepare_scan_inputs — never read jax.default_backend() at trace
    # time (a stale-cache hazard; see kernels/ops.py) — and is part of
    # this hashable spec, hence of every jit key.
    kernel_backend: str = "xla"
    kernel_interpret: bool = True


def _possible_widths(n_local: int, p: int, full: bool) -> set:
    if full:
        return {n_local}
    return {k * n_local // p - (k - 1) * n_local // p for k in range(1, p + 1)}


def _static_spec(
    problem: FiniteSumProblem,
    config: MethodConfig,
    num_workers: int,
    num_iterations: int,
    cost_scale: float,
    universe: SlotUniverse | None = None,
    tiled: bool = False,
    active_cap: int = 0,
    has_churn: bool = False,
    kernel_backend: str = "xla",
    kernel_interpret: bool = True,
) -> _StaticSpec:
    n = problem.num_samples
    N = num_workers
    cfg = config
    base_start = tuple(p_start(n, N, i + 1) for i in range(N))
    base_stop = tuple(p_stop(n, N, i + 1) for i in range(N))
    n_local = [b - a + 1 for a, b in zip(base_start, base_stop)]
    process_full = cfg.name in ("gd", "coded")
    sub_p = tuple(min(cfg.subpartitions, nl) for nl in n_local)
    widths = set()
    for nl, p in zip(n_local, sub_p):
        widths |= _possible_widths(nl, p, process_full)
    ladder: tuple[int, ...] = ()
    if cfg.load_balance:
        ladder = lb_ladder_for(cfg, np.asarray(n_local))
        if not process_full:
            # any ladder interval's width can appear once repartitions start
            for a, b in zip(base_start, base_stop):
                nl = b - a + 1
                for raw in ladder:
                    widths |= _possible_widths(nl, min(raw, nl), False)
    buckets = tuple(sorted({width_bucket(m, n) for m in widths}))
    if cfg.uses_cache:
        if cfg.load_balance:
            assert universe is not None
            slot_offsets = (0,) * N  # slots come from the universe table
            num_slots = universe.num_slots
            cache_mode = "tiled" if tiled else "universe"
        else:
            offsets = np.concatenate([[0], np.cumsum(sub_p)])
            slot_offsets = tuple(int(o) for o in offsets[:-1])
            num_slots = int(offsets[-1])
            cache_mode = "grid"
    else:
        slot_offsets = (0,) * N
        num_slots = 0
        cache_mode = "none"
    margin_eff = cfg.margin if (cfg.uses_margin and cfg.margin > 0) else 0.0
    return _StaticSpec(
        name=cfg.name,
        w_wait=effective_w(cfg, N),
        eta=float(cfg.eta),
        margin=float(margin_eff),
        comp_scale=float(
            cost_scale * (1.0 / cfg.code_rate if cfg.name == "coded" else 1.0)
        ),
        process_full=process_full,
        uses_cache=cfg.uses_cache,
        accepts_stale=cfg.accepts_stale,
        num_iterations=num_iterations,
        base_start=base_start,
        base_stop=base_stop,
        sub_p=sub_p,
        buckets=buckets,
        slot_offsets=slot_offsets,
        num_slots=num_slots,
        cache_mode=cache_mode,
        active_cap=int(active_cap),
        load_balance=bool(cfg.load_balance),
        ladder=ladder,
        lb_interval=float(cfg.lb_interval),
        lb_startup_delay=float(cfg.lb_startup_delay),
        lb_margin=float(cfg.margin),
        lb_p0=int(cfg.subpartitions),
        has_churn=bool(has_churn),
        kernel_backend=kernel_backend,
        kernel_interpret=bool(kernel_interpret),
    )


def _bcast(mask, value_ndim: int):
    """Reshape a mask so it broadcasts over trailing value dimensions."""
    return mask.reshape(mask.shape + (1,) * value_ndim)


def _sub_blocks_for(kernels: FusedKernels, spec: _StaticSpec):
    """The §3 block-subgradient callable for the spec's kernel backend.

    ``"pallas"`` binds the problem's Pallas twin with the spec's static
    interpret flag (capability-checked by :func:`kernel_backend_capability`
    before any spec with it is built); both return the same
    ``(Vb, starts, widths, pad_width) -> [G, ...]`` signature.
    """
    if spec.kernel_backend == "pallas":
        pallas_fn = kernels.sub_blocks_pallas
        assert pallas_fn is not None, "capability check admitted a None twin"
        return functools.partial(pallas_fn, interpret=spec.kernel_interpret)
    return kernels.sub_blocks


def _subgradients(kernels: FusedKernels, spec: _StaticSpec, V, lo, hi):
    """[S, N, ...] block subgradients via the static width-bucket ladder.

    One kernel dispatch per possible bucket (all S*N tasks each time), rows
    selected by their actual width — bit-identical to the host wrapper,
    which routes each row to the same bucket.
    """
    S, N = lo.shape
    n = kernels.num_samples
    widths = hi - lo + 1
    vdim = len(kernels.value_shape)
    Vb = jnp.broadcast_to(
        V[:, None], (S, N) + kernels.value_shape
    ).reshape((S * N,) + kernels.value_shape)
    lo_f = lo.reshape(-1)
    w_f = widths.reshape(-1)
    out = None
    prev = 0
    sub_blocks = _sub_blocks_for(kernels, spec)
    for b in spec.buckets:
        block = sub_blocks(Vb, lo_f, w_f, b).reshape(
            (S, N) + kernels.value_shape
        )
        if b == n:
            sel = widths == n
        else:
            sel = (widths != n) & (widths <= b) & (widths > prev)
        out = block if out is None else jnp.where(_bcast(sel, vdim), block, out)
        prev = b
    return out


def _apply_cache_events(
    spec: _StaticSpec,
    slot_width,
    cache_state,
    ev_valid,
    ev_time,
    ev_slot,
    ev_tag,
    ev_vals,
):
    """The §5 update for one iteration's events, as masked scatters.

    ``ev_*`` are ``[S, E_ev]`` tables (stale then fresh halves for DSAG,
    fresh only for SAG).  Events are ranked per scenario by a stable sort
    on event time (+inf where invalid) and applied rank by rank: one rank
    holds at most one event per scenario, so its updates are a single
    vectorized masked scatter, and the per-scenario float accumulation
    order of the running sums matches the host cache's time-ordered
    inserts bit for bit.  With a fixed slot grid an active exact-match
    slot is the only possible overlap, so the scalar cache's eviction walk
    reduces to staleness dominance + in-place update (the SAG fast path).
    """
    st = cache_state
    S, E_ev = ev_time.shape
    vdim = st["values"].ndim - 2
    order = jnp.argsort(jnp.where(ev_valid, ev_time, jnp.inf), axis=1, stable=True)
    s_idx = jnp.arange(S)
    flat_vals = ev_vals.reshape((S * E_ev,) + ev_vals.shape[2:])

    def rank_body(j, state):
        sums, values, iters, covered, rejected = state
        e = order[:, j]
        flat = s_idx * E_ev + e
        valid = ev_valid.reshape(-1)[flat]
        slot = jnp.clip(ev_slot.reshape(-1)[flat], 0, spec.num_slots - 1)
        tag = ev_tag.reshape(-1)[flat]
        v64 = flat_vals[flat].astype(jnp.float64)
        cur_it = iters[s_idx, slot]
        active = cur_it >= 0
        dom = active & (cur_it >= tag)
        acc = valid & ~dom
        rej = valid & dom
        old = values[s_idx, slot]
        delta = v64 - jnp.where(_bcast(active, vdim), old, 0.0)
        sums = jnp.where(_bcast(acc, vdim), sums + delta, sums)
        values = values.at[s_idx, slot].set(jnp.where(_bcast(acc, vdim), v64, old))
        iters = iters.at[s_idx, slot].set(jnp.where(acc, tag, cur_it))
        covered = covered + jnp.where(acc & ~active, slot_width[slot], 0)
        rejected = rejected + rej.astype(rejected.dtype)
        return sums, values, iters, covered, rejected

    sums, values, iters, covered, rejected = jax.lax.fori_loop(
        0,
        E_ev,
        rank_body,
        (st["sums"], st["values"], st["iters"], st["covered"], st["rejected"]),
    )
    return dict(
        sums=sums, values=values, iters=iters, covered=covered, rejected=rejected
    )


def _apply_cache_events_pallas(
    spec: _StaticSpec,
    slot_width,
    cache_state,
    ev_valid,
    ev_time,
    ev_slot,
    ev_tag,
    ev_vals,
):
    """The §5 grid-cache update through the fused Pallas kernel.

    Ranking and pre-gathering stay in XLA (the stable argsort +
    ``take_along_axis`` moves :func:`_apply_cache_events` performs inside
    its loop, hoisted out — pure data movement, bit-identical operands);
    the rank walk itself runs as ``kernels/cache_events.grid_cache_update``,
    one program per scenario, fusing the value-table scatter and the
    running-sum update into a single pass.  Value dimensions are flattened
    to one feature axis for the kernel and reshaped back (a bitwise no-op).
    """
    st = cache_state
    S, E_ev = ev_time.shape
    E = spec.num_slots
    vdim = st["values"].ndim - 2
    vshape = st["values"].shape[2:]
    F = int(np.prod(vshape)) if vdim else 1
    order = jnp.argsort(jnp.where(ev_valid, ev_time, jnp.inf), axis=1, stable=True)
    valid_r = jnp.take_along_axis(ev_valid, order, axis=1)
    slot_r = jnp.clip(jnp.take_along_axis(ev_slot, order, axis=1), 0, E - 1)
    tag_r = jnp.take_along_axis(ev_tag, order, axis=1)
    vals_r = jnp.take_along_axis(
        ev_vals, order.reshape(order.shape + (1,) * vdim), axis=1
    ).astype(jnp.float64)
    sums, values, iters, covered, rejected = grid_cache_update(
        valid_r,
        slot_r,
        tag_r,
        vals_r.reshape(S, E_ev, F),
        st["sums"].reshape(S, F),
        st["values"].reshape(S, E, F),
        st["iters"],
        st["covered"],
        st["rejected"],
        slot_width,
        interpret=spec.kernel_interpret,
    )
    return dict(
        sums=sums.reshape(st["sums"].shape),
        values=values.reshape(st["values"].shape),
        iters=iters,
        covered=covered,
        rejected=rejected,
    )


def _apply_cache_events_lb(
    spec: _StaticSpec,
    slot_width,
    overlap_idx,
    cache_state,
    ev_valid,
    ev_time,
    ev_slot,
    ev_tag,
    ev_vals,
):
    """The full §5 update over the pre-allocated §6 slot universe.

    Like :func:`_apply_cache_events`, but once repartitions are possible an
    event's interval can overlap *other* active slots.  ``overlap_idx[e]``
    statically lists the same-worker slots intersecting slot ``e``
    (sorted by interval start, -1 padded); per event rank the update is
    the scalar cache's walk verbatim: staleness dominance over all active
    overlaps, sequential eviction subtraction in start order (a masked
    ``fori_loop``, preserving the scalar float grouping), then the insert
    — the SAG-style in-place delta when the event's own slot is active
    (disjointness makes it the only possible overlap), a plain add
    otherwise.  Also maintains the eviction counter the host caches track.

    Performance shape (load-bearing — the first implementation was ~100x
    slower than the host engine): inside the rank loop the big ``[S, E,
    ...]`` value table is **write-only** (tracelint TL002 machine-checks
    this).  Reading it there (for eviction subtraction or the in-place
    delta) defeats XLA's in-place aliasing of the loop carry under
    ``lax.scan`` and copies the whole table once per event rank (~minutes
    per 100-worker run); ``lax.cond`` is no escape (~9 ms per rank on the
    CPU thunk runtime — the capture pattern tracelint TL005 flags).  Instead, the live value
    of any slot is *reconstructed* from small read-only buffers: ``wmap``
    maps each slot to the rank of its last accepted write this iteration
    (so the value is a row of the ranked event table), and slots not yet
    written this iteration read from ``values0``, the frozen loop-entry
    buffer — one table copy per iteration instead of one per rank.  Both
    sources hold bit-identical float64 values to what the table itself
    would return.  The rank loop and the eviction sub-loop run to
    *dynamic* trip counts (deepest valid rank / last evicted overlap), so
    empty ranks and the no-eviction common case cost nothing.
    """
    st = cache_state
    S, E_ev = ev_time.shape
    E = spec.num_slots
    Omax = overlap_idx.shape[1]
    vdim = st["values"].ndim - 2
    order = jnp.argsort(jnp.where(ev_valid, ev_time, jnp.inf), axis=1, stable=True)
    s_idx = jnp.arange(S)
    # event tables in rank order: one gather each, outside the rank loop
    valid_r = jnp.take_along_axis(ev_valid, order, axis=1)
    slot_r = jnp.clip(jnp.take_along_axis(ev_slot, order, axis=1), 0, E - 1)
    tag_r = jnp.take_along_axis(ev_tag, order, axis=1)
    vals_r = jnp.take_along_axis(
        ev_vals, order.reshape(order.shape + (1,) * vdim), axis=1
    ).astype(jnp.float64)
    values0 = st["values"]  # frozen pre-iteration table (read-only below)
    wmap0 = jnp.full((S, E), -1, jnp.int32)
    # ranks beyond every scenario's valid events are exact no-ops: skip
    n_ranks = jnp.max(jnp.sum(valid_r, axis=1))

    def rank_body(j, state):
        sums, values, iters, covered, rejected, evictions, wmap = state
        valid = valid_r[:, j]
        slot = slot_r[:, j]
        tag = tag_r[:, j]
        v64 = vals_r[:, j]
        ov = overlap_idx[slot]  # [S, Omax]
        ov_safe = jnp.clip(ov, 0, E - 1)
        ov_iters = iters[s_idx[:, None], ov_safe]
        ov_active = (ov >= 0) & (ov_iters >= 0)
        own_it = iters[s_idx, slot]
        own_active = own_it >= 0
        # staleness dominance over every active overlapping entry
        dom = (own_active & (own_it >= tag)) | jnp.any(
            ov_active & (ov_iters >= tag[:, None]), axis=1
        )
        acc = valid & ~dom
        rej = valid & dom
        evict = ov_active & acc[:, None]
        # live values of the overlap candidates, reconstructed (see above)
        widx = wmap[s_idx[:, None], ov_safe]  # [S, Omax]
        v_new = vals_r[s_idx[:, None], jnp.clip(widx, 0, E_ev - 1)]
        v_old = values0[s_idx[:, None], ov_safe]
        v_sub = jnp.where(_bcast(widx >= 0, vdim), v_new, v_old)

        def sub_body(o, acc_sm):
            return jnp.where(
                _bcast(evict[:, o], vdim), acc_sm - v_sub[:, o], acc_sm
            )

        # masked sequential subtraction in start order (overlap lists are
        # pre-sorted); trip count = last evicted overlap, usually 0
        n_sub = jnp.max(jnp.where(evict, jnp.arange(Omax) + 1, 0))
        sums = jax.lax.fori_loop(0, n_sub, sub_body, sums)
        # deactivate evicted slots via an O(S*Omax) scatter-min: evicted
        # slots get -1, padding writes a huge sentinel (a no-op under
        # min), so duplicate indices from the -1 padding clip cannot
        # corrupt real slots
        upd = jnp.where(evict, jnp.int64(-1), jnp.iinfo(jnp.int64).max)
        iters = iters.at[s_idx[:, None], ov_safe].min(upd)
        removed = jnp.sum(jnp.where(evict, slot_width[ov_safe], 0), axis=1)
        evictions = evictions + jnp.sum(evict, axis=1)
        # insert: exact-active match -> in-place delta (degrades to SAG);
        # otherwise v - 0.0 == v, the scalar slow path's plain add.  The
        # old value is reconstructed, never read from the live table.
        own_wi = wmap[s_idx, slot]
        own_live = jnp.where(
            _bcast(own_wi >= 0, vdim),
            vals_r[s_idx, jnp.clip(own_wi, 0, E_ev - 1)],
            values0[s_idx, slot],
        )
        delta = v64 - jnp.where(_bcast(own_active, vdim), own_live, 0.0)
        sums = jnp.where(_bcast(acc, vdim), sums + delta, sums)
        values = values.at[s_idx, slot].set(
            jnp.where(_bcast(acc, vdim), v64, own_live)
        )
        # the event's own slot is never in its own overlap list, so the
        # scatter-min above cannot have touched own_it
        iters = iters.at[s_idx, slot].set(jnp.where(acc, tag, own_it))
        wmap = wmap.at[s_idx, slot].set(jnp.where(acc, jnp.int32(j), own_wi))
        covered = covered + jnp.where(
            acc, jnp.where(own_active, 0, slot_width[slot]) - removed, 0
        )
        rejected = rejected + rej.astype(rejected.dtype)
        return sums, values, iters, covered, rejected, evictions, wmap

    out = jax.lax.fori_loop(
        0,
        n_ranks,
        rank_body,
        (
            st["sums"],
            st["values"],
            st["iters"],
            st["covered"],
            st["rejected"],
            st["evictions"],
            wmap0,
        ),
    )
    return dict(
        sums=out[0],
        values=out[1],
        iters=out[2],
        covered=out[3],
        rejected=out[4],
        evictions=out[5],
    )


def _apply_cache_events_tiled(
    spec: _StaticSpec,
    slot_width,
    slot_starts,
    slot_stops,
    ev_worker,
    cache_state,
    ev_valid,
    ev_time,
    ev_slot,
    ev_tag,
    ev_vals,
):
    """The §5 update over per-worker *active-entry* tables (tiled §6 cache).

    Same scalar-cache walk as :func:`_apply_cache_events_lb`, but instead
    of dense ``[S, E]`` state over the whole ladder universe, each worker
    owns ``A = spec.active_cap`` entry rows (``slots``/``iters``/values),
    where ``A`` is the greedy interval-scheduling bound on simultaneously
    active disjoint intervals (:func:`~repro.core.gradient_cache.
    active_slot_capacity`).  Overlap candidates are the event worker's own
    ``A`` entries, tested at runtime against the universe's start/stop
    tables — within-worker overlap is the only kind the partitioner can
    produce, so the candidate set is complete.  Eviction subtraction is
    sorted by interval start to reproduce the scalar walk's float order,
    and the insert lands in the exact active entry (in-place delta) or the
    first free row (a free row always exists: active set ∪ new interval is
    disjoint, hence within ``A``).

    The same write-only value-table discipline as the dense path applies:
    inside the rank loop ``values`` (``[S, N, A, ...]``) is only ever
    scattered to; live entry values are reconstructed from the ranked
    event table via ``wmap`` or from ``values0``, the frozen loop-entry
    copy.
    """
    st = cache_state
    S, E_ev = ev_time.shape
    E = spec.num_slots
    values = st["values"]  # [S, N, A, *vshape]
    N, A = values.shape[1], values.shape[2]
    vdim = values.ndim - 3
    order = jnp.argsort(jnp.where(ev_valid, ev_time, jnp.inf), axis=1, stable=True)
    s_idx = jnp.arange(S)
    a_idx = jnp.arange(A)
    valid_r = jnp.take_along_axis(ev_valid, order, axis=1)
    slot_r = jnp.clip(jnp.take_along_axis(ev_slot, order, axis=1), 0, E - 1)
    tag_r = jnp.take_along_axis(ev_tag, order, axis=1)
    vals_r = jnp.take_along_axis(
        ev_vals, order.reshape(order.shape + (1,) * vdim), axis=1
    ).astype(jnp.float64)
    worker_r = jnp.take_along_axis(
        jnp.broadcast_to(ev_worker[None, :], (S, E_ev)), order, axis=1
    )
    values0 = values  # frozen pre-iteration table (read-only below)
    wmap0 = jnp.full((S, N, A), -1, jnp.int32)
    n_ranks = jnp.max(jnp.sum(valid_r, axis=1))

    def rank_body(j, state):
        sums, values, iters, slots, covered, rejected, evictions, wmap = state
        valid = valid_r[:, j]
        slot = slot_r[:, j]
        tag = tag_r[:, j]
        v64 = vals_r[:, j]
        w_e = worker_r[:, j]
        # the event worker's entry rows: [S, A] gathers of small tables
        es = slots[s_idx, w_e]
        ei = iters[s_idx, w_e]
        wm = wmap[s_idx, w_e]
        active = ei >= 0
        es_safe = jnp.clip(es, 0, E - 1)
        e_lo = slot_starts[es_safe]
        e_hi = slot_stops[es_safe]
        ev_lo = slot_starts[slot][:, None]
        ev_hi = slot_stops[slot][:, None]
        ovl = active & (e_lo <= ev_hi) & (ev_lo <= e_hi)
        exact = ovl & (es == slot[:, None])
        dom = jnp.any(ovl & (ei >= tag[:, None]), axis=1)
        acc = valid & ~dom
        rej = valid & dom
        evict = ovl & ~exact & acc[:, None]
        # live entry values, reconstructed (write-only table discipline)
        v_new = vals_r[s_idx[:, None], jnp.clip(wm, 0, E_ev - 1)]
        v_old = values0[s_idx[:, None], w_e[:, None], a_idx[None, :]]
        v_live = jnp.where(_bcast(wm >= 0, vdim), v_new, v_old)  # [S, A, ...]

        def sub_body(o, acc_sm):
            eidx = ord_e[:, o]
            m = evict[s_idx, eidx]
            return jnp.where(
                _bcast(m, vdim), acc_sm - v_live[s_idx, eidx], acc_sm
            )

        # eviction subtraction in interval-start order (the scalar walk's
        # order; active disjoint intervals have distinct starts, so the
        # order is unique); trip count = number evicted, usually 0
        big = jnp.iinfo(jnp.int64).max
        ord_e = jnp.argsort(jnp.where(evict, e_lo, big), axis=1, stable=True)
        n_sub = jnp.max(jnp.sum(evict, axis=1))
        sums = jax.lax.fori_loop(0, n_sub, sub_body, sums)
        ei = jnp.where(evict, jnp.int64(-1), ei)
        removed = jnp.sum(jnp.where(evict, slot_width[es_safe], 0), axis=1)
        evictions = evictions + jnp.sum(evict, axis=1)
        # insert target: the exact active entry (in-place delta; by
        # disjointness it is then the only overlap and nothing was
        # evicted), else the first free row post-eviction
        exact_any = jnp.any(exact, axis=1)
        tgt = jnp.where(
            exact_any, jnp.argmax(exact, axis=1), jnp.argmax(ei < 0, axis=1)
        )
        own_live = v_live[s_idx, tgt]
        delta = v64 - jnp.where(_bcast(exact_any, vdim), own_live, 0.0)
        sums = jnp.where(_bcast(acc, vdim), sums + delta, sums)
        values = values.at[s_idx, w_e, tgt].set(
            jnp.where(_bcast(acc, vdim), v64, own_live)
        )
        ei = ei.at[s_idx, tgt].set(jnp.where(acc, tag, ei[s_idx, tgt]))
        es = es.at[s_idx, tgt].set(jnp.where(acc, slot, es[s_idx, tgt]))
        wm = wm.at[s_idx, tgt].set(jnp.where(acc, jnp.int32(j), wm[s_idx, tgt]))
        iters = iters.at[s_idx, w_e].set(ei)
        slots = slots.at[s_idx, w_e].set(es)
        wmap = wmap.at[s_idx, w_e].set(wm)
        covered = covered + jnp.where(
            acc, jnp.where(exact_any, 0, slot_width[slot]) - removed, 0
        )
        rejected = rejected + rej.astype(rejected.dtype)
        return sums, values, iters, slots, covered, rejected, evictions, wmap

    out = jax.lax.fori_loop(
        0,
        n_ranks,
        rank_body,
        (
            st["sums"],
            values,
            st["iters"],
            st["slots"],
            st["covered"],
            st["rejected"],
            st["evictions"],
            wmap0,
        ),
    )
    return dict(
        sums=out[0],
        values=out[1],
        iters=out[2],
        slots=out[3],
        covered=out[4],
        rejected=out[5],
        evictions=out[6],
    )


def _clear_dead_dense(slot_width, cache_state, clear, order_key):
    """Drop dead workers' active §5 entries from a dense ``[S, E]`` cache.

    The churn twin of ``GradientCache.clear_range``: ``clear`` marks the
    entries to remove, and the running sums subtract them *sequentially*
    in interval-start order — ``order_key`` is the slot index for the grid
    cache (index order == start order there) and the universe start table
    otherwise.  The host caches clear per dead worker in worker order
    (disjoint worker-ordered base ranges) and walk each worker's entries
    start-ascending, so one global start-ascending walk reproduces their
    float grouping bit for bit.  Clearing is NOT an eviction: the counter
    is untouched.  The value table is read only at loop-invariant
    positions (it is not part of the fori_loop carry), so the TL002
    per-rank-copy hazard of the event loops does not arise; the trip
    count is the deepest per-scenario clear, zero in churn-free stretches.
    """
    st = cache_state
    S, _E = clear.shape
    vdim = st["values"].ndim - 2
    s_idx = jnp.arange(S)
    big = jnp.iinfo(jnp.int64).max
    order = jnp.argsort(
        jnp.where(clear, order_key[None, :], big), axis=1, stable=True
    )
    n_clear = jnp.max(jnp.sum(clear, axis=1))
    values = st["values"]

    def sub_body(j, sums):
        e = order[:, j]
        m = clear[s_idx, e]
        return jnp.where(_bcast(m, vdim), sums - values[s_idx, e], sums)

    out = dict(st)
    out["sums"] = jax.lax.fori_loop(0, n_clear, sub_body, st["sums"])
    out["covered"] = st["covered"] - jnp.sum(
        jnp.where(clear, slot_width[None, :], 0), axis=1
    )
    out["iters"] = jnp.where(clear, jnp.int64(-1), st["iters"])
    return out


def _clear_dead_tiled(spec, slot_width, slot_starts, cache_state, dead):
    """Dead-worker §5 clear for the tiled per-worker active-entry tables.

    Same order contract as :func:`_clear_dead_dense`: active intervals are
    disjoint within a worker and base ranges disjoint across workers, so
    sorting every cleared entry by its interval start reproduces the host
    cache's per-worker start-ascending walk globally.  Cleared rows keep
    their stale ``slots`` value — deactivated entries (``iters == -1``)
    are invisible to both the overlap test and the free-row search.
    """
    st = cache_state
    iters = st["iters"]  # [S, N, A]
    S, N, A = iters.shape
    E = spec.num_slots
    vdim = st["values"].ndim - 3
    clear = dead[:, :, None] & (iters >= 0)
    es_safe = jnp.clip(st["slots"], 0, E - 1)
    clear_f = clear.reshape(S, N * A)
    big = jnp.iinfo(jnp.int64).max
    order = jnp.argsort(
        jnp.where(clear_f, slot_starts[es_safe].reshape(S, N * A), big),
        axis=1,
        stable=True,
    )
    n_clear = jnp.max(jnp.sum(clear_f, axis=1))
    s_idx = jnp.arange(S)
    vals_f = st["values"].reshape((S, N * A) + st["values"].shape[3:])

    def sub_body(j, sums):
        e = order[:, j]
        m = clear_f[s_idx, e]
        return jnp.where(_bcast(m, vdim), sums - vals_f[s_idx, e], sums)

    out = dict(st)
    out["sums"] = jax.lax.fori_loop(0, n_clear, sub_body, st["sums"])
    out["covered"] = st["covered"] - jnp.sum(
        jnp.where(clear, slot_width[es_safe], 0), axis=(1, 2)
    )
    out["iters"] = jnp.where(clear, jnp.int64(-1), iters)
    return out


def _fresh_accumulate(kernels, fresh, finish, vals):
    """gd/sgd: sum fresh values per scenario in event-time order."""
    S, N = fresh.shape
    vdim = len(kernels.value_shape)
    order = jnp.argsort(jnp.where(fresh, finish, jnp.inf), axis=1, stable=True)
    s_idx = jnp.arange(S)
    flat_vals = vals.reshape((S * N,) + vals.shape[2:])
    grad0 = jnp.zeros((S,) + kernels.value_shape, dtype=jnp.float64)

    def rank_body(j, grad_acc):
        e = order[:, j]
        flat = s_idx * N + e
        valid = fresh.reshape(-1)[flat]
        v64 = flat_vals[flat].astype(jnp.float64)
        return jnp.where(_bcast(valid, vdim), grad_acc + v64, grad_acc)

    return jax.lax.fori_loop(0, N, rank_body, grad0)


def _run_scan(
    kernels: FusedKernels,
    spec: _StaticSpec,
    slot_table,
    slot_width,
    slot_starts,
    slot_stops,
    overlap_idx,
    comm,
    comp_unit,
    slowdown,
    burst_start,
    burst_end,
    burst_factor,
    V0,
    eval_mask,
    churn_times,
    churn_slowdown,
    churn_alive,
    slot_owner,
    lb_key,
):
    """THE per-iteration scan body + driver, shared by every configuration.

    ``spec`` statically selects the (lo, hi, slot) source — the fixed
    subpartition grid, or the §6 candidate after Algorithm-2 alignment —
    and the cache layout (``spec.cache_mode``); everything else (trace
    replay, event algebra, subgradients, iterate update, telemetry) is
    written once.  Under ``shard_map`` this function sees the local
    scenario shard: every per-scenario value is row-independent, and the
    cross-shard-varying dynamic trip counts / ``lax.cond`` decisions only
    skip work that is an exact no-op, so shards reproduce the
    single-device bits.
    """
    S, N, _K = comm.shape
    T = spec.num_iterations
    n = kernels.num_samples
    vshape = kernels.value_shape
    vdim = len(vshape)
    base_start = jnp.asarray(spec.base_start, dtype=jnp.int64)
    base_stop = jnp.asarray(spec.base_stop, dtype=jnp.int64)
    n_local = base_stop - base_start + 1
    sub_p = jnp.asarray(spec.sub_p, dtype=jnp.int64)
    offsets = jnp.asarray(spec.slot_offsets, dtype=jnp.int64)
    E = spec.num_slots
    if spec.cache_mode == "grid":
        # static slot grid: slot (i, k) -> interval width
        sw = []
        for i in range(N):
            nl, p = spec.base_stop[i] - spec.base_start[i] + 1, spec.sub_p[i]
            if spec.process_full:
                sw.extend([nl] * p)
            else:
                sw.extend([k * nl // p - (k - 1) * nl // p for k in range(1, p + 1)])
        slot_width = jnp.asarray(sw, dtype=jnp.int64)

    s_idx2 = jnp.arange(S)[:, None]
    w_idx2 = jnp.arange(N)[None, :]

    if spec.load_balance:
        L = len(spec.ladder)
        raw = jnp.asarray(spec.ladder, dtype=jnp.int64)
        # per-worker effective ladder (int twin of jlb.ladder_tables)
        eff = jnp.minimum(raw[None, :], n_local[:, None])  # [N, L]
        idx_cap = jnp.minimum(
            jnp.sum(raw[None, :] < n_local[:, None], axis=1), L - 1
        )
        n_j_b = jnp.broadcast_to(n_local.astype(jnp.float64), (S, N))

        def snap_int(p_vals):
            """Ladder index of exact-member p values ([S, N] int)."""
            cnt = jnp.sum(eff[None, :, :] <= p_vals[:, :, None], axis=-1)
            return jnp.clip(cnt - 1, 0, idx_cap[None, :])

    if spec.accepts_stale:
        ev_worker = jnp.concatenate([jnp.arange(N), jnp.arange(N)])
    else:
        ev_worker = jnp.arange(N)

    if spec.has_churn:
        # boundary_before: the time that opened each churn row (-inf for
        # row 0) — the §6 re-profiling cutoff after a fleet change
        churn_bound = jnp.concatenate(
            [jnp.full((1,), -jnp.inf, dtype=jnp.float64), churn_times]
        )
        if spec.uses_cache and spec.cache_mode != "tiled":
            if spec.cache_mode == "grid":
                # per-worker contiguous slot blocks: index order == start
                # order, and the owner map is static
                own = []
                for i in range(N):
                    own.extend([i] * spec.sub_p[i])
                owner_of_slot = jnp.asarray(own, dtype=jnp.int64)
                clear_key = jnp.arange(E, dtype=jnp.int64)
            else:  # universe: slots are (worker, rung) blocks, so index
                # order is NOT start order — use the universe tables
                owner_of_slot = slot_owner
                clear_key = slot_starts

    def burst_factor_at(start):
        if burst_start.shape[2] == 0:
            return jnp.ones_like(start)
        tt = start[:, :, None]
        active = (burst_start <= tt) & (tt < burst_end)
        return jnp.where(active, burst_factor, 1.0).max(axis=2)

    def body(carry, xs):
        t, do_eval = xs
        V = carry["V"]
        free_at = carry["free_at"]
        sub_k = carry["sub_k"]
        cache_state = carry["cache"]
        lat_matrix = carry["lat"]
        assign = carry["iter_end"]

        if spec.has_churn:
            with _phase("events"):
                # liveness sampled once per iteration at assignment time (the
                # scalar simulator / host engine convention).  A worker dead at
                # assignment has its in-flight completion discarded: it goes
                # idle with no stale event, no cache write, no profiler sample.
                rows_assign = jnp.searchsorted(
                    churn_times, assign, side="right"
                ).astype(jnp.int64)
                alive = churn_alive[rows_assign]
                free_at = jnp.where(alive, free_at, assign[:, None])
                if spec.load_balance:
                    changed = rows_assign != carry["prev_row"]
                    # fleet changed: drop the contribution floor so Algorithm 1
                    # re-baselines, and re-profile from the churn boundary
                    h_min_cur = jnp.where(changed, jnp.nan, carry["h_min"])
                    lb_since = jnp.where(
                        changed, churn_bound[rows_assign], carry["lb_since"]
                    )
            if spec.uses_cache:
                with _phase("cache"):
                    if spec.cache_mode == "tiled":
                        cache_state = _clear_dead_tiled(
                            spec, slot_width, slot_starts, cache_state, ~alive
                        )
                    else:
                        clear = (~alive)[:, owner_of_slot] & (
                            cache_state["iters"] >= 0
                        )
                        cache_state = _clear_dead_dense(
                            slot_width, cache_state, clear, clear_key
                        )
        with _phase("events"):
            idle = free_at <= assign[:, None]

            # -- the (lo, hi, slot) source --------------------------------------
            if spec.load_balance:
                # Algorithm-2 alignment for pending repartitions (tentative)
                sub_idx = carry["sub_idx"]
                pending_p = carry["pending_p"]
                cur_p = eff[w_idx2, sub_idx]
                p_req = jnp.clip(pending_p, 1, n_local[None, :])
                needs = (pending_p >= 0) & (p_req != cur_p)
                _, k_new = jlb.align_batch(n_local[None, :], cur_p, p_req, sub_k, needs)
                cand_idx = jnp.where(needs, snap_int(p_req), sub_idx)
                cand_k = jnp.where(needs, k_new, sub_k)
                cand_p = jnp.where(needs, p_req, cur_p)
            else:
                cand_k = sub_k
                cand_p = sub_p[None, :]

            if spec.process_full:
                lo = jnp.broadcast_to(base_start, (S, N))
                hi = jnp.broadcast_to(base_stop, (S, N))
            else:
                lo = base_start[None, :] + (cand_k - 1) * n_local[None, :] // cand_p
                hi = base_start[None, :] + cand_k * n_local[None, :] // cand_p - 1
            cost = (kernels.cost_per_row * (hi - lo + 1)) * spec.comp_scale

            # -- §3 trace replay (THE shared latency expression) ----------------
            start = jnp.where(idle, assign[:, None], free_at)
            comm_d = jnp.take_along_axis(comm, carry["draw_idx"][:, :, None], axis=2)[
                :, :, 0
            ]
            unit = jnp.take_along_axis(
                comp_unit, carry["draw_idx"][:, :, None], axis=2
            )[:, :, 0]
            # guarded_comp_latency carries the FMA seam (tracelint TL001): the
            # jnp.maximum(..., 0.0) inside it keeps LLVM from contracting the
            # last §3 multiply into the task_finish_time add below.
            if spec.has_churn:
                # per-task slowdown row at the task's START time (the traced
                # twin of ChurnSchedule.slowdown_at)
                sd = churn_slowdown[
                    jnp.searchsorted(churn_times, start, side="right"), w_idx2
                ]
            else:
                sd = slowdown[None, :]
            comp_d = guarded_comp_latency(unit, cost, sd, burst_factor_at(start))

            # -- event resolution (the shared method-semantics helpers) ---------
            finish = task_finish_time(start, comp_d, comm_d)
            if spec.has_churn:
                # dead workers never contribute finish times; wait for
                # min(w, #alive) of the living fleet (sort+gather picks the
                # same element as the static top-w, so all-alive churn stays
                # bit-identical to the churn-free body)
                finish_eff = jnp.where(alive, finish, jnp.inf)
                w_eff = jnp.minimum(spec.w_wait, jnp.sum(alive, axis=1))
                tau_w = jnp.take_along_axis(
                    jnp.sort(finish_eff, axis=1), w_eff[:, None] - 1, axis=1
                )[:, 0]
            else:
                tau_w = jnp.sort(finish, axis=1)[:, spec.w_wait - 1]
            if spec.margin > 0.0:
                deadline = margin_deadline(tau_w, assign, spec.margin)
            else:
                deadline = tau_w
            started = idle | (free_at <= deadline[:, None])
            if spec.has_churn:
                started = started & alive
            fresh = started & (finish <= deadline[:, None])
            stale_done = (~idle) & (free_at <= deadline[:, None])
            fresh_cnt = fresh.sum(axis=1)
            stale_ev = jnp.where(stale_done, free_at, -jnp.inf)
            fresh_ev = jnp.where(fresh, finish, -jnp.inf)
            iter_end_new = jnp.maximum(
                jnp.maximum(stale_ev.max(axis=1), fresh_ev.max(axis=1)), tau_w
            )

            # -- latency attribution by the task's own iteration ----------------
            flight_titer = carry["flight_titer"]
            flight_comp = carry["flight_comp"]
            flight_comm = carry["flight_comm"]
            titer_safe = jnp.clip(flight_titer, 0, T - 1)
            cur = lat_matrix[s_idx2, titer_safe, w_idx2]
            lat_matrix = lat_matrix.at[s_idx2, titer_safe, w_idx2].set(
                jnp.where(stale_done, flight_comp + flight_comm, cur)
            )
            lat_matrix = lat_matrix.at[:, t, :].set(
                jnp.where(fresh, comp_d + comm_d, lat_matrix[:, t, :])
            )

            if spec.load_balance:
                # -- §6.1 profiler feed: one task-slot sample per observed
                # completion (same slots and float expressions as MomentBuffer)
                prof_t, prof_comm, prof_comp, prof_valid = carry["prof"]
                flight_assigned = carry["flight_assigned"]
                stale_rt = free_at - flight_assigned
                stale_comm = jnp.maximum(stale_rt - flight_comp, 0.0)
                prof_t = prof_t.at[s_idx2, w_idx2, titer_safe].set(
                    jnp.where(stale_done, free_at, prof_t[s_idx2, w_idx2, titer_safe])
                )
                prof_comm = prof_comm.at[s_idx2, w_idx2, titer_safe].set(
                    jnp.where(
                        stale_done, stale_comm, prof_comm[s_idx2, w_idx2, titer_safe]
                    )
                )
                prof_comp = prof_comp.at[s_idx2, w_idx2, titer_safe].set(
                    jnp.where(
                        stale_done, flight_comp, prof_comp[s_idx2, w_idx2, titer_safe]
                    )
                )
                prof_valid = prof_valid.at[s_idx2, w_idx2, titer_safe].set(
                    prof_valid[s_idx2, w_idx2, titer_safe] | stale_done
                )
                fresh_rt = finish - assign[:, None]
                fresh_comm = jnp.maximum(fresh_rt - comp_d, 0.0)
                prof_t = prof_t.at[:, :, t].set(jnp.where(fresh, finish, prof_t[:, :, t]))
                prof_comm = prof_comm.at[:, :, t].set(
                    jnp.where(fresh, fresh_comm, prof_comm[:, :, t])
                )
                prof_comp = prof_comp.at[:, :, t].set(
                    jnp.where(fresh, comp_d, prof_comp[:, :, t])
                )
                prof_valid = prof_valid.at[:, :, t].set(prof_valid[:, :, t] | fresh)

        # -- batched subgradients (skipped entirely for coded) --------------
        if spec.name != "coded":
            with _phase("subgrad"):
                vals = _subgradients(kernels, spec, V, lo, hi)
        else:
            vals = None

        # -- §5 cache / gradient accumulation -------------------------------
        with _phase("cache"):
            if spec.uses_cache:
                if spec.load_balance:
                    slot_cur = slot_table[w_idx2, cand_idx, cand_k - 1]
                else:
                    slot_cur = offsets[None, :] + sub_k - 1
                if spec.accepts_stale:  # dsag: stale half then fresh half
                    flight_slot = carry["flight_slot"]
                    ev_valid = jnp.concatenate([stale_done, fresh], axis=1)
                    ev_time = jnp.concatenate([free_at, finish], axis=1)
                    ev_slot = jnp.concatenate([flight_slot, slot_cur], axis=1)
                    ev_tag = jnp.concatenate(
                        [flight_titer, jnp.full((S, N), 1, jnp.int64) * t], axis=1
                    )
                    ev_vals = jnp.concatenate([carry["flight_val"], vals], axis=1)
                else:  # sag: fresh results only
                    ev_valid, ev_time = fresh, finish
                    ev_slot = slot_cur
                    ev_tag = jnp.full((S, N), 1, jnp.int64) * t
                    ev_vals = vals
                if spec.cache_mode == "universe":
                    cache_state = _apply_cache_events_lb(
                        spec, slot_width, overlap_idx, cache_state, ev_valid,
                        ev_time, ev_slot, ev_tag, ev_vals,
                    )
                elif spec.cache_mode == "tiled":
                    cache_state = _apply_cache_events_tiled(
                        spec, slot_width, slot_starts, slot_stops, ev_worker,
                        cache_state, ev_valid, ev_time, ev_slot, ev_tag, ev_vals,
                    )
                elif spec.kernel_backend == "pallas":
                    # grid cache only: the §6 universe/tiled walks stay XLA
                    # (their eviction logic has no Pallas twin yet — ROADMAP)
                    cache_state = _apply_cache_events_pallas(
                        spec, slot_width, cache_state, ev_valid, ev_time, ev_slot,
                        ev_tag, ev_vals,
                    )
                else:
                    cache_state = _apply_cache_events(
                        spec, slot_width, cache_state, ev_valid, ev_time, ev_slot,
                        ev_tag, ev_vals,
                    )
                xi = jnp.maximum(cache_state["covered"] / n, 1e-12)
                grad = cache_state["sums"] / _bcast(xi, vdim) + (
                    kernels.regularizer_grad(V)
                )
            elif spec.name == "coded":
                slot_cur = None
                # idealized MDS bound: exact gradient at full-range width
                with _phase("subgrad"):
                    g = _sub_blocks_for(kernels, spec)(
                        V,
                        jnp.ones((S,), jnp.int64),
                        jnp.full((S,), n, jnp.int64),
                        n,
                    ).astype(jnp.float64)
                grad = g + kernels.regularizer_grad(V)
            elif spec.name == "gd":
                slot_cur = None
                grad = _fresh_accumulate(kernels, fresh, finish, vals) + (
                    kernels.regularizer_grad(V)
                )
            else:  # sgd: scale the partial sum by observed coverage
                slot_cur = None
                grad_acc = _fresh_accumulate(kernels, fresh, finish, vals)
                covered_f = jnp.sum(jnp.where(fresh, hi - lo + 1, 0), axis=1)
                xi = jnp.maximum(covered_f / n, 1e-12)
                grad = grad_acc / _bcast(xi, vdim) + kernels.regularizer_grad(V)

        # -- iterate update + suboptimality ---------------------------------
        with _phase("update"):
            V_new = kernels.project((V - spec.eta * grad).astype(V.dtype))
        with _phase("eval"):
            subopt_t = jax.lax.cond(
                do_eval,
                lambda v: kernels.suboptimality(v),
                lambda v: jnp.full((S,), jnp.nan, dtype=jnp.float64),
                V_new,
            )

        # -- commit worker state for started tasks --------------------------
        out = dict(carry)
        with _phase("update"):
            if spec.load_balance:
                out["sub_idx"] = jnp.where(started, cand_idx, sub_idx)
                out["pending_p"] = jnp.where(started, -1, pending_p)
                out["flight_assigned"] = jnp.where(
                    started, assign[:, None], carry["flight_assigned"]
                )
            if spec.process_full:
                if spec.load_balance:
                    sub_k = jnp.where(started, cand_k, sub_k)
            else:
                sub_k = jnp.where(started, cand_k % cand_p + 1, sub_k)
            out["sub_k"] = sub_k
            out["free_at"] = jnp.where(started, finish, free_at)
            out["draw_idx"] = carry["draw_idx"] + started.astype(jnp.int64)
            if spec.uses_cache:
                out["flight_slot"] = jnp.where(started, slot_cur, carry["flight_slot"])
            out["flight_titer"] = jnp.where(started, t, flight_titer)
            out["flight_comp"] = jnp.where(started, comp_d, flight_comp)
            out["flight_comm"] = jnp.where(started, comm_d, flight_comm)
            if spec.accepts_stale:
                out["flight_val"] = jnp.where(
                    _bcast(started, vdim), vals, carry["flight_val"]
                )
        out["V"] = V_new
        out["iter_end"] = iter_end_new
        out["cache"] = cache_state
        out["lat"] = lat_matrix

        # -- §6 background load balancer (Algorithm 1, jittable) ------------
        if spec.load_balance:
            current_p = carry["current_p"]
            h_min = h_min_cur if spec.has_churn else carry["h_min"]
            next_lb = carry["next_lb"]
            pending_p = out["pending_p"]
            with _phase("lb"):
                due = iter_end_new >= next_lb
            out["prof"] = (prof_t, prof_comm, prof_comp, prof_valid)
            if spec.has_churn:
                out["prev_row"] = rows_assign
                out["lb_since"] = lb_since

            def lb_block(args):
                pending_p, current_p, h_min, next_lb = args
                e_cm, v_cm, e_cp, v_cp, cnt = jlb.window_moments(
                    prof_t, prof_comm, prof_comp, prof_valid, iter_end_new,
                    jlb.PROFILER_WINDOW,
                    since=lb_since if spec.has_churn else None,
                )
                if spec.has_churn:
                    # dead workers can't produce samples — don't wait on them
                    ready = jnp.all((cnt >= 1) | ~alive, axis=1)
                else:
                    ready = jnp.all(cnt >= 1, axis=1)
                next_lb2 = jnp.where(due, iter_end_new + spec.lb_interval, next_lb)
                act = due & ready

                def run_opt(_):
                    # the make_optimizer_inputs variance floors, verbatim
                    p_new, h_min2, _, publish = jlb.lb_update(
                        current_p.astype(jnp.float64),
                        e_cm,
                        jnp.maximum(v_cm, 1e-18),
                        e_cp,
                        jnp.maximum(v_cp, 1e-18),
                        n_j_b,
                        h_min,
                        act,
                        ladder=spec.ladder,
                        w=spec.w_wait,
                        margin=spec.lb_margin,
                        key=lb_key,
                        alive=alive if spec.has_churn else None,
                    )
                    changed = publish[:, None] & (p_new != current_p)
                    return (
                        jnp.where(changed, p_new, pending_p),
                        jnp.where(publish[:, None], p_new, current_p),
                        h_min2,
                        publish,
                    )

                def no_opt(_):
                    return pending_p, current_p, h_min, jnp.zeros((S,), bool)

                pending2, current2, h_min2, publish = jax.lax.cond(
                    jnp.any(act), run_opt, no_opt, None
                )
                return pending2, current2, h_min2, next_lb2, publish

            def no_lb(args):
                pending_p, current_p, h_min, next_lb = args
                return pending_p, current_p, h_min, next_lb, jnp.zeros((S,), bool)

            with _phase("lb"):
                pending_p, current_p, h_min, next_lb, published = jax.lax.cond(
                    jnp.any(due), lb_block, no_lb,
                    (pending_p, current_p, h_min, next_lb),
                )
            out["pending_p"] = pending_p
            out["current_p"] = current_p
            out["h_min"] = h_min
            out["next_lb"] = next_lb
        else:
            published = jnp.zeros((S,), bool)

        return out, (iter_end_new, subopt_t, fresh_cnt, published)

    val_dtype = jnp.dtype(kernels.value_dtype)
    if spec.cache_mode == "grid":
        cache0 = dict(
            sums=jnp.zeros((S,) + vshape, dtype=jnp.float64),
            values=jnp.zeros((S, max(E, 1)) + vshape, dtype=jnp.float64),
            iters=jnp.full((S, max(E, 1)), -1, dtype=jnp.int64),
            covered=jnp.zeros((S,), dtype=jnp.int64),
            rejected=jnp.zeros((S,), dtype=jnp.int64),
        )
    elif spec.cache_mode == "universe":
        cache0 = dict(
            sums=jnp.zeros((S,) + vshape, dtype=jnp.float64),
            values=jnp.zeros((S, max(E, 1)) + vshape, dtype=jnp.float64),
            iters=jnp.full((S, max(E, 1)), -1, dtype=jnp.int64),
            covered=jnp.zeros((S,), dtype=jnp.int64),
            rejected=jnp.zeros((S,), dtype=jnp.int64),
            evictions=jnp.zeros((S,), dtype=jnp.int64),
        )
    elif spec.cache_mode == "tiled":
        A = max(spec.active_cap, 1)
        cache0 = dict(
            sums=jnp.zeros((S,) + vshape, dtype=jnp.float64),
            values=jnp.zeros((S, N, A) + vshape, dtype=jnp.float64),
            iters=jnp.full((S, N, A), -1, dtype=jnp.int64),
            slots=jnp.full((S, N, A), -1, dtype=jnp.int64),
            covered=jnp.zeros((S,), dtype=jnp.int64),
            rejected=jnp.zeros((S,), dtype=jnp.int64),
            evictions=jnp.zeros((S,), dtype=jnp.int64),
        )
    else:
        cache0 = dict(rejected=jnp.zeros((S,), dtype=jnp.int64))
    carry0 = dict(
        V=V0,
        free_at=jnp.zeros((S, N)),
        iter_end=jnp.zeros((S,)),
        draw_idx=jnp.zeros((S, N), dtype=jnp.int64),
        sub_k=jnp.ones((S, N), dtype=jnp.int64),
        flight_slot=jnp.full((S, N), -1, dtype=jnp.int64),
        flight_titer=jnp.full((S, N), -1, dtype=jnp.int64),
        flight_comp=jnp.zeros((S, N)),
        flight_comm=jnp.zeros((S, N)),
        flight_val=jnp.zeros((S, N) + vshape, dtype=val_dtype),
        cache=cache0,
        # explicit dtype: python-float fills would enter the scan carry
        # weakly typed (tracelint TL004)
        lat=jnp.full((S, T, N), jnp.nan, dtype=jnp.float64),
    )
    if spec.load_balance:
        sub_p0 = jnp.asarray(spec.sub_p, dtype=jnp.int64)
        idx0 = jnp.clip(jnp.sum(eff <= sub_p0[:, None], axis=1) - 1, 0, idx_cap)
        carry0["sub_idx"] = jnp.broadcast_to(idx0, (S, N))
        carry0["pending_p"] = jnp.full((S, N), -1, dtype=jnp.int64)
        # current_p is the optimizer's view of the published p
        carry0["current_p"] = jnp.full((S, N), spec.lb_p0, dtype=jnp.int64)
        carry0["h_min"] = jnp.full((S,), jnp.nan, dtype=jnp.float64)
        carry0["next_lb"] = jnp.full(
            (S,), spec.lb_startup_delay, dtype=jnp.float64
        )
        carry0["flight_assigned"] = jnp.zeros((S, N))
        if spec.has_churn:
            # churn times are strictly positive, so row 0 is active at t=0
            # and its opening boundary is -inf (the static `since`)
            carry0["prev_row"] = jnp.zeros((S,), dtype=jnp.int64)
            carry0["lb_since"] = jnp.full((S,), -jnp.inf, dtype=jnp.float64)
        carry0["prof"] = (
            jnp.zeros((S, N, T)),
            jnp.zeros((S, N, T)),
            jnp.zeros((S, N, T)),
            jnp.zeros((S, N, T), dtype=bool),
        )
    xs = (jnp.arange(T, dtype=jnp.int64), eval_mask)
    with jax.named_scope(spec.name):
        carry, ys = jax.lax.scan(body, carry0, xs)
    times, subopt, fresh_counts, published = ys
    evictions = carry["cache"].get(
        "evictions", jnp.zeros((S,), dtype=jnp.int64)
    )
    return (
        times.T,
        subopt.T,
        fresh_counts.T,
        carry["lat"],
        carry["cache"]["rejected"],
        evictions,
        published.T,  # [S, T] publication schedule (all-False without §6)
    )


def _scan_jit_for(kernels: FusedKernels, mesh=None):
    """Per-kernels jitted driver, keyed by the scenario mesh.

    The jit cache is owned by the kernels object rather than a module-level
    callable: a module-level ``jax.jit`` would keep every problem's data
    matrices (captured by the static ``kernels`` argument) alive for the
    process lifetime; this way the compiled executables are garbage
    collected with the problem.  With a mesh, the driver is wrapped in
    ``shard_map`` over the ``"data"`` (scenario) axis: the five slot
    tables, ``slowdown``, ``eval_mask`` and the PRNG key are replicated,
    every ``[S, ...]`` array is sharded on its leading axis, and so is
    every output.
    """
    cache = getattr(kernels, "_scan_driver_jits", None)
    if cache is None:
        cache = {}
        kernels._scan_driver_jits = cache
    key = (
        None
        if mesh is None
        else (mesh.axis_names, tuple(d.id for d in mesh.devices.flat))
    )
    fn = cache.get(key)
    if fn is None:
        if mesh is None:
            fn = jax.jit(_run_scan, static_argnums=(0, 1))
        else:
            repl, data = P(), P("data")
            in_specs = (repl,) * 5 + (
                data, data, repl, data, data, data, data, repl,
            ) + (repl,) * 5  # churn tables, slot owners, PRNG key
            out_specs = (data,) * 7

            def _run_scan_sharded(kernels_, spec_, *arrays):
                # the name keeps "_run_scan" in the executable's name, which
                # the benchmark's trace reduction looks for
                body = functools.partial(_run_scan, kernels_, spec_)
                # check_vma=False: every output is data-sharded, so the
                # varying-manual-axes check has nothing to prove; left on,
                # it rejects every loop whose carry starts replicated and
                # turns sharded (the §5 rank walks add replicated slot
                # widths to sharded counters) unless each gets a pvary.
                return jax.shard_map(
                    body, mesh=mesh, in_specs=in_specs,
                    out_specs=out_specs, check_vma=False,
                )(*arrays)

            fn = jax.jit(_run_scan_sharded, static_argnums=(0, 1))
        cache[key] = fn
    return fn


def scan_capability(
    problem: FiniteSumProblem,
    config: MethodConfig,
    num_workers: int,
    *,
    slot_budget: int | None = None,
) -> EngineCapability:
    """Structured report of how the fused scan would run this config.

    * :data:`~repro.experiments.engine.CAP_OK` — supported; §6 configs fit
      the dense slot universe within ``slot_budget``.
    * :data:`~repro.experiments.engine.CAP_TILED` — supported; the §6
      ladder universe exceeds the budget, so the scan uses the tiled
      active-slot cache (``slots_resident`` names its footprint).
    * :data:`~repro.experiments.engine.CAP_ACTIVE_SET` — unsupported: even
      the tiled cache's resident entries exceed the budget; route to the
      host engine.
    * :data:`~repro.experiments.engine.CAP_LB_ACCELERATOR` — unsupported:
      a §6 config off the CPU.  Its scan body compiles for a TPU v5e, but
      runs there never got past the first iteration (ROADMAP 2.1); the
      host engine (``kind="host"``) still runs it.

    ``slot_budget`` defaults to :data:`LB_MAX_SLOTS`.  Bounds here are
    cheap overestimates (no universe is built): the dense bound is
    ``N * sum(min(rung, max n_local))``; the tiled bound is the
    minimum-interval-width packing cap per worker, which the exact greedy
    capacity (:func:`~repro.core.gradient_cache.active_slot_capacity`)
    never exceeds.
    """
    budget = int(LB_MAX_SLOTS if slot_budget is None else slot_budget)
    if config.load_balance and jax.default_backend() != "cpu":
        return EngineCapability(
            supported=False,
            code=CAP_LB_ACCELERATOR,
            detail=(
                f"the fused scan does not run §6 load-balanced configs on "
                f"{jax.default_backend()}: the body compiles, but no run has "
                f"got past its first iteration there (ROADMAP 2.1); pass "
                f"EngineConfig(kind='host') to run it on the host engine"
            ),
            slot_budget=budget,
        )
    if not (config.load_balance and config.uses_cache):
        return EngineCapability(
            supported=True,
            code=CAP_OK,
            detail="fused scan supports this config",
            slot_budget=budget,
        )
    n = problem.num_samples
    N = num_workers
    n_local = np.array(
        [p_stop(n, N, i + 1) - p_start(n, N, i + 1) + 1 for i in range(N)]
    )
    ladder = lb_ladder_for(config, n_local)
    total = int(sum(min(int(r), int(n_local.max())) for r in ladder)) * N
    if total <= budget:
        return EngineCapability(
            supported=True,
            code=CAP_OK,
            detail=(
                f"§6 ladder slot universe fits densely "
                f"({total} slots <= budget {budget})"
            ),
            slots_total=total,
            slots_resident=total,
            slot_budget=budget,
        )
    p_top = max(int(r) for r in ladder)
    cap = 0
    for nl in n_local:
        w_min = max(int(nl) // min(p_top, int(nl)), 1)
        cap = max(cap, int(nl) // w_min)
    resident = N * cap
    if resident <= budget:
        return EngineCapability(
            supported=True,
            code=CAP_TILED,
            detail=(
                f"§6 ladder slot universe needs up to {total} slots "
                f"(> slot budget {budget}); running the fused scan with the "
                f"tiled active-slot cache (<= {resident} resident entries)"
            ),
            slots_total=total,
            slots_resident=resident,
            slot_budget=budget,
        )
    return EngineCapability(
        supported=False,
        code=CAP_ACTIVE_SET,
        detail=(
            f"even the tiled active-slot cache needs up to {resident} "
            f"resident entries (> slot budget {budget}); the fused scan "
            f"cannot hold this config — use EngineConfig(kind='host') or "
            f"raise slot_budget"
        ),
        slots_total=total,
        slots_resident=resident,
        slot_budget=budget,
    )


def scan_unsupported_reason(
    problem: FiniteSumProblem, config: MethodConfig, num_workers: int
) -> str | None:
    """Why the fused scan cannot run this config (None = it can).

    Deprecated string shim over :func:`scan_capability` — callers should
    branch on the structured report's ``code`` instead of this text.
    Note that since the tiled cache landed, oversized §6 universes are
    *supported* (they return None here); only configs whose active-entry
    footprint exceeds the budget report a reason.
    """
    warnings.warn(
        "scan_unsupported_reason is deprecated; use scan_capability and "
        "branch on the structured report's code",
        DeprecationWarning,
        stacklevel=2,
    )
    cap = scan_capability(problem, config, num_workers)
    return None if cap.supported else cap.detail


def kernel_backend_capability(
    problem: FiniteSumProblem,
    kernel_backend: str,
    config: MethodConfig,
) -> EngineCapability:
    """Whether the fused scan can route this problem's hot paths to Pallas.

    ``"xla"`` is always supported.  ``"pallas"`` requires the problem to
    publish Pallas twins (``FusedKernels.sub_blocks_pallas``) and a
    float32 in-flight value dtype (the only dtype the kernels are
    validated for — see ``kernels/block_sub.py``).  A ``config`` whose
    cache is the fixed grid (the §5 path the grid-cache kernel takes) is
    refused off the CPU: that kernel's state is
    float64/int64, which XLA:TPU does not accept in a Pallas call, and
    the f32/i32 regime that would lift this is ROADMAP 1.3.  Reported
    codes: :data:`~repro.experiments.engine.CAP_PALLAS_UNAVAILABLE`,
    :data:`~repro.experiments.engine.CAP_PALLAS_DTYPE`,
    :data:`~repro.experiments.engine.CAP_PALLAS_X64_STATE`.
    """
    if kernel_backend != "pallas":
        return EngineCapability(
            supported=True, code=CAP_OK, detail="xla kernel backend"
        )
    kernels = problem.fused_kernels()
    if kernels.sub_blocks_pallas is None:
        return EngineCapability(
            supported=False,
            code=CAP_PALLAS_UNAVAILABLE,
            detail=(
                f"kernel_backend='pallas' requested but "
                f"{type(problem).__name__} publishes no Pallas kernels "
                f"(FusedKernels.sub_blocks_pallas is None); use "
                f"kernel_backend='xla'"
            ),
        )
    if np.dtype(kernels.value_dtype) != np.float32:
        return EngineCapability(
            supported=False,
            code=CAP_PALLAS_DTYPE,
            detail=(
                f"kernel_backend='pallas' supports float32 in-flight "
                f"values only; {type(problem).__name__} declares "
                f"{np.dtype(kernels.value_dtype).name}"
            ),
        )
    grid_cache = config.uses_cache and not config.load_balance
    if grid_cache and jax.default_backend() != "cpu":
        return EngineCapability(
            supported=False,
            code=CAP_PALLAS_X64_STATE,
            detail=(
                f"kernel_backend='pallas' cannot run the {config.name} grid "
                f"cache on {jax.default_backend()}: the grid-cache kernel's "
                f"state is float64/int64, which XLA:TPU refuses inside a "
                f"Pallas call (ROADMAP 1.3, an f32/i32 regime, lifts this); "
                f"use kernel_backend='xla'"
            ),
        )
    return EngineCapability(
        supported=True, code=CAP_OK, detail="pallas kernel backend available"
    )


def prepare_scan_inputs(
    problem: FiniteSumProblem,
    traces: FleetTraces,
    config: MethodConfig,
    num_iterations: int,
    *,
    cost_scale: float = 1.0,
    eval_every: int = 1,
    seed: int = 0,
    slot_budget: int | None = None,
    pad: int = 0,
    kernel_backend: str = "xla",
):
    """Static spec + kernels + the full ``_run_scan`` operand tuple.

    The one place the fused engine's positional calling convention is
    encoded.  Shared between :func:`run_convergence_scan` and the
    tracelint entry registry (``repro.analysis.lint.entries``), so the
    static analyzer always traces the production scan body with
    production-shaped operands instead of a hand-maintained replica.
    ``pad`` edge-pads the scenario axis with copies of the last scenario
    (``shard_map`` divisibility).  Raises
    :class:`~repro.experiments.engine.EngineCapabilityError` for
    genuinely unsupported configs.
    """
    cap = scan_capability(
        problem, config, traces.num_workers, slot_budget=slot_budget
    )
    if not cap.supported:
        raise EngineCapabilityError(cap)
    kcap = kernel_backend_capability(problem, kernel_backend, config)
    if not kcap.supported:
        raise EngineCapabilityError(kcap)
    # resolve the interpret decision NOW, outside any trace: reading
    # jax.default_backend() inside a jitted wrapper bakes a stale value
    # into the cached executable (the kernels/ops.py bug class)
    kernel_interpret = jax.default_backend() == "cpu"
    tiled = cap.code == CAP_TILED
    S = traces.num_scenarios
    T = num_iterations
    if T > traces.horizon:
        raise ValueError(
            f"traces hold {traces.horizon} draws/worker but {T} iterations requested"
        )
    universe = None
    active_cap = 0
    if config.load_balance and config.uses_cache:
        n = problem.num_samples
        N = traces.num_workers
        base_start = [p_start(n, N, i + 1) for i in range(N)]
        base_stop = [p_stop(n, N, i + 1) for i in range(N)]
        n_local = np.asarray(base_stop) - np.asarray(base_start) + 1
        universe = build_slot_universe(
            base_start,
            base_stop,
            lb_ladder_for(config, n_local),
            with_overlaps=not tiled,
        )
        if tiled:
            active_cap = int(active_slot_capacity(universe).max())
    spec = _static_spec(
        problem,
        config,
        traces.num_workers,
        T,
        cost_scale,
        universe=universe,
        tiled=tiled,
        active_cap=active_cap,
        has_churn=traces.churn is not None,
        kernel_backend=kernel_backend,
        kernel_interpret=kernel_interpret,
    )
    kernels = problem.fused_kernels()
    V0 = np.repeat(problem.init(seed)[None], S, axis=0)
    eval_mask = np.zeros(T, dtype=bool)
    eval_mask[::eval_every] = True
    eval_mask[T - 1] = True

    def padded(a):
        if pad == 0:
            return a
        return np.concatenate([a, np.repeat(a[-1:], pad, axis=0)], axis=0)

    with x64():
        empty = jnp.zeros((S + pad, traces.num_workers, 0))
        has_b = traces.has_bursts
        trace_args = (
            jnp.asarray(padded(traces.comm)),
            jnp.asarray(padded(traces.comp_unit)),
            jnp.asarray(traces.slowdown),
            jnp.asarray(padded(traces.burst_start)) if has_b else empty,
            jnp.asarray(padded(traces.burst_end)) if has_b else empty,
            jnp.asarray(padded(traces.burst_factor)) if has_b else empty,
            jnp.asarray(padded(V0)),
            jnp.asarray(eval_mask),
        )
        if universe is not None:
            slot_table = jnp.asarray(universe.slot_table)
            slot_width = jnp.asarray(universe.widths)
            slot_starts = jnp.asarray(universe.starts)
            slot_stops = jnp.asarray(universe.stops)
            overlap_idx = jnp.asarray(universe.overlap_idx)
        else:  # grid / non-cache configs: keep the unused tables minimal
            N = traces.num_workers
            L = max(len(spec.ladder), 1)
            pmax = max(spec.ladder) if spec.ladder else 1
            slot_table = jnp.zeros((N, L, pmax), dtype=jnp.int64)
            slot_width = jnp.zeros((1,), dtype=jnp.int64)
            slot_starts = jnp.zeros((1,), dtype=jnp.int64)
            slot_stops = jnp.zeros((1,), dtype=jnp.int64)
            overlap_idx = jnp.full((1, 1), -1, dtype=jnp.int64)
        ch = traces.churn
        if ch is not None:
            churn_times = jnp.asarray(ch.times, dtype=jnp.float64)
            churn_slowdown = jnp.asarray(ch.slowdown, dtype=jnp.float64)
            churn_alive = jnp.asarray(ch.alive, dtype=bool)
        else:  # unused by the traced body (spec.has_churn gates it out);
            # fixed operand count keeps one calling convention
            churn_times = jnp.zeros((0,), dtype=jnp.float64)
            churn_slowdown = jnp.zeros((0, traces.num_workers), jnp.float64)
            churn_alive = jnp.zeros((0, traces.num_workers), dtype=bool)
        slot_owner = (
            jnp.asarray(universe.owners)
            if universe is not None
            else jnp.zeros((1,), dtype=jnp.int64)
        )
        scan_args = (
            slot_table,
            slot_width,
            slot_starts,
            slot_stops,
            overlap_idx,
            *trace_args,
            churn_times,
            churn_slowdown,
            churn_alive,
            slot_owner,
            jax.random.PRNGKey(seed),
        )
    return spec, kernels, scan_args


def run_convergence_scan(
    problem: FiniteSumProblem,
    traces: FleetTraces,
    config: MethodConfig,
    num_iterations: int,
    *,
    cost_scale: float = 1.0,
    eval_every: int = 1,
    seed: int = 0,
    engine: EngineConfig | None = None,
):
    """Train ``config`` on every scenario of ``traces`` in one XLA dispatch.

    Bit-exact against the host engine and the scalar simulator on the same
    traces (see module docstring), §6 load-balanced configs included.
    ``engine`` supplies the scenario mesh (``mesh`` / ``num_devices``),
    the slot budget, and the ``kernel_backend``; its ``kind`` is ignored
    here — this *is* the scan engine.  Raises :class:`~repro.experiments.engine.EngineCapabilityError`
    for the one unsupported case (see :func:`scan_capability`)."""
    from repro.experiments.convergence import ConvergenceBatchResult

    eng = as_engine_config(engine, _stacklevel=3)
    mesh = eng.mesh
    if mesh is None and eng.num_devices is not None:
        from repro.launch.mesh import make_scenario_mesh

        mesh = make_scenario_mesh(eng.num_devices)
    D = 1 if mesh is None else int(np.prod(mesh.devices.shape))
    S = traces.num_scenarios
    # shard_map needs the scenario axis divisible by the mesh: edge-pad
    # with copies of the last scenario (exact per-row math makes padding
    # rows inert) and slice every output back to S
    pad = (-S) % D
    with jax.profiler.TraceAnnotation("repro.prepare"):
        spec, kernels, scan_args = prepare_scan_inputs(
            problem,
            traces,
            config,
            num_iterations,
            cost_scale=cost_scale,
            eval_every=eval_every,
            seed=seed,
            slot_budget=eng.slot_budget,
            pad=pad,
            kernel_backend=eng.kernel_backend,
        )
    with x64(), jax.profiler.TraceAnnotation("repro.dispatch"):
        outs = _scan_jit_for(kernels, mesh)(kernels, spec, *scan_args)
    with jax.profiler.TraceAnnotation("repro.fetch"):
        with x64():
            times, subopt, fresh, lat, rejected, evictions, published = (
                np.asarray(o)[:S] for o in outs
            )
        repartition_events = [
            [float(times[s, t]) for t in np.flatnonzero(published[s])]
            for s in range(S)
        ]
        result = ConvergenceBatchResult(
            times=times,
            suboptimality=subopt,
            fresh_counts=np.asarray(fresh, dtype=np.int64),
            per_worker_latency=lat,
            repartition_events=repartition_events,
            evictions=np.asarray(evictions, dtype=np.int64),
            rejected_stale=np.asarray(rejected, dtype=np.int64),
            engine="scan",
        )
    if spec.name != "coded" and jax.profiler.TraceAnnotation.is_enabled():
        # a zero-length span: its arguments carry the scan's work counters,
        # counted only while a profiler session records them
        with jax.profiler.TraceAnnotation(
            "repro.counts", method=spec.name, **scan_counts(spec, result)
        ):
            pass
    return result


def scan_counts(spec: _StaticSpec, result) -> dict[str, int]:
    """Work counters of one scan with block subgradients, from its static
    spec and its fetched results (no device work):

    * ``subgrad_rows``: block-subgradient rows computed, S*T*N per bucket
      of the width ladder (each bucket evaluates every task);
    * ``events``: task results that reached the §5 cache or the gradient
      sum: fresh ones, plus stale arrivals where the method takes them
      (each stale arrival writes its task's latency row, so those are the
      finite latencies beyond the fresh ones);
    * ``rejected``: stale results the cache refused;
    * ``walk_ranks``: ranks the §5 walk visited, S*T times the event table's
      width (2N with stale arrivals, N without; 0 without a cache).
    """
    S, T, N = result.per_worker_latency.shape
    fresh = int(result.fresh_counts.sum())
    events = (
        int(np.isfinite(result.per_worker_latency).sum())
        if spec.accepts_stale
        else fresh
    )
    walk = (2 * N if spec.accepts_stale else N) if spec.uses_cache else 0
    return dict(
        subgrad_rows=S * T * N * len(spec.buckets),
        events=events,
        rejected=int(result.rejected_stale.sum()),
        walk_ranks=S * T * walk,
    )
