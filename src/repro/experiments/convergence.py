"""Batched convergence sweeps: the full DSAG/SAG/SGD update rule over all
scenarios of a :class:`~repro.latency.model.FleetTraces` draw at once.

PR 1's sweep engine batched the §7 *iteration-time* dynamics; the paper's
headline claims (DSAG up to ~50% faster than SAG, >2x faster than coded
methods) are about *time-to-suboptimality*, which needs the whole training
loop: gradient cache, coverage scaling ξ, the §5.1 margin, stale
integration, and the §6 load balancer.  This module runs that loop for all
``[S]`` scenarios simultaneously:

* the event dynamics of each iteration are resolved with the same ``[S, N]``
  array algebra as :func:`repro.experiments.sweep.replay_batch` (idle/busy
  resolution, w-th order statistic, margin deadline, queue feedback);
* subgradients are evaluated as ``[S, ...]`` stacks through
  :meth:`~repro.core.problems.FiniteSumProblem.subgradient_blocks` — one JAX
  dispatch per iteration instead of one per (scenario, worker) task;
* per-scenario cache state lives in a
  :class:`~repro.core.gradient_cache.BatchedGradientCache` (shared interval
  slots, ``[S, ...]`` sums);
* the §6 loop is batched end to end: per-scenario
  :class:`~repro.latency.profiler.LatencyProfiler` moments feed ``[S, N]``
  :class:`~repro.lb.optimizer.OptimizerInputs`, and
  :meth:`~repro.lb.optimizer.LoadBalanceOptimizer.optimize_batch` balances
  every due scenario in one call.

The load-bearing property (pinned by ``tests/test_convergence.py``): for
every scenario ``s``, the batched run is *bit-exact* against the scalar
:class:`~repro.cluster.simulator.TrainingSimulator` replaying the same
trace through ``TraceLatencySource(traces, s)`` — times, suboptimality,
fresh counts, per-worker latencies, cache telemetry, and the
load-balancing republication schedule.  The batching is a reformulation of
the method, not an approximation of it.
"""

from __future__ import annotations

import dataclasses
import time
from collections.abc import Sequence

import jax
import numpy as np

from repro.cluster.simulator import (
    MethodConfig,
    RunHistory,
    TraceLatencySource,
    TrainingSimulator,
    effective_w,
    lb_ladder_for,
    make_optimizer_inputs,
    margin_deadline,
    task_finish_time,
)
from repro.core.gradient_cache import BatchedGradientCache, scenario_ranks
from repro.core.problems import FiniteSumProblem
from repro.experiments.engine import (
    CAP_AUTO_NO_HOST,
    CAP_PALLAS_HOST,
    EngineCapability,
    EngineCapabilityError,
    EngineConfig,
    as_engine_config,
)
from repro.latency.model import ClusterLatencyModel, FleetTraces, sample_fleet
from repro.latency.profiler import MomentBuffer
from repro.lb.optimizer import LoadBalanceOptimizer
from repro.lb.partitioner import _align, p_start, p_stop


@dataclasses.dataclass
class ConvergenceBatchResult:
    """Per-scenario training traces of one batched convergence run.

    Scenario ``s`` of every array equals the corresponding field of the
    :class:`RunHistory` a scalar ``TrainingSimulator`` produces on the same
    trace, bit for bit.
    """

    times: np.ndarray  # [S, T]
    suboptimality: np.ndarray  # [S, T] (NaN where not evaluated)
    fresh_counts: np.ndarray  # [S, T]
    per_worker_latency: np.ndarray  # [S, T, N] (see RunHistory semantics)
    repartition_events: list[list[float]]  # per scenario
    evictions: np.ndarray  # [S]
    rejected_stale: np.ndarray  # [S]
    engine: str = "host"  # the engine that ran: "scan" or "host"

    @property
    def num_scenarios(self) -> int:
        return self.times.shape[0]

    def history(self, s: int) -> RunHistory:
        """Scenario ``s`` as a scalar :class:`RunHistory`."""
        return RunHistory(
            times=self.times[s],
            suboptimality=self.suboptimality[s],
            fresh_counts=self.fresh_counts[s],
            per_worker_latency=self.per_worker_latency[s],
            repartition_events=list(self.repartition_events[s]),
            evictions=int(self.evictions[s]),
            rejected_stale=int(self.rejected_stale[s]),
        )

    def time_to_gap(self, gap: float) -> np.ndarray:
        """[S] first sim time at which suboptimality <= gap (inf if never)."""
        ok = np.nan_to_num(self.suboptimality, nan=np.inf) <= gap
        any_ok = ok.any(axis=1)
        first = np.argmax(ok, axis=1)
        out = np.full(self.num_scenarios, np.inf)
        rows = np.flatnonzero(any_ok)
        out[rows] = self.times[rows, first[rows]]
        return out


def run_convergence_batch(
    problem: FiniteSumProblem,
    traces: FleetTraces,
    config: MethodConfig,
    num_iterations: int,
    *,
    cost_scale: float = 1.0,
    eval_every: int | None = None,
    seed: int = 0,
    engine: EngineConfig | None = None,
) -> ConvergenceBatchResult:
    """Train ``config`` on every scenario of ``traces`` simultaneously.

    Equivalent to ``TrainingSimulator(problem, cluster, config,
    latency_source=TraceLatencySource(traces, s), ...).run(num_iterations)``
    for each scenario ``s`` — resolved with ``[S, N]`` array operations and
    batched JAX subgradient evaluation instead of a per-event Python loop.

    ``engine`` is an :class:`~repro.experiments.engine.EngineConfig`
    selecting the implementation (default: ``EngineConfig()``):

    * ``kind="scan"`` — the fused ``jax.lax.scan`` engine
      (:func:`repro.experiments.fused.run_convergence_scan`): the whole
      iteration body (event algebra, subgradients, cache scatter, iterate
      update, suboptimality, and the §6 load balancer) is one jittable
      function scanned over iterations; §6 slot universes above the
      config's ``slot_budget`` run with the tiled active-slot cache, and
      ``mesh`` / ``num_devices`` shard the scenario axis over devices.
      Raises :class:`~repro.experiments.engine.EngineCapabilityError` for
      the one genuinely unsupported case
      (:func:`repro.experiments.fused.scan_capability`).
    * ``kind="host"`` — the numpy-driven batched loop below (one Python
      iteration per training iteration, batched kernels inside; the
      device mesh does not apply here).
    * ``kind="auto"`` (default) — ``"scan"`` unless the capability report
      says unsupported, which routes to ``"host"`` on the CPU and raises
      ``CAP_AUTO_NO_HOST`` on an accelerator (never a silent host run).

    Legacy ``engine="auto"|"scan"|"host"`` strings still work as
    deprecated aliases (``DeprecationWarning``).  ``eval_every`` defaults
    to the engine config's cadence (itself defaulting to 1); passing it
    explicitly overrides both.

    All engines are bit-exact against each other and against the scalar
    simulator (pinned by ``tests/test_convergence.py`` /
    ``tests/test_fused.py`` / ``tests/test_lb_scan.py`` /
    ``tests/test_sharded.py``).
    """
    eng = as_engine_config(engine, _stacklevel=3)
    if eval_every is None:
        eval_every = eng.eval_every
    kind = eng.kind
    if kind == "auto":
        from repro.experiments.fused import scan_capability

        cap = scan_capability(
            problem, config, traces.num_workers, slot_budget=eng.slot_budget
        )
        if not cap.supported and jax.default_backend() != "cpu":
            raise EngineCapabilityError(
                EngineCapability(
                    supported=False,
                    code=CAP_AUTO_NO_HOST,
                    detail=(
                        f"kind='auto' will not route to the host engine on "
                        f"{jax.default_backend()}: {cap.detail} (pass "
                        f"EngineConfig(kind='host') to run it on the host)"
                    ),
                )
            )
        kind = "scan" if cap.supported else "host"
    if kind == "host" and eng.kernel_backend == "pallas":
        # the host loop drives the problem's numpy wrappers — there is no
        # Pallas path to take, so honoring the request is impossible
        raise EngineCapabilityError(
            EngineCapability(
                supported=False,
                code=CAP_PALLAS_HOST,
                detail=(
                    "kernel_backend='pallas' requires the fused scan "
                    "engine; this config resolved to kind='host' "
                    "(pass EngineConfig(kind='scan') or drop the Pallas "
                    "backend)"
                ),
            )
        )
    if kind == "scan":
        from repro.experiments.fused import run_convergence_scan

        return run_convergence_scan(
            problem,
            traces,
            config,
            num_iterations,
            cost_scale=cost_scale,
            eval_every=eval_every,
            seed=seed,
            engine=eng,
        )
    S, N = traces.num_scenarios, traces.num_workers
    n = problem.num_samples
    T = num_iterations
    cfg = config
    if T > traces.horizon:
        raise ValueError(
            f"traces hold {traces.horizon} draws/worker but {T} iterations requested"
        )
    w_wait = effective_w(cfg, N)
    comp_scale = cost_scale * (1.0 / cfg.code_rate if cfg.name == "coded" else 1.0)
    process_full = cfg.name in ("gd", "coded")
    margin_eff = cfg.margin if (cfg.uses_margin and cfg.margin > 0) else 0.0

    V0 = problem.init(seed)
    vshape = V0.shape
    V = np.repeat(V0[None], S, axis=0)
    bshape = (S,) + (1,) * len(vshape)  # per-scenario scalar broadcast
    cache = (
        BatchedGradientCache(S, n, np.zeros(vshape, dtype=np.float64))
        if cfg.uses_cache
        else None
    )

    # -- batched subpartition state (paper §6.3, one Subpartitioner per
    # (scenario, worker) flattened into integer arrays) --------------------
    base_start = np.array([p_start(n, N, i + 1) for i in range(N)], dtype=np.int64)
    base_stop = np.array([p_stop(n, N, i + 1) for i in range(N)], dtype=np.int64)
    n_local = base_stop - base_start + 1
    sub_p = np.broadcast_to(
        np.minimum(cfg.subpartitions, n_local), (S, N)
    ).copy()
    sub_k = np.ones((S, N), dtype=np.int64)
    pending_p = np.full((S, N), -1, dtype=np.int64)

    free_at = np.zeros((S, N))
    iter_end = np.zeros(S)
    draw_idx = np.zeros((S, N), dtype=np.int64)

    # in-flight task per (scenario, worker): what the busy worker is
    # computing right now (value captured from the assignment iterate)
    flight_lo = np.zeros((S, N), dtype=np.int64)
    flight_hi = np.zeros((S, N), dtype=np.int64)
    flight_titer = np.full((S, N), -1, dtype=np.int64)
    flight_val: np.ndarray | None = None  # allocated at first evaluation
    flight_comp = np.zeros((S, N))
    flight_comm = np.zeros((S, N))
    flight_assigned = np.zeros((S, N))

    times = np.zeros((S, T))
    subopt = np.full((S, T), np.nan)
    fresh_counts = np.zeros((S, T), dtype=np.int64)
    lat_matrix = np.full((S, T, N), np.nan)
    repartition_events: list[list[float]] = [[] for _ in range(S)]

    needs_values = cfg.name in ("gd", "sgd", "sag", "dsag")
    lbbuf = MomentBuffer(S, N, T) if cfg.load_balance else None
    lb = (
        LoadBalanceOptimizer(seed=seed, ladder=lb_ladder_for(cfg, n_local))
        if cfg.load_balance
        else None
    )
    h_min = np.full(S, np.nan)
    next_lb = np.full(S, cfg.lb_startup_delay if cfg.load_balance else np.inf)
    current_p = np.full((S, N), cfg.subpartitions, dtype=np.int64)
    n_i = n_local.astype(np.float64)

    churn = traces.churn
    alive: np.ndarray | None = None
    if churn is not None:
        prev_row = churn.row_at(np.zeros(S))
        lb_since = np.asarray(churn.boundary_before(prev_row), dtype=np.float64)
    else:
        lb_since = None

    for t in range(T):
        assign = iter_end.copy()
        if churn is not None:
            # liveness sampled once per iteration at assignment time (same
            # convention as the scalar simulator and replay_batch)
            alive = churn.alive_at(assign)
            rows_now = churn.row_at(assign)
            changed = rows_now != prev_row
            if changed.any() and cfg.load_balance:
                # fleet changed: drop the contribution floor so the §6
                # optimizer re-baselines, and re-profile from the boundary
                h_min = np.where(changed, np.nan, h_min)
                lb_since = np.where(
                    changed, churn.boundary_before(rows_now), lb_since
                )
            prev_row = rows_now
            # dead at assignment: the in-flight completion never happens —
            # the worker goes idle with no stale event, no cache write, no
            # profiler sample, no latency attribution
            free_at = np.where(alive, free_at, assign[:, None])
            if cache is not None:
                # clear dead workers' §5 entries; np.nonzero is row-major so
                # within each scenario the clears run in worker order ==
                # interval-start order (the canonical churn float order)
                for s, i in zip(*np.nonzero(~alive)):
                    cache.clear_range(
                        int(s), int(base_start[i]), int(base_stop[i])
                    )
        idle = free_at <= assign[:, None]

        # -- Algorithm-2 alignment for pending repartitions (tentative: the
        # new (p, k) is committed only for workers that actually start) ----
        pend = pending_p >= 0
        if pend.any():
            cand_p = sub_p.copy()
            cand_k = sub_k.copy()
            for s, i in zip(*np.nonzero(pend)):
                p_req = int(min(max(1, pending_p[s, i]), n_local[i]))
                if p_req != sub_p[s, i]:
                    _, k_new = _align(
                        int(n_local[i]), int(sub_p[s, i]), p_req, int(sub_k[s, i])
                    )
                    cand_p[s, i] = p_req
                    cand_k[s, i] = k_new
        else:
            cand_p, cand_k = sub_p, sub_k

        if process_full:
            lo = np.broadcast_to(base_start, (S, N))
            hi = np.broadcast_to(base_stop, (S, N))
        else:
            lo = base_start[None, :] + (cand_k - 1) * n_local[None, :] // cand_p
            hi = base_start[None, :] + cand_k * n_local[None, :] // cand_p - 1
        cost = problem.compute_cost_batch(lo, hi) * comp_scale

        # -- event resolution (same algebra as replay_batch) ---------------
        start = np.where(idle, assign[:, None], free_at)
        comm_d, comp_d = traces.task_latency_parts(draw_idx, start, cost)
        finish = task_finish_time(start, comp_d, comm_d)
        if churn is None:
            tau_w = np.partition(finish, w_wait - 1, axis=1)[:, w_wait - 1]
        else:
            # dead workers never contribute finish times; wait for
            # min(w, #alive) of the living fleet (sort+gather picks the same
            # element as partition, so all-alive stays bit-identical)
            finish_eff = np.where(alive, finish, np.inf)
            w_eff = np.minimum(w_wait, alive.sum(axis=1))
            tau_w = np.sort(finish_eff, axis=1)[np.arange(S), w_eff - 1]
        if margin_eff > 0.0:
            deadline = margin_deadline(tau_w, assign, margin_eff)
        else:
            deadline = tau_w
        started = idle | (free_at <= deadline[:, None])
        if churn is not None:
            started &= alive
        fresh = started & (finish <= deadline[:, None])
        stale_done = (~idle) & (free_at <= deadline[:, None])
        fresh_counts[:, t] = fresh.sum(axis=1)

        stale_ev = np.where(stale_done, free_at, -np.inf)
        fresh_ev = np.where(fresh, finish, -np.inf)
        iter_end = np.maximum(
            np.maximum(stale_ev.max(axis=1), fresh_ev.max(axis=1)), tau_w
        )
        times[:, t] = iter_end

        st_s, st_w = np.nonzero(stale_done)
        f_s, f_w = np.nonzero(fresh)
        # latency attribution by the task's own iteration (RunHistory)
        lat_matrix[st_s, flight_titer[st_s, st_w], st_w] = (
            flight_comp[st_s, st_w] + flight_comm[st_s, st_w]
        )
        lat_matrix[f_s, t, f_w] = comp_d[f_s, f_w] + comm_d[f_s, f_w]

        # -- §6.1 profiler feed (before flight state is overwritten): one
        # task-slot sample per observed completion, read back through the
        # shared jittable window-moments kernel -----------------------------
        if cfg.load_balance:
            lbbuf.record(
                st_s,
                st_w,
                flight_titer[st_s, st_w],
                free_at[st_s, st_w],
                free_at[st_s, st_w] - flight_assigned[st_s, st_w],
                flight_comp[st_s, st_w],
            )
            lbbuf.record(
                f_s,
                f_w,
                np.full(f_s.size, t, np.int64),
                finish[f_s, f_w],
                finish[f_s, f_w] - assign[f_s],
                comp_d[f_s, f_w],
            )

        # -- batched subgradient evaluation --------------------------------
        # dsag integrates stale results, so every started task's value is
        # eventually consumed; the other methods only ever use fresh values
        if cfg.name == "dsag":
            need = started
        elif needs_values:
            need = fresh
        else:  # coded recomputes the exact gradient; task values are unused
            need = np.zeros_like(fresh)
        val_index = np.full((S, N), -1, dtype=np.int64)
        vals: np.ndarray | None = None
        if need.any():
            # one masked-width dispatch for the whole mixed-width task batch
            # (bit-identical to per-width bucketing — pinned by tests)
            v_s, v_w = np.nonzero(need)
            val_index[v_s, v_w] = np.arange(v_s.size)
            vals = problem.subgradient_blocks_masked(
                V[v_s], lo[v_s, v_w], hi[v_s, v_w]
            )

        # -- cache / gradient-accumulator updates in event-time order ------
        if cfg.uses_cache:
            if cfg.accepts_stale:
                ev_s = np.concatenate([st_s, f_s])
                ev_w = np.concatenate([st_w, f_w])
                ev_time = np.concatenate([free_at[st_s, st_w], finish[f_s, f_w]])
                ev_lo = np.concatenate([flight_lo[st_s, st_w], lo[f_s, f_w]])
                ev_hi = np.concatenate([flight_hi[st_s, st_w], hi[f_s, f_w]])
                ev_iter = np.concatenate(
                    [flight_titer[st_s, st_w], np.full(f_s.size, t, np.int64)]
                )
                n_stale = st_s.size
            else:  # sag: fresh results only
                ev_s, ev_w = f_s, f_w
                ev_time = finish[f_s, f_w]
                ev_lo, ev_hi = lo[f_s, f_w], hi[f_s, f_w]
                ev_iter = np.full(f_s.size, t, np.int64)
                n_stale = 0
            if ev_s.size:
                if n_stale:
                    ev_vals = np.concatenate(
                        [
                            flight_val[ev_s[:n_stale], ev_w[:n_stale]],
                            vals[val_index[ev_s[n_stale:], ev_w[n_stale:]]],
                        ]
                    )
                else:
                    ev_vals = vals[val_index[ev_s, ev_w]]
                # time-ordered masked scatters instead of a per-event loop
                # (per-scenario §5 semantics preserved bit for bit)
                order = np.argsort(ev_time, kind="stable")
                cache.insert_events(
                    ev_s[order],
                    ev_lo[order],
                    ev_hi[order],
                    ev_iter[order],
                    ev_vals[order],
                )
        elif cfg.name in ("gd", "sgd"):
            grad_acc = np.zeros((S,) + vshape, dtype=np.float64)
            covered = np.zeros(S, dtype=np.int64)
            if f_s.size:
                order = np.argsort(finish[f_s, f_w], kind="stable")
                os_, ow_ = f_s[order], f_w[order]
                ranks = scenario_ranks(os_)
                for r in range(int(ranks.max()) + 1):
                    sel = ranks == r  # <= one event per scenario: masked add
                    grad_acc[os_[sel]] += vals[val_index[os_[sel], ow_[sel]]]
            np.add.at(covered, f_s, hi[f_s, f_w] - lo[f_s, f_w] + 1)

        # -- commit worker state for started tasks --------------------------
        sub_p = np.where(started, cand_p, sub_p)
        if process_full:
            sub_k = np.where(started, cand_k, sub_k)
        else:
            sub_k = np.where(started, cand_k % cand_p + 1, sub_k)
        pending_p = np.where(started, -1, pending_p)
        free_at = np.where(started, finish, free_at)
        draw_idx += started
        flight_lo = np.where(started, lo, flight_lo)
        flight_hi = np.where(started, hi, flight_hi)
        flight_titer = np.where(started, t, flight_titer)
        flight_comp = np.where(started, comp_d, flight_comp)
        flight_comm = np.where(started, comm_d, flight_comm)
        flight_assigned = np.where(started, assign[:, None], flight_assigned)
        if cfg.name == "dsag" and vals is not None:
            if flight_val is None:
                flight_val = np.zeros((S, N) + vshape, dtype=vals.dtype)
            v_s, v_w = np.nonzero(need)
            flight_val[v_s, v_w] = vals

        # -- iterate update -------------------------------------------------
        if cfg.uses_cache:
            xi = np.maximum(cache.coverage, 1e-12)
            grad = cache.sums / xi.reshape(bshape) + problem.regularizer_grad(V)
        elif cfg.name == "coded":
            g = problem.subgradient_blocks(
                V, np.ones(S, np.int64), np.full(S, n, np.int64)
            ).astype(np.float64)
            grad = g + problem.regularizer_grad(V)
        elif cfg.name == "gd":
            grad = grad_acc + problem.regularizer_grad(V)
        else:  # sgd: scale the partial sum by observed coverage
            xi = np.maximum(covered / n, 1e-12)
            grad = grad_acc / xi.reshape(bshape) + problem.regularizer_grad(V)
        V = problem.project_batch((V - cfg.eta * grad).astype(V.dtype, copy=False))

        if t % eval_every == 0 or t == T - 1:
            # one [S] JAX dispatch (the scalar simulator delegates to the
            # same kernel at S = 1, so the bits agree)
            subopt[:, t] = problem.suboptimality_batch(V)

        # -- load balancing (batched §6 background loop) --------------------
        if cfg.load_balance:
            due = iter_end >= next_lb
            if due.any():
                e_cm, v_cm, e_cp, v_cp, cnt = lbbuf.moments(
                    iter_end, since=lb_since
                )
                ready = cnt >= 1
                if churn is not None:
                    # dead workers can't produce samples — don't wait on them
                    ready = ready | ~alive
                ready = ready.all(axis=1)
                next_lb = np.where(due, iter_end + cfg.lb_interval, next_lb)
                act = due & ready
                if act.any():
                    inputs = make_optimizer_inputs(
                        e_cm, v_cm, e_cp, v_cp,
                        np.broadcast_to(n_i, (S, N)),
                        w_wait,
                        cfg.margin,
                    )
                    p_new, h_min, _, publish = lb.update_batch(
                        current_p, inputs, h_min, active=act, alive=alive
                    )
                    for s in np.flatnonzero(publish):
                        changed = p_new[s] != current_p[s]
                        pending_p[s, changed] = p_new[s, changed]
                        current_p[s] = p_new[s]
                        repartition_events[s].append(float(iter_end[s]))

    return ConvergenceBatchResult(
        times=times,
        suboptimality=subopt,
        fresh_counts=fresh_counts,
        per_worker_latency=lat_matrix,
        repartition_events=repartition_events,
        evictions=cache.evictions.copy() if cache is not None else np.zeros(S, np.int64),
        rejected_stale=(
            cache.rejected_stale.copy() if cache is not None else np.zeros(S, np.int64)
        ),
    )


# ---------------------------------------------------------------------------
# Convergence-sweep driver (Figs. 10-12 made cheap enough for CI)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ConvergenceSweepOutcome:
    """All methods' batched convergence runs on one shared trace draw."""

    results: dict[str, ConvergenceBatchResult]
    methods: dict[str, MethodConfig]
    traces: FleetTraces
    problem: FiniteSumProblem
    cluster: ClusterLatencyModel
    num_iterations: int
    cost_scale: float
    eval_every: int
    seed: int
    engine_seconds: float

    def time_to_gap(self, method: str, gap: float) -> np.ndarray:
        return self.results[method].time_to_gap(gap)


def default_convergence_methods(
    n_workers: int,
    *,
    w: int,
    eta: float = 0.25,
    subpartitions: int = 10,
    load_balance_dsag: bool = False,
) -> dict[str, MethodConfig]:
    """The paper's §7 time-to-gap columns: DSAG, SAG (w = N), SGD, coded."""
    methods = {
        "dsag": MethodConfig(
            name="dsag", w=w, eta=eta, subpartitions=subpartitions,
            load_balance=load_balance_dsag,
        ),
        "sag": MethodConfig(name="sag", w=n_workers, eta=eta,
                            subpartitions=subpartitions),
        "sgd": MethodConfig(name="sgd", w=w, eta=eta, subpartitions=subpartitions),
        "coded": MethodConfig(name="coded", w=0, eta=1.0,
                              subpartitions=subpartitions),
    }
    return methods


def run_convergence_sweep(
    problem: FiniteSumProblem,
    cluster: ClusterLatencyModel,
    methods: dict[str, MethodConfig],
    *,
    n_scenarios: int = 10,
    num_iterations: int = 100,
    cost_scale: float = 1.0,
    eval_every: int = 1,
    regime=None,
    burst_rate: float | None = None,
    burst_factor_mean: float | None = None,
    burst_duration_mean: float | None = None,
    seed: int = 0,
    engine: EngineConfig | None = None,
) -> ConvergenceSweepOutcome:
    """Run every method over one shared scenario batch (common random
    numbers: all methods see the same latency draws, like the paper's
    paired comparisons on one cluster).

    ``regime`` is an optional :class:`~repro.experiments.grid.BurstRegime`
    (the iteration-time grid's burst environments); explicit ``burst_*``
    keywords override its fields.  ``engine`` (an
    :class:`~repro.experiments.engine.EngineConfig` or a deprecated legacy
    string) is forwarded to :func:`run_convergence_batch` per method.
    """
    if regime is not None:
        burst_rate = regime.rate if burst_rate is None else burst_rate
        burst_factor_mean = (
            regime.factor_mean if burst_factor_mean is None else burst_factor_mean
        )
        burst_duration_mean = (
            regime.duration_mean if burst_duration_mean is None else burst_duration_mean
        )
    with jax.profiler.TraceAnnotation("repro.sweep", seed=seed):
        with jax.profiler.TraceAnnotation("repro.sample_fleet"):
            traces = sample_fleet(
                cluster,
                n_scenarios,
                num_iterations,
                burst_rate=burst_rate,
                burst_factor_mean=burst_factor_mean,
                burst_duration_mean=burst_duration_mean,
                seed=seed + 1,
            )
        eng = as_engine_config(engine, _stacklevel=3)
        results: dict[str, ConvergenceBatchResult] = {}
        t0 = time.perf_counter()
        for name, cfg in methods.items():
            with jax.profiler.TraceAnnotation("repro.method", method=name):
                results[name] = run_convergence_batch(
                    problem,
                    traces,
                    cfg,
                    num_iterations,
                    cost_scale=cost_scale,
                    eval_every=eval_every,
                    seed=seed,
                    engine=eng,
                )
        engine_seconds = time.perf_counter() - t0
        return ConvergenceSweepOutcome(
            results=results,
            methods=dict(methods),
            traces=traces,
            problem=problem,
            cluster=cluster,
            num_iterations=num_iterations,
            cost_scale=cost_scale,
            eval_every=eval_every,
            seed=seed,
            engine_seconds=engine_seconds,
        )


#: Calibrated parameters of the paper-scale PCA convergence sweep (Figs.
#: 10-12 at the genomics matrix's actual row count).  ``gap=1e-4`` sits in
#: the regime where ignoring-stragglers SGD has stalled but the
#: cache-based methods keep converging — the paper's reason for DSAG —
#: while DSAG reaches it ~2.5-3x before SAG and the coded bound
#: (ordering pinned by the committed ``BENCH_convergence.json``).
PAPER_SCALE_PCA = dict(
    n_rows=50_000,
    n_cols=96,
    k=3,
    n_workers=50,
    subpartitions=5,
    w=40,
    eta=0.9,
    gap=1e-4,
    n_scenarios=4,
    num_iterations=80,
    eval_every=4,
)


def make_paper_scale_pca(
    n_rows: int = PAPER_SCALE_PCA["n_rows"],
    n_cols: int = PAPER_SCALE_PCA["n_cols"],
    k: int = PAPER_SCALE_PCA["k"],
    seed: int = 0,
):
    """The n≈50k synthetic genomics matrix as a :class:`PCAProblem`."""
    from repro.core.problems import PCAProblem, make_genomics_like_matrix

    return PCAProblem(X=make_genomics_like_matrix(n_rows, n_cols, seed=seed), k=k)


def paper_scale_pca_sweep(
    *,
    scale: float = 1.0,
    seed: int = 0,
    regime=None,
    engine: EngineConfig | None = None,
    n_scenarios: int | None = None,
) -> tuple[ConvergenceSweepOutcome, float]:
    """Run the calibrated paper-scale PCA convergence sweep.

    ``scale`` shrinks the grid uniformly (rows, iterations, scenarios) for
    smoke tests; 1.0 is the benchmark configuration.  ``n_scenarios``
    overrides the scenario count alone (the ``pca_grid_sharded`` bench
    column runs 10x the calibrated grid through the sharded scan).
    Returns ``(outcome, gap)`` with ``gap`` the calibrated time-to-gap
    threshold.
    """
    from repro.experiments.grid import HEAVY_BURSTS
    from repro.latency.model import make_heterogeneous_cluster

    p = PAPER_SCALE_PCA
    n_rows = max(int(p["n_rows"] * scale), 512)
    n_iter = max(int(p["num_iterations"] * scale), 10)
    n_scen = (
        int(n_scenarios)
        if n_scenarios is not None
        else max(int(p["n_scenarios"] * scale), 2)
    )
    prob = make_paper_scale_pca(n_rows=n_rows, seed=seed)
    N, sp = p["n_workers"], p["subpartitions"]
    c_task = prob.compute_cost(1, max(prob.num_samples // (N * sp), 1))
    cluster = make_heterogeneous_cluster(N, seed=seed, burst_rate=0.0, load_unit=c_task)
    methods = default_convergence_methods(
        N, w=p["w"], eta=p["eta"], subpartitions=sp
    )
    outcome = run_convergence_sweep(
        prob,
        cluster,
        methods,
        n_scenarios=n_scen,
        num_iterations=n_iter,
        eval_every=p["eval_every"],
        regime=regime if regime is not None else HEAVY_BURSTS,
        seed=seed,
        engine=engine,
    )
    return outcome, float(p["gap"])


def scalar_convergence_run(
    outcome: ConvergenceSweepOutcome, method: str, scenario: int
) -> RunHistory:
    """Ground truth: one scenario through the scalar TrainingSimulator."""
    sim = TrainingSimulator(
        outcome.problem,
        outcome.cluster,
        outcome.methods[method],
        cost_scale=outcome.cost_scale,
        eval_every=outcome.eval_every,
        seed=outcome.seed,
        latency_source=TraceLatencySource(outcome.traces, scenario),
    )
    return sim.run(outcome.num_iterations)


def scalar_convergence_seconds(
    outcome: ConvergenceSweepOutcome,
    *,
    methods: Sequence[str] | None = None,
    max_scenarios: int | None = None,
) -> tuple[float, float]:
    """Wall-clock of the same grid through the scalar training simulator.

    Replays ``max_scenarios`` scenarios (all by default) of each method
    through :class:`TrainingSimulator` on the same traces.  Returns
    ``(measured_seconds, extrapolated_seconds)`` where the extrapolation
    scales the measured subset up to the full grid — the honest baseline
    when the full scalar grid would take minutes.
    """
    names = list(methods) if methods is not None else list(outcome.methods)
    S = outcome.traces.num_scenarios
    S_run = S if max_scenarios is None else min(max_scenarios, S)
    t0 = time.perf_counter()
    for name in names:
        for s in range(S_run):
            scalar_convergence_run(outcome, name, s)
    measured = time.perf_counter() - t0
    return measured, measured * (S / max(S_run, 1))
