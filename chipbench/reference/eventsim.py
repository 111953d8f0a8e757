"""Plain reference of the simulated fleet and the methods it runs.

Written from the paper (arXiv:2111.13877) and the semantics the
configuration states, independently of the program:

* §3 latency model: worker i's task of computational load c takes
  comm + comp, with comm ~ Gamma(i) and comp = unit * c * slowdown * burst,
  unit ~ Gamma(i) per unit load, and multiplicative bursts (§3.2) that
  arrive as an alternating renewal process.  Each worker consumes its
  draws in order, one per started task.
* §4.2 worker model: a worker is busy or idle and holds at most one queued
  task (a length-1 FILO queue); an idle worker starts the new iterate's
  task at assignment, a busy one queues it and starts it when its current
  task returns.
* The coordinator waits for the w-th fresh result of the iteration; DSAG
  then keeps collecting for ``margin`` times that wait (§5.1).  Results
  are processed in order of arrival; ties go to the task started first.
* §5 gradient cache: keyed by sample interval, tagged with the iteration
  of the iterate it was computed from.  An arrival that overlaps an entry
  at least as recent is discarded; otherwise the overlapping entries are
  evicted and it is inserted.  DSAG inserts stale results too, SAG only
  fresh ones.  The update is V <- G(V - eta (H / xi + grad R(V))) with H
  the cache sum and xi the covered fraction of the samples.  SGD uses
  only the fresh results of the iteration, scaled by their coverage;
  coded computing recovers the exact gradient once ceil(r N) results are
  in, at 1/r the load per worker.

Event times and the cache sum are kept in ``hi`` (float64, or float32 for
the control); subgradient values and the iterate are float32.
"""

from __future__ import annotations

import bisect
import dataclasses
import heapq
import math

import numpy as np


# ---------------------------------------------------------------------------
# Fleet and traces
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Fleet:
    """Per-worker gamma parameters (shape, scale) and slowdowns."""

    comm_shape: np.ndarray
    comm_scale: np.ndarray
    comp_shape: np.ndarray
    comp_scale: np.ndarray
    slowdown: np.ndarray

    @property
    def num_workers(self) -> int:
        return int(self.comm_shape.size)


def _gamma_from_mean_var(mean: float, var: float) -> tuple[float, float]:
    var = max(var, 1e-18)
    return mean * mean / var, var / mean


def make_fleet(num_workers: int, comm_range, comp_range, cv_comm: float,
               cv_comp: float, load_unit: float, seed: int) -> Fleet:
    """Heterogeneous fleet: per-worker means uniform over the measured ranges
    (comm then comp, worker by worker), fixed coefficients of variation."""
    rng = np.random.default_rng(seed)
    params = []
    for _ in range(num_workers):
        e_y = rng.uniform(*comm_range)
        e_z = rng.uniform(*comp_range) / load_unit
        params.append(
            _gamma_from_mean_var(e_y, (cv_comm * e_y) ** 2)
            + _gamma_from_mean_var(e_z, (cv_comp * e_z) ** 2)
        )
    p = np.array(params, dtype=np.float64)
    return Fleet(p[:, 0], p[:, 1], p[:, 2], p[:, 3], np.ones(num_workers))


@dataclasses.dataclass(frozen=True)
class Traces:
    comm: np.ndarray  # [S, N, K]
    comp_unit: np.ndarray  # [S, N, K]
    burst_start: np.ndarray  # [S, N, M]
    burst_end: np.ndarray
    burst_factor: np.ndarray


def sample_traces(fleet: Fleet, scenarios: int, horizon: int, *, burst_rate: float,
                  burst_factor_mean: float, burst_duration_mean: float,
                  seed: int, max_bursts: int = 4096) -> Traces:
    """All draws of a sweep: gammas first (comm, then per-unit comp), then
    the burst windows out to twice the slowest worker's expected makespan."""
    S, N, K = scenarios, fleet.num_workers, horizon
    rng = np.random.default_rng(seed)
    comm = rng.gamma(fleet.comm_shape[None, :, None], fleet.comm_scale[None, :, None],
                     size=(S, N, K))
    comp_unit = rng.gamma(fleet.comp_shape[None, :, None], fleet.comp_scale[None, :, None],
                          size=(S, N, K))
    rates = np.full(N, burst_rate, dtype=np.float64)
    f_means = np.full(N, burst_factor_mean, dtype=np.float64)
    d_means = np.full(N, burst_duration_mean, dtype=np.float64)
    if np.all(rates <= 0.0):
        empty = np.zeros((S, N, 0))
        return Traces(comm, comp_unit, empty, empty.copy(), empty.copy())
    per_task = np.max(fleet.comm_shape * fleet.comm_scale
                      + (fleet.comp_shape * fleet.comp_scale) * 1.0 * fleet.slowdown)
    duty = (rates * d_means) / (1.0 + rates * d_means)
    inflation = 1.0 + float(np.max(duty * (f_means - 1.0)))
    time_horizon = 2.0 * K * float(per_task) * inflation
    mean_cycle = 1.0 / float(np.max(rates)) + float(np.min(d_means))
    M = int(math.ceil(1.5 * time_horizon / mean_cycle) + 6)
    if M > max_bursts:
        raise ValueError(f"{M} burst windows needed, more than {max_bursts}")
    scale = np.where(rates > 0.0, 1.0 / np.maximum(rates, 1e-30), 1.0)
    gaps = rng.exponential(scale[None, :, None], size=(S, N, M))
    gaps = np.where(rates[None, :, None] > 0.0, gaps, np.inf)
    durations = rng.exponential(d_means[None, :, None], size=(S, N, M))
    in_burst_at_0 = rng.random((S, N)) < duty[None, :]
    gaps[:, :, 0] = np.where(in_burst_at_0, 0.0, gaps[:, :, 0])
    factors = 1.0 + rng.exponential(np.maximum(f_means - 1.0, 1e-12)[None, :, None],
                                    size=(S, N, M))
    starts = np.cumsum(gaps, axis=2) + np.cumsum(durations, axis=2) - durations
    return Traces(comm, comp_unit, starts, starts + durations, factors)


# ---------------------------------------------------------------------------
# Methods
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Method:
    name: str  # sgd | sag | dsag | coded
    w: int
    eta: float
    subpartitions: int
    margin: float = 0.02
    code_rate: float = 45.0 / 49.0

    def wait_for(self, N: int) -> int:
        if self.name == "coded":
            return int(math.ceil(self.code_rate * N))
        return min(self.w if self.w > 0 else N, N)


@dataclasses.dataclass
class Run:
    times: np.ndarray  # [T] time at which iteration t's update happened
    suboptimality: np.ndarray  # [T], NaN where not evaluated
    fresh_counts: np.ndarray  # [T]
    latency: np.ndarray  # [T, N] comp + comm of the task started at t, NaN if none came back
    evictions: int
    rejected_stale: int


class _Cache:
    """Interval-keyed cache with a running sum (paper §5)."""

    def __init__(self, n: int, shape, hi):
        self.n = n
        self.starts: list[int] = []
        self.entries: list[list] = []  # [start, stop, iteration, value]
        self.sum = np.zeros(shape, dtype=hi)
        self.covered = 0
        self.evictions = 0
        self.rejected = 0

    def insert(self, start: int, stop: int, it: int, value: np.ndarray) -> None:
        lo = bisect.bisect_left(self.starts, start)
        if lo > 0 and self.entries[lo - 1][1] >= start:
            lo -= 1
        hi = bisect.bisect_right(self.starts, stop)
        over = self.entries[lo:hi]
        if any(e[2] >= it for e in over):
            self.rejected += 1
            return
        if len(over) == 1 and over[0][0] == start and over[0][1] == stop:
            self.sum += value.astype(self.sum.dtype) - over[0][3].astype(self.sum.dtype)
            over[0][2], over[0][3] = it, value
            return
        for e in over:
            self.sum -= e[3].astype(self.sum.dtype)
            self.covered -= e[1] - e[0] + 1
        self.evictions += len(over)
        del self.entries[lo:hi]
        del self.starts[lo:hi]
        pos = bisect.bisect_left(self.starts, start)
        self.starts.insert(pos, start)
        self.entries.insert(pos, [start, stop, it, value])
        self.sum += value.astype(self.sum.dtype)
        self.covered += stop - start + 1


def simulate(problem, fleet: Fleet, traces: Traces, scenario: int, method: Method,
             iterations: int, eval_every: int, seed: int, hi=np.float64) -> Run:
    """One method on one scenario of the traces, event by event."""
    hi = np.dtype(hi)
    H = hi.type
    s = scenario
    n, N, T = problem.n, fleet.num_workers, iterations
    comm, comp_unit = traces.comm[s].astype(hi), traces.comp_unit[s].astype(hi)
    b_start, b_end = traces.burst_start[s].astype(hi), traces.burst_end[s].astype(hi)
    b_factor = traces.burst_factor[s].astype(hi)
    slowdown = fleet.slowdown.astype(hi)
    base = [((i * n) // N + 1, ((i + 1) * n) // N) for i in range(N)]
    p = [min(method.subpartitions, hi_ - lo_ + 1) for lo_, hi_ in base]
    k_next = [1] * N
    draws = [0] * N
    busy_until = [H(0)] * N
    queued: list = [None] * N
    name = method.name
    full = name == "coded"
    uses_cache = name in ("sag", "dsag")
    comp_scale = H(1.0 / method.code_rate if name == "coded" else 1.0)
    w_eff = method.wait_for(N)
    needs_values = name != "coded"

    def burst(i: int, t) -> object:
        idx = int(np.searchsorted(b_start[i], t, side="right")) - 1
        if idx >= 0 and t < b_end[i, idx]:
            return b_factor[i, idx]
        return H(1.0)

    heap: list = []
    seq = 0

    def start(i: int, task, now):
        nonlocal seq
        it, V_task, assigned = task
        if full:
            lo_, hi_ = base[i]
        else:
            nl = base[i][1] - base[i][0] + 1
            kk = k_next[i]
            lo_ = base[i][0] + ((kk - 1) * nl) // p[i]
            hi_ = base[i][0] + (kk * nl) // p[i] - 1
            k_next[i] = kk % p[i] + 1
        value = problem.subgradient(V_task, lo_, hi_) if needs_values else None
        load = H(problem.cost_per_row * (hi_ - lo_ + 1)) * comp_scale
        j = draws[i]
        draws[i] += 1
        comp = comp_unit[i, j] * load * slowdown[i] * burst(i, now)
        fin = now + (comp + comm[i, j])
        busy_until[i] = fin
        heapq.heappush(heap, (fin, seq, i, lo_, hi_, it, value, comp + comm[i, j]))
        seq += 1

    V = problem.init(seed)
    cache = _Cache(n, V.shape, hi) if uses_cache else None
    times = np.zeros(T, dtype=hi)
    subopt = np.full(T, np.nan)
    fresh_counts = np.zeros(T, dtype=np.int64)
    latency = np.full((T, N), np.nan, dtype=hi)
    now = H(0)
    for t in range(T):
        task = (t, V, now)
        for i in range(N):
            if busy_until[i] <= now:
                start(i, task, now)
            else:
                queued[i] = task
        fresh = 0
        fresh_values = []
        deadline = H(np.inf)
        iter_start = now
        while heap and (fresh < w_eff or heap[0][0] <= deadline):
            if heap[0][0] > deadline:
                break
            fin, _, i, lo_, hi_, it, value, lat = heapq.heappop(heap)
            now = fin
            latency[it, i] = lat
            if queued[i] is not None:
                task_q, queued[i] = queued[i], None
                start(i, task_q, now)
            else:
                busy_until[i] = now
            is_fresh = it == t
            if uses_cache:
                if is_fresh or name == "dsag":
                    cache.insert(lo_, hi_, it, value)
            elif is_fresh:
                fresh_values.append((hi_ - lo_ + 1, value))
            if is_fresh:
                fresh += 1
                if fresh == w_eff:
                    if name == "dsag" and method.margin > 0:
                        deadline = now + H(method.margin) * (now - iter_start)
                    else:
                        break
        if uses_cache:
            grad = cache.sum / max(cache.covered / n, 1e-12) + problem.regularizer_grad(V)
        elif name == "coded":
            grad = problem.subgradient(V, 1, n).astype(hi) + problem.regularizer_grad(V)
        else:  # sgd
            acc = np.zeros(V.shape, dtype=hi)
            for _, val in fresh_values:
                acc += val
            acc = acc / max(sum(m for m, _ in fresh_values) / n, 1e-12)
            grad = acc + problem.regularizer_grad(V)
        V = problem.project((V - method.eta * grad).astype(V.dtype))
        times[t] = now
        fresh_counts[t] = fresh
        if t % eval_every == 0 or t == T - 1:
            subopt[t] = problem.suboptimality(V)
    return Run(
        times=times,
        suboptimality=subopt,
        fresh_counts=fresh_counts,
        latency=latency,
        evictions=cache.evictions if cache else 0,
        rejected_stale=cache.rejected if cache else 0,
    )
