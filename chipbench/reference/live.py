"""Plain reference of one live DSAG training job (arXiv:2111.13877, §4.2, §5, §5.1, §6.3).

Written from the paper and the semantics the live trainer states,
independently of the program:

* **Tier 2, the coordinator.**  Each of the G groups is a §4.2 worker that
  computes its whole partition's gradient each task (subpartitions 1):
  busy or idle, with a length-1 FILO queue.  At step t every idle group
  starts the new iterate's task and every busy one queues it.  Results are
  taken in order of arrival (ties to the task started first) until the
  w-th fresh one, then for ``margin`` times the wait so far (§5.1).  A
  fresh result sets the group's ``mask`` bit; a stale one is accepted into
  the cache (DSAG: a group's results arrive in the order of its tasks, so
  each dominates the entry it replaces) and sets its ``flush`` bit.
  Latencies are the §3 model's, drawn from the traces as ``eventsim`` does.
* **Failure detection (§6.3).**  A group that has missed ``max_misses``
  deadlines in a row (no fresh result) is failed: its stale results are not
  taken into the cache, and its cache entry is evicted on the step it
  fails (``evict``).  A fresh result ends the failure.
* **Tier 1, the step.**  Every group's gradient is computed from the
  current iterate.  The cache holds one entry per group; a group with a
  fresh result stores it, a flushed one stores its oldest gradient still in
  flight (the one computed on the first step it missed since its last
  fresh result), an evicted one is emptied.  H is the sum of the entries,
  xi the share of groups whose entry is filled, and the iterate moves by
  V <- V - eta H / (xi G): plain gradient steps on the cache's estimate.

Event times are kept in ``hi``; the loss, H and the update are computed in
``hi`` from values stored in the configuration's float32 (``lo`` for the
control, see ``precision.py``): the data, each group gradient, each cache
entry and the iterate.
"""

from __future__ import annotations

import dataclasses
import heapq

import numpy as np

from chipbench.reference import eventsim
from chipbench.reference.precision import rounder


@dataclasses.dataclass
class Streams:
    """Per-step coordinator decisions of one job ([T, G] bool) and the
    virtual time at which each step's collection ended ([T])."""

    mask: np.ndarray
    flush: np.ndarray
    evict: np.ndarray
    times: np.ndarray


@dataclasses.dataclass
class Trajectory:
    """What one job's steps give: the mean group loss at each step's
    iterate, the norm of the update direction H / (xi G) the optimizer
    gets, and the final iterate."""

    loss: np.ndarray  # [T]
    grad_norm: np.ndarray  # [T]
    params: np.ndarray


def control_streams(fleet: eventsim.Fleet, traces: eventsim.Traces, loads, w: int,
                    margin: float, steps: int, max_misses: int, hi=np.float64) -> Streams:
    """Tier 2 over scenario 0 of ``traces``, with per-group loads ``loads``."""
    hi = np.dtype(hi)
    H = hi.type
    G, T = fleet.num_workers, steps
    comm, comp_unit = traces.comm[0].astype(hi), traces.comp_unit[0].astype(hi)
    b_start, b_end = traces.burst_start[0].astype(hi), traces.burst_end[0].astype(hi)
    b_factor = traces.burst_factor[0].astype(hi)
    slowdown = fleet.slowdown.astype(hi)
    loads = np.asarray(loads).astype(hi)

    def burst(i: int, t):
        idx = int(np.searchsorted(b_start[i], t, side="right")) - 1
        if idx >= 0 and t < b_end[i, idx]:
            return b_factor[i, idx]
        return H(1.0)

    draws = [0] * G
    busy_until = [H(0)] * G
    queued: list = [None] * G
    heap: list = []
    seq = 0

    def start(i: int, it: int, now):
        nonlocal seq
        j = draws[i]
        draws[i] += 1
        comp = comp_unit[i, j] * loads[i] * slowdown[i] * burst(i, now)
        fin = now + (comp + comm[i, j])
        busy_until[i] = fin
        heapq.heappush(heap, (fin, seq, i, it))
        seq += 1

    mask = np.zeros((T, G), bool)
    flush = np.zeros((T, G), bool)
    evict = np.zeros((T, G), bool)
    times = np.zeros(T, dtype=hi)
    misses = np.zeros(G, np.int64)
    failed = np.zeros(G, bool)
    now = H(0)
    for t in range(T):
        for i in range(G):
            if busy_until[i] <= now:
                start(i, t, now)
            else:
                queued[i] = t
        fresh = 0
        deadline = H(np.inf)
        iter_start = now
        while heap and (fresh < w or heap[0][0] <= deadline):
            if heap[0][0] > deadline:
                break
            fin, _, i, it = heapq.heappop(heap)
            now = fin
            if queued[i] is not None:
                q, queued[i] = queued[i], None
                start(i, q, now)
            else:
                busy_until[i] = now
            if it == t:
                mask[t, i] = True
                fresh += 1
                if fresh == w:
                    if margin > 0:
                        deadline = now + H(margin) * (now - iter_start)
                    else:
                        break
            else:
                flush[t, i] = True
        times[t] = now
        misses = np.where(mask[t], 0, misses + 1)
        now_failed = misses >= max_misses
        flush[t] &= ~now_failed
        evict[t] = now_failed & ~failed
        failed = now_failed
    return Streams(mask, flush, evict, times)


def replay(problem, streams: Streams, V0: np.ndarray, eta: float, hi=np.float64,
           lo=np.float32) -> Trajectory:
    """Tier 1 fed ``streams``.  ``problem.group_losses_and_grads(V)`` gives
    each group's loss and gradient at ``V`` in ``hi``."""
    hi = np.dtype(hi)
    r = rounder(lo)
    T, G = streams.mask.shape

    def store(a):  # a value held in the configuration's storage precision
        return r(np.asarray(a, np.float32)).astype(hi)

    V = store(V0)
    cache = np.zeros((G,) + V.shape, hi)
    pending = np.zeros_like(cache)
    pending_valid = np.zeros(G, bool)
    filled = np.zeros(G, bool)
    h = np.zeros(V.shape, hi)
    loss = np.zeros(T, hi)
    grad_norm = np.zeros(T, hi)
    col = (slice(None),) + (None,) * V.ndim
    for t in range(T):
        losses, grads = problem.group_losses_and_grads(V)
        grads = store(grads)
        loss[t] = losses.mean(dtype=hi)
        evict = streams.evict[t]
        fresh = streams.mask[t] & ~evict
        flushed = streams.flush[t] & ~fresh & pending_valid
        new = np.where(fresh[col], grads, np.where(flushed[col], pending, cache))
        new = np.where(evict[col], hi.type(0), new)
        h = h + (new - cache).sum(axis=0, dtype=hi)
        pending = np.where((fresh | flushed | ~pending_valid)[col], grads, pending)
        filled = (filled | fresh | flushed) & ~evict
        pending_valid = ~fresh & ~evict
        cache = new
        xi = min(max(filled.mean(), 1e-6), 1.0)
        direction = h / hi.type(xi * G)
        grad_norm[t] = np.sqrt(np.sum(direction * direction, dtype=hi))
        V = store(V - hi.type(eta) * direction)
    return Trajectory(loss=loss, grad_norm=grad_norm, params=V)
