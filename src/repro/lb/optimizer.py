"""Load-balancing optimizer (paper §6.2, Algorithm 1) — host entry points.

Given per-worker latency statistics from the profiler, produce an updated
subpartition-count vector p' that (i) equalizes expected total per-iteration
latency across workers and (ii) respects the contribution constraint
h(p') >= h_min, where h is estimated by replaying pre-sampled what-if
latency traces through the batched §4.2 event dynamics.

Since the fused-scan engine learned to run §6 configs, **all numerical
work lives in** :mod:`repro.lb.jit_optimizer` as traceable JAX functions:
the hill-climb moves on the finite p-ladder
(:func:`repro.lb.partitioner.build_p_ladder`), the what-if traces are
``jax.random.gamma`` draws, and every phase operates on masked ``[S, N]``
arrays.  This class is the numpy-facing wrapper those host callers (the
scalar :class:`~repro.cluster.simulator.TrainingSimulator`, the batched
host convergence engine, and the standalone tests) share; the fused scan
traces the very same functions inline, which is what makes the three
engines bit-exact on §6 configs (pinned by ``tests/test_lb_scan.py``).

The §6.2 linearisation is unchanged:

    e'_{Z,i} = e_{Z,i} * p_i / p'_i        (computation mean)
    v'_{Z,i} = v_{Z,i} * p_i^2 / p'_i^2    (computation variance)
    e'_{X,i} = e_{Y,i} + e'_{Z,i}          (total)

and h is evaluated with a 1% tolerance (the paper's noise allowance).
"""

from __future__ import annotations

import dataclasses
from collections.abc import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.lb import jit_optimizer as jlb
from repro.lb.partitioner import build_p_ladder
from repro.precision import x64


@dataclasses.dataclass
class OptimizerInputs:
    """Latest profiler statistics.

    Arrays are ``[N]`` for a single scenario (the scalar simulator) or
    ``[S, N]`` for a batch (the vectorized convergence engines); ``w`` and
    ``margin`` are shared across the batch (one method configuration).
    """

    e_comm: np.ndarray  # e_{Y,i}
    v_comm: np.ndarray  # v_{Y,i}
    e_comp: np.ndarray  # e_{Z,i}  (at the CURRENT p_i)
    v_comp: np.ndarray  # v_{Z,i}
    samples_per_worker: np.ndarray  # n_i
    w: int  # wait-for-w setting of the running method
    margin: float = 0.02

    def as_batch(self) -> "OptimizerInputs":
        """View with a leading scenario axis (no copy for 2-D inputs)."""
        if np.ndim(self.e_comm) == 2:
            return self
        return OptimizerInputs(
            e_comm=np.asarray(self.e_comm, np.float64)[None, :],
            v_comm=np.asarray(self.v_comm, np.float64)[None, :],
            e_comp=np.asarray(self.e_comp, np.float64)[None, :],
            v_comp=np.asarray(self.v_comp, np.float64)[None, :],
            samples_per_worker=np.asarray(self.samples_per_worker, np.float64)[None, :],
            w=self.w,
            margin=self.margin,
        )


class LoadBalanceOptimizer:
    """Iterative ladder solver for paper Eq. (7) / Algorithm 1.

    ``ladder`` fixes the candidate subpartition counts; when omitted it is
    built from the first ``optimize*`` call's current p and sample counts
    (:func:`build_p_ladder`).  The convergence engines pass their ladder
    explicitly so the host optimizer and the fused scan climb the exact
    same rungs.
    """

    def __init__(
        self,
        *,
        h_tolerance: float = jlb.H_TOLERANCE,
        sim_iterations: int = jlb.SIM_ITERATIONS,
        max_rounds: int = jlb.MAX_ROUNDS,
        improvement_threshold: float = jlb.IMPROVEMENT_THRESHOLD,
        seed: int = 0,
        ladder: tuple[int, ...] | None = None,
    ):
        self.h_tolerance = h_tolerance
        self.sim_iterations = sim_iterations
        self.max_rounds = max_rounds
        #: only publish a new p if the objective improves by this much
        #: (paper §6.3 first mitigation strategy, default 10%)
        self.improvement_threshold = improvement_threshold
        self.seed = seed
        self.ladder = tuple(ladder) if ladder is not None else None
        self.h_min: float | None = None
        #: h at the *returned* p' of the last optimize() call — kept
        #: consistent with the returned vector even when the slack phase
        #: backs a violating step out
        self.last_h: float | None = None

    # -- shared pieces -----------------------------------------------------
    def _ladder_for(self, p: np.ndarray, n_j: np.ndarray) -> tuple[int, ...]:
        if self.ladder is None:
            self.ladder = build_p_ladder(int(np.max(p)), int(np.max(n_j)))
        return self.ladder

    def _key(self):
        return jax.random.PRNGKey(self.seed)

    @staticmethod
    def objective(e_x: np.ndarray):
        """max/min ratio of expected per-worker total latency (Eq. 7)."""
        lo = np.maximum(e_x.min(axis=-1), 1e-12)
        ratio = e_x.max(axis=-1) / lo
        return float(ratio) if np.ndim(ratio) == 0 else ratio

    # -- h(p) via batched what-if trace replay ------------------------------
    def estimate_h(
        self, inputs: OptimizerInputs, p: Sequence[int], p_new: Sequence[int]
    ) -> float:
        """Scalar convenience: h(p') for one scenario's inputs.

        Deterministic given (seed, inputs, p, p') — the same jitted
        estimator Algorithm 1 calls internally, so re-estimating at a
        returned vector reproduces ``last_h`` exactly.
        """
        b = inputs.as_batch()
        fn = jlb._estimate_h_jitted(
            int(b.w), int(self.sim_iterations), float(b.margin)
        )
        with x64():
            h = fn(
                jnp.asarray(b.e_comm, jnp.float64),
                jnp.asarray(b.v_comm, jnp.float64),
                jnp.asarray(b.e_comp, jnp.float64),
                jnp.asarray(b.v_comp, jnp.float64),
                jnp.asarray(b.samples_per_worker, jnp.float64),
                jnp.asarray(p, jnp.float64)[None, :],
                jnp.asarray(p_new, jnp.float64)[None, :],
                self._key(),
            )
        return float(np.asarray(h)[0])

    # -- Algorithm 1 + publication gate (batched) ---------------------------
    def update_batch(
        self,
        p: np.ndarray,
        inputs: OptimizerInputs,
        h_min: np.ndarray | None = None,
        active: np.ndarray | None = None,
        alive: np.ndarray | None = None,
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """Run Algorithm 1 + the §6.3 publish gate for S scenarios at once.

        ``p`` is ``[S, N]`` int, ``inputs`` holds ``[S, N]`` arrays,
        ``h_min`` the per-scenario contribution floor carried across calls
        (NaN = not yet established), and ``active`` masks which scenarios
        actually balance this round (inactive rows pass through).
        ``alive`` ([S, N] bool, optional) is the churn liveness mask: dead
        workers are excluded from the hill-climb and their p frozen (see
        :func:`repro.lb.jit_optimizer.algorithm1`).  Returns
        ``(p_new [S, N] int64, h_min [S], last_h [S], publish [S])``.
        """
        p = np.asarray(p, dtype=np.int64)
        S, N = p.shape
        if h_min is None:
            h_min = np.full(S, np.nan)
        if active is None:
            active = np.ones(S, dtype=bool)
        ladder = self._ladder_for(p, inputs.samples_per_worker)
        fn = jlb._lb_update_jitted(
            ladder,
            int(inputs.w),
            int(self.sim_iterations),
            float(self.h_tolerance),
            int(self.max_rounds),
            float(self.improvement_threshold),
            float(inputs.margin),
            with_alive=alive is not None,
        )
        with x64():
            args = (
                jnp.asarray(p, jnp.float64),
                jnp.asarray(inputs.e_comm, jnp.float64),
                jnp.asarray(inputs.v_comm, jnp.float64),
                jnp.asarray(inputs.e_comp, jnp.float64),
                jnp.asarray(inputs.v_comp, jnp.float64),
                jnp.asarray(inputs.samples_per_worker, jnp.float64),
                jnp.asarray(h_min, jnp.float64),
                jnp.asarray(active, bool),
                self._key(),
            )
            if alive is not None:
                args = args + (jnp.asarray(alive, bool),)
            p_new, h_min_out, last_h, publish = fn(*args)
        return (
            np.asarray(p_new, np.int64),
            np.asarray(h_min_out, np.float64),
            np.asarray(last_h, np.float64),
            np.asarray(publish, bool),
        )

    def optimize_batch(
        self,
        p: np.ndarray,
        inputs: OptimizerInputs,
        h_min: np.ndarray | None = None,
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Algorithm 1 for S scenarios (no publish gate): see update_batch."""
        p_new, h_min_out, last_h, _ = self.update_batch(p, inputs, h_min)
        return p_new, h_min_out, last_h

    def optimize(self, p: Sequence[int], inputs: OptimizerInputs) -> np.ndarray:
        """Scalar entry point: Algorithm 1 for one scenario (S = 1 batch)."""
        hm = None if self.h_min is None else np.array([self.h_min])
        p_new, h_min, last_h = self.optimize_batch(
            np.asarray(p, dtype=np.int64)[None, :], inputs.as_batch(), hm
        )
        self.h_min = float(h_min[0])
        self.last_h = float(last_h[0])
        return p_new[0]

    # -- publication gate (paper §6.3) -------------------------------------
    def should_publish_batch(
        self, p: np.ndarray, p_new: np.ndarray, inputs: OptimizerInputs
    ) -> np.ndarray:
        """[S] bool: Eq.-(7) objective improves by > improvement_threshold."""
        fn = jlb._should_publish_jitted(float(self.improvement_threshold))
        with x64():
            out = fn(
                jnp.asarray(p, jnp.float64),
                jnp.asarray(p_new, jnp.float64),
                jnp.asarray(inputs.e_comm, jnp.float64),
                jnp.asarray(inputs.e_comp, jnp.float64),
            )
        return np.asarray(out, bool)

    def should_publish(
        self, p: Sequence[int], p_new: Sequence[int], inputs: OptimizerInputs
    ) -> bool:
        """Paper §6.3: only distribute p' if the Eq.-(7) objective improves by
        more than ``improvement_threshold`` (cache evictions are costly)."""
        return bool(
            self.should_publish_batch(
                np.asarray(p, np.float64)[None, :],
                np.asarray(p_new, np.float64)[None, :],
                inputs.as_batch(),
            )[0]
        )
