"""Finite-sum problems from the paper's experiments (§2, §7).

* :class:`PCAProblem` — PCA cast as empirical-risk minimization (paper Eq. 9):
      R(V) = 1/2 ||V||_F^2,   f_i(V) = 1/2 ||x_i - x_i V V^T||^2,
  with G = Gram-Schmidt orthonormalization.  The block subgradient only needs
  the Gram product  A_b V = X_b^T (X_b V)  — the paper's Eq. (3) hot spot,
  served by the reduce form in ``kernels/ref`` (XLA) or ``kernels/block_sub``
  (Pallas):
      ∇_V Σ_{i∈b} f_i = -2 A_b V + A_b V (V^T V) + V (V^T A_b V).
* :class:`LogisticRegressionProblem` — L2-regularized logistic regression on
  HIGGS-like data:  f_i(V) = log(1 + exp(-b_i x_i^T V)) / n,
  R(V) = (λ/2)||V||^2, G = identity, λ = 1/n (paper §7).

Metrics follow the paper: explained-variance suboptimality for PCA and
classification-error/objective suboptimality for logreg, both against a
directly computed optimum.

Every float expression that feeds the convergence engines lives in exactly
one place: a per-problem set of JAX kernels (:class:`FusedKernels`) that the
scalar :class:`~repro.cluster.simulator.TrainingSimulator`, the batched host
engine (:mod:`repro.experiments.convergence`), and the fused
``jax.lax.scan`` engine (:mod:`repro.experiments.fused`) all share.  The
numpy-facing methods are thin wrappers; bit-exact equivalence of the three
paths rests on this delegation plus two structural properties: batch-size
invariance of the kernels (empirically pinned on CPU by
``tests/test_fused.py``) and the static :func:`width_bucket` ladder —
every interval width maps to one fixed gather shape, so a given (iterate,
interval) is evaluated at identical static shapes by every engine.  The
ladder is what carries bit-reproducibility: XLA's reduction lane grouping
*changes with the padded length*, so masking alone (zero rows contribute
0.0 mathematically, not positionally) would not keep the bits stable
across different pad widths.
"""

from __future__ import annotations

import dataclasses
import functools
from collections.abc import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import block_sub, ref
from repro.precision import x64


class FiniteSumProblem:
    """Interface shared by the coordinator/cluster simulator.

    The ``*_blocks`` / ``*_batch`` methods are the batched counterparts used
    by the vectorized convergence engines
    (:mod:`repro.experiments.convergence`, :mod:`repro.experiments.fused`):
    they evaluate G tasks (one iterate + one sample interval each) in a
    single JAX dispatch.  Each row of the result must be *bit-identical* to
    the corresponding scalar call — the batched engines' equivalence
    guarantee against the scalar
    :class:`~repro.cluster.simulator.TrainingSimulator` rests on it, so the
    scalar methods delegate to the batched kernels at batch size 1.
    """

    num_samples: int

    def init(self, seed: int = 0) -> np.ndarray:
        raise NotImplementedError

    def fused_kernels(self) -> "FusedKernels":
        """The problem's traceable JAX kernels (shared by every engine)."""
        raise NotImplementedError

    def subgradient(self, V: np.ndarray, start: int, stop: int) -> np.ndarray:
        """Sum of ∇f_k(V) for k in [start, stop] (1-based inclusive)."""
        return self.subgradient_blocks(
            np.asarray(V)[None],
            np.array([start], dtype=np.int64),
            np.array([stop], dtype=np.int64),
        )[0]

    def subgradient_blocks(
        self, V_stack: np.ndarray, starts: np.ndarray, stops: np.ndarray
    ) -> np.ndarray:
        """[G, ...] block subgradients for G (iterate, interval) tasks.

        All intervals must have the same width; row g must equal
        ``subgradient(V_stack[g], starts[g], stops[g])`` bit-for-bit.
        """
        starts = np.asarray(starts, dtype=np.int64)
        stops = np.asarray(stops, dtype=np.int64)
        widths = stops - starts + 1
        if widths.size == 0:
            k = self.fused_kernels()
            return np.zeros((0,) + k.value_shape, dtype=k.value_dtype)
        m = int(widths[0])
        if not np.all(widths == m):
            raise ValueError("subgradient_blocks requires equal-width intervals")
        return self._call_sub_kernel(
            V_stack, starts, widths, width_bucket(m, self.num_samples)
        )

    def subgradient_blocks_masked(
        self, V_stack: np.ndarray, starts: np.ndarray, stops: np.ndarray
    ) -> np.ndarray:
        """Like :meth:`subgradient_blocks` but for *mixed-width* intervals.

        Rows are grouped by their :func:`width_bucket` (at most a couple of
        buckets in practice — the §6.3 partition arithmetic only produces
        floor/ceil widths plus the full range) and each bucket is one
        dispatch.  Because the bucket of a width is a pure function of the
        width, every caller — the scalar simulator at G = 1, this wrapper,
        and the fused scan — evaluates a given (iterate, interval) at the
        exact same static shapes, which is what makes the results
        bit-identical across engines (pinned by ``tests/test_fused.py``).
        """
        starts = np.asarray(starts, dtype=np.int64)
        stops = np.asarray(stops, dtype=np.int64)
        widths = stops - starts + 1
        if widths.size == 0:
            k = self.fused_kernels()
            return np.zeros((0,) + k.value_shape, dtype=k.value_dtype)
        buckets = np.array([width_bucket(int(m), self.num_samples) for m in widths])
        out: np.ndarray | None = None
        for b in np.unique(buckets):
            sel = buckets == b
            block = self._call_sub_kernel(
                np.asarray(V_stack)[sel], starts[sel], widths[sel], int(b)
            )
            if out is None:
                out = np.empty((widths.size,) + block.shape[1:], dtype=block.dtype)
            out[sel] = block
        return out

    def _call_sub_kernel(self, V_stack, starts, widths, pad_width: int):
        k = self.fused_kernels()
        with x64():
            out = k.sub_blocks_jit(
                jnp.asarray(V_stack),
                jnp.asarray(starts),
                jnp.asarray(widths),
                pad_width,
            )
            return np.asarray(out)

    def regularizer_grad(self, V: np.ndarray) -> np.ndarray:
        raise NotImplementedError

    def project(self, V: np.ndarray) -> np.ndarray:
        """The G(·) operator of paper Eq. (2)."""
        return V

    def project_batch(self, V_stack: np.ndarray) -> np.ndarray:
        """Apply G(·) to a stack of iterates; identity by default."""
        return V_stack

    def suboptimality(self, V: np.ndarray) -> float:
        return float(self.suboptimality_batch(np.asarray(V)[None])[0])

    def suboptimality_batch(self, V_stack: np.ndarray) -> np.ndarray:
        """[S] suboptimality gaps in one JAX dispatch.

        Row s must equal ``suboptimality(V_stack[s])`` bit-for-bit: the
        kernel maps the single-iterate evaluation over the batch with
        ``lax.map`` (a batched ``dot_general`` would reassociate the
        reductions and break batch invariance on CPU).
        """
        k = self.fused_kernels()
        with x64():
            return np.asarray(k.suboptimality_jit(jnp.asarray(V_stack)))

    #: ops per sample row (set by subclasses; the static cost constant must
    #: be readable without building the JAX kernels — e.g. logreg's kernels
    #: materialize the Newton optimum, which cost-only callers never need)
    cost_per_row: float

    def compute_cost(self, start: int, stop: int) -> float:
        """Computational load c of the block (paper §3: ops count)."""
        return float(self.cost_per_row * (stop - start + 1))

    def compute_cost_batch(self, starts: np.ndarray, stops: np.ndarray) -> np.ndarray:
        """Vectorized :meth:`compute_cost` (same float expression per row)."""
        rows = np.asarray(stops, dtype=np.int64) - np.asarray(starts, np.int64) + 1
        return self.cost_per_row * rows


@dataclasses.dataclass
class FusedKernels:
    """One problem's traceable JAX kernels plus their jitted entry points.

    ``sub_blocks(V_stack, starts, widths, pad_width)`` evaluates G block
    subgradients at a static gather width (rows past each width masked to
    zero); ``suboptimality`` / ``project`` / ``regularizer_grad`` operate on
    ``[S, ...]`` iterate stacks.  ``suboptimality_stacked`` is the same
    gap as one float64 contraction of the data with every iterate at once:
    it reduces per iterate as ``suboptimality`` does, but its batched dot
    does not round like the per-iterate one, so only ``suboptimality`` is
    batch invariant (the fused scan takes the stacked form off the CPU).
    ``value_dtype`` is the dtype ``sub_blocks`` returns (the fused engine
    sizes its in-flight value buffers with it).  The raw callables are
    traceable from inside an outer ``jax.jit`` / ``lax.scan`` (the fused
    engine); the ``*_jit`` fields are the standalone jitted versions the
    numpy wrappers use.  Instances hash by identity, so they can be passed
    as static arguments to jitted drivers.
    """

    num_samples: int
    value_shape: tuple[int, ...]
    value_dtype: np.dtype
    cost_per_row: float
    sub_blocks: Callable  # (Vb, starts, widths, pad_width) -> [G, ...]
    suboptimality: Callable  # [S, ...] -> [S]
    suboptimality_stacked: Callable  # [B, ...] -> [B], one contraction
    project: Callable  # [S, ...] -> [S, ...]
    regularizer_grad: Callable  # [S, ...] -> [S, ...]
    # Pallas twin of sub_blocks — (Vb, starts, widths, pad_width, interpret)
    # with both trailing args static; None when the problem has no Pallas
    # kernels (the engine's kernel-backend capability check reports it)
    sub_blocks_pallas: Callable | None = None

    def __post_init__(self):
        self.sub_blocks_jit = jax.jit(self.sub_blocks, static_argnums=3)
        self.suboptimality_jit = jax.jit(self.suboptimality)
        self.project_jit = jax.jit(self.project)

    def __hash__(self):  # identity hash: usable as a jit static argument
        return id(self)

    def __eq__(self, other):
        return self is other


def width_bucket(m: int, num_samples: int) -> int:
    """Static gather width used to evaluate an interval of width ``m``.

    The next power of two, except the full range keeps its exact width (no
    point doubling the gather for the gd/coded full-dataset blocks).  The
    kernels' reductions are *not* invariant to the padded length (XLA's
    lane grouping changes with the shape), so bit-reproducibility across
    engines comes from this ladder being a pure function of the width:
    every caller evaluates a given width at the same static shape.
    """
    if m == num_samples:
        return m
    return 1 << (m - 1).bit_length()


def _pad_pow2(Vb, starts, widths):
    """Pad a task batch to the next power-of-two size (repeat the last row).

    The batched subgradient kernels are batch-invariant (each row's result
    is independent of what else shares the batch), so padding does not
    change any real row's bits — but it bounds the number of distinct batch
    shapes XLA ever sees to O(log G_max) per gather width, instead of one
    recompilation for every fleet configuration the event dynamics happen
    to produce.  Shapes are static at trace time, so this is usable from
    inside the fused scan as well.
    """
    g = Vb.shape[0]
    bucket = 1 << (g - 1).bit_length()
    if bucket == g:
        return Vb, starts, widths, g
    pad = bucket - g
    return (
        jnp.concatenate([Vb, jnp.repeat(Vb[-1:], pad, axis=0)]),
        jnp.concatenate([starts, jnp.repeat(starts[-1:], pad)]),
        jnp.concatenate([widths, jnp.repeat(widths[-1:], pad)]),
        g,
    )


def _packed_rows(X, y=None):
    """Zero-argument getter of ``block_sub.pack_rows(X, y)``, built on the
    first Pallas call and kept: the XLA backend never pays for the
    lane-padded copy.  Evaluated eagerly even when first reached from
    inside the fused scan's trace, so the table is one device constant."""

    @functools.cache
    def rows():
        with jax.ensure_compile_time_eval():
            return block_sub.pack_rows(X, y)

    return rows


# ---------------------------------------------------------------------------
# PCA (power-method family) on a genomics-like sparse binary matrix
# ---------------------------------------------------------------------------


def make_genomics_like_matrix(
    n: int, d: int, *, density: float = 0.0536, seed: int = 0
) -> np.ndarray:
    """Synthetic stand-in for the 1000-Genomes binary matrix (§2): sparse
    binary with ~5.36% density and a planted low-rank structure so the top
    principal components are well separated (row-permuted, like the paper)."""
    rng = np.random.default_rng(seed)
    # planted structure: rows belong to "populations" of decreasing size with
    # distinct variant patterns, giving a well-separated top spectrum (the
    # real 1000-Genomes matrix likewise has dominant population components)
    k0 = 6
    # geometric population sizes and disjoint dense column blocks give a
    # well-separated eigenvalue ladder (ratio ~0.5 between consecutive
    # principal values), so power-method-family convergence is observable
    sizes = 0.5 ** np.arange(k0)
    sizes = sizes / sizes.sum()
    assign = np.clip(np.searchsorted(np.cumsum(sizes), rng.random(n)), 0, k0 - 1)
    cols = np.arange(d)
    block = np.minimum(cols * k0 // d, k0 - 1)  # column -> population block
    dense_mask = block[None, :] == assign[:, None]
    # calibrate hi/lo to hit the target overall density
    frac_dense = float(dense_mask.mean())
    hi = min(0.7 * density / max(frac_dense, 1e-6), 0.95)
    lo = max((density - hi * frac_dense) / max(1 - frac_dense, 1e-6), density * 0.05)
    probs = np.where(dense_mask, hi, lo)
    x = (rng.random((n, d)) < probs).astype(np.float32)
    perm = rng.permutation(n)
    return x[perm]


@dataclasses.dataclass
class PCAProblem(FiniteSumProblem):
    X: np.ndarray  # [n, d]
    k: int = 3

    def __post_init__(self):
        self.num_samples = int(self.X.shape[0])
        self.dim = int(self.X.shape[1])
        self.cost_per_row = 2.0 * self.dim * self.k
        with x64():
            self._Xj = jnp.asarray(self.X)
            self._X64 = jnp.asarray(self.X, dtype=jnp.float64)
        # reference optimum: exact top-k eigendecomposition of X^T X
        gram = np.asarray(self.X, dtype=np.float64).T @ np.asarray(self.X, np.float64)
        evals = np.linalg.eigvalsh(gram)
        self._opt_explained = float(np.sum(np.sort(evals)[::-1][: self.k]))
        self._total_var = float(np.trace(gram))
        self._kernels: FusedKernels | None = None

    def init(self, seed: int = 0) -> np.ndarray:
        rng = np.random.default_rng(seed)
        v = rng.normal(size=(self.dim, self.k)).astype(np.float32)
        q, _ = np.linalg.qr(v)
        return q

    def fused_kernels(self) -> FusedKernels:
        if self._kernels is not None:
            return self._kernels
        Xj, X64 = self._Xj, self._X64
        n = self.num_samples
        opt, total = self._opt_explained, self._total_var

        def sub_blocks(Vb, starts, widths, pad_width: int):
            # -X_b^T (X_b V) with a leading batch axis.  On the Stiefel
            # manifold enforced by G (V^T V = I),
            #   f_i(V) = 1/2||x_i - x_i V V^T||^2 = 1/2||x_i||^2 - 1/2||x_i V||^2,
            # so the block subgradient is -X_b^T (X_b V) — exactly the worker
            # computation of paper Eq. (3).  With eta = 1 the GD update
            # V - (V - A V) = A V followed by Gram-Schmidt *is* the power
            # method, as stated in §7.  Rows past each interval's width are
            # zero-masked (they contribute 0.0 to both reductions); bit
            # reproducibility across engines comes from every caller using
            # the same static width_bucket pad per width, NOT from pad-width
            # invariance — see width_bucket.  The reduce form (one column
            # at a time, as in logreg) keeps every row batch-invariant.
            Vb, starts, widths, g = _pad_pow2(Vb, starts, widths)
            return ref.block_sub_pca_ref(Xj, Vb, starts, widths, pad_width)[:g]

        rows = _packed_rows(Xj)

        def sub_blocks_pallas(Vb, starts, widths, pad_width: int, interpret: bool):
            # same _pad_pow2 batching as the XLA form, then one Pallas
            # program per task evaluating the identical expression (see
            # kernels/block_sub.py for the bit-exactness contract)
            if pad_width > block_sub.MAX_WINDOW_ROWS:
                return sub_blocks(Vb, starts, widths, pad_width)
            Vb, starts, widths, g = _pad_pow2(Vb, starts, widths)
            return block_sub.pca_block_sub(
                rows(), Vb, starts, widths, pad_width, interpret=interpret
            )[:g]

        def explained_one(V):
            xv = X64 @ V.astype(jnp.float64)
            return jnp.sum(xv * xv)

        def suboptimality(V_stack):
            # (optimal explained variance - achieved) / total variance — the
            # paper's 'suboptimality gap' for PCA, nonnegative up to roundoff
            def one(V):
                return jnp.maximum((opt - explained_one(V)) / total, 1e-16)

            return jax.lax.map(one, V_stack)

        def suboptimality_stacked(V_stack):
            # every iterate's k columns side by side: X64 @ [d, B k]
            B = V_stack.shape[0]
            V_cat = jnp.moveaxis(V_stack, 0, 1).reshape(self.dim, B * self.k)
            xv = X64 @ V_cat.astype(jnp.float64)
            explained = jnp.sum((xv * xv).reshape(n, B, self.k), axis=(0, 2))
            return jnp.maximum((opt - explained) / total, 1e-16)

        def project(V_stack):
            # Gram-Schmidt == thin-QR orthonormalization (sign-fixed); on CPU
            # jnp.linalg.qr loops LAPACK per matrix, so rows are
            # batch-invariant (pinned by tests)
            q, r = jnp.linalg.qr(V_stack)
            diag = jnp.diagonal(r, axis1=-2, axis2=-1)
            return q * jnp.sign(diag)[..., None, :]

        self._kernels = FusedKernels(
            num_samples=n,
            value_shape=(self.dim, self.k),
            value_dtype=np.result_type(self.X.dtype, np.float32),
            cost_per_row=self.cost_per_row,
            sub_blocks=sub_blocks,
            suboptimality=suboptimality,
            suboptimality_stacked=suboptimality_stacked,
            project=project,
            regularizer_grad=lambda V_stack: V_stack,  # ∇ 1/2||V||_F^2
            sub_blocks_pallas=sub_blocks_pallas,
        )
        self._explained_jit = jax.jit(lambda Vs: jax.lax.map(explained_one, Vs))
        return self._kernels

    def regularizer_grad(self, V: np.ndarray) -> np.ndarray:
        return V  # ∇ 1/2||V||_F^2

    def project(self, V: np.ndarray) -> np.ndarray:
        return self.project_batch(np.asarray(V)[None])[0]

    def project_batch(self, V_stack: np.ndarray) -> np.ndarray:
        # delegates to the shared QR kernel: the scalar simulator, the host
        # batched engine, and the fused scan all orthonormalize with the
        # exact same bits
        k = self.fused_kernels()
        with x64():
            return np.asarray(k.project_jit(jnp.asarray(V_stack)))

    def explained_variance(self, V: np.ndarray) -> float:
        self.fused_kernels()
        with x64():
            return float(self._explained_jit(jnp.asarray(V)[None])[0])

    # compute_cost doc: c = 2 ζ d k rows with ζ the density (paper §3); for
    # our dense representation ζ=1 gives ops of the dense Gram product —
    # encoded as FusedKernels.cost_per_row = 2 d k.


# ---------------------------------------------------------------------------
# Logistic regression on HIGGS-like data
# ---------------------------------------------------------------------------


def make_higgs_like(
    n: int, d: int = 28, *, seed: int = 0
) -> tuple[np.ndarray, np.ndarray]:
    """Synthetic binary-classification data shaped like HIGGS (28 features,
    labels ±1), feature-normalized with an intercept appended (paper §7)."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    w_true = rng.normal(size=(d,)).astype(np.float32)
    logits = x @ w_true + 0.5 * rng.normal(size=(n,)).astype(np.float32)
    y = np.where(rng.random(n) < 1.0 / (1.0 + np.exp(-logits)), 1.0, -1.0).astype(
        np.float32
    )
    # normalize to zero mean / unit variance, add intercept = 1
    x = (x - x.mean(axis=0)) / (x.std(axis=0) + 1e-8)
    x = np.concatenate([x, np.ones((n, 1), np.float32)], axis=1)
    return x, y


@dataclasses.dataclass
class LogisticRegressionProblem(FiniteSumProblem):
    X: np.ndarray  # [n, d] (already includes intercept column)
    y: np.ndarray  # [n] in {-1, +1}
    lam: float | None = None  # default 1/n, as in the paper

    def __post_init__(self):
        self.num_samples = int(self.X.shape[0])
        self.dim = int(self.X.shape[1])
        self.cost_per_row = 2.0 * self.dim
        if self.lam is None:
            self.lam = 1.0 / self.num_samples
        with x64():
            self._Xj = jnp.asarray(self.X)
            self._yj = jnp.asarray(self.y)
            self._X64 = jnp.asarray(self.X, dtype=jnp.float64)
            self._y64 = jnp.asarray(self.y, dtype=jnp.float64)
        self._opt = None  # lazy: computed by Newton iterations on first use
        self._kernels: FusedKernels | None = None

    def init(self, seed: int = 0) -> np.ndarray:
        return np.zeros((self.dim,), dtype=np.float32)

    def fused_kernels(self) -> FusedKernels:
        if self._kernels is not None:
            return self._kernels
        Xj, yj = self._Xj, self._yj
        X64, y64 = self._X64, self._y64
        n, lam = self.num_samples, self.lam

        def sub_blocks(Vb, starts, widths, pad_width: int):
            # Uses explicit elementwise-multiply + axis reductions rather
            # than matmuls: XLA lowers a [m, d] @ [d] mat-vec and a
            # [G, m, d] batched product to different kernels with different
            # accumulation orders, so matmul results would depend on the
            # batch size.  The reduce-based form is batch-invariant (pinned
            # by tests); labels are zero-masked past each interval's width,
            # and every caller evaluates a given width at the same static
            # width_bucket pad — the reduction is NOT invariant to the pad
            # length itself (see width_bucket).
            Vb, starts, widths, g = _pad_pow2(Vb, starts, widths)
            return ref.block_sub_logreg_ref(Xj, yj, Vb, starts, widths, pad_width)[:g]

        rows = _packed_rows(Xj, yj)

        def sub_blocks_pallas(Vb, starts, widths, pad_width: int, interpret: bool):
            if pad_width > block_sub.MAX_WINDOW_ROWS:
                return sub_blocks(Vb, starts, widths, pad_width)
            Vb, starts, widths, g = _pad_pow2(Vb, starts, widths)
            return block_sub.logreg_block_sub(
                rows(), Vb, starts, widths, pad_width, interpret=interpret
            )[:g]

        def objective_one(V):
            V64 = V.astype(jnp.float64)
            z = y64 * (X64 @ V64)
            # log1p(exp(-z)) stable
            return jnp.mean(jnp.logaddexp(0.0, -z)) + 0.5 * lam * jnp.sum(V64 * V64)

        def objective(V_stack):
            return jax.lax.map(objective_one, V_stack)

        self._objective_jit = jax.jit(objective)
        # materialize the Newton optimum now: the suboptimality kernel must
        # close over a concrete float (it may first be traced from inside
        # the fused scan, where resolving the lazy property would nest a
        # jit call into the trace)
        opt_obj = self.optimum_objective

        def suboptimality(V_stack):
            return jnp.maximum(objective(V_stack) - opt_obj, 1e-16)

        def suboptimality_stacked(V_stack):
            # objective_one for every iterate from one X64 @ [d, B]
            V64 = V_stack.astype(jnp.float64)
            z = y64[:, None] * (X64 @ V64.T)
            obj = jnp.mean(jnp.logaddexp(0.0, -z), axis=0) + 0.5 * lam * jnp.sum(
                V64 * V64, axis=1
            )
            return jnp.maximum(obj - opt_obj, 1e-16)

        self._kernels = FusedKernels(
            num_samples=n,
            value_shape=(self.dim,),
            value_dtype=np.result_type(self.X.dtype, np.float32),
            cost_per_row=self.cost_per_row,
            sub_blocks=sub_blocks,
            suboptimality=suboptimality,
            suboptimality_stacked=suboptimality_stacked,
            project=lambda V_stack: V_stack,  # G = identity
            regularizer_grad=lambda V_stack: lam * V_stack,
            sub_blocks_pallas=sub_blocks_pallas,
        )
        return self._kernels

    def objective(self, V: np.ndarray) -> float:
        return float(self.objective_batch(np.asarray(V)[None])[0])

    def objective_batch(self, V_stack: np.ndarray) -> np.ndarray:
        """[S] objectives through the shared JAX kernel (one dispatch)."""
        if not hasattr(self, "_objective_jit"):  # set mid-build by fused_kernels
            self.fused_kernels()
        with x64():
            return np.asarray(self._objective_jit(jnp.asarray(V_stack)))

    def _solve_optimum(self) -> np.ndarray:
        """Newton's method — logreg is strongly convex with λ>0."""
        v = np.zeros(self.dim, dtype=np.float64)
        x = self.X.astype(np.float64)
        y = self.y.astype(np.float64)
        n = self.num_samples
        for _ in range(50):
            z = y * (x @ v)
            s = 1.0 / (1.0 + np.exp(z))  # σ(-z)
            grad = -(x.T @ (y * s)) / n + self.lam * v
            w = s * (1.0 - s)
            hess = (x.T * w) @ x / n + self.lam * np.eye(self.dim)
            step = np.linalg.solve(hess, grad)
            v = v - step
            if np.linalg.norm(step) < 1e-12:
                break
        return v

    @property
    def optimum_objective(self) -> float:
        if self._opt is None:
            self._opt = self._solve_optimum()
            self._opt_obj = self.objective(self._opt)
        return self._opt_obj

    def regularizer_grad(self, V: np.ndarray) -> np.ndarray:
        return self.lam * V
