"""Plain reference of the paper's PCA problem (arXiv:2111.13877, Eq. 9).

R(V) = 1/2 ||V||_F^2 and f_i(V) = 1/2 ||x_i - x_i V V^T||^2 with V kept on
the Stiefel manifold by a sign-fixed thin QR (Gram-Schmidt).  On the
manifold the block subgradient over rows b is -X_b^T (X_b V).  The
suboptimality is (sum of the top-k eigenvalues of X^T X - ||X V||_F^2)
over trace(X^T X), floored at 1e-16.

Precisions follow the configuration: the data, the iterate and each block
subgradient are float32 (``lo``), the eigen-solve, the explained variance
and the suboptimality float64 (``hi``).  The control lowers each by one
step: ``hi`` float32, ``lo`` bfloat16 (values rounded to it where they are
stored: the data, the iterate, each block subgradient).  A second control,
``ev`` float32, lowers the suboptimality alone and keeps the rest as stated.
"""

from __future__ import annotations

import numpy as np

from chipbench.reference.precision import rounder


class PCAReference:
    def __init__(self, X: np.ndarray, k: int, hi=np.float64, lo=np.float32, ev=None):
        self.r = rounder(lo)
        self.X = self.r(np.asarray(X, dtype=np.float32))
        self.n, self.d = self.X.shape
        self.k = int(k)
        self.hi = np.dtype(hi)
        Xh = self.X.astype(self.hi)
        gram = Xh.T @ Xh
        evals = np.linalg.eigvalsh(gram)
        self.opt = np.sort(evals)[::-1][: self.k].sum(dtype=self.hi)
        self.total = np.trace(gram)
        self.ev = self.hi if ev is None else np.dtype(ev)
        self._Xe = self.X.astype(self.ev)
        self.cost_per_row = 2.0 * self.d * self.k

    def init(self, seed: int) -> np.ndarray:
        v = np.random.default_rng(seed).normal(size=(self.d, self.k)).astype(np.float32)
        q, _ = np.linalg.qr(v)
        return self.r(q)

    def subgradient(self, V: np.ndarray, start: int, stop: int) -> np.ndarray:
        """Sum of the block's subgradients, rows start..stop (1-based, inclusive)."""
        Xb = self.X[start - 1 : stop]
        return self.r(-(Xb.T @ (Xb @ V)))

    def regularizer_grad(self, V: np.ndarray) -> np.ndarray:
        return V

    def project(self, V: np.ndarray) -> np.ndarray:
        q, r = np.linalg.qr(V)
        return self.r(q * np.sign(np.diagonal(r))[None, :])

    def suboptimality(self, V: np.ndarray) -> float:
        ev = self.ev.type
        xv = self._Xe @ V.astype(self.ev)
        gap = (ev(self.opt) - np.sum(xv * xv)) / ev(self.total)
        return float(max(gap, ev(1e-16)))
