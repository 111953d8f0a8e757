"""Plain reference of the paper's logistic regression (arXiv:2111.13877, §7).

f_i(V) = log(1 + exp(-y_i x_i^T V)) / n and R(V) = (lam/2) ||V||^2 with
lam = 1/n and no projection.  The block subgradient over rows b is
-sum_b x_i y_i sigmoid(-y_i x_i^T V) / n.  The suboptimality is the
objective minus the objective at the optimum, which Newton's method finds,
floored at 1e-16.

Precisions follow the configuration: the data, the iterate and each block
subgradient are float32 (``lo``), the optimum, the objective and the
suboptimality float64 (``hi``).  The control lowers each by one step:
``hi`` float32, ``lo`` bfloat16 (values rounded to it where they are
stored: the data, the iterate, each block subgradient).  A second control,
``ev`` float32, lowers the objective and the suboptimality alone and keeps
the rest as stated.
"""

from __future__ import annotations

import numpy as np

from chipbench.reference.precision import rounder


class LogregReference:
    def __init__(self, X: np.ndarray, y: np.ndarray, hi=np.float64, lo=np.float32, ev=None):
        self.r = rounder(lo)
        self.X = self.r(np.asarray(X, dtype=np.float32))
        self.y = np.asarray(y, dtype=np.float32)
        self.n, self.d = self.X.shape
        self.lam = 1.0 / self.n
        self.hi = np.dtype(hi)
        self._Xh = self.X.astype(self.hi)
        self._yh = self.y.astype(self.hi)
        self.ev = self.hi if ev is None else np.dtype(ev)
        self.opt = self.objective(self._newton(), self.hi)
        self.cost_per_row = 2.0 * self.d

    def _newton(self) -> np.ndarray:
        x, y, n = self._Xh, self._yh, self.n
        v = np.zeros(self.d, dtype=self.hi)
        for _ in range(50):
            s = 1.0 / (1.0 + np.exp(y * (x @ v)))
            grad = -(x.T @ (y * s)) / n + self.lam * v
            hess = (x.T * (s * (1.0 - s))) @ x / n + self.lam * np.eye(self.d, dtype=self.hi)
            step = np.linalg.solve(hess, grad)
            v = v - step
            if np.linalg.norm(step) < 1e-12:
                break
        return v

    def objective(self, V: np.ndarray, dt):
        dt = np.dtype(dt)
        Vh = np.asarray(V).astype(dt)
        z = self._yh.astype(dt, copy=False) * (self._Xh.astype(dt, copy=False) @ Vh)
        return np.mean(np.logaddexp(dt.type(0), -z)) + dt.type(0.5 * self.lam) * np.sum(Vh * Vh)

    def init(self, seed: int) -> np.ndarray:
        return np.zeros(self.d, dtype=np.float32)

    def subgradient(self, V: np.ndarray, start: int, stop: int) -> np.ndarray:
        Xb, yb = self.X[start - 1 : stop], self.y[start - 1 : stop]
        s = np.float32(1.0) / (np.float32(1.0) + np.exp(yb * (Xb @ V)))
        return self.r(-(Xb.T @ (yb * s)) / np.float32(self.n))

    def regularizer_grad(self, V: np.ndarray) -> np.ndarray:
        return self.lam * V

    def project(self, V: np.ndarray) -> np.ndarray:
        return self.r(V)

    def suboptimality(self, V: np.ndarray) -> float:
        ev = self.ev.type
        return float(max(self.objective(V, self.ev) - ev(self.opt), ev(1e-16)))
