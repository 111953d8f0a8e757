"""Plain reference of the live logistic regression job's group losses
(arXiv:2111.13877, §7, as the live trainer states it).

The rows are cut into G equal consecutive ranges, one per group.  Group
g's loss is its share of the objective scaled so that the mean over groups
is the objective itself:

    L_g(V) = G/n sum_{i in g} log(1 + exp(-y_i x_i^T V)) + (lam/2) ||V||^2,

with lam = 1/n, and its gradient is
G/n sum_{i in g} -y_i x_i sigmoid(-y_i x_i^T V) + lam V.  Everything is
computed in ``hi`` from data stored in ``lo`` (see ``precision.py``).
"""

from __future__ import annotations

import numpy as np

from chipbench.reference.precision import rounder


class LiveLogregReference:
    def __init__(self, X: np.ndarray, y: np.ndarray, groups: int, hi=np.float64,
                 lo=np.float32):
        self.hi = np.dtype(hi)
        n, d = X.shape
        if n % groups:
            raise ValueError(f"{n} rows do not split into {groups} equal groups")
        X = rounder(lo)(np.asarray(X, np.float32)).astype(self.hi)
        self.X = X.reshape(groups, n // groups, d)
        self.y = np.asarray(y, np.float32).astype(self.hi).reshape(groups, n // groups)
        self.G, self.n, self.d = groups, n, d
        self.lam = self.hi.type(1.0 / n)

    def init(self, seed: int) -> np.ndarray:
        return np.zeros(self.d, dtype=np.float32)

    def group_losses_and_grads(self, V: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """([G] losses, [G, d] gradients) at ``V``."""
        V = np.asarray(V).astype(self.hi)
        scale = self.hi.type(self.G / self.n)
        z = self.y * np.einsum("gmd,d->gm", self.X, V)
        reg = self.hi.type(0.5) * self.lam * np.sum(V * V)
        losses = scale * np.logaddexp(self.hi.type(0), -z).sum(axis=1) + reg
        s = self.hi.type(1) / (self.hi.type(1) + np.exp(z))
        grads = scale * np.einsum("gmd,gm->gd", self.X, -self.y * s) + self.lam * V
        return losses, grads
