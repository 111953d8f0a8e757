"""Logistic regression on HIGGS-like data (arXiv:2111.13877, §7).

The generator is a copy of the program's ``make_higgs_like``
(``src/repro/core/problems.py``): Gaussian features, labels from a noisy
planted logistic model, features standardised, an intercept column
appended.
"""

from __future__ import annotations

import numpy as np

from chipbench.reference.logreg import LogregReference


def make_data(cfg: dict) -> dict:
    n, d = int(cfg["rows"]), int(cfg["features"])
    rng = np.random.default_rng(int(cfg["data_seed"]))
    x = rng.normal(size=(n, d)).astype(np.float32)
    w_true = rng.normal(size=(d,)).astype(np.float32)
    logits = x @ w_true + 0.5 * rng.normal(size=(n,)).astype(np.float32)
    y = np.where(rng.random(n) < 1.0 / (1.0 + np.exp(-logits)), 1.0, -1.0).astype(np.float32)
    x = (x - x.mean(axis=0)) / (x.std(axis=0) + 1e-8)
    x = np.concatenate([x, np.ones((n, 1), np.float32)], axis=1)
    return {"X": x, "y": y}


def reference(data: dict, cfg: dict, hi=np.float64, lo=np.float32, ev=None) -> LogregReference:
    return LogregReference(data["X"], data["y"], hi=hi, lo=lo, ev=ev)


def program_problem(data: dict, cfg: dict):
    from repro.core.problems import LogisticRegressionProblem

    return LogisticRegressionProblem(X=data["X"], y=data["y"])


def _cols(cfg: dict) -> int:
    return int(cfg["features"]) + 1  # the intercept column


def cost_per_row(cfg: dict) -> float:
    """The §3 computational load of one row (2 d, d with the intercept)."""
    return 2.0 * _cols(cfg)


# -- useful FLOPs, counted from the shapes ------------------------------------


def task_flops(cfg: dict, rows: float) -> float:
    """X_b V (2 rows d), the margins and sigmoid weights (4 rows), and
    X_b^T (y s) (2 rows d)."""
    return rows * (4.0 * _cols(cfg) + 4.0)


def eval_flops(cfg: dict) -> float:
    """X V (2 n d), the n log-losses and their mean (4 n), and the
    regulariser (2 d)."""
    n, d = int(cfg["rows"]), _cols(cfg)
    return 2.0 * n * d + 4.0 * n + 2.0 * d


def update_flops(cfg: dict) -> float:
    """V - eta (H / xi + lam V)."""
    return 5.0 * _cols(cfg)
