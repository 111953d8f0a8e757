"""PCA on a genomics-like sparse binary matrix (arXiv:2111.13877, §7).

The generator is a copy of the program's ``make_genomics_like_matrix``
(``src/repro/core/problems.py``): rows belong to populations of
geometrically decreasing size, each with a dense block of columns, at the
stated overall density, then rows are permuted.
"""

from __future__ import annotations

import numpy as np

from chipbench.reference.pca import PCAReference


def make_data(cfg: dict) -> dict:
    n, d, density = int(cfg["rows"]), int(cfg["cols"]), float(cfg["density"])
    rng = np.random.default_rng(int(cfg["data_seed"]))
    k0 = 6
    sizes = 0.5 ** np.arange(k0)
    sizes = sizes / sizes.sum()
    assign = np.clip(np.searchsorted(np.cumsum(sizes), rng.random(n)), 0, k0 - 1)
    block = np.minimum(np.arange(d) * k0 // d, k0 - 1)
    dense = block[None, :] == assign[:, None]
    frac = float(dense.mean())
    hi = min(0.7 * density / max(frac, 1e-6), 0.95)
    lo = max((density - hi * frac) / max(1 - frac, 1e-6), density * 0.05)
    x = (rng.random((n, d)) < np.where(dense, hi, lo)).astype(np.float32)
    return {"X": x[rng.permutation(n)]}


def reference(data: dict, cfg: dict, hi=np.float64, lo=np.float32, ev=None) -> PCAReference:
    return PCAReference(data["X"], int(cfg["k"]), hi=hi, lo=lo, ev=ev)


def program_problem(data: dict, cfg: dict):
    from repro.core.problems import PCAProblem

    return PCAProblem(X=data["X"], k=int(cfg["k"]))


def cost_per_row(cfg: dict) -> float:
    """The §3 computational load of one row, as the program's latency model
    charges it (2 d k)."""
    return 2.0 * int(cfg["cols"]) * int(cfg["k"])


# -- useful FLOPs, counted from the shapes ------------------------------------


def task_flops(cfg: dict, rows: float) -> float:
    """-X_b^T (X_b V) over ``rows`` rows: two products of 2 rows d k each."""
    return 4.0 * rows * int(cfg["cols"]) * int(cfg["k"])


def eval_flops(cfg: dict) -> float:
    """||X V||_F^2: the product (2 n d k) and the sum of squares (2 n k)."""
    n, d, k = int(cfg["rows"]), int(cfg["cols"]), int(cfg["k"])
    return 2.0 * n * d * k + 2.0 * n * k


def update_flops(cfg: dict) -> float:
    """V - eta (H / xi + V) and the thin QR of a d x k matrix (4 d k^2)."""
    d, k = int(cfg["cols"]), int(cfg["k"])
    return 4.0 * d * k + 4.0 * d * k * k
