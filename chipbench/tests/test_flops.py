"""The FLOP counts of each problem kind against hand counts at tiny shapes,
and against XLA's own count of the same expressions on the CPU."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from chipbench.kinds import load_kind

PCA = {"rows": 5, "cols": 3, "k": 2}
LOGREG = {"rows": 5, "features": 2}


def test_pca_hand_counts():
    pca = load_kind("pca")
    # X_b V: 2 rows x 3 cols x 2 comps x 2 (mul, add) = 24; X_b^T (X_b V): 24 more
    assert pca.task_flops(PCA, 2) == 48
    # X V over 5 rows (2*5*3*2 = 60) and the 10 squares summed (20)
    assert pca.eval_flops(PCA) == 80
    assert pca.cost_per_row(PCA) == 12


def test_logreg_hand_counts():
    lr = load_kind("logreg")
    # d = 3 with the intercept; per row: x.V (6), margin, exp, add, divide
    # (4), x * (y s) summed (6) = 16
    assert lr.task_flops(LOGREG, 2) == 32
    # X V (30), 5 log-losses and their mean (20), the regulariser (6)
    assert lr.eval_flops(LOGREG) == 56
    assert lr.cost_per_row(LOGREG) == 6


def _xla_flops(fn, *shapes) -> float:
    args = [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]
    return jax.jit(fn).lower(*args).compile().cost_analysis()["flops"]


@pytest.mark.parametrize("rows", [2, 7])
def test_pca_task_matches_xla_matmul_count(rows):
    d, k = PCA["cols"], PCA["k"]
    xla = _xla_flops(lambda X, V: -(X.T @ (X @ V)), (rows, d), (d, k))
    # XLA counts the two products and the negation (d k)
    assert load_kind("pca").task_flops(PCA, rows) == pytest.approx(xla - d * k)
