"""The logistic regression job as the live form (``forms/live.py``) runs it.

What a kind gives the live form: ``ARCH``, the program's name of the job
the ``Trainer`` runs; ``make_data``; ``program_job``, the program's job
object over the benchmark's data; ``reference``, each group's loss and
gradient in plain numpy; ``group_loads``, each group's §3 computational
load; and ``step_flops``, the useful FLOPs of one training step.
"""

from __future__ import annotations

import numpy as np

from chipbench.kinds import logreg
from chipbench.kinds.logreg import make_data  # noqa: F401  (the sweep cells' data)
from chipbench.reference.logreg_live import LiveLogregReference

ARCH = "logreg"


def program_job(data: dict, cfg: dict, groups: int):
    """The program's ``PaperJob``: the data cut into ``groups`` partitions."""
    from repro.launch.paper_jobs import PaperJob

    return PaperJob(problem=logreg.program_problem(data, cfg), num_groups=groups, name=ARCH)


def reference(data: dict, cfg: dict, groups: int, hi=np.float64,
              lo=np.float32) -> LiveLogregReference:
    return LiveLogregReference(data["X"], data["y"], groups, hi=hi, lo=lo)


def group_loads(cfg: dict, groups: int) -> np.ndarray:
    """A group's task covers its whole partition: cost_per_row x n / G."""
    return np.full(groups, logreg.cost_per_row(cfg) * (int(cfg["rows"]) // groups))


def step_flops(cfg: dict, groups: int) -> float:
    """One step: every group's loss and gradient over its rows, then the
    cache update and the iterate update, counted from the shapes.

    Per row: X V (2 d), the margin (1), the log-loss and its sum (2), the
    sigmoid weight (3: exp, add, divide), y s (1) and X^T (y s) (2 d), so
    4 d + 7.  Per group: the regulariser's value (2 d + 2) and gradient
    (2 d: lam V and the add).  The cache: G d for the deltas and G d to sum
    them into H.  The update: H / (xi G) (d), its norm (2 d), V - eta Hhat
    (2 d)."""
    n, d, G = int(cfg["rows"]), logreg._cols(cfg), int(groups)
    return float(n * (4 * d + 7) + G * (4 * d + 2) + 2 * G * d + 5 * d)
