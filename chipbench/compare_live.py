"""The comparison that decides ``correct`` in a live cell: one job of the
program against the reference's replay of the same job."""

from __future__ import annotations

import numpy as np

from chipbench.compare import max_rel

NUMBERS = ("event_rel", "loss_rel", "grad_rel", "param_rel")


def job_numbers(program: dict, streams, traj, V0) -> dict:
    """``program`` holds what the trainer gave for one job: ``mask``,
    ``flush``, ``evict`` ([T, G] streams), ``virtual`` ([T] times),
    ``loss`` and ``grad_norm`` ([T]) and the final ``params``.

    * ``event_rel`` (Tier 2): the largest relative gap between the virtual
      times at which the steps' collections ended; infinite where a mask,
      flush or evict bit differs;
    * ``loss_rel``: the largest relative gap between the per-step losses;
    * ``grad_rel``: the largest relative gap between the per-step norms of
      the update direction H / (xi G) the optimizer got;
    * ``param_rel``: the gap between the norms of the iterate's change over
      the job, relative to the reference's.
    """
    same = all(np.array_equal(np.asarray(program[k], bool), getattr(streams, k))
               for k in ("mask", "flush", "evict"))
    V0 = np.asarray(V0, np.float64)
    moved_p = np.linalg.norm(np.asarray(program["params"], np.float64) - V0)
    moved_r = np.linalg.norm(np.asarray(traj.params, np.float64) - V0)
    return {
        "event_rel": max_rel(program["virtual"], streams.times) if same else float("inf"),
        "loss_rel": max_rel(program["loss"], traj.loss),
        "grad_rel": max_rel(program["grad_norm"], traj.grad_norm),
        "param_rel": float(abs(moved_p - moved_r) / max(moved_r, 1e-300)),
    }
