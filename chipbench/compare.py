"""The comparison that decides ``correct``: the numbers and their limits."""

from __future__ import annotations

import numpy as np


def max_rel(a, b, floor: float = 1e-300) -> float:
    """Largest |a - b| / max(|b|, floor) over the finite entries; inf where
    the NaN pattern differs (a result present on one side only)."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    fa, fb = np.isfinite(a), np.isfinite(b)
    if a.shape != b.shape or not np.array_equal(fa, fb):
        return float("inf")
    if not fa.any():
        return 0.0
    return float(np.max(np.abs(a[fa] - b[fa]) / np.maximum(np.abs(b[fa]), floor)))


def answer_numbers(program: dict, s: int, run, gap: float) -> dict:
    """One answer: scenario ``s`` of one method in one sweep.  ``program``
    holds the method's [S, ...] arrays from the program, ``run`` the
    reference's run of that scenario.

    * ``event_rel`` (§3 trace replay, §4.2 event algebra, §5 cache
      bookkeeping): the largest relative gap between the iteration times
      and between the per-task latencies; infinite where an integer
      stream differs (fresh results per iteration, evictions, rejected
      stale results) or a result is present on one side only;
    * ``subopt_rel`` (block subgradients, cache sum, update, eval): the
      largest gap between the evaluated suboptimalities, relative to the
      reference's value or, below the configuration's gap, to the gap.
    """
    same_ints = (
        np.array_equal(np.asarray(program["fresh_counts"][s]), run.fresh_counts)
        and int(program["evictions"][s]) == run.evictions
        and int(program["rejected_stale"][s]) == run.rejected_stale
    )
    return {
        "event_rel": max(
            max_rel(program["times"][s], run.times),
            max_rel(program["latency"][s], run.latency),
            0.0 if same_ints else float("inf"),
        ),
        "subopt_rel": max_rel(program["suboptimality"][s], run.suboptimality, gap),
    }


def sweep_numbers(program: dict, reference: dict, gap: float) -> dict:
    """Every answer of one sweep: ``reference[method]`` holds the
    reference's runs, one per scenario."""
    out = {"event_rel": 0.0, "subopt_rel": 0.0}
    for name, runs in reference.items():
        for s, run in enumerate(runs):
            out = merge(out, answer_numbers(program[name], s, run, gap))
    return out


def merge(a: dict, b: dict) -> dict:
    """Worst of two readings of the same numbers."""
    return {k: max(a[k], b[k]) for k in a}


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    ok = all(numbers[k] <= limits[k] for k in limits)
    return ok, checks
