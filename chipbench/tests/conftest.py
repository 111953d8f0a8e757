"""Tiny cells for the benchmark's own tests (``pytest chipbench/``), run on
the CPU: the same forms, references and comparison as the chip cells, at a
size a test run holds."""

from __future__ import annotations

import json
import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

TRAFFIC = json.loads((ROOT / "chipbench/traffic/sweep.json").read_text())


def tiny_config(name: str) -> dict:
    cfg = json.loads((ROOT / f"chipbench/configs/{name}.json").read_text())
    cfg.update(rows=1200, workers=6, w=5, subpartitions=2, scenarios=2, iterations=12,
               eval_every=3)
    if "cols" in cfg:
        cfg["cols"] = 96
    return cfg


@pytest.fixture(params=["pca_genomics_50w", "logreg_higgs_100w"])
def tiny(request):
    cfg = tiny_config(request.param)
    limits = json.loads((ROOT / f"chipbench/limits/{request.param}.sweep.json").read_text())["limits"]
    return cfg, limits


class Counter:
    count = 0


def make_ctx(cfg: dict, limits: dict, seed: int = 2**31 + 11, trace: bool = False):
    """What ``run.py`` hands a form, without the look for a chip."""
    import time

    import jax

    devs = jax.devices()[:1]

    def device_block():
        return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": 1,
                "memory_peak_bytes": 0}

    return types.SimpleNamespace(
        t0=time.perf_counter(), seed=seed, seconds=0.01, trace=trace,
        cell={"name": f"{cfg['name']}.tiny", "chips": 1}, config=cfg, traffic=dict(TRAFFIC),
        limits=limits, compiles=Counter(), err=sys.stderr,
        device_block=device_block, read_per_layer=lambda reading: {},
    )
