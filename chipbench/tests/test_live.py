"""The live form and the four-chip sweep cell on the CPU: each cell run end
to end through ``run.py``, the live faults, the live metrics' entries and
readers, and the live step's FLOP count."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest
from conftest import make_ctx, tiny_config

from chipbench.faults import LIVE_FAULTS

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
LIVE = "logreg_higgs_100w.live"
LIVE_METRICS = ("host_ms.live", "idle_share.live", "mfu.live")


def _tiny_live(seed: int = 2**31 + 17):
    from chipbench.harness import CompileCounter

    cfg = tiny_config("logreg_higgs_100w")
    limits = json.loads((ROOT / f"chipbench/limits/{LIVE}.json").read_text())["limits"]
    ctx = make_ctx(cfg, limits, seed=seed)
    ctx.seconds, ctx.cell = 0.3, {"name": "logreg_higgs_100w.tiny_live", "chips": 1}
    ctx.traffic = {**json.loads((ROOT / "chipbench/traffic/live.json").read_text()),
                   "steps_per_job": 12}
    ctx.compiles = CompileCounter()
    return ctx


def test_live_cell_on_the_cpu(capsys):
    """The live cell at its real size, the look for a chip skipped: a
    correct result line with the cell's end-to-end metrics."""
    from chipbench import run

    assert run.main(["--workload", LIVE, "--seed", str(2**32 + 5), "--seconds", "0.3",
                     "--trace", "0"], allow_cpu=True) == 0
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) == {"setup_s", "train_steps_per_s"}
    assert line["metrics"]["train_steps_per_s"]["unit"] == "steps/s"
    assert line["correct"] and line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["checks"]) == {"event_rel", "loss_rel", "grad_rel", "param_rel"}
    assert " 0 compilations inside it" in out.err


def test_live_form_tiny():
    from chipbench.forms import live

    ctx = _tiny_live()
    r = live.run(ctx)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 1
    # the Tier-2 streams and virtual times are the reference's to the bit
    assert r["checks"]["event_rel"]["value"] == 0.0
    assert r["metrics"]["train_steps_per_s"] > 0


def test_jobs_past_the_prepared_ones(capsys):
    """A window that runs more jobs than set-up prepared prepares the rest
    inside it, with the same traces and seeds: still correct."""
    from chipbench.forms import live

    ctx = _tiny_live()
    ctx.traffic["jobs_ready"] = 1
    r = live.run(ctx)
    assert r["correct"] and r["attempted"] >= 2
    err = capsys.readouterr().err
    assert f" {r['attempted'] - 1} jobs prepared inside it" in err
    assert "1 jobs prepared" in err


def test_calibrate_reads_the_live_cell(capsys):
    """``calibrate.py`` on the live cell at its real size, one seed each:
    sound jobs read under the committed limits, and the program's own
    lower path and every planted fault fail them."""
    from chipbench import calibrate

    assert calibrate.main(["--workload", LIVE, "--seeds", "1", "--control-seeds", "1",
                           "--fault-seeds", "1", "--base-seed", str(2**31 + 41)]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    limits = json.loads((ROOT / f"chipbench/limits/{LIVE}.json").read_text())["limits"]
    assert len(out["lower"]) == 2  # check_per_job jobs of the one seed
    assert all(out["lower_max"][k] < v for k, v in limits.items())
    assert set(out["faults"]) == set(LIVE_FAULTS)
    assert all(out["fails_now"].values()), out["fails_now"]


@pytest.mark.parametrize("fault", sorted(LIVE_FAULTS))
def test_live_fault_reads_false(fault, monkeypatch):
    """The timed path broken underneath, the rest of a run as it is."""
    from chipbench.forms import live

    LIVE_FAULTS[fault](monkeypatch.setattr)
    r = live.run(_tiny_live())
    assert not r["correct"] and r["failed"] >= 1


def test_live_metrics_list_only_the_live_cell():
    for m in BENCH["per_layer"]:
        if m["name"].endswith(".live"):
            assert m["workloads"] == [LIVE] and m["moves"] == "train_steps_per_s"
    assert {m["name"] for m in BENCH["per_layer"] if m["name"].endswith(".live")} == set(
        LIVE_METRICS)
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["train_steps_per_s"]["workloads"] == [LIVE]


def test_live_readers():
    """The live readers on a hand-made reduction: 2 jobs of 10 steps, the
    spans 50 ms with 5 ms of device work in them."""
    from chipbench.harness import per_layer_readers

    cell = next(w for w in BENCH["workloads"] if w["name"] == LIVE)
    trace = {"window_s": 0.05, "busy_s": 0.005, "spans": 2, "span_s": 0.05,
             "span_busy_s": 0.005, "modules": {"jit_step": 0.004, "jit_other": 0.001}}
    reading = {"trace": trace, "requests": 2, "steps": 20, "useful_flops": 1e9,
               "peak_flops": 2e14}
    got = {e["name"]: r.read(reading) for e, r in per_layer_readers(BENCH, cell)}
    assert got == pytest.approx({"host_ms.live": 2.25, "idle_share.live": 90.0,
                                 "mfu.live": 0.01})
    # nothing to read: no metric, never a zero share
    empty = {"window_s": 0.0, "busy_s": 0.0, "spans": 0, "span_s": 0.0, "span_busy_s": 0.0,
             "modules": {}}
    assert all(r.read({**reading, "trace": empty}) is None
               for _, r in per_layer_readers(BENCH, cell))
    mfu = dict((e["name"], r) for e, r in per_layer_readers(BENCH, cell))["mfu.live"]
    assert mfu.read({**reading, "peak_flops": None}) is None


def test_live_step_flops_hand_count():
    from chipbench.kinds import load_kind

    # rows 2, d = 3 with the intercept, G = 2: rows 2 x (4 d + 7) = 38,
    # groups 2 x (4 d + 2) = 28, the cache 2 G d = 12, the update 5 d = 15
    assert load_kind("logreg_live").step_flops({"rows": 2, "features": 2}, 2) == 93


def test_live_step_flops_against_xla():
    """The per-row part of the count against XLA's count of one group's
    loss and gradient on the CPU, which counts each elementwise op as one."""
    import jax
    import jax.numpy as jnp

    from chipbench.kinds import load_kind

    m, d = 64, 5

    def loss(V, X, y):
        z = y * jnp.sum(X * V[None, :], axis=1)
        return jnp.sum(jnp.logaddexp(0.0, -z)) + 0.5 * jnp.sum(V * V)

    args = [jax.ShapeDtypeStruct(s, jnp.float32) for s in ((d,), (m, d), (m,))]
    xla = jax.jit(jax.value_and_grad(loss)).lower(*args).compile().cost_analysis()["flops"]
    ours = load_kind("logreg_live").step_flops({"rows": m, "features": d - 1}, 1)
    # same order: XLA's logaddexp and its gradient take a few ops more per row
    assert 0.5 * xla <= ours <= 1.5 * xla


def test_sweep4_on_four_cpu_devices(tmp_path):
    """The four-chip sweep cell through ``run.py`` at a tiny size, on four
    virtual CPU devices, in a process of its own (the flag must be set
    before JAX starts): a checkout whose configuration is cut small."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    (tmp_path / "src").symlink_to(ROOT / "src")
    cfg = tiny_config("pca_genomics_50w")
    (tmp_path / "chipbench/configs/pca_genomics_50w.json").write_text(json.dumps(cfg))
    code = ("import sys; sys.path[:0] = ['.', 'src']; from chipbench import run; "
            "sys.exit(run.main(['--workload', 'pca_genomics_50w.sweep4', '--seed', "
            f"'{2**31 + 23}', '--seconds', '0.2', '--trace', '0'], allow_cpu=True))")
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    p = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0
    assert set(line["metrics"]) == {"setup_s", "sim_iters_per_s"}
    assert line["device"]["count"] == 4
