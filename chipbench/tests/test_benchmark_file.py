"""BENCHMARK.json against the benchmark's contract, and ``run.py``'s refusals."""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads"):
        assert len({x["name"] for x in BENCH[k]}) == len(BENCH[k])
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert all(UNIT.match(m["unit"]) and m["better"] in ("lower", "higher") for m in metrics)
    assert 1 <= BENCH["run_seconds"] <= 51


def test_every_piece_is_found_by_name():
    configs = {c["name"]: c for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert all(k in cfg for k in c["reduced"])
    used = set()
    for w in BENCH["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        assert len(w["why"]) <= 200
        traffic = json.loads((ROOT / f"chipbench/traffic/{w['traffic']}.json").read_text())
        assert (ROOT / f"chipbench/forms/{traffic['form']}.py").is_file()
        assert (ROOT / f"chipbench/limits/{w['name']}.json").is_file()
        used.add(w["config"])
    assert used == set(configs)
    for m in BENCH["per_layer"]:
        assert (ROOT / f"chipbench/metrics/{m['name']}.py").is_file()
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}


def test_every_cell_reports_enough():
    for w in BENCH["workloads"]:
        e2e = [m["name"] for m in BENCH["end_to_end"]
               if "workloads" not in m or w["name"] in m["workloads"]]
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = [m for m in BENCH["per_layer"] if w["name"] in m.get("workloads", [])]
        assert layer and all(m["moves"] in e2e for m in layer)


def _run(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", BENCH["workloads"][0]["name"],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_refuses_without_a_chip():
    p = _run(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    p = _run(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0 and p.stdout.strip() == ""


@pytest.mark.parametrize("w", [w["name"] for w in BENCH["workloads"]])
def test_limits_separate_their_readings(w):
    """Each limit lies above the largest sound reading and below the least
    control reading recorded for it (PERF.md section 2)."""
    rec = json.loads((ROOT / f"chipbench/limits/{w}.json").read_text())
    readings = rec.get("readings")
    assert readings, "limits without the readings they were set from"
    for k, v in rec["limits"].items():
        assert readings["lower_max"][k] < v < readings["upper_min"][k], k


@pytest.mark.parametrize("w", [w["name"] for w in BENCH["workloads"]])
def test_every_planted_fault_fails_a_number(w):
    """Each fault read at the cell's own size fails at least one limit."""
    rec = json.loads((ROOT / f"chipbench/limits/{w}.json").read_text())
    faults = rec["readings"]["faults_min"]
    assert set(faults) == {"state_unchanged", "half_batch", "answer_altered"}
    for name, least in faults.items():
        assert any(least[k] > v for k, v in rec["limits"].items()), name


def test_result_line_on_the_cpu(capsys):
    """The whole run of a cell at its real size, the look for a chip
    skipped: the last stdout line has the contract's keys, ``checks`` last,
    and the cell's end-to-end metrics with their units."""
    from chipbench import run

    cell = next(w for w in BENCH["workloads"] if w["config"] == "logreg_higgs_100w")
    assert run.main(["--workload", cell["name"], "--seed", str(2**31 + 3), "--seconds", "0.1",
                     "--trace", "0"], allow_cpu=True) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    units = {m["name"]: m["unit"] for m in BENCH["end_to_end"]}
    assert {k: v["unit"] for k, v in line["metrics"].items()} == {
        "setup_s": units["setup_s"], "sim_iters_per_s": units["sim_iters_per_s"]}
    assert line["correct"] and line["failed"] == 0
    assert set(line["checks"]) == {"event_rel", "subopt_rel"}
