"""What every cell shares: the benchmark file, the device, compile counting,
per-layer metric readers and the result line."""

from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
HERE = Path(__file__).resolve().parent
#: outputs of a run (traces); git-ignored
WORK = HERE / ".work"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def find_cell(bench: dict, name: str) -> tuple[dict, dict]:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json (have {sorted(cells)})")
    cell = cells[name]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return cell, config


def load_json(rel: str) -> dict:
    return json.loads((ROOT / rel).read_text())


def devices(chips: int, *, allow_cpu: bool = False) -> list:
    """The chips this cell runs on; raises ``NoChip`` rather than fall back."""
    import jax

    devs = jax.devices()
    if not allow_cpu and devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def device_block(devs) -> dict:
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devs]
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "memory_peak_bytes": max(peaks),
    }


class CompileCounter:
    """Counts XLA backend compilations; the event also wraps a program loaded
    from the persistent cache, so a cache hit counts as one too."""

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0

        def on_duration(event: str, duration: float, **_kw) -> None:
            if event == "/jax/core/compile/backend_compile_duration":
                self.count += 1
                self.seconds += duration

        jax.monitoring.register_event_duration_secs_listener(on_duration)


def per_layer_readers(bench: dict, cell: dict) -> list[tuple[dict, object]]:
    """(entry, reader module) of every per-layer metric this cell reports.

    A metric with a ``workloads`` list is read in those cells; one without
    it in every cell that reports the end-to-end metric it moves."""
    reported = {m["name"] for m in end_to_end_entries(bench, cell)}
    out = []
    for m in bench["per_layer"]:
        if "workloads" in m:
            if cell["name"] not in m["workloads"]:
                continue
        elif m["moves"] not in reported:
            continue
        path = HERE / "metrics" / f"{m['name']}.py"
        spec = importlib.util.spec_from_file_location(f"chipbench_metric_{m['name']}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        out.append((m, mod))
    return out


def end_to_end_entries(bench: dict, cell: dict) -> list[dict]:
    return [
        m for m in bench["end_to_end"]
        if "workloads" not in m or cell["name"] in m["workloads"]
    ]


def emit(result: dict, checks: dict) -> None:
    """Each compared number beside its limit as the last lines of stderr, and
    the result as the last line of stdout with ``checks`` as its last key."""
    for name, c in checks.items():
        print(f"check {name} = {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    line = dict(result)
    line["checks"] = checks
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
