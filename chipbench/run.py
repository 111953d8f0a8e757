"""Run one cell of the chip benchmark once.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Reads ``BENCHMARK.json`` at the checkout's root, finds the cell, its
configuration file and its traffic mix (``chipbench/traffic/<traffic>.json``),
and hands them to the form the traffic names (``chipbench/forms/<form>.py``).
With ``--trace 0`` it prints the cell's end-to-end metrics, with ``--trace 1``
its per-layer metrics read from a profiler trace.  The last line of standard
output is one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` also ``breakdown``, and last the
compared numbers beside their limits under ``checks``).

Without a TPU, or with fewer chips than the cell asks for, or outside a
checkout that holds the program (``src/repro``), it exits non-zero and
prints no result.  JAX's persistent compilation cache lives in
``<checkout>/.jax_cache`` unless ``JAX_COMPILATION_CACHE_DIR`` names another.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


class Context:
    """What a form needs from the harness for one run."""

    def __init__(self, args, bench, cell, config, traffic, limits, devs, compiles):
        self.t0 = T0
        self.seed, self.seconds, self.trace = args.seed, args.seconds, bool(args.trace)
        self.bench, self.cell, self.config, self.traffic = bench, cell, config, traffic
        self.limits, self.devs, self.compiles = limits, devs, compiles
        self.err = sys.stderr

    def device_block(self) -> dict:
        from chipbench.harness import device_block

        return device_block(self.devs)

    def read_per_layer(self, reading: dict) -> dict:
        """Every per-layer metric of this cell whose reader finds something."""
        from chipbench.harness import per_layer_readers

        out = {}
        for entry, reader in per_layer_readers(self.bench, self.cell):
            value = reader.read(reading)
            if value is not None:
                out[entry["name"]] = {"value": value, "unit": entry["unit"]}
        return out


def main(argv=None, *, allow_cpu: bool = False) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chipbench: no program under {ROOT / 'src'}", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

    from chipbench import harness

    bench = harness.load_benchmark()
    cell, config_entry = harness.find_cell(bench, args.workload)
    config = harness.load_json(config_entry["file"])
    traffic = harness.load_json(f"chipbench/traffic/{cell['traffic']}.json")
    limits = harness.load_json(f"chipbench/limits/{cell['name']}.json")["limits"]
    try:
        devs = harness.devices(int(cell["chips"]), allow_cpu=allow_cpu)
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3

    from repro.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", file=sys.stderr)
    import jax

    # every program goes to the persistent cache, however short its
    # compile, so that only a checkout's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = harness.CompileCounter()
    import importlib

    form = importlib.import_module(f"chipbench.forms.{traffic['form']}")
    ctx = Context(args, bench, cell, config, traffic, limits, devs, compiles)
    result = form.run(ctx)
    if not ctx.trace:
        # the cell's end-to-end metrics, by name and unit from BENCHMARK.json
        values = result["metrics"]
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in harness.end_to_end_entries(bench, cell)
        }
    checks = result.pop("checks")
    if result.get("breakdown") is None:
        result.pop("breakdown", None)
    harness.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
