"""§Perf hillclimb driver: hypothesis -> change -> re-lower -> record.

Runs named TrainConfig variants of the three chosen cells and appends every
iteration (hypothesis text, overrides, the three roofline terms, verdict) to
experiments/perf_log.json.  EXPERIMENTS.md §Perf renders from that log.

  PYTHONPATH=src:. python -m benchmarks.hillclimb --cell qwen05 --iter fused_loss
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

BASE = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "experiments")
LOG = os.path.join(BASE, "perf_log.json")

# (cell key) -> (arch, shape)
CELLS = {
    "qwen05": ("qwen1.5-0.5b", "train_4k"),
    "deepseek": ("deepseek-v2-236b", "train_4k"),
    "qwen7b": ("qwen2-7b", "train_4k"),
}

# iteration name -> (hypothesis, TrainConfig overrides)
ITERATIONS = {
    "baseline": ("paper-faithful DSAG step, full remat, plain CE loss", {}),
    "fused_loss": (
        "memory term is dominated by [B,S,152k] logits (bf16 + fp32 casts "
        "~3.5 GiB/device each way); fusing CE with the unembed matmul and "
        "chunking over vocab removes the logit round-trips -> expect the "
        "memory term to drop by >30% on small-model cells",
        {"fused_loss": True},
    ),
    "fused_loss_selective": (
        "with logits gone, full-remat recompute (+1 fwd of compute and "
        "activation traffic) is the next memory/compute cost; selective "
        "remat (save dot outputs) trades VMEM for ~25% less recompute",
        {"fused_loss": True, "remat": "selective"},
    ),
    "int8_gather": (
        "collective term is dominated by per-layer FSDP weight all-gathers "
        "(bf16); int8 per-row-scaled gathers halve that wire volume -> "
        "expect collective term ~-40% on FSDP-bound cells",
        {"fused_loss": True, "quantized_fsdp_allgather": True},
    ),
    "bf16_reduce": (
        "qwen05 lesson: the memory AND collective terms are dominated by "
        "fp32 attention-score buffers and fp32 TP all-reduces riding the "
        "dot accumulator type, NOT by logits (hypothesis 'fused_loss' was "
        "refuted).  Emitting sharded-contraction dots in bf16 halves the "
        "activation all-reduce wire volume -> expect collective ~-30%",
        {"fused_loss": True, "bf16_reduce": True},
    ),
    "bf16_reduce_int8": (
        "stack int8 FSDP weight gathers on bf16 TP-reduces: weight all-"
        "gathers are the other half of the collective term on FSDP cells",
        {"fused_loss": True, "bf16_reduce": True, "quantized_fsdp_allgather": True},
    ),
    "flash_kernel": (
        "S x S score buffers (fp32, fwd+remat+bwd) dominate the memory term "
        "(qwen05: ~75%% of bytes); the Pallas flash-attention kernel "
        "(validated vs ref in interpret mode) keeps them in VMEM.  XLA-CPU "
        "cannot execute the TPU kernel, so this iteration reports the "
        "analyzer's fused-scores memory term (memory_s_flash) alongside the "
        "measured one",
        {"fused_loss": True},
    ),
    "int8_gather_cf1": (
        "MoE dispatch buffers and EP combine collectives scale with the "
        "capacity factor; cf 1.25 -> 1.0 cuts expert-path traffic 20% at "
        "the cost of more token drops (training-quality tradeoff noted)",
        {"fused_loss": True, "quantized_fsdp_allgather": True},
    ),
}


def log_append(entry: dict) -> None:
    os.makedirs(BASE, exist_ok=True)
    log = []
    if os.path.exists(LOG):
        with open(LOG) as f:
            log = json.load(f)
    log.append(entry)
    with open(LOG, "w") as f:
        json.dump(log, f, indent=2)


def run_iteration(cell_key: str, iter_name: str) -> dict:
    arch, shape = CELLS[cell_key]
    hypothesis, overrides = ITERATIONS[iter_name]
    # subprocess for a fresh XLA (device-count env must be first)
    code = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import json
from repro.launch.dryrun import run_cell
res = run_cell({arch!r}, {shape!r}, False, overrides={overrides!r})
print("RESULT" + json.dumps(res["roofline"] | {{"mem_gib": res["memory"]["peak_estimate_bytes"] / 2**30}}))
"""
    # fake 512-device CPU mesh: the child must never take an accelerator
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=1800, env=env
    )
    if proc.returncode != 0:
        entry = {
            "cell": cell_key, "arch": arch, "shape": shape, "iteration": iter_name,
            "hypothesis": hypothesis, "overrides": overrides, "status": "fail",
            "error": proc.stderr[-1500:],
        }
        log_append(entry)
        print(f"[hillclimb] {cell_key}/{iter_name} FAILED")
        return entry
    rl = json.loads(proc.stdout.split("RESULT", 1)[1])
    entry = {
        "cell": cell_key, "arch": arch, "shape": shape, "iteration": iter_name,
        "hypothesis": hypothesis, "overrides": overrides, "status": "ok",
        "compute_s": rl["compute_s"], "memory_s": rl["memory_s"],
        "collective_s": rl["collective_s"], "dominant": rl["dominant"],
        "mfu": rl["mfu"], "mem_gib": rl["mem_gib"],
        "useful_flops_fraction": rl["useful_flops_fraction"],
        "memory_s_flash": rl.get("memory_s_flash", 0.0),
        "attn_score_gib": rl.get("attn_score_bytes", 0.0) / 2**30,
    }
    log_append(entry)
    print(
        f"[hillclimb] {cell_key}/{iter_name}: c/m/x = "
        f"{rl['compute_s']:.3f}/{rl['memory_s']:.3f}/{rl['collective_s']:.3f} s "
        f"dom={rl['dominant']} mfu={rl['mfu']:.3f}"
    )
    return entry


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", choices=list(CELLS), required=True)
    ap.add_argument("--iter", choices=list(ITERATIONS), required=True)
    args = ap.parse_args()
    run_iteration(args.cell, args.iter)


if __name__ == "__main__":
    main()
