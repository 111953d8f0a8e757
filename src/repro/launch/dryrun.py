import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production mesh and extract memory / cost / collective analyses.

The three lines above MUST stay first: jax locks the platform and the
device count on first initialization, and the production meshes need 512
host devices.  The platform is pinned to the CPU so that, on a machine with
an accelerator, neither this process nor the per-cell children it spawns
take the chip.

  one cell:  PYTHONPATH=src python -m repro.launch.dryrun \
                 --arch qwen2-7b --shape train_4k [--multi-pod]
  all cells: PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
             (spawns one subprocess per cell; resumes from existing JSON)

Results land in experiments/dryrun/<mesh>/<arch>__<shape>.json and are the
inputs for EXPERIMENTS.md §Dry-run / §Roofline.
"""

import argparse
import dataclasses
import json
import subprocess
import sys
import time
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis import roofline as roofline_mod
from repro.configs import (
    ARCHS,
    SHAPES,
    TrainConfig,
    cell_is_runnable,
    get_config,
    input_specs,
)
from repro.core.dsag_pjit import (
    GroupSpec,
    init_train_state,
    make_group_spec,
    make_train_step,
    train_state_specs,
)
from repro.launch.mesh import make_production_mesh
from repro.models import build_model
from repro.models.model import cache_abstract, cache_specs
from repro.models.sharding import set_mesh

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "experiments", "dryrun")


# ---------------------------------------------------------------------------
# Per-arch training configuration heuristics (production defaults)
# ---------------------------------------------------------------------------


def default_train_config(num_params: int, multi_pod: bool, overrides: dict | None = None) -> TrainConfig:
    big = num_params > 50e9
    kwargs: dict[str, Any] = dict(
        optimizer="adafactor" if big else "adamw",
        fsdp=num_params > 1e9,
        dsag=True,
        dsag_cache_dtype="int8" if num_params > 10e9 else "bfloat16",
        remat="full",
    )
    if big:
        # pod-granularity groups multi-pod; ZeRO-layout time-sliced groups on
        # a single pod (see DESIGN.md §6 memory discussion)
        kwargs.update(
            dsag_groups="pod" if multi_pod else "zero", dsag_num_groups=2
        )
    else:
        kwargs.update(dsag_groups="dp")
    if overrides:
        kwargs.update(overrides)
    return TrainConfig(**kwargs)


def sanitize_spec(spec: P, shape, mesh) -> P:
    """Drop mesh axes from dims they do not evenly divide (e.g. batch=1 cells
    cannot shard the batch axis)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    ent = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for dim, e in zip(shape, ent):
        axes = e if isinstance(e, tuple) else (e,) if e else ()
        factor = 1
        for a in axes:
            factor *= sizes[a]
        out.append(e if factor and dim % factor == 0 else None)
    return P(*out)


def _attach(abstract_tree, spec_tree, mesh):
    """Zip ShapeDtypeStructs with PartitionSpecs (flatten-order aligned)."""
    a_leaves, a_def = jax.tree_util.tree_flatten(abstract_tree)
    s_leaves = [
        s
        for s in jax.tree_util.tree_leaves(
            spec_tree, is_leaf=lambda x: isinstance(x, P)
        )
    ]
    assert len(a_leaves) == len(s_leaves), (len(a_leaves), len(s_leaves))
    out = [
        jax.ShapeDtypeStruct(
            a.shape,
            a.dtype,
            sharding=NamedSharding(mesh, sanitize_spec(s, a.shape, mesh)),
        )
        for a, s in zip(a_leaves, s_leaves)
    ]
    return jax.tree_util.tree_unflatten(a_def, out)


def _grouped_batch_abstract(cfg, shape, gs: GroupSpec, mesh):
    """[P, B/P, ...] train-batch stand-ins with group-aware shardings."""
    flat = input_specs(cfg, shape, mesh=None)
    pcount = gs.num_groups
    inner_dp = tuple(
        a for a in mesh.axis_names if a in ("pod", "data") and a not in gs.axes
    )
    inner = inner_dp if len(inner_dp) > 1 else (inner_dp[0] if inner_dp else None)
    out = {}
    for name, sds in flat.items():
        b = sds.shape[0]
        assert b % pcount == 0, (name, b, pcount)
        shape_g = (pcount, b // pcount) + sds.shape[1:]
        spec = P(gs.group_partition, inner, *([None] * (len(sds.shape) - 1)))
        out[name] = jax.ShapeDtypeStruct(
            shape_g, sds.dtype, sharding=NamedSharding(mesh, spec)
        )
    return out


# ---------------------------------------------------------------------------
# Cell runners
# ---------------------------------------------------------------------------


def run_cell(arch: str, shape_name: str, multi_pod: bool, overrides=None) -> dict:
    """overrides: TrainConfig field overrides (hillclimb iterations)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    set_mesh(mesh)
    model = build_model(cfg)
    nparams = model.num_params()
    t0 = time.time()

    if shape.kind == "train":
        tc = default_train_config(nparams, multi_pod, overrides)
        if tc.bf16_reduce:
            from repro.models.layers import set_tp_reduce_dtype

            set_tp_reduce_dtype(jnp.bfloat16)
        gs = make_group_spec(tc, mesh)
        param_specs = model.param_specs(tc.fsdp)

        def loss_fn(p, b):
            return model.train_loss(p, b, remat=tc.remat, fused_loss=tc.fused_loss)

        step = make_train_step(loss_fn, tc, gs, mesh, param_specs)
        params_abs = model.abstract()
        state_abs = jax.eval_shape(lambda pa: init_train_state(pa, tc, gs), params_abs)
        state_specs = train_state_specs(tc, gs, param_specs)
        state_in = _attach(state_abs, state_specs, mesh)
        batch_in = _grouped_batch_abstract(cfg, shape, gs, mesh)
        mask_in = jax.ShapeDtypeStruct(
            (gs.num_groups,), jnp.bool_, sharding=NamedSharding(mesh, P())
        )
        lowered = jax.jit(step).lower(state_in, batch_in, mask_in, mask_in)
        extra = {"train_config": dataclasses.asdict(tc), "num_groups": gs.num_groups}
    elif shape.kind == "prefill":
        param_specs = model.param_specs(nparams > 1e9)
        params_in = _attach(model.abstract(), param_specs, mesh)
        batch_in = input_specs(cfg, shape, mesh=mesh)

        def prefill(p, b):
            from repro.models.sharding import degather

            p = degather(p, param_specs, mesh)
            return model.prefill(p, b, cache_len=shape.seq_len)

        lowered = jax.jit(prefill).lower(params_in, batch_in)
        extra = {}
    else:  # decode
        param_specs = model.param_specs(nparams > 1e9)
        params_in = _attach(model.abstract(), param_specs, mesh)
        tok_raw = input_specs(cfg, shape, mesh=None)["tokens"]
        dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
        dp = dp if len(dp) > 1 else dp[0]
        tok_in = jax.ShapeDtypeStruct(
            tok_raw.shape,
            tok_raw.dtype,
            sharding=NamedSharding(
                mesh, sanitize_spec(P(dp, None), tok_raw.shape, mesh)
            ),
        )
        cache_abs = cache_abstract(cfg, shape.global_batch, shape.seq_len)
        cache_in = _attach(cache_abs, cache_specs(cfg), mesh)
        idx_in = jax.ShapeDtypeStruct(
            (), jnp.int32, sharding=NamedSharding(mesh, P())
        )
        def decode(p, tok, cache, idx):
            from repro.models.sharding import degather

            p = degather(p, param_specs, mesh)
            return model.decode_step(p, tok, cache, idx)

        lowered = jax.jit(decode, donate_argnums=(2,)).lower(
            params_in, tok_in, cache_in, idx_in
        )
        extra = {}

    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    # the fake host mesh stands in for a v5e pod: project onto v5e peaks
    rl = roofline_mod.derive(
        cfg, shape, nparams, cost, hlo, mesh.devices.size, roofline_mod.V5E
    )

    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "status": "ok",
        "num_params": nparams,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_estimate_bytes": mem.argument_size_in_bytes
            + mem.output_size_in_bytes
            + mem.temp_size_in_bytes
            - mem.alias_size_in_bytes,
        },
        "cost": {k: v for k, v in cost.items() if "flops" in k or k == "bytes accessed"},
        "roofline": rl.as_dict(),
        **extra,
    }
    return result


def result_path(arch: str, shape_name: str, multi_pod: bool) -> str:
    mesh_dir = "2x16x16" if multi_pod else "16x16"
    d = os.path.join(RESULTS_DIR, mesh_dir)
    os.makedirs(d, exist_ok=True)
    return os.path.join(d, f"{arch}__{shape_name}.json")


def run_all(multi_pod: bool, force: bool = False) -> int:
    """Spawn one subprocess per cell (fresh XLA each time); resume-safe."""
    failures = 0
    for arch in ARCHS:
        cfg = get_config(arch)
        for shape_name, shape in SHAPES.items():
            if not cell_is_runnable(cfg, shape):
                continue
            path = result_path(arch, shape_name, multi_pod)
            if os.path.exists(path) and not force:
                with open(path) as f:
                    if json.load(f).get("status") == "ok":
                        print(f"[dryrun] skip (done): {arch} x {shape_name}")
                        continue
            cmd = [
                sys.executable, "-m", "repro.launch.dryrun",
                "--arch", arch, "--shape", shape_name,
            ] + (["--multi-pod"] if multi_pod else [])
            print(f"[dryrun] {arch} x {shape_name} ({'2x16x16' if multi_pod else '16x16'}) ...", flush=True)
            # the child inherits the CPU pin set at the top of this module
            proc = subprocess.run(cmd, capture_output=True, text=True, timeout=3600)
            if proc.returncode != 0:
                failures += 1
                err = (proc.stderr or "")[-2000:]
                with open(path, "w") as f:
                    json.dump(
                        {"arch": arch, "shape": shape_name, "status": "fail",
                         "mesh": "2x16x16" if multi_pod else "16x16",
                         "error": err},
                        f, indent=2,
                    )
                print(f"[dryrun]   FAIL:\n{err}")
            else:
                print(proc.stdout.strip().splitlines()[-1] if proc.stdout.strip() else "")
    return failures


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    if args.all:
        failures = run_all(args.multi_pod, force=args.force)
        sys.exit(1 if failures else 0)

    assert args.arch and args.shape, "--arch and --shape required (or --all)"
    result = run_cell(args.arch, args.shape, args.multi_pod)
    path = result_path(args.arch, args.shape, args.multi_pod)
    with open(path, "w") as f:
        json.dump(result, f, indent=2)
    mem_gb = result["memory"]["peak_estimate_bytes"] / 2**30
    rl = result["roofline"]
    print(
        f"[dryrun] {args.arch} x {args.shape} OK: compile {result['compile_s']:.0f}s, "
        f"~{mem_gb:.2f} GiB/device, terms c/m/x = "
        f"{rl['compute_s']:.4f}/{rl['memory_s']:.4f}/{rl['collective_s']:.4f} s, "
        f"dominant={rl['dominant']}, mfu={rl['mfu']:.3f}"
    )


if __name__ == "__main__":
    main()
