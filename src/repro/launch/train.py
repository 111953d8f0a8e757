"""End-to-end trainer: DSAG Tier-1 step + Tier-2 control loop.

Runs anywhere: on CPU it trains reduced configs for real (examples/
quickstart.py), on a pod slice it is the production entry point.  Wires
together:

  model zoo / paper problems -> dsag_pjit step -> deadline controller
  (mask/flush/evict) -> failure detector -> checkpoint manager ->
  (optional) straggler simulation

Two kinds of jobs share the loop:

* transformer archs from the model zoo (``--arch qwen1.5-0.5b``), the
  scaffold's LLM smoke path;
* the paper's problems (``--arch logreg`` / ``--arch pca``,
  ``launch/paper_jobs.py``), which is the *live* counterpart of the
  convergence engines — replay a ``FleetTraces`` scenario through the
  controller (``TrainerOptions.traces``) and the (mask, flush, evict)
  streams match the scalar ``TrainingSimulator`` bit-for-bit (the
  cross-layer pin; see ``repro/ft/validation.py``), while
  ``time_scale > 0`` turns the virtual straggler waits into real sleeps
  so measured wall-clock reflects each method's §5 semantics.

Usage (CPU-scale):
  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b --smoke \
      --steps 100 --batch 8 --seq 128
  PYTHONPATH=src python -m repro.launch.train --arch logreg --smoke \
      --steps 20 --check
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.compile_cache import enable_compile_cache
from repro.configs import TrainConfig, get_config, get_smoke_config
from repro.core.dsag_pjit import (
    GroupSpec,
    init_train_state,
    make_group_spec,
    make_train_step,
    train_state_specs,
)
from repro.data import make_batch_iterator
from repro.ft import DeadlineController, FailureDetector
from repro.ft.validation import trace_latency_fn
from repro.latency.model import make_heterogeneous_cluster
from repro.launch.paper_jobs import (
    PAPER_ARCHES,
    make_paper_job,
    paper_train_config,
)
from repro.models import build_model
from repro.models.sharding import set_mesh


@dataclasses.dataclass
class TrainerOptions:
    arch: str = "qwen1.5-0.5b"
    smoke: bool = True
    steps: int = 50
    global_batch: int = 8
    seq_len: int = 128
    seed: int = 0
    checkpoint_dir: str | None = None
    restore: bool = False
    mesh: Any | None = None
    train_config: TrainConfig = dataclasses.field(default_factory=TrainConfig)
    #: simulate straggling groups (CPU runs): per-step latency draws feed the
    #: deadline controller exactly like real step timings would on a pod
    simulate_stragglers: bool = True
    dsag_w: int | None = None  # wait-for-w groups (default: 3/4 of P)
    log_every: int = 10
    # ---- paper-problem / live-validation options -------------------------
    num_groups: int | None = None  # group count for paper archs (default 4)
    samples: int = 1024  # problem size for paper archs
    method: str = "dsag"  # dsag | sag (controller stale-acceptance mode)
    #: replay a pre-sampled FleetTraces scenario through the controller
    #: instead of live-sampling the straggler cluster (the pinned path)
    traces: Any | None = None
    scenario: int = 0
    #: seconds of real sleep per unit of virtual straggler time; > 0 makes
    #: measured wall-clock reflect the method's §5 collection behavior
    time_scale: float = 0.0
    eval_every: int = 0  # paper archs: suboptimality eval cadence (0 = off)
    failure_max_misses: int = 5


class Trainer:
    def __init__(self, opts: TrainerOptions):
        self.opts = opts
        tc = opts.train_config
        if opts.method not in ("dsag", "sag"):
            raise ValueError(f"method {opts.method!r} not in ('dsag', 'sag')")
        self.job = None
        if opts.arch in PAPER_ARCHES:
            G = opts.num_groups or 4
            self.gs = GroupSpec(num_groups=G, axes=())
            self.job = make_paper_job(
                opts.arch, G, samples=opts.samples, seed=opts.seed
            )
            self.data = self.job.batch_iterator()
            loss_fn = self.job.loss_fn
            project_fn = self.job.project_fn if opts.arch == "pca" else None
            self.state_shardings = None
            step = make_train_step(
                loss_fn, tc, self.gs, None, None, project_fn=project_fn
            )
            self.step_fn = jax.jit(step, donate_argnums=(0,))
        else:
            cfg = get_smoke_config(opts.arch) if opts.smoke else get_config(opts.arch)
            self.cfg = cfg
            self.model = build_model(cfg)
            set_mesh(opts.mesh)
            self.gs = make_group_spec(tc, opts.mesh)
            if opts.global_batch % self.gs.num_groups:
                raise ValueError(
                    f"global batch {opts.global_batch} not divisible by "
                    f"{self.gs.num_groups} DSAG groups"
                )
            self.data = make_batch_iterator(
                cfg, self.gs.num_groups, opts.global_batch, opts.seq_len, seed=opts.seed
            )

            def loss_fn(params, batch):
                return self.model.train_loss(params, batch, remat=tc.remat)

            param_specs = (
                self.model.param_specs(tc.fsdp) if opts.mesh is not None else None
            )
            step = make_train_step(loss_fn, tc, self.gs, opts.mesh, param_specs)
            if opts.mesh is not None:
                from jax.sharding import NamedSharding

                specs = train_state_specs(tc, self.gs, self.model.param_specs(tc.fsdp))
                self.state_shardings = jax.tree.map(
                    lambda s: NamedSharding(opts.mesh, s),
                    specs,
                    is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec),
                )
            else:
                self.state_shardings = None
            self.step_fn = jax.jit(step, donate_argnums=(0,))

        # Tier-2 control plane
        w = opts.dsag_w or max(1, (3 * self.gs.num_groups) // 4)
        self.deadlines = DeadlineController(
            self.gs.num_groups,
            w=w,
            margin=tc.dsag_margin,
            accepts_stale=opts.method == "dsag",
        )
        self.failures = FailureDetector(
            self.gs.num_groups, max_misses=opts.failure_max_misses
        )
        self.ckpt = (
            CheckpointManager(opts.checkpoint_dir, keep=tc.keep_checkpoints)
            if opts.checkpoint_dir
            else None
        )
        if opts.traces is not None:
            loads = (
                self.job.loads
                if self.job is not None
                else np.ones(self.gs.num_groups)
            )
            self._latency_of = trace_latency_fn(opts.traces, opts.scenario, loads)
            self._churn = opts.traces.churn
            self.straggler_sim = None
        else:
            self._latency_of = None
            self._churn = None
            self.straggler_sim = (
                make_heterogeneous_cluster(
                    self.gs.num_groups,
                    comp_range=(0.9, 1.4),
                    comm_range=(0.01, 0.05),
                    cv_comp=0.08,
                    seed=opts.seed + 3,
                )
                if opts.simulate_stragglers
                else None
            )

    # -- lifecycle ---------------------------------------------------------
    def init_state(self):
        if self.job is not None:
            params = self.job.init_params(self.opts.seed)
        else:
            params = self.model.init(jax.random.key(self.opts.seed))
        state = init_train_state(params, self.opts.train_config, self.gs)
        if self.state_shardings is not None:
            state = jax.tree.map(
                lambda x, s: jax.device_put(x, s), state, self.state_shardings
            )
        return state

    def maybe_restore(self, state):
        if self.ckpt is None or not self.opts.restore:
            return state, 0
        restored, step = self.ckpt.restore_latest(state, self.state_shardings)
        if restored is None:
            return state, 0
        print(f"[train] restored checkpoint at step {step}")
        return restored, step + 1

    def _group_latencies(self, step: int) -> np.ndarray:
        if self.straggler_sim is None:
            return np.ones(self.gs.num_groups)
        return self.straggler_sim.sample_all(c=1.0, now=float(step))

    def _step_inputs(self, step: int):
        """One Tier-2 decision: (mask, flush, evict, virtual elapsed)."""
        if self._latency_of is not None:
            alive = (
                self._churn.alive_at(self.deadlines.now)
                if self._churn is not None
                else None
            )
            si = self.deadlines.step_inputs(self._latency_of, alive=alive)
            mask_np, flush_np, evict_np = si.mask, si.flush, si.evict
            elapsed = si.elapsed
        else:
            lat = self._group_latencies(step)
            mask_np, flush_np = self.deadlines.step_masks(lat, step)
            evict_np = np.zeros(self.gs.num_groups, dtype=bool)
            elapsed = 0.0
        was_failed = self.failures.failed.copy()
        self.failures.observe(mask_np)
        # failed groups cannot flush; newly-failed groups get their cache
        # entry evicted (paper §6.3) so H stays unbiased
        flush_np = np.logical_and(flush_np, ~self.failures.failed)
        evict_np = np.logical_or(
            evict_np, np.logical_and(self.failures.failed, ~was_failed)
        )
        return mask_np, flush_np, evict_np, elapsed

    # -- main loop ----------------------------------------------------------
    def run(self) -> dict[str, list]:
        opts = self.opts
        tc = opts.train_config
        state = self.init_state()
        state, start_step = self.maybe_restore(state)
        history: dict[str, list] = {
            "loss": [],
            "xi": [],
            "mask_count": [],
            "step_time": [],
            "virtual": [],
            "eval": [],  # (step, wall s, virtual s, suboptimality)
            # per-step Tier-2 decisions, for the cross-layer pin against the
            # scalar simulator's recorded streams (ft/validation.py)
            "mask_stream": [],
            "flush_stream": [],
            "evict_stream": [],
        }
        #: device-side metric buffer — materialized every log_every steps
        #: (and at the end) so the host never forces a per-step sync
        pending: list[tuple[int, dict, float]] = []

        def drain():
            for s, m, dt in pending:
                history["loss"].append(float(m["loss"]))
                history["xi"].append(float(m["xi"]))
                history["mask_count"].append(int(m["mask_count"]))
                history["step_time"].append(dt)
            pending.clear()

        wall0 = time.perf_counter()
        for step in range(start_step, opts.steps):
            batch = next(self.data)
            if tc.dsag:
                mask_np, flush_np, evict_np, elapsed = self._step_inputs(step)
                history["mask_stream"].append(mask_np.copy())
                history["flush_stream"].append(flush_np.copy())
                history["evict_stream"].append(evict_np.copy())
            else:
                mask_np = np.ones(self.gs.num_groups, bool)
                flush_np = np.zeros(self.gs.num_groups, bool)
                evict_np = flush_np
                elapsed = 0.0
            if opts.time_scale > 0 and elapsed > 0:
                # make the virtual straggler wait real: measured wall-clock
                # then reflects the method's §5 collection behavior
                time.sleep(elapsed * opts.time_scale)
            t0 = time.perf_counter()
            state, metrics = self.step_fn(
                state,
                jax.tree.map(jnp.asarray, batch),
                jnp.asarray(mask_np),
                jnp.asarray(flush_np),
                jnp.asarray(evict_np),
            )
            pending.append((step, metrics, time.perf_counter() - t0))
            history["virtual"].append(float(self.deadlines.now))
            if (
                self.job is not None
                and opts.eval_every > 0
                and (step % opts.eval_every == 0 or step == opts.steps - 1)
            ):
                # pulls the params (a sync point) — keep the cadence coarse
                gap = self.job.suboptimality(state["params"])
                history["eval"].append(
                    (step, time.perf_counter() - wall0, float(self.deadlines.now), gap)
                )
            if step % opts.log_every == 0:
                drain()
                print(
                    f"[train] step {step:5d} loss {history['loss'][-1]:.4f} "
                    f"xi {history['xi'][-1]:.2f} "
                    f"fresh {history['mask_count'][-1]}/{self.gs.num_groups} "
                    f"({history['step_time'][-1]*1e3:.0f} ms)"
                )
            if self.ckpt and (step + 1) % tc.checkpoint_every == 0:
                self.ckpt.save(step, state)
        drain()
        if self.ckpt and opts.steps > start_step:
            self.ckpt.save(opts.steps - 1, state, blocking=True)
        history["wall_seconds"] = [time.perf_counter() - wall0]
        return history


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--arch",
        default="qwen1.5-0.5b",
        help=f"model-zoo arch, or one of {PAPER_ARCHES} for the paper's "
        "live CPU problems",
    )
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--samples", type=int, default=1024)
    ap.add_argument("--groups", type=int, default=None)
    ap.add_argument("--method", default="dsag", choices=["dsag", "sag"])
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--restore", action="store_true")
    ap.add_argument("--no-dsag", action="store_true")
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument(
        "--check",
        action="store_true",
        help="assert ξ reached 1.0 and the loss decreased (CI smoke gate)",
    )
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.arch in PAPER_ARCHES:
        lr = args.lr if args.lr != 3e-4 else 0.25  # paper-scale step size
        tc = paper_train_config(lr, dsag=not args.no_dsag)
    else:
        tc = TrainConfig(
            dsag=not args.no_dsag, optimizer=args.optimizer, learning_rate=args.lr
        )
    opts = TrainerOptions(
        arch=args.arch,
        smoke=args.smoke,
        steps=args.steps,
        global_batch=args.batch,
        seq_len=args.seq,
        samples=args.samples,
        num_groups=args.groups,
        method=args.method,
        checkpoint_dir=args.checkpoint_dir,
        restore=args.restore,
        train_config=tc,
    )
    hist = Trainer(opts).run()
    if hist["loss"]:
        print(f"[train] done; final loss {hist['loss'][-1]:.4f}")
    else:
        # e.g. --restore resumed at or past --steps: nothing ran, nothing
        # to report (this used to IndexError)
        print("[train] done; no steps to run")
    if args.check:
        if not hist["loss"]:
            raise SystemExit("[check] FAILED: no steps ran")
        first = float(np.mean(hist["loss"][: max(1, len(hist["loss"]) // 4)]))
        last = float(np.mean(hist["loss"][-max(1, len(hist["loss"]) // 4) :]))
        xi_max = max(hist["xi"])
        ok = last < first and xi_max >= 1.0 - 1e-6
        print(
            f"[check] loss {first:.4f} -> {last:.4f}; max xi {xi_max:.3f}: "
            f"{'OK' if ok else 'FAILED'}"
        )
        if not ok:
            raise SystemExit(1)


if __name__ == "__main__":
    main()
