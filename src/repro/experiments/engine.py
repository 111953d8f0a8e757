"""Typed execution configuration for the convergence engines.

Historically :func:`repro.experiments.convergence.run_convergence_batch`
took a stringly-typed ``engine: str = "auto"`` kwarg plus scattered
execution keywords, and the fused scan signalled its one unsupported case
by raising a ``ValueError`` whose *text* callers string-matched.  This
module replaces both:

* :class:`EngineConfig` — a frozen dataclass bundling every execution
  decision: engine kind, the scenario-axis device mesh, the §6
  slot-universe residency budget, and the evaluation cadence.  Legacy
  ``engine="scan"|"host"|"auto"`` strings keep working as deprecated
  aliases (:func:`as_engine_config` emits a ``DeprecationWarning``).
* :class:`EngineCapability` — a structured capability report with stable
  reason codes (``CAP_*``), so ``auto`` routing, error messages, and
  tests compare codes instead of exception prose.  The fused engine
  raises :class:`EngineCapabilityError` (a ``ValueError`` carrying the
  report) for genuinely unsupported configs.

This module is dependency-light on purpose: :mod:`repro.experiments.fused`
imports it, never the other way around.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Any

#: capability reason codes (stable API — tests compare these, not prose)
CAP_OK = "ok"
#: the §6 ladder universe exceeds the dense residency budget; the scan
#: runs anyway with the tiled active-slot cache (supported, informational)
CAP_TILED = "slot-universe-tiled"
#: even the tiled cache's resident active-slot set exceeds the budget —
#: the one genuinely unsupported fused-scan case (route to the host engine)
CAP_ACTIVE_SET = "active-slots-exceed-budget"
#: kernel_backend="pallas" requested but the problem publishes no Pallas
#: kernels (FusedKernels.sub_blocks_pallas is None)
CAP_PALLAS_UNAVAILABLE = "pallas-kernels-unavailable"
#: kernel_backend="pallas" requested for a problem whose in-flight value
#: dtype the Pallas kernels don't cover (only float32 is validated)
CAP_PALLAS_DTYPE = "pallas-unsupported-dtype"
#: kernel_backend="pallas" requested together with the host engine, which
#: drives the problem's numpy wrappers and never takes the Pallas path
CAP_PALLAS_HOST = "pallas-requires-scan-engine"
#: kernel_backend="pallas" requested for a grid-cache config on an
#: accelerator: the grid-cache kernel's state is float64/int64, which
#: XLA:TPU refuses inside a Pallas call (ROADMAP 1.3 moves it to f32/i32)
CAP_PALLAS_X64_STATE = "pallas-grid-cache-needs-x64"
#: kind="auto" on an accelerator for a config the scan cannot run: the
#: host engine is never chosen silently there (ask for kind="host")
CAP_AUTO_NO_HOST = "auto-never-routes-to-host-on-accelerator"
#: a §6 load-balanced config on an accelerator: its scan body compiles
#: for a TPU v5e, but no run of it has finished there (ROADMAP 2.1)
CAP_LB_ACCELERATOR = "lb-scan-unfinished-on-accelerator"

_KINDS = ("auto", "scan", "host")
_KERNEL_BACKENDS = ("xla", "pallas")


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Execution configuration of one convergence-batch run.

    ``kind`` selects the implementation (``"scan"`` — the fused
    ``jax.lax.scan`` engine, ``"host"`` — the numpy-driven batched loop,
    ``"auto"`` — scan unless :func:`repro.experiments.fused.scan_capability`
    reports the config unsupported, which routes to the host engine on
    the CPU and raises :class:`EngineCapabilityError` on an accelerator).

    ``num_devices`` / ``mesh`` shard the *scenario axis* of the fused scan
    over devices via ``shard_map`` (see
    :func:`repro.launch.mesh.make_scenario_mesh`).  ``None`` runs
    unsharded on the default device; an explicit ``mesh`` (a 1-D
    ``jax.sharding.Mesh`` over the batch axis) takes precedence over
    ``num_devices``.  Per-scenario results are bit-exact against the
    unsharded scan for any device count (uneven ``S % D`` batches are
    edge-padded and sliced back).

    ``slot_budget`` caps how many §6 slot-universe entries the fused scan
    keeps *densely resident* per scenario (default
    ``fused.LB_MAX_SLOTS``).  Universes above the budget run with the
    tiled active-slot cache instead of falling back to the host engine.

    ``eval_every`` is the suboptimality evaluation cadence (iterations).

    ``kernel_backend`` selects how the fused scan evaluates its two hot
    paths (the §3 block-subgradient gather and the §5 grid-cache event
    application): ``"xla"`` — the jnp forms (default), ``"pallas"`` — the
    ``repro.kernels`` Pallas twins (``interpret=True`` on CPU so CI
    exercises the path everywhere; compiled on TPU, where the grid-cache
    kernel is refused until its state leaves float64).  Results are pinned
    bit-exact across backends on the CPU.
    """

    kind: str = "auto"
    num_devices: int | None = None
    mesh: Any | None = None  # a 1-D jax.sharding.Mesh over the batch axis
    slot_budget: int | None = None
    eval_every: int = 1
    kernel_backend: str = "xla"

    def __post_init__(self):
        if self.kind not in _KINDS:
            raise ValueError(
                f"unknown engine kind {self.kind!r}; expected one of {_KINDS}"
            )
        if self.kernel_backend not in _KERNEL_BACKENDS:
            raise ValueError(
                f"unknown kernel_backend {self.kernel_backend!r}; expected "
                f"one of {_KERNEL_BACKENDS}"
            )
        if self.num_devices is not None and self.num_devices < 1:
            raise ValueError("num_devices must be >= 1")
        if self.slot_budget is not None and self.slot_budget < 1:
            raise ValueError("slot_budget must be >= 1")
        if self.eval_every < 1:
            raise ValueError("eval_every must be >= 1")


def as_engine_config(engine, *, _stacklevel: int = 2) -> EngineConfig:
    """Coerce ``engine`` to an :class:`EngineConfig`.

    Accepts an :class:`EngineConfig` (returned unchanged), ``None`` (the
    defaults), or a legacy ``"auto"|"scan"|"host"`` string — the
    deprecated alias for ``EngineConfig(kind=...)``, kept working with a
    ``DeprecationWarning``.

    ``_stacklevel`` lets the engine entry points that merely forward
    their ``engine`` kwarg here (e.g. ``run_convergence_batch``) attribute
    the warning to *their* caller — the line that actually wrote the
    legacy string — instead of to the forwarding frame.  The default
    points at a direct caller of this function.
    """
    if engine is None:
        return EngineConfig()
    if isinstance(engine, EngineConfig):
        return engine
    if isinstance(engine, str):
        warnings.warn(
            f"engine={engine!r} strings are deprecated; pass "
            f"EngineConfig(kind={engine!r}) instead",
            DeprecationWarning,
            stacklevel=_stacklevel,
        )
        return EngineConfig(kind=engine)
    raise TypeError(
        f"engine must be an EngineConfig or a legacy string, got {type(engine)}"
    )


@dataclasses.dataclass(frozen=True)
class EngineCapability:
    """Structured report of whether the fused scan can run a config.

    ``code`` is one of the ``CAP_*`` constants; ``supported`` says whether
    ``engine kind="scan"`` will run (possibly tiled) or raise.  The slot
    accounting fields let callers and error messages name the limit
    without re-deriving it: ``slots_total`` is the full §6 ladder
    universe, ``slots_resident`` how many slots the selected cache layout
    keeps densely materialized per scenario, ``slot_budget`` the budget
    they were compared against.
    """

    supported: bool
    code: str
    detail: str = ""
    slots_total: int = 0
    slots_resident: int = 0
    slot_budget: int = 0


class EngineCapabilityError(ValueError):
    """Raised by the fused engine for genuinely unsupported configs.

    A ``ValueError`` for backwards compatibility; carries the structured
    :class:`EngineCapability` as ``.capability`` so callers branch on
    ``capability.code`` instead of matching the message text.
    """

    def __init__(self, capability: EngineCapability):
        super().__init__(capability.detail)
        self.capability = capability
