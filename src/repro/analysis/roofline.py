"""Three-term roofline derivation from a compiled dry-run artifact.

Per-chip peaks come from :data:`PEAKS`, keyed by ``jax.Device.device_kind``;
a kind that is not in the table is an error, never a default.

  compute term    = HLO_FLOPs / peak_flops           (per-device HLO)
  memory term     = HLO_bytes / hbm_bw
  collective term = wire_bytes / link_bw             (ring model, per device)

The dominant term is the bottleneck; roofline fraction for the report is
  max(compute, memory, collective) vs. the ideal compute-only time,
and MODEL_FLOPS / HLO_FLOPs measures how much compiled compute is useful
(remat recompute, MoE capacity slack, head padding all show up here).
"""

from __future__ import annotations

import dataclasses
import math

from repro.analysis.hlo import HloCost, analyze_hlo, sxs_buffer_bytes
from repro.configs.base import ModelConfig, ShapeConfig


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops: float  # bf16 FLOP/s per chip
    hbm_bw: float  # bytes/s per chip
    link_bw: float  # bytes/s per ICI link


#: ``device_kind`` JAX reports for a TPU v5e chip
V5E = "TPU v5 lite"

#: Published per-chip peaks by ``device_kind``.  TPU v5e: Google Cloud
#: documentation, "TPU v5e" — 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s,
#: 1,600 Gbit/s chip-to-chip interconnect (200 GB/s over 4 links, so
#: 50 GB/s per link).
PEAKS = {
    V5E: ChipPeaks(flops=197e12, hbm_bw=819e9, link_bw=50e9),
}


def peaks_for(device_kind: str) -> ChipPeaks:
    """The published peaks of ``device_kind``; raises on an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; add them "
            f"to repro.analysis.roofline.PEAKS with their source"
        ) from None


@dataclasses.dataclass
class Roofline:
    flops_per_device: float
    bytes_per_device: float
    collectives: dict
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops_per_device: float
    useful_flops_fraction: float
    step_time_s: float
    mfu: float
    attn_score_bytes: float = 0.0
    memory_s_flash: float = 0.0  # memory term with score traffic fused away

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def model_flops(
    cfg: ModelConfig, shape: ShapeConfig, num_params: int, active_params: int | None
) -> float:
    """MODEL_FLOPS = 6·N·D for training (N = active params for MoE),
    2·N·D for inference forward passes (D = processed tokens)."""
    n = active_params or num_params
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    # decode: one token per sequence
    return 2.0 * n * shape.global_batch


def active_params(cfg: ModelConfig, num_params: int) -> int | None:
    """Active parameters per token for MoE models (shared + top-k routed)."""
    if not cfg.num_experts:
        return None
    full_expert = 3 * cfg.d_model * cfg.d_ff_expert  # swiglu
    routed_total = cfg.num_experts * full_expert * cfg.num_layers
    routed_active = cfg.top_k * full_expert * cfg.num_layers
    return num_params - routed_total + routed_active


def derive(
    cfg: ModelConfig,
    shape: ShapeConfig,
    num_params: int,
    cost: dict[str, float],
    hlo_text: str,
    num_devices: int,
    device_kind: str,
) -> Roofline:
    # NOTE: cost_analysis() on the CPU backend counts while-loop bodies once
    # (see analysis/hlo.py header), so all three terms come from the
    # loop-aware HLO analysis; `cost` is kept only as a cross-check input.
    coll = analyze_hlo(hlo_text)
    flops = coll.flops
    bytes_accessed = coll.bytes
    peaks = peaks_for(device_kind)
    compute_s = flops / peaks.flops
    memory_s = bytes_accessed / peaks.hbm_bw
    collective_s = coll.total_wire_bytes / peaks.link_bw
    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    dominant = max(terms, key=terms.get)
    mf = model_flops(cfg, shape, num_params, active_params(cfg, num_params))
    mf_dev = mf / num_devices
    step = max(terms.values())
    score_bytes = sxs_buffer_bytes(hlo_text)
    return Roofline(
        attn_score_bytes=score_bytes,
        memory_s_flash=max(bytes_accessed - score_bytes, 0.0) / peaks.hbm_bw,
        flops_per_device=flops,
        bytes_per_device=bytes_accessed,
        collectives=coll.as_dict(),
        compute_s=compute_s,
        memory_s=memory_s,
        collective_s=collective_s,
        dominant=dominant,
        model_flops_per_device=mf_dev,
        useful_flops_fraction=mf_dev / flops if flops else 0.0,
        step_time_s=step,
        mfu=(mf_dev / peaks.flops) / step if step > 0 else 0.0,
    )
