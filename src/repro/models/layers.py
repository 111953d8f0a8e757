"""Common layers + the parameter-declaration system.

Every parameter is declared once (shape, per-dim logical axes, init), and
generic walkers derive from the declarations:

  * materialized parameters          (``init_from_decls``)
  * ShapeDtypeStruct abstract params (``abstract_from_decls`` — dry-run)
  * PartitionSpec trees              (``specs_from_decls`` via logical->mesh
                                      rules; TP over 'model', optional FSDP
                                      over 'data')

Logical axis names: vocab, embed, heads, kv_heads, head, mlp, expert,
expert_mlp, lora, d_inner, ssm_heads, state, groups, conv, layers, pos, none.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig

# ---------------------------------------------------------------------------
# TP-reduction dtype: projections that contract a 'model'-sharded dim emit
# partial sums that XLA all-reduces in the dot's output dtype.  bf16 halves
# that wire volume (per-shard MXU accumulation stays fp32 internally).
# ---------------------------------------------------------------------------

_TP_REDUCE_DTYPE = None  # None -> XLA default (fp32 accum type)


def set_tp_reduce_dtype(dtype) -> None:
    global _TP_REDUCE_DTYPE
    _TP_REDUCE_DTYPE = dtype


def tp_contract(subscript: str, x, w):
    """einsum whose contraction dim is TP-sharded (the psum site)."""
    if _TP_REDUCE_DTYPE is not None:
        return jnp.einsum(subscript, x, w, preferred_element_type=_TP_REDUCE_DTYPE)
    return jnp.einsum(subscript, x, w)


# ---------------------------------------------------------------------------
# Parameter declarations
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ParamDecl:
    shape: tuple[int, ...]
    logical: tuple[str, ...]  # one logical axis name per dim
    init: str = "normal"  # normal | zeros | ones | scaled (1/sqrt(fan_in))
    dtype: str | None = None  # override model dtype (e.g. fp32 for norms)

    def __post_init__(self):
        assert len(self.shape) == len(self.logical), (self.shape, self.logical)


#: logical axes that stack independent weight matrices (never a fan-in)
_STACK_AXES = ("layers", "expert")


def _init_leaf(decl: ParamDecl, key, dtype) -> jnp.ndarray:
    dt = jnp.dtype(decl.dtype or dtype)
    if decl.init == "zeros":
        return jnp.zeros(decl.shape, dt)
    if decl.init == "ones":
        return jnp.ones(decl.shape, dt)
    if decl.init == "scaled":
        # fan-in is the first dim that is not a stacking axis: a scanned
        # layer stack or an expert bank must not set the scale
        fan_in = next(n for n, ax in zip(decl.shape, decl.logical) if ax not in _STACK_AXES)
        std = 1.0 / math.sqrt(max(fan_in, 1))
        return (jax.random.normal(key, decl.shape, jnp.float32) * std).astype(dt)
    if decl.init == "normal":
        return (jax.random.normal(key, decl.shape, jnp.float32) * 0.02).astype(dt)
    raise ValueError(decl.init)


def is_decl(x) -> bool:
    return isinstance(x, ParamDecl)


def init_from_decls(decls, key, dtype) -> Any:
    leaves, treedef = jax.tree.flatten(decls, is_leaf=is_decl)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(
        treedef, [_init_leaf(d, k, dtype) for d, k in zip(leaves, keys)]
    )


def abstract_from_decls(decls, dtype) -> Any:
    return jax.tree.map(
        lambda d: jax.ShapeDtypeStruct(d.shape, jnp.dtype(d.dtype or dtype)),
        decls,
        is_leaf=is_decl,
    )


def make_rules(cfg: ModelConfig, fsdp: bool) -> dict[str, str | None]:
    """Logical-axis -> mesh-axis mapping.  TP over 'model'; FSDP adds 'data'
    on the embed axis.  MoE: shard the expert dim when it divides the TP
    degree (deepseek 160/16), else shard each expert's ffn dim (grok 8e)."""
    rules: dict[str, str | None] = {
        "vocab": "model",
        "heads": "model",
        # kv weights replicated unless the (padded) kv head count is TP-
        # divisible (MHA models like qwen1.5-32b shard kv over 'model')
        "kv_heads": "model"
        if (cfg.num_kv_heads + cfg.kv_pad_to - 1) // cfg.kv_pad_to * cfg.kv_pad_to % 16 == 0
        else None,
        "head": None,
        "mlp": "model",
        "lora": None,
        "d_inner": "model",
        "ssm_heads": "model",
        "state": None,
        "groups": None,
        "conv": None,
        "layers": None,
        "pos": None,
        "none": None,
        "embed": "data" if fsdp else None,
        "embed2": "data" if fsdp else None,
        "expert": "model",
        "expert_mlp": None,
    }
    if cfg.num_experts and cfg.num_experts % 16 != 0:
        rules["expert"] = None
        rules["expert_mlp"] = "model"
    return rules


def specs_from_decls(decls, rules: dict[str, str | None]) -> Any:
    def to_spec(d: ParamDecl) -> P:
        return P(*[rules.get(ax) for ax in d.logical])

    return jax.tree.map(to_spec, decls, is_leaf=is_decl)


# ---------------------------------------------------------------------------
# Normalization
# ---------------------------------------------------------------------------


def rmsnorm_decls(dim: int, axis: str = "embed2") -> dict[str, ParamDecl]:
    return {"scale": ParamDecl((dim,), (axis,), init="ones", dtype="float32")}


def rmsnorm(params, x, eps: float) -> jnp.ndarray:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * params["scale"]).astype(dt)


def layernorm_decls(dim: int, axis: str = "embed2") -> dict[str, ParamDecl]:
    return {
        "scale": ParamDecl((dim,), (axis,), init="ones", dtype="float32"),
        "bias": ParamDecl((dim,), (axis,), init="zeros", dtype="float32"),
    }


def layernorm(params, x, eps: float) -> jnp.ndarray:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    x = (x - mean) * jax.lax.rsqrt(var + eps)
    return (x * params["scale"] + params["bias"]).astype(dt)


def norm_decls(cfg: ModelConfig, dim: int | None = None) -> dict[str, ParamDecl]:
    dim = dim or cfg.d_model
    if cfg.family == "enc_dec":
        return layernorm_decls(dim)
    return rmsnorm_decls(dim)


def apply_norm(cfg: ModelConfig, params, x) -> jnp.ndarray:
    if cfg.family == "enc_dec":
        return layernorm(params, x, cfg.norm_eps)
    return rmsnorm(params, x, cfg.norm_eps)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: [..., seq, heads, head_dim]; positions: [..., seq]."""
    head_dim = x.shape[-1]
    freqs = rope_frequencies(head_dim, theta)  # [hd/2]
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [..., s, hd/2]
    cos = jnp.cos(angles)[..., :, None, :]  # [..., s, 1, hd/2]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP (dense FFN)
# ---------------------------------------------------------------------------


def mlp_decls(cfg: ModelConfig, d_ff: int | None = None, swiglu: bool = True):
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    if swiglu:
        return {
            "w_gate": ParamDecl((d, f), ("embed", "mlp"), init="scaled"),
            "w_up": ParamDecl((d, f), ("embed", "mlp"), init="scaled"),
            "w_down": ParamDecl((f, d), ("mlp", "embed"), init="scaled"),
        }
    return {
        "w_up": ParamDecl((d, f), ("embed", "mlp"), init="scaled"),
        "b_up": ParamDecl((f,), ("mlp",), init="zeros"),
        "w_down": ParamDecl((f, d), ("mlp", "embed"), init="scaled"),
        "b_down": ParamDecl((d,), ("embed",), init="zeros"),
    }


def mlp_apply(params, x, swiglu: bool = True) -> jnp.ndarray:
    if swiglu:
        gate = jnp.einsum("...d,df->...f", x, params["w_gate"])
        up = jnp.einsum("...d,df->...f", x, params["w_up"])
        h = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
        return tp_contract("...f,fd->...d", h, params["w_down"])
    h = jnp.einsum("...d,df->...f", x, params["w_up"]) + params["b_up"].astype(x.dtype)
    h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    return tp_contract("...f,fd->...d", h, params["w_down"]) + params[
        "b_down"
    ].astype(x.dtype)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------


def round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def embed_decls(cfg: ModelConfig) -> dict[str, ParamDecl]:
    v = round_up(cfg.vocab_size, 256)  # pad for clean vocab sharding
    out = {"tok": ParamDecl((v, cfg.d_model), ("vocab", "embed"))}
    if not cfg.tie_embeddings:
        out["unembed"] = ParamDecl((cfg.d_model, v), ("embed", "vocab"), init="scaled")
    return out


def embed_lookup(params, tokens, d_model: int, dtype) -> jnp.ndarray:
    return params["tok"].astype(dtype)[tokens]


def unembed(cfg: ModelConfig, params, x) -> jnp.ndarray:
    if cfg.tie_embeddings:
        return jnp.einsum("...d,vd->...v", x, params["tok"].astype(x.dtype))
    return jnp.einsum("...d,dv->...v", x, params["unembed"].astype(x.dtype))
