"""Shared model configuration and primitive layers (pure JAX).

One ``ModelConfig`` covers every assigned architecture family:
dense / moe / ssm (rwkv6) / hybrid (zamba2) / vlm / audio. Parameters are
plain pytrees (dicts of jnp arrays); every creator also returns a matching
PartitionSpec tree via ``repro.launch.sharding`` rules.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    family: str = "dense"        # dense | moe | rwkv6 | hybrid | vlm | audio
    n_layers: int = 2
    d_model: int = 256
    n_heads: int = 4
    n_kv_heads: int = 4
    d_ff: int = 1024
    vocab: int = 1024
    head_dim: Optional[int] = None
    rope_theta: float = 10_000.0
    norm_eps: float = 1e-5
    act: str = "silu"            # silu (swiglu) | gelu
    tie_embeddings: bool = False
    # --- MoE ---
    n_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25
    shared_expert_ff: int = 0
    router_jitter: float = 0.0
    # --- SSM / hybrid ---
    ssm_state: int = 0           # mamba2 state size N
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    attn_every: int = 0          # hybrid: shared attention block period
    rwkv_head_dim: int = 64
    # --- VLM ---
    cross_attn_every: int = 0    # vlm: cross-attn layer period
    n_image_tokens: int = 0
    # --- numerics / policy ---
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: str = "full"          # none | full | dots
    scan_layers: bool = True
    use_kernels: bool = False    # route hot paths through Pallas kernels
    # --- beyond-paper perf knobs (see EXPERIMENTS.md §Perf) ---
    seq_shard_attn: bool = False   # shard long-context attention over seq

    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads

    def param_count(self) -> int:
        """Total parameters (for 6ND MODEL_FLOPS accounting)."""
        d, f, V, L = self.d_model, self.d_ff, self.vocab, self.n_layers
        hd = self.hd
        emb = V * d * (1 if self.tie_embeddings else 2)
        if self.family in ("dense", "vlm", "audio", "moe"):
            attn = d * (self.n_heads * hd) + 2 * d * (self.n_kv_heads * hd) \
                + (self.n_heads * hd) * d
            if self.family == "moe":
                ff = self.n_experts * (3 * d * f) + d * self.n_experts
                if self.shared_expert_ff:
                    ff += 3 * d * self.shared_expert_ff
            else:
                ff = 3 * d * f
            per_layer = attn + ff + 2 * d
            extra = 0
            if self.family == "vlm" and self.cross_attn_every:
                n_cross = L // self.cross_attn_every
                extra = n_cross * (attn + 2 * d)
            return emb + L * per_layer + extra + d
        if self.family == "rwkv6":
            # time mix: wr/wk/wv/wg/ww + wo = 6 d^2; channel: w_k/w_v (2df)
            # + w_r (d^2); small vectors
            per_layer = 7 * d * d + 2 * d * f + 12 * d
            return emb + L * per_layer + d
        if self.family == "hybrid":
            d_in = self.ssm_expand * d
            h_m = d_in // self.ssm_head_dim
            per_m = d * (2 * d_in + 2 * self.ssm_state + h_m) \
                + d_in * d + 5 * d_in + 2 * h_m + d
            # ONE shared transformer block (attn + MLP), reused at every
            # attn_every-th position (the Zamba2 design)
            hd = self.hd
            shared = d * (self.n_heads * hd) + 2 * d * (self.n_kv_heads * hd) \
                + (self.n_heads * hd) * d + 3 * d * f + 2 * d
            return emb + L * per_m + shared + d
        raise ValueError(self.family)

    def active_param_count(self) -> int:
        """Active parameters per token (MoE: top_k of n_experts)."""
        if self.family != "moe" or not self.n_experts:
            return self.param_count()
        d, f, L = self.d_model, self.d_ff, self.n_layers
        hd = self.hd
        attn = d * (self.n_heads * hd) + 2 * d * (self.n_kv_heads * hd) \
            + (self.n_heads * hd) * d
        ff_active = self.top_k * (3 * d * f) + d * self.n_experts
        if self.shared_expert_ff:
            ff_active += 3 * d * self.shared_expert_ff
        emb = self.vocab * d * (1 if self.tie_embeddings else 2)
        return emb + L * (attn + ff_active + 2 * d) + d


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------


def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float) -> jnp.ndarray:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def act_fn(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[name]


def rope_freqs(hd: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float) -> jnp.ndarray:
    """x: (..., seq, heads, hd); positions: (..., seq)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (...,S,hd/2)
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def init_dense(key, shape, in_axis: int = 0, dtype=jnp.float32):
    fan_in = shape[in_axis]
    std = 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, dtype=jnp.float32) * std).astype(dtype)


def split_keys(key, n: int):
    return list(jax.random.split(key, n))


def causal_mask_logits(scores: jnp.ndarray, q_pos: jnp.ndarray,
                       k_pos: jnp.ndarray) -> jnp.ndarray:
    """scores: (..., q, k) masked where k_pos > q_pos."""
    mask = k_pos[None, :] > q_pos[:, None]
    return jnp.where(mask, jnp.finfo(scores.dtype).min, scores)


# ---------------------------------------------------------------------------
# mesh-aware sharding hints (no-ops outside a mesh context)
# ---------------------------------------------------------------------------


def ambient_mesh_axes() -> dict:
    """{axis_name: size} of the mesh set by ``jax.set_mesh``, or {}
    when there is none."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return {}
    return dict(zip(mesh.axis_names, mesh.axis_sizes))


def model_axis_size() -> int:
    return ambient_mesh_axes().get("model", 1)


def dp_axis_names() -> tuple:
    axes = ambient_mesh_axes()
    return tuple(a for a in ("pod", "data") if a in axes)


def constrain(x: jnp.ndarray, *spec) -> jnp.ndarray:
    """with_sharding_constraint on the ambient mesh; identity off-mesh.
    Axes the mesh lacks, or that do not divide the dim, stay unsharded."""
    axes = ambient_mesh_axes()
    if not axes:
        return x
    fixed = []
    for dim, ax in zip(x.shape, spec):
        if ax is None:
            fixed.append(None)
            continue
        size = 1
        for a in (ax if isinstance(ax, tuple) else (ax,)):
            size *= axes.get(a, 1)
        fixed.append(ax if size > 1 and dim % size == 0 else None)
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.PartitionSpec(*fixed))
