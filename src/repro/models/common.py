"""Shared model primitives: norms, RoPE, initializers, dtype policy."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig

# --------------------------------------------------------------------------- #
# dtype policy
# --------------------------------------------------------------------------- #


def activation_dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def param_dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# --------------------------------------------------------------------------- #
# Initializers (all explicit so full-scale init can go through eval_shape)
# --------------------------------------------------------------------------- #


def dense_init(key, shape, dtype, in_axis_size: Optional[int] = None):
    """Truncated-normal fan-in init (matches common LM practice)."""
    fan_in = in_axis_size if in_axis_size is not None else shape[0]
    std = 1.0 / np.sqrt(max(fan_in, 1))
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * std).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


def zeros_init(_key, shape, dtype):
    return jnp.zeros(shape, dtype)


def ones_init(_key, shape, dtype):
    return jnp.ones(shape, dtype)


# --------------------------------------------------------------------------- #
# Normalization
# --------------------------------------------------------------------------- #


def init_norm(key, cfg: ModelConfig, d: Optional[int] = None):
    """Returns the params dict for one norm (possibly empty for nonparam_ln)."""
    del key
    d = d or cfg.d_model
    if cfg.norm_type == "nonparam_ln":
        return {}
    if cfg.norm_type == "layernorm":
        return {"scale": jnp.ones((d,), param_dtype(cfg)),
                "bias": jnp.zeros((d,), param_dtype(cfg))}
    return {"scale": jnp.ones((d,), param_dtype(cfg))}


def apply_norm(params, cfg: ModelConfig, x):
    """RMSNorm / LayerNorm / OLMo's non-parametric LayerNorm.

    Statistics in f32, output cast back to the activation dtype.
    """
    xdt = x.dtype
    x = x.astype(jnp.float32)
    if cfg.norm_type == "rmsnorm":
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        y = x * jax.lax.rsqrt(var + cfg.norm_eps)
        y = y * params["scale"].astype(jnp.float32)
    else:
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        y = (x - mean) * jax.lax.rsqrt(var + cfg.norm_eps)
        if cfg.norm_type == "layernorm":
            y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
        # nonparam_ln: no affine (OLMo)
    return y.astype(xdt)


def rms_norm_headwise(x, scale, eps: float = 1e-6):
    """Per-head RMSNorm for qk-norm (scale shaped [head_dim])."""
    xdt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    return y.astype(xdt)


# --------------------------------------------------------------------------- #
# RoPE
# --------------------------------------------------------------------------- #


def rope_freqs(head_dim: int, theta: float):
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x, positions, theta: float):
    """Rotate ``x [..., S, H, D]`` by per-token ``positions [..., S]``.

    Uses the split-halves convention (x = [x1 | x2]); self-consistent across
    the whole codebase (q and k use the same convention, so attention scores
    depend only on relative positions).
    """
    *_, seq, heads, dim = x.shape
    del seq, heads
    freqs = rope_freqs(dim, theta)                              # [D/2]
    angles = positions[..., None].astype(jnp.float32) * freqs   # [..., S, D/2]
    cos = jnp.cos(angles)[..., None, :]                         # [..., S, 1, D/2]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------- #
# Misc
# --------------------------------------------------------------------------- #


def split_keys(key, n: int):
    return list(jax.random.split(key, n))


def count_params(params) -> int:
    return int(sum(np.prod(x.shape) for x in jax.tree.leaves(params)))
