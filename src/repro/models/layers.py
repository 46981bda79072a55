"""Common model primitives: RMSNorm, RoPE, SwiGLU, embeddings, init utils.

All modules are (init, apply) pairs over plain-dict pytrees — no framework
dependency, so the same code paths run under jit, shard_map, and
``jax.eval_shape`` (the dry-run never allocates real parameters).
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import YarnConfig


Params = Dict[str, jnp.ndarray]


def _dtype(name: str):
    return jnp.dtype(name)


def dense_init(key, shape, dtype, scale: float = 1.0):
    """Truncated-normal fan-in init."""
    fan_in = shape[0] if len(shape) >= 2 else 1
    std = scale / (fan_in ** 0.5)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


# --------------------------------------------------------------------------
# RMSNorm
# --------------------------------------------------------------------------

def rmsnorm_init(d: int, dtype) -> Params:
    return {"scale": jnp.ones((d,), dtype=dtype)}


def rmsnorm(params: Params, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    orig_dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(orig_dtype)


# --------------------------------------------------------------------------
# Rotary position embedding
# --------------------------------------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature factor, ``0.1 m ln s + 1``."""
    if factor <= 1.0:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def _yarn_correction_dim(rotations: float, dim: int, theta: float,
                         max_pos: int) -> float:
    return (dim * math.log(max_pos / (rotations * 2 * math.pi))
            / (2 * math.log(theta)))


def rope_frequencies(head_dim: int, theta: float,
                     yarn: Optional[YarnConfig] = None) -> jnp.ndarray:
    """Inverse frequencies of the ``head_dim // 2`` rotary pairs. With
    ``yarn``, the pairs between the correction dims of ``beta_fast`` and
    ``beta_slow`` blend from the original frequency to it over
    ``factor`` along a linear ramp (DeepSeek-V2's YaRN)."""
    half = head_dim // 2
    if yarn is None:
        return 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    extra = 1.0 / (theta ** (np.arange(half, dtype=np.float64) / half))
    orig = yarn.original_max_position_embeddings
    low = max(math.floor(_yarn_correction_dim(
        yarn.beta_fast, head_dim, theta, orig)), 0)
    high = min(math.ceil(_yarn_correction_dim(
        yarn.beta_slow, head_dim, theta, orig)), head_dim - 1)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return jnp.asarray(extra / yarn.factor * ramp + extra * (1.0 - ramp),
                       jnp.float32)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               yarn: Optional[YarnConfig] = None) -> jnp.ndarray:
    """x: [..., T, H, D]; positions: broadcastable to [..., T]."""
    freqs = rope_frequencies(x.shape[-1], theta, yarn)    # [D/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., T, D/2]
    angles = angles[..., None, :]                         # [..., T, 1, D/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if yarn is not None:
        ratio = (yarn_mscale(yarn.factor, yarn.mscale)
                 / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
        cos, sin = cos * ratio, sin * ratio
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# SwiGLU MLP
# --------------------------------------------------------------------------

def mlp_init(key, d_model: int, d_ff: int, dtype) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, (d_model, d_ff), dtype),
        "w_up": dense_init(k2, (d_model, d_ff), dtype),
        "w_down": dense_init(k3, (d_ff, d_model), dtype),
    }


def mlp(params: Params, x: jnp.ndarray) -> jnp.ndarray:
    gate = jax.nn.silu(x @ params["w_gate"])
    return (gate * (x @ params["w_up"])) @ params["w_down"]


# --------------------------------------------------------------------------
# Embedding + slimmed action head (paper App. D.1)
# --------------------------------------------------------------------------

def embedding_init(key, vocab: int, d_model: int, dtype) -> Params:
    return {"table": dense_init(key, (vocab, d_model), dtype, scale=1.0)}


def embed(params: Params, tokens: jnp.ndarray) -> jnp.ndarray:
    return jnp.take(params["table"], tokens, axis=0)


def action_head_init(key, d_model: int, action_vocab: int, dtype) -> Params:
    return {"w": dense_init(key, (d_model, action_vocab), dtype)}


def action_head(params: Params, x: jnp.ndarray) -> jnp.ndarray:
    # Logits in f32 for a numerically stable softmax/log-softmax downstream.
    return (x @ params["w"]).astype(jnp.float32)


def slim_lm_head(full_head_w: jnp.ndarray, start: int, end: int) -> Params:
    """Paper App. D.1: crop [d_model, vocab] -> [d_model, n_actions] in place.

    ``full_head_w`` is the pretrained lm_head weight; [start, end) is the
    action-token range of the original vocabulary.
    """
    return {"w": full_head_w[:, start:end]}
