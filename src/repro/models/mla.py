"""Multi-head latent attention (DeepSeek-V2, no query LoRA): training,
prefill, and single-token decode through a latent cache.

Per layer input ``x`` [B, T, d]:

* ``q = x W_q``, per head ``[q_nope | q_pe]``;
* ``[c_kv | k_pe] = x W_kva``; ``c_kv <- RMSNorm(c_kv)``;
* ``[k_nope | v] = c_kv W_kvb`` per head; ``q_pe`` and the one ``k_pe``
  that all heads share take (YaRN) rotary embedding;
* causal softmax of ``[q_nope | q_pe] . [k_nope | k_pe]`` at scale
  ``qk_head_dim ** -0.5 * m ** 2`` (``m`` YaRN's ``mscale_all_dim``
  factor), then ``o W_o``.

The decode cache holds the latent, ``kv_lora_rank + qk_rope_head_dim``
values per token per layer (the normed ``c_kv`` and the roped ``k_pe``),
and no per-head keys or values: decode expands them from the cache.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import dispatch
from repro.models.attention import cache_layout, cache_write, decode_valid
from repro.models.layers import (Params, apply_rope, dense_init, rmsnorm,
                                 rmsnorm_init, yarn_mscale)


class LatentCache(NamedTuple):
    ckv: jnp.ndarray          # [B, S, kv_lora_rank], RMS-normed
    kpe: jnp.ndarray          # [B, S, qk_rope_head_dim], roped
    positions: jnp.ndarray    # [B, S] int32, -1 = empty
    length: jnp.ndarray       # [B] int32


def mla_init(key, cfg: ModelConfig, dtype) -> Params:
    a, d, h = cfg.mla, cfg.d_model, cfg.num_heads
    kq, kkva, kkvb, ko = jax.random.split(key, 4)
    return {
        "wq": dense_init(kq, (d, h, a.qk_head_dim), dtype),
        "w_kva": dense_init(kkva, (d, a.kv_lora_rank + a.qk_rope_head_dim),
                            dtype),
        "kv_norm": rmsnorm_init(a.kv_lora_rank, dtype),
        "w_kvb": dense_init(kkvb, (a.kv_lora_rank, h,
                                   a.qk_nope_head_dim + a.v_head_dim), dtype),
        "wo": dense_init(ko, (h, a.v_head_dim, d), dtype),
    }


def softmax_scale(cfg: ModelConfig) -> float:
    scale = cfg.mla.qk_head_dim ** -0.5
    y = cfg.rope_scaling
    if y is not None and y.mscale_all_dim:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def _rope(x, positions, cfg: ModelConfig):
    return apply_rope(x, positions, cfg.rope_theta, cfg.rope_scaling)


def _query(p: Params, x, positions, cfg: ModelConfig):
    nope = cfg.mla.qk_nope_head_dim
    q = jnp.einsum("btd,dhk->bthk", x, p["wq"])
    return jnp.concatenate([q[..., :nope], _rope(q[..., nope:], positions,
                                                 cfg)], axis=-1)


def _latent(p: Params, x, positions, cfg: ModelConfig):
    """(normed c_kv [B,T,r], roped k_pe [B,T,dr])."""
    r = cfg.mla.kv_lora_rank
    c = x @ p["w_kva"]
    ckv = rmsnorm(p["kv_norm"], c[..., :r], cfg.norm_eps)
    kpe = _rope(c[..., None, r:], positions, cfg)[..., 0, :]
    return ckv, kpe


def _keys_values(p: Params, ckv, kpe, cfg: ModelConfig):
    """Per-head keys [B,S,H,qk] and values [B,S,H,v] from the latent."""
    nope = cfg.mla.qk_nope_head_dim
    kv = jnp.einsum("bsr,rhk->bshk", ckv, p["w_kvb"])
    pe = jnp.broadcast_to(kpe[:, :, None, :].astype(kv.dtype),
                          kv.shape[:3] + kpe.shape[-1:])
    return jnp.concatenate([kv[..., :nope], pe], axis=-1), kv[..., nope:]


def _causal(p: Params, x, positions, cfg: ModelConfig, window, block,
            unroll):
    q = _query(p, x, positions, cfg)
    ckv, kpe = _latent(p, x, positions, cfg)
    k, v = _keys_values(p, ckv, kpe, cfg)
    scale = softmax_scale(cfg)
    if block is not None and x.shape[1] > block:
        out = dispatch.attention(q, k, v, window=window, block=block,
                                 unroll=unroll, scale=scale).astype(x.dtype)
    else:
        out = dispatch.dense_attention(q, k, v, window=window, scale=scale)
    return jnp.einsum("bthk,hkd->btd", out, p["wo"]), ckv, kpe


@jax.named_scope("mla")
def mla_forward(p: Params, x, cfg: ModelConfig, *,
                window: Optional[int] = None, block: Optional[int] = None,
                unroll: bool = False) -> jnp.ndarray:
    """Causal latent attention over the whole sequence (training)."""
    positions = jnp.arange(x.shape[1])[None, :]
    return _causal(p, x, positions, cfg, window, block, unroll)[0]


def init_cache(batch: int, cache_len: int, cfg: ModelConfig,
               dtype) -> LatentCache:
    a = cfg.mla
    return LatentCache(
        ckv=jnp.zeros((batch, cache_len, a.kv_lora_rank), dtype),
        kpe=jnp.zeros((batch, cache_len, a.qk_rope_head_dim), dtype),
        positions=jnp.full((batch, cache_len), -1, jnp.int32),
        length=jnp.zeros((batch,), jnp.int32))


@jax.named_scope("mla")
def mla_prefill(p: Params, x, cfg: ModelConfig, *, cache_len: int,
                window: Optional[int] = None, block: Optional[int] = None,
                unroll: bool = False) -> Tuple[jnp.ndarray, LatentCache]:
    """Causal latent attention over the prompt; emits the latent cache."""
    b, t, _ = x.shape
    positions = jnp.arange(t)[None, :]
    out, ckv, kpe = _causal(p, x, positions, cfg, window, block, unroll)
    ckv, kpe, pos_c = cache_layout((ckv, kpe), positions, cache_len)
    return out, LatentCache(ckv=ckv, kpe=kpe, positions=pos_c,
                            length=jnp.full((b,), t, jnp.int32))


@jax.named_scope("mla")
def mla_decode(p: Params, x, cache: LatentCache, cfg: ModelConfig, *,
               window: Optional[int] = None, uniform: bool = False
               ) -> Tuple[jnp.ndarray, LatentCache]:
    """One new token per sequence (x: [B, 1, d]) against the latent
    cache; keys and values are expanded from the cached latent."""
    pos = cache.length
    q = _query(p, x, pos[:, None], cfg)
    ckv_new, kpe_new = _latent(p, x, pos[:, None], cfg)
    ckv, kpe, pos_c = cache_write((cache.ckv, cache.kpe), (ckv_new, kpe_new),
                                  cache.positions, pos, uniform)
    k, v = _keys_values(p, ckv, kpe, cfg)
    out = dispatch.decode_attention(q, k, v, decode_valid(pos_c, pos, window),
                                    scale=softmax_scale(cfg))
    out = jnp.einsum("bthk,hkd->btd", out, p["wo"])
    return out, LatentCache(ckv=ckv, kpe=kpe, positions=pos_c,
                            length=pos + 1)
