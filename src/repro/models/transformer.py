"""Policy-backbone assembly for every assigned architecture family.

Layers are *stacked* (leading ``L`` axis) and iterated with ``lax.scan`` so
the lowered HLO stays compact for 30–64-layer models; ``remat=True`` wraps
the scan body in ``jax.checkpoint`` (per-layer activation checkpointing —
the memory/compute trade recorded in the roofline's MODEL_FLOPS ratio).

Three entry points per family:
  * ``forward``  — teacher-forced scoring (training / value recomputation)
  * ``prefill``  — prompt pass that also emits the decode cache
  * ``decode``   — one token against the cache (``serve_step``)

Hybrid (zamba2) note: the *shared* attention block is applied before every
``shared_every``-th Mamba2 layer; its KV cache has one slot per application
(not per layer) so a 32k/500k-context decode cache stays proportional to the
number of applications (DESIGN.md §4).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_lib
from repro.models import mla as mla_lib
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.attention import KVCache
from repro.models.layers import (
    Params,
    action_head,
    action_head_init,
    dense_init,
    embed,
    embedding_init,
    mlp,
    mlp_init,
    rmsnorm,
    rmsnorm_init,
)
from repro.models.ssm import SSMState

FRONTEND_DIM = 1024  # stub modality-frontend embedding width (ViT/EnCodec)


class DecodeCache(NamedTuple):
    """Family-polymorphic decode cache. Attention caches are ``KVCache``s,
    or ``mla.LatentCache``s for latent attention."""

    attn: Optional[KVCache]      # stacked [L or n_shared, ...] or None
    ssm: Optional[SSMState]      # stacked [L, ...] or None
    dense: Optional[KVCache] = None  # a moe stack's leading dense layers


# ---------------------------------------------------------------------------
# Parameter initialization
# ---------------------------------------------------------------------------

def _attn_init(key, cfg: ModelConfig, dtype) -> Params:
    if cfg.mla is not None:
        return mla_lib.mla_init(key, cfg, dtype)
    return attn_lib.attention_init(key, cfg.d_model, cfg.num_heads,
                                   cfg.num_kv_heads, cfg.head_dim, dtype)


def _attn_block_init(key, cfg: ModelConfig, d_ff: int, dtype) -> Params:
    k1, k2 = jax.random.split(key)
    return {
        "attn_norm": rmsnorm_init(cfg.d_model, dtype),
        "attn": _attn_init(k1, cfg, dtype),
        "mlp_norm": rmsnorm_init(cfg.d_model, dtype),
        "mlp": mlp_init(k2, cfg.d_model, d_ff, dtype),
    }


def _moe_block_init(key, cfg: ModelConfig, dtype) -> Params:
    k1, k2 = jax.random.split(key)
    return {
        "attn_norm": rmsnorm_init(cfg.d_model, dtype),
        "attn": _attn_init(k1, cfg, dtype),
        "mlp_norm": rmsnorm_init(cfg.d_model, dtype),
        "moe": moe_lib.moe_init(k2, cfg.d_model, cfg.moe, dtype),
    }


def _ssm_block_init(key, cfg: ModelConfig, dtype) -> Params:
    return {
        "norm": rmsnorm_init(cfg.d_model, dtype),
        "ssm": ssm_lib.ssm_init(key, cfg.d_model, cfg.ssm, dtype),
    }


def _stacked_init(init_fn, key, n: int):
    keys = jax.random.split(key, n)
    return jax.vmap(init_fn)(keys)


def hybrid_layout(cfg: ModelConfig) -> Tuple[int, int, int]:
    """(n_macro, group, remainder): shared attn fires n_macro (+1 if rem)
    times, before each macro group of ``group`` Mamba2 layers."""
    g = cfg.hybrid.shared_every
    n_macro = cfg.num_layers // g
    rem = cfg.num_layers % g
    return n_macro, g, rem


def num_shared_applications(cfg: ModelConfig) -> int:
    n_macro, _, rem = hybrid_layout(cfg)
    return n_macro + (1 if rem else 0)


def init_params(cfg: ModelConfig, key) -> Params:
    dtype = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 8)
    params: Dict[str, Any] = {
        "embed": embedding_init(ks[0], cfg.vocab_size, cfg.d_model, dtype),
        "final_norm": rmsnorm_init(cfg.d_model, dtype),
        "action_head": action_head_init(
            ks[1], cfg.d_model, cfg.action_vocab_size, dtype),
    }
    if cfg.num_prefix_tokens:
        params["prefix_proj"] = {
            "w": dense_init(ks[2], (FRONTEND_DIM, cfg.d_model), dtype)}

    if cfg.arch_type in ("dense", "audio", "vlm"):
        params["layers"] = _stacked_init(
            lambda k: _attn_block_init(k, cfg, cfg.d_ff, dtype),
            ks[3], cfg.num_layers)
    elif cfg.arch_type == "moe":
        if cfg.first_k_dense:
            params["layers_dense"] = _stacked_init(
                lambda k: _attn_block_init(k, cfg, cfg.d_ff, dtype), ks[6],
                cfg.first_k_dense)
        params["layers"] = _stacked_init(
            lambda k: _moe_block_init(k, cfg, dtype), ks[3],
            cfg.num_layers - cfg.first_k_dense)
    elif cfg.arch_type == "ssm":
        params["layers"] = _stacked_init(
            lambda k: _ssm_block_init(k, cfg, dtype), ks[3], cfg.num_layers)
    elif cfg.arch_type == "hybrid":
        n_macro, g, rem = hybrid_layout(cfg)
        params["layers"] = _stacked_init(
            lambda k: _ssm_block_init(k, cfg, dtype), ks[3], n_macro * g)
        if rem:
            params["layers_rem"] = _stacked_init(
                lambda k: _ssm_block_init(k, cfg, dtype), ks[4], rem)
        params["shared_attn"] = _attn_block_init(
            ks[5], cfg, cfg.hybrid.shared_d_ff, dtype)
    else:
        raise ValueError(f"unknown arch_type {cfg.arch_type}")
    return params


# ---------------------------------------------------------------------------
# Embedding of (prefix, tokens)
# ---------------------------------------------------------------------------

def embed_inputs(cfg: ModelConfig, params: Params, tokens: jnp.ndarray,
                 prefix_embeds: Optional[jnp.ndarray]) -> jnp.ndarray:
    x = embed(params["embed"], tokens)
    if prefix_embeds is not None:
        proj = prefix_embeds.astype(x.dtype) @ params["prefix_proj"]["w"]
        x = jnp.concatenate([proj, x], axis=1)
    return x.astype(jnp.dtype(cfg.compute_dtype))


# ---------------------------------------------------------------------------
# Blocks (single layer, unstacked params)
# ---------------------------------------------------------------------------

def _attn_forward(p: Params, h, cfg: ModelConfig, window, block, unroll):
    if cfg.mla is not None:
        return mla_lib.mla_forward(p, h, cfg, window=window, block=block,
                                   unroll=unroll)
    return attn_lib.attention_forward(
        p, h, rope_theta=cfg.rope_theta, window=window, block=block,
        unroll=unroll)


def _attn_block_forward(p: Params, x, cfg: ModelConfig, window,
                        block=None, unroll=False):
    h = rmsnorm(p["attn_norm"], x, cfg.norm_eps)
    x = x + _attn_forward(p["attn"], h, cfg, window, block, unroll)
    h = rmsnorm(p["mlp_norm"], x, cfg.norm_eps)
    return x + mlp(p["mlp"], h)


def _moe_block_forward(p: Params, x, cfg: ModelConfig, window, block=None,
                       unroll=False):
    h = rmsnorm(p["attn_norm"], x, cfg.norm_eps)
    x = x + _attn_forward(p["attn"], h, cfg, window, block, unroll)
    h = rmsnorm(p["mlp_norm"], x, cfg.norm_eps)
    out, aux = moe_lib.moe_forward(p["moe"], h, cfg.moe)
    return x + out, aux


def _ssm_block_forward(p: Params, x, cfg: ModelConfig):
    h = rmsnorm(p["norm"], x, cfg.norm_eps)
    return x + ssm_lib.ssm_forward(p["ssm"], h, cfg.d_model, cfg.ssm)


_ZERO_AUX = {"load_balance": 0.0, "router_z": 0.0}
# per-layer readings of the expert layer, averaged (not summed) over layers
_MEAN_AUX = ("held_frac", "load_max")


# ---------------------------------------------------------------------------
# Forward (teacher-forced scoring)
# ---------------------------------------------------------------------------

def forward(cfg: ModelConfig, params: Params, tokens: jnp.ndarray,
            prefix_embeds: Optional[jnp.ndarray] = None, *,
            window: Optional[int] = None,
            remat: bool = False,
            block: Optional[int] = None,
            unroll: bool = False,
            act_sharding=None,
            head: bool = True) -> Dict[str, jnp.ndarray]:
    """Returns {"hidden": [B,S,d], "logits": [B,S,Va] (f32), "aux": {...}}.

    ``head=False`` skips the action-head projection (``logits`` is None) —
    the fused-loss path applies the head blockwise inside the loss kernel
    instead of materializing [B, S, Va] logits here.

    ``act_sharding`` (a NamedSharding over [B, S, d]) pins the layer-scan
    carry — i.e. the remat-saved residual stream — to an explicit layout
    (batch on data, d_model on model). Without it GSPMD may save carries
    with the batch axis replicated, blowing up the remat stack 16x on
    large models (EXPERIMENTS.md §Perf).
    """
    x = embed_inputs(cfg, params, tokens, prefix_embeds)

    def _pin(h):
        if act_sharding is not None:
            return jax.lax.with_sharding_constraint(h, act_sharding)
        return h

    x = _pin(x)

    def dense_stack(x, layers):
        ur = jax.tree.leaves(layers)[0].shape[0] if unroll else 1

        def body(carry, layer_p):
            return _pin(_attn_block_forward(layer_p, carry, cfg, window,
                                            block, unroll)), None
        if remat:
            body = jax.checkpoint(body)
        return jax.lax.scan(body, x, layers, unroll=ur)[0]

    if cfg.arch_type in ("dense", "audio", "vlm"):
        x = dense_stack(x, params["layers"])
        aux = dict(_ZERO_AUX)
    elif cfg.arch_type == "moe":
        if cfg.first_k_dense:
            x = dense_stack(x, params["layers_dense"])
        ur = cfg.num_layers - cfg.first_k_dense if unroll else 1

        def body(carry, layer_p):
            out, aux = _moe_block_forward(layer_p, carry, cfg, window, block,
                                          unroll)
            return _pin(out), aux
        if remat:
            body = jax.checkpoint(body)
        x, auxs = jax.lax.scan(body, x, params["layers"], unroll=ur)
        aux = {k: (jnp.mean if k in _MEAN_AUX else jnp.sum)(v)
               for k, v in auxs.items()}
    elif cfg.arch_type == "ssm":
        ur = cfg.num_layers if unroll else 1

        def body(carry, layer_p):
            return _pin(_ssm_block_forward(layer_p, carry, cfg)), None
        if remat:
            body = jax.checkpoint(body)
        x, _ = jax.lax.scan(body, x, params["layers"], unroll=ur)
        aux = dict(_ZERO_AUX)
    elif cfg.arch_type == "hybrid":
        n_macro, g, rem = hybrid_layout(cfg)
        stacked = jax.tree.map(
            lambda a: a.reshape((n_macro, g) + a.shape[1:]), params["layers"])

        def inner(carry, layer_p):
            return _ssm_block_forward(layer_p, carry, cfg), None

        def macro(carry, macro_p):
            h = _attn_block_forward(params["shared_attn"], carry, cfg,
                                    window, block, unroll)
            h, _ = jax.lax.scan(inner, h, macro_p, unroll=g if unroll else 1)
            return _pin(h), None
        if remat:
            macro = jax.checkpoint(macro)
        x, _ = jax.lax.scan(macro, x, stacked,
                            unroll=n_macro if unroll else 1)
        if rem:
            x = _attn_block_forward(params["shared_attn"], x, cfg, window,
                                    block, unroll)
            x, _ = jax.lax.scan(inner, x, params["layers_rem"],
                                unroll=rem if unroll else 1)
        aux = dict(_ZERO_AUX)
    else:
        raise ValueError(cfg.arch_type)

    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = action_head(params["action_head"], x) if head else None
    return {"hidden": x, "logits": logits, "aux": aux}


# ---------------------------------------------------------------------------
# Decode cache init
# ---------------------------------------------------------------------------

def init_decode_cache(cfg: ModelConfig, batch: int, cache_len: int, *,
                      window: Optional[int] = None) -> DecodeCache:
    dtype = jnp.dtype(cfg.compute_dtype)
    eff_len = min(cache_len, window) if window else cache_len
    attn_cache = None
    ssm_cache = None
    dense_cache = None

    def attn_caches(n):
        if cfg.mla is not None:
            one = lambda _: mla_lib.init_cache(batch, eff_len, cfg,  # noqa
                                               dtype)
        else:
            one = lambda _: attn_lib.init_cache(  # noqa: E731
                batch, eff_len, cfg.num_kv_heads, cfg.head_dim, dtype)
        return jax.vmap(one)(jnp.arange(n))

    if cfg.arch_type in ("dense", "audio", "vlm"):
        attn_cache = attn_caches(cfg.num_layers)
    elif cfg.arch_type == "moe":
        attn_cache = attn_caches(cfg.num_layers - cfg.first_k_dense)
        if cfg.first_k_dense:
            dense_cache = attn_caches(cfg.first_k_dense)
    elif cfg.arch_type == "ssm":
        def one(_):
            return ssm_lib.init_ssm_state(batch, cfg.d_model, cfg.ssm, dtype)
        ssm_cache = jax.vmap(one)(jnp.arange(cfg.num_layers))
    elif cfg.arch_type == "hybrid":
        n_shared = num_shared_applications(cfg)

        def one_a(_):
            return attn_lib.init_cache(batch, eff_len, cfg.num_kv_heads,
                                       cfg.head_dim, dtype)
        attn_cache = jax.vmap(one_a)(jnp.arange(n_shared))

        def one_s(_):
            return ssm_lib.init_ssm_state(batch, cfg.d_model, cfg.ssm, dtype)
        ssm_cache = jax.vmap(one_s)(jnp.arange(cfg.num_layers))
    return DecodeCache(attn=attn_cache, ssm=ssm_cache, dense=dense_cache)


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------

def prefill(cfg: ModelConfig, params: Params, tokens: jnp.ndarray,
            prefix_embeds: Optional[jnp.ndarray] = None, *,
            cache_len: Optional[int] = None,
            window: Optional[int] = None,
            block: Optional[int] = None,
            unroll: bool = False
            ) -> Tuple[Dict[str, jnp.ndarray], DecodeCache]:
    x = embed_inputs(cfg, params, tokens, prefix_embeds)
    b, t, _ = x.shape
    cache_len = cache_len or t
    eff_len = min(cache_len, window) if window else cache_len

    def attn_sub(p, h):
        hn = rmsnorm(p["attn_norm"], h, cfg.norm_eps)
        if cfg.mla is not None:
            out, cache = mla_lib.mla_prefill(
                p["attn"], hn, cfg, cache_len=eff_len, window=window,
                block=block, unroll=unroll)
        else:
            out, cache = attn_lib.attention_prefill(
                p["attn"], hn, rope_theta=cfg.rope_theta, cache_len=eff_len,
                window=window, block=block, unroll=unroll)
        return h + out, cache

    def stack(x, layers, moe: bool):
        def body(carry, layer_p):
            h, cache = attn_sub(layer_p, carry)
            hn = rmsnorm(layer_p["mlp_norm"], h, cfg.norm_eps)
            if moe:
                out, _ = moe_lib.moe_forward(layer_p["moe"], hn, cfg.moe)
            else:
                out = mlp(layer_p["mlp"], hn)
            return h + out, cache
        n = jax.tree.leaves(layers)[0].shape[0]
        return jax.lax.scan(body, x, layers, unroll=n if unroll else 1)

    if cfg.arch_type in ("dense", "audio", "vlm", "moe"):
        dense_cache = None
        if cfg.first_k_dense:
            x, dense_cache = stack(x, params["layers_dense"], False)
        x, attn_cache = stack(x, params["layers"], cfg.arch_type == "moe")
        cache = DecodeCache(attn=attn_cache, ssm=None, dense=dense_cache)
    elif cfg.arch_type == "ssm":
        def body(carry, layer_p):
            hn = rmsnorm(layer_p["norm"], carry, cfg.norm_eps)
            out, st = ssm_lib.ssm_forward(layer_p["ssm"], hn, cfg.d_model,
                                          cfg.ssm, return_state=True)
            return carry + out, st
        x, ssm_cache = jax.lax.scan(body, x, params["layers"],
                                    unroll=cfg.num_layers if unroll else 1)
        cache = DecodeCache(attn=None, ssm=ssm_cache)
    elif cfg.arch_type == "hybrid":
        n_macro, g, rem = hybrid_layout(cfg)
        stacked = jax.tree.map(
            lambda a: a.reshape((n_macro, g) + a.shape[1:]), params["layers"])

        def inner(carry, layer_p):
            hn = rmsnorm(layer_p["norm"], carry, cfg.norm_eps)
            out, st = ssm_lib.ssm_forward(layer_p["ssm"], hn, cfg.d_model,
                                          cfg.ssm, return_state=True)
            return carry + out, st

        def macro(carry, macro_p):
            h, kv = attn_sub(params["shared_attn"], carry)
            hn = rmsnorm(params["shared_attn"]["mlp_norm"], h, cfg.norm_eps)
            h = h + mlp(params["shared_attn"]["mlp"], hn)
            h, sts = jax.lax.scan(inner, h, macro_p,
                                  unroll=g if unroll else 1)
            return h, (kv, sts)
        x, (kv_macro, ssm_macro) = jax.lax.scan(
            macro, x, stacked, unroll=n_macro if unroll else 1)
        # flatten [n_macro, g, ...] -> [n_macro*g, ...]
        ssm_flat = jax.tree.map(
            lambda a: a.reshape((n_macro * g,) + a.shape[2:]), ssm_macro)
        kv_all = kv_macro
        if rem:
            h, kv_r = attn_sub(params["shared_attn"], x)
            hn = rmsnorm(params["shared_attn"]["mlp_norm"], h, cfg.norm_eps)
            h = h + mlp(params["shared_attn"]["mlp"], hn)
            x, ssm_rem = jax.lax.scan(inner, h, params["layers_rem"],
                                      unroll=rem if unroll else 1)
            kv_all = jax.tree.map(
                lambda a, b: jnp.concatenate([a, b[None]], axis=0),
                kv_macro, kv_r)
            ssm_flat = jax.tree.map(
                lambda a, b: jnp.concatenate([a, b], axis=0),
                ssm_flat, ssm_rem)
        cache = DecodeCache(attn=kv_all, ssm=ssm_flat)
    else:
        raise ValueError(cfg.arch_type)

    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = action_head(params["action_head"], x)
    return {"hidden": x, "logits": logits}, cache


# ---------------------------------------------------------------------------
# Decode (one token)
# ---------------------------------------------------------------------------

def decode(cfg: ModelConfig, params: Params, token: jnp.ndarray,
           cache: DecodeCache, *, window: Optional[int] = None,
           unroll: bool = False, uniform: bool = False
           ) -> Tuple[Dict[str, jnp.ndarray], DecodeCache]:
    """token: [B] or [B,1] int32 -> logits [B, 1, Va]."""
    if token.ndim == 1:
        token = token[:, None]
    x = embed(params["embed"], token).astype(jnp.dtype(cfg.compute_dtype))

    def attn_sub(p, h, kv):
        hn = rmsnorm(p["attn_norm"], h, cfg.norm_eps)
        if cfg.mla is not None:
            out, kv = mla_lib.mla_decode(p["attn"], hn, kv, cfg,
                                         window=window, uniform=uniform)
        else:
            out, kv = attn_lib.attention_decode(
                p["attn"], hn, kv, rope_theta=cfg.rope_theta, window=window,
                uniform=uniform)
        return h + out, kv

    def stack(x, layers, kvs, moe: bool):
        def body(carry, scanned):
            layer_p, kv = scanned
            h, kv = attn_sub(layer_p, carry, kv)
            hn = rmsnorm(layer_p["mlp_norm"], h, cfg.norm_eps)
            if moe:
                out, _ = moe_lib.moe_forward(layer_p["moe"], hn, cfg.moe)
            else:
                out = mlp(layer_p["mlp"], hn)
            return h + out, kv
        n = jax.tree.leaves(layers)[0].shape[0]
        return jax.lax.scan(body, x, (layers, kvs),
                            unroll=n if unroll else 1)

    if cfg.arch_type in ("dense", "audio", "vlm", "moe"):
        dense_cache = None
        if cfg.first_k_dense:
            x, dense_cache = stack(x, params["layers_dense"], cache.dense,
                                   False)
        x, attn_cache = stack(x, params["layers"], cache.attn,
                              cfg.arch_type == "moe")
        new_cache = DecodeCache(attn=attn_cache, ssm=None, dense=dense_cache)
    elif cfg.arch_type == "ssm":
        def body(carry, scanned):
            layer_p, st = scanned
            hn = rmsnorm(layer_p["norm"], carry, cfg.norm_eps)
            out, st = ssm_lib.ssm_decode(layer_p["ssm"], hn, st, cfg.d_model,
                                         cfg.ssm)
            return carry + out, st
        x, ssm_cache = jax.lax.scan(body, x, (params["layers"], cache.ssm),
                                    unroll=cfg.num_layers if unroll else 1)
        new_cache = DecodeCache(attn=None, ssm=ssm_cache)
    elif cfg.arch_type == "hybrid":
        n_macro, g, rem = hybrid_layout(cfg)
        stacked = jax.tree.map(
            lambda a: a.reshape((n_macro, g) + a.shape[1:]), params["layers"])
        ssm_macro = jax.tree.map(
            lambda a: a.reshape((n_macro, g) + a.shape[1:]),
            jax.tree.map(lambda a: a[:n_macro * g], cache.ssm))
        kv_macro = jax.tree.map(lambda a: a[:n_macro], cache.attn)

        def inner(carry, scanned):
            layer_p, st = scanned
            hn = rmsnorm(layer_p["norm"], carry, cfg.norm_eps)
            out, st = ssm_lib.ssm_decode(layer_p["ssm"], hn, st, cfg.d_model,
                                         cfg.ssm)
            return carry + out, st

        def macro(carry, scanned):
            macro_p, kv, sts = scanned
            h, kv = attn_sub(params["shared_attn"], carry, kv)
            hn = rmsnorm(params["shared_attn"]["mlp_norm"], h, cfg.norm_eps)
            h = h + mlp(params["shared_attn"]["mlp"], hn)
            h, sts = jax.lax.scan(inner, h, (macro_p, sts),
                                  unroll=g if unroll else 1)
            return h, (kv, sts)
        x, (kv_new, ssm_new) = jax.lax.scan(
            macro, x, (stacked, kv_macro, ssm_macro),
            unroll=n_macro if unroll else 1)
        ssm_flat = jax.tree.map(
            lambda a: a.reshape((n_macro * g,) + a.shape[2:]), ssm_new)
        kv_all = kv_new
        if rem:
            kv_r = jax.tree.map(lambda a: a[n_macro], cache.attn)
            ssm_r = jax.tree.map(lambda a: a[n_macro * g:], cache.ssm)
            h, kv_r = attn_sub(params["shared_attn"], x, kv_r)
            hn = rmsnorm(params["shared_attn"]["mlp_norm"], h, cfg.norm_eps)
            h = h + mlp(params["shared_attn"]["mlp"], hn)
            x, ssm_r = jax.lax.scan(inner, h, (params["layers_rem"], ssm_r),
                                    unroll=rem if unroll else 1)
            kv_all = jax.tree.map(
                lambda a, b: jnp.concatenate([a, b[None]], axis=0),
                kv_new, kv_r)
            ssm_flat = jax.tree.map(
                lambda a, b: jnp.concatenate([a, b], axis=0), ssm_flat, ssm_r)
        new_cache = DecodeCache(attn=kv_all, ssm=ssm_flat)
    else:
        raise ValueError(cfg.arch_type)

    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = action_head(params["action_head"], x)
    return {"hidden": x, "logits": logits}, new_cache
