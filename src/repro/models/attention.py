"""Grouped-query attention: training, prefill, and single-token decode paths.

Decode supports both a full KV cache (decode_32k) and a ring-buffer
sliding-window cache (the ``long_500k`` sub-quadratic fallback for dense
architectures — see DESIGN.md §4).
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import dispatch
from repro.models.layers import Params, apply_rope, dense_init

NEG_INF = -1e30


class KVCache(NamedTuple):
    """KV cache; ``positions`` carries absolute positions (ring buffers
    overwrite slots out of order). ``length`` = tokens generated so far."""

    k: jnp.ndarray            # [B, S_cache, KV, D]
    v: jnp.ndarray            # [B, S_cache, KV, D]
    positions: jnp.ndarray    # [B, S_cache] int32, -1 = empty
    length: jnp.ndarray       # [B] int32


def attention_init(key, d_model: int, num_heads: int, num_kv_heads: int,
                   head_dim: int, dtype) -> Params:
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": dense_init(kq, (d_model, num_heads, head_dim), dtype),
        "wk": dense_init(kk, (d_model, num_kv_heads, head_dim), dtype),
        "wv": dense_init(kv, (d_model, num_kv_heads, head_dim), dtype),
        "wo": dense_init(ko, (num_heads, head_dim, d_model), dtype),
    }


def _project_qkv(params: Params, x: jnp.ndarray, positions: jnp.ndarray,
                 rope_theta: float):
    q = jnp.einsum("btd,dhk->bthk", x, params["wq"])
    k = jnp.einsum("btd,dhk->bthk", x, params["wk"])
    v = jnp.einsum("btd,dhk->bthk", x, params["wv"])
    q = apply_rope(q, positions, rope_theta)
    k = apply_rope(k, positions, rope_theta)
    return q, k, v


def _gqa_scores(q: jnp.ndarray, k: jnp.ndarray) -> jnp.ndarray:
    """q: [B,T,H,D], k: [B,S,KV,D] -> scores [B,H,T,S] f32 (head-grouped).

    f32 accumulation is requested via ``preferred_element_type`` — a post
    hoc ``.astype`` would let XLA materialize f32 COPIES of the (possibly
    cache-sized) operands instead of widening inside the dot.
    """
    b, t, h, d = q.shape
    kv = k.shape[2]
    group = h // kv
    qg = q.reshape(b, t, kv, group, d)
    scores = jnp.einsum("btkgd,bskd->bkgts", qg, k,
                        preferred_element_type=jnp.float32)
    return scores.reshape(b, h, t, k.shape[1])


def _gqa_combine(weights: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """weights: [B,H,T,S], v: [B,S,KV,D] -> [B,T,H,D]."""
    b, h, t, s = weights.shape
    kv = v.shape[2]
    group = h // kv
    wg = weights.reshape(b, kv, group, t, s)
    out = jnp.einsum("bkgts,bskd->btkgd", wg, v)
    return out.reshape(b, t, h, v.shape[3])


def causal_mask(t: int, window: Optional[int] = None) -> jnp.ndarray:
    """[T, T] additive mask; optional sliding window."""
    i = jnp.arange(t)[:, None]
    j = jnp.arange(t)[None, :]
    ok = j <= i
    if window is not None:
        ok &= (i - j) < window
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def attention_forward(params: Params, x: jnp.ndarray, *, rope_theta: float,
                      window: Optional[int] = None,
                      positions: Optional[jnp.ndarray] = None,
                      block: Optional[int] = None,
                      unroll: bool = False) -> jnp.ndarray:
    """Full causal self-attention for training / teacher-forced scoring.

    ``block`` switches to the blockwise path (O(T·block) score memory
    instead of O(T²)) — required for the 4k/32k production shapes. The
    blockwise path is routed through ``repro.kernels.dispatch``: the Pallas
    flash kernel on TPU, the jnp online-softmax twin elsewhere; identical
    numerics (tests assert allclose vs the dense path).
    """
    b, t, _ = x.shape
    if positions is None:
        positions = jnp.arange(t)[None, :]
    q, k, v = _project_qkv(params, x, positions, rope_theta)
    if block is not None and t > block:
        out = dispatch.attention(q, k, v, window=window, block=block,
                                 unroll=unroll)
        out = out.astype(x.dtype)
    else:
        out = dispatch.dense_attention(q, k, v, window=window)
    return jnp.einsum("bthk,hkd->btd", out, params["wo"])


def _blockwise_attn(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    scale: float, *, window: Optional[int],
                    block: int, unroll: bool = False) -> jnp.ndarray:
    """Online-softmax attention scanned over KV blocks (flash-attention
    algorithm in pure jnp — the jnp twin of ``repro.kernels.flash_attention``).

    q: [B,T,H,D]; k/v: [B,S,KV,D]. Causal over absolute positions 0..T-1
    (q) vs 0..S-1 (k); requires S % block == 0.
    """
    b, t, h, d = q.shape
    s = k.shape[1]
    if s % block:                      # prefix tokens make S ragged — pad
        pad = block - s % block
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nb = k.shape[1] // block
    qpos = jnp.arange(t)[:, None]                       # [T, 1]

    def body(carry, j):
        m, l, acc = carry
        kj = jax.lax.dynamic_slice_in_dim(k, j * block, block, axis=1)
        vj = jax.lax.dynamic_slice_in_dim(v, j * block, block, axis=1)
        kpos = j * block + jnp.arange(block)[None, :]   # [1, block]
        ok = (kpos <= qpos) & (kpos < s)
        if window is not None:
            ok &= (qpos - kpos) < window
        scores = _gqa_scores(q, kj) * scale
        scores = jnp.where(ok[None, None], scores, NEG_INF)  # [B,H,T,blk]
        m_new = jnp.maximum(m, scores.max(axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new[..., None])
        l_new = l * corr + p.sum(axis=-1)
        pv = _gqa_combine(p.astype(v.dtype), vj).astype(jnp.float32)
        # pv: [B,T,H,D] -> match acc layout [B,H,T,D]
        acc_new = acc * corr[..., None] + pv.transpose(0, 2, 1, 3)
        return (m_new, l_new, acc_new), None

    init = (jnp.full((b, h, t), NEG_INF, jnp.float32),
            jnp.zeros((b, h, t), jnp.float32),
            jnp.zeros((b, h, t, v.shape[-1]), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(jax.checkpoint(body), init, jnp.arange(nb),
                                  unroll=nb if unroll else 1)
    out = acc / jnp.maximum(l, 1e-30)[..., None]        # [B,H,T,D]
    return out.transpose(0, 2, 1, 3)                    # [B,T,H,D]


def init_cache(batch: int, cache_len: int, num_kv_heads: int, head_dim: int,
               dtype) -> KVCache:
    return KVCache(
        k=jnp.zeros((batch, cache_len, num_kv_heads, head_dim), dtype=dtype),
        v=jnp.zeros((batch, cache_len, num_kv_heads, head_dim), dtype=dtype),
        positions=jnp.full((batch, cache_len), -1, dtype=jnp.int32),
        length=jnp.zeros((batch,), dtype=jnp.int32),
    )


def attention_prefill(params: Params, x: jnp.ndarray, *, rope_theta: float,
                      cache_len: int,
                      window: Optional[int] = None,
                      block: Optional[int] = None,
                      unroll: bool = False
                      ) -> Tuple[jnp.ndarray, KVCache]:
    """Causal attention over the prompt; emits the populated KV cache."""
    b, t, _ = x.shape
    positions = jnp.arange(t)[None, :]
    q, k, v = _project_qkv(params, x, positions, rope_theta)
    if block is not None and t > block:
        out = dispatch.attention(q, k, v, window=window, block=block,
                                 unroll=unroll).astype(x.dtype)
    else:
        out = dispatch.dense_attention(q, k, v, window=window)
    out = jnp.einsum("bthk,hkd->btd", out, params["wo"])

    k_c, v_c, pos_c = cache_layout((k, v), positions, cache_len)
    cache = KVCache(k=k_c, v=v_c, positions=pos_c,
                    length=jnp.full((b,), t, dtype=jnp.int32))
    return out, cache


def cache_layout(xs, positions: jnp.ndarray, cache_len: int):
    """Prompt arrays ``xs`` ([B, T, ...] each) and their positions
    ([1, T]) laid out in a ``cache_len``-slot cache: padded with empty
    slots, or, when the cache is shorter, the last ``cache_len`` tokens
    in ring order (slot = position % cache_len). Returns (*xs, positions
    [B, S] int32)."""
    b, t = xs[0].shape[:2]
    if cache_len >= t:
        pad = cache_len - t
        out = [jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
               for x in xs]
        pos_c = jnp.pad(jnp.broadcast_to(positions, (b, t)),
                        ((0, 0), (0, pad)), constant_values=-1)
    else:  # ring buffer keeps the last ``cache_len`` tokens
        out = [x[:, t - cache_len:] for x in xs]
        pos_c = jnp.broadcast_to(positions[:, t - cache_len:], (b, cache_len))
        # ring layout: slot = pos % cache_len
        slots = pos_c[0] % cache_len
        inv = jnp.argsort(slots)
        out = [x[:, inv] for x in out]
        pos_c = pos_c[:, inv]
    return (*out, pos_c.astype(jnp.int32))


def cache_write(cached, new, positions: jnp.ndarray, pos: jnp.ndarray,
                uniform: bool):
    """Write one decoded token's arrays ``new`` ([B, 1, ...] each) into the
    ``cached`` arrays ([B, S, ...]) at slot ``pos % S``. Returns (*cached,
    positions). ``uniform``: every row is at the same position, so the
    write is one dynamic-update-slice at a scalar slot."""
    b = pos.shape[0]
    slot = (pos % cached[0].shape[1]).astype(jnp.int32)
    if uniform:
        s0 = slot[0]
        out = [jax.lax.dynamic_update_slice_in_dim(c, x.astype(c.dtype), s0,
                                                   axis=1)
               for c, x in zip(cached, new)]
        pos_c = jax.lax.dynamic_update_slice(
            positions, pos[:, None], (jnp.int32(0), s0))
    else:
        b_idx = jnp.arange(b)
        out = [c.at[b_idx, slot].set(x[:, 0].astype(c.dtype))
               for c, x in zip(cached, new)]
        pos_c = positions.at[b_idx, slot].set(pos)
    return (*out, pos_c)


def decode_valid(pos_c: jnp.ndarray, pos: jnp.ndarray,
                 window: Optional[int]) -> jnp.ndarray:
    """[B, S] cache slots the token at ``pos`` may attend to."""
    valid = pos_c >= 0
    if window is not None:
        valid &= (pos[:, None] - pos_c) < window
    return valid & (pos_c <= pos[:, None])


def attention_decode(params: Params, x: jnp.ndarray, cache: KVCache, *,
                     rope_theta: float,
                     window: Optional[int] = None,
                     uniform: bool = False
                     ) -> Tuple[jnp.ndarray, KVCache]:
    """One new token per sequence. x: [B, 1, d].

    ``uniform=True`` (§Perf hillclimb): when every sequence in the batch is
    at the SAME position (lockstep serving), the cache update is a single
    dynamic-update-slice at a scalar slot instead of a batched scatter —
    GSPMD keeps the batch-sharded cache in place (a scatter with per-row
    indices forces replication)."""
    pos = cache.length                                     # [B]
    q = jnp.einsum("btd,dhk->bthk", x, params["wq"])
    k_new = jnp.einsum("btd,dhk->bthk", x, params["wk"])
    v_new = jnp.einsum("btd,dhk->bthk", x, params["wv"])
    q = apply_rope(q, pos[:, None], rope_theta)
    k_new = apply_rope(k_new, pos[:, None], rope_theta)

    k_c, v_c, pos_c = cache_write((cache.k, cache.v), (k_new, v_new),
                                  cache.positions, pos, uniform)
    valid = decode_valid(pos_c, pos, window)
    out = dispatch.decode_attention(q, k_c, v_c, valid)
    out = jnp.einsum("bthk,hkd->btd", out, params["wo"])
    new_cache = KVCache(k=k_c, v=v_c, positions=pos_c, length=pos + 1)
    return out, new_cache
