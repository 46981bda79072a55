"""Single-token decode attention as a Pallas TPU kernel.

The inference plane's hot loop is the autoregressive decode inside
``sample_action_sequence``: one new query token per sequence attending
over the KV cache. Unlike prefill, validity is *data-dependent* — ring
slots may be empty (position -1), out of the sliding window, or ahead of
the sequence (cache rows written by longer sequences in the batch) — so
the mask arrives as a precomputed additive bias instead of being derived
from grid positions.

  * grid = (batch blocks, cache blocks): one step serves ``rows`` batch
    rows over every head. The LAST axis is sequential on TPU, so the
    online-softmax state (m, l, acc) lives in VMEM scratch across cache
    blocks and is finalized on the last one. A cache that fits the VMEM
    budget whole is one block, so a short cache takes one step per batch
    block, and a long one still streams;
  * K/V are read as the cache stores them: ``[B, S, KV, D]`` enters as
    its free ``[B, S * KV, D]`` view, in blocks of ``(rows, block_s * KV,
    D)`` — slot-major, KV heads interleaved, ``head_dim`` on the lanes.
    Nothing is transposed, and nothing is padded to a 128-slot tile;
  * ``rows`` and ``block_s`` follow from the call's shapes and a VMEM
    budget (``_tiles``), with no setting of their own. Neither has to
    divide its axis: the rows of a ragged last batch block are computed
    and never written back, and slots past the cache's end in a ragged
    last cache block are excluded by position inside the kernel;
  * per batch row the scores of every query head against every cached
    (slot, KV head) pair are one ``[H, D] x [D, block_s * KV]`` MXU
    product; the pairs of other KV heads are masked off (column c holds
    KV head ``c % KV``, query head h reads KV head ``h // group``), which
    covers MHA, GQA and MQA alike. The masked pairs cost MXU work (a
    factor KV) but no HBM bytes, and the call stays bound by reading the
    cache. The f32 online softmax runs over the scores' lanes, and
    ``o = p @ V`` is a second MXU product with f32 accumulation;
  * ``bias``: [B, S] f32, 0 where the cache slot is attendable and
    ``NEG_INF`` where it is not; it enters repeated per KV head as
    ``[B, 1, S * KV]`` rows that line up with the score columns.

Validated in interpret mode against the dense jnp decode path and
compiled for a described TPU v5e in ``tests/test_tpu_compile.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import _NT, LANES

NEG_INF = -1e30
# VMEM bytes for one K block, and for one batch row's scores; K and V are
# double-buffered, so a step holds several times this. A 4 MiB K block
# overflows the v5e's default scoped VMEM (compile rehearsal).
_BLOCK_BYTES = 1 << 20


def _tiles(b: int, s: int, h: int, kv: int, d: int, itemsize: int):
    """(rows, block_s): the whole cache of as many batch rows as the
    budget holds, else one row streamed in blocks of a multiple of 128
    slots (so that a block's ``block_s * KV`` columns fill lane tiles)."""
    slot = max(kv * d * itemsize, h * kv * 4)
    rows = _BLOCK_BYTES // (s * slot)
    if rows >= 1:
        rows = min(rows, b)
        return -(-b // -(-b // rows)), s       # even out the batch blocks
    return 1, min(s, max(LANES, _BLOCK_BYTES // slot // LANES * LANES))


def _decode_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, scale: float, kv: int, group: int, seq: int):
    sj = pl.program_id(1)
    h, n = q_ref.shape[1], k_ref.shape[1]            # n = block_s * KV

    @pl.when(sj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    col = jax.lax.broadcasted_iota(jnp.int32, (h, n), 1)
    live = (col % kv) == (jax.lax.broadcasted_iota(jnp.int32, (h, n), 0)
                          // group)
    ragged = seq % (n // kv) != 0
    if ragged:                        # slots past the cache's end
        live &= sj * n + col < seq * kv
        in_cache = (sj * n + jax.lax.broadcasted_iota(
            jnp.int32, (n, 1), 0)) < seq * kv
    for r in range(q_ref.shape[0]):
        s = jax.lax.dot_general(q_ref[r], k_ref[r], _NT,
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(live, s + bias_ref[r], NEG_INF)        # [H, n]
        v = v_ref[r]
        if ragged:
            v = jnp.where(in_cache, v, jnp.zeros_like(v))
        m_prev = m_scr[r]                                     # [H, LANES]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])
        l_scr[r] = l_scr[r] * corr + p.sum(axis=-1, keepdims=True)
        acc_scr[r] = acc_scr[r] * corr[:, :1] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[r] = m_new

    @pl.when(sj == pl.num_programs(1) - 1)
    def _final():
        denom = jnp.maximum(l_scr[...][..., :1], 1e-30)
        o_ref[...] = (acc_scr[...] / denom).astype(o_ref.dtype)


def decode_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     bias: jnp.ndarray, *,
                     interpret: bool = False) -> jnp.ndarray:
    """q: [B, 1, H, D]; k/v: [B, S, KV, D]; bias: [B, S] f32 additive
    (0 attendable / NEG_INF masked) → [B, 1, H, D] in q.dtype."""
    b, t, h, d = q.shape
    assert t == 1, f"decode kernel wants one query token, got T={t}"
    s, kv = k.shape[1], k.shape[2]
    assert h % kv == 0, (h, kv)
    rows, block_s = _tiles(b, s, h, kv, d, k.dtype.itemsize)
    n = block_s * kv

    q_spec = pl.BlockSpec((rows, h, d), lambda i, j: (i, 0, 0))
    kv_spec = pl.BlockSpec((rows, n, d), lambda i, j: (i, j, 0))
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=d ** -0.5, kv=kv,
                          group=h // kv, seq=s),
        grid=(pl.cdiv(b, rows), pl.cdiv(s, block_s)),
        in_specs=[q_spec, kv_spec, kv_spec,
                  pl.BlockSpec((rows, 1, n), lambda i, j: (i, 0, j))],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((rows, h, LANES), jnp.float32),
                        pltpu.VMEM((rows, h, LANES), jnp.float32),
                        pltpu.VMEM((rows, h, d), jnp.float32)],
        interpret=interpret,
    )(q.reshape(b, h, d), k.reshape(b, s * kv, d), v.reshape(b, s * kv, d),
      jnp.repeat(bias.astype(jnp.float32), kv, axis=1)[:, None, :])
    return out.reshape(b, 1, h, d)
