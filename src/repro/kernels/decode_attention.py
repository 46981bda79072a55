"""Single-token decode attention as a Pallas TPU kernel.

The inference plane's hot loop is the autoregressive decode inside
``sample_action_sequence``: one new query token per sequence attending
over the KV cache. Unlike prefill, validity is *data-dependent* — ring
slots may be empty (position -1), out of the sliding window, or ahead of
the sequence (cache rows written by longer sequences in the batch) — so
the mask arrives as a precomputed additive bias instead of being derived
from grid positions:

  * grid = (batch, kv-heads, kv-blocks); the LAST axis is sequential on
    TPU, so the online-softmax state (m, l, acc) lives in VMEM scratch
    across kv-block steps and is finalized on the last step (the same
    update as ``flash_attention``);
  * one grid step serves the whole GQA group of a kv head: the query
    block is ``[group, D]`` — a block whose minor axes equal the array's
    own, which the TPU compiler accepts for any group size (MHA: 1);
  * K/V are read heads-major (``[B, KV, S, D]``), so every block's minor
    axes are ``(block_k, head_dim)``;
  * ``bias``: [B, S] f32, 0 where the cache slot is attendable and
    ``NEG_INF`` where it is not; cache padding to the block multiple is
    masked the same way. It enters the kernel as ``[B, 1, S]`` rows.

Validated in interpret mode against the dense jnp decode path and
compiled for a described TPU v5e in ``tests/test_tpu_compile.py``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.flash_attention import (_NT, _heads_major, _lanes,
                                           _softmax_scratch, _softmax_step)

NEG_INF = -1e30


def _decode_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, scale: float):
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    s = jax.lax.dot_general(q_ref[...], k_ref[...], _NT,
                            preferred_element_type=jnp.float32) * scale
    _softmax_step(s + bias_ref[...], v_ref[...], m_scr, l_scr, acc_scr)

    @pl.when(kj == nk - 1)
    def _final():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = (acc_scr[...] / _lanes(denom, acc_scr.shape[1])
                      ).astype(o_ref.dtype)


def decode_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     bias: jnp.ndarray, *, block_k: int = 128,
                     interpret: bool = False) -> jnp.ndarray:
    """q: [B, 1, H, D]; k/v: [B, S, KV, D]; bias: [B, S] f32 additive
    (0 attendable / NEG_INF masked) → [B, 1, H, D] in q.dtype."""
    b, t, h, d = q.shape
    assert t == 1, f"decode kernel wants one query token, got T={t}"
    s, kv = k.shape[1], k.shape[2]
    assert h % kv == 0, (h, kv)
    group = h // kv
    scale = d ** -0.5

    sp = math.ceil(s / block_k) * block_k
    kh = _heads_major(k, sp - s)
    vh = _heads_major(v, sp - s)
    bias = jnp.pad(bias.astype(jnp.float32), ((0, 0), (0, sp - s)),
                   constant_values=NEG_INF)[:, None, :]
    qg = q.reshape(b, kv, group, d)          # head h = kv_head * group + g

    q_spec = pl.BlockSpec((None, None, group, d),
                          lambda bi, gi, kj: (bi, gi, 0, 0))
    kv_spec = pl.BlockSpec((None, None, block_k, d),
                           lambda bi, gi, kj: (bi, gi, kj, 0))
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale),
        grid=(b, kv, sp // block_k),
        in_specs=[q_spec, kv_spec, kv_spec,
                  pl.BlockSpec((None, 1, block_k),
                               lambda bi, gi, kj: (bi, 0, kj))],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, group, d), q.dtype),
        scratch_shapes=_softmax_scratch(group, d),
        interpret=interpret,
    )(qg, kh, vh, bias)
    return out.reshape(b, 1, h, d)
