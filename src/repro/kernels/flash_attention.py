"""Flash attention as a Pallas TPU kernel (DESIGN.md §7).

Inference-worker prefill/decode dominates rollout latency (paper §3.2) —
this is the hot spot the framework optimizes. TPU adaptation of the
flash-attention algorithm:

  * the wrapper moves heads ahead of the sequence (``[B, H, T, D]``), so
    every block's two minor axes are ``(block, head_dim)`` — the
    (sublane, lane) tile the TPU compiler requires, with head_dim on the
    128-wide lanes;
  * grid = (batch, q-heads, q-blocks, kv-blocks); the LAST grid axis is
    iterated sequentially on TPU ("arbitrary" dimension semantics), so the
    online-softmax state (m, l, acc) lives in VMEM scratch across kv-block
    steps and is finalized on the last step;
  * per-row statistics (running max, running sum, the saved LSE and the
    backward's D = rowsum(dO∘O)) are kept lane-replicated as
    ``[rows, 128]`` tiles — a lone ``[rows]`` vector has no TPU tile;
  * GQA is handled by mapping each q-head grid index to its kv head
    (h // group) in the K/V index maps — no KV duplication in HBM;
  * causal + sliding-window masking from absolute positions;
  * short rows are packed: for self-attention (S == T) with T at most half
    a q block, ``rows_per_tile`` sequences lie end to end in one tile —
    ``P = min(B, block_q // T)``, from the shapes alone — and the mask
    adds a block-diagonal term (a query sees the keys of its own sequence
    only). Causality and the window hold within each sequence, both
    positions carrying the same offset. The grid and the padded copies
    shrink about P-fold; at T = 20 six rows fill 120 of 128 tile rows,
    where one row filled 20. At P = 1 the wrapper runs as unpacked.

The BACKWARD is a pair of real Pallas kernels too (no twin recompute):
the forward optionally saves the per-row log-sum-exp (``return_lse``), and
``flash_attention_bwd`` replays the online softmax from (q, k, v, LSE) —
``p = exp(s - LSE)`` directly, no second max/sum pass — accumulating dq
over kv blocks in one kernel and dk/dv over q blocks in the other. GQA
dk/dv come out per q-head and are summed over the group outside.

Validated in interpret mode against ``ref.reference_attention`` (CPU) and
compiled for a described TPU v5e in ``tests/test_tpu_compile.py``.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128                    # width of a lane-replicated per-row tile
_NT = (((1,), (1,)), ((), ()))  # dot_general dims for a @ b.T


def _lanes(x: jnp.ndarray, n: int) -> jnp.ndarray:
    """Widen a lane-replicated ``[rows, LANES]`` tile to ``[rows, n]``."""
    if n % LANES == 0:
        return jnp.tile(x, (1, n // LANES))
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))   # interpret-only


def _softmax_step(s, v, m_scr, l_scr, acc_scr):
    """One online-softmax update of (m, l, acc) with a [rows, bk] score
    block ``s`` (already masked) and its [bk, D] values ``v``."""
    m_prev = m_scr[...]                                # [rows, LANES]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - _lanes(m_new, s.shape[1]))         # [rows, bk]
    l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * _lanes(corr, acc_scr.shape[1]) + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new


def _softmax_scratch(rows: int, d: int):
    return [pltpu.VMEM((rows, LANES), jnp.float32),    # running max m
            pltpu.VMEM((rows, LANES), jnp.float32),    # running sum l
            pltpu.VMEM((rows, d), jnp.float32)]        # accumulator


def _segment(pos, seg_len: int):
    """floor(pos / seg_len): which packed sequence a tile position is in.
    Computed in f32 on the VPU, and exact: (pos + ½) / seg_len lies at
    least ½ / seg_len from a whole number, far beyond f32 rounding at tile
    positions."""
    return jnp.floor((pos.astype(jnp.float32) + 0.5) * (1.0 / seg_len))


def _mask(qi, kj, block_q, block_k, seq_k, causal, window, seg_len):
    """[block_q, block_k] validity from absolute positions. With
    ``seg_len`` the rows hold sequences of that length packed end to end,
    and a query sees only the keys of its own sequence."""
    shape = (block_q, block_k)
    qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    kpos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    mask = kpos < seq_k
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= (qpos - kpos) < window
    if seg_len is not None:
        # a column and a row of positions, broadcast in the compare
        qseg = _segment(qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, 1), 0), seg_len)
        kseg = _segment(kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1), seg_len)
        mask &= qseg == kseg
    return mask


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, *refs,
                 scale: float, block_q: int, block_k: int, seq_k: int,
                 causal: bool, window: Optional[int],
                 seg_len: Optional[int], save_lse: bool):
    if save_lse:
        lse_ref, m_scr, l_scr, acc_scr = refs
    else:
        m_scr, l_scr, acc_scr = refs
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    s = jax.lax.dot_general(q_ref[...], k_ref[...], _NT,
                            preferred_element_type=jnp.float32) * scale
    mask = _mask(qi, kj, block_q, block_k, seq_k, causal, window, seg_len)
    _softmax_step(jnp.where(mask, s, NEG_INF), v_ref[...], m_scr, l_scr,
                  acc_scr)

    @pl.when(kj == nk - 1)
    def _final():
        denom = jnp.maximum(l_scr[...], 1e-30)         # [bq, LANES]
        o_ref[...] = (acc_scr[...] / _lanes(denom, acc_scr.shape[1])
                      ).astype(o_ref.dtype)
        if save_lse:
            # per-row log-sum-exp: the softmax residual the backward
            # kernels replay p = exp(s - LSE) from (no second pass)
            lse_ref[...] = m_scr[...] + jnp.log(denom)


def _heads_major(x, seq_pad: int):
    """[B, T, H, D] -> [B, H, T + seq_pad, D]."""
    x = jnp.swapaxes(x, 1, 2)
    if seq_pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, seq_pad), (0, 0)))
    return x


def rows_per_tile(b: int, t: int, s: int, block_q: int) -> int:
    """How many sequences share one q tile. Self-attention rows (S == T)
    of at most half a tile are packed end to end, as many as fit and the
    batch holds; longer rows and cross lengths take a tile each."""
    if s != t or 2 * t > block_q:
        return 1
    return min(b, block_q // t)


def _pack(x, p: int, pad_value: float = 0.0):
    """[B, T, ...] -> [ceil(B / p), p·T, ...]: p sequences end to end in
    each row, the batch padded with rows of ``pad_value``."""
    b, t = x.shape[:2]
    nb = -(-b // p)
    if nb * p != b:
        x = jnp.pad(x, ((0, nb * p - b),) + ((0, 0),) * (x.ndim - 1),
                    constant_values=pad_value)
    return x.reshape((nb, p * t) + x.shape[2:])


def _unpack(x, b: int, t: int):
    """Inverse of ``_pack``: [B / p, p·T, ...] -> [B, T, ...]."""
    return x.reshape((-1, t) + x.shape[2:])[:b]


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: Optional[int] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False, return_lse: bool = False):
    """q: [B, T, H, D]; k/v: [B, S, KV, D] with H % KV == 0 → [B, T, H, D].

    T and S are padded to block multiples internally; the causal mask uses
    unpadded absolute positions, and key padding is masked out. Short
    self-attention rows are packed ``rows_per_tile`` to a tile under a
    block-diagonal mask (module docstring).
    ``return_lse`` additionally returns the per-row log-sum-exp
    [B, H, T] f32 — the residual ``flash_attention_bwd`` needs.
    """
    b0, t0 = q.shape[:2]
    p = rows_per_tile(b0, t0, k.shape[1], block_q)
    if p > 1:
        q, k, v = (_pack(x, p) for x in (q, k, v))
    b, t, h, d = q.shape
    s, kv = k.shape[1], k.shape[2]
    assert h % kv == 0, (h, kv)
    group = h // kv
    scale = d ** -0.5

    tp = math.ceil(t / block_q) * block_q
    sp = math.ceil(s / block_k) * block_k
    qh = _heads_major(q, tp - t)
    kh = _heads_major(k, sp - s)
    vh = _heads_major(v, sp - s)

    seg_len = t0 if p > 1 else None
    if p > 1:
        # at trace time; imported here, as the runtime package imports
        # the models and so this module
        from repro.runtime import telemetry as _tel
        _tel.instant("kernels.flash_pack", cat="kernels",
                     args={"t": t0, "rows_per_tile": p,
                           "tile_fill": t / tp})
    kernel = functools.partial(
        _attn_kernel, scale=scale, block_q=block_q, block_k=block_k,
        seq_k=s, causal=causal, window=window, seg_len=seg_len,
        save_lse=return_lse)
    q_spec = pl.BlockSpec((None, None, block_q, d),
                          lambda bi, hi, qi, kj: (bi, hi, qi, 0))
    kv_spec = pl.BlockSpec((None, None, block_k, d),
                           lambda bi, hi, qi, kj: (bi, hi // group, kj, 0))
    out_specs = [q_spec]
    out_shape = [jax.ShapeDtypeStruct((b, h, tp, d), q.dtype)]
    if return_lse:
        out_specs.append(pl.BlockSpec(
            (None, None, block_q, LANES),
            lambda bi, hi, qi, kj: (bi, hi, qi, 0)))
        out_shape.append(jax.ShapeDtypeStruct((b, h, tp, LANES),
                                              jnp.float32))
    got = pl.pallas_call(
        kernel,
        grid=(b, h, tp // block_q, sp // block_k),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=_softmax_scratch(block_q, d),
        interpret=interpret,
    )(qh, kh, vh)
    out = jnp.swapaxes(got[0][:, :, :t], 1, 2)
    if p > 1:
        out = _unpack(out, b0, t0)
    if not return_lse:
        return out
    lse = got[1][:, :, :t, 0]
    if p > 1:
        lse = jnp.swapaxes(_unpack(jnp.swapaxes(lse, 1, 2), b0, t0), 1, 2)
    return out, lse


# ---------------------------------------------------------------------------
# Backward: two Pallas kernels replaying the online softmax from the LSE
# ---------------------------------------------------------------------------

def _replay(q, k, do, v, lse, dd, mask, scale):
    """Softmax probabilities and score cotangents of one [bq, bk] block:
    p = exp(s − LSE); ds = p ∘ (dO·Vᵀ − D)."""
    bk = k.shape[0]
    s = jax.lax.dot_general(q, k, _NT,
                            preferred_element_type=jnp.float32) * scale
    p = jnp.where(mask, jnp.exp(s - _lanes(lse, bk)), 0.0)
    dp = jax.lax.dot_general(do, v, _NT, preferred_element_type=jnp.float32)
    return p, p * (dp - _lanes(dd, bk))


def _attn_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref,
                        dq_ref, dq_scr, *, scale, block_q, block_k, seq_k,
                        causal, window, seg_len):
    """dq accumulated over kv blocks (last grid axis sequential):
    dq += ds·K·scale."""
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(kj == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    k = k_ref[...].astype(jnp.float32)
    mask = _mask(qi, kj, block_q, block_k, seq_k, causal, window, seg_len)
    _, ds = _replay(q_ref[...].astype(jnp.float32), k,
                    do_ref[...].astype(jnp.float32),
                    v_ref[...].astype(jnp.float32), lse_ref[...],
                    dd_ref[...], mask, scale)
    dq_scr[...] += jnp.dot(ds, k, preferred_element_type=jnp.float32) * scale

    @pl.when(kj == nk - 1)
    def _final():
        dq_ref[...] = dq_scr[...]


def _attn_bwd_dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, dd_ref,
                         dk_ref, dv_ref, dk_scr, dv_scr, *, scale, block_q,
                         block_k, seq_k, causal, window, seg_len):
    """dk/dv for one k-block accumulated over q blocks (last grid axis):
    dv += pᵀ·dO; dk += dsᵀ·Q·scale. Emitted per q-head; the wrapper sums
    heads over each GQA group."""
    ki = pl.program_id(2)
    qj = pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(qj == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q = q_ref[...].astype(jnp.float32)
    do = do_ref[...].astype(jnp.float32)
    mask = _mask(qj, ki, block_q, block_k, seq_k, causal, window, seg_len)
    p, ds = _replay(q, k_ref[...].astype(jnp.float32), do,
                    v_ref[...].astype(jnp.float32), lse_ref[...],
                    dd_ref[...], mask, scale)
    dv_scr[...] += jnp.dot(p.T, do, preferred_element_type=jnp.float32)
    dk_scr[...] += jnp.dot(ds.T, q,
                           preferred_element_type=jnp.float32) * scale

    @pl.when(qj == nq - 1)
    def _final():
        dk_ref[...] = dk_scr[...]
        dv_ref[...] = dv_scr[...]


# padded q rows carry dO = 0 and D = 0, so their p·(…) products vanish;
# padding the LSE with this pushes p itself to exp(s − big) ≈ 0 as well,
# keeping every padded contribution exactly zero
_LSE_PAD = 1e30


def _row_stat(x, seq_pad: int, pad_value: float = 0.0):
    """[B, H, T] f32 -> lane-replicated [B, H, T + seq_pad, LANES]."""
    x = jnp.pad(x, ((0, 0), (0, 0), (0, seq_pad)), constant_values=pad_value)
    return jnp.broadcast_to(x[..., None], x.shape + (LANES,))


def flash_attention_bwd(q, k, v, out, lse, do, *, causal: bool = True,
                        window: Optional[int] = None, block_q: int = 128,
                        block_k: int = 128, interpret: bool = False):
    """Gradients (dq, dk, dv) from the saved forward residuals.

    q: [B,T,H,D]; k/v: [B,S,KV,D]; out/do: like q; lse: [B,H,T] f32 from
    ``flash_attention(..., return_lse=True)``. Recompute-free: the online
    softmax is replayed as ``p = exp(s − LSE)`` — one pass per kernel.
    """
    # D = rowsum(dO ∘ O): tiny elementwise reduce, cheaper outside
    dd = (do.astype(jnp.float32) * out.astype(jnp.float32)).sum(-1)
    b0, t0 = q.shape[:2]
    p = rows_per_tile(b0, t0, k.shape[1], block_q)
    if p > 1:
        # the forward's packing; the batch's padding rows, like the
        # tile's, carry dO = 0, D = 0 and the LSE sentinel
        q, k, v, do, dd = (_pack(x, p) for x in (q, k, v, do, dd))
        lse = jnp.swapaxes(_pack(jnp.swapaxes(lse, 1, 2), p, _LSE_PAD),
                           1, 2)
    b, t, h, d = q.shape
    s, kv = k.shape[1], k.shape[2]
    group = h // kv
    scale = d ** -0.5

    tp = math.ceil(t / block_q) * block_q
    sp = math.ceil(s / block_k) * block_k
    qh, doh = _heads_major(q, tp - t), _heads_major(do, tp - t)
    kh, vh = _heads_major(k, sp - s), _heads_major(v, sp - s)
    lse_l = _row_stat(lse, tp - t, _LSE_PAD)
    dd_l = _row_stat(jnp.swapaxes(dd, 1, 2), tp - t)

    # index maps: in the dq kernel the q-block index is grid axis 2 and
    # the kv-block axis 3; the dkv kernel swaps them
    def q_spec(width, qax):
        return pl.BlockSpec(
            (None, None, block_q, width),
            (lambda bi, hi, i, j: (bi, hi, i, 0)) if qax == 2 else
            (lambda bi, hi, i, j: (bi, hi, j, 0)))

    def k_spec(kax):
        return pl.BlockSpec(
            (None, None, block_k, d),
            (lambda bi, hi, i, j: (bi, hi // group, j, 0)) if kax == 3 else
            (lambda bi, hi, i, j: (bi, hi // group, i, 0)))

    kernel_kw = dict(scale=scale, block_q=block_q, block_k=block_k,
                     seq_k=s, causal=causal, window=window,
                     seg_len=t0 if p > 1 else None)
    dq = pl.pallas_call(
        functools.partial(_attn_bwd_dq_kernel, **kernel_kw),
        grid=(b, h, tp // block_q, sp // block_k),
        in_specs=[q_spec(d, 2), k_spec(3), k_spec(3), q_spec(d, 2),
                  q_spec(LANES, 2), q_spec(LANES, 2)],
        out_specs=q_spec(d, 2),
        out_shape=jax.ShapeDtypeStruct((b, h, tp, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(qh, kh, vh, doh, lse_l, dd_l)

    dkv_spec = pl.BlockSpec((None, None, block_k, d),
                            lambda bi, hi, i, j: (bi, hi, i, 0))
    dkh, dvh = pl.pallas_call(
        functools.partial(_attn_bwd_dkv_kernel, **kernel_kw),
        grid=(b, h, sp // block_k, tp // block_q),
        in_specs=[k_spec(2), k_spec(2), q_spec(d, 3), q_spec(d, 3),
                  q_spec(LANES, 3), q_spec(LANES, 3)],
        out_specs=[dkv_spec, dkv_spec],
        out_shape=[jax.ShapeDtypeStruct((b, h, sp, d), jnp.float32)] * 2,
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret,
    )(kh, vh, qh, doh, lse_l, dd_l)

    # GQA: per-q-head dk/dv fold back onto their kv head
    def fold(x):
        x = x[:, :, :s].reshape(b, kv, group, s, d).sum(2)
        return jnp.swapaxes(x, 1, 2)

    dq = jnp.swapaxes(dq[:, :, :t], 1, 2)
    dk, dv = fold(dkh), fold(dvh)
    if p > 1:
        dq, dk, dv = (_unpack(x, b0, t0) for x in (dq, dk, dv))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)
