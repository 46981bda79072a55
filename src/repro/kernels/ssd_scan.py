"""Chunked Mamba2 SSD scan as a Pallas TPU kernel (DESIGN.md §7).

The SSD dual form splits the recurrence into MXU-friendly intra-chunk
matmuls and a tiny inter-chunk state recurrence. TPU mapping:

  * the wrapper moves heads ahead of time (x: ``[B, H, T, P]``; dt:
    ``[B, H, 1, T]`` rows), so every block's two minor axes are
    ``(chunk, P)`` or ``(1, chunk)`` — tiles the TPU compiler accepts;
  * grid = (batch, heads, chunks); chunks are the LAST (sequential) axis so
    the running state S [P, N] persists in VMEM scratch across chunk steps;
  * per chunk, the [q, q] decay-masked attention-like matrix and the
    [q, P/N] tiles are dense dots on the MXU. Per-position vectors (dt,
    their cumulative sums) come in both orientations: a ``[1, q]`` row and
    a ``[q, 1]`` column, converted by masked reductions
    (``_row_to_col``/``_col_to_row``) — no 1-D vectors, no cumsum
    primitive inside the kernel;
  * A[h] is read as a scalar from SMEM;
  * everything is fp32 inside the kernel (the state recurrence is
    numerically delicate); inputs may be bf16.

Matches ``ref.reference_ssd`` (the stepwise linear-form oracle) — the SSD
"duality" is exactly what the allclose test asserts.

The BACKWARD is a real Pallas kernel as well: the forward optionally saves
each chunk's *entering* state (``return_states``), and ``ssd_scan_bwd``
walks the chunks in REVERSE (index map ``nc - 1 - ci``) carrying the
state cotangent dS in VMEM scratch, with heads innermost so the
head-summed dB/dC output blocks are revisited consecutively. dA comes out
as per-(batch, head, chunk) partials summed by the wrapper.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_TN = (((0,), (0,)), ((), ()))   # a.T @ b


def _iotas(q: int):
    return (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0),
            jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))


def _row_to_col(row):
    """[1, q] -> [q, 1] (exact: a masked sum of one term per row)."""
    ii, jj = _iotas(row.shape[1])
    return jnp.sum(jnp.where(ii == jj, row, 0.0), axis=1, keepdims=True)


def _col_to_row(col):
    """[q, 1] -> [1, q]."""
    ii, jj = _iotas(col.shape[0])
    return jnp.sum(jnp.where(ii == jj, col, 0.0), axis=0, keepdims=True)


def _dot(a, b, dims=None):
    if dims is None:
        return jnp.dot(a, b, preferred_element_type=jnp.float32)
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _chunk_terms(dt_row, a, bm, cm):
    """Shared per-chunk quantities of the forward and the backward.

    Returns (dt_col, cum_col, cum_total (1,1), g [q,q], cb [q,q]) with
    cum the inclusive cumulative sum of dA = dt·a and
    g[i, j] = exp(cum_i − cum_j) for j ≤ i (0 above the diagonal)."""
    ii, jj = _iotas(dt_row.shape[1])
    da_row = dt_row * a
    cum_col = jnp.sum(jnp.where(jj <= ii, da_row, 0.0), axis=1,
                      keepdims=True)
    cum_row = _col_to_row(cum_col)
    cum_total = jnp.sum(da_row, axis=1, keepdims=True)
    g = jnp.where(jj <= ii, jnp.exp(cum_col - cum_row), 0.0)
    cb = _dot(cm, bm, _NT)
    return _row_to_col(dt_row), cum_col, cum_total, g, cb


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, s_final_ref,
                *refs, save_states: bool):
    if save_states:
        s_all_ref, state_scr = refs
    else:
        (state_scr,) = refs
    hi = pl.program_id(1)
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[...].astype(jnp.float32)                   # [q, P]
    dt_row = dt_ref[...].astype(jnp.float32)             # [1, q]
    bm = b_ref[...].astype(jnp.float32)                  # [q, N]
    cm = c_ref[...].astype(jnp.float32)                  # [q, N]
    dt_col, cum, cum_total, g, cb = _chunk_terms(dt_row, a_ref[hi], bm, cm)

    # intra-chunk: y[i] = Σ_{j<=i} (C_i·B_j) exp(cum_i − cum_j) dt_j x_j
    y = _dot(cb * g * dt_row, x)                         # [q, P]

    # inter-chunk: y[i] += exp(cum_i) · C_i · S_enterᵀ
    state = state_scr[...]                               # [P, N]
    if save_states:
        # the chunk's ENTERING state — the residual the backward kernel
        # replays this chunk's forward from
        s_all_ref[...] = state
    y += jnp.exp(cum) * _dot(cm, state, _NT)

    # state update: S ← exp(cum_total)·S + Σ_j exp(cum_total−cum_j) dt_j x_j B_jᵀ
    decay_in = jnp.exp(cum_total - cum) * dt_col         # [q, 1]
    s_new = jnp.exp(cum_total) * state + _dot(x * decay_in, bm, _TN)
    state_scr[...] = s_new

    y_ref[...] = y.astype(y_ref.dtype)

    @pl.when(ci == nc - 1)
    def _final():
        s_final_ref[...] = s_new.astype(s_final_ref.dtype)


def _smem_spec():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def ssd_scan(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
             Bm: jnp.ndarray, Cm: jnp.ndarray, *, chunk: int = 128,
             interpret: bool = False, return_states: bool = False):
    """x: [B,T,H,P]; dt: [B,T,H]; A: [H]; Bm/Cm: [B,T,N] (single group).

    Returns (y [B,T,H,P] f32, final_state [B,H,P,N] f32); T % chunk == 0.
    ``return_states`` additionally returns every chunk's entering state
    [B, NC, H, P, N] f32 — the residual ``ssd_scan_bwd`` needs.
    """
    b, t, h, p = x.shape
    n = Bm.shape[-1]
    assert t % chunk == 0, (t, chunk)
    nc = t // chunk

    kernel = functools.partial(_ssd_kernel, save_states=return_states)
    x_spec = pl.BlockSpec((None, None, chunk, p),
                          lambda bi, hi, ci: (bi, hi, ci, 0))
    out_specs = [
        x_spec,
        pl.BlockSpec((None, None, p, n), lambda bi, hi, ci: (bi, hi, 0, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((b, h, t, p), jnp.float32),
        jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
    ]
    if return_states:
        out_specs.append(pl.BlockSpec(
            (None, None, None, p, n), lambda bi, hi, ci: (bi, ci, hi, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((b, nc, h, p, n),
                                              jnp.float32))
    bc_spec = pl.BlockSpec((None, chunk, n), lambda bi, hi, ci: (bi, ci, 0))
    got = pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[
            x_spec,
            pl.BlockSpec((None, None, 1, chunk),
                         lambda bi, hi, ci: (bi, hi, 0, ci)),
            _smem_spec(),
            bc_spec,
            bc_spec,
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(jnp.swapaxes(x, 1, 2), jnp.swapaxes(dt, 1, 2)[:, :, None, :],
      A.astype(jnp.float32), Bm, Cm)
    y = jnp.swapaxes(got[0], 1, 2)
    return (y, *got[1:])


# ---------------------------------------------------------------------------
# Backward: reverse-chunk kernel carrying the state cotangent in scratch
# ---------------------------------------------------------------------------

def _ssd_bwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, senter_ref, dy_ref,
                    dsfin_ref, dx_ref, ddt_ref, da_ref, db_ref, dc_ref,
                    ds_scr):
    """One (batch, chunk, head) step of the reverse sweep.

    Grid = (b, nc, h) with heads INNERMOST: dB/dC accumulate across heads,
    so their (batch, chunk) output block must be revisited on consecutive
    sequential steps. Chunks run reversed via the ``nc - 1 - ci`` index
    maps; the per-head state cotangent dS lives in ``ds_scr[h]`` across
    chunk steps. All forward intra-chunk quantities are recomputed in f32
    from the saved inputs + the chunk's entering state.
    """
    ci = pl.program_id(1)
    hi = pl.program_id(2)

    @pl.when(ci == 0)
    def _seed():
        # reverse sweep starts at the LAST chunk: seed with the final
        # state's cotangent
        ds_scr[hi] = dsfin_ref[...]

    x = x_ref[...].astype(jnp.float32)                   # [q, P]
    dt_row = dt_ref[...].astype(jnp.float32)             # [1, q]
    a = a_ref[hi]
    bm = b_ref[...].astype(jnp.float32)                  # [q, N]
    cm = c_ref[...].astype(jnp.float32)                  # [q, N]
    S = senter_ref[...]                                  # [P, N] entering
    dy = dy_ref[...].astype(jnp.float32)                 # [q, P]
    M = ds_scr[hi]                                       # [P, N] dS_out

    q = dt_row.shape[1]
    dt_col, cum, ct, g, cb = _chunk_terms(dt_row, a, bm, cm)
    e = jnp.exp(cum)                                     # [q, 1]
    decay_out = jnp.exp(ct - cum)                        # [q, 1]
    w = cb * g * dt_row

    # --- intra-chunk path: y = W·x -------------------------------------------
    dw = _dot(dy, x, _NT)                                # [q, q]
    dx = _dot(w, dy, _TN)                                # [q, P]
    dcb = dw * g * dt_row
    dcm = _dot(dcb, bm)
    dbm = _dot(dcb, cm, _TN)
    ddt = jnp.sum(dw * cb * g, axis=0, keepdims=True)    # [1, q]

    # --- state-output path: S_out = e^ct·S + (x ∘ decay_out·dt)ᵀ·B ----------
    xm = _dot(x, M)                                      # [q, N]
    dx += (decay_out * dt_col) * _dot(bm, M, _NT)
    dbm += (decay_out * dt_col) * xm
    di = jnp.sum(xm * bm, axis=1, keepdims=True)  # d(decay_in = e^{ct-c}dt)
    ddt += _col_to_row(di * decay_out)

    # --- inter-chunk y path: y += e ∘ (C·S_enterᵀ) ---------------------------
    cs = _dot(cm, S, _NT)                                # [q, P]
    dcm += e * _dot(dy, S)

    # --- cum / ct cotangents -------------------------------------------------
    gg = dw * cb * dt_row * g                    # dG ∘ G (i, j)
    dcum = (jnp.sum(gg, axis=1, keepdims=True)   # +row(i), −col(j)
            - _row_to_col(jnp.sum(gg, axis=0, keepdims=True)))
    dcum += jnp.sum(dy * cs, axis=1, keepdims=True) * e
    dcum -= di * decay_out * dt_col              # exp(ct − cum_j) direct
    dct = jnp.sum(di * decay_out * dt_col, axis=0, keepdims=True)
    dct += jnp.exp(ct) * jnp.sum(jnp.sum(M * S, axis=1, keepdims=True),
                                 axis=0, keepdims=True)
    last = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    dcum += jnp.where(last, dct, 0.0)            # ct = cum[q-1]
    # cum = cumsum(dA)  ⇒  ddA_j = Σ_{i≥j} dcum_i (reverse cumsum)
    ii, jj = _iotas(q)
    dda = jnp.sum(jnp.where(ii >= jj, dcum, 0.0), axis=0, keepdims=True)
    ddt += dda * a
    da = jnp.sum(dda * dt_row, axis=1, keepdims=True)    # (1, 1)

    # --- carry to the previous chunk ----------------------------------------
    ds_scr[hi] = jnp.exp(ct) * M + _dot(dy * e, cm, _TN)

    dx_ref[...] = dx
    ddt_ref[...] = ddt
    da_ref[...] = jnp.broadcast_to(da, da_ref.shape)

    @pl.when(hi == 0)
    def _first_head():
        db_ref[...] = dbm
        dc_ref[...] = dcm

    @pl.when(hi != 0)
    def _other_heads():
        db_ref[...] += dbm
        dc_ref[...] += dcm


def ssd_scan_bwd(x, dt, A, Bm, Cm, s_enter, dy, ds_final, *,
                 chunk: int = 128, interpret: bool = False):
    """Gradients (dx, ddt, dA, dBm, dCm) of ``ssd_scan``.

    Inputs as the forward, plus ``s_enter`` [B,NC,H,P,N] from
    ``ssd_scan(..., return_states=True)`` and the output cotangents
    (dy [B,T,H,P], ds_final [B,H,P,N]). One reverse pallas sweep — no
    forward recompute.
    """
    b, t, h, p = x.shape
    n = Bm.shape[-1]
    nc = t // chunk
    rev = lambda ci: nc - 1 - ci     # noqa: E731 - reversed chunk order

    x_spec = pl.BlockSpec((None, None, chunk, p),
                          lambda bi, ci, hi: (bi, hi, rev(ci), 0))
    dt_spec = pl.BlockSpec((None, None, 1, chunk),
                           lambda bi, ci, hi: (bi, hi, 0, rev(ci)))
    bc_spec = pl.BlockSpec((None, chunk, n),
                           lambda bi, ci, hi: (bi, rev(ci), 0))
    # dA partials: one lane-dense (8, 128) tile per (batch, head, chunk)
    da_spec = pl.BlockSpec((None, None, 8, 128),
                           lambda bi, ci, hi: (bi, hi, rev(ci), 0))
    dx, ddt, da_part, dbm, dcm = pl.pallas_call(
        _ssd_bwd_kernel,
        grid=(b, nc, h),
        in_specs=[
            x_spec,
            dt_spec,
            _smem_spec(),
            bc_spec,
            bc_spec,
            pl.BlockSpec((None, None, None, p, n),
                         lambda bi, ci, hi: (bi, rev(ci), hi, 0, 0)),
            x_spec,
            pl.BlockSpec((None, None, p, n),
                         lambda bi, ci, hi: (bi, hi, 0, 0)),
        ],
        out_specs=[x_spec, dt_spec, da_spec, bc_spec, bc_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, t, p), jnp.float32),
            jax.ShapeDtypeStruct((b, h, 1, t), jnp.float32),
            jax.ShapeDtypeStruct((b, h, nc * 8, 128), jnp.float32),
            jax.ShapeDtypeStruct((b, t, n), jnp.float32),
            jax.ShapeDtypeStruct((b, t, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((h, p, n), jnp.float32)],
        interpret=interpret,
    )(jnp.swapaxes(x, 1, 2), jnp.swapaxes(dt, 1, 2)[:, :, None, :],
      A.astype(jnp.float32), Bm, Cm, s_enter,
      jnp.swapaxes(dy.astype(jnp.float32), 1, 2),
      ds_final.astype(jnp.float32))
    # per-(b, head, chunk) dA partials fold to [H] outside the kernel
    da = da_part[:, :, ::8, 0].sum(axis=(0, 2))
    return (jnp.swapaxes(dx, 1, 2).astype(x.dtype),
            jnp.swapaxes(ddt[:, :, 0], 1, 2).astype(dt.dtype),
            da.astype(A.dtype), dbm.astype(Bm.dtype), dcm.astype(Cm.dtype))
