"""Grouped matrix multiplication in Pallas for the expert layer.

The rows of ``lhs`` are sorted by group (expert); ``group_sizes[i]`` rows
belong to group ``i`` and the rows past ``sum(group_sizes)`` to none.

* ``gmm(lhs [m, k], rhs [g, k, n], sizes)`` gives ``lhs[rows of i] @
  rhs[i]`` for every group, zeros in the rows of no group
  (``transpose_rhs``: ``rhs [g, n, k]``, the input gradient's form);
* ``tgmm(lhs [m, k], dout [m, n], sizes)`` gives ``lhs[rows of i].T @
  dout[rows of i]`` for every group, ``[g, k, n]`` (the weight gradient;
  an empty group gets zeros).

The design follows the ``megablox`` kernels of JAX: the grid walks only
the row tiles that hold rows of some group (a data-dependent count, from
scalar-prefetched metadata), a tile that two groups share is visited once
by each and written under a row mask, so no work is done for rows of no
group. ``grouped_matmul`` puts them together under a custom VJP.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128


def _tile(dim: int, cap: int) -> int:
    """The largest multiple of 128 that divides ``dim`` and is at most
    ``cap``; ``dim`` itself where it is at most ``cap`` or has none."""
    if dim <= cap:
        return dim
    for t in range(cap - cap % LANE, 0, -LANE):
        if dim % t == 0:
            return t
    return dim


def row_tile(m: int) -> int:
    return LANE if m <= 8 * LANE else 2 * LANE


def group_metadata(sizes: jnp.ndarray, m: int, tm: int, visit_empty: bool):
    """(group offsets [g+1], group of each grid step, row tile of each grid
    step), and the number of grid steps that hold rows. A group that
    starts inside a row tile adds a step; with ``visit_empty`` an empty
    group takes one step (so that ``tgmm`` writes its zeros)."""
    g = sizes.shape[0]
    tiles_m = m // tm
    ends = jnp.cumsum(sizes)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    starts = offsets[:-1]
    span = (ends + tm - 1) // tm * tm - starts // tm * tm
    group_tiles = jnp.where(sizes == 0, 0, span) // tm
    if visit_empty:
        group_tiles = jnp.where(sizes == 0, 1, group_tiles)
    steps = tiles_m + g - 1
    group_ids = jnp.repeat(jnp.arange(g, dtype=jnp.int32), group_tiles,
                           total_repeat_length=steps)
    # a row tile is visited once by its owner, once more by every group
    # that starts inside it
    extra = (starts % tm != 0) & (sizes != 0)
    if visit_empty:
        extra = extra | (sizes == 0)
    first = jnp.where(extra, starts // tm, tiles_m)
    visits = jnp.zeros(tiles_m + 1, jnp.int32).at[first].add(1)[:tiles_m] + 1
    m_tile_ids = jnp.repeat(jnp.arange(tiles_m, dtype=jnp.int32), visits,
                            total_repeat_length=steps)
    return (offsets, group_ids, m_tile_ids), group_tiles.sum()


def _row_mask(meta, step, tm: int, width: int):
    offsets, group_ids, m_tile_ids = meta
    gid = group_ids[step]
    rows = lax.broadcasted_iota(jnp.int32, (tm, width), 0) \
        + m_tile_ids[step] * tm
    return (rows >= offsets[gid]) & (rows < offsets[gid + 1])


def _gmm_kernel(offsets, group_ids, m_tile_ids, lhs_ref, rhs_ref, out_ref,
                acc_ref, *, tm: int, tn: int, tiles_k: int,
                transpose_rhs: bool):
    step, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    dims = (((1,), (1,)), ((), ())) if transpose_rhs else (((1,), (0,)),
                                                          ((), ()))
    acc_ref[...] += lax.dot_general(lhs_ref[...], rhs_ref[...], dims,
                                    preferred_element_type=jnp.float32)

    @pl.when(ki == tiles_k - 1)
    def _store():
        mask = _row_mask((offsets, group_ids, m_tile_ids), step, tm, tn)
        out_ref[...] = jnp.where(mask, acc_ref[...],
                                 out_ref[...].astype(jnp.float32)
                                 ).astype(out_ref.dtype)


def gmm(lhs, rhs, sizes, *, transpose_rhs: bool = False,
        interpret: bool = False):
    """Per-group ``lhs @ rhs[i]`` over row-sorted ``lhs`` [m, k]; rhs
    [g, k, n] ([g, n, k] with ``transpose_rhs``) -> [m, n] in lhs.dtype."""
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm, tk, tn = row_tile(m), _tile(k, 512), _tile(n, 1408)
    mp = -(-m // tm) * tm
    lhs_p = jnp.pad(lhs, ((0, mp - m), (0, 0)))
    meta, active = group_metadata(sizes, mp, tm, visit_empty=False)
    tiles_k = k // tk

    def lhs_idx(ni, step, ki, offsets, group_ids, m_tile_ids):
        return m_tile_ids[step], ki

    def rhs_idx(ni, step, ki, offsets, group_ids, m_tile_ids):
        return (group_ids[step], ni, ki) if transpose_rhs else (
            group_ids[step], ki, ni)

    def out_idx(ni, step, ki, offsets, group_ids, m_tile_ids):
        return m_tile_ids[step], ni

    rhs_block = (None, tn, tk) if transpose_rhs else (None, tk, tn)
    out = pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, tn=tn, tiles_k=tiles_k,
                          transpose_rhs=transpose_rhs),
        out_shape=jax.ShapeDtypeStruct((mp, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[pl.BlockSpec((tm, tk), lhs_idx),
                      pl.BlockSpec(rhs_block, rhs_idx)],
            out_specs=pl.BlockSpec((tm, tn), out_idx),
            grid=(n // tn, active, tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(*meta, lhs_p, rhs)
    # rows of no group were never written
    rows = lax.broadcasted_iota(jnp.int32, (m, 1), 0)
    return jnp.where(rows < meta[0][-1], out[:m], 0).astype(lhs.dtype)


def _tgmm_kernel(offsets, group_ids, m_tile_ids, lhs_ref, rhs_ref, out_ref,
                 acc_ref, *, tm: int, tk: int, tn: int):
    step = pl.program_id(2)
    meta = (offsets, group_ids, m_tile_ids)
    gid = group_ids[step]
    prev = group_ids[jnp.maximum(step - 1, 0)]
    last = step == pl.num_programs(2) - 1
    nxt = group_ids[jnp.where(last, step, step + 1)]

    @pl.when((step == 0) | (prev != gid))
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(offsets[gid + 1] > offsets[gid])
    def _accumulate():
        a = jnp.where(_row_mask(meta, step, tm, tk),
                      lhs_ref[...].astype(jnp.float32), 0.0)
        b = jnp.where(_row_mask(meta, step, tm, tn),
                      rhs_ref[...].astype(jnp.float32), 0.0)
        acc_ref[...] += lax.dot_general(
            a.astype(lhs_ref.dtype), b.astype(rhs_ref.dtype),
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(last | (nxt != gid))
    def _store():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def tgmm(lhs, dout, sizes, *, out_dtype=None, interpret: bool = False):
    """Per-group ``lhs[rows].T @ dout[rows]``; lhs [m, k], dout [m, n]
    -> [g, k, n] (an empty group's block is zeros)."""
    m, k = lhs.shape
    n = dout.shape[1]
    g = sizes.shape[0]
    tm, tk, tn = row_tile(m), _tile(k, 512), _tile(n, 1408)
    mp = -(-m // tm) * tm
    pad = ((0, mp - m), (0, 0))
    meta, active = group_metadata(sizes, mp, tm, visit_empty=True)

    def lhs_idx(ni, ki, step, offsets, group_ids, m_tile_ids):
        return m_tile_ids[step], ki

    def rhs_idx(ni, ki, step, offsets, group_ids, m_tile_ids):
        return m_tile_ids[step], ni

    def out_idx(ni, ki, step, offsets, group_ids, m_tile_ids):
        return group_ids[step], ki, ni

    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm, tk=tk, tn=tn),
        out_shape=jax.ShapeDtypeStruct((g, k, n), out_dtype or lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[pl.BlockSpec((tm, tk), lhs_idx),
                      pl.BlockSpec((tm, tn), rhs_idx)],
            out_specs=pl.BlockSpec((None, tk, tn), out_idx),
            grid=(n // tn, k // tk, active),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(*meta, jnp.pad(lhs, pad), jnp.pad(dout, pad))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def grouped_matmul(lhs, rhs, sizes, interpret: bool = False):
    """``gmm`` with its gradients as Pallas kernels: ``dlhs`` is ``gmm``
    against the transposed weights, ``drhs`` is ``tgmm``."""
    return gmm(lhs, rhs, sizes, interpret=interpret)


def _gm_fwd(lhs, rhs, sizes, interpret):
    return gmm(lhs, rhs, sizes, interpret=interpret), (lhs, rhs, sizes)


def _gm_bwd(interpret, res, g):
    lhs, rhs, sizes = res
    g = g.astype(lhs.dtype)
    dlhs = gmm(g, rhs, sizes, transpose_rhs=True, interpret=interpret)
    drhs = tgmm(lhs, g, sizes, out_dtype=rhs.dtype, interpret=interpret)
    return dlhs, drhs, None


grouped_matmul.defvjp(_gm_fwd, _gm_bwd)
