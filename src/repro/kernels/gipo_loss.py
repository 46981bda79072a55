"""Fused token-level GIPO loss as Pallas TPU kernels (DESIGN.md §7).

The naive objective touches the [N, V_action] logit tensor three times
(log-softmax, gather, ratio product) and twice more for the entropy bonus
and KL penalty. The kernels stream token blocks through VMEM once: per
block they fuse row-max → log-sum-exp → target gather → Gaussian trust
weight (eq. 5) → surrogate (eq. 6) → entropy → k3-KL → partial reductions,
emitting one lane-dense (8, 128) tile of partial sums per block. The
host-side wrapper sums the partials — no [N, V] intermediate ever returns
to HBM.

Two fusion levels:

  * ``gipo_head_loss``   — logits-level: consumes [N, V] logits. Custom
    VJP: an analytic backward kernel re-streams the same blocks and emits
    ``d_logits`` directly, so the backward never materializes a second
    [N, V] softmax intermediate (the block softmax lives only in VMEM).
  * ``fused_policy_loss`` — hidden-level: consumes [N, d] hidden states
    plus the slimmed action-head weight [d, Va] and computes the logits
    block *inside* the kernel. Forward and backward never write an
    [N, Va] tensor to HBM at all: the backward emits ``d_hidden`` per
    block and accumulates ``d_w`` across the sequential grid.

TPU layout: the per-token vectors (targets, μ log-probs, advantages,
mask) enter as ``[N, 1]`` columns — blocks ``(block_n, 1)`` whose minor
axis equals the array's own, the orientation the ``[block_n, V]`` logits
broadcast against. The backward's loss cotangents are scalars read from
SMEM.

Gradients are defined w.r.t. logits (resp. hidden + head weight) only;
``targets``/``logp_old``/``advantages``/``mask`` are treated as constants,
matching the trainer where advantages are stop-gradient and the rest is
rollout data. Metric outputs are stop-gradiented explicitly.

The per-block math lives in plain-jnp helpers (``_fwd_partials``,
``_block_dlogits``) shared verbatim by the Pallas kernel bodies and by the
streaming jnp twins in ``repro.kernels.dispatch`` — one source of truth.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Column layout of the per-block partial sums (padded to 8 for layout):
#   0: Σ pg        1: Σ ratio   2: Σ omega   3: Σ mask (token count)
#   4: Σ entropy   5: Σ k3-KL   6: Σ stale   7: unused
N_COLS = 8
_TILE = (8, 128)     # one lane-dense partial-sum tile per token block


# ---------------------------------------------------------------------------
# Shared block math (pure jnp — used by kernels AND the jnp twins)
# ---------------------------------------------------------------------------

def _rowsum(x):
    return jnp.sum(x, axis=-1, keepdims=True)


def _expm1(x):
    """exp(x) − 1 without cancellation near 0, from primitives the TPU
    kernel compiler lowers (it has no ``expm1``)."""
    return jnp.tanh(0.5 * x) * (jnp.exp(x) + 1.0)


def _softmax_rows(logits32: jnp.ndarray, targets: jnp.ndarray):
    """Row-streamed log-softmax pieces. logits32: [bn, V] f32; targets
    [bn, 1]. Per-row results are [bn, 1] columns."""
    shifted = logits32 - jnp.max(logits32, axis=-1, keepdims=True)
    expsh = jnp.exp(shifted)
    sumexp = _rowsum(expsh)
    lse = jnp.log(sumexp)
    onehot = (jax.lax.broadcasted_iota(jnp.int32, logits32.shape, 1)
              == targets)
    logp_new = _rowsum(jnp.where(onehot, shifted, 0.0)) - lse
    p = expsh / sumexp                                 # [bn, V]
    logp = shifted - lse                               # [bn, V]
    ent = -_rowsum(p * logp)                           # [bn, 1]
    return p, logp, onehot, logp_new, ent


def _fwd_partials(logits32, targets, logp_old, adv, mask, sigma: float,
                  sg=lambda x: x):
    """One block's partial sums (see N_COLS layout), each a (1, 1) array.

    ``sg``: stop-gradient hook for the trust weight's log-ratio (eq. 5).
    The Pallas kernels leave it as identity — their backward is analytic
    and already treats ω as constant; the autodiffed jnp twin must pass
    ``jax.lax.stop_gradient`` to get the same semantics.
    """
    _, _, _, logp_new, ent = _softmax_rows(logits32, targets)
    lr = logp_new - logp_old
    ratio = jnp.exp(lr)
    omega = jnp.exp(-0.5 * jnp.square(sg(lr) / sigma))  # eq. 5
    pg = -(omega * ratio * adv)                        # eq. 6
    k3 = _expm1(-lr) + lr                              # k3 KL estimator
    stale = (jnp.abs(sg(lr)) > 2.0 * sigma).astype(jnp.float32)
    total = lambda x: jnp.sum(x * mask, axis=0, keepdims=True)  # noqa: E731
    return [total(pg), total(ratio), total(omega), total(jnp.ones_like(pg)),
            total(ent), total(k3), total(stale)]


def _partials_vector(parts) -> jnp.ndarray:
    """(1, 1) partials -> the [N_COLS] vector (jnp twins)."""
    pad = [jnp.zeros((1, 1), jnp.float32)] * (N_COLS - len(parts))
    return jnp.concatenate(parts + pad, axis=1)[0]


def _partials_tile(parts) -> jnp.ndarray:
    """(1, 1) partials -> one lane-dense tile, column c holding part c."""
    lane = jax.lax.broadcasted_iota(jnp.int32, _TILE, 1)
    tile = jnp.zeros(_TILE, jnp.float32)
    for c, part in enumerate(parts):
        tile = jnp.where(lane == c, part, tile)
    return tile


def _sum_tiles(tiles: jnp.ndarray) -> jnp.ndarray:
    """[nb * 8, 128] kernel output -> the summed [N_COLS] vector."""
    return tiles.reshape(-1, *_TILE)[:, 0, :N_COLS].sum(axis=0)


def _block_dlogits(logits32, targets, logp_old, adv, mask, sigma: float,
                   c_pg, c_kl, c_ent):
    """Analytic d_logits for one block, f32 [bn, V].

    c_* are upstream cotangents already divided by the global denominator.
    Derivation (per valid row, ∂logp_new/∂z_v = onehot_v − p_v):
      pg:  ∂(−ω ρ Â)/∂logp_new = −ω ρ Â        (ω is stop-gradient)
      kl:  ∂k3/∂logp_new       = 1 − e^{−log ρ}
      ent: ∂H/∂z_v             = −p_v (log p_v + H)
    """
    p, logp, onehot, logp_new, ent = _softmax_rows(logits32, targets)
    lr = logp_new - logp_old
    ratio = jnp.exp(lr)
    omega = jnp.exp(-0.5 * jnp.square(lr / sigma))
    g = (c_pg * (-(omega * ratio * adv))
         + c_kl * (1.0 - jnp.exp(-lr))) * mask         # [bn, 1]
    d = g * (onehot.astype(jnp.float32) - p)
    d += (c_ent * mask) * (-(p * (logp + ent)))
    return d


def _finalize(sums: jnp.ndarray):
    """Partial-sum vector [8] -> (pg, entropy, kl, metrics).

    Metrics are diagnostics, not loss terms — stop-gradient them here so
    the autodiffed jnp twins match the custom-VJP kernels (whose backward
    ignores the metrics cotangents by construction)."""
    denom = jnp.maximum(sums[3], 1.0)
    pg = sums[0] / denom
    metrics = {"ratio_mean": sums[1] / denom,
               "omega_mean": sums[2] / denom,
               "stale_frac": sums[6] / denom}
    return (pg, sums[4] / denom, sums[5] / denom,
            jax.tree.map(jax.lax.stop_gradient, metrics))


def _pad_rows(block_n: int, *arrays):
    """Pad every array's leading axis to a multiple of ``block_n``."""
    n = arrays[0].shape[0]
    np_ = math.ceil(n / block_n) * block_n
    if np_ == n:
        return arrays
    pad = np_ - n
    return tuple(jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                 for a in arrays)


def _columns(*vectors):
    """[N] per-token vectors -> [N, 1] columns."""
    return tuple(v.reshape(-1, 1) for v in vectors)


def _col_spec(block_n: int):
    return pl.BlockSpec((block_n, 1), lambda i: (i, 0))


def _zero_mask_pad(i, block_n: int, valid_n: int, mask):
    rows = i * block_n + jax.lax.broadcasted_iota(jnp.int32, (block_n, 1), 0)
    return jnp.where(rows < valid_n, mask, 0.0)


# ---------------------------------------------------------------------------
# Logits-level kernels
# ---------------------------------------------------------------------------

def _gipo_fwd_kernel(logits_ref, targets_ref, logp_old_ref, adv_ref, mask_ref,
                     out_ref, *, sigma: float, block_n: int, valid_n: int):
    i = pl.program_id(0)
    mask = _zero_mask_pad(i, block_n, valid_n, mask_ref[...])
    out_ref[...] = _partials_tile(_fwd_partials(
        logits_ref[...].astype(jnp.float32), targets_ref[...],
        logp_old_ref[...], adv_ref[...], mask, sigma))


def _gipo_bwd_kernel(logits_ref, targets_ref, logp_old_ref, adv_ref, mask_ref,
                     coef_ref, dlogits_ref, *, sigma: float, block_n: int,
                     valid_n: int):
    i = pl.program_id(0)
    mask = _zero_mask_pad(i, block_n, valid_n, mask_ref[...])
    d = _block_dlogits(logits_ref[...].astype(jnp.float32), targets_ref[...],
                       logp_old_ref[...], adv_ref[...], mask, sigma,
                       coef_ref[0], coef_ref[1], coef_ref[2])
    dlogits_ref[...] = d.astype(dlogits_ref.dtype)


def _gipo_fwd_call(logits, targets, logp_old, advantages, mask, sigma,
                   block_n, interpret):
    n, v = logits.shape
    logits, *cols = _pad_rows(
        block_n, logits, *_columns(targets, logp_old, advantages, mask))
    grid = (logits.shape[0] // block_n,)
    kernel = functools.partial(_gipo_fwd_kernel, sigma=sigma,
                               block_n=block_n, valid_n=n)
    partials = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_n, v), lambda i: (i, 0))]
        + [_col_spec(block_n)] * 4,
        out_specs=pl.BlockSpec(_TILE, lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((grid[0] * _TILE[0], _TILE[1]),
                                       jnp.float32),
        interpret=interpret,
    )(logits, *cols)
    return _finalize(_sum_tiles(partials))


def _gipo_bwd_call(logits, targets, logp_old, advantages, mask, sigma,
                   block_n, interpret, coefs):
    n, v = logits.shape
    dtype = logits.dtype
    logits, *cols = _pad_rows(
        block_n, logits, *_columns(targets, logp_old, advantages, mask))
    grid = (logits.shape[0] // block_n,)
    kernel = functools.partial(_gipo_bwd_kernel, sigma=sigma,
                               block_n=block_n, valid_n=n)
    d = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_n, v), lambda i: (i, 0))]
        + [_col_spec(block_n)] * 4
        + [pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((block_n, v), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((logits.shape[0], v), dtype),
        interpret=interpret,
    )(logits, *cols, coefs)
    return d[:n]


def _loss_coefs(mask, cts) -> jnp.ndarray:
    """Fold the (pg, ent, kl) cotangents and 1/denom into an [8] vector
    (read by the backward kernels as SMEM scalars)."""
    ct_pg, ct_ent, ct_kl, _ = cts
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.stack([ct_pg / denom, ct_kl / denom, ct_ent / denom,
                      *([jnp.zeros(())] * (N_COLS - 3))]).astype(jnp.float32)


def _int_zero(x):
    return np.zeros(x.shape, dtype=jax.dtypes.float0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _gipo_head_loss_vjp(logits, targets, logp_old, advantages, mask,
                        sigma, block_n, interpret):
    return _gipo_fwd_call(logits, targets, logp_old, advantages, mask,
                          sigma, block_n, interpret)


def _gipo_head_fwd(logits, targets, logp_old, advantages, mask,
                   sigma, block_n, interpret):
    out = _gipo_fwd_call(logits, targets, logp_old, advantages, mask,
                         sigma, block_n, interpret)
    return out, (logits, targets, logp_old, advantages, mask)


def _gipo_head_bwd(sigma, block_n, interpret, res, cts):
    logits, targets, logp_old, advantages, mask = res
    d = _gipo_bwd_call(logits, targets, logp_old, advantages, mask,
                       sigma, block_n, interpret, _loss_coefs(mask, cts))
    return (d, _int_zero(targets), jnp.zeros_like(logp_old),
            jnp.zeros_like(advantages), jnp.zeros_like(mask))


_gipo_head_loss_vjp.defvjp(_gipo_head_fwd, _gipo_head_bwd)


def gipo_head_loss(logits, targets, logp_old, advantages, mask,
                   sigma: float, block_n: int = 256,
                   interpret: bool = False):
    """Fused GIPO surrogate + entropy + k3-KL over [N, V] logits.

    Returns ``(pg_loss, entropy, kl, metrics)`` — all masked means over the
    N token rows. Differentiable w.r.t. ``logits`` via an analytic backward
    Pallas kernel (see module docstring for the constant-input convention).
    The metrics are explicitly stop-gradiented — the custom VJP only
    propagates the (pg, entropy, kl) cotangents.
    """
    pg, ent, kl, metrics = _gipo_head_loss_vjp(
        logits, targets, logp_old, advantages, mask, sigma, block_n,
        interpret)
    return pg, ent, kl, jax.tree.map(jax.lax.stop_gradient, metrics)


def gipo_loss_fused(logits: jnp.ndarray, targets: jnp.ndarray,
                    logp_old: jnp.ndarray, advantages: jnp.ndarray,
                    mask: jnp.ndarray, sigma: float, *,
                    block_n: int = 256,
                    interpret: bool = False
                    ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """logits: [N, V]; targets/logp_old/advantages/mask: [N].

    Returns (scalar pg loss, metrics) matching ``ref.reference_gipo_loss``;
    differentiable w.r.t. ``logits`` (custom VJP, analytic backward kernel).
    """
    pg, ent, kl, metrics = gipo_head_loss(logits, targets, logp_old,
                                          advantages, mask, sigma, block_n,
                                          interpret)
    metrics = dict(metrics, entropy=ent, kl=kl)
    return pg, jax.tree.map(jax.lax.stop_gradient, metrics)


# ---------------------------------------------------------------------------
# Hidden-level kernels: the action-head matmul fused into the loss
# ---------------------------------------------------------------------------

def _policy_fwd_kernel(hidden_ref, w_ref, targets_ref, logp_old_ref, adv_ref,
                       mask_ref, out_ref, *, sigma: float, block_n: int,
                       valid_n: int):
    i = pl.program_id(0)
    logits = jnp.dot(hidden_ref[...], w_ref[...],
                     preferred_element_type=jnp.float32)   # [bn, Va] f32
    mask = _zero_mask_pad(i, block_n, valid_n, mask_ref[...])
    out_ref[...] = _partials_tile(_fwd_partials(
        logits, targets_ref[...], logp_old_ref[...], adv_ref[...], mask,
        sigma))


def _policy_bwd_kernel(hidden_ref, w_ref, targets_ref, logp_old_ref, adv_ref,
                       mask_ref, coef_ref, dh_ref, dw_ref, *, sigma: float,
                       block_n: int, valid_n: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    h = hidden_ref[...]
    w32 = w_ref[...].astype(jnp.float32)
    logits = jnp.dot(h, w_ref[...], preferred_element_type=jnp.float32)
    mask = _zero_mask_pad(i, block_n, valid_n, mask_ref[...])
    d = _block_dlogits(logits, targets_ref[...], logp_old_ref[...],
                       adv_ref[...], mask, sigma, coef_ref[0], coef_ref[1],
                       coef_ref[2])
    dh_ref[...] = jnp.dot(d, w32.T,
                          preferred_element_type=jnp.float32
                          ).astype(dh_ref.dtype)
    # d_w accumulates across the sequential grid (constant index map)
    dw_ref[...] += jnp.dot(h.astype(jnp.float32).T, d,
                           preferred_element_type=jnp.float32)


def _policy_fwd_call(hidden, w, targets, logp_old, advantages, mask,
                     sigma, block_n, interpret):
    n, d = hidden.shape
    v = w.shape[1]
    hidden, *cols = _pad_rows(
        block_n, hidden, *_columns(targets, logp_old, advantages, mask))
    grid = (hidden.shape[0] // block_n,)
    kernel = functools.partial(_policy_fwd_kernel, sigma=sigma,
                               block_n=block_n, valid_n=n)
    partials = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),
            pl.BlockSpec((d, v), lambda i: (0, 0)),
        ] + [_col_spec(block_n)] * 4,
        out_specs=pl.BlockSpec(_TILE, lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((grid[0] * _TILE[0], _TILE[1]),
                                       jnp.float32),
        interpret=interpret,
    )(hidden, w, *cols)
    return _finalize(_sum_tiles(partials))


def _policy_bwd_call(hidden, w, targets, logp_old, advantages, mask,
                     sigma, block_n, interpret, coefs):
    n, d = hidden.shape
    v = w.shape[1]
    hidden_p, *cols = _pad_rows(
        block_n, hidden, *_columns(targets, logp_old, advantages, mask))
    grid = (hidden_p.shape[0] // block_n,)
    kernel = functools.partial(_policy_bwd_kernel, sigma=sigma,
                               block_n=block_n, valid_n=n)
    dh, dw = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),
            pl.BlockSpec((d, v), lambda i: (0, 0)),
        ] + [_col_spec(block_n)] * 4
        + [pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),
            pl.BlockSpec((d, v), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((hidden_p.shape[0], d), hidden.dtype),
            jax.ShapeDtypeStruct((d, v), jnp.float32),
        ],
        interpret=interpret,
    )(hidden_p, w, *cols, coefs)
    return dh[:n], dw.astype(w.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _fused_policy_loss_vjp(hidden, w, targets, logp_old, advantages, mask,
                           sigma, block_n, interpret):
    return _policy_fwd_call(hidden, w, targets, logp_old, advantages, mask,
                            sigma, block_n, interpret)


def _policy_fwd(hidden, w, targets, logp_old, advantages, mask,
                sigma, block_n, interpret):
    out = _policy_fwd_call(hidden, w, targets, logp_old, advantages, mask,
                           sigma, block_n, interpret)
    return out, (hidden, w, targets, logp_old, advantages, mask)


def _policy_bwd(sigma, block_n, interpret, res, cts):
    hidden, w, targets, logp_old, advantages, mask = res
    dh, dw = _policy_bwd_call(hidden, w, targets, logp_old, advantages, mask,
                              sigma, block_n, interpret,
                              _loss_coefs(mask, cts))
    return (dh, dw, _int_zero(targets), jnp.zeros_like(logp_old),
            jnp.zeros_like(advantages), jnp.zeros_like(mask))


_fused_policy_loss_vjp.defvjp(_policy_fwd, _policy_bwd)


def fused_policy_loss(hidden, w, targets, logp_old, advantages, mask,
                      sigma: float, block_n: int = 256,
                      interpret: bool = False):
    """Action head + GIPO/entropy/KL fused over [N, d] hidden states.

    ``hidden @ w`` is computed blockwise inside the kernel; neither forward
    nor backward ever writes an [N, Va] logit/softmax tensor to HBM. Returns
    ``(pg_loss, entropy, kl, metrics)``; differentiable w.r.t. ``hidden``
    and ``w`` (analytic backward kernel, ``d_w`` accumulated across the
    sequential grid). The metrics are explicitly stop-gradiented — the
    custom VJP only propagates the (pg, entropy, kl) cotangents.
    """
    pg, ent, kl, metrics = _fused_policy_loss_vjp(
        hidden, w, targets, logp_old, advantages, mask, sigma, block_n,
        interpret)
    return pg, ent, kl, jax.tree.map(jax.lax.stop_gradient, metrics)
