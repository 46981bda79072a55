"""Kernel dispatch: route hot ops to Pallas on TPU, to jnp twins elsewhere.

Every hot op in the stack has two implementations with identical semantics:
a Pallas kernel (``flash_attention``, ``gipo_loss``, ``fused_policy_loss``)
that lowers to Mosaic on TPU, and a streaming pure-jnp twin that XLA
compiles well on CPU/GPU. This module picks between them at trace time.

Mode resolution (first match wins):

  1. ``set_mode(...)`` / the ``forced(...)`` context manager (tests),
  2. the ``REPRO_KERNELS`` environment variable,
  3. the ``mode`` argument threaded from config (``RLConfig.kernel_dispatch``),
  4. ``"auto"``: Pallas iff ``jax.default_backend() == "tpu"`` — the same
     rule as ``ops._auto_interpret``.

Modes: ``"auto"`` | ``"pallas"`` | ``"jnp"``. Forcing ``"pallas"`` off-TPU
runs the kernels in interpret mode (slow — correctness testing only).

Note the decision is taken at *trace* time: flipping the env var does not
retrigger tracing of an already-jitted train step.
"""
from __future__ import annotations

import contextlib
import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import gipo_loss as _gl
from repro.kernels.flash_attention import flash_attention

_MODE_ENV = "REPRO_KERNELS"
_MODES = ("auto", "pallas", "jnp")
_override: Optional[str] = None


def set_mode(mode: Optional[str]) -> None:
    """Process-wide override; ``None`` restores env/auto resolution."""
    global _override
    if mode is not None and mode not in _MODES:
        raise ValueError(f"mode must be one of {_MODES}, got {mode!r}")
    _override = mode


@contextlib.contextmanager
def forced(mode: str):
    """Temporarily force a dispatch mode (tests)."""
    prev = _override
    set_mode(mode)
    try:
        yield
    finally:
        set_mode(prev)


def resolve_mode(mode: Optional[str] = None) -> str:
    if _override is not None:
        return _override
    env = os.environ.get(_MODE_ENV)
    if env:
        if env not in _MODES:
            raise ValueError(f"{_MODE_ENV} must be one of {_MODES}, "
                             f"got {env!r}")
        return env
    if mode is not None:
        if mode not in _MODES:
            raise ValueError(f"dispatch mode must be one of {_MODES}, "
                             f"got {mode!r}")
        return mode
    return "auto"


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def use_pallas(mode: Optional[str] = None) -> bool:
    m = resolve_mode(mode)
    return m == "pallas" or (m == "auto" and _on_tpu())


def interpret_mode() -> bool:
    """Whether a dispatched ``pallas_call`` should run in interpret mode
    (mirrors ``ops._auto_interpret(None)``)."""
    return not _on_tpu()


def _per_device(fn, *args):
    """Call a Pallas route once per device of the enclosing mesh.

    The TPU compiler cannot partition a Mosaic kernel across devices, so
    under a multi-device mesh (``jax.set_mesh``, as the data-parallel
    trainer traces its step) the call runs inside ``shard_map`` on
    replicated operands: every device computes the whole op, as it does
    the rest of that trainer's replicated-batch step."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1:
        return fn(*args)
    return jax.shard_map(fn, in_specs=P(), out_specs=P(),
                         check_vma=False)(*args)


# ---------------------------------------------------------------------------
# Streaming jnp twins (share the block math with the Pallas kernels)
# ---------------------------------------------------------------------------

def _scan_blocks(body, operands, block_n: int):
    """Pad leading axes to ``block_n``, reshape to [nb, block_n, ...] and
    scan ``body`` over blocks accumulating the 8-column partial sums. The
    body is checkpointed so the backward re-streams blocks instead of
    saving per-block softmax residuals."""
    padded = _gl._pad_rows(block_n, *operands)
    nb = padded[0].shape[0] // block_n
    blocks = tuple(a.reshape((nb, block_n) + a.shape[1:]) for a in padded)

    def step(acc, blk):
        return acc + body(*blk), None

    sums, _ = jax.lax.scan(jax.checkpoint(step),
                           jnp.zeros((_gl.N_COLS,), jnp.float32), blocks)
    return sums


def _jnp_gipo_loss(logits, targets, logp_old, advantages, mask, sigma,
                   block_n):
    def body(lg, tg, lo, ad, mk):
        return _gl._partials_vector(_gl._fwd_partials(
            lg.astype(jnp.float32), tg, lo, ad, mk, sigma,
            sg=jax.lax.stop_gradient))
    sums = _scan_blocks(body, (logits, *_gl._columns(
        targets, logp_old, advantages, mask)), block_n)
    return _gl._finalize(sums)


def _jnp_policy_loss(hidden, w, targets, logp_old, advantages, mask, sigma,
                     block_n):
    def body(h, tg, lo, ad, mk):
        logits = jnp.dot(h, w, preferred_element_type=jnp.float32)
        return _gl._partials_vector(_gl._fwd_partials(
            logits, tg, lo, ad, mk, sigma, sg=jax.lax.stop_gradient))
    sums = _scan_blocks(body, (hidden, *_gl._columns(
        targets, logp_old, advantages, mask)), block_n)
    return _gl._finalize(sums)


# ---------------------------------------------------------------------------
# Dispatched ops
# ---------------------------------------------------------------------------

PALLAS_BLOCK_N = 256    # VMEM-sized token block for the TPU kernels
TWIN_BLOCK_N = 1024     # larger blocks amortize scan overhead on CPU/GPU


def loss_block_n(mode: Optional[str] = None) -> int:
    return PALLAS_BLOCK_N if use_pallas(mode) else TWIN_BLOCK_N


def gipo_loss(logits, targets, logp_old, advantages, mask, *, sigma: float,
              block_n: Optional[int] = None, mode: Optional[str] = None):
    """Logits-level fused GIPO/entropy/KL -> (pg, entropy, kl, metrics)."""
    block_n = block_n or loss_block_n(mode)
    if use_pallas(mode):
        interpret = interpret_mode()
        return _per_device(
            lambda *a: _gl.gipo_head_loss(*a, sigma, block_n, interpret),
            logits, targets, logp_old, advantages, mask)
    return _jnp_gipo_loss(logits, targets, logp_old, advantages, mask,
                          sigma, block_n)


def policy_head_loss(hidden, w, targets, logp_old, advantages, mask, *,
                     sigma: float, block_n: Optional[int] = None,
                     mode: Optional[str] = None):
    """Hidden-level fused action head + GIPO/entropy/KL loss.

    hidden: [N, d]; w: [d, Va]; rest [N]. Both routes stream token blocks
    and never materialize an [N, Va] softmax intermediate — the Pallas path
    via the custom-VJP kernels, the jnp path via a checkpointed block scan.
    """
    block_n = block_n or loss_block_n(mode)
    if use_pallas(mode):
        interpret = interpret_mode()
        return _per_device(
            lambda *a: _gl.fused_policy_loss(*a, sigma, block_n, interpret),
            hidden, w, targets, logp_old, advantages, mask)
    return _jnp_policy_loss(hidden, w, targets, logp_old, advantages, mask,
                            sigma, block_n)


# ---------------------------------------------------------------------------
# Attention: Pallas flash forward + Pallas flash backward (LSE residual)
# ---------------------------------------------------------------------------

def _attn_pallas_ok(q, v, scale) -> bool:
    """The kernels take one head dim for q, k and v and the softmax scale
    ``head_dim ** -0.5``; on a real TPU they want MXU-aligned head dims.
    The jnp twin handles the rest (latent attention's 192-wide q/k heads
    with 128-wide values and a YaRN-scaled scale). Interpret mode (CPU)
    takes any aligned shape."""
    if scale is not None or v.shape[-1] != q.shape[-1]:
        return False
    return interpret_mode() or q.shape[-1] % 128 == 0


def _twin_attention(q, k, v, window, block, unroll=False, scale=None):
    from repro.models.attention import _blockwise_attn
    if scale is None:
        scale = q.shape[-1] ** -0.5
    return _blockwise_attn(q, k, v, scale, window=window, block=block,
                           unroll=unroll).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_with_twin_bwd(q, k, v, window, block_q, block_k, interpret):
    return flash_attention(q, k, v, causal=True, window=window,
                           block_q=block_q, block_k=block_k,
                           interpret=interpret)


def _flash_fwd(q, k, v, window, block_q, block_k, interpret):
    # differentiated forward saves the online-softmax LSE so the backward
    # kernels replay p = exp(s - LSE) instead of recomputing the softmax
    out, lse = flash_attention(q, k, v, causal=True, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret, return_lse=True)
    return out, (q, k, v, out, lse)


def _flash_bwd(window, block_q, block_k, interpret, res, g):
    # Backward = the real Pallas dq and dk/dv kernels over the saved LSE
    # (recompute-free; see kernels/flash_attention.py).
    from repro.kernels.flash_attention import flash_attention_bwd
    q, k, v, out, lse = res
    return flash_attention_bwd(q, k, v, out, lse, g, causal=True,
                               window=window, block_q=block_q,
                               block_k=block_k, interpret=interpret)


_flash_with_twin_bwd.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# SSD scan (Mamba2): Pallas chunked forward + Pallas reverse-sweep backward
# ---------------------------------------------------------------------------

def _ssd_pallas_ok(chunk: int) -> bool:
    """On a real TPU the kernel's dt rows are ``(1, chunk)`` blocks, which
    want whole 128-wide lane tiles. Interpret mode takes any chunk."""
    return interpret_mode() or chunk % 128 == 0


def _twin_ssd(x, dt, A, Bm, Cm, chunk):
    from repro.models.ssm import ssd_chunked
    return ssd_chunked(x, dt, A, Bm, Cm, chunk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _ssd_with_twin_bwd(x, dt, A, Bm, Cm, chunk, interpret):
    from repro.kernels.ssd_scan import ssd_scan as _pallas_ssd
    return _pallas_ssd(x, dt, A, Bm, Cm, chunk=chunk, interpret=interpret)


def _ssd_fwd(x, dt, A, Bm, Cm, chunk, interpret):
    # differentiated forward saves every chunk's ENTERING state so the
    # backward sweep replays each chunk without rerunning the recurrence
    from repro.kernels.ssd_scan import ssd_scan as _pallas_ssd
    y, s_final, s_enter = _pallas_ssd(x, dt, A, Bm, Cm, chunk=chunk,
                                      interpret=interpret,
                                      return_states=True)
    return (y, s_final), (x, dt, A, Bm, Cm, s_enter)


def _ssd_bwd(chunk, interpret, res, g):
    # Backward = the real Pallas reverse-chunk kernel carrying the state
    # cotangent in scratch (see kernels/ssd_scan.py).
    from repro.kernels.ssd_scan import ssd_scan_bwd
    x, dt, A, Bm, Cm, s_enter = res
    dy, ds_final = g
    return ssd_scan_bwd(x, dt, A, Bm, Cm, s_enter, dy, ds_final,
                        chunk=chunk, interpret=interpret)


_ssd_with_twin_bwd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd_scan(x, dt, A, Bm, Cm, *, chunk: int = 128,
             mode: Optional[str] = None):
    """Chunked Mamba2 SSD scan. x: [B,T,H,P]; dt: [B,T,H] (f32,
    post-softplus); A: [H] (negative); Bm/Cm: [B,T,N] (single group).
    Returns (y [B,T,H,P] f32, final_state [B,H,P,N] f32).

    Routes to the Pallas kernel when enabled and shape-eligible (the
    kernel wants T an exact multiple of ``chunk``, and on TPU a chunk of
    whole lane tiles; ragged lengths and decode-time carried state stay
    on the jnp path). Backward on the
    Pallas route is the reverse-chunk Pallas kernel replaying saved
    entering states (``ssd_scan_bwd``); the jnp path uses its own VJP.
    """
    t = x.shape[1]
    if (use_pallas(mode) and t >= chunk and t % chunk == 0
            and _ssd_pallas_ok(chunk)):
        interpret = interpret_mode()
        return _per_device(
            lambda *a: _ssd_with_twin_bwd(*a, chunk, interpret),
            x, dt, A, Bm, Cm)
    return _twin_ssd(x, dt, A, Bm, Cm, chunk)


# ---------------------------------------------------------------------------
# Attention routing
# ---------------------------------------------------------------------------

def _flash(q, k, v, window, block):
    interpret = interpret_mode()
    return _per_device(
        lambda *a: _flash_with_twin_bwd(*a, window, block, block, interpret),
        q, k, v)


def attention(q, k, v, *, window: Optional[int] = None, block: int = 128,
              unroll: bool = False, scale: Optional[float] = None,
              mode: Optional[str] = None):
    """Causal (optionally sliding-window) blockwise attention on projected
    q/k/v. q/k: [B,T,H,D], [B,S,KV,D]; v: [B,S,KV,Dv] -> [B,T,H,Dv] in
    q.dtype. ``scale`` defaults to ``D ** -0.5``.

    Routes to the Pallas flash kernel when enabled and shape-eligible;
    its backward is the pair of Pallas dq and dk/dv kernels over the
    saved online-softmax LSE (recompute-free — no O(T²) score tensor
    either way). Otherwise the jnp twin runs both ways.
    """
    if use_pallas(mode) and _attn_pallas_ok(q, v, scale):
        return _flash(q, k, v, window, block)
    return _twin_attention(q, k, v, window, block, unroll, scale)


# ---------------------------------------------------------------------------
# Decode-path routing: single-token decode + the dense small-T fallback
# ---------------------------------------------------------------------------

def _twin_dense(q, k, v, window, scale=None):
    """Dense causal attention — the exact math of the historical inline
    small-T path in ``models.attention`` (f32 scores + additive causal
    mask + softmax cast to q.dtype before the value combine)."""
    from repro.models.attention import (_gqa_combine, _gqa_scores,
                                        causal_mask)
    t = q.shape[1]
    if scale is None:
        scale = q.shape[-1] ** -0.5
    scores = _gqa_scores(q, k) * scale
    scores = scores + causal_mask(t, window)[None, None]
    weights = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return _gqa_combine(weights, v)


def dense_attention(q, k, v, *, window: Optional[int] = None,
                    block: int = 128, scale: Optional[float] = None,
                    mode: Optional[str] = None):
    """Dense small-T causal attention (T == S, no KV cache): the fallback
    the blockwise path skips when the whole sequence fits one block.
    q/k: [B,T,H,D], [B,T,KV,D]; v: [B,T,KV,Dv] -> [B,T,H,Dv] in q.dtype;
    ``scale`` defaults to ``D ** -0.5``.

    Pallas route: the flash kernel (it pads T up to one block tile
    internally, so a 17-token prompt still runs as a single MXU tile);
    differentiable through the twin-VJP wrapper like ``attention``.
    """
    if use_pallas(mode) and _attn_pallas_ok(q, v, scale):
        return _flash(q, k, v, window, block)
    return _twin_dense(q, k, v, window, scale)


def _twin_decode(q, k, v, valid, scale=None):
    """Single-token decode over a (possibly ring-layout) KV cache — the
    exact math of the historical inline path in ``attention_decode``."""
    from repro.models.attention import NEG_INF, _gqa_combine, _gqa_scores
    if scale is None:
        scale = q.shape[-1] ** -0.5
    scores = _gqa_scores(q, k) * scale                    # [B,H,1,S]
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    weights = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return _gqa_combine(weights, v)


def decode_attention(q, k, v, valid, *, scale: Optional[float] = None,
                     mode: Optional[str] = None):
    """Single-token attention decode. q: [B,1,H,D]; k: [B,S,KV,D]; v:
    [B,S,KV,Dv]; ``scale`` defaults to ``D ** -0.5``; valid: [B,S] bool
    (cache slots this token may attend to — empty ring slots,
    out-of-window and future positions already excluded)
    -> [B,1,H,Dv] in q.dtype.

    Validity is data-dependent (ring caches overwrite slots out of
    order), so the Pallas route carries it as an additive bias instead of
    deriving a mask from grid positions. Inference-only — no VJP wrapper.
    """
    if use_pallas(mode) and _attn_pallas_ok(q, v, scale):
        from repro.kernels.decode_attention import (
            decode_attention as _pallas_decode)
        from repro.models.attention import NEG_INF
        bias = jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)
        interpret = interpret_mode()
        return _per_device(
            lambda *a: _pallas_decode(*a, interpret=interpret),
            q, k, v, bias)
    return _twin_decode(q, k, v, valid, scale)


# ---------------------------------------------------------------------------
# Grouped matmul (the expert layer): Pallas gmm / tgmm under a custom VJP
# ---------------------------------------------------------------------------

def _gmm_pallas_ok(lhs, rhs) -> bool:
    """On a real TPU the kernels tile the contracted and output widths in
    whole 128-lane tiles. Interpret mode takes any shape."""
    return interpret_mode() or (lhs.shape[1] % 128 == 0
                                and rhs.shape[2] % 128 == 0)


def grouped_matmul(lhs, rhs, sizes, *, mode: Optional[str] = None):
    """``lhs[rows of i] @ rhs[i]`` for the row-sorted groups of ``sizes``
    (the rows past ``sum(sizes)`` read zeros). lhs: [m, k]; rhs: [g, k, n];
    sizes: [g] int32 -> [m, n] in lhs.dtype.

    Pallas route: ``kernels/grouped_matmul.py``, whose grid visits only
    the row tiles that hold a group's rows, with ``gmm`` for the input
    gradient and ``tgmm`` for the weight gradient. The twin is
    ``lax.ragged_dot``, differentiated by JAX.
    """
    if use_pallas(mode) and _gmm_pallas_ok(lhs, rhs):
        from repro.kernels.grouped_matmul import grouped_matmul as _pallas
        interpret = interpret_mode()
        return _per_device(lambda *a: _pallas(*a, interpret), lhs, rhs,
                           sizes)
    return jax.lax.ragged_dot(lhs, rhs, sizes,
                              preferred_element_type=jnp.float32
                              ).astype(lhs.dtype)
