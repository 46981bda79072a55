"""Dispatch-layer parity tests: the Pallas kernels (interpret mode) and
their streaming jnp twins must agree with the dense references on forward
values AND gradients, across dense/GQA shapes and ragged
``N % block_n != 0`` edges. Also covers mode resolution and the
fused-vs-reference trainer path (loss + parameter grads)."""
import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import dispatch, ref
from repro.kernels.gipo_loss import fused_policy_loss, gipo_head_loss

RNG = np.random.default_rng(11)
SIGMA = 0.2
TOL = dict(rtol=2e-4, atol=2e-5)


def _tok_data(n, v):
    return (jnp.asarray(RNG.integers(0, v, n), jnp.int32),
            jnp.asarray(RNG.standard_normal(n) * 0.3, jnp.float32),
            jnp.asarray(RNG.standard_normal(n), jnp.float32),
            jnp.asarray((RNG.random(n) > 0.15).astype(np.float32)))


def _combine(out):
    pg, ent, kl, _ = out
    return pg + 0.1 * kl - 0.01 * ent


def _close(a, b, **kw):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               **(kw or TOL))


# ---------------------------------------------------------------------------
# mode resolution
# ---------------------------------------------------------------------------

def test_mode_resolution(monkeypatch):
    monkeypatch.delenv("REPRO_KERNELS", raising=False)
    assert dispatch.resolve_mode() == "auto"
    assert dispatch.resolve_mode("jnp") == "jnp"
    with pytest.raises(ValueError):
        dispatch.resolve_mode("palas")      # config typo must not silently
    #                                         fall back to auto routing
    monkeypatch.setenv("REPRO_KERNELS", "pallas")
    assert dispatch.resolve_mode() == "pallas"
    assert dispatch.resolve_mode("jnp") == "pallas"       # env beats config
    with dispatch.forced("jnp"):                          # forced beats env
        assert dispatch.resolve_mode() == "jnp"
        assert not dispatch.use_pallas()
    assert dispatch.resolve_mode() == "pallas"
    monkeypatch.setenv("REPRO_KERNELS", "bogus")
    with pytest.raises(ValueError):
        dispatch.resolve_mode()
    with pytest.raises(ValueError):
        dispatch.set_mode("bogus")


def test_auto_mode_off_tpu_uses_jnp_twin(monkeypatch):
    monkeypatch.delenv("REPRO_KERNELS", raising=False)
    # conftest pins JAX_PLATFORMS=cpu, so auto must route to the twins
    assert not dispatch.use_pallas()
    assert dispatch.interpret_mode()


# ---------------------------------------------------------------------------
# fused GIPO loss: logits level
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,v,block_n", [
    (64, 32, 32),            # exact multiple
    (300, 64, 128),          # ragged N % block_n
    (257, 48, 128),          # ragged by one
    (100, 256, 256),         # single partial block, full action vocab
])
@pytest.mark.parametrize("impl", ["pallas", "jnp"])
def test_gipo_loss_parity(n, v, block_n, impl):
    logits = jnp.asarray(RNG.standard_normal((n, v)) * 2, jnp.float32)
    targets, logp_old, adv, mask = _tok_data(n, v)

    def fused(lg):
        if impl == "pallas":
            return gipo_head_loss(lg, targets, logp_old, adv, mask,
                                  SIGMA, block_n, True)
        return dispatch._jnp_gipo_loss(lg, targets, logp_old, adv, mask,
                                       SIGMA, block_n)

    def reference(lg):
        # identity head weight makes the hidden-level oracle a logits oracle
        return ref.reference_policy_loss(
            lg, jnp.eye(lg.shape[1], dtype=jnp.float32), targets, logp_old,
            adv, mask, SIGMA)

    got, exp = fused(logits), reference(logits)
    for g, e in zip(got[:3], exp[:3]):
        _close(g, e)
    for k in exp[3]:
        _close(got[3][k], exp[3][k])
    g_f = jax.grad(lambda lg: _combine(fused(lg)))(logits)
    g_r = jax.grad(lambda lg: _combine(reference(lg)))(logits)
    _close(g_f, g_r, rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# fused policy loss: hidden level (action head inside the kernel)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,d,v,block_n", [
    (128, 32, 32, 64),
    (300, 64, 48, 128),      # ragged
    (65, 16, 256, 64),       # ragged by one, full action vocab
])
@pytest.mark.parametrize("impl", ["pallas", "jnp"])
def test_policy_head_loss_parity(n, d, v, block_n, impl):
    hidden = jnp.asarray(RNG.standard_normal((n, d)), jnp.float32)
    w = jnp.asarray(RNG.standard_normal((d, v)) * 0.2, jnp.float32)
    targets, logp_old, adv, mask = _tok_data(n, v)

    def fused(h, w_):
        if impl == "pallas":
            return fused_policy_loss(h, w_, targets, logp_old, adv, mask,
                                     SIGMA, block_n, True)
        return dispatch._jnp_policy_loss(h, w_, targets, logp_old, adv,
                                         mask, SIGMA, block_n)

    def reference(h, w_):
        return ref.reference_policy_loss(h, w_, targets, logp_old, adv,
                                         mask, SIGMA)

    got, exp = fused(hidden, w), reference(hidden, w)
    for g, e in zip(got[:3], exp[:3]):
        _close(g, e)
    dh_f, dw_f = jax.grad(lambda h, w_: _combine(fused(h, w_)),
                          argnums=(0, 1))(hidden, w)
    dh_r, dw_r = jax.grad(lambda h, w_: _combine(reference(h, w_)),
                          argnums=(0, 1))(hidden, w)
    _close(dh_f, dh_r, rtol=5e-4, atol=5e-5)
    _close(dw_f, dw_r, rtol=5e-4, atol=5e-5)


def test_policy_head_loss_bf16_hidden():
    n, d, v = 256, 32, 64
    hidden = jnp.asarray(RNG.standard_normal((n, d)), jnp.bfloat16)
    w = jnp.asarray(RNG.standard_normal((d, v)) * 0.2, jnp.bfloat16)
    targets, logp_old, adv, mask = _tok_data(n, v)
    pg_p, *_ = fused_policy_loss(hidden, w, targets, logp_old, adv, mask,
                                 SIGMA, 128, True)
    pg_r, *_ = ref.reference_policy_loss(hidden, w, targets, logp_old, adv,
                                         mask, SIGMA)
    assert float(pg_p) == pytest.approx(float(pg_r), rel=5e-2, abs=5e-2)


# ---------------------------------------------------------------------------
# attention routing
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,t,s,h,kv,d", [
    (1, 128, 128, 4, 4, 64),     # MHA square
    (2, 128, 128, 4, 1, 64),     # MQA
    (2, 64, 256, 8, 2, 64),      # GQA, cross lengths
    (1, 100, 100, 4, 2, 64),     # ragged vs block (padding path)
])
@pytest.mark.parametrize("window", [None, 64])
def test_attention_dispatch_parity(b, t, s, h, kv, d, window):
    q = jnp.asarray(RNG.standard_normal((b, t, h, d)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((b, s, kv, d)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((b, s, kv, d)), jnp.float32)
    with dispatch.forced("pallas"):
        out_p = dispatch.attention(q, k, v, window=window, block=64)
    with dispatch.forced("jnp"):
        out_j = dispatch.attention(q, k, v, window=window, block=64)
    exp = ref.reference_attention(q, k, v, window=window)
    _close(out_p, exp, rtol=2e-5, atol=2e-5)
    _close(out_j, exp, rtol=2e-5, atol=2e-5)

    def loss(mode):
        def f(q_, k_, v_):
            with dispatch.forced(mode):
                out = dispatch.attention(q_, k_, v_, window=window, block=64)
            return jnp.sum(out * out)
        return f
    g_p = jax.grad(loss("pallas"), argnums=(0, 1, 2))(q, k, v)
    g_j = jax.grad(loss("jnp"), argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_p, g_j):
        _close(a, b_, rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# real Pallas backward kernels (ISSUE 9): exact grad parity vs the jnp
# twins at head_dim 64 AND 128 (interpret mode; the dq and dk/dv kernels
# replay the saved LSE — any drift in the backward math shows up here)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("window", [None, 32])
def test_flash_backward_kernel_grad_parity(d, window):
    b, t, s, h, kv = 1, 128, 128, 4, 2
    q = jnp.asarray(RNG.standard_normal((b, t, h, d)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((b, s, kv, d)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((b, s, kv, d)), jnp.float32)

    def loss(mode):
        def f(q_, k_, v_):
            with dispatch.forced(mode):
                out = dispatch.attention(q_, k_, v_, window=window,
                                         block=64)
            return jnp.sum(out * out)
        return f

    g_p = jax.grad(loss("pallas"), argnums=(0, 1, 2))(q, k, v)
    g_j = jax.grad(loss("jnp"), argnums=(0, 1, 2))(q, k, v)
    for got, exp in zip(g_p, g_j):
        _close(got, exp, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("d", [64, 128])
def test_flash_backward_ragged_rows_grad_parity(d):
    """Padded q rows (T % block != 0) must contribute exactly zero grad:
    the backward kernels pad the LSE with a sentinel so exp(s - LSE)
    vanishes on dead rows."""
    b, t, s, h, kv = 1, 50, 50, 4, 4
    q = jnp.asarray(RNG.standard_normal((b, t, h, d)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((b, s, kv, d)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((b, s, kv, d)), jnp.float32)

    def loss(mode):
        def f(q_, k_, v_):
            with dispatch.forced(mode):
                out = dispatch.attention(q_, k_, v_, block=64)
            return jnp.sum(out * out)
        return f

    g_p = jax.grad(loss("pallas"), argnums=(0, 1, 2))(q, k, v)
    g_j = jax.grad(loss("jnp"), argnums=(0, 1, 2))(q, k, v)
    for got, exp in zip(g_p, g_j):
        _close(got, exp, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("p", [64, 128])
def test_ssd_backward_kernel_grad_parity(p):
    """The reverse-chunk SSD kernel: grads for every input (x, dt, A, B,
    C) through BOTH outputs — a nonzero final-state cotangent seeds the
    reverse state sweep."""
    x, dt, a, bm, cm = _ssd_data(b=1, t=64, h=2, p=p, n=4, seed=9)

    def loss(mode):
        def f(x_, dt_, a_, b_, c_):
            with dispatch.forced(mode):
                y_, s_ = dispatch.ssd_scan(x_, dt_, a_, b_, c_, chunk=32)
            return jnp.sum(y_ * y_) + jnp.sum(jnp.sin(s_))
        return f

    g_p = jax.grad(loss("pallas"), argnums=(0, 1, 2, 3, 4))(x, dt, a, bm,
                                                            cm)
    g_j = jax.grad(loss("jnp"), argnums=(0, 1, 2, 3, 4))(x, dt, a, bm, cm)
    for got, exp in zip(g_p, g_j):
        _close(got, exp, rtol=2e-4, atol=2e-3)


# ---------------------------------------------------------------------------
# decode-path routing: single-token decode + the dense small-T fallback
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,h,kv,d", [
    (1, 128, 4, 4, 64),          # MHA, cache == block
    (2, 200, 8, 2, 64),          # GQA, 200 slots in one block
    (3, 33, 4, 1, 64),           # MQA, tiny cache
    (4, 20, 8, 8, 64),           # the serve cell's MHA call scaled down
    (3, 20, 8, 4, 64),           # GQA, group 2
    (3, 20, 8, 2, 64),           # GQA, group 4
    (2, 20, 12, 1, 64),          # MQA, 12 members on one KV head
    (2, 1100, 8, 4, 64),         # cache streamed in blocks, ragged last
    (5, 400, 4, 4, 64),          # batch not a multiple of the rows a step
])
def test_decode_attention_dispatch_parity(b, s, h, kv, d):
    """The Pallas decode kernel matches the jnp twin bit-for-shape on
    data-dependent validity masks (ring gaps, short sequences)."""
    q = jnp.asarray(RNG.standard_normal((b, 1, h, d)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((b, s, kv, d)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((b, s, kv, d)), jnp.float32)
    # ring-shaped validity: random holes, but at least one live slot/row
    valid = jnp.asarray(RNG.random((b, s)) > 0.4)
    valid = valid.at[:, 0].set(True)
    with dispatch.forced("pallas"):
        out_p = dispatch.decode_attention(q, k, v, valid)
    with dispatch.forced("jnp"):
        out_j = dispatch.decode_attention(q, k, v, valid)
    _close(out_p, out_j, rtol=2e-5, atol=2e-5)


def test_decode_attention_masks_invalid_slots():
    """Fully-masked-but-one: the output must equal attending the single
    live slot exactly (masking is NEG_INF-additive, not a renormalize)."""
    b, s, h, d = 2, 64, 4, 64
    q = jnp.asarray(RNG.standard_normal((b, 1, h, d)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((b, s, h, d)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((b, s, h, d)), jnp.float32)
    valid = jnp.zeros((b, s), bool).at[:, 7].set(True)
    for mode in ("pallas", "jnp"):
        with dispatch.forced(mode):
            out = dispatch.decode_attention(q, k, v, valid)
        _close(out[:, 0], v[:, 7], rtol=2e-5, atol=2e-5)


def test_decode_attention_masks_invalid_slots_last_block():
    """The only live slot lies in the last, ragged block of a streamed
    cache: every earlier block is masked whole, and the online softmax
    still ends on that slot alone."""
    from repro.kernels.decode_attention import _tiles
    b, s, h, d = 2, 1100, 4, 64
    block_s = _tiles(b, s, h, h, d, 4)[1]
    assert block_s < s and s % block_s
    q = jnp.asarray(RNG.standard_normal((b, 1, h, d)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((b, s, h, d)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((b, s, h, d)), jnp.float32)
    valid = jnp.zeros((b, s), bool).at[:, s - 1].set(True)
    for mode in ("pallas", "jnp"):
        with dispatch.forced(mode):
            out = dispatch.decode_attention(q, k, v, valid)
        _close(out[:, 0], v[:, s - 1], rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("b,s,h,kv,d,streamed,ragged_rows", [
    (2, 1100, 8, 4, 64, True, False),
    (5, 400, 4, 4, 64, False, True),
])
def test_decode_attention_tiles(b, s, h, kv, d, streamed, ragged_rows):
    """The parity cases above reach the tilings they are there for: a
    cache split into blocks with a ragged last one, and a batch whose
    last block of rows is short (float32 caches, as the tests run)."""
    from repro.kernels.decode_attention import _tiles
    rows, block_s = _tiles(b, s, h, kv, d, 4)
    assert (block_s < s and s % block_s != 0) == streamed
    assert (b % rows != 0) == ragged_rows


def test_decode_attention_tiles_serve_call():
    """The serve cell's call (batch 32, 20 cache slots, 32 bf16 heads of
    128) takes the whole cache of several rows a step."""
    from repro.kernels.decode_attention import _tiles
    rows, block_s = _tiles(32, 20, 32, 32, 128, 2)
    assert block_s == 20 and 4 <= rows <= 8


@pytest.mark.parametrize("t,h,kv", [(16, 4, 4), (100, 8, 2)])
@pytest.mark.parametrize("window", [None, 8])
def test_dense_attention_dispatch_parity(t, h, kv, window):
    """Dense small-T fallback: both routes match the dense reference."""
    b, d = 2, 64
    q = jnp.asarray(RNG.standard_normal((b, t, h, d)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((b, t, kv, d)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((b, t, kv, d)), jnp.float32)
    exp = ref.reference_attention(q, k, v, window=window)
    for mode in ("pallas", "jnp"):
        with dispatch.forced(mode):
            out = dispatch.dense_attention(q, k, v, window=window)
        _close(out, exp, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# short rows packed several to a flash tile under a block-diagonal mask
# ---------------------------------------------------------------------------

def _pallas_dense_grads(q, k, v, window):
    def loss(q_, k_, v_):
        with dispatch.forced("pallas"):
            out = dispatch.dense_attention(q_, k_, v_, window=window)
        return jnp.sum(out * out), out
    (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                         has_aux=True)(q, k, v)
    return (out, *grads)


@pytest.mark.parametrize("b,t,h,kv,window,rows", [
    (12, 20, 16, 8, None, 6),    # the train cells' T, GQA 16/8
    (7, 20, 4, 4, 8, 6),         # batch not a multiple of the rows a tile
    (2, 20, 16, 8, 8, 2),        # batch under the rows a tile
    (9, 13, 4, 4, None, 9),      # the serve prefill's T, MHA
    (2, 13, 4, 2, 8, 2),
    (2, 100, 4, 2, None, 1),     # over half a tile: unpacked, bit for bit
])
def test_flash_packed_rows_parity(monkeypatch, b, t, h, kv, window, rows):
    """Forward and (dq, dk, dv) of the flash kernels on packed rows match
    the dense reference and its gradient; where one row fills a tile the
    wrapper traces the unpacked route op for op."""
    fa = importlib.import_module("repro.kernels.flash_attention")
    assert fa.rows_per_tile(b, t, t, 128) == rows
    d = 64
    q = jnp.asarray(RNG.standard_normal((b, t, h, d)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((b, t, kv, d)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((b, t, kv, d)), jnp.float32)
    got = _pallas_dense_grads(q, k, v, window)

    def ref_loss(q_, k_, v_):
        out = ref.reference_attention(q_, k_, v_, window=window)
        return jnp.sum(out * out), out
    (_, out), grads = jax.value_and_grad(ref_loss, argnums=(0, 1, 2),
                                         has_aux=True)(q, k, v)
    _close(got[0], out, rtol=2e-5, atol=2e-5)
    for a, e in zip(got[1:], grads):
        _close(a, e, rtol=2e-4, atol=2e-4)
    if rows == 1:
        packed = jax.make_jaxpr(_pallas_dense_grads, static_argnums=3)(
            q, k, v, window)
        monkeypatch.setattr(fa, "rows_per_tile", lambda *_: 1)
        unpacked = _pallas_dense_grads(q, k, v, window)
        assert str(jax.make_jaxpr(_pallas_dense_grads, static_argnums=3)(
            q, k, v, window)) == str(packed)
        for a, e in zip(got, unpacked):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(e))


@pytest.mark.parametrize("t,rows", [(20, 6), (100, None)])
def test_flash_pack_event(monkeypatch, t, rows):
    """Under ``REPRO_TRACE`` the forward records ``kernels.flash_pack``
    when it packs rows, with the rows a tile and their fill; one row a
    tile records nothing."""
    from repro.runtime import telemetry
    monkeypatch.setattr(telemetry, "RECORDING", True)
    telemetry.reset()
    q = jnp.zeros((6, t, 2, 64), jnp.float32)
    with dispatch.forced("pallas"):
        jax.eval_shape(dispatch.dense_attention, q, q, q)
    got = [e["args"] for e in telemetry.drain()
           if e["name"] == "kernels.flash_pack"]
    if rows is None:
        assert got == []
    else:
        assert got == [{"t": t, "rows_per_tile": rows,
                        "tile_fill": rows * t / 128}]


def test_attention_decode_routes_through_dispatch():
    """models.attention.attention_decode answers identically whichever
    side dispatch routes to (the decode path is now dispatched)."""
    from repro.models.attention import (attention_decode, attention_init,
                                        attention_prefill)
    key = jax.random.PRNGKey(0)
    params = attention_init(key, 64, 4, 2, 64, jnp.float32)
    x = jnp.asarray(RNG.standard_normal((2, 9, 64)), jnp.float32)
    outs = {}
    for mode in ("pallas", "jnp"):
        with dispatch.forced(mode):
            _, cache = attention_prefill(params, x, rope_theta=1e4,
                                         cache_len=16)
            step = jnp.ones((2, 1, 64), jnp.float32) * 0.1
            outs[mode], _ = attention_decode(params, step, cache,
                                             rope_theta=1e4)
    _close(outs["pallas"], outs["jnp"], rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# ssd_scan routing (Mamba2): both sides match the stepwise oracle, fwd + bwd
# ---------------------------------------------------------------------------

def _ssd_data(b=2, t=64, h=3, p=8, n=4, seed=5):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((b, t, h, p)), jnp.float32),
            jnp.asarray(rng.uniform(0.01, 0.1, (b, t, h)), jnp.float32),
            -jnp.asarray(rng.uniform(0.5, 1.5, (h,)), jnp.float32),
            jnp.asarray(rng.standard_normal((b, t, n)), jnp.float32),
            jnp.asarray(rng.standard_normal((b, t, n)), jnp.float32))


@pytest.mark.parametrize("mode", ["pallas", "jnp"])
def test_ssd_scan_dispatch_parity(mode):
    x, dt, a, bm, cm = _ssd_data()
    with dispatch.forced(mode):
        y, s = dispatch.ssd_scan(x, dt, a, bm, cm, chunk=32)
    y_ref, s_ref = ref.reference_ssd(x, dt, a, bm, cm)
    _close(y, y_ref, rtol=2e-4, atol=2e-4)
    _close(s, s_ref, rtol=2e-4, atol=2e-4)

    def loss(m):
        def f(x_, dt_, b_, c_):
            with dispatch.forced(m):
                y_, s_ = dispatch.ssd_scan(x_, dt_, a, b_, c_, chunk=32)
            return jnp.sum(y_ * y_) + jnp.sum(s_)
        return f
    g_m = jax.grad(loss(mode), argnums=(0, 1, 2, 3))(x, dt, bm, cm)
    g_j = jax.grad(loss("jnp"), argnums=(0, 1, 2, 3))(x, dt, bm, cm)
    for got, exp in zip(g_m, g_j):
        _close(got, exp, rtol=2e-4, atol=2e-4)


def test_ssd_scan_ragged_length_falls_back_to_twin():
    """T not divisible by chunk is not kernel-eligible: the twin must serve
    it even when Pallas is forced (same eligibility idea as attention)."""
    x, dt, a, bm, cm = _ssd_data(t=24)
    with dispatch.forced("pallas"):
        y, s = dispatch.ssd_scan(x, dt, a, bm, cm, chunk=32)
    y_ref, s_ref = ref.reference_ssd(x, dt, a, bm, cm)
    _close(y, y_ref, rtol=2e-4, atol=2e-4)
    _close(s, s_ref, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("mode", ["pallas", "jnp"])
def test_ssm_forward_routes_through_dispatch(mode):
    """The model layer produces identical outputs on both dispatch sides."""
    from repro.configs.base import SSMConfig
    from repro.models.ssm import ssm_forward, ssm_init
    cfg = SSMConfig(state_dim=8, head_dim=4, expand=2, chunk=16)
    d_model = 16
    params = ssm_init(jax.random.PRNGKey(0), d_model, cfg, jnp.float32)
    u = jnp.asarray(RNG.standard_normal((2, 32, d_model)), jnp.float32)
    with dispatch.forced(mode):
        out = ssm_forward(params, u, d_model, cfg)
    with dispatch.forced("jnp"):
        exp = ssm_forward(params, u, d_model, cfg)
    _close(out, exp, rtol=5e-4, atol=5e-4)


# ---------------------------------------------------------------------------
# seq-train path (launch/steps.py): fused loss vs reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["pallas", "jnp"])
def test_seq_fused_loss_matches_reference(mode):
    from repro.configs import get_config, reduced
    from repro.configs.base import RLConfig
    from repro.core.advnorm import init_adv_state
    from repro.launch.steps import seq_loss_fn
    from repro.models.policy import init_policy_params

    cfg = reduced(get_config("deepseek-7b"), layers=2, d_model=64)
    cfg = dataclasses.replace(cfg, param_dtype="float32",
                              compute_dtype="float32")
    params = init_policy_params(cfg, jax.random.PRNGKey(0))
    b, s = 2, 16
    rng = np.random.default_rng(3)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)),
                              jnp.int32),
        "behavior_logp": jnp.asarray(rng.standard_normal((b, s)) * 0.3,
                                     jnp.float32),
        "rewards": jnp.asarray(rng.standard_normal((b, s - 1)), jnp.float32),
        "dones": jnp.zeros((b, s - 1), jnp.float32),
        "mask": jnp.ones((b, s - 1), jnp.float32),
    }
    adv_state = init_adv_state()
    rl_ref = RLConfig(grad_accum=1)
    rl_fused = dataclasses.replace(rl_ref, fused_loss=True)

    l_ref, (m_ref, _) = seq_loss_fn(params, batch, adv_state, cfg, rl_ref,
                                    remat=False)
    g_ref = jax.grad(lambda p: seq_loss_fn(p, batch, adv_state, cfg,
                                           rl_ref, remat=False)[0])(params)
    with dispatch.forced(mode):
        l_f, (m_f, _) = seq_loss_fn(params, batch, adv_state, cfg,
                                    rl_fused, remat=False)
        g_f = jax.grad(lambda p: seq_loss_fn(p, batch, adv_state, cfg,
                                             rl_fused, remat=False)[0]
                       )(params)
    _close(l_f, l_ref, rtol=1e-5, atol=1e-6)
    for key in ("pg_loss", "value_loss", "kl"):
        _close(m_f[key], m_ref[key], rtol=1e-4, atol=1e-5)
    flat_ref = jax.tree_util.tree_leaves_with_path(g_ref)
    flat_f = dict(jax.tree_util.tree_leaves_with_path(g_f))
    assert len(flat_ref) == len(flat_f)
    for path, leaf in flat_ref:
        scale = float(jnp.max(jnp.abs(leaf))) + 1e-8
        diff = float(jnp.max(jnp.abs(leaf - flat_f[path])))
        assert diff <= 1e-5 + 1e-4 * scale, (path, diff, scale)


# ---------------------------------------------------------------------------
# trainer-path parity: fused loss vs reference (loss AND parameter grads)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["pallas", "jnp"])
def test_fused_train_loss_matches_reference(mode):
    from repro.configs import get_config, reduced
    from repro.configs.base import RLConfig
    from repro.core.train_step import init_train_state, loss_fn
    from repro.data.trajectory import dummy_batch

    cfg = reduced(get_config("deepseek-7b"), layers=2, d_model=64)
    cfg = dataclasses.replace(cfg, param_dtype="float32",
                              compute_dtype="float32")
    state = init_train_state(cfg, jax.random.PRNGKey(0))
    batch = dummy_batch(4, 3, 6, cfg.action_dim, cfg.vocab_size,
                        cfg.action_vocab_size)
    rl_ref = RLConfig(grad_accum=1, entropy_coef=0.01)
    rl_fused = dataclasses.replace(rl_ref, fused_loss=True)

    def total(p, rl):
        return loss_fn(p, batch, state.adv_norm, cfg, rl)

    l_ref, (m_ref, _) = total(state.params, rl_ref)
    g_ref = jax.grad(lambda p: total(p, rl_ref)[0])(state.params)
    with dispatch.forced(mode):
        l_f, (m_f, _) = total(state.params, rl_fused)
        g_f = jax.grad(lambda p: total(p, rl_fused)[0])(state.params)

    _close(l_f, l_ref, rtol=1e-5, atol=1e-6)
    for key in ("pg_loss", "value_loss", "kl", "entropy", "ratio_mean",
                "omega_mean", "stale_frac"):
        _close(m_f[key], m_ref[key], rtol=1e-4, atol=1e-5)
    flat_ref = jax.tree_util.tree_leaves_with_path(g_ref)
    flat_f = dict(jax.tree_util.tree_leaves_with_path(g_f))
    assert len(flat_ref) == len(flat_f)
    for path, leaf in flat_ref:
        scale = float(jnp.max(jnp.abs(leaf))) + 1e-8
        diff = float(jnp.max(jnp.abs(leaf - flat_f[path])))
        assert diff <= 1e-5 + 1e-4 * scale, (path, diff, scale)
