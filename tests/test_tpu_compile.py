"""Compile rehearsals for a described TPU v5e: every Pallas kernel family
of the main path, at the widths ``chip_smoke.py`` runs, goes through the
chip's own compiler — tiling, VMEM and lowering refusals show up here, on
a machine with no chip. Nothing runs; these say nothing about results or
times.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU runtime,
and every test-collecting worker imports this file. Keep all such tests
in this one file.
"""
import importlib
import importlib.util
import os
import pathlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import dispatch
from repro.kernels import gipo_loss as gl
from repro.kernels.decode_attention import decode_attention

fa = importlib.import_module("repro.kernels.flash_attention")
gm = importlib.import_module("repro.kernels.grouped_matmul")
ssd = importlib.import_module("repro.kernels.ssd_scan")

DEEPSEEK = get_config("deepseek-7b")
H, D = DEEPSEEK.num_heads, DEEPSEEK.head_dim          # 32 x 128
HBM_BYTES = 16_909_336_064    # memory_stats()["bytes_limit"], one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def shape(one_chip):
    def make(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    return make


def _compile(fn, *args):
    """Compile for the described chip; assert a Pallas kernel is in it."""
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_flash_attention_forward(shape):
    q = shape((2, 1024, H, D))
    _compile(lambda q, k, v: fa.flash_attention(q, k, v), q, q, q)


def test_flash_attention_grad(shape):
    def loss(q, k, v):
        out = dispatch._flash_with_twin_bwd(q, k, v, None, 128, 128, False)
        return out.astype(jnp.float32).sum()
    q = shape((2, 1024, H, D))
    _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), q, q, q)


def test_decode_attention(shape):
    _compile(lambda q, k, v, b: decode_attention(q, k, v, b),
             shape((8, 1, H, D)), shape((8, 300, H, D)),
             shape((8, 300, H, D)), shape((8, 300), jnp.float32))
    # the serve cell's call: the kernel reads the cache as it is stored,
    # so no bf16 array is made around it but views of its operands
    serve = _compile(decode_attention, shape((32, 1, H, D)),
                     shape((32, 20, H, D)), shape((32, 20, H, D)),
                     shape((32, 20), jnp.float32))
    made = [line for line in serve.as_text().splitlines()
            if re.search(r"= bf16\[", line)
            and not re.search(r" (parameter|bitcast|custom-call)\(", line)]
    assert not made, made
    # GQA at internlm2-1.8b's widths: 16 heads over 8 KV heads
    _compile(decode_attention, shape((32, 1, 16, D)), shape((32, 20, 8, D)),
             shape((32, 20, 8, D)), shape((32, 20), jnp.float32))


def _token_args(shape, n):
    return (shape((n,), jnp.int32), shape((n,), jnp.float32),
            shape((n,), jnp.float32), shape((n,), jnp.float32))


def test_fused_policy_loss_value_and_grad(shape):
    def loss(h, w, *rest):
        pg, ent, kl, _ = gl.fused_policy_loss(h, w, *rest, 0.2, 256, False)
        return pg + 0.1 * kl - 0.01 * ent
    n = 2048
    _compile(jax.value_and_grad(loss, argnums=(0, 1)),
             shape((n, DEEPSEEK.d_model)),
             shape((DEEPSEEK.d_model, DEEPSEEK.action_vocab_size)),
             *_token_args(shape, n))


def test_gipo_head_loss_value_and_grad(shape):
    def loss(logits, *rest):
        pg, ent, kl, _ = gl.gipo_head_loss(logits, *rest, 0.2, 256, False)
        return pg + 0.1 * kl - 0.01 * ent
    n = 2048
    _compile(jax.value_and_grad(loss),
             shape((n, DEEPSEEK.action_vocab_size), jnp.float32),
             *_token_args(shape, n))


def test_ssd_scan_value_and_grad_mamba2_widths(shape):
    cfg = get_config("mamba2-2.7b")
    s = cfg.ssm
    h, t = s.num_heads(cfg.d_model), 1024

    def loss(x, dt, a, bm, cm):
        y, state = dispatch._ssd_with_twin_bwd(x, dt, a, bm, cm, s.chunk,
                                               False)
        return y.sum() + state.sum()
    args = (shape((1, t, h, s.head_dim)), shape((1, t, h), jnp.float32),
            shape((h,), jnp.float32), shape((1, t, s.state_dim)),
            shape((1, t, s.state_dim)))
    _compile(lambda *a: ssd.ssd_scan(*a, chunk=s.chunk), *args)
    _compile(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)), *args)


@pytest.mark.parametrize("rows", [34560, 192])
def test_grouped_matmul_value_and_grad_v2_lite_widths(shape, rows):
    """``_gmm_kernel`` and ``_tgmm_kernel`` at DeepSeek-V2-Lite's expert
    widths over 8 held experts: the train cell's row buffer (5,760 tokens
    of a micro-batch x top 6; about 540 rows per held expert) and a decode
    batch (32 tokens x top 6)."""
    cfg = get_config("deepseek-v2-lite")
    d, ff = cfg.d_model, cfg.moe.d_ff

    def loss(x, w_in, w_out, sizes):
        h = gm.grouped_matmul(x, w_in, sizes, False)
        return gm.grouped_matmul(h, w_out, sizes, False).astype(
            jnp.float32).sum()
    compiled = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                        shape((rows, d)), shape((8, d, ff)),
                        shape((8, ff, d)), shape((8,), jnp.int32))
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 5     # 2 fwd, 2 dlhs, 2 tgmm


def test_smoke_train_step_fits_one_chip(shape, monkeypatch):
    """The donated trainer step of ``chip_smoke.py``'s configuration,
    with its Pallas kernels, plus two published bf16 weight copies for
    the inference tier, fits one chip's HBM."""
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", pathlib.Path(__file__).resolve().parents[1]
        / "chip_smoke.py")
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    from repro.configs.base import RLConfig
    from repro.core.train_step import init_train_state, train_step
    from repro.data.trajectory import dummy_batch

    monkeypatch.setattr(dispatch, "interpret_mode", lambda: False)
    cfg = smoke.smoke_config()
    place = lambda tree: jax.tree.map(  # noqa: E731
        lambda x: shape(x.shape, x.dtype), tree)
    state = place(jax.eval_shape(lambda k: init_train_state(cfg, k),
                                 jax.random.PRNGKey(0)))
    batch = place(dummy_batch(
        smoke.BATCH_EPISODES, smoke.SEGMENT_HORIZON, 12, cfg.action_dim,
        cfg.vocab_size, cfg.action_vocab_size,
        num_prefix=cfg.num_prefix_tokens))
    with dispatch.forced("pallas"):
        compiled = jax.jit(
            lambda s, b: train_step(s, b, cfg=cfg, rl=RLConfig()),
            donate_argnums=(0,)).lower(state, batch).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    param_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(state.params))
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert peak + 2 * param_bytes < HBM_BYTES, (peak, param_bytes)
