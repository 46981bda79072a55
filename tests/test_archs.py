"""Deliverable (f): per-architecture smoke tests.

For each of the ten assigned architectures, instantiate the REDUCED
same-family variant (≤2 layers, d_model ≤ 512, ≤4 experts) and run one
forward + one train step on CPU asserting output shapes and no NaNs, plus
prefill/decode consistency (the serve path). The FULL configs are exercised
only via the dry-run (ShapeDtypeStruct, no allocation).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED_ARCHS, get_config, reduced
from repro.configs.base import RLConfig
from repro.core.train_step import init_train_state, make_train_step
from repro.data.trajectory import dummy_batch
from repro.models import transformer

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module", params=ASSIGNED_ARCHS + ["deepseek-v2-lite"])
def arch_setup(request):
    cfg = reduced(get_config(request.param))
    params = transformer.init_params(cfg, KEY)
    return request.param, cfg, params


def _prefix(cfg, b):
    if cfg.num_prefix_tokens:
        return jnp.asarray(
            np.random.default_rng(0).standard_normal(
                (b, min(cfg.num_prefix_tokens, 4),
                 transformer.FRONTEND_DIM)), jnp.float32)
    return None


def test_reduced_config_limits(arch_setup):
    name, cfg, _ = arch_setup
    assert cfg.num_layers <= 2
    assert cfg.d_model <= 512
    if cfg.moe is not None:
        assert cfg.moe.num_experts <= 4


def test_forward_shapes_and_finiteness(arch_setup):
    name, cfg, params = arch_setup
    b, t = 2, 16
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, cfg.vocab_size, (b, t)),
        jnp.int32)
    out = transformer.forward(cfg, params, tokens, _prefix(cfg, b))
    p = 0 if _prefix(cfg, b) is None else _prefix(cfg, b).shape[1]
    assert out["logits"].shape == (b, t + p, cfg.action_vocab_size)
    assert out["hidden"].shape == (b, t + p, cfg.d_model)
    assert np.isfinite(np.asarray(out["logits"])).all(), name


def test_prefill_decode_consistency(arch_setup):
    """Teacher-forced forward and prefill+decode must produce the same
    logits for the same token stream (KV-cache / SSM-state correctness)."""
    name, cfg, params = arch_setup
    b, t = 2, 12
    rng = np.random.default_rng(2)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, t + 1)),
                         jnp.int32)
    full = transformer.forward(cfg, params, tokens)
    res, cache = transformer.prefill(cfg, params, tokens[:, :t],
                                     cache_len=t + 4)
    dec, cache = transformer.decode(cfg, params, tokens[:, t], cache)
    got = np.asarray(dec["logits"][:, 0])
    want = np.asarray(full["logits"][:, t])
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_decode_matches_stepwise(arch_setup):
    """Multi-step decode: logits at each step match teacher forcing."""
    name, cfg, params = arch_setup
    b, t0, steps = 1, 6, 3
    rng = np.random.default_rng(3)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, t0 + steps)),
                         jnp.int32)
    _, cache = transformer.prefill(cfg, params, tokens[:, :t0],
                                   cache_len=t0 + steps)
    for i in range(steps):
        dec, cache = transformer.decode(cfg, params, tokens[:, t0 + i],
                                        cache)
    full = transformer.forward(cfg, params, tokens)
    np.testing.assert_allclose(
        np.asarray(dec["logits"][:, 0]),
        np.asarray(full["logits"][:, t0 + steps - 1]),
        rtol=5e-3, atol=5e-3)


def test_sliding_window_decode(arch_setup):
    """The long_500k fallback: ring-buffer window cache stays finite and
    matches windowed teacher forcing for attention archs."""
    name, cfg, params = arch_setup
    if cfg.is_attention_free:
        pytest.skip("attention-free: native O(1) state, no window cache")
    window = 8
    b, t = 1, 12
    rng = np.random.default_rng(4)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, t + 1)),
                         jnp.int32)
    full = transformer.forward(cfg, params, tokens, window=window)
    _, cache = transformer.prefill(cfg, params, tokens[:, :t],
                                   cache_len=window, window=window)
    dec, _ = transformer.decode(cfg, params, tokens[:, t], cache,
                                window=window)
    np.testing.assert_allclose(np.asarray(dec["logits"][:, 0]),
                               np.asarray(full["logits"][:, t]),
                               rtol=5e-3, atol=5e-3)


def test_one_train_step(arch_setup):
    """One RL train step per arch: loss finite, params move, no NaNs."""
    name, cfg, params = arch_setup
    rl = RLConfig(grad_accum=2, lr_policy=1e-4, lr_value=1e-3)
    state = init_train_state(cfg, KEY)
    batch = dummy_batch(4, 3, 8, cfg.action_dim, cfg.vocab_size,
                        cfg.action_vocab_size,
                        num_prefix=min(cfg.num_prefix_tokens, 4) or 0)
    step = make_train_step(cfg, rl, donate=False)
    new_state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"])), name
    assert np.isfinite(float(metrics["grad_norm"])), name
    moved = jax.tree.reduce(
        lambda acc, pq: acc or bool(jnp.any(pq)), jax.tree.map(
            lambda a, b: jnp.any(a != b), state.params, new_state.params),
        False)
    assert moved, f"{name}: parameters did not update"
    leaves = jax.tree.leaves(new_state.params)
    assert all(np.isfinite(np.asarray(x, np.float32)).all() for x in leaves)


def test_blockwise_attention_matches_dense(arch_setup):
    name, cfg, params = arch_setup
    if cfg.is_attention_free:
        pytest.skip("no attention")
    b, t = 1, 64
    tokens = jnp.asarray(
        np.random.default_rng(5).integers(0, cfg.vocab_size, (b, t)),
        jnp.int32)
    dense = transformer.forward(cfg, params, tokens)
    blocked = transformer.forward(cfg, params, tokens, block=16)
    np.testing.assert_allclose(np.asarray(blocked["logits"]),
                               np.asarray(dense["logits"]),
                               rtol=2e-3, atol=2e-3)


def test_moe_capacity_and_balance():
    """The expert layer has no capacity: every (token, expert) assignment
    is computed, and the output is the per-token top-k mixture, written
    here directly, for every token."""
    cfg = reduced(get_config("dbrx-132b"))
    from repro.models import moe as moe_lib
    params = moe_lib.moe_init(KEY, cfg.d_model, cfg.moe, jnp.float32)
    x = jnp.asarray(np.random.default_rng(6).standard_normal(
        (2, 32, cfg.d_model)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        out, aux = moe_lib.moe_forward(params, x, cfg.moe)
        xf = x.reshape(-1, cfg.d_model)
        probs = jax.nn.softmax(xf @ params["router"], axis=-1)
        gates, experts = jax.lax.top_k(probs, cfg.moe.top_k)
        gates = gates / gates.sum(-1, keepdims=True)    # dbrx renormalises
        want = jnp.zeros_like(xf)
        for e in range(cfg.moe.num_experts):
            g = jnp.sum(jnp.where(experts == e, gates, 0.0), -1)
            h = jax.nn.silu(xf @ params["w_gate"][e]) * (xf @ params["w_up"][e])
            want = want + g[:, None] * (h @ params["w_down"][e])
    assert out.shape == x.shape
    scale = float(jnp.abs(want).max())      # float32 rounding of the sums
    np.testing.assert_allclose(np.asarray(out).reshape(want.shape),
                               np.asarray(want), rtol=1e-5,
                               atol=1e-5 * scale)
    assert float(aux["held_frac"]) == 1.0       # all experts held here
    assert float(aux["load_max"]) >= 1.0
    assert float(aux["load_balance"]) >= 0.0


def test_uniform_decode_matches_scatter_path():
    """§Perf: the lockstep (scalar-slot) cache update must be numerically
    identical to the batched-scatter path when positions are uniform."""
    cfg = reduced(get_config("internlm2-1.8b"))
    params = transformer.init_params(cfg, KEY)
    b, t = 2, 6
    rng = np.random.default_rng(7)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, t + 2)),
                         jnp.int32)
    _, c1 = transformer.prefill(cfg, params, tokens[:, :t], cache_len=t + 2)
    _, c2 = transformer.prefill(cfg, params, tokens[:, :t], cache_len=t + 2)
    for i in range(2):
        d1, c1 = transformer.decode(cfg, params, tokens[:, t + i], c1,
                                    uniform=False)
        d2, c2 = transformer.decode(cfg, params, tokens[:, t + i], c2,
                                    uniform=True)
    np.testing.assert_allclose(np.asarray(d1["logits"]),
                               np.asarray(d2["logits"]), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(c1.attn.k, np.float32),
                               np.asarray(c2.attn.k, np.float32))


def test_split_inproj_equivalent_families():
    """§Perf: the shard-aligned split projection is the same model family —
    both layouts train and decode without NaNs and agree between their own
    forward/decode paths."""
    import dataclasses
    base = reduced(get_config("mamba2-2.7b"))
    split = dataclasses.replace(
        base, ssm=dataclasses.replace(base.ssm, fused_in_proj=False))
    for cfg in (base, split):
        params = transformer.init_params(cfg, KEY)
        tokens = jnp.asarray(
            np.random.default_rng(8).integers(0, cfg.vocab_size, (1, 9)),
            jnp.int32)
        full = transformer.forward(cfg, params, tokens)
        assert np.isfinite(np.asarray(full["logits"])).all()
        _, cache = transformer.prefill(cfg, params, tokens[:, :8],
                                       cache_len=12)
        dec, _ = transformer.decode(cfg, params, tokens[:, 8], cache)
        np.testing.assert_allclose(np.asarray(dec["logits"][:, 0]),
                                   np.asarray(full["logits"][:, 8]),
                                   rtol=2e-3, atol=2e-3)
