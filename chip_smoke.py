"""Bring-up smoke run of the async RL loop on one TPU chip.

    python3 chip_smoke.py              # one chip: kernels + the async loop
    python3 chip_smoke.py --chips 4    # four chips: ZeRO trainer step only

One process drives the chip from start to end. On one chip it

  (a) exits non-zero at once when JAX finds no TPU (there is no CPU path);
  (b) checks each main-path Pallas kernel against its jnp twin at
      deepseek-7b widths — flash attention forward and backward, decode
      attention, and the fused policy loss with its gradients;
  (c) builds ``AcceRLSystem`` at the published deepseek-7b widths with
      only the depth cut, runs ``run_async(train_steps=3)`` and checks the
      trainer, the inference tier, service health and that the compiled
      programs carry the Pallas kernels (``tpu_custom_call``);
  (d) prints, as its last line, ``{"ok": true, "device": {...}}``.

``--chips 4`` instead runs one trainer step of the same configuration on
the four-device ZeRO data-parallel mesh (``TrainerWorker``) and on one
device, and checks that loss and grad norm agree and that params and
Adam moments sit on four devices with a quarter of the moment bytes each.
Every number goes on a line before the last.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

# One decoder layer of 30. The compile rehearsal for a described v5e
# (compiled.memory_analysis() of the donated train step, 8 episodes x 5
# steps, grad_accum 2) gives 6.5 GB of arguments and 4.5 GB of temporaries
# at 644M params; the inference tier keeps up to two more published bf16
# copies (2.6 GB). Two layers (846M params) give 8.5 GB and 7.0 GB, about
# 18.9 GB with the copies, past the chip's 16.9 GB limit.
LAYERS = 1
BATCH_EPISODES = 8          # trainer super-batch (segments)
SEGMENT_HORIZON = 4         # env steps per segment
MAX_EPISODE_STEPS = 8
ROLLOUT_WORKERS = 8
INFERENCE_BUCKET = 8        # one padded batch shape -> one inference compile
TRAIN_STEPS = 3
SEED = 0

BF16_TOL = 2e-2     # max |pallas - twin| / max |twin|, bf16 inputs


def log(msg: str) -> None:
    print(msg, flush=True)


def smoke_config(layers: int = LAYERS):
    """deepseek-7b at its published widths, cut in depth only, with the
    one-token frame prefix every policy in ``AcceRLSystem`` consumes."""
    from repro.configs import get_config
    return dataclasses.replace(get_config("deepseek-7b"), num_layers=layers,
                               num_prefix_tokens=1)


def describe_cuts(cfg) -> str:
    from repro.configs import get_config
    published = get_config(cfg.name).num_layers
    return (f"config {cfg.name}: d_model {cfg.d_model}, heads "
            f"{cfg.num_heads}x{cfg.head_dim}, kv {cfg.num_kv_heads}, d_ff "
            f"{cfg.d_ff}, vocab {cfg.vocab_size}, action vocab "
            f"{cfg.action_vocab_size}, {cfg.param_dtype} | cut: layers "
            f"{published} -> {cfg.num_layers} (compile rehearsal: two exceed "
            f"one chip), "
            f"batch {BATCH_EPISODES} episodes x {SEGMENT_HORIZON} steps, "
            f"episodes <= {MAX_EPISODE_STEPS} steps, {ROLLOUT_WORKERS} "
            f"rollout workers, inference bucket {INFERENCE_BUCKET}, weights "
            f"random from seed {SEED}")


# ---------------------------------------------------------------------------
# (b) kernels against their jnp twins
# ---------------------------------------------------------------------------

def _rel_err(got, want) -> float:
    import jax
    import numpy as np
    worst = 0.0
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        g = np.asarray(g, np.float32)
        w = np.asarray(w, np.float32)
        if not (np.all(np.isfinite(g)) and np.all(np.isfinite(w))):
            return math.inf
        scale = max(float(np.max(np.abs(w))), 1e-2)
        worst = max(worst, float(np.max(np.abs(g - w))) / scale)
    return worst


def compare_to_twin(name: str, fn, args) -> None:
    """Run ``fn`` on the dispatched route, which must lower to a Pallas
    TPU kernel, and on the jnp twin; fail past ``BF16_TOL``."""
    import jax
    from repro.kernels import dispatch
    # routing binds at trace time and is not part of jit's cache key:
    # each side gets its own wrapper, hence its own trace
    jitted = jax.jit(lambda *a: fn(*a))
    assert "tpu_custom_call" in jitted.lower(*args).as_text(), \
        f"{name}: no Pallas kernel in the dispatched program"
    got = jitted(*args)
    with dispatch.forced("jnp"):
        want = jax.jit(lambda *a: fn(*a))(*args)
    err = _rel_err(got, want)
    log(f"kernel {name}: max rel err {err:.3e} (tol {BF16_TOL:.0e})")
    if not err <= BF16_TOL:
        raise AssertionError(f"kernel {name} disagrees with its jnp twin: "
                             f"{err:.3e} > {BF16_TOL}")


def check_kernels(cfg, *, seq: int = 1024, batch: int = 2,
                  cache: int = 512, tokens: int = 2048) -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels import dispatch

    h, d, kv = cfg.num_heads, cfg.head_dim, cfg.num_kv_heads
    key = jax.random.PRNGKey(SEED)
    kq, kk, kvv, kd, kh, kw, kt, ka = jax.random.split(key, 8)
    bf16 = jnp.bfloat16
    q = jax.random.normal(kq, (batch, seq, h, d), bf16)
    k = jax.random.normal(kk, (batch, seq, kv, d), bf16)
    v = jax.random.normal(kvv, (batch, seq, kv, d), bf16)

    def attn(q, k, v):
        return dispatch.attention(q, k, v, block=128)
    compare_to_twin("flash_fwd", attn, (q, k, v))

    def attn_grads(q, k, v, g):
        out, vjp = jax.vjp(attn, q, k, v)
        return vjp(g)
    g = jax.random.normal(kd, q.shape, bf16)
    compare_to_twin("flash_bwd", attn_grads, (q, k, v, g))

    dq = jax.random.normal(kq, (8, 1, h, d), bf16)
    dk = jax.random.normal(kk, (8, cache, kv, d), bf16)
    dv = jax.random.normal(kvv, (8, cache, kv, d), bf16)
    fill = jnp.arange(8) * (cache // 8) + 7          # ragged cache fill
    valid = jnp.arange(cache)[None, :] < fill[:, None]
    compare_to_twin(
        "decode", lambda q, k, v, m: dispatch.decode_attention(q, k, v, m),
        (dq, dk, dv, valid))

    va = cfg.action_vocab_size
    hidden = jax.random.normal(kh, (tokens, cfg.d_model), bf16)
    w = (jax.random.normal(kw, (cfg.d_model, va), jnp.float32)
         * cfg.d_model ** -0.5).astype(bf16)
    targets = jax.random.randint(kt, (tokens,), 0, va)
    logp_old = -jnp.log(float(va)) + 0.1 * jax.random.normal(ka, (tokens,))
    adv = jax.random.normal(kd, (tokens,))
    mask = (jnp.arange(tokens) % 7 != 0).astype(jnp.float32)

    def loss(hidden, w):
        pg, ent, kl, _ = dispatch.policy_head_loss(
            hidden, w, targets, logp_old, adv, mask, sigma=0.2)
        return pg + 0.1 * kl - 0.01 * ent
    compare_to_twin("policy_loss", jax.value_and_grad(loss, argnums=(0, 1)),
                    (hidden, w))


# ---------------------------------------------------------------------------
# (c) the async loop through AcceRLSystem
# ---------------------------------------------------------------------------

def build_system(cfg):
    from repro.configs.base import RLConfig, RuntimeConfig
    from repro.runtime import AcceRLSystem
    rt = RuntimeConfig(num_rollout_workers=ROLLOUT_WORKERS,
                       inference_batch=INFERENCE_BUCKET,
                       batch_buckets=(INFERENCE_BUCKET,))
    return AcceRLSystem(cfg, RLConfig(), rt, suite="spatial",
                        segment_horizon=SEGMENT_HORIZON,
                        max_episode_steps=MAX_EPISODE_STEPS,
                        batch_episodes=BATCH_EPISODES, seed=SEED)


def run_system(system, *, wall_timeout_s: float = 600.0) -> dict:
    """``run_async`` plus the checks on what came out of it."""
    t0 = time.monotonic()
    m = system.run_async(train_steps=TRAIN_STEPS,
                         wall_timeout_s=wall_timeout_s)
    log(f"run_async: {m['train_steps']} trainer steps, {m['env_steps']} env "
        f"steps, {m['episodes']} episodes in {time.monotonic() - t0:.1f} s")
    assert m["train_steps"] >= TRAIN_STEPS, m["train_steps"]
    for i, step in enumerate(system.trainer.metrics_log):
        log(f"trainer step {i}: loss {step['loss']!r} grad_norm "
            f"{step['grad_norm']!r} policy_lag {step['policy_lag']!r}")
        assert math.isfinite(step["loss"]) and math.isfinite(
            step["grad_norm"]), step
    inf = system.inference
    version = inf.metrics.gauge("weight_version", -1.0)
    log(f"inference: {inf.requests_served} requests in {inf.batches_run} "
        f"batches, serving weight version {version:g}")
    assert inf.requests_served > 0
    assert version >= 1, "the inference tier never acquired a published step"
    bad = {n: h["error"] for n, h in system.health().items() if h["error"]}
    assert not bad, bad
    return m


def kernels_in_programs(system) -> None:
    """The lowered trainer step and inference fn carry Pallas kernels."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.data.trajectory import dummy_batch
    from repro.models.transformer import FRONTEND_DIM
    cfg = system.cfg
    trainer, inf = system.trainer, system.inference
    batch = dummy_batch(BATCH_EPISODES, SEGMENT_HORIZON, 12, cfg.action_dim,
                        cfg.vocab_size, cfg.action_vocab_size,
                        num_prefix=cfg.num_prefix_tokens)
    train_hlo = trainer._step_fn.lower(trainer.state, batch).as_text()
    params, _ = system.store.acquire(timeout=1.0)
    nb = INFERENCE_BUCKET
    infer_hlo = inf._fn.lower(
        params, jax.random.PRNGKey(0), np.zeros((nb, 12), np.int32),
        np.zeros((nb,), np.int32),
        jnp.zeros((nb, 1, FRONTEND_DIM), jnp.float32)).as_text()
    counts = {name: text.count("tpu_custom_call")
              for name, text in (("trainer step", train_hlo),
                                 ("inference fn", infer_hlo))}
    log(f"tpu_custom_call sites: {counts}")
    assert all(counts.values()), counts


# ---------------------------------------------------------------------------
# --chips 4: the ZeRO data-parallel trainer step against one device
# ---------------------------------------------------------------------------

def _tree_bytes_by_device(tree) -> dict:
    import jax
    out: dict = {}
    for leaf in jax.tree.leaves(tree):
        for shard in leaf.addressable_shards:
            out[shard.device.id] = out.get(shard.device.id, 0) \
                + shard.data.nbytes
    return out


def zero_phase(cfg, *, tol: float = BF16_TOL) -> dict:
    import gc
    import importlib
    import jax
    from repro.configs.base import RLConfig, RuntimeConfig
    from repro.data.trajectory import dummy_batch
    from repro.runtime.step_program import build_train_step_program
    from repro.runtime.trainer import TrainerWorker
    from repro.runtime.weight_store import VersionedWeightStore
    core = importlib.import_module("repro.core.train_step")

    n_dev = len(jax.devices())
    rl = RLConfig()
    batch = dummy_batch(BATCH_EPISODES, SEGMENT_HORIZON, 12, cfg.action_dim,
                        cfg.vocab_size, cfg.action_vocab_size,
                        num_prefix=cfg.num_prefix_tokens, seed=SEED)

    # one device: the plain step on the default device
    state = core.init_train_state(cfg, jax.random.PRNGKey(SEED))
    step = build_train_step_program(cfg, rl).fused(donate=True)
    state, ref = step(state, batch)
    ref = {k: float(ref[k]) for k in ("loss", "grad_norm")}
    del state, step
    gc.collect()

    class _NoSource:
        def pop_batch(self, n, timeout=None):
            return []

    trainer = TrainerWorker(cfg, rl, RuntimeConfig(), _NoSource(),
                            VersionedWeightStore(), seed=SEED)
    trainer.begin_inline()
    got = trainer.train_on_batch(batch)
    log(f"zero step on {n_dev} devices: loss {got['loss']!r} grad_norm "
        f"{got['grad_norm']!r}; on 1 device: loss {ref['loss']!r} "
        f"grad_norm {ref['grad_norm']!r}")
    for k in ("loss", "grad_norm"):
        diff = abs(got[k] - ref[k]) / max(abs(ref[k]), 1e-6)
        log(f"zero {k}: rel diff {diff:.3e} (tol {tol:.0e})")
        assert diff <= tol, (k, got[k], ref[k])

    params_by_dev = _tree_bytes_by_device(trainer.state.params)
    moments = (trainer.state.opt.mu, trainer.state.opt.nu)
    moments_by_dev = _tree_bytes_by_device(moments)
    total = sum(leaf.nbytes for leaf in jax.tree.leaves(moments))
    log(f"zero placement: param bytes by device {params_by_dev}; moment "
        f"bytes by device {moments_by_dev} of {total} total")
    assert len(params_by_dev) == n_dev and len(moments_by_dev) == n_dev
    for nbytes in moments_by_dev.values():
        share = nbytes / total
        assert abs(share - 1 / n_dev) < 0.05, (share, moments_by_dev)
    return {"ref": ref, "got": got, "moments_by_dev": moments_by_dev}


# ---------------------------------------------------------------------------

def device_line() -> str:
    import jax
    dev = jax.devices()[0]
    return json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}})


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the ZeRO data-parallel trainer step")
    args = ap.parse_args()

    # the TPU runtime logs to stderr, not to files outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devices[0].platform!r}); this check runs on the chip only",
              file=sys.stderr)
        return 1
    if len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    cfg = smoke_config()
    log(describe_cuts(cfg))
    log(f"params {cfg.param_count()} (analytic, without the value head)")
    t0 = time.monotonic()
    if args.chips == 4:
        zero_phase(cfg)
    else:
        check_kernels(cfg)
        log(f"kernels checked in {time.monotonic() - t0:.1f} s")
        system = build_system(cfg)
        log(f"system built in {time.monotonic() - t0:.1f} s")
        run_system(system)
        kernels_in_programs(system)
    stats = devices[0].memory_stats() or {}
    log(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')!r} of "
        f"bytes_limit {stats.get('bytes_limit')!r} on device 0; total "
        f"{time.monotonic() - t0:.1f} s")
    print(device_line(), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
