"""The AcceRL trainer step: GIPO + just-in-time GAE + lagged normalization
with sequential micro-batch slicing and gradient accumulation (paper §5,
App. C).

Structure per optimizer step (one gradient-accumulation window):
  1. slice the batch *sequentially* into micro-batches (contiguous memory —
     the paper's replacement for global shuffling),
  2. per micro-batch: training forward → values → GAE on the spot (value
     recomputation without a second forward pass) → normalize with the
     PREVIOUS step's global stats (eq. 8) → GIPO/PPO loss → grads,
  3. accumulate grads and the packed (sum, sum², count) advantage stats,
  4. single optimizer update; fold the stats into the Welford running state
     (the deferred "synchronous aggregation at the end of backpropagation").

Under pjit the batch is sharded over ``data`` so the ``jnp.sum`` inside the
stats produces the paper's single all-reduce automatically; ``shard_map``
users can call ``advnorm.psum_stats`` explicitly.
"""
from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, RLConfig
from repro.core import advnorm, gae, gipo
from repro.core.advnorm import AdvNormState
from repro.data.trajectory import TrajectoryBatch
from repro.kernels import dispatch
from repro.models.policy import (
    action_log_prob,
    policy_forward,
    policy_forward_hidden,
)
from repro.optim import adamw


class TrainState(NamedTuple):
    params: dict
    opt: adamw.AdamWState
    adv_norm: AdvNormState
    version: jnp.ndarray            # i32 — published-policy version counter


def init_train_state(cfg: ModelConfig, key, *, mesh=None) -> TrainState:
    """Build the live trainer state.

    With ``mesh`` (any mesh carrying a ``data`` axis), the f32 Adam
    moments are materialized under ``optim.zero.shard_moments_spec`` —
    ZeRO-2: parameters stay replicated over ``data`` while each moment
    tensor's largest divisible axis is sharded over it (paper §3.1,
    "partition optimizer states ... supporting larger micro-batch
    sizes"). On a single-device mesh this is a no-op, so the wiring is
    unconditional in :class:`~repro.runtime.trainer.TrainerWorker`.
    """
    from repro.models.policy import init_policy_params
    params = init_policy_params(cfg, key)
    state = TrainState(params=params, opt=adamw.init(params),
                       adv_norm=advnorm.init_adv_state(),
                       version=jnp.zeros((), jnp.int32))
    if mesh is not None and getattr(mesh, "devices", None) is not None \
            and mesh.devices.size > 1:
        state = jax.device_put(state, state_shardings(cfg, mesh))
    return state


def state_shardings(cfg: ModelConfig, mesh) -> TrainState:
    """NamedSharding tree of the live TrainState on ``mesh``: params under
    the TP/FSDP rules, f32 Adam moments additionally ZeRO-sharded over
    ``data`` (``optim.zero``), the scalars replicated."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.models.policy import init_policy_params
    from repro.optim import zero
    from repro.sharding import rules
    shapes = jax.eval_shape(functools.partial(init_policy_params, cfg),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    pspec = rules.param_specs(cfg, shapes, mesh)
    moments = zero.moment_shardings(shapes, mesh, param_specs=pspec)
    rep = NamedSharding(mesh, P())
    return TrainState(
        params=jax.tree.map(lambda s: NamedSharding(mesh, s), pspec,
                            is_leaf=lambda x: isinstance(x, P)),
        opt=adamw.AdamWState(step=rep, mu=moments, nu=moments),
        adv_norm=jax.tree.map(lambda _: rep,
                              jax.eval_shape(advnorm.init_adv_state)),
        version=rep)


def _score_batch(cfg: ModelConfig, params, micro: TrajectoryBatch, *,
                 remat: bool):
    """Teacher-forced scoring of every (obs, action) step incl. bootstrap.

    Returns (logits [b,T+1,A,V], values [b,T+1])."""
    b, tp1 = micro.obs_tokens.shape[:2]
    flat = lambda x: x.reshape((b * tp1,) + x.shape[2:])
    prefix = None
    if micro.prefix_embeds is not None:
        prefix = flat(micro.prefix_embeds)
    out = policy_forward(cfg, params, flat(micro.obs_tokens),
                         flat(micro.actions), flat(micro.steps),
                         prefix_embeds=prefix, remat=remat)
    logits = out.logits.reshape(b, tp1, *out.logits.shape[1:])
    values = out.value.reshape(b, tp1)
    return logits, values, out.aux


def _score_batch_hidden(cfg: ModelConfig, params, micro: TrajectoryBatch, *,
                        remat: bool):
    """Head-free twin of ``_score_batch`` for the fused-loss path.

    Returns (pred_hidden [b,T+1,A,d], values [b,T+1], aux)."""
    b, tp1 = micro.obs_tokens.shape[:2]
    flat = lambda x: x.reshape((b * tp1,) + x.shape[2:])
    prefix = None
    if micro.prefix_embeds is not None:
        prefix = flat(micro.prefix_embeds)
    out = policy_forward_hidden(cfg, params, flat(micro.obs_tokens),
                                flat(micro.actions), flat(micro.steps),
                                prefix_embeds=prefix, remat=remat)
    hidden = out.pred_hidden.reshape(b, tp1, *out.pred_hidden.shape[1:])
    values = out.value.reshape(b, tp1)
    return hidden, values, out.aux


def _gae_and_norm(values, micro: TrajectoryBatch, adv_state: AdvNormState,
                  rl: RLConfig):
    """Just-in-time GAE (value recomputation, App. C.1) + lagged norm.

    Ablation (Fig. 7): value_recompute=False falls back to the STALE
    values recorded at collection time — misaligned targets."""
    values_for_gae = values if rl.value_recompute else micro.behavior_value
    adv, returns = gae.jit_gae_from_forward(
        values_for_gae, micro.rewards, micro.dones, rl.discount,
        rl.gae_lambda)
    stats = advnorm.local_stats(adv, micro.mask)
    adv_n = advnorm.normalize_lagged(adv, adv_state)
    return jax.lax.stop_gradient(adv_n), returns, stats


def _assemble_loss(cfg: ModelConfig, rl: RLConfig, pg, v_loss, kl, ent,
                   aux, stats, pg_metrics):
    """Combine the loss terms and build the metrics dict — shared by the
    reference and fused paths so they cannot drift apart."""
    total = pg + rl.value_coef * v_loss + rl.kl_coef * kl \
        - rl.entropy_coef * ent
    if cfg.arch_type == "moe":
        total = total + aux["load_balance"] + aux["router_z"]
    metrics = {
        "loss": total, "pg_loss": pg, "value_loss": v_loss, "kl": kl,
        "entropy": ent, "adv_mean_raw": stats[0] / jnp.maximum(stats[2], 1.0),
        **pg_metrics,
    }
    if cfg.arch_type == "moe":
        metrics["moe_load_balance"] = aux["load_balance"]
        metrics["moe_held_frac"] = aux["held_frac"]
        metrics["moe_load_max"] = aux["load_max"]
    return total, (metrics, stats)


def _fused_loss_fn(params, micro: TrajectoryBatch, adv_state: AdvNormState,
                   cfg: ModelConfig, rl: RLConfig, *, remat: bool
                   ) -> Tuple[jnp.ndarray, Tuple[Dict, jnp.ndarray]]:
    """Fused-loss path: the action head + GIPO/entropy/KL run block-fused
    on hidden states (kernels/dispatch.py) — the [b,T,A,Va] logit tensor
    and its log-softmax are never materialized. Exact parity (loss and
    grads) with the reference path is asserted in tests."""
    t = micro.horizon
    hidden, values, aux = _score_batch_hidden(cfg, params, micro,
                                              remat=remat)
    adv_n, returns, stats = _gae_and_norm(values, micro, adv_state, rl)

    b = hidden.shape[0]
    a_dim = micro.actions.shape[2]
    hid = hidden[:, :t].reshape(b * t * a_dim, -1)
    pg, ent, kl, pg_metrics = dispatch.policy_head_loss(
        hid, params["action_head"]["w"],
        micro.actions[:, :t].reshape(-1),
        micro.behavior_logp[:, :t].reshape(-1),
        jnp.broadcast_to(adv_n[..., None], (b, t, a_dim)).reshape(-1),
        jnp.broadcast_to(micro.mask[..., None], (b, t, a_dim)).reshape(-1),
        sigma=rl.gipo_sigma, mode=rl.kernel_dispatch)
    pg_metrics = jax.tree.map(jax.lax.stop_gradient, pg_metrics)

    v_loss = gipo.value_loss(values[:, :t], jax.lax.stop_gradient(returns),
                             micro.mask)
    return _assemble_loss(cfg, rl, pg, v_loss, kl, ent, aux, stats,
                          pg_metrics)


def loss_fn(params, micro: TrajectoryBatch, adv_state: AdvNormState,
            cfg: ModelConfig, rl: RLConfig, *, remat: bool = False
            ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    if rl.fused_loss and rl.algo == "gipo":
        return _fused_loss_fn(params, micro, adv_state, cfg, rl,
                              remat=remat)
    t = micro.horizon
    logits, values, aux = _score_batch(cfg, params, micro, remat=remat)
    adv_n, returns, stats = _gae_and_norm(values, micro, adv_state, rl)

    # --- token-level policy loss (App. D.3) ----------------------------------
    logp_new = action_log_prob(logits[:, :t], micro.actions[:, :t])
    logp_old = micro.behavior_logp[:, :t]
    if rl.algo == "gipo":
        pg, pg_metrics = gipo.gipo_loss(logp_new, logp_old, adv_n,
                                        micro.mask, rl.gipo_sigma)
    else:
        pg, pg_metrics = gipo.ppo_loss(logp_new, logp_old, adv_n,
                                       micro.mask, rl.ppo_clip)

    # --- value loss: bootstrap column excluded ("loss forcibly set to 0") ---
    v_loss = gipo.value_loss(values[:, :t], jax.lax.stop_gradient(returns),
                             micro.mask)
    kl = gipo.kl_penalty(logp_new, logp_old, micro.mask)
    ent = gipo.entropy_bonus(logits[:, :t], micro.mask)
    return _assemble_loss(cfg, rl, pg, v_loss, kl, ent, aux, stats,
                          pg_metrics)


def _microbatches(batch: TrajectoryBatch, n_micro: int):
    """Sequential contiguous slicing along the batch axis (App. C.1)."""
    b = batch.obs_tokens.shape[0]
    mb = b // n_micro

    def slice_i(i):
        def sl(x):
            if x is None:
                return None
            return jax.lax.dynamic_slice_in_dim(x, i * mb, mb, axis=0)
        return jax.tree.map(sl, batch,
                            is_leaf=lambda v: v is None)
    return slice_i, mb


# --------------------------------------------------------------------------
# Stage functions. These ARE the training step: ``train_step`` composes
# them under one jit (the fused path), and runtime/pipeline_exec.py jits
# each one separately as a RUN instruction body — both paths execute the
# same math, so parity is structural rather than asserted-after-the-fact.
# Each runs under a ``jax.named_scope`` of its stage's name, so the ops of
# the fused step carry their stage in their metadata, where a profile of
# the device finds it.
# --------------------------------------------------------------------------

@jax.named_scope("grad_reduce")
def zero_grads_like(params):
    """Fresh f32 accumulator matching ``params`` (one per accumulation
    window — the pipeline FREEs it after the optimizer update)."""
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


@jax.named_scope("fwd_bwd")
def microbatch_grads(params, micro: TrajectoryBatch,
                     adv_state: AdvNormState, *, cfg: ModelConfig,
                     rl: RLConfig, remat: bool = False):
    """fwd_bwd stage: grads + (metrics, packed adv stats) for one
    micro-batch against frozen params (eq. 7)."""
    grad_fn = jax.grad(
        functools.partial(loss_fn, cfg=cfg, rl=rl, remat=remat),
        has_aux=True)
    return grad_fn(params, micro, adv_state)


@jax.named_scope("grad_reduce")
def accumulate_grads(acc, grads, stats_acc, stats, n_micro: int):
    """grad_reduce stage: fold one micro-batch's grads into the f32
    accumulator (mean over the window) and sum the packed stats."""
    acc = jax.tree.map(
        lambda a, g: a + g.astype(jnp.float32) / n_micro, acc, grads)
    return acc, stats_acc + stats


@jax.named_scope("optim_update")
def apply_update(state: TrainState, grads, stats, metrics, *,
                 rl: RLConfig) -> Tuple[TrainState, Dict]:
    """optim_update stage: AdamW with the per-head lr tree, then fold the
    deferred advantage stats (end-of-backprop aggregation, App. C.1)."""
    lr_p = adamw.warmup_schedule(rl.lr_policy, rl.warmup_steps)(state.opt.step)
    lr_v = adamw.warmup_schedule(rl.lr_value, rl.warmup_steps)(state.opt.step)
    lr_tree = _lr_tree(state.params, lr_p, lr_v)
    new_params, new_opt, gnorm = adamw.update(
        grads, state.opt, state.params, lr_tree,
        max_grad_norm=rl.max_grad_norm)

    new_adv = advnorm.welford_update(state.adv_norm, stats)
    metrics = dict(metrics)
    metrics["grad_norm"] = gnorm
    metrics["adv_count"] = new_adv.count
    new_state = TrainState(params=new_params, opt=new_opt, adv_norm=new_adv,
                           version=state.version + 1)
    return new_state, metrics


def train_step(state: TrainState, batch: TrajectoryBatch, *,
               cfg: ModelConfig, rl: RLConfig,
               remat: bool = False) -> Tuple[TrainState, Dict]:
    """One optimizer step = ``rl.grad_accum`` micro-batch passes."""
    n_micro = rl.grad_accum
    slice_i, _ = _microbatches(batch, n_micro)

    def body(carry, i):
        grads_acc, stats_acc = carry
        micro = slice_i(i)
        grads, (metrics, stats) = microbatch_grads(
            state.params, micro, state.adv_norm, cfg=cfg, rl=rl, remat=remat)
        grads_acc, stats_acc = accumulate_grads(grads_acc, grads, stats_acc,
                                                stats, n_micro)
        return (grads_acc, stats_acc), metrics

    (grads, stats), metrics = jax.lax.scan(
        body, (zero_grads_like(state.params), jnp.zeros((3,))),
        jnp.arange(n_micro))
    metrics = jax.tree.map(lambda m: m[-1], metrics)
    return apply_update(state, grads, stats, metrics, rl=rl)


def _lr_tree(params, lr_policy, lr_value):
    """Per-leaf learning rates: the value head trains 10× hotter (Table 3)."""
    def assign(path, leaf):
        keys = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        return lr_value if "value_head" in keys else lr_policy
    return jax.tree_util.tree_map_with_path(assign, params)


def make_train_step(cfg: ModelConfig, rl: RLConfig, *, remat: bool = False,
                    donate: bool = True):
    """jit-compiled train step bound to a config."""
    fn = functools.partial(train_step, cfg=cfg, rl=rl, remat=remat)
    return jax.jit(fn, donate_argnums=(0,) if donate else ())
