"""The VLA policy: backbone + slimmed action head + value head.

An env step consumes an observation embedding (stub frontend) plus the
instruction tokens, and emits ``action_dim`` discrete action tokens
(token-level optimization, paper App. D.3). ``score_trajectory`` is the
teacher-forced pass used by the trainer — it returns per-token log-probs
and per-step values in one forward (the JIT value-recomputation input).
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import transformer
from repro.models.layers import Params
from repro.models.value_head import value_head, value_head_init


class PolicyOutput(NamedTuple):
    logits: jnp.ndarray        # [B, A, Va] f32 — per action-token logits
    value: jnp.ndarray         # [B]
    hidden: jnp.ndarray        # [B, S, d]
    aux: Dict[str, jnp.ndarray]  # MoE load-balance / router-z terms


def init_policy_params(cfg: ModelConfig, key) -> Params:
    k1, k2 = jax.random.split(key)
    params = transformer.init_params(cfg, k1)
    params["value_head"] = value_head_init(
        k2, cfg.d_model, cfg.max_episode_steps)
    return params


def _teacher_forced(cfg: ModelConfig, params: Params,
                    obs_tokens: jnp.ndarray, action_tokens: jnp.ndarray,
                    step_t: jnp.ndarray,
                    prefix_embeds: Optional[jnp.ndarray], *,
                    remat: bool, head: bool):
    """Shared teacher-forced pass. Returns (transformer out, pred slice,
    value). ``pred`` selects the position that predicts action token k —
    prefix_len + T_obs + k - 1, the standard next-token factorization —
    in ONE place so the logits and fused-hidden paths cannot drift."""
    a = action_tokens.shape[1]
    tokens = jnp.concatenate([obs_tokens, action_tokens], axis=1)
    out = transformer.forward(cfg, params, tokens,
                              prefix_embeds=prefix_embeds, remat=remat,
                              head=head)
    t_total = out["hidden"].shape[1]
    pred = slice(t_total - a - 1, t_total - 1)
    act_hidden = out["hidden"][:, t_total - a:]                  # [B, A, d]
    value = value_head(params["value_head"], act_hidden, step_t)
    return out, pred, value


def policy_forward(cfg: ModelConfig, params: Params, obs_tokens: jnp.ndarray,
                   action_tokens: jnp.ndarray, step_t: jnp.ndarray,
                   prefix_embeds: Optional[jnp.ndarray] = None, *,
                   remat: bool = False) -> PolicyOutput:
    """Teacher-forced scoring of one env step.

    obs_tokens: [B, T_obs] instruction/context tokens
    action_tokens: [B, A] the action tokens taken
    step_t: [B] episode step index (value-head step embedding)

    Logits for action token k are read at the position *preceding* it
    (standard next-token factorization).
    """
    out, pred, value = _teacher_forced(cfg, params, obs_tokens,
                                       action_tokens, step_t, prefix_embeds,
                                       remat=remat, head=True)
    return PolicyOutput(logits=out["logits"][:, pred], value=value,
                        hidden=out["hidden"], aux=out["aux"])


class PolicyHidden(NamedTuple):
    pred_hidden: jnp.ndarray   # [B, A, d] — hidden at the position that
    #                            predicts each action token (pre action-head)
    value: jnp.ndarray         # [B]
    aux: Dict[str, jnp.ndarray]


def policy_forward_hidden(cfg: ModelConfig, params: Params,
                          obs_tokens: jnp.ndarray,
                          action_tokens: jnp.ndarray, step_t: jnp.ndarray,
                          prefix_embeds: Optional[jnp.ndarray] = None, *,
                          remat: bool = False) -> PolicyHidden:
    """Teacher-forced scoring that stops before the action head.

    The fused-loss trainer path consumes these hidden states directly: the
    action-head matmul and the GIPO/entropy/KL loss run block-fused in
    ``repro.kernels.dispatch.policy_head_loss``, so the [B, A, Va] logit
    tensor is never materialized.
    """
    out, pred, value = _teacher_forced(cfg, params, obs_tokens,
                                       action_tokens, step_t, prefix_embeds,
                                       remat=remat, head=False)
    return PolicyHidden(pred_hidden=out["hidden"][:, pred], value=value,
                        aux=out["aux"])


def action_log_prob(logits: jnp.ndarray,
                    action_tokens: jnp.ndarray) -> jnp.ndarray:
    """Token-level log-probs. logits: [B, A, Va]; actions: [B, A] -> [B, A]."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.take_along_axis(
        logp, action_tokens[..., None], axis=-1)[..., 0]


def sample_actions(key, logits: jnp.ndarray,
                   temperature: float = 1.0) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sample action tokens; returns (tokens [B, A], log_probs [B, A])."""
    if temperature != 1.0:
        logits = logits / temperature
    tokens = jax.random.categorical(key, logits, axis=-1)
    return tokens, action_log_prob(logits, tokens)


def sample_action_sequence(cfg: ModelConfig, params: Params, key,
                           obs_tokens: jnp.ndarray, step_t: jnp.ndarray,
                           prefix_embeds: Optional[jnp.ndarray] = None,
                           temperature: float = 1.0
                           ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Autoregressive action sampling for one env step (inference worker).

    Prefills the observation context, then decodes ``cfg.action_dim``
    action tokens against the KV/state cache. Returns
    (action_tokens [B, A], behavior_logp μ [B, A], value V(o_t) [B]).
    """
    a = cfg.action_dim
    prefix_len = prefix_embeds.shape[1] if prefix_embeds is not None else 0
    cache_len = prefix_len + obs_tokens.shape[1] + a
    # named scopes mark the stages in the ops' metadata (device profiles)
    with jax.named_scope("prefill"):
        out, cache = transformer.prefill(cfg, params, obs_tokens,
                                         prefix_embeds, cache_len=cache_len)
        first_logits = out["logits"][:, -1]              # [B, Va]

    @jax.named_scope("decode")
    def body(carry, key_i):
        logits, cache = carry
        if temperature != 1.0:
            logits = logits / temperature
        tok = jax.random.categorical(key_i, logits, axis=-1)     # [B]
        logp = jnp.take_along_axis(
            jax.nn.log_softmax(logits, axis=-1), tok[:, None], axis=-1)[:, 0]
        dec, cache = transformer.decode(cfg, params, tok, cache)
        hidden = dec["hidden"][:, 0]                     # [B, d]
        return (dec["logits"][:, -1], cache), (tok, logp, hidden)

    keys = jax.random.split(key, a)
    _, (tokens, logps, hiddens) = jax.lax.scan(
        body, (first_logits, cache), keys)
    tokens = tokens.T                                    # [B, A]
    logps = logps.T
    act_hidden = jnp.moveaxis(hiddens, 0, 1)             # [B, A, d]
    with jax.named_scope("value_head"):
        value = value_head(params["value_head"], act_hidden, step_t)
    return tokens, logps, value


def make_inference_fn(cfg: ModelConfig, temperature: float = 1.0):
    """jit-compiled batched inference entry point for the service pool."""
    def fn(params, key, obs_tokens, step_t, prefix_embeds=None):
        return sample_action_sequence(cfg, params, key, obs_tokens, step_t,
                                      prefix_embeds, temperature)
    return jax.jit(fn)
