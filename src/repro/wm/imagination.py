"""Imagination rollouts (paper §4.1).

A real frame o_t seeds the rollout (ô_t = o_t); the policy M_policy produces
â_t; M_obs samples ô_{t+1}; M_reward scores both frames; the imagined
reward is the potential difference (eq. 4)

    r̂_t = M_reward(ô_{t+1}) − M_reward(ô_t)

scaled by ``reward_scale``, with the termination signal d̂one from the
success probability. Trajectories are STRICTLY capped at horizon H to bound
autoregressive compounding error, packaged per eq. 3, and pushed to B_img.

The whole horizon-H rollout is ONE jitted ``lax.scan`` program, so an
imagination worker generates a full τ̂ batch per device dispatch —
"completely bypassing the physical simulator's latency".
"""
from __future__ import annotations

import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, WMConfig

from repro.models.policy import sample_action_sequence
from repro.models.transformer import FRONTEND_DIM
from repro.runtime import telemetry as _tel
from repro.runtime.service import Service
from repro.wm import denoiser as dn
from repro.wm import reward as rw

SUCCESS_THRESHOLD = 0.9


def _frame_prefix(frames: jnp.ndarray) -> jnp.ndarray:
    """[B, F_env] -> [B, 1, FRONTEND_DIM] zero-padded stub embedding."""
    b, f = frames.shape
    pad = jnp.zeros((b, FRONTEND_DIM - f), frames.dtype)
    return jnp.concatenate([frames, pad], axis=-1)[:, None, :]


def imagine_rollout(policy_params, obs_params, reward_params, key,
                    tokens: jnp.ndarray, frame0: jnp.ndarray,
                    step0: jnp.ndarray, *, cfg: ModelConfig,
                    wm: WMConfig) -> Dict[str, jnp.ndarray]:
    """Horizon-H imagined rollout from real seed frames.

    tokens: [B, T_obs] (instruction — constant across the horizon);
    frame0: [B, F]; step0: [B]. Returns eq.-3 arrays with an H+1 slot.
    """
    b, f = frame0.shape
    h_frames = jnp.repeat(frame0[:, None, :], wm.history_frames, axis=1)
    p0 = rw.reward_apply(reward_params, frame0)

    def body(carry, key_t):
        frame, hist, step, p_cur = carry
        k_act, k_obs = jax.random.split(key_t)
        actions, logp, value = sample_action_sequence(
            cfg, policy_params, k_act, tokens, step, _frame_prefix(frame))
        frame_next = dn.sample_next_frame(obs_params, k_obs, hist, actions,
                                          wm)
        p_next = rw.reward_apply(reward_params, frame_next)
        reward = wm.reward_scale * (p_next - p_cur)          # eq. 4
        done = (p_next > SUCCESS_THRESHOLD).astype(jnp.float32)
        hist = jnp.concatenate([hist[:, 1:], frame_next[:, None]], axis=1)
        out = dict(frame=frame, actions=actions, logp=logp, value=value,
                   reward=reward, done=done, step=step)
        return (frame_next, hist, step + 1, p_next), out

    keys = jax.random.split(key, wm.imagine_horizon)
    (frame_h, _, step_h, _), outs = jax.lax.scan(
        body, (frame0, h_frames, step0, p0), keys)

    # [H, B, ...] -> [B, H, ...]; append the H+1 bootstrap slot
    tr = lambda x: jnp.moveaxis(x, 0, 1)
    frames = jnp.concatenate([tr(outs["frame"]), frame_h[:, None]], axis=1)
    steps = jnp.concatenate([tr(outs["step"]), step_h[:, None]], axis=1)
    zeros_a = jnp.zeros((b, 1) + outs["actions"].shape[2:],
                        outs["actions"].dtype)
    zeros_l = jnp.zeros((b, 1) + outs["logp"].shape[2:], jnp.float32)
    return {
        "frames": frames,                                     # [B, H+1, F]
        "obs_tokens": jnp.repeat(tokens[:, None], wm.imagine_horizon + 1,
                                 axis=1),
        "actions": jnp.concatenate([tr(outs["actions"]), zeros_a], axis=1),
        "behavior_logp": jnp.concatenate([tr(outs["logp"]), zeros_l],
                                         axis=1),
        "behavior_value": jnp.concatenate(
            [tr(outs["value"]), jnp.zeros((b, 1))], axis=1),
        "rewards": tr(outs["reward"]),
        "dones": tr(outs["done"]),
        "steps": steps.astype(jnp.int32),
        "mask": jnp.ones((b, wm.imagine_horizon), jnp.float32),
    }


def make_imagine_fn(cfg: ModelConfig, wm: WMConfig):
    def fn(policy_params, obs_params, reward_params, key, tokens, frame0,
           step0):
        return imagine_rollout(policy_params, obs_params, reward_params,
                               key, tokens, frame0, step0, cfg=cfg, wm=wm)
    return jax.jit(fn)


def imagine_segment(*args, **kwargs):
    """Alias kept for the public API (one τ̂ segment per call)."""
    return imagine_rollout(*args, **kwargs)


class ImaginationWorker(Service):
    """Generates imagined segments from real seed frames in B_wm and pushes
    them to B_img — the WM-mode replacement for environment interaction.
    An imagination *producer service* registered on the bus by the
    world-model attachment."""

    def __init__(self, worker_id: int, cfg: ModelConfig, wm: WMConfig,
                 store, wm_params_ref, frame_channel, img_channel, *,
                 batch: int = 16, seed: int = 0):
        super().__init__(f"imagination-{worker_id}", role="imagination")
        self.cfg, self.wm = cfg, wm
        self.store = store                    # policy weight store
        self.wm_params_ref = wm_params_ref    # dict with obs/reward params
        self.frame_channel = frame_channel    # B_wm (real transitions)
        self.img_channel = img_channel        # B_img
        self.batch = batch
        self._fn = make_imagine_fn(cfg, wm)
        self._key = jax.random.PRNGKey(seed + 7777)

    @property
    def segments_done(self) -> int:
        return int(self.metrics.counter("segments"))

    @property
    def imagined_steps(self) -> int:
        return int(self.metrics.counter("imagined_steps"))

    def _run(self) -> None:
        params, version = None, -1
        while not self._stop.is_set():
            got = self.store.acquire(newer_than=-1, timeout=0.2)
            if got is None:
                continue
            params, version = got
            seeds = self.frame_channel.sample(self.batch)
            if seeds is None:
                time.sleep(0.05)
                continue
            tokens = np.stack([s["tokens"] for s in seeds])
            frames = np.stack([s["frame"] for s in seeds]).astype(np.float32)
            steps = np.array([s["step"] for s in seeds], np.int32)
            self._key, sub = jax.random.split(self._key)
            # the imagined batch's trace id: the policy version it was
            # dreamed under, so wm.imagine lines up with the
            # weights.publish flow on the Perfetto timeline
            with _tel.span("wm.imagine", cat="wm", trace=int(version),
                           args={"batch": self.batch,
                                 "horizon": self.wm.imagine_horizon,
                                 "version": int(version)}, flow="step"):
                with self.metrics.timer("busy_s"):
                    out = self._fn(params, self.wm_params_ref["obs"],
                                   self.wm_params_ref["reward"], sub, tokens,
                                   frames, steps)
                    out = {k: np.asarray(v) for k, v in out.items()}
            for i in range(self.batch):
                self.img_channel.put({
                    "obs_tokens": out["obs_tokens"][i],
                    "frames": out["frames"][i],
                    "actions": out["actions"][i],
                    "behavior_logp": out["behavior_logp"][i],
                    "behavior_value": out["behavior_value"][i],
                    "rewards": out["rewards"][i],
                    "dones": out["dones"][i],
                    "steps": out["steps"][i],
                    "mask": out["mask"][i],
                    "policy_version": np.int32(version),
                    "task_id": np.int32(0),
                    "success": np.float32(0.0),
                })
            self.metrics.inc("segments", self.batch)
            self.metrics.inc("imagined_steps",
                             self.batch * self.wm.imagine_horizon)
