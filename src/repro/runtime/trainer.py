"""Trainer worker (paper §3.1, App. C/D).

Continuously pops prefetched super-batches from its experience source
(never waiting on rollouts — macro-asynchrony), runs the GIPO + JIT-GAE
train step, and publishes versioned weights through the store with the
drain protocol. ``weight_sync_interval`` throttles publishes ("broadcast
only when an actual update occurs").

The trainer is a :class:`~repro.runtime.service.Service`. Two drive modes,
same train path:

  * free-running (``start``) — the asynchronous pipeline: the service
    thread pops from the prefetcher and steps continuously;
  * inline (``begin_inline`` + ``train_on_batch``) — the barrier scheduler
    drives steps between rollout rounds, reproducing the synchronous
    baseline's cluster barrier without duplicating any training code.

The source is any ``pop_batch(n, timeout)`` provider — the real segment
channel ``B``, or a :class:`~repro.runtime.experience.MixedExperienceSource`
blending ``B`` and ``B_img`` when a world model is attached.
"""
from __future__ import annotations

import functools
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, RLConfig, RuntimeConfig
from repro.core.train_step import (TrainState, init_train_state,
                                   state_shardings)
from repro.data.prefetch import Prefetcher
from repro.data.trajectory import TrajectoryBatch
from repro.models.transformer import FRONTEND_DIM
from repro.runtime import telemetry as _tel
from repro.runtime.service import Service
from repro.runtime.weight_store import VersionedWeightStore


def collate_segments(segments: List[Dict[str, np.ndarray]],
                     metrics=None) -> TrajectoryBatch:
    """Stack rollout segments into a TrajectoryBatch (prefetcher thread).

    When tracing is on, rollout workers stamp ``_trace``/``_t_put`` into
    each segment; the trainer-side span here closes the per-episode flow
    (rollout.put -> server.apply -> trainer.collate) and the end-to-end
    batch age lands in the ``batch_age_s`` histogram.
    """
    if _tel.RECORDING:
        now = time.time()
        for s in segments:
            trace = s.get("_trace")
            if trace is None:
                continue
            _tel.instant("trainer.collate", cat="trainer",
                         trace=int(trace),
                         args={"batch": len(segments)}, flow="end")
            if metrics is not None and s.get("_t_put") is not None:
                metrics.observe("batch_age_s",
                                max(now - float(s["_t_put"]), 0.0))
    stack = lambda k: np.stack([s[k] for s in segments])
    frames = stack("frames")                        # [B, T+1, F_env]
    b, tp1, f = frames.shape
    prefix = np.zeros((b, tp1, 1, FRONTEND_DIM), np.float32)
    prefix[..., 0, :min(f, FRONTEND_DIM)] = frames[..., :FRONTEND_DIM]
    return TrajectoryBatch(
        obs_tokens=stack("obs_tokens").astype(np.int32),
        actions=stack("actions").astype(np.int32),
        behavior_logp=stack("behavior_logp").astype(np.float32),
        behavior_value=stack("behavior_value").astype(np.float32),
        rewards=stack("rewards").astype(np.float32),
        dones=stack("dones").astype(np.float32),
        steps=stack("steps").astype(np.int32),
        mask=stack("mask").astype(np.float32),
        policy_version=stack("policy_version").astype(np.int32),
        prefix_embeds=prefix,
    )


class TrainerWorker(Service):
    def __init__(self, cfg: ModelConfig, rl: RLConfig, rt: RuntimeConfig,
                 source, store: VersionedWeightStore, *,
                 batch_episodes: int = 8, seed: int = 0,
                 checkpoint_dir=None, checkpoint_interval: int = 0,
                 name: str = "trainer"):
        super().__init__(name, role="trainer")
        self.cfg, self.rl, self.rt = cfg, rl, rt
        self.source = source
        self.store = store

        # Both drive modes build the step through the same IR
        # (runtime/step_program.py) and materialize optimizer moments
        # under the ZeRO-2 shardings (no-op on one device).
        from repro.runtime import step_program
        n_micro = rt.pipeline_microbatches or rl.grad_accum
        if rt.pipeline:
            from repro.runtime import pipeline_exec
            self._layout = pipeline_exec.SubmeshLayout.split(
                jax.devices(), wm_devices=rt.pipeline_wm_devices)
            self._mesh = self._layout.policy.mesh()
            self.program = step_program.build_train_step_program(
                cfg, rl, n_micro=n_micro, mesh=self._mesh)
            self.state: TrainState = init_train_state(
                cfg, jax.random.PRNGKey(seed), mesh=self._mesh)
            self.pipeline = pipeline_exec.PipelineExecutor(
                self.program, self._layout, n_micro=n_micro,
                metrics=self.metrics)
            self._step_fn = None
        else:
            from repro.launch.mesh import make_local_mesh
            self._mesh = make_local_mesh()
            mesh = self._mesh if self._mesh.devices.size > 1 else None
            self.program = step_program.build_train_step_program(
                cfg, rl, n_micro=n_micro, mesh=mesh)
            self.state = init_train_state(
                cfg, jax.random.PRNGKey(seed), mesh=self._mesh)
            self.pipeline = None
            # the step updates the state in place (donated): without it a
            # second copy of params + f32 moments is live across the step
            self._step_fn = self.program.fused(
                donate=True,
                state_shardings=(state_shardings(cfg, mesh) if mesh
                                 else None))
        self.prefetcher = Prefetcher(
            source, batch_episodes,
            functools.partial(collate_segments, metrics=self.metrics),
            depth=rt.prefetch_depth,
            drain_timeout_s=rt.prefetch_drain_timeout_s,
            idle_timeout_max_s=rt.prefetch_idle_timeout_s,
            stage_batches=rt.prefetch_staging,
            to_device=rt.prefetch_to_device)
        self.checkpoint_dir = checkpoint_dir
        self.checkpoint_interval = checkpoint_interval
        self.metrics_log: List[Dict] = []

    # -- registry-backed counters ----------------------------------------------
    @property
    def steps_done(self) -> int:
        return int(self.metrics.counter("steps"))

    @property
    def samples_seen(self) -> int:
        return int(self.metrics.counter("samples"))

    @property
    def policy_lag(self) -> List[float]:
        return self.metrics.series("policy_lag")

    @property
    def busy_s(self) -> float:
        return self.metrics.counter("busy_s")

    def _publish(self, version: int, step: int = 0) -> None:
        """Publish weights and open the policy-lag trace flow. The version
        is the flow id on both ends, so publish -> acquire -> first action
        line up in the trace viewer without any shared state.

        The store gets a copy: the inference tier may still be serving a
        published version after the next step has donated the state."""
        self.store.publish(jax.tree.map(jnp.copy, self.state.params),
                           version)
        if _tel.RECORDING:
            _tel.instant("weights.publish", cat="weights", trace=version,
                         args={"version": version, "step": step},
                         flow="start")

    # -- lifecycle -------------------------------------------------------------
    def on_start(self) -> None:
        # version 0 published so inference can begin before the first step
        self._publish(0)
        self.prefetcher.start()

    def begin_inline(self) -> None:
        """Scheduler-driven mode: publish v0 and mark the clock, without
        the free-running thread or the prefetcher."""
        self.started_at = time.monotonic()
        self._publish(0)

    def set_wm_stage(self, stage_fn, feed_fn, *, wm_micro: int = 1) -> None:
        """Attach the world-model trainer as the second pipeline stage
        (pipeline mode only — see WorldModelAttachment.bind)."""
        if self.pipeline is None:
            raise RuntimeError("set_wm_stage requires rt.pipeline")
        self.pipeline.set_wm_stage(stage_fn, feed_fn, wm_micro=wm_micro)

    def stop(self) -> None:
        was_running = bool(self._threads)
        super().stop()
        if was_running:
            self.prefetcher.stop()
            self.join(timeout=10.0)
        if self.pipeline is not None:
            self.pipeline.close()

    # -- loop -------------------------------------------------------------------
    def _run(self) -> None:
        while not self._stop.is_set():
            batch = self.prefetcher.get(timeout=0.2)
            if batch is None:
                continue
            self.train_on_batch(batch)

    def train_on_batch(self, batch: TrajectoryBatch) -> Dict:
        with _tel.span("trainer.step"):
            with self.metrics.timer("busy_s"):
                version = int(self.state.version)
                lag = version - float(np.mean(batch.policy_version))
                self.metrics.record("policy_lag", lag)
                self.metrics.observe("policy_lag", lag)
                with _tel.span("trainer.dispatch"):
                    if self.pipeline is not None:
                        self.state, metrics, _ = self.pipeline.run_round(
                            self.state, batch)
                    elif self._mesh.devices.size > 1:
                        # traced under the mesh so dispatched kernels run
                        # per device (kernels.dispatch._per_device)
                        with jax.set_mesh(self._mesh):
                            self.state, metrics = self._step_fn(self.state,
                                                                batch)
                    else:
                        self.state, metrics = self._step_fn(self.state,
                                                            batch)
                steps = int(self.metrics.inc("steps"))
                self.metrics.inc("samples",
                                 float(np.asarray(batch.mask).sum()))
                if steps % self.rt.weight_sync_interval == 0:
                    with _tel.span("trainer.publish"):
                        if self.rt.drain:
                            self.store.begin_publish()  # drain, App. D.6
                        self._publish(version + 1, step=steps)
                if (self.checkpoint_dir and self.checkpoint_interval
                        and steps % self.checkpoint_interval == 0):
                    from repro.data import checkpoint
                    with _tel.span("trainer.checkpoint"):
                        checkpoint.save(self.checkpoint_dir, steps,
                                        self.state)
            # the host waits here for the step's device work to finish
            with _tel.span("trainer.sync"):
                out = {k: float(v) for k, v in metrics.items()}
        out["policy_lag"] = lag
        self.metrics_log.append(out)
        return out

    # -- metrics -----------------------------------------------------------------
    def sps(self) -> float:
        if not self.started_at:
            return 0.0
        return self.samples_seen / max(
            time.monotonic() - self.started_at, 1e-9)
