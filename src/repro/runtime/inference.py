"""Inference-as-a-Service pool (paper §3.2).

Rollout workers submit per-env observation requests and suspend; every
inference worker drains a shared request queue and triggers a batched
forward pass under the dynamic window rule (eq. 1):

    Trigger = (|Q| >= B) ∨ (t_now − t_first >= T_max)

TPU adaptation (DESIGN.md §2): dynamic batches are padded up to the nearest
bucket size so the jitted program never recompiles for new batch shapes.

The drain protocol (App. D.6): when the weight store raises its drain flag,
workers stop scheduling NEW batches, finish the in-flight one, then swap
weights in place before resuming — update atomicity + version consistency.

The pool is a :class:`~repro.runtime.service.Service` with one thread per
``rt.num_inference_workers``. The live window parameters
(``window_batch`` / ``window_wait_s``) are mutable so a scheduler can
re-shape the eq.-1 trigger — the barrier scheduler widens the window to
one-batch-per-lockstep-tick to reproduce the synchronous step barrier.
"""
from __future__ import annotations

import queue
import threading
import time
from concurrent.futures import Future
from typing import List, Optional, Sequence

import jax
import numpy as np

from repro.configs.base import ModelConfig, RuntimeConfig
from repro.models.policy import make_inference_fn
from repro.models.transformer import FRONTEND_DIM
from repro.runtime import telemetry as _tel
from repro.runtime.service import Service
from repro.runtime.weight_store import VersionedWeightStore


class _Request:
    __slots__ = ("obs_tokens", "frame", "step", "future", "t_arrival")

    def __init__(self, obs_tokens, frame, step):
        self.obs_tokens = obs_tokens        # [T_obs] i32
        self.frame = frame                  # [F] f32 or None
        self.step = step                    # int
        self.future: Future = Future()
        self.t_arrival = time.monotonic()


def pad_to_bucket(n: int, buckets: Sequence[int]) -> int:
    """Smallest bucket that fits ``n`` requests. ``n`` larger than the
    biggest bucket is the caller's bug — windows must be split first
    (``split_window``), otherwise the pad count would go negative and the
    stacked batch would silently carry ``n`` rows instead of ``nb``."""
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(
        f"window of {n} requests exceeds the largest batch bucket "
        f"{buckets[-1]}; split the window before padding")


def split_window(n: int, buckets: Sequence[int]) -> List[int]:
    """Chunk an ``n``-request window into bucket-sized pieces: full largest
    buckets, then one bucket-padded remainder."""
    top = buckets[-1]
    sizes = [top] * (n // top)
    if n % top:
        sizes.append(n % top)
    return sizes


class InferenceService(Service):
    """Centralized inference pool: one shared queue, N worker threads."""

    def __init__(self, cfg: ModelConfig, store: VersionedWeightStore,
                 rt: RuntimeConfig, *, temperature: float = 1.0, seed: int = 0):
        super().__init__("inference", role="inference")
        self.cfg = cfg
        self.store = store
        self.rt = rt
        self._fn = make_inference_fn(cfg, temperature)
        self._q: "queue.Queue[_Request]" = queue.Queue()
        self._key = jax.random.PRNGKey(seed)
        self._key_lock = threading.Lock()
        # live eq.-1 window parameters (schedulers may re-shape these)
        self.window_batch = rt.inference_batch
        self.window_wait_s = rt.inference_max_wait_s
        # versions whose first post-swap action has been trace-marked
        # (closes the publish -> acquire -> first-action flow)
        self._first_action_traced: set = set()

    # -- registry-backed counters ----------------------------------------------
    @property
    def batches_run(self) -> int:
        return int(self.metrics.counter("batches"))

    @property
    def requests_served(self) -> int:
        return int(self.metrics.counter("requests"))

    @property
    def padded_slots(self) -> int:
        return int(self.metrics.counter("padded_slots"))

    @property
    def weight_swaps(self) -> int:
        return int(self.metrics.counter("weight_swaps"))

    @property
    def degenerate_batches(self) -> int:
        return int(self.metrics.counter("degenerate_batches"))

    # -- client API -----------------------------------------------------------
    def submit(self, obs_tokens: np.ndarray, frame: Optional[np.ndarray],
               step: int) -> Future:
        """Asynchronous request; the rollout worker suspends on the future."""
        req = _Request(obs_tokens, frame, step)
        self._q.put(req)
        return req.future

    # -- service surface --------------------------------------------------------
    def _thread_targets(self):
        return [self._run] * self.rt.num_inference_workers

    # -- worker loop --------------------------------------------------------------
    def _next_key(self):
        with self._key_lock:
            self._key, sub = jax.random.split(self._key)
        return sub

    def _collect_window(self) -> List[_Request]:
        """Dynamic-window batching, eq. 1.

        The T_max timer anchors to COLLECTION start, not the first
        request's arrival: a request that sat queued while a previous
        batch was in flight would otherwise expire the window the moment
        it is picked up, dispatching degenerate 1-item batches exactly
        when the queue is busiest (the window never gets its T_max to
        fill). Queue wait before collection is tracked separately as the
        ``queue_wait_s`` series.
        """
        reqs: List[_Request] = []
        t_start = None
        while not self._stop.is_set():
            b, t_max = self.window_batch, self.window_wait_s
            timeout = 0.002 if t_start is None else max(
                0.0, t_max - (time.monotonic() - t_start))
            try:
                r = self._q.get(timeout=max(timeout, 1e-4))
                now = time.monotonic()
                if t_start is None:
                    t_start = now
                reqs.append(r)
                wait = max(now - r.t_arrival, 0.0)
                self.metrics.record("queue_wait_s", wait)
                self.metrics.observe("queue_wait_s", wait)
            except queue.Empty:
                pass
            if reqs and (len(reqs) >= b or
                         time.monotonic() - t_start >= t_max):
                # eq.-1 vital: how long the window took to fill (or time
                # out) from the first request picked up to dispatch
                self.metrics.observe("window_fill_s",
                                     time.monotonic() - t_start)
                return reqs
        return reqs

    def _note_swap(self, version: int) -> None:
        self.metrics.inc("weight_swaps")
        # bridged gauge: remote workers report which policy version
        # their colocated inference pool is serving
        self.metrics.set_gauge("weight_version", float(version))
        if _tel.RECORDING:
            # middle leg of the policy-lag flow (version is the flow id)
            _tel.instant("weights.acquire", cat="weights",
                         trace=int(version),
                         args={"version": int(version)}, flow="step")

    def _run(self) -> None:
        params, version = None, -1
        while not self._stop.is_set():
            # one pass of the worker loop: a stall between its stages (the
            # GIL, lost after resolving a batch wakes its clients) lies in
            # this span and in none of the stages
            with _tel.span("infer.loop"):
                # drain protocol: no NEW batch while the trainer is publishing
                if self.store.draining or params is None:
                    with _tel.span("infer.swap"):
                        got = self.store.acquire(newer_than=version,
                                                 timeout=0.1)
                    if got is not None:
                        params, version = got
                        self._note_swap(version)
                    if params is None:
                        continue
                with _tel.span("infer.collect"):
                    reqs = self._collect_window()
                if not reqs:
                    continue
                # the drain flag may have been raised while this worker was
                # parked inside _collect_window — a window carved AFTER the
                # signal is a NEW batch and must wait for the swap (update
                # atomicity: no batch starts on stale weights mid-publish).
                # The flag is up only mid-publish, so a version that landed
                # while the window filled is taken up here too.
                with _tel.span("infer.swap"):
                    while ((self.store.draining
                            or self.store.version() > version)
                           and not self._stop.is_set()):
                        got = self.store.acquire(newer_than=version,
                                                 timeout=0.1)
                        if got is not None:
                            params, version = got
                            self._note_swap(version)
                            break
                if len(reqs) == 1:
                    # a 1-item window after a non-empty wait is the shape the
                    # wait-anchoring bug produced; kept as a counter so the
                    # regression stays observable in metrics()["services"]
                    self.metrics.inc("degenerate_batches")
                # autoscaling signal: how deep the queue still is after this
                # window was carved off (ElasticPolicy consumes it bridged)
                self.metrics.set_gauge("queue_depth", float(self._q.qsize()))
                # oversized windows (window_batch > largest bucket) are split
                # into bucket-sized chunks instead of under-padding silently
                start = 0
                for size in split_window(len(reqs), self.rt.batch_buckets):
                    self._run_batch(reqs[start:start + size], params, version)
                    start += size

    def _run_batch(self, reqs: List[_Request], params, version: int) -> None:
        with self.metrics.timer("busy_s"), _tel.span("infer.batch"):
            n = len(reqs)
            with _tel.span("infer.prepare"):
                nb = pad_to_bucket(n, self.rt.batch_buckets)
                self.metrics.inc("padded_slots", nb - n)
                # autoscaling signal: fraction of the padded batch carrying
                # real requests (low fill = idle accelerator slots)
                self.metrics.set_gauge("window_fill", n / nb)
                obs = np.stack([r.obs_tokens for r in reqs] +
                               [reqs[-1].obs_tokens] * (nb - n))
                steps = np.array([r.step for r in reqs] +
                                 [reqs[-1].step] * (nb - n), np.int32)
                prefix = None
                if reqs[0].frame is not None:
                    fr = np.stack([r.frame for r in reqs] +
                                  [reqs[-1].frame] * (nb - n))
                    prefix = _frame_to_prefix(fr)
                key = self._next_key()
            with _tel.span("infer.device"):
                tokens, logps, values = self._fn(params, key, obs, steps,
                                                 prefix)
                tokens, logps, values = (np.asarray(tokens),
                                         np.asarray(logps),
                                         np.asarray(values))
            with _tel.span("infer.resolve"):
                for i, r in enumerate(reqs):
                    r.future.set_result({
                        "actions": tokens[i], "logp": logps[i],
                        "value": float(values[i]),
                        "policy_version": version,
                    })
                self.metrics.inc("batches")
                self.metrics.inc("requests", n)
                if (_tel.RECORDING
                        and version not in self._first_action_traced):
                    # closes the publish -> acquire -> first-action flow:
                    # the first batch served with this weight version
                    self._first_action_traced.add(version)
                    _tel.instant("infer.first_action", cat="weights",
                                 trace=int(version),
                                 args={"version": int(version), "batch": n},
                                 flow="end")


def _frame_to_prefix(frames: np.ndarray) -> np.ndarray:
    """[B, F_env] env frame -> [B, 1, FRONTEND_DIM] stub frontend embedding
    (zero-padded — the allowed modality-frontend carve-out)."""
    b, f = frames.shape
    out = np.zeros((b, 1, FRONTEND_DIM), np.float32)
    out[:, 0, :min(f, FRONTEND_DIM)] = frames[:, :FRONTEND_DIM]
    return out
