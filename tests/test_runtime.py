"""Runtime integration tests: buffers, weight store + drain, the
dynamic-window batching trigger, segmenting, and a short end-to-end async
run (trainer steps happen, policy version advances, lag bounded)."""
import time

import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.configs.base import RLConfig, RuntimeConfig
from repro.data.replay import FIFOReplayBuffer, RingReplayBuffer
from repro.runtime import (DirectTransport, DiskTransport,
                           SerializedTransport, VersionedWeightStore)
from repro.runtime.inference import pad_to_bucket
from repro.runtime.rollout import episode_to_segments


def _tiny():
    import dataclasses
    cfg = reduced(get_config("deepseek-7b"), layers=2, d_model=64)
    return dataclasses.replace(cfg, num_prefix_tokens=1)


# ---------------------------------------------------------------------------
# buffers
# ---------------------------------------------------------------------------

def test_fifo_order_and_drop():
    buf = FIFOReplayBuffer(capacity=3)
    for i in range(5):
        buf.push(i)
    assert buf.total_dropped == 2
    assert buf.pop_batch(3, timeout=0.1) == [2, 3, 4]   # oldest first


def test_fifo_nonblocking_producer():
    """Full buffer never blocks the producer (full asynchrony)."""
    buf = FIFOReplayBuffer(capacity=1)
    t0 = time.monotonic()
    for i in range(1000):
        buf.push(i)
    assert time.monotonic() - t0 < 1.0
    assert len(buf) == 1


def test_ring_buffer_sampling():
    buf = RingReplayBuffer(capacity=10)
    assert buf.sample(2) is None
    for i in range(25):
        buf.push(i)
    s = buf.sample(50)
    assert all(15 <= x < 25 for x in s)     # only the newest capacity kept


# ---------------------------------------------------------------------------
# weight store + transports + drain
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("transport", [DirectTransport(),
                                       SerializedTransport(),
                                       DiskTransport()])
def test_store_roundtrip(transport):
    import jax
    params = {"w": np.arange(6, dtype=np.float32).reshape(2, 3),
              "nested": {"b": np.ones(4, np.float32)}}
    store = VersionedWeightStore(transport=transport)
    store.publish(params, 3)
    got, v = store.acquire()
    assert v == 3
    np.testing.assert_array_equal(np.asarray(got["w"]), params["w"])
    np.testing.assert_array_equal(np.asarray(got["nested"]["b"]),
                                  params["nested"]["b"])


def test_drain_protocol():
    store = VersionedWeightStore()
    store.publish({"w": 1}, 0)
    assert not store.draining
    store.begin_publish()
    assert store.draining                 # inference stops scheduling
    store.publish({"w": 2}, 1)
    assert not store.draining             # cleared atomically with swap
    got, v = store.acquire(newer_than=0)
    assert v == 1 and got["w"] == 2


def test_acquire_blocks_until_newer():
    store = VersionedWeightStore()
    store.publish({"w": 1}, 0)
    assert store.acquire(newer_than=0, timeout=0.2) is None


# ---------------------------------------------------------------------------
# eq. 1 dynamic window
# ---------------------------------------------------------------------------

def test_bucket_padding():
    buckets = (1, 2, 4, 8, 16, 32)
    assert pad_to_bucket(1, buckets) == 1
    assert pad_to_bucket(3, buckets) == 4
    assert pad_to_bucket(9, buckets) == 16
    # regression: n > largest bucket used to return buckets[-1], making the
    # pad count negative so the stacked batch silently kept n rows
    with pytest.raises(ValueError):
        pad_to_bucket(100, buckets)
    from repro.runtime.inference import split_window
    assert split_window(100, buckets) == [32, 32, 32, 4]
    assert split_window(32, buckets) == [32]
    assert split_window(5, buckets) == [5]
    assert sum(split_window(33, buckets)) == 33


def test_oversized_window_served_in_chunks():
    """inference_batch > the largest bucket: every request still gets a
    correctly-shaped result (the window is split, not under-padded)."""
    from repro.models.policy import init_policy_params
    import jax
    cfg = _tiny()
    rt = RuntimeConfig(num_inference_workers=1, inference_batch=6,
                       inference_max_wait_s=2.0, batch_buckets=(1, 2, 4))
    store = VersionedWeightStore()
    store.publish(init_policy_params(cfg, jax.random.PRNGKey(0)), 0)
    from repro.runtime import InferenceService
    service = InferenceService(cfg, store, rt).start()
    try:
        rng = np.random.default_rng(0)
        futs = [service.submit(
            rng.integers(0, cfg.vocab_size, 12).astype(np.int32),
            rng.random(192).astype(np.float32), 0) for _ in range(6)]
        for f in futs:
            res = f.result(timeout=120.0)
            assert res["actions"].shape == (cfg.action_dim,)
        assert service.requests_served == 6
        assert service.batches_run >= 2     # 6 reqs over max bucket 4 → split
    finally:
        service.stop()


def test_dynamic_window_trigger_batch_size():
    """|Q| >= B triggers immediately; otherwise T_max bounds the wait."""
    from repro.models.policy import init_policy_params
    import jax
    cfg = _tiny()
    rt = RuntimeConfig(num_inference_workers=1, inference_batch=4,
                       inference_max_wait_s=0.5)
    store = VersionedWeightStore()
    store.publish(init_policy_params(cfg, jax.random.PRNGKey(0)), 0)
    from repro.runtime import InferenceService
    service = InferenceService(cfg, store, rt).start()
    try:
        rng = np.random.default_rng(0)
        futs = [service.submit(
            rng.integers(0, cfg.vocab_size, 12).astype(np.int32),
            rng.random(192).astype(np.float32), 0) for _ in range(4)]
        t0 = time.monotonic()
        for f in futs:
            f.result(timeout=120.0)
        # batch of 4 == B fired without waiting T_max (generous compile slack)
        assert service.batches_run >= 1
        one = service.submit(
            rng.integers(0, cfg.vocab_size, 12).astype(np.int32),
            rng.random(192).astype(np.float32), 0)
        res = one.result(timeout=60.0)     # lone request: released by T_max
        assert "actions" in res
    finally:
        service.stop()


# ---------------------------------------------------------------------------
# segmenting (eq. 2 layout)
# ---------------------------------------------------------------------------

def _fake_traj(t, a=3):
    return {
        "obs_tokens": [np.full(5, i, np.int32) for i in range(t + 1)],
        "frames": [np.full(7, i, np.float32) for i in range(t + 1)],
        "actions": [np.full(a, i, np.int32) for i in range(t + 1)],
        "behavior_logp": [np.zeros(a, np.float32)] * (t + 1),
        "values": [float(i) for i in range(t + 1)],
        "rewards": [0.1 * i for i in range(t)],
        "dones": [0.0] * (t - 1) + [1.0],
        "steps": list(range(t + 1)),
        "policy_version": 5, "task_id": 2, "success": 1.0,
    }


def test_segments_cover_episode_exactly():
    t, h = 10, 4
    segs = episode_to_segments(_fake_traj(t), h)
    assert len(segs) == 3                   # 4 + 4 + 2(padded)
    assert sum(int(s["mask"].sum()) for s in segs) == t
    # bootstrap slot of segment k = first obs of segment k+1
    np.testing.assert_array_equal(segs[0]["obs_tokens"][-1],
                                  segs[1]["obs_tokens"][0])
    # eq. 2 shapes: T+1 entries for obs/actions/μ/v, T for r/done/mask
    s = segs[0]
    assert len(s["obs_tokens"]) == h + 1
    assert len(s["rewards"]) == h
    assert s["policy_version"] == 5


def test_segment_padding_masked():
    segs = episode_to_segments(_fake_traj(5), 4)
    tail = segs[-1]
    assert tail["mask"].tolist() == [1.0, 0.0, 0.0, 0.0]
    assert tail["rewards"][1] == 0.0        # padded reward zeroed


# ---------------------------------------------------------------------------
# end-to-end async smoke
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_async_system_end_to_end():
    from repro.runtime import AcceRLSystem
    cfg = reduced(get_config("deepseek-7b"), layers=2, d_model=64)
    rl = RLConfig(grad_accum=1, lr_policy=1e-4, lr_value=1e-3)
    rt = RuntimeConfig(num_rollout_workers=2, inference_batch=4)
    sys_ = AcceRLSystem(cfg, rl, rt, suite="spatial", segment_horizon=4,
                        max_episode_steps=8, batch_episodes=4)
    m = sys_.run_async(train_steps=2, wall_timeout_s=240.0)
    assert m["train_steps"] >= 2
    assert m["env_steps"] > 0
    assert m["episodes"] > 0
    assert 0 <= m["mean_policy_lag"] < 50


def test_inference_tier_serves_published_versions():
    """A version published between two windows — no drain flag seen, as
    with ``rt.drain=False`` or a publish that lands while the tier is
    busy — is served from the next window on."""
    from repro.models.policy import init_policy_params
    import jax
    cfg = _tiny()
    rt = RuntimeConfig(num_inference_workers=1, inference_batch=1,
                       batch_buckets=(1,))
    store = VersionedWeightStore()
    params = init_policy_params(cfg, jax.random.PRNGKey(0))
    store.publish(params, 0)
    from repro.runtime import InferenceService
    service = InferenceService(cfg, store, rt).start()
    obs = np.zeros(12, np.int32)
    frame = np.zeros(192, np.float32)
    try:
        first = service.submit(obs, frame, 0).result(timeout=120.0)
        assert first["policy_version"] == 0
        store.publish(params, 1)
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            res = service.submit(obs, frame, 0).result(timeout=120.0)
            if res["policy_version"] == 1:
                break
        assert res["policy_version"] == 1
        assert service.metrics.gauge("weight_version") == 1.0
    finally:
        service.stop()
