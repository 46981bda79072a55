"""End-to-end remote-rollout acceptance (ISSUE 3): an AcceRLSystem with a
rollout worker in a REAL spawned subprocess (SocketChannel segments +
WeightStoreTransport weights) trains to its step budget, emits the same
metric schema as the in-process run with the remote worker's snapshot
under ``metrics()["services"]``, and a SIGKILLed worker is contained as a
failed service instead of a hang.

These spawn jax-initializing subprocesses — slow by nature; CI runs them
in a dedicated multiprocess smoke job with a hard timeout."""
import os
import signal
import threading
import time

import pytest

from repro.configs import get_config, reduced
from repro.configs.base import RLConfig, RuntimeConfig, TransportConfig
from repro.runtime import ServiceFailure


def _system(*, remote_workers=1, local_workers=1, kind="socket", seed=0,
            put_window=0):
    from repro.runtime import AcceRLSystem
    cfg = reduced(get_config("deepseek-7b"), layers=2, d_model=64)
    rl = RLConfig(grad_accum=1, lr_policy=1e-4, lr_value=1e-3)
    rt = RuntimeConfig(
        num_rollout_workers=local_workers, inference_batch=4,
        transport=TransportConfig(remote_rollout_workers=remote_workers,
                                  kind=kind, put_window=put_window))
    return AcceRLSystem(cfg, rl, rt, suite="spatial", segment_horizon=4,
                        max_episode_steps=8, batch_episodes=4, seed=seed)


@pytest.mark.slow
def test_remote_rollout_e2e_schema_and_snapshot():
    """Acceptance: train N steps with a spawned rollout worker; the metric
    schema equals the in-process run's and the remote snapshot rides along."""
    m_local = _system(remote_workers=0, seed=1).run_async(
        train_steps=2, wall_timeout_s=240.0)
    # remote-only rollout: the trainer can reach its budget ONLY through
    # the wire, so remote contribution is guaranteed rather than racing
    # the child's startup against a local worker on a slow machine
    sys_ = _system(remote_workers=1, local_workers=0, seed=0)
    m = sys_.run_async(train_steps=2, wall_timeout_s=240.0)

    assert m["train_steps"] >= 2 and m["env_steps"] > 0
    # same top-level schema as the in-process run — topology is invisible
    assert set(m) == set(m_local)
    # the remote worker's snapshot is part of the parent's service report
    assert "remote-rollout-0" in m["services"]
    remote = m["services"]["remote-rollout-0"]
    assert remote["counters"].get("env_steps", 0) > 0
    assert remote["counters"].get("segments", 0) > 0
    assert remote["counters"].get("weight_swaps", 0) > 0  # pulled weights
    # ... and contributes to the aggregates like a local worker would
    host = sys_.remote_hosts[0]
    assert host.env_steps > 0 and host.reports_seen > 0
    assert m["env_steps"] >= host.env_steps
    assert {"inference", "rollout-0"} <= set(host.remote_services)
    # clean cooperative shutdown: everything stopped, nothing failed
    health = sys_.health()
    assert all(h["state"] == "stopped" for h in health.values()), health
    # the child process is really gone
    assert not host.process.is_alive()


@pytest.mark.slow
def test_remote_worker_kill_is_contained():
    """Acceptance: SIGKILL the worker mid-run — the run returns (no hang)
    and the host surfaces as a failed service with the exit code."""
    sys_ = _system(remote_workers=1, local_workers=1, seed=2)
    host = sys_.remote_hosts[0]

    def killer():
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            # wait until the child demonstrably produced data, then murder it
            if host.metrics.counter("env_steps") > 0:
                os.kill(host.process.pid, signal.SIGKILL)
                return
            time.sleep(0.05)

    t = threading.Thread(target=killer, daemon=True)
    t.start()
    t0 = time.monotonic()
    with pytest.raises(ServiceFailure) as exc:
        sys_.run_async(train_steps=1_000_000, wall_timeout_s=180.0)
    wall = time.monotonic() - t0
    t.join(timeout=5.0)
    m = exc.value.metrics
    assert [c["service"] for c in exc.value.crashes] == ["remote-rollout-0"]

    assert wall < 150.0, "kill was not contained — run hit the wall timeout"
    health = sys_.health()
    assert health["remote-rollout-0"]["state"] == "failed"
    assert "died" in health["remote-rollout-0"]["error"]
    # the rest of the system was stopped in an orderly way, and the
    # metric schema survived the crash
    assert health["trainer"]["state"] == "stopped"
    assert "services" in m and "remote-rollout-0" in m["services"]


@pytest.mark.slow
def test_remote_rollout_e2e_shm_kind():
    """The SHM data plane drives the same e2e loop (weights above the
    threshold travel via shared memory)."""
    from repro.runtime.transport.channel import shared_memory
    if shared_memory is None:
        pytest.skip("multiprocessing.shared_memory unavailable")
    sys_ = _system(remote_workers=1, local_workers=0, kind="shm", seed=3)
    m = sys_.run_async(train_steps=1, wall_timeout_s=240.0)
    assert m["train_steps"] >= 1
    remote = m["services"]["remote-rollout-0"]
    assert remote["counters"].get("env_steps", 0) > 0
    assert all(h["state"] == "stopped" for h in sys_.health().values())


@pytest.mark.slow
def test_remote_rollout_e2e_streaming_ring_kind():
    """Streaming smoke (ISSUE 5): the full async system trains with the
    remote worker flushing through the pipelined put stream into
    persistent SHM rings — zero per-message segment churn on the server,
    stream frames actually carried the segments, and shutdown leaves
    nothing failed."""
    from repro.runtime.transport.channel import shared_memory
    if shared_memory is None:
        pytest.skip("multiprocessing.shared_memory unavailable")
    sys_ = _system(remote_workers=1, local_workers=0, kind="ring", seed=4,
                   put_window=16)
    m = sys_.run_async(train_steps=2, wall_timeout_s=240.0)
    assert m["train_steps"] >= 2
    remote = m["services"]["remote-rollout-0"]
    assert remote["counters"].get("env_steps", 0) > 0
    assert remote["counters"].get("segments", 0) > 0
    server = sys_.transport_server.metrics
    # the segments crossed through the STREAM + RING data plane ...
    assert server.counter("stream_items") > 0
    assert server.counter("ring_records_in") > 0
    # ... with no per-message segment churn on the experience path (the
    # weight wire may legitimately create reply segments)
    assert server.counter("shm_segments_attached") == 0
    assert all(h["state"] == "stopped" for h in sys_.health().values())
