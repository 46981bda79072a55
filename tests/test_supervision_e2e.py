"""Supervision end-to-end acceptance (ISSUE 4): a Supervisor-hosted
rollout worker is SIGKILLed mid-run and the system keeps training —
respawned (spawn mode) or re-accepted on redial (connect mode) within its
restart budget, with `metrics()["services"]` showing a single healthy
worker entry whose counters stay monotonic across the restart; exhausting
the budget surfaces FAILED exactly as PR 3's containment did.

These spawn jax-initializing subprocesses — slow by nature; CI runs them
in the dedicated supervision-smoke job under a hard SIGKILL timeout."""
import multiprocessing
import os
import signal
import threading
import time

import pytest

from repro.configs import get_config, reduced
from repro.configs.base import (RLConfig, RuntimeConfig, SupervisionConfig,
                                TransportConfig)
from repro.runtime import ServiceFailure


def _system(*, spawn_workers=0, connect_workers=0, local_workers=0,
            restart="on_failure", max_restarts=2, seed=0,
            liveness_timeout_s=1.0):
    from repro.runtime import AcceRLSystem
    cfg = reduced(get_config("deepseek-7b"), layers=2, d_model=64)
    rl = RLConfig(grad_accum=1, lr_policy=1e-4, lr_value=1e-3)
    rt = RuntimeConfig(
        num_rollout_workers=local_workers, inference_batch=4,
        transport=TransportConfig(
            remote_rollout_workers=spawn_workers,
            connect_rollout_workers=connect_workers,
            heartbeat_s=0.1, token="e2e-token",
            reconnect_attempts=3,
            supervision=SupervisionConfig(
                restart=restart, max_restarts=max_restarts,
                backoff_initial_s=0.05, backoff_max_s=0.5,
                liveness_timeout_s=liveness_timeout_s)))
    return AcceRLSystem(cfg, rl, rt, suite="spatial", segment_horizon=4,
                        max_episode_steps=8, batch_episodes=4, seed=seed)


@pytest.mark.slow
def test_spawned_worker_sigkill_is_respawned_within_budget():
    """Acceptance (spawn mode): SIGKILL the only rollout worker mid-run;
    the Supervisor respawns it, training reaches its budget, and the
    service report shows ONE healthy worker entry with monotonic
    counters."""
    sys_ = _system(spawn_workers=1, restart="on_failure", seed=0)
    slot = sys_.remote_hosts[0]
    steps_at_kill = [0]

    def killer():
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            if slot.env_steps > 0 and slot.process is not None:
                steps_at_kill[0] = slot.env_steps
                os.kill(slot.process.pid, signal.SIGKILL)
                return
            time.sleep(0.05)

    t = threading.Thread(target=killer, daemon=True)
    t.start()
    m = sys_.run_async(train_steps=2, wall_timeout_s=240.0)
    t.join(timeout=5.0)

    assert steps_at_kill[0] > 0, "killer never fired"
    assert m["train_steps"] >= 2
    assert slot.restarts >= 1
    # single coherent worker entry, not one per incarnation
    names = [n for n in m["services"] if n.startswith("remote-rollout")]
    assert names == ["remote-rollout-0"]
    entry = m["services"]["remote-rollout-0"]
    assert entry["counters"]["restarts"] >= 1
    # monotonic across the restart: the final total includes the dead
    # incarnation's work (the killed process had made progress)
    assert entry["counters"]["env_steps"] >= steps_at_kill[0]
    # clean end state: the slot was healthy post-restart and stopped
    health = sys_.health()
    assert health["remote-rollout-0"]["state"] == "stopped", health
    assert health["remote-rollout-0"]["error"] is None
    assert health["supervisor"]["state"] == "stopped"
    assert not slot.process.is_alive()


@pytest.mark.slow
def test_budget_zero_surfaces_failed_like_pr3():
    """Acceptance (budget exhaustion): with a zero restart budget the
    first SIGKILL exhausts it — the slot surfaces FAILED and the run
    returns promptly, exactly PR 3's containment."""
    sys_ = _system(spawn_workers=1, local_workers=1, restart="on_failure",
                   max_restarts=0, seed=1)
    slot = sys_.remote_hosts[0]

    def killer():
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            if slot.env_steps > 0 and slot.process is not None:
                os.kill(slot.process.pid, signal.SIGKILL)
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

    assert wall < 150.0, "exhaustion was not contained — hit wall timeout"
    health = sys_.health()
    assert health["remote-rollout-0"]["state"] == "failed"
    assert "restart budget exhausted" in health["remote-rollout-0"]["error"]
    assert health["trainer"]["state"] == "stopped"
    assert "services" in m and "remote-rollout-0" in m["services"]


def _connect_worker(address, token):
    """Child body for a connect-mode worker process (module-level so the
    spawn start method can pickle it)."""
    import sys
    from repro.launch.worker import run
    sys.exit(run(f"{address[0]}:{address[1]}", token=token,
                 hello_timeout_s=180.0, retry_s=0.2))


@pytest.mark.slow
def test_connect_worker_kill_and_redial_is_reaccepted():
    """Acceptance (connect mode): a dialed-in worker is SIGKILLed; a NEW
    worker process redials and is re-accepted into the same slot within
    the restart budget; the trainer reaches its budget and the slot ends
    healthy with monotonic counters."""
    ctx = multiprocessing.get_context("spawn")
    sys_ = _system(connect_workers=1, restart="on_failure", max_restarts=3,
                   seed=2, liveness_timeout_s=1.0)
    slot = sys_.remote_hosts[0]
    address = sys_.transport_server.address
    procs = []

    def controller():
        deadline = time.monotonic() + 200.0
        w1 = ctx.Process(target=_connect_worker,
                         args=(address, "e2e-token"), daemon=True)
        w1.start()
        procs.append(w1)
        while time.monotonic() < deadline:       # let it produce, then kill
            if slot.env_steps > 0:
                break
            time.sleep(0.05)
        steps_at_kill = slot.env_steps
        os.kill(w1.pid, signal.SIGKILL)
        w2 = ctx.Process(target=_connect_worker,
                         args=(address, "e2e-token"), daemon=True)
        w2.start()                               # redials until re-accepted
        procs.append(w2)
        return steps_at_kill

    result = {}
    t = threading.Thread(
        target=lambda: result.update(steps=controller()), daemon=True)
    t.start()
    m = sys_.run_async(train_steps=2, wall_timeout_s=240.0)
    t.join(timeout=10.0)

    assert m["train_steps"] >= 2
    assert result.get("steps", 0) > 0, "first worker never produced"
    assert slot.restarts >= 1, "kill was never detected as a restart"
    assert slot.incarnation >= 2, "redial was not re-accepted"
    names = [n for n in m["services"] if n.startswith("connect-rollout")]
    assert names == ["connect-rollout-0"]
    entry = m["services"]["connect-rollout-0"]
    assert entry["counters"]["env_steps"] >= result["steps"]
    health = sys_.health()
    assert health["connect-rollout-0"]["state"] == "stopped", health
    assert health["connect-rollout-0"]["error"] is None
    # the replacement worker saw the stop flag (or the server vanish) and
    # exited on its own; the first one died by our SIGKILL
    for p in procs:
        p.join(timeout=30.0)
        if p.is_alive():                      # never leak a worker process
            p.kill()
            p.join(timeout=5.0)
    assert procs[0].exitcode == -signal.SIGKILL
    assert not procs[1].is_alive()
