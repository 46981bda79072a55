"""Refusals that keep a failed chip run from passing as a short success,
checked on the CPU: a crashed service fails the scheduled run, a child
process that would open the accelerator its parent holds is refused at
launch, and the persistent compile cache goes where the environment or
the fixed in-checkout default says."""
import pathlib
import time

import jax
import pytest

from repro.configs import get_config, reduced
from repro.configs.base import (RLConfig, RuntimeConfig, SupervisionConfig,
                                TransportConfig)
from repro.launch import compile_cache
from repro.runtime import AcceRLSystem, ServiceFailure
from repro.runtime.transport import supervision
from repro.runtime.transport.remote import RemoteWorkerSpec

ROOT = pathlib.Path(__file__).resolve().parents[1]
CFG = reduced(get_config("deepseek-7b"), layers=2, d_model=64)


def _tiny_system(rt: RuntimeConfig = None) -> AcceRLSystem:
    rl = RLConfig(grad_accum=1, lr_policy=1e-4, lr_value=1e-3)
    rt = rt or RuntimeConfig(num_rollout_workers=2, inference_batch=4)
    return AcceRLSystem(CFG, rl, rt, suite="spatial", segment_horizon=4,
                        max_episode_steps=8, batch_episodes=4)


# ---------------------------------------------------------------------------
# a crashed service fails the run
# ---------------------------------------------------------------------------

def test_crashed_service_makes_run_async_raise():
    sys_ = _tiny_system()

    def broken_inference(*args, **kwargs):
        raise RuntimeError("inference program failed to compile")
    sys_.inference._fn = broken_inference

    t0 = time.monotonic()
    with pytest.raises(ServiceFailure) as exc:
        sys_.run_async(train_steps=2, wall_timeout_s=120.0)
    assert time.monotonic() - t0 < 60.0, "the crash was not acted on"
    crash = exc.value.crashes[0]
    assert crash["service"] == "inference"
    assert "failed to compile" in crash["error"]
    assert "Traceback" in crash["traceback"]
    # the run was still wound down in order, and its metrics survive
    assert "services" in exc.value.metrics
    assert sys_.health()["trainer"]["state"] == "stopped"


# ---------------------------------------------------------------------------
# one process per chip
# ---------------------------------------------------------------------------

def _spec(**kw) -> RemoteWorkerSpec:
    return RemoteWorkerSpec(name="remote-rollout-0", cfg=CFG, rl=RLConfig(),
                            rt=RuntimeConfig(), address=("127.0.0.1", 1),
                            **kw)


def test_parent_on_cpu_holds_no_device():
    assert not supervision.parent_holds_device()
    supervision.check_spawn(_spec())          # CPU tests spawn as before


def test_spawn_needing_the_device_is_refused(monkeypatch):
    monkeypatch.setattr(supervision, "parent_holds_device", lambda: True)
    endpoint = supervision.SpawnedEndpoint()
    for spec in (_spec(),                              # colocated pool
                 _spec(kind="inference")):             # the shared tier
        with pytest.raises(RuntimeError, match="refusing to spawn"):
            endpoint.launch(spec)
        assert endpoint.process is None                # nothing started
    # an env-only child that sends its requests to this process needs
    # no device of its own
    supervision.check_spawn(_spec(inference="remote",
                                  infer_address=("127.0.0.1", 1)))


def test_refused_spawn_fails_the_run(monkeypatch):
    monkeypatch.setattr(supervision, "parent_holds_device", lambda: True)
    rt = RuntimeConfig(
        num_rollout_workers=1, inference_batch=4,
        transport=TransportConfig(
            remote_rollout_workers=1,
            supervision=SupervisionConfig(restart="on_failure")))
    sys_ = _tiny_system(rt)
    with pytest.raises(ServiceFailure) as exc:
        sys_.run_async(train_steps=2, wall_timeout_s=60.0)
    crash = exc.value.crashes[0]
    assert crash["service"] == "supervisor"
    assert "refusing to spawn" in crash["error"]


# ---------------------------------------------------------------------------
# compile cache placement
# ---------------------------------------------------------------------------

def test_compile_cache_honours_the_environment(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # no other path


def test_compile_cache_default_is_fixed_in_the_checkout(monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first = compile_cache.enable_compile_cache()
        assert first == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
        assert compile_cache.enable_compile_cache() == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()

