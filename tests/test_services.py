"""Service / Scheduler / ExperienceChannel architecture tests: the uniform
lifecycle, crash containment, the metrics registry, channel backpressure
policies, the real/imagined experience mix, the dynamic step barrier, and
the one-code-path guarantee (sync and async emit the same metric schema)."""
import threading
import time

import numpy as np
import pytest

from repro.runtime import (FifoChannel, MetricsRegistry,
                           MixedExperienceSource, RingChannel, Service,
                           ServiceRegistry, ServiceState)
from repro.runtime.scheduler import BarrierGate, _DynamicBarrier


# ---------------------------------------------------------------------------
# Service lifecycle
# ---------------------------------------------------------------------------

class _Ticker(Service):
    def __init__(self, name="ticker", fail=False):
        super().__init__(name, role="test")
        self.fail = fail

    def _run(self):
        if self.fail:
            raise RuntimeError("boom")
        while not self._stop.is_set():
            self.metrics.inc("ticks")
            time.sleep(0.005)


def test_service_lifecycle_states():
    s = _Ticker()
    assert s.status == ServiceState.NEW
    s.start()
    assert s.status == ServiceState.RUNNING
    with pytest.raises(RuntimeError):
        s.start()                      # double-start is a caller bug
    time.sleep(0.05)
    s.stop()
    s.join()
    assert s.status == ServiceState.STOPPED
    assert s.healthy
    assert s.metrics.counter("ticks") > 0
    assert s.uptime_s > 0


def test_service_crash_marks_failed():
    s = _Ticker(fail=True).start()
    s.join(timeout=2.0)
    assert s.status == ServiceState.FAILED
    assert not s.healthy
    assert "boom" in repr(s.error)
    assert "boom" in s.health()["error"]


def test_service_stop_before_start_is_safe():
    s = _Ticker()
    s.stop()
    assert s.status == ServiceState.STOPPED


def test_registry_orders_and_roles():
    reg = ServiceRegistry()
    a = reg.register(_Ticker("a"))
    b = reg.register(_Ticker("b"))
    with pytest.raises(ValueError):
        reg.register(_Ticker("a"))     # duplicate name
    assert [s.name for s in reg.all(role="test")] == ["a", "b"]
    reg.start_all(exclude_roles=("test",))
    assert a.status == ServiceState.NEW     # excluded role untouched
    reg.start_all()
    reg.stop_all()
    reg.join_all()
    assert all(h["state"] == ServiceState.STOPPED
               for h in reg.health().values())
    assert set(reg.snapshot()) == {"a", "b"}
    assert reg.deregister("a") is a
    assert "a" not in reg
    assert b is reg.get("b")


def test_metrics_registry():
    m = MetricsRegistry("t")
    assert m.inc("c", 2.0) == 2.0
    assert m.inc("c") == 3.0
    m.set_gauge("g", 7.0)
    m.record("s", 1.0)
    m.record("s", 3.0)
    assert m.series_mean("s") == 2.0
    with m.timer("busy_s"):
        time.sleep(0.01)
    snap = m.snapshot()
    assert snap["counters"]["c"] == 3.0
    assert snap["counters"]["busy_s"] >= 0.01
    assert snap["gauges"]["g"] == 7.0
    assert snap["series"]["s"] == {"count": 2, "mean": 2.0, "last": 3.0}


# ---------------------------------------------------------------------------
# ExperienceChannel backpressure policies
# ---------------------------------------------------------------------------

def test_fifo_channel_drop_oldest():
    ch = FifoChannel(2, policy="drop_oldest")
    assert all(ch.put(i) for i in range(4))
    assert ch.total_dropped == 2
    assert ch.pop_batch(2, timeout=0.1) == [2, 3]


def test_fifo_channel_drop_newest():
    ch = FifoChannel(2, policy="drop_newest")
    assert ch.put(0) and ch.put(1)
    assert not ch.put(2)               # rejected, queued data wins
    assert ch.total_dropped == 1
    assert ch.pop_batch(2, timeout=0.1) == [0, 1]


def test_fifo_channel_block_waits_for_consumer():
    ch = FifoChannel(1, policy="block", block_timeout=2.0)
    ch.put(0)
    t = threading.Thread(target=lambda: (time.sleep(0.05),
                                         ch.pop_batch(1, timeout=1.0)))
    t0 = time.monotonic()              # before the start: the pop comes
    t.start()                          # 0.05 s after t0 at the earliest
    assert ch.put(1)                   # blocks until the pop frees a slot
    assert time.monotonic() - t0 >= 0.04
    t.join()
    assert ch.total_dropped == 0


def test_fifo_channel_block_timeout_rejects():
    ch = FifoChannel(1, policy="block", block_timeout=0.05)
    ch.put(0)
    assert not ch.put(1)
    assert ch.total_dropped == 1


def test_fifo_channel_bad_policy():
    with pytest.raises(ValueError):
        FifoChannel(4, policy="bogus")


def test_ring_channel_sampling():
    ch = RingChannel(4, seed=0)
    assert ch.sample(2) is None
    for i in range(10):
        ch.put(i)
    assert all(6 <= x < 10 for x in ch.sample(32))
    assert ch.stats()["pushed"] == 10.0


# ---------------------------------------------------------------------------
# MixedExperienceSource (B + B_img composition)
# ---------------------------------------------------------------------------

def _filled(n, tag):
    ch = FifoChannel(100)
    for i in range(n):
        ch.put((tag, i))
    return ch

def test_mixed_source_respects_ratio():
    src = MixedExperienceSource(_filled(10, "real"), _filled(10, "img"),
                                real_fraction=0.5)
    batch = src.pop_batch(8, timeout=1.0)
    tags = [t for t, _ in batch]
    assert tags.count("real") == 4 and tags.count("img") == 4
    assert src.real_consumed == 4 and src.imagined_consumed == 4


def test_mixed_source_pure_imagined():
    src = MixedExperienceSource(_filled(10, "real"), _filled(10, "img"),
                                real_fraction=0.0)
    batch = src.pop_batch(6, timeout=1.0)
    assert all(t == "img" for t, _ in batch)


def test_mixed_source_backfills_on_starvation():
    src = MixedExperienceSource(_filled(10, "real"), _filled(2, "img"),
                                real_fraction=0.25)
    batch = src.pop_batch(8, timeout=1.0)
    tags = [t for t, _ in batch]
    assert len(batch) == 8
    assert tags.count("img") == 2      # all that existed
    assert tags.count("real") == 6     # real backfilled beyond its 25%


def test_mixed_source_timeout_carries_partial():
    real, img = _filled(3, "real"), _filled(0, "img")
    src = MixedExperienceSource(real, img, real_fraction=1.0)
    assert src.pop_batch(8, timeout=0.05) is None    # only 3 available
    for i in range(5):
        real.put(("real", 100 + i))
    batch = src.pop_batch(8, timeout=1.0)
    assert len(batch) == 8             # the 3 carried + 5 fresh, none lost
    assert src.real_consumed == 8


def test_mixed_source_zero_fraction_is_a_hard_pin():
    """real_fraction=0.0 (paper §4) must NEVER consume real segments, even
    when imagination is starved — it waits instead of diluting the diet."""
    real, img = _filled(10, "real"), _filled(0, "img")
    src = MixedExperienceSource(real, img, real_fraction=0.0)
    assert src.pop_batch(4, timeout=0.05) is None
    assert src.real_consumed == 0 and len(real) == 10
    for i in range(4):
        img.put(("img", i))
    assert all(t == "img" for t, _ in src.pop_batch(4, timeout=1.0))


def test_mixed_source_one_fraction_is_a_hard_pin():
    real, img = _filled(0, "real"), _filled(10, "img")
    src = MixedExperienceSource(real, img, real_fraction=1.0)
    assert src.pop_batch(4, timeout=0.05) is None
    assert src.imagined_consumed == 0 and len(img) == 10


def test_mixed_source_rejects_bad_fraction():
    with pytest.raises(ValueError):
        MixedExperienceSource(_filled(1, "r"), _filled(1, "i"),
                              real_fraction=1.5)


# ---------------------------------------------------------------------------
# Dynamic step barrier (sync mode)
# ---------------------------------------------------------------------------

def test_dynamic_barrier_lockstep_and_leave():
    barrier = _DynamicBarrier()
    stop = threading.Event()
    arrived = []
    lock = threading.Lock()

    def worker(idx, steps):
        barrier.join()
        for s in range(steps):
            barrier.wait(stop)
            with lock:
                arrived.append((idx, s))
        barrier.leave()

    ts = [threading.Thread(target=worker, args=(i, 3 if i == 0 else 5))
          for i in range(3)]
    # stagger the joins so parties grows while others already wait
    for t in ts:
        t.start()
        time.sleep(0.01)
    for t in ts:
        t.join(timeout=5.0)
    assert not any(t.is_alive() for t in ts), "barrier deadlocked"
    # worker 0 leaves after 3 steps; the other two still finish 5 each
    assert len(arrived) == 3 + 5 + 5


def test_barrier_gate_permits_are_episode_quota():
    gate = BarrierGate(lockstep=False)
    stop = threading.Event()
    gate.release(2)
    assert gate.begin_episode(stop)
    assert gate.begin_episode(stop)
    got = []
    t = threading.Thread(target=lambda: got.append(gate.begin_episode(stop)))
    t.start()
    time.sleep(0.1)
    assert not got                     # quota exhausted: worker parked
    stop.set()
    t.join(timeout=2.0)
    assert got == [False]              # released by shutdown, not a permit


def test_barrier_gate_counts_aborted_episodes():
    """end_episode fires for aborted episodes too, so a permit can never
    leak and stall a sync round at the episode barrier."""
    gate = BarrierGate(lockstep=True)
    stop = threading.Event()
    gate.release(2)
    assert gate.begin_episode(stop)
    gate.end_episode()                 # completed normally
    assert gate.begin_episode(stop)
    gate.end_episode()                 # aborted mid-episode: still counted
    assert gate.completed == 2


def test_scheduler_fail_fast_on_crashed_service():
    from repro.runtime.scheduler import Scheduler

    class _Sys:
        registry = ServiceRegistry()
    t = _Sys.registry.register(_Ticker("crasher", fail=True)).start()
    t.join(timeout=2.0)
    assert Scheduler._failed(_Sys)     # poll loops break instead of spinning


# ---------------------------------------------------------------------------
# one code path, one schema: sync and async metrics agree
# ---------------------------------------------------------------------------

def _tiny_system(**kw):
    from repro.configs import get_config, reduced
    from repro.configs.base import RLConfig, RuntimeConfig
    from repro.runtime import AcceRLSystem
    cfg = reduced(get_config("deepseek-7b"), layers=2, d_model=64)
    rl = RLConfig(grad_accum=1, lr_policy=1e-4, lr_value=1e-3)
    rt = RuntimeConfig(num_rollout_workers=2, inference_batch=4)
    return AcceRLSystem(cfg, rl, rt, suite="spatial", segment_horizon=4,
                        max_episode_steps=8, batch_episodes=4, **kw)


@pytest.mark.slow
def test_sync_and_async_share_code_path_and_schema():
    """Acceptance: run_sync and run_async drive the SAME services; both
    reach the step budget and emit identical metric keys."""
    ma = _tiny_system(seed=0).run_async(train_steps=2, wall_timeout_s=240.0)
    ms = _tiny_system(seed=1).run_sync(train_steps=2, episodes_per_round=2,
                                       wall_timeout_s=240.0)
    assert ma["train_steps"] >= 2 and ms["train_steps"] >= 2
    assert ma["env_steps"] > 0 and ms["env_steps"] > 0
    assert set(ma) == set(ms)
    for key in ("wall_s", "train_steps", "env_steps", "episodes", "sps_env",
                "sps_train", "trainer_util", "inference_util",
                "mean_policy_lag", "mean_return", "success_rate",
                "buffer_dropped", "inference_batches", "sync_latency_s"):
        assert key in ma, key          # the pre-refactor schema, preserved


@pytest.mark.slow
def test_wm_attaches_without_subclassing():
    """Acceptance: the world model registers services on the bus of a plain
    AcceRLSystem — no orchestrator subclass anywhere."""
    from repro.configs.base import WMConfig
    from repro.runtime import AcceRLSystem
    from repro.wm import AcceRLWMSystem, WorldModelAttachment

    from repro.configs import get_config, reduced
    from repro.configs.base import RLConfig, RuntimeConfig

    cfg = reduced(get_config("deepseek-7b"), layers=2, d_model=64)
    rl = RLConfig(grad_accum=1, lr_policy=1e-4, lr_value=1e-3)
    rt = RuntimeConfig(num_rollout_workers=2, inference_batch=4)
    wm = WMConfig(imagine_horizon=2, history_frames=2, diffusion_steps=4,
                  obs_train_interval=2, reward_train_interval=5)
    sys_ = AcceRLWMSystem(cfg, rl, rt, wm, suite="spatial",
                          segment_horizon=4, max_episode_steps=8,
                          imagination_batch=4)
    assert type(sys_) is AcceRLSystem
    assert isinstance(sys_.attachments[0], WorldModelAttachment)
    # the SAME trainer service, rewired onto the mixed (B, B_img) source
    assert sys_.img_trainer is sys_.trainer
    assert isinstance(sys_.trainer.source, MixedExperienceSource)
    names = set(sys_.registry.snapshot())
    assert {"inference", "trainer", "wm-trainer",
            "imagination-0"} <= names
    m = sys_.run_wm(train_steps=1, wall_timeout_s=240.0)
    assert m["img_train_steps"] >= 1
    assert m["imagined_steps"] > 0
    assert set(m["wm_updates"]) == {"obs", "reward"}
    assert m["real_env_steps"] == m["env_steps"]


def test_mixed_diet_rejects_horizon_mismatch():
    """A mixed real/imagined diet collates both segment kinds into one
    super-batch — bind() must refuse mismatched time axes loudly instead
    of letting np.stack die inside the prefetcher thread."""
    from repro.configs import get_config, reduced
    from repro.configs.base import RLConfig, RuntimeConfig, WMConfig
    from repro.wm import AcceRLWMSystem

    cfg = reduced(get_config("deepseek-7b"), layers=2, d_model=64)
    rl = RLConfig(grad_accum=1)
    wm = WMConfig(imagine_horizon=2, history_frames=2, diffusion_steps=4)
    rt = RuntimeConfig(num_rollout_workers=1, mix_real_fraction=0.25)
    with pytest.raises(ValueError, match="segment_horizon"):
        AcceRLWMSystem(cfg, rl, rt, wm, segment_horizon=4,
                       max_episode_steps=8)
    # matching horizons bind fine; the pure-imagined extreme (0.0) never
    # mixes kinds, so mismatched horizons stay allowed there
    AcceRLWMSystem(cfg, rl, rt, wm, segment_horizon=2, max_episode_steps=8)
    rt0 = RuntimeConfig(num_rollout_workers=1, mix_real_fraction=0.0)
    AcceRLWMSystem(cfg, rl, rt0, wm, segment_horizon=4, max_episode_steps=8)
