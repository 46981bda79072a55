"""Observability plane units: the span recorder (ring buffers, trace
context, Chrome-trace export), histogram metrics and their cross-
incarnation folds, bounded series storage, structured crash records, the
TelemetrySink service, the server's metrics.snapshot / trace.dump
endpoints, and the inference-tier saturation signal in ElasticPolicy.

The cross-PROCESS trace join (child span → worker.report → server fold →
one dump) lives in tests/test_telemetry_e2e.py (CI telemetry-smoke job).
"""
import json
import os
import pathlib
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.configs.base import RLConfig, RuntimeConfig, TelemetryConfig
from repro.runtime import telemetry
from repro.runtime.service import (HIST_BUCKETS, HIST_MIN_EXP,
                                   SERIES_WINDOW, MetricsRegistry, Service,
                                   ServiceRegistry, _hist_bucket,
                                   _hist_merge)
from repro.runtime.transport import (ElasticPolicy, RemoteWorkerSpec,
                                     RestartPolicy, Supervisor,
                                     TransportServer)
from repro.runtime.transport.channel import WireClient
from repro.runtime.transport.remote import _merge_snapshots
from repro.runtime.transport.supervision import (SupervisedWorker,
                                                 WorkerEndpoint)


@pytest.fixture(autouse=True)
def _clean_telemetry():
    telemetry.reset()
    yield
    telemetry.reset()


# ---------------------------------------------------------------------------
# gating: REPRO_TRACE decides recording once; importing stays jax-free
# ---------------------------------------------------------------------------

INSTRUMENTED = ("repro.runtime.rollout", "repro.runtime.trainer",
                "repro.runtime.inference", "repro.runtime.experience",
                "repro.runtime.pipeline_exec", "repro.wm.imagination",
                "repro.runtime.transport.channel",
                "repro.runtime.transport.server",
                "repro.runtime.transport.remote",
                "repro.runtime.transport.weights",
                "repro.runtime.transport.inference_plane")


def test_trace_gating_is_import_inert():
    """Off: nothing is buffered and no ring is registered, and importing
    telemetry (with its service base) does not import jax. On: every
    instrumented module records through its binding."""
    src = pathlib.Path(__file__).resolve().parent.parent / "src"
    record = textwrap.dedent("""
        import importlib
        mods = [importlib.import_module(m) for m in %r]
        from repro.runtime import telemetry as tel
        on = %r
        assert tel.RECORDING == on
        for m in mods:
            assert m._tel is tel, m.__name__
            with m._tel.span(m.__name__):
                m._tel.instant("mark")
        events = tel.drain()
        assert bool(tel._bufs) == on, tel._bufs
        names = {e["name"] for e in events if e["ph"] == "X"}
        assert names == ({m.__name__ for m in mods} if on else set()), names
        """)
    # the package's __init__ loads the runtime (and jax): stand it in, so
    # that only telemetry and what it imports are loaded
    bare = textwrap.dedent("""
        import sys, types
        pkg = types.ModuleType("repro.runtime")
        pkg.__path__ = [%r]
        sys.modules["repro.runtime"] = pkg
        import repro.runtime.telemetry as tel
        with tel.span("x"):
            pass
        assert "jax" not in sys.modules, "telemetry imported jax"
        """) % str(src / "repro" / "runtime")
    for on in (False, True):
        env = {k: v for k, v in os.environ.items() if k != "REPRO_TRACE"}
        env["PYTHONPATH"] = str(src)
        env["JAX_PLATFORMS"] = "cpu"
        if on:
            env["REPRO_TRACE"] = "1"
        for prog in (record % (INSTRUMENTED, on), bare):
            proc = subprocess.run([sys.executable, "-c", prog], env=env,
                                  capture_output=True, text=True)
            assert proc.returncode == 0, proc.stderr


@pytest.fixture
def recording(monkeypatch):
    """Spans and instants record into the rings, as under REPRO_TRACE."""
    monkeypatch.setattr(telemetry, "RECORDING", True)


# ---------------------------------------------------------------------------
# span recorder: context, rings, flows, Chrome export
# ---------------------------------------------------------------------------

def test_span_context_inheritance_and_wire_ctx(recording):
    assert telemetry.wire_ctx() == {}
    with telemetry.span("outer", flow="start") as (trace, sid):
        assert telemetry.current() == (trace, sid)
        ctx = telemetry.wire_ctx()
        assert ctx == {"tr": trace, "sp": sid}
        with telemetry.span("inner") as (t2, s2):
            assert t2 == trace and s2 != sid   # same trace, new span
    assert telemetry.current() is None
    events = telemetry.drain()
    slices = [e for e in events if e["ph"] == "X"]
    assert {e["name"] for e in slices} == {"outer", "inner"}
    inner = next(e for e in slices if e["name"] == "inner")
    assert inner["args"]["trace"] == trace
    assert inner["args"]["parent"] == sid
    flows = [e for e in events if e["ph"] == "s"]
    assert flows and flows[0]["id"] == trace and "bp" not in flows[0]


def test_instant_flow_step_binds_enclosing(recording):
    telemetry.instant("hop", trace=42, flow="step")
    events = telemetry.drain()
    flow = next(e for e in events if e["ph"] == "t")
    assert flow["id"] == 42 and flow["bp"] == "e"


def test_ring_buffer_bounds_memory(monkeypatch, recording):
    monkeypatch.setattr(telemetry, "BUF_EVENTS", 8)
    for i in range(20):
        telemetry.instant(f"e{i}")
    events = [e for e in telemetry.drain() if e["ph"] == "i"]
    assert len(events) <= 8
    assert events[-1]["name"] == "e19"          # newest survives the wrap


def test_extend_foreign_bounded(monkeypatch):
    monkeypatch.setattr(telemetry, "FOREIGN_EVENTS", 4)
    telemetry.extend_foreign([{"name": f"f{i}", "ph": "i"}
                              for i in range(10)])
    got = telemetry.drain()
    assert len(got) == 4 and got[-1]["name"] == "f9"


def test_dump_writes_chrome_trace_format(tmp_path, recording):
    with telemetry.span("work", cat="test", args={"k": 1}, flow="start"):
        telemetry.instant("mark", trace=7)
    out = tmp_path / "trace.json"
    n = telemetry.dump(str(out), process_name="unit")
    doc = json.loads(out.read_text())
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    events = doc["traceEvents"]
    assert len(events) == n + 2                 # + two metadata events
    assert events[0] == {"name": "process_name", "ph": "M",
                         "pid": os.getpid(), "tid": 0,
                         "args": {"name": "unit"}}
    assert events[1] == {"name": "trace_dropped", "ph": "M",
                         "pid": os.getpid(), "tid": 0,
                         "args": {"rings": 0, "foreign": 0}}
    for e in events[2:]:
        assert {"name", "ph", "ts", "pid"} <= set(e)
        assert isinstance(e["ts"], int)
    sl = next(e for e in events if e.get("ph") == "X")
    assert sl["dur"] >= 1 and sl["args"]["k"] == 1
    assert telemetry.drain() == []              # dump drained the buffers


def test_dump_reports_dropped_events_and_drain_resets(tmp_path, monkeypatch,
                                                      recording):
    """A dumped file whose rings wrapped says how many events it lost;
    the counts start again from zero after the drain."""
    monkeypatch.setattr(telemetry, "BUF_EVENTS", 4)
    monkeypatch.setattr(telemetry, "FOREIGN_EVENTS", 2)
    for i in range(10):
        telemetry.instant(f"e{i}")
    telemetry.extend_foreign([{"name": f"f{i}", "ph": "i"} for i in range(5)])
    out = tmp_path / "wrapped.json"
    telemetry.dump(str(out))
    meta = [e for e in json.loads(out.read_text())["traceEvents"]
            if e.get("name") == "trace_dropped"]
    assert meta == [{"name": "trace_dropped", "ph": "M", "pid": os.getpid(),
                     "tid": 0, "args": {"rings": 6, "foreign": 3}}]
    telemetry.instant("after")
    telemetry.dump(str(out))
    meta = [e for e in json.loads(out.read_text())["traceEvents"]
            if e.get("name") == "trace_dropped"]
    assert meta[0]["args"] == {"rings": 0, "foreign": 0}


# ---------------------------------------------------------------------------
# spans on the profiler's clock, stage scopes in the compiled programs
# ---------------------------------------------------------------------------

def _tiny_cfg():
    import dataclasses
    cfg = reduced(get_config("deepseek-7b"), layers=2, d_model=64)
    return dataclasses.replace(cfg, num_prefix_tokens=1)


def _profile_spans(trace_dir):
    """[(name, start_ns, end_ns)] of the ``repro.`` spans of each host
    thread in the profile written under ``trace_dir``."""
    from jax.profiler import ProfileData
    path = next(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = [(e.name, e.start_ns, e.end_ns) for e in line.events
                     if e.name.startswith("repro.")]
            if spans:
                out.append(spans)
    return out


def _children(spans, parent):
    """Names, in order, of the spans inside each ``parent`` span."""
    found = []
    for name, s, e in spans:
        if name == parent:
            found.append([n for n, cs, ce in sorted(spans, key=lambda x: x[1])
                          if n != parent and s <= cs and ce <= e])
    return found


def test_program_spans_land_in_the_profile(tmp_path):
    """One InferenceService batch and one ``train_on_batch`` under the
    profiler: the program's spans are in the trace, each layer nested in
    its batch or step, on the thread that fed the device."""
    import jax
    from repro.data.trajectory import dummy_batch
    from repro.models.policy import init_policy_params
    from repro.runtime.inference import InferenceService
    from repro.runtime.trainer import TrainerWorker
    from repro.runtime.weight_store import VersionedWeightStore

    cfg = _tiny_cfg()
    store = VersionedWeightStore()
    store.publish(init_policy_params(cfg, jax.random.PRNGKey(0)), 0)
    service = InferenceService(cfg, store, RuntimeConfig(
        num_inference_workers=1, inference_batch=2,
        inference_max_wait_s=0.5, batch_buckets=(2,)))
    rl = RLConfig(grad_accum=2, lr_policy=1e-4, lr_value=1e-3)
    trainer = TrainerWorker(cfg, rl, RuntimeConfig(), None,
                            VersionedWeightStore(), batch_episodes=2)
    trainer.begin_inline()
    batch = dummy_batch(2, 3, 12, cfg.action_dim, cfg.vocab_size,
                        cfg.action_vocab_size, num_prefix=1)
    rng = np.random.default_rng(0)
    obs = rng.integers(0, cfg.vocab_size, (2, 12)).astype(np.int32)
    frame = rng.random((2, 192)).astype(np.float32)
    with jax.profiler.trace(str(tmp_path)):
        service.start()
        try:
            futs = [service.submit(obs[i], frame[i], 0) for i in range(2)]
            for f in futs:
                f.result(timeout=120.0)
        finally:
            service.stop()
            service.join(timeout=30.0)
        trainer.train_on_batch(batch)
    spans = _profile_spans(tmp_path)
    infer = [v for v in spans if any(n == "repro.infer.batch"
                                     for n, _, _ in v)]
    train = [v for v in spans if any(n == "repro.trainer.step"
                                     for n, _, _ in v)]
    assert len(infer) == 1 and len(train) == 1, spans
    assert {n for n, _, _ in infer[0]} == {
        "repro.infer.loop", "repro.infer.swap", "repro.infer.collect",
        "repro.infer.batch", "repro.infer.prepare", "repro.infer.device",
        "repro.infer.resolve"}
    # every stage lies inside a pass of the worker loop
    loops = [(s, e) for n, s, e in infer[0] if n == "repro.infer.loop"]
    for n, s, e in infer[0]:
        assert any(ls <= s and e <= le for ls, le in loops), n
    assert _children(infer[0], "repro.infer.batch")[0] == [
        "repro.infer.prepare", "repro.infer.device", "repro.infer.resolve"]
    assert _children(train[0], "repro.trainer.step") == [[
        "repro.trainer.dispatch", "repro.trainer.publish",
        "repro.trainer.sync"]]


def test_stage_scopes_in_lowered_programs():
    """The fused train step and the inference fn carry their stages as
    named scopes, which the compiled ops keep as metadata."""
    import jax
    from repro.core.train_step import init_train_state
    from repro.data.trajectory import dummy_batch
    from repro.models.policy import make_inference_fn
    from repro.runtime.step_program import build_train_step_program

    cfg = _tiny_cfg()
    rl = RLConfig(grad_accum=2)
    state = init_train_state(cfg, jax.random.PRNGKey(0))
    batch = dummy_batch(2, 3, 12, cfg.action_dim, cfg.vocab_size,
                        cfg.action_vocab_size, num_prefix=1)
    step = build_train_step_program(cfg, rl).fused()
    text = step.lower(state, batch).as_text(debug_info=True)
    for scope in ("fwd_bwd", "grad_reduce", "optim_update"):
        assert f"{scope}/" in text, scope
    obs = np.zeros((2, 12), np.int32)
    prefix = np.zeros((2, 1, 1024), np.float32)
    text = make_inference_fn(cfg).lower(
        state.params, jax.random.PRNGKey(0), obs, np.zeros((2,), np.int32),
        prefix).as_text(debug_info=True)
    for scope in ("prefill", "decode", "value_head"):
        assert f"{scope}/" in text, scope


# ---------------------------------------------------------------------------
# histograms: layout, merge algebra, incarnation folds
# ---------------------------------------------------------------------------

def test_hist_bucket_layout():
    assert _hist_bucket(-1.0) == 0 and _hist_bucket(0.0) == 0
    assert _hist_bucket(2.0 ** (HIST_MIN_EXP - 3)) == 0
    assert _hist_bucket(2.0 ** HIST_MIN_EXP) == 1
    assert _hist_bucket(1.0) == -HIST_MIN_EXP + 1
    assert _hist_bucket(1e30) == HIST_BUCKETS - 1   # top bucket open
    # half-open buckets: [2^(i-1), 2^i)
    assert _hist_bucket(0.5) == _hist_bucket(0.75) != _hist_bucket(1.0)


def test_observe_and_hist_summary():
    m = MetricsRegistry("t")
    for v in (0.5, 1.5, 1.5, 8.0):
        m.observe("lat", v)
    h = m.hist("lat")
    assert h["count"] == 4 and h["sum"] == pytest.approx(11.5)
    assert h["min"] == 0.5 and h["max"] == 8.0
    assert sum(h["buckets"].values()) == 4
    assert all(isinstance(k, str) for k in h["buckets"])
    assert m.hist("missing") is None
    assert m.hist("missing", default={"count": 0})["count"] == 0
    assert m.snapshot()["hists"]["lat"]["count"] == 4


def test_hist_merge_is_associative_addition():
    a = {"count": 2, "sum": 3.0, "min": 1.0, "max": 2.0,
         "buckets": {"21": 2}}
    b = {"count": 1, "sum": 8.0, "min": 8.0, "max": 8.0,
         "buckets": {"24": 1}}
    ab = _hist_merge(a, b)
    assert ab["count"] == 3 and ab["sum"] == pytest.approx(11.0)
    assert ab["min"] == 1.0 and ab["max"] == 8.0
    assert ab["buckets"] == {"21": 2, "24": 1}
    assert _hist_merge(None, a) == a and _hist_merge(a, None) == a
    assert _hist_merge(None, None)["count"] == 0


def test_hist_incarnation_fold_monotone_and_no_double_count():
    """Satellite: histogram + series folds through begin_remote_incarnation
    stay monotone and double-count-free across a worker restart."""
    child = MetricsRegistry("child")
    for v in (1.0, 2.0, 4.0):
        child.observe("age", v)
        child.record("ret", v)
    parent = MetricsRegistry("slot")
    snap = child.snapshot()
    parent.apply_remote(snap)
    parent.apply_remote(snap)                   # re-report: idempotent
    assert parent.hist("age")["count"] == 3
    assert parent.snapshot()["series"]["ret"]["count"] == 3

    parent.begin_remote_incarnation()           # worker restarted
    assert parent.hist("age")["count"] == 3     # fold is monotone
    child2 = MetricsRegistry("child")           # re-reports from zero
    child2.observe("age", 16.0)
    child2.record("ret", 16.0)
    parent.apply_remote(child2.snapshot())
    h = parent.hist("age")
    assert h["count"] == 4 and h["sum"] == pytest.approx(23.0)
    assert h["max"] == 16.0
    s = parent.snapshot()["series"]["ret"]
    assert s["count"] == 4
    assert s["mean"] == pytest.approx(23.0 / 4)

    parent.begin_remote_incarnation()           # second restart, no report
    assert parent.hist("age")["count"] == 4     # still no double count


def test_merge_snapshots_folds_hists_across_services():
    a, b = MetricsRegistry("a"), MetricsRegistry("b")
    a.observe("wait", 1.0)
    b.observe("wait", 3.0)
    b.observe("other", 5.0)
    merged = _merge_snapshots([a.snapshot(), b.snapshot()])
    assert merged["hists"]["wait"]["count"] == 2
    assert merged["hists"]["wait"]["sum"] == pytest.approx(4.0)
    assert merged["hists"]["other"]["count"] == 1


# ---------------------------------------------------------------------------
# bounded series: the unbounded-append regression
# ---------------------------------------------------------------------------

def test_series_storage_is_bounded_but_mean_is_exact():
    m = MetricsRegistry("t")
    n = SERIES_WINDOW + 300
    for i in range(n):
        m.record("x", float(i))
    win = m.series("x")
    assert len(win) == SERIES_WINDOW            # memory stays O(window)
    assert win[-1] == float(n - 1)
    assert win[0] == float(n - SERIES_WINDOW)
    assert m.series_mean("x") == pytest.approx((n - 1) / 2)  # ALL samples
    snap = m.snapshot()["series"]["x"]
    assert snap["count"] == n and snap["last"] == float(n - 1)


# ---------------------------------------------------------------------------
# structured crash records
# ---------------------------------------------------------------------------

class _Crashy(Service):
    def _run(self):
        raise RuntimeError("boom in the loop")


def test_service_crash_record_surfaced_in_health():
    svc = _Crashy("crashy")
    t0 = time.monotonic()
    svc.start()
    for _ in range(200):
        if svc.crash is not None:
            break
        time.sleep(0.01)
    crash = svc.health()["crash"]
    assert crash is not None
    assert crash["service"] == "crashy"
    assert crash["error"] == repr(svc.error)
    assert "RuntimeError: boom in the loop" in crash["traceback"]
    assert crash["t_mono"] >= t0
    assert isinstance(crash["incarnation"], int)
    svc.stop()
    svc.join()


def test_mark_failed_records_crash_without_traceback_frame():
    svc = _Crashy("marked")
    svc.mark_failed(ValueError("external verdict"))
    crash = svc.health()["crash"]
    assert crash["service"] == "marked"
    assert "external verdict" in crash["error"]


def test_healthy_service_has_no_crash_record():
    svc = Service("fine")
    assert svc.health()["crash"] is None


# ---------------------------------------------------------------------------
# TelemetrySink: registry sampling, bounded history, JSONL
# ---------------------------------------------------------------------------

def test_telemetry_sink_samples_and_bounds_history(tmp_path):
    reg = ServiceRegistry()
    svc = reg.register(Service("worker"))
    svc.metrics.inc("ticks", 3.0)
    svc.metrics.observe("lat", 0.25)
    path = tmp_path / "sink.jsonl"
    sink = telemetry.TelemetrySink(reg, interval_s=10.0, history=3,
                                   path=str(path))
    sink.on_start()
    for _ in range(5):
        sink.sample()
    assert len(sink.tail()) == 3                # history bounded
    latest = sink.latest()
    assert latest["services"]["worker"]["counters"]["ticks"] == 3.0
    assert latest["services"]["worker"]["hists"]["lat"]["count"] == 1
    assert latest["health"]["worker"]["state"] == "new"
    sink.on_stop()                              # final sample + close
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert len(lines) == 6
    assert lines[-1]["services"]["worker"]["counters"]["ticks"] == 3.0


def test_sink_sample_carries_crash_records():
    reg = ServiceRegistry()
    svc = reg.register(_Crashy("crashy"))
    svc.mark_failed(RuntimeError("dead"))
    sink = telemetry.TelemetrySink(reg)
    s = sink.sample()
    assert s["health"]["crashy"]["crash"]["service"] == "crashy"


def test_telemetry_config_on_runtime_config():
    rt = RuntimeConfig()
    assert rt.telemetry == TelemetryConfig()
    assert rt.telemetry.sink is False


# ---------------------------------------------------------------------------
# wire endpoints: metrics.snapshot + trace.dump
# ---------------------------------------------------------------------------

def test_server_metrics_snapshot_and_trace_dump_endpoints():
    srv = TransportServer()
    srv.start()
    try:
        client = WireClient(srv.address)
        resp, _ = client.request({"m": "metrics.snapshot"})
        assert resp["ok"] and srv.name in resp["sample"]["services"]
        # with a provider (the orchestrator wires the sink) the sample is
        # whatever the provider returns
        srv.snapshot_provider = lambda: {"services": {"x": 1}}
        resp, _ = client.request({"m": "metrics.snapshot"})
        assert resp["sample"] == {"services": {"x": 1}}
        # this test process is not trace-gated: trace.dump says so
        resp, _ = client.request({"m": "trace.dump"})
        assert resp["ok"] and resp["enabled"] is False
        assert resp["events"] == []
        client.close()
    finally:
        srv.stop()
        srv.join()


# ---------------------------------------------------------------------------
# elastic supervision: inference-tier saturation signal
# ---------------------------------------------------------------------------

class StubServer:
    def __init__(self):
        self.sinks = {}

    def register_worker_sink(self, name, host):
        self.sinks[name] = host

    def set_hello_handler(self, fn):
        pass


class FakeEndpoint(WorkerEndpoint):
    mode = "spawn"

    def __init__(self):
        self._failure = None

    def launch(self, spec):
        self._failure = None

    def failure(self):
        return self._failure


def _spec(name):
    return RemoteWorkerSpec(name=name,
                            cfg=reduced(get_config("deepseek-7b")),
                            rl=RLConfig(), rt=RuntimeConfig(),
                            address=("127.0.0.1", 1))


class ElasticSupervisor(Supervisor):
    def _elastic_add(self, spec):
        slot = SupervisedWorker(spec, FakeEndpoint(), self.server)
        slot.start()
        self.slots.append(slot)
        return slot


def test_tier_policy_validation():
    ElasticPolicy(tier_queue_hot=8.0, tier_fill_hot=0.95)
    with pytest.raises(ValueError):
        ElasticPolicy(tier_queue_hot=-1.0)
    with pytest.raises(ValueError):
        ElasticPolicy(tier_fill_hot=1.5)


def test_saturated_tier_triggers_scale_up():
    """Satellite: a saturated inference tier must scale the fleet up even
    when the experience queue alone would not."""
    signals = {"depth_frac": 0.5,                # mid-queue: no depth case
               "infer_queue_depth": 12.0, "infer_window_fill": 0.2}
    sup = ElasticSupervisor(StubServer(), RestartPolicy())
    sup.enable_elastic(ElasticPolicy(min_workers=0, max_workers=2,
                                     interval_s=1.0, tier_queue_hot=8.0),
                       lambda seq: _spec(f"elastic-{seq}"),
                       lambda: signals)
    sup._elastic_step(100.0)
    assert len(sup.slots) == 1, "hot tier queue must trigger scale-up"
    assert sup.metrics.gauge("elastic_tier_saturated") == 1.0
    signals["infer_queue_depth"] = 0.0           # pressure gone
    sup._elastic_step(102.0)
    assert len(sup.slots) == 1
    assert sup.metrics.gauge("elastic_tier_saturated") == 0.0


def test_saturated_tier_blocks_scale_down():
    signals = {"depth_frac": 0.0, "infer_window_fill": 0.0}
    sup = ElasticSupervisor(StubServer(), RestartPolicy())
    sup.enable_elastic(ElasticPolicy(min_workers=0, max_workers=1,
                                     interval_s=1.0, tier_fill_hot=0.9),
                       lambda seq: _spec(f"elastic-{seq}"),
                       lambda: signals)
    sup._elastic_step(100.0)
    assert len(sup.slots) == 1
    # queue says scale down, but the tier is saturated: hold the fleet
    signals["depth_frac"] = 1.0
    signals["infer_window_fill"] = 0.95
    sup._elastic_step(102.0)
    assert len(sup.slots) == 1 and sup.slots[0].phase == "up"
    signals["infer_window_fill"] = 0.0           # pressure gone: drain
    sup._elastic_step(104.0)
    assert sup.slots[0].phase == "draining"


def test_tier_thresholds_default_off():
    signals = {"depth_frac": 0.5,
               "infer_queue_depth": 1e9, "infer_window_fill": 1.0}
    sup = ElasticSupervisor(StubServer(), RestartPolicy())
    sup.enable_elastic(ElasticPolicy(min_workers=0, max_workers=2,
                                     interval_s=1.0),
                       lambda seq: _spec(f"elastic-{seq}"),
                       lambda: signals)
    sup._elastic_step(100.0)
    assert sup.slots == [], "tier signals are opt-in (0 disables)"
