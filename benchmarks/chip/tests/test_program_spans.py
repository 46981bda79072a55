"""CPU tests of the reduction of the program's spans and stage scopes
(``program_spans.py``).

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/chip/tests -q
"""
from __future__ import annotations

import json
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
CHIP = HERE.parent
ROOT = CHIP.parents[1]
sys.path[:0] = [str(ROOT / "src"), str(CHIP)]

import program_spans as ps  # noqa: E402
import trace_reduce as tr  # noqa: E402

FUSION = "%fusion.3 = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(%p.1), kind=kLoop"
KERNEL = ('%fwd_bwd.7 = (f32[8]{0}, f32[8]{0}) custom-call(%p.2), '
          'custom_call_target="tpu_custom_call"')
COPY = "%copy.1 = f32[8]{0} copy(%p.3)"


def _excerpt():
    """Two train steps on one thread: the device runs 10-40 and 60-85
    inside a window of 0-100; the feeding thread's spans and a client
    thread's benchmark span around them."""
    trace = tr.Trace(
        modules=[("jit_fused(1)", 10, 40), ("jit_fused(1)", 60, 85)],
        ops=[(KERNEL, 10, 25), (FUSION, 25, 35), (COPY, 35, 40),
             (KERNEL, 60, 70), (FUSION, 70, 85)],
        spans=[("t0", "bench.window", 0, 100),
               ("t0", "bench.trainer.train_on_batch", 2, 50),
               ("t0", "bench.trainer.train_on_batch", 50, 95)])
    spans = [(1, "trainer.step", 2, 48), (1, "trainer.dispatch", 5, 12),
             (1, "trainer.publish", 12, 14), (1, "trainer.sync", 14, 47),
             (1, "trainer.step", 52, 94), (1, "trainer.dispatch", 55, 62),
             (1, "trainer.sync", 62, 90),
             (2, "client.think", 40, 100)]
    scopes = {ps.instr_key(KERNEL): "fwd_bwd",
              ps.instr_key(FUSION): "optim_update"}
    return trace, spans, scopes


def test_reduction_of_program_spans_and_scopes():
    trace, spans, scopes = _excerpt()
    r = ps.reduce(trace, spans, scopes)
    assert r.span_count == {"client.think": 1, "trainer.dispatch": 2,
                            "trainer.publish": 1, "trainer.step": 2,
                            "trainer.sync": 2}
    assert {k: round(v * 1e9) for k, v in r.scope_s.items()} == {
        "fwd_bwd": 25, "optim_update": 25, "unscoped": 5}
    assert [[k, round(v * 1e9)] for k, v in r.unscoped_ops] == [["copy.1", 5]]
    # idle: 0-10, 40-60, 85-100 = 45 ns
    assert round(r.idle_s * 1e9) == 45
    assert {k: round(v * 1e9) for k, v in r.idle_in_s.items()} == {
        "client.think": 35, "trainer.dispatch": 5 + 5, "trainer.publish": 0,
        "trainer.step": 8 + 8 + 8 + 9, "trainer.sync": 7 + 5,
    }
    # everything but 0-2 is under some span
    assert round(r.idle_attributed_s * 1e9) == 43
    assert r.serve_host_idle_s is None
    # labelled by the span of the feeding thread that is innermost over
    # most of the gap (the client thread's span does not vote): 40-60 is
    # 7 of sync, 1 + 3 of step alone, 4 of none, 5 of dispatch
    assert [[label, round(g * 1e9)] for label, g in r.idle_gaps] == [
        ["trainer.sync", 20], ["trainer.sync", 15],
        ["trainer.dispatch", 10]]
    # a gap that no span of the feeding thread overlaps keeps the
    # benchmark's own label
    assert ps.label_gap(trace, spans, (95, 99)) == "host"
    assert ps.label_gap(trace, spans, (48, 51)) == "trainer.train_on_batch"
    # both dispatch spans hold the start of a program, 5 ns in
    assert r.dispatch_on_clock["trainer.dispatch"]["share"] == 1.0
    assert r.dispatch_on_clock["trainer.dispatch"]["offset_ms"] == \
        pytest.approx([5e-6] * 3)
    m = ps.metrics(r, 100e-9, {"steps": 2})
    assert m["train.fwd_bwd_ms.train"] == pytest.approx(1e3 * 25e-9 / 2)
    assert m["train.optim_ms.train"] == pytest.approx(1e3 * 25e-9 / 2)
    assert m["train.grad_reduce_ms.train"] is None
    assert m["infer.host_idle.serve"] is None
    assert m["infer.queue_wait_ms.serve"] is None


def test_serve_metrics_on_hand_made_batches():
    trace = tr.Trace(
        modules=[("jit_fn(2)", 20, 60), ("jit_fn(2)", 90, 130)],
        ops=[(FUSION, 20, 30), (KERNEL, 30, 60), (FUSION, 90, 100),
             (KERNEL, 100, 130)],
        spans=[("t0", "bench.window", 0, 150)])
    spans = [(3, "infer.collect", 0, 10), (3, "infer.batch", 10, 70),
             (3, "infer.prepare", 10, 18), (3, "infer.device", 18, 65),
             (3, "infer.resolve", 65, 70), (3, "infer.collect", 70, 80),
             (3, "infer.batch", 80, 140), (3, "infer.prepare", 80, 88),
             (3, "infer.device", 88, 135), (3, "infer.resolve", 135, 140)]
    scopes = {ps.instr_key(FUSION): "prefill", ps.instr_key(KERNEL): "decode"}
    r = ps.reduce(trace, spans, scopes)
    # idle 0-20, 60-90, 130-150; host work: 0-18, 65-88, 135-140
    assert round(r.serve_host_idle_s * 1e9) == 18 + 23 + 5
    assert round(r.idle_attributed_s * 1e9) == 20 + 30 + 10
    assert r.dispatch_on_clock == {
        "infer.device": {"share": 1.0, "offset_ms": pytest.approx([2e-6] * 3)}}
    m = ps.metrics(r, 150e-9, {"queue_wait_count": 4,
                               "queue_wait_sum": 0.5})
    assert m["infer.host_idle.serve"] == pytest.approx(100 * 46 / 150)
    assert m["infer.prefill_ms.serve"] == pytest.approx(1e3 * 10e-9)
    assert m["infer.decode_ms.serve"] == pytest.approx(1e3 * 30e-9)
    assert m["infer.queue_wait_ms.serve"] == pytest.approx(125.0)


def test_parent_style_trace_reads_nothing_new():
    """On the recorded trace of the benchmark's first PR (no program
    spans, no scopes) nothing new is read, and the gaps keep the labels
    that ``trace_reduce`` gives them."""
    rec = json.loads((HERE / "data" / "train_steps_trace.json").read_text())
    trace = tr.Trace(modules=[tuple(m) for m in rec["modules"]],
                     ops=[tuple(o) for o in rec["ops"]],
                     spans=[tuple(s) for s in rec["spans"]])
    r = ps.reduce(trace, [], {})
    s = tr.summarize(trace)
    assert r.span_count == {} and r.idle_in_s == {}
    assert r.idle_attributed_s == 0.0 and r.dispatch_on_clock == {}
    assert set(r.scope_s) == {"unscoped"}
    assert r.idle_gaps == s.idle_gaps
    assert r.idle_s == pytest.approx(s.window_s - s.busy_s)
    m = ps.metrics(r, s.window_s, {"steps": 2, "requests": 64.0})
    assert m == dict.fromkeys(m)


def test_dispatch_spans_against_the_programs_they_launch():
    """A program that the profile puts before the span that launches it
    reads a negative offset, and its span holds no program start."""
    modules = [("m", 10, 50), ("k", 60, 61), ("m", 70, 120)]
    got = ps.on_clock(modules, [(12, 55), (58, 125)])
    assert got["share"] == 0.5
    assert got["offset_ms"] == pytest.approx([-2e-6, 12e-6, 12e-6])


@pytest.mark.parametrize("text, key", [
    (FUSION + ', metadata={op_name="jit(f)/optim_update/add"}',
     "%fusion.3 = bf16[8,128]{1,0:T(8,128)(2,1)}"),
    ("  ROOT " + KERNEL, "%fwd_bwd.7 = (f32[8]{0}, f32[8]{0})"),
    ("%while = (s32[]{:T(128)}, f32[2]{0:T(128)}) while(%t)",
     "%while = (s32[]{:T(128)}, f32[2]{0:T(128)})"),
])
def test_instruction_keys(text, key):
    assert ps.instr_key(text) == key


def test_scope_sites_read_the_compiled_metadata():
    """The compiled program's ``op_name`` carries the stage scopes of the
    fused train step; every instruction that has one gets a scope."""
    import dataclasses

    import jax

    from repro.configs import get_config, reduced
    from repro.configs.base import RLConfig
    from repro.core.train_step import init_train_state
    from repro.data.trajectory import dummy_batch
    from repro.runtime.step_program import build_train_step_program

    cfg = dataclasses.replace(
        reduced(get_config("deepseek-7b"), layers=2, d_model=64),
        num_prefix_tokens=1)
    state = init_train_state(cfg, jax.random.PRNGKey(0))
    batch = dummy_batch(2, 3, 12, cfg.action_dim, cfg.vocab_size,
                        cfg.action_vocab_size, num_prefix=1)
    text = build_train_step_program(cfg, RLConfig(grad_accum=2)).fused() \
        .lower(state, batch).compile().as_text()
    sites = ps.scope_sites(text)
    found = set(sites.values())
    assert {"fwd_bwd", "grad_reduce", "optim_update"} <= found
    assert found <= set(ps.SCOPES) | {ps.UNSCOPED}


def test_load_spans_from_a_profile(tmp_path):
    import jax
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("repro.trainer.step"):
            with jax.profiler.TraceAnnotation("repro.trainer.sync"):
                pass
        with jax.profiler.TraceAnnotation("bench.window"):
            pass
    path = str(next(tmp_path.rglob("*.xplane.pb")))
    spans = ps.load_spans(path)
    assert sorted(name for _, name, _, _ in spans) == [
        "trainer.step", "trainer.sync"]
    (t1, _, s1, e1), (t2, _, s2, e2) = sorted(spans, key=lambda x: x[2])
    assert t1 == t2 and s1 <= s2 and e2 <= e1
