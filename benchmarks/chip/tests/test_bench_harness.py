"""CPU tests of the chip benchmark's harness.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/chip/tests -q

* the trace reduction on a recorded excerpt and on hand-made intervals;
* the operation counts against hand counts for one layer of each
  configuration;
* the plain reference against the program's policy at a reduced size;
* the harness refuses a CPU device and an unknown device kind;
* the precision control of each cell comes out as not correct, through
  the runner the chip runs, and each planted fault through a whole run of
  ``run.py`` past its look for a chip, on several seeds (at a reduced
  size, with limits read at that size on those seeds,
  ``data/reduced_size_limits.json``).
"""
from __future__ import annotations

import copy
import json
import pathlib
import sys

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parent
CHIP = HERE.parent
ROOT = CHIP.parents[1]
sys.path[:0] = [str(ROOT / "src"), str(CHIP)]

import drive  # noqa: E402
import families  # noqa: E402
import flops  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
import trace_reduce as tr  # noqa: E402
import traffic_gen  # noqa: E402


def _json(path):
    return json.loads(pathlib.Path(path).read_text())


CONFIGS = {p.stem: _json(p) for p in (CHIP / "configs").glob("*.json")}
TRAIN_MIX = _json(CHIP / "traffic" / "train.json")
SERVE_MIX = _json(CHIP / "traffic" / "serve-64env.json")


def tiny_config(name="deepseek-7b-l1", **over):
    """The configuration at a reduced size: two layers, narrow widths,
    head_dim and the stub frontend as published."""
    c = copy.deepcopy(CONFIGS[name])
    kv = 1 if c["num_key_value_heads"] < c["num_attention_heads"] else 2
    c.update(hidden_size=256, intermediate_size=512, num_attention_heads=2,
             num_key_value_heads=kv, vocab_size=1000, num_hidden_layers=2)
    c.update(over)
    return c


def tiny_train_mix():
    mix = copy.deepcopy(TRAIN_MIX)
    mix.update(segments=4, pool_batches=2)
    mix["rl"]["micro_batch"] = 2
    return mix


def tiny_serve_mix():
    mix = copy.deepcopy(SERVE_MIX)
    mix.update(clients=6, checked_requests=16, ramp_s=0.5)
    return mix


# ---------------------------------------------------------------------------
# trace reduction
# ---------------------------------------------------------------------------

def test_union_gaps_and_labels_on_hand_made_trace():
    trace = tr.Trace(
        modules=[("a", 10, 20), ("b", 15, 30), ("c", 50, 60),
                 ("d", 90, 120)],
        ops=[('%k.1 = f32[8]{0} custom-call(), custom_call_target='
              '"tpu_custom_call"', 12, 18),
             ('%k.1 = f32[8]{0} custom-call(), custom_call_target='
              '"tpu_custom_call"', 52, 55),
             ("%f.2 = f32[8]{0} fusion()", 20, 30)],
        spans=[("t0", "bench.window", 0, 100),
               ("t1", "bench.client.wait", 30, 50),
               ("t2", "bench.client.wait", 30, 45),
               ("t3", "bench.client.think", 35, 48)])
    s = tr.summarize(trace, {"%k.1 = f32[8]{0}": "_decode_kernel"})
    assert s.busy_s * 1e9 == pytest.approx(20 + 10 + 10)     # 10-30, 50-60, 90-100
    assert s.window_s * 1e9 == pytest.approx(100)
    assert s.idle_share == pytest.approx(0.6)
    assert s.kernel_s == {"_decode_kernel": pytest.approx(9e-9)}
    gaps = {round(g * 1e9): label for label, g in s.idle_gaps}
    assert gaps == {30: "host", 20: "client.wait", 10: "host"}


def test_reduction_of_recorded_train_steps():
    rec = _json(HERE / "data" / "train_steps_trace.json")
    trace = tr.Trace(modules=[tuple(m) for m in rec["modules"]],
                     ops=[tuple(o) for o in rec["ops"]],
                     spans=[tuple(s) for s in rec["spans"]])
    lo, hi = trace.window()
    # programs run one at a time on the chip: busy is their summed time
    busy = sum(min(e, hi) - max(s, lo) for _, s, e in rec["modules"]
               if min(e, hi) > max(s, lo))
    s = tr.summarize(trace)
    assert s.busy_s == pytest.approx(busy * 1e-9, rel=1e-9)
    assert s.window_s == pytest.approx((hi - lo) * 1e-9)
    assert 0.0 < s.idle_share < 0.2
    sites = {tr.op_key(o[0]): "_attn_kernel"
             for o in rec["ops"] if o[0].startswith("%closed_call.66 ")}
    want = sum(e - s_ for t, s_, e in rec["ops"]
               if t.startswith("%closed_call.66 ")) * 1e-9
    got = tr.kernel_seconds(trace, sites)
    assert got == {"_attn_kernel": pytest.approx(want)} and want > 0
    assert all(label == "trainer.train_on_batch" or label == "host"
               for label, _ in s.idle_gaps)


def test_kernel_sites_reads_the_mosaic_body():
    import base64
    body = base64.b64encode(b"\x00func @_attn_bwd_dq_kernel\x01").decode()
    text = ('  ROOT %closed_call.68 = f32[2,128]{1,0} custom-call(%p.1), '
            'custom_call_target="tpu_custom_call", backend_config='
            '{"custom_call_config":{"body":"' + body + '"}}')
    assert tr.kernel_sites(text) == {
        "%closed_call.68 = f32[2,128]{1,0}": "_attn_bwd_dq_kernel"}


# ---------------------------------------------------------------------------
# operation counts
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name, attn, mlp", [
    # d * hd * (2 H + 2 KV) and 3 d ff, by hand
    ("deepseek-7b-l1", 4096 * 128 * (64 + 64), 3 * 4096 * 11008),
    ("internlm2-1.8b-l4", 2048 * 128 * (32 + 16), 3 * 2048 * 8192),
])
def test_layer_counts_by_hand(name, attn, mlp):
    c = CONFIGS[name]
    assert flops.layer_matmul_params(c) == attn + mlp
    one = dict(c, num_hidden_layers=1)
    # flash, one layer, one 20-token row: 210 causal pairs
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    f = flops.flash_train(one, 1, 20)
    assert f["flops"] == 12 * 128 * 210 * h
    q, k = 20 * h * 128 * 2, 20 * kv * 128 * 2
    lse = 20 * h * 4
    assert f["bytes"] == (2 * q + 2 * k + lse) + (3 * q + 2 * k + 2 * lse) \
        + (2 * q + 4 * k + 2 * lse)
    d = flops.decode_request(one, 13, 1)          # 14 keys
    assert d["flops"] == 4 * 128 * 14 * h
    assert d["bytes"] == 2 * 14 * kv * 128 * 2 + 2 * h * 128 * 2 + 14 * 4


def test_step_flops_dominated_by_layers():
    c = CONFIGS["deepseek-7b-l1"]
    step = flops.train_step_flops(c, 32, 8, 12)
    layers = 6 * flops.layer_matmul_params(c) * 20 * 32 * 9
    assert layers < step < 1.1 * layers


# ---------------------------------------------------------------------------
# the reference against the program at a reduced size
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_reference_matches_program_policy(name):
    import jax
    from repro.models.policy import policy_forward
    config = tiny_config(name, torch_dtype="float32")
    fam = families.load(config)
    cfg = fam.model_config(config)
    params = drive.make_params(fam, config, 7)
    drive.check_params(params, cfg)
    spec = reference.Spec.from_config(config)
    rng = np.random.default_rng(0)
    n = 5
    obs = rng.integers(0, config["vocab_size"], (n, 12)).astype(np.int32)
    act = rng.integers(0, 256, (n, 7)).astype(np.int32)
    steps = rng.integers(0, 60, n).astype(np.int32)
    prefix = rng.standard_normal((n, 1, 1024)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        out = policy_forward(cfg, params, obs, act, steps, prefix)
    want_lp = np.take_along_axis(
        np.asarray(jax.nn.log_softmax(out.logits, -1)), act[..., None],
        -1)[..., 0]
    got_lp, got_v = reference.serve_readings(params, obs, act, steps,
                                             prefix, spec)
    np.testing.assert_allclose(got_lp, want_lp, atol=1e-4)
    np.testing.assert_allclose(got_v, np.asarray(out.value), atol=1e-4)


# ---------------------------------------------------------------------------
# the harness's refusals
# ---------------------------------------------------------------------------

def test_refuses_cpu(capsys):
    assert run.main(["--workload", "deepseek-7b-l1.train", "--seed", "1",
                     "--seconds", "1", "--trace", "0"]) == 3
    assert "no TPU" in capsys.readouterr().err


def test_refuses_unknown_device_kind():
    with pytest.raises(run.BenchError, match="no peaks"):
        run.device_peak("TPU v9 imaginary")
    assert run.device_peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_seed_maps_large_seeds_apart():
    assert traffic_gen.seed32(2 ** 33 + 5) != traffic_gen.seed32(5)
    assert 0 <= traffic_gen.seed32(2 ** 40) < 2 ** 31


# ---------------------------------------------------------------------------
# the control and the faults come out as not correct
# ---------------------------------------------------------------------------

def _ctx(config, mix, seed, controls=False):
    import time
    return drive.Ctx(config, mix, seed=seed, seconds=0.5,
                     trace=False, t_start=time.monotonic(),
                     controls=controls)


def _limits(workload):
    """Limits for the reduced size these tests run at: the separation of
    program, control and faults is checked there, not the chip's."""
    return _json(HERE / "data" / "reduced_size_limits.json")[workload]


CELLS = {
    "deepseek-7b-l1.train": (drive.run_train, tiny_train_mix),
    "internlm2-1.8b-l4.train": (drive.run_train, tiny_train_mix),
    "deepseek-7b-l24.serve-64env": (drive.run_serve, tiny_serve_mix),
}


def _run_cell(workload, seed=11, controls=False):
    runner, mix = CELLS[workload]
    return runner(_ctx(tiny_config(workload.rsplit(".", 1)[0]), mix(),
                       seed=seed, controls=controls))


# the seeds the reduced-size limits were read on
SEEDS = (100, 101, 102, 103, 104, 105)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("workload", sorted(CELLS))
def test_sound_run_correct_and_control_not(workload, seed):
    out = _run_cell(workload, seed=seed, controls=True)
    limits = _limits(workload)
    assert run.judge(out.readings, limits)[1] and out.failed == 0
    assert not run.judge(out.control["control_fp8"], limits)[1]
    if "fault_half_batch" in out.control:
        assert not run.judge(out.control["fault_half_batch"], limits)[1]


def _stuck(step):
    """A train step that returns its state unchanged."""
    import jax
    import jax.numpy as jnp

    def fn(state, batch):
        _, metrics = step(jax.tree.map(jnp.copy, state), batch)
        return state, metrics
    return fn


def _half(step):
    """A train step over the first half of the batch only."""
    def fn(state, batch):
        n = batch.obs_tokens.shape[0] // 2
        return step(state, type(batch)(*(x[:n] for x in batch)))
    return fn


def _fault_step(monkeypatch, fault):
    from repro.runtime.step_program import StepProgram
    fused = StepProgram.fused

    def faulty(self, **kw):
        return fault(fused(self, **kw))
    monkeypatch.setattr(StepProgram, "fused", faulty)


def _fault_token(monkeypatch):
    """Every served action token altered where it is produced."""
    import jax
    from repro.runtime import inference
    made = inference.make_inference_fn

    def altered(cfg, temperature=1.0):
        fn = made(cfg, temperature)

        def wrapped(*a):
            tokens, logp, value = fn(*a)
            return (tokens + 1) % cfg.action_vocab_size, logp, value
        return jax.jit(wrapped)
    monkeypatch.setattr(inference, "make_inference_fn", altered)


FAULTS = [("deepseek-7b-l1.train", "state_unchanged"),
          ("deepseek-7b-l1.train", "half_batch"),
          ("internlm2-1.8b-l4.train", "state_unchanged"),
          ("internlm2-1.8b-l4.train", "half_batch"),
          ("deepseek-7b-l24.serve-64env", "token_altered")]


def _result_of_run(monkeypatch, capsys, workload, seed):
    """``run.main`` for the cell at the reduced size, past the look for a
    chip; the result line it prints last."""
    import jax
    from repro.launch import compile_cache
    bench, cell = run.load_cell(workload)[:2]
    _, mix = CELLS[workload]
    small = (bench, cell, tiny_config(workload.rsplit(".", 1)[0]), mix(),
             _limits(workload))
    peaks = run.device_peak("TPU v5 lite")
    monkeypatch.setattr(run, "load_cell", lambda name: small)
    monkeypatch.setattr(run, "check_device", lambda chips: jax.devices())
    monkeypatch.setattr(run, "device_peak", lambda kind: peaks)
    monkeypatch.setattr(compile_cache, "enable_compile_cache",
                        lambda: "off")
    assert run.main(["--workload", workload, "--seed", str(seed),
                     "--seconds", "0.5", "--trace", "0"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("seed", SEEDS[:2])
@pytest.mark.parametrize("workload, fault", FAULTS)
def test_fault_comes_out_not_correct(monkeypatch, capsys, workload, fault,
                                     seed):
    if fault == "token_altered":
        _fault_token(monkeypatch)
    else:
        _fault_step(monkeypatch, {"state_unchanged": _stuck,
                                  "half_batch": _half}[fault])
    result = _result_of_run(monkeypatch, capsys, workload, seed)
    assert result["correct"] is False
    assert list(result)[-1] == "checks"
