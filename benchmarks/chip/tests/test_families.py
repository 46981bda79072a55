"""CPU tests of the architecture families (``families/``) and of what the
harness hands the metric readers.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/chip/tests -q

* the ``dense`` family gives, for each configuration file, the weights,
  work counts and reference outputs that its code gave before it moved
  behind the family interface (``data/dense_parent_outputs.json``);
* a family that only the tests have (``data/families/moe1.py``: the dense
  stack with its MLP as the one expert of the program's ``moe`` layer,
  one leaf more) has its configuration, weights, reference and work keys
  used by the train runner, and a metric reader sees its work key and the
  program's own readings;
* a serve run hands the readers the tier's counters and histograms;
* a configuration that names a family with no file makes ``run.py`` exit 3
  before it looks for a chip.
"""
from __future__ import annotations

import hashlib
import json
import pathlib
import sys
import time

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parent
CHIP = HERE.parent
ROOT = CHIP.parents[1]
sys.path[:0] = [str(ROOT / "src"), str(CHIP), str(HERE)]

import drive  # noqa: E402
import families  # noqa: E402
import run  # noqa: E402
import traffic_gen  # noqa: E402
from test_bench_harness import (CONFIGS, SERVE_MIX, TRAIN_MIX,  # noqa: E402
                                tiny_config, tiny_serve_mix, tiny_train_mix)

PARENT = json.loads((HERE / "data" / "dense_parent_outputs.json")
                    .read_text())
TEST_FAMILIES = HERE / "data" / "families"


def _close(got, want):
    """Equal, floats to within float32 rounding of another CPU's kernels."""
    if isinstance(want, dict):
        assert sorted(got) == sorted(want)
        for k in want:
            _close(got[k], want[k])
    elif isinstance(want, list):
        assert len(got) == len(want)
        for g, w in zip(got, want):
            _close(g, w)
    else:
        assert got == pytest.approx(want, rel=1e-5, abs=1e-7)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_dense_family_is_the_code_it_replaced(name):
    import jax
    config = tiny_config(name)
    fam = families.load(config)
    assert fam is not None and "family" not in CONFIGS[name]
    want = PARENT[name]

    # weights: bit for bit
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(drive.make_params(fam, config, 7)):
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == want["weights_sha256"]

    # work of 3 steps or requests at the full size, as the runners merge it
    full = CONFIGS[name]
    t = TRAIN_MIX["instruction_tokens"]
    assert drive.window_work(3, fam.train_step_flops(
        full, TRAIN_MIX["segments"], TRAIN_MIX["horizon"], t),
        fam.kernel_work(full, TRAIN_MIX)) == want["work_train"]
    assert drive.window_work(3.0, fam.serve_request_flops(
        full, SERVE_MIX["instruction_tokens"]),
        fam.kernel_work(full, SERVE_MIX)) == want["work_serve"]

    # the reference, its control and the planted fault
    spec = fam.Spec.from_config(config)
    rng = np.random.default_rng(0)
    n = 5
    obs = rng.integers(0, config["vocab_size"], (n, 12)).astype(np.int32)
    act = rng.integers(0, 256, (n, 7)).astype(np.int32)
    steps = rng.integers(0, 60, n).astype(np.int32)
    prefix = rng.standard_normal((n, 1, 1024)).astype(np.float32)
    lp, v = fam.serve_readings(drive.make_params(fam, config, 7), obs, act,
                               steps, prefix, spec)
    _close(np.asarray(lp).ravel().tolist(), want["serve_logp"])
    _close(np.asarray(v).tolist(), want["serve_value"])
    mix = tiny_train_mix()
    brng = np.random.default_rng([7, 1])
    batches = [traffic_gen.train_batch(brng, mix, config) for _ in range(2)]
    for key, kw in (("train_f32", {}), ("train_fp8", {"prec": "fp8"}),
                    ("train_half_batch", {"half_batch": True})):
        got = fam.train_reference(drive.make_params(fam, config, 7), batches,
                                  mix["rl"], spec, **kw)
        _close(got, want[key])


def _ctx(config, mix, seed):
    return drive.Ctx(config, mix, seed=seed, seconds=0.5, trace=False,
                     t_start=time.monotonic(),
                     family=families.load(config, TEST_FAMILIES))


@pytest.fixture(scope="module")
def moe1_train():
    config = dict(tiny_config("deepseek-7b-l1"), family="moe1")
    return config, drive.run_train(_ctx(config, tiny_train_mix(), 101))


def test_test_only_family_drives_the_train_runner(moe1_train):
    config, out = moe1_train
    with pytest.raises(LookupError):           # not one of the benchmark's
        families.load(config)
    # the program ran the family's ModelConfig with the family's weights
    # (``check_params`` holds them to the program's own tree), and the
    # family's reference read them: the dense one finds no ``mlp`` leaf
    limits = json.loads((HERE / "data" / "reduced_size_limits.json")
                        .read_text())["deepseek-7b-l1.train"]
    checks, ok = run.judge(out.readings, limits)
    assert ok, checks
    steps = out.counters["steps"]
    fam = families.load(config, TEST_FAMILIES)
    per_step = fam.kernel_work(config, tiny_train_mix())
    assert out.work["expert_flops"] == steps * per_step["expert_flops"]
    assert out.work["flash_flops"] == steps * per_step["flash_flops"]
    assert set(out.program) >= {"loss", "pg_loss", "grad_norm"}


def test_readers_see_family_work_and_program_readings(moe1_train, tmp_path):
    config, out = moe1_train
    (tmp_path / "expert_work.train.py").write_text(
        "def read(o, peak):\n"
        "    return o.work['expert_flops'] / o.counters['steps']\n")
    (tmp_path / "mean_loss.train.py").write_text(
        "def read(o, peak):\n    return o.program.get('loss')\n")
    bench = {"end_to_end": [{"name": "train_tokens_per_s"}],
             "per_layer": [{"name": n, "unit": "x",
                            "moves": "train_tokens_per_s"}
                           for n in ("expert_work.train", "mean_loss.train")]}
    got = run.read_metrics(out, run.metrics_for(bench, {"name": "c"}, True,
                                                where=tmp_path),
                           run.device_peak("TPU v5 lite"))
    per_step = families.load(config, TEST_FAMILIES).kernel_work(
        config, tiny_train_mix())["expert_flops"]
    assert got["expert_work.train"]["value"] == per_step
    assert got["mean_loss.train"]["value"] == pytest.approx(
        out.program["loss"])


def test_serve_run_hands_readers_the_tier_counters():
    config = tiny_config("deepseek-7b-l24")
    out = drive.run_serve(drive.Ctx(config, tiny_serve_mix(), seed=102,
                                    seconds=1.0, trace=False,
                                    t_start=time.monotonic()))
    p = out.program
    assert p["requests"] == out.counters["requests"] > 0
    assert p["padded_slots"] == out.counters["padded_slots"]
    assert p["queue_wait_s.count"] > 0 and p["queue_wait_s.sum"] > 0
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {"name": "deepseek-7b-l24.serve-64env"}
    got = run.read_metrics(out, run.metrics_for(bench, cell, True),
                           run.device_peak("TPU v5 lite"))
    # untraced: only the readers of counters and host-clock work read
    assert set(got) == {"mfu.serve", "infer.batch_fill.serve",
                        "infer.queue_wait_ms.serve"}
    assert got["infer.queue_wait_ms.serve"]["value"] == pytest.approx(
        1e3 * p["queue_wait_s.sum"] / p["queue_wait_s.count"])


def test_unknown_family_exits_3_before_the_chip(monkeypatch, capsys):
    bench, cell, config, mix, limits = run.load_cell("deepseek-7b-l1.train")
    monkeypatch.setattr(run, "load_cell", lambda name: (
        bench, cell, dict(config, family="no-such-family"), mix, limits))

    def no_chip(chips):
        raise AssertionError("looked for a chip")
    monkeypatch.setattr(run, "check_device", no_chip)
    assert run.main(["--workload", "deepseek-7b-l1.train", "--seed", "1",
                     "--seconds", "1", "--trace", "0"]) == 3
    assert "no-such-family" in capsys.readouterr().err
