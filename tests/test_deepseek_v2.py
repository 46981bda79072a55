"""DeepSeek-V2 (latent attention, YaRN, an expert layer that holds a share
of the experts) against its plain float32 reference.

The reference is the benchmark's ``deepseek_v2`` family
(``benchmarks/chip/families/deepseek_v2.py``): the published layer
equations in ``jax.numpy`` under ``highest`` matmul precision, importing
nothing of ``repro.models``. At a small size with seeded float32 weights
(d 256, 4 heads of 32 + 32 over a 64-wide latent, values 32, 16 experts
of which 4 are held, top 3, 2 shared experts, one dense layer then two
expert layers) the program must give the reference's teacher-forced
logits, GIPO loss and gradients of a train step, and, through prefill and
the latent cache, its logits token by token. Both compute in float32, so
the tolerances are float32 rounding over a few layers (1e-4 relative);
a bfloat16 step lies orders of magnitude above them.
"""
import copy
import dataclasses
import functools
import json
import math
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

CHIP = pathlib.Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
sys.path[:0] = [str(CHIP)]

import drive  # noqa: E402
import families  # noqa: E402
import reference  # noqa: E402
import traffic_gen  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.train_step import init_train_state, make_train_step  # noqa
from repro.data.trajectory import TrajectoryBatch  # noqa: E402
from repro.models import mla as mla_lib  # noqa: E402
from repro.models import moe as moe_lib  # noqa: E402
from repro.models import transformer  # noqa: E402
from repro.models.layers import rope_frequencies  # noqa: E402

TOL = dict(rtol=1e-4, atol=1e-5)


def small_config(**over):
    c = copy.deepcopy(json.loads(
        (CHIP / "configs" / "deepseek-v2-lite-l6.json").read_text()))
    c.update(hidden_size=256, intermediate_size=512, num_attention_heads=4,
             num_key_value_heads=4, kv_lora_rank=64, qk_nope_head_dim=32,
             qk_rope_head_dim=32, v_head_dim=32, moe_intermediate_size=128,
             n_routed_experts=4, num_experts_per_tok=3, vocab_size=1000,
             num_hidden_layers=3, torch_dtype="float32")
    c["published"] = dict(c["published"], n_routed_experts=16)
    c.update(over)
    return c


def small_mix():
    mix = json.loads((CHIP / "traffic" / "train-ep8.json").read_text())
    mix.update(segments=4)
    mix["rl"] = dict(mix["rl"], micro_batch=2, grad_accum=2)
    return mix


@pytest.fixture(scope="module")
def setup():
    config = small_config()
    fam = families.load(config)
    cfg = fam.model_config(config)
    params = drive.make_params(fam, config, 11)
    drive.check_params(params, cfg)
    return config, fam, cfg, params, fam.Spec.from_config(config)


def _inputs(config, n=3, t=12, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, config["vocab_size"], (n, t)).astype(np.int32)
    prefix = rng.standard_normal((n, 1, 1024)).astype(np.float32)
    return ids, prefix


def _ref_logits(params, fam, spec, ids, prefix):
    with jax.default_matmul_precision("highest"):
        hid = fam.decoder(params, params["embed"]["table"], ids, prefix,
                          spec, "f32")
        return reference.mm("ntd,dv->ntv", hid, params["action_head"]["w"],
                            "f32")


# ---------------------------------------------------------------------------
# YaRN and the softmax scale, against a direct transcription
# ---------------------------------------------------------------------------

def _published_inv_freq(dim, base, factor, orig, beta_fast, beta_slow):
    """``DeepseekV2YarnRotaryEmbedding._set_cos_sin_cache``, as published."""
    def find_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) / (
            2 * math.log(base))
    low = max(math.floor(find_dim(beta_fast)), 0)
    high = min(math.ceil(find_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    linear = (np.arange(dim // 2, dtype=np.float32) - low) / (high - low)
    inv_freq_mask = 1.0 - np.clip(linear, 0, 1)
    freq_extra = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float32)
                                 / dim))
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2,
                                                    dtype=np.float32) / dim))
    return freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask


def test_yarn_frequencies_and_softmax_scale():
    cfg = get_config("deepseek-v2-lite")
    y = cfg.rope_scaling
    want = _published_inv_freq(64, 10_000.0, 40.0, 4096, 32, 1)
    got = np.asarray(rope_frequencies(64, cfg.rope_theta, y))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # the ramp blends: the fastest pairs keep their frequency, the slowest
    # are divided by the factor
    assert got[0] == pytest.approx(1.0)
    assert got[-1] == pytest.approx(want[-1]) and got[-1] < 1e-4 / 40 * 2
    mscale = 0.1 * 0.707 * math.log(40) + 1.0
    assert mla_lib.softmax_scale(cfg) == pytest.approx(
        192 ** -0.5 * mscale * mscale, rel=1e-12)


def test_family_refuses_a_routed_scaling_factor():
    """The program scales no routed gate, so a configuration file that
    asks for a factor other than 1 is refused, not run unscaled."""
    config = small_config(routed_scaling_factor=16)
    fam = families.load(config)
    with pytest.raises(ValueError, match="routed_scaling_factor"):
        fam.model_config(config)
    assert fam.model_config(small_config()).moe.norm_topk_prob is False


# ---------------------------------------------------------------------------
# the program against the reference
# ---------------------------------------------------------------------------

def test_teacher_forced_logits_match_reference(setup):
    config, fam, cfg, params, spec = setup
    ids, prefix = _inputs(config)
    out = transformer.forward(cfg, params, jnp.asarray(ids),
                              jnp.asarray(prefix))
    want = _ref_logits(params, fam, spec, ids, prefix)
    np.testing.assert_allclose(np.asarray(out["logits"]), np.asarray(want),
                               **TOL)
    # the share of assignments on held experts is about 4 of 16
    assert 0.1 < float(out["aux"]["held_frac"]) < 0.45


def test_prefill_then_latent_decode_matches_reference(setup):
    config, fam, cfg, params, spec = setup
    ids, prefix = _inputs(config, t=14, seed=1)
    t0 = 8
    want = np.asarray(_ref_logits(params, fam, spec, ids, prefix))
    out, cache = transformer.prefill(cfg, params, jnp.asarray(ids[:, :t0]),
                                     jnp.asarray(prefix),
                                     cache_len=1 + ids.shape[1])
    a = cfg.mla
    assert cache.attn.ckv.shape[-1] + cache.attn.kpe.shape[-1] \
        == a.kv_lora_rank + a.qk_rope_head_dim            # the latent only
    got = [np.asarray(out["logits"][:, -1])]
    for i in range(t0, ids.shape[1] - 1):
        dec, cache = transformer.decode(cfg, params, jnp.asarray(ids[:, i]),
                                        cache)
        got.append(np.asarray(dec["logits"][:, 0]))
    np.testing.assert_allclose(np.stack(got, 1), want[:, t0:-1], **TOL)


def _prog_step(cfg, params, batch, rl):
    state = init_train_state(cfg, jax.random.PRNGKey(0))._replace(
        params=params)
    new, metrics = make_train_step(cfg, drive._rl({"rl": rl}),
                                   donate=False)(state, TrajectoryBatch(
                                       **batch))
    norms, diff_norms = drive._norm_fns()
    grad = {k: v / (1 - reference.ADAM_B1)
            for k, v in drive._named(norms(new.opt.mu)).items()}
    change = drive._named(diff_norms(new.params, params))
    return {k: float(v) for k, v in metrics.items()}, grad, change


@pytest.fixture(scope="module")
def train_step(setup):
    config, fam, cfg, params, spec = setup
    mix = small_mix()
    batch = traffic_gen.train_batch(np.random.default_rng([5, 1]), mix,
                                    config)
    prog = _prog_step(cfg, params, batch, mix["rl"])
    ref = fam.train_reference(params, [batch], mix["rl"], spec,
                              block_segments=1)
    return config, fam, spec, params, mix, batch, prog, ref


def test_train_step_loss_and_grads_match_reference(train_step):
    config, fam, spec, params, mix, batch, prog, ref = train_step
    metrics, grad, change = prog
    want = ref["steps"][0]
    for k in ("loss", "pg_loss", "value_loss", "kl", "entropy",
              "grad_norm"):
        assert metrics[k] == pytest.approx(want[k], rel=1e-4, abs=1e-7), k
    assert set(grad) == set(ref["first_grad"])
    for k, v in ref["first_grad"].items():
        assert grad[k] == pytest.approx(v, rel=1e-3, abs=1e-9), k
    readings = drive.train_readings([metrics], grad, change, ref, mix["rl"])
    assert readings["grad_gap"] < 1e-3 and readings["pg_gap"] < 1e-4
    # 4 of 16 experts held: about a quarter of the assignments land here
    assert 0.1 < metrics["moe_held_frac"] < 0.45
    assert metrics["moe_load_max"] >= 1.0


def _capacity_moe(fam, capacity_factor=1.25):
    """The expert layer of the deleted GShard path: in priority order
    (every token's first choice, then second ...) each expert keeps the
    first ``capacity_factor * tokens * k / experts`` assignments and drops
    the rest."""
    def moe(p, x, spec, prec):
        vals, idx = fam.route(p, x, spec, prec)
        n, k, e = x.shape[0] * x.shape[1], spec.top_k, spec.experts
        cap = int(capacity_factor * n * k / e)
        onehot = jax.nn.one_hot(idx.reshape(n, k), e, dtype=jnp.int32)
        prio = onehot.transpose(1, 0, 2).reshape(k * n, e)
        within = (jnp.cumsum(prio, 0) - prio).reshape(k, n, e).transpose(
            1, 0, 2)
        keep = jnp.sum(within * onehot, -1) < cap
        vals = jnp.where(keep.reshape(idx.shape), vals, 0.0)
        return fam.expert_share(p, x, spec, prec, vals, idx)
    return moe


def test_dropping_assignments_reads_far_above_the_sound_gap(train_step):
    config, fam, spec, params, mix, batch, prog, ref = train_step
    sound = drive.train_readings([prog[0]], prog[1], prog[2], ref,
                                 mix["rl"])
    dropped = fam.train_reference(
        params, [batch], mix["rl"], spec, block_segments=1,
        decoder=functools.partial(fam.decoder, moe=_capacity_moe(fam)))
    fault = drive.train_readings(dropped["steps"], dropped["first_grad"],
                                 dropped["change"], ref, mix["rl"])
    assert max(fault["loss_gap"] / sound["loss_gap"],
               fault["grad_gap"] / sound["grad_gap"]) >= 10, (fault, sound)


def test_expert_shares_add_up_to_the_uncut_layer(setup):
    """Over the four shares of 16 experts, each share's output less the
    shared experts, plus the shared experts once, is the whole layer."""
    config, fam, cfg, params, spec = setup
    rng = np.random.default_rng(3)
    full = {k: jnp.asarray(rng.standard_normal(s) * 0.05, jnp.float32)
            for k, s in (("w_gate", (16, 256, 128)), ("w_up", (16, 256, 128)),
                         ("w_down", (16, 128, 256)))}
    full["router"] = jnp.asarray(rng.standard_normal((256, 16)) * 0.05,
                                 jnp.float32)
    full["shared"] = jax.tree.map(lambda a: a[0],
                                  params["layers"]["moe"]["shared"])
    x = jnp.asarray(rng.standard_normal((2, 9, 256)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = fam.moe(full, x, dataclasses.replace(spec, held=16), "f32")
        shared = reference.swiglu(full["shared"], x, "f32")
        total = shared
        for first in range(0, 16, 4):
            m = dataclasses.replace(cfg.moe, held_first=first, held=4)
            share = {k: (v[first:first + 4] if k.startswith("w_") else v)
                     for k, v in full.items()}
            out, aux = moe_lib.moe_forward(share, x, m)
            total = total + out - shared
            assert 0.0 <= float(aux["held_frac"]) <= 1.0
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), **TOL)


# ---------------------------------------------------------------------------
# named scopes: the layer's own inside the stage scopes
# ---------------------------------------------------------------------------

def _op_names(compiled_text):
    import re
    return re.findall(r'op_name="([^"]*)"', compiled_text)


def test_layer_scopes_sit_inside_the_stage_scopes(setup):
    """The ops of ``mla``, ``moe_route``, ``moe_experts`` and
    ``moe_shared`` carry those scopes, and the innermost stage scope the
    profile reduction finds for them is still the step's stage (small
    loop-invariant ops that the compiler hoists out of every stage, such
    as the rotary tables, read as unscoped, as they do in any layer)."""
    import trace_reduce
    from repro.models.policy import make_inference_fn
    config, fam, cfg, params, spec = setup
    mix = small_mix()
    batch = traffic_gen.train_batch(np.random.default_rng(0), mix, config)
    state = init_train_state(cfg, jax.random.PRNGKey(0))
    step = make_train_step(cfg, drive._rl(mix), donate=False)
    names = _op_names(step.lower(state, TrajectoryBatch(**batch))
                      .compile().as_text())
    layer = ("mla", "moe_route", "moe_experts", "moe_shared")
    for scope in layer:
        inside = [n for n in names if scope in n.split("/")]
        assert inside, scope
        stages = {trace_reduce.scope_of(n) for n in inside}
        assert stages - {trace_reduce.UNSCOPED} == {"fwd_bwd"}, scope
    ids, prefix = _inputs(config, n=2)
    names = _op_names(make_inference_fn(cfg).lower(
        params, jax.random.PRNGKey(0), jnp.asarray(ids),
        jnp.zeros((2,), jnp.int32), jnp.asarray(prefix)).compile().as_text())
    stages = {trace_reduce.scope_of(n) for n in names
              if "mla" in n.split("/")}
    assert stages - {trace_reduce.UNSCOPED} == {"prefill", "decode"}
