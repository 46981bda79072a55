"""Train-step IR (runtime/step_program.py): dataflow validation, fused
parity against the historical step, microbatch gradient-accumulation
parity, and the ZeRO-2 optimizer-state wiring."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.configs.base import RLConfig
from repro.core.train_step import init_train_state, make_train_step
from repro.data.trajectory import dummy_batch
from repro.runtime.step_program import (StageSpec, StepProgram,
                                        build_train_step_program)

CFG = reduced(get_config("deepseek-7b"), layers=2, d_model=64)


def _batch(b=4, seed=0):
    return dummy_batch(b, 4, 12, CFG.action_dim, CFG.vocab_size,
                       CFG.action_vocab_size, seed=seed)


def _max_diff(t1, t2):
    d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(
        a.astype(jnp.float32) - b.astype(jnp.float32)))), t1, t2)
    return max(jax.tree.leaves(d))


# ---------------------------------------------------------------------------
# IR structure
# ---------------------------------------------------------------------------

def test_program_shape():
    prog = build_train_step_program(CFG, RLConfig(grad_accum=3))
    assert [s.name for s in prog.stages] == [
        "collate", "fwd_bwd", "grad_reduce", "optim_update", "publish"]
    assert prog.n_micro == 3
    assert prog.stage("fwd_bwd").per_micro
    assert prog.stage("grad_reduce").init is not None
    assert prog.stage("collate").kind == "host"
    assert prog.stage("publish").kind == "host"
    desc = prog.describe()
    for name in ("collate", "fwd_bwd", "grad_reduce", "optim_update"):
        assert name in desc
    with pytest.raises(KeyError):
        prog.stage("nope")


def test_program_rejects_dangling_input():
    with pytest.raises(ValueError, match="reads"):
        StepProgram(name="bad", inputs=("a",), stages=(
            StageSpec("s1", inputs=("a", "ghost"), outputs=("b",)),))


def test_program_rejects_duplicate_stage():
    with pytest.raises(ValueError, match="duplicate"):
        StepProgram(name="bad", inputs=("a",), stages=(
            StageSpec("s1", inputs=("a",), outputs=("b",)),
            StageSpec("s1", inputs=("b",), outputs=("c",))))


def test_stage_dataflow_chains():
    """Later stages may only read external feeds or earlier outputs —
    the declared order must itself be a valid topological order."""
    prog = build_train_step_program(CFG, RLConfig())
    produced = set(prog.inputs)
    for s in prog.stages:
        assert all(b in produced for b in s.inputs)
        produced.update(s.outputs)


# ---------------------------------------------------------------------------
# fused parity: the IR's fused form IS the historical train step
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fused_loss", [True, False])
def test_fused_form_matches_make_train_step(fused_loss):
    rl = RLConfig(grad_accum=2, fused_loss=fused_loss, lr_policy=1e-4,
                  lr_value=1e-3)
    state = init_train_state(CFG, jax.random.PRNGKey(0))
    batch = _batch(seed=7)

    s1, m1 = make_train_step(CFG, rl, donate=False)(state, batch)
    prog = build_train_step_program(CFG, rl)
    s2, m2 = prog.fused(donate=False)(state, batch)

    assert _max_diff(s1.params, s2.params) == 0.0
    assert float(m1["loss"]) == float(m2["loss"])
    assert int(s2.version) == 1


# ---------------------------------------------------------------------------
# microbatch gradient-accumulation parity (satellite): K accumulated
# micro-batches == one full batch at fixed seed, fused and plain paths
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fused_loss", [True, False])
@pytest.mark.parametrize("k", [2, 4])
def test_grad_accum_parity(fused_loss, k):
    # full-ones mask → every micro-batch carries the same token count, so
    # the mean-of-means equals the full-batch mean exactly
    rl_full = RLConfig(grad_accum=1, fused_loss=fused_loss,
                       lr_policy=1e-4, lr_value=1e-3)
    rl_micro = RLConfig(grad_accum=k, fused_loss=fused_loss,
                        lr_policy=1e-4, lr_value=1e-3)
    state = init_train_state(CFG, jax.random.PRNGKey(1))
    batch = _batch(b=8, seed=11)
    assert np.all(np.asarray(batch.mask) == 1.0)

    s_full, m_full = make_train_step(CFG, rl_full, donate=False)(state, batch)
    s_k, m_k = make_train_step(CFG, rl_micro, donate=False)(state, batch)

    assert _max_diff(s_full.params, s_k.params) < 1e-5
    # the accumulated adv stats are sums — identical partitioning or not
    assert abs(float(s_full.adv_norm.count) - float(s_k.adv_norm.count)) < 1e-3


# ---------------------------------------------------------------------------
# ZeRO-2 wiring (satellite): moments under shard_moments_spec, realized
# per-device footprint == the analytic claim
# ---------------------------------------------------------------------------

def test_moment_shardings_single_device_noop():
    """On a 1-device mesh init_train_state's ZeRO path must be a no-op."""
    from repro.launch.mesh import make_local_mesh
    mesh = make_local_mesh()
    s0 = init_train_state(CFG, jax.random.PRNGKey(0))
    s1 = init_train_state(CFG, jax.random.PRNGKey(0), mesh=mesh)
    assert _max_diff(s0.opt.mu, s1.opt.mu) == 0.0


def test_program_declares_zero_specs():
    """With a mesh, optim_update's state buffer declares params under the
    TP rules and moments additionally sharded over ``data``."""
    from jax.sharding import AbstractMesh
    from jax.sharding import PartitionSpec as P
    mesh = AbstractMesh((16, 16), ("data", "model"))
    prog = build_train_step_program(CFG, RLConfig(), mesh=mesh)
    specs = prog.stage("optim_update").specs["state"]
    assert set(specs) == {"params", "moments", "scalars"}
    assert specs["scalars"] == P()
    n_zero = sum(
        1 for pp, mp in zip(jax.tree.leaves(specs["params"]),
                            jax.tree.leaves(specs["moments"]))
        if mp != pp and any(
            "data" in (e if isinstance(e, tuple) else (e,)) for e in mp))
    assert n_zero > 0, "no moment tensor picked up a data-axis shard"


_REALIZED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax, jax.numpy as jnp
from jax.sharding import Mesh
import numpy as np
from repro.optim import adamw, zero

D = 8
mesh = Mesh(np.array(jax.devices()).reshape(D, 1), ("data", "model"))
# every axis divisible by D -> the analytic bound is achieved exactly
params = {"w1": jnp.zeros((64, 32)), "w2": jnp.zeros((16, 128)),
          "b": jnp.zeros((256,))}
opt = adamw.init(params)
opt = zero.shard_opt_state(opt, mesh)
count = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
want = zero.moments_bytes_per_device(count, D, zero=True)
got = zero.realized_moments_bytes_per_device(opt)
assert got == want, (got, want)
# and the un-sharded baseline really is D x bigger
assert zero.realized_moments_bytes_per_device(adamw.init(params)) \
    == zero.moments_bytes_per_device(count, D, zero=False)
print("OK", got)
"""


def test_realized_moments_bytes_match_analytic():
    """Spawn with 8 forced CPU devices: the measured per-device moment
    footprint equals ``moments_bytes_per_device`` (the §3.1 claim)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _REALIZED_SCRIPT],
                         capture_output=True, text=True, timeout=300,
                         env=env)
    assert out.returncode == 0, out.stderr
    assert out.stdout.startswith("OK")
