"""Distribution-layer tests: partition rules must produce divisible,
duplicate-free specs for EVERY assigned architecture on both production
meshes — cheap structural checks (AbstractMesh, no devices)."""
import jax
import numpy as np
import pytest
from jax.sharding import AbstractMesh
from jax.sharding import PartitionSpec as P

from repro.configs import ASSIGNED_ARCHS, INPUT_SHAPES, get_config
from repro.launch import steps
from repro.optim import zero
from repro.sharding import rules

MESHES = {
    "16x16": AbstractMesh((16, 16), ("data", "model")),
    "2x16x16": AbstractMesh((2, 16, 16), ("pod", "data", "model")),
}


def _axis_size(mesh, axis):
    if isinstance(axis, tuple):
        return int(np.prod([mesh.shape[a] for a in axis]))
    return mesh.shape[axis]


def _check_spec_tree(shapes, specs, mesh):
    leaves_sh = jax.tree.leaves(shapes)
    leaves_sp = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(leaves_sh) == len(leaves_sp)
    for sh, sp in zip(leaves_sh, leaves_sp):
        used = []
        for i, axis in enumerate(sp):
            if axis is None:
                continue
            names = axis if isinstance(axis, tuple) else (axis,)
            for nm in names:
                assert nm not in used, f"dup axis {nm} in {sp} for {sh.shape}"
                used.append(nm)
            assert sh.shape[i] % _axis_size(mesh, axis) == 0, \
                f"{sh.shape}[{i}] not divisible by {axis} under {sp}"


@pytest.mark.parametrize("mesh_name", list(MESHES))
@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_param_specs_divisible(arch, mesh_name):
    cfg = get_config(arch)
    mesh = MESHES[mesh_name]
    shapes = steps.param_structs(cfg)
    for fsdp in (False, True):
        specs = rules.param_specs(cfg, shapes, mesh, fsdp=fsdp)
        _check_spec_tree(shapes, specs, mesh)


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_zero_moments_specs(arch):
    cfg = get_config(arch)
    mesh = MESHES["16x16"]
    shapes = steps.param_structs(cfg)
    pspec = rules.param_specs(cfg, shapes, mesh, fsdp=True)
    mspec = zero.shard_moments_spec(shapes, pspec, data_axis="data",
                                    data_size=16)
    _check_spec_tree(shapes, mspec, mesh)


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_big_tensors_are_sharded(arch):
    """No parameter tensor above 64 MiB (bf16) may stay fully replicated
    on the single-pod mesh — the memory-fit precondition."""
    cfg = get_config(arch)
    mesh = MESHES["16x16"]
    shapes = steps.param_structs(cfg)
    fsdp = cfg.param_count() > rules.FSDP_PARAM_THRESHOLD
    specs = rules.param_specs(cfg, shapes, mesh, fsdp=fsdp)

    def check(path, sh, sp):
        nbytes = int(np.prod(sh.shape)) * 2
        if nbytes > 64 * 2**20:
            assert any(a is not None for a in sp), \
                f"{path}: {sh.shape} ({nbytes/2**20:.0f} MiB) replicated"
    jax.tree_util.tree_map_with_path(
        lambda p, sh, sp: check(p, sh, sp), shapes, specs,
        is_leaf=lambda x: isinstance(x, P))


@pytest.mark.parametrize("shape", INPUT_SHAPES, ids=lambda s: s.name)
def test_data_specs(shape):
    mesh = MESHES["16x16"]
    spec = rules.data_spec(mesh, shape.global_batch, 2, seq_axis=1,
                           seq_len=shape.seq_len)
    if shape.global_batch >= 16:
        assert spec[0] is not None          # batch sharded on data
    else:
        assert spec[0] is None              # long_500k: context parallelism
        assert spec[1] == "data"


@pytest.mark.parametrize("arch", ["granite-20b", "zamba2-1.2b",
                                  "mamba2-2.7b", "dbrx-132b"])
def test_cache_specs_cover_decode(arch):
    import jax.numpy as jnp
    from repro.models import transformer
    cfg = get_config(arch)
    mesh = MESHES["16x16"]
    cache = jax.eval_shape(
        lambda: transformer.init_decode_cache(cfg, 128, 4096))
    specs = rules.cache_specs(cfg, cache, mesh, 128, 4096)
    _check_spec_tree(cache, specs, mesh)
    # the KV/state payload must be batch-sharded
    flat = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert any(any(a is not None for a in sp) for sp in flat)


def test_choose_accum_monotone():
    from repro.configs.base import ShapeConfig
    mesh = MESHES["16x16"]
    small = get_config("internlm2-1.8b")
    big = get_config("granite-20b")
    shp = ShapeConfig("train_4k", 4096, 256, "train")
    assert steps.choose_accum(big, shp, mesh) >= \
        steps.choose_accum(small, shp, mesh)
