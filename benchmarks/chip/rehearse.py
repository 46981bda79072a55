"""Compile each configuration's programs for a described, unattached v5e.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py [cell ...]

For every cell of ``BENCHMARK.json`` (all, or those named): the jitted
weights of the configuration's family, then for a train cell the
program's donated train step at the mix's shapes, and for a serve cell
the program's inference fn at each batch bucket, then the family's
reference programs (``reference_programs``), through the TPU compiler on a v5e described by
``jax.experimental.topologies``. Prints each program's
``memory_analysis()``. Nothing runs on a chip, so this says nothing about
results or times; it refuses what the chip's compiler would refuse and
shows what a program alone holds on the device.
"""
from __future__ import annotations

import functools
import json
import os
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1] / "src"), str(HERE)]


def _mem(compiled) -> str:
    m = compiled.memory_analysis()
    return (f"arguments {m.argument_size_in_bytes} output "
            f"{m.output_size_in_bytes} alias {m.alias_size_in_bytes} temp "
            f"{m.temp_size_in_bytes} (peak "
            f"{m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes + m.temp_size_in_bytes})")


def main(names) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import drive
    import families
    import traffic_gen
    from repro.core.train_step import init_train_state
    from repro.data.trajectory import TrajectoryBatch
    from repro.kernels import dispatch
    from repro.models.policy import make_inference_fn
    from repro.models.transformer import FRONTEND_DIM
    from repro.runtime.step_program import build_train_step_program

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    place = lambda tree: jax.tree.map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=one),
        tree)
    dispatch.interpret_mode = lambda: False
    bench = json.loads((HERE.parents[1] / "BENCHMARK.json").read_text())
    files = {c["name"]: c["file"] for c in bench["configs"]}
    for cell in bench["workloads"]:
        if names and cell["name"] not in names:
            continue
        config = json.loads((HERE.parents[1] / files[cell["config"]])
                            .read_text())
        mix = json.loads((HERE / "traffic" / f"{cell['traffic']}.json")
                         .read_text())
        fam = families.load(config)
        cfg = fam.model_config(config)
        draw = jax.jit(functools.partial(fam.draw_params, config))
        shapes = place(jax.eval_shape(draw, jax.random.PRNGKey(0)))
        pbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
        c = draw.lower(place(jax.random.PRNGKey(0))).compile()
        print(f"{cell['name']} weights ({pbytes} bytes): {_mem(c)}",
              flush=True)
        if mix["entry"] == "train":
            rl = drive._rl(mix)
            batch = traffic_gen.train_batch(np.random.default_rng(0), mix,
                                            config)
            state = place(jax.eval_shape(lambda k: init_train_state(cfg, k),
                                         jax.random.PRNGKey(0)))
            with dispatch.forced("pallas"):
                step = build_train_step_program(cfg, rl).fused(donate=True)
                c = step.lower(state, place(TrajectoryBatch(**batch))
                               ).compile()
            print(f"{cell['name']} train step: {_mem(c)}", flush=True)
        else:
            t = mix["instruction_tokens"]
            with dispatch.forced("pallas"):
                fn = make_inference_fn(cfg)
                for nb in mix["buckets"]:
                    c = fn.lower(
                        shapes, place(jax.random.PRNGKey(0)),
                        place(np.zeros((nb, t), np.int32)),
                        place(np.zeros((nb,), np.int32)),
                        place(np.zeros((nb, 1, FRONTEND_DIM), np.float32))
                    ).compile()
                    print(f"{cell['name']} inference fn (batch {nb}): "
                          f"{_mem(c)}", flush=True)
        for label, fn, args in fam.reference_programs(config, mix):
            c = fn.lower(*place(args)).compile()
            print(f"{cell['name']} {label}: {_mem(c)}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
