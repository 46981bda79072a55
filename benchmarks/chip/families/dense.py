"""The ``dense`` family: causal MHA/GQA decoder layers with a SwiGLU MLP
(deepseek-7b, internlm2-1.8b), the family of a configuration file that
names none.

Weights from ``weights.py``, the plain reference from ``reference.py``,
operation counts from ``flops.py``; the configuration's sizes map onto the
program's registry arch.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import flops
import reference
import weights
from flops import (seq_shape, serve_request_flops,  # noqa: F401
                   train_step_flops)
from reference import Spec, serve_readings, train_reference  # noqa: F401

draw_params = weights.draw


def model_config(config: Dict):
    """The program's ModelConfig for a configuration file: the registry
    arch with every size the file states."""
    from repro.configs import get_config
    from repro.models.transformer import FRONTEND_DIM
    ph = config["policy_head"]
    if ph["frontend_dim"] != FRONTEND_DIM:
        raise ValueError(f"frontend_dim {ph['frontend_dim']} is not the "
                         f"program's stub frontend width {FRONTEND_DIM}")
    d, h = config["hidden_size"], config["num_attention_heads"]
    cfg = dataclasses.replace(
        get_config(config["arch"]),
        num_layers=config["num_hidden_layers"], d_model=d, num_heads=h,
        num_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], vocab_size=config["vocab_size"],
        norm_eps=config["rms_norm_eps"], rope_theta=config["rope_theta"],
        action_vocab_size=ph["action_vocab_size"],
        action_dim=ph["action_dim"],
        max_episode_steps=ph["max_episode_steps"],
        num_prefix_tokens=ph["num_prefix_tokens"],
        param_dtype=config["torch_dtype"],
        compute_dtype=config["torch_dtype"],
        head_dim_override=(None if config["head_dim"] * h == d
                           else config["head_dim"]))
    if cfg.head_dim != config["head_dim"]:
        raise ValueError(f"head_dim {cfg.head_dim} != {config['head_dim']}")
    return cfg


def kernel_work(config: Dict, mix: Dict) -> Dict[str, float]:
    """The flash kernels' work in one optimizer step, or the decode
    kernel's in one answered request."""
    s = seq_shape(config, mix["instruction_tokens"])
    if mix["entry"] == "train":
        rows = mix["segments"] * (mix["horizon"] + 1)
        f = flops.flash_train(config, rows, s["tokens"])
        return {"flash_flops": f["flops"], "flash_bytes": f["bytes"]}
    d = flops.decode_request(config, s["prefix"] + mix["instruction_tokens"],
                             s["actions"])
    return {"decode_flops": d["flops"], "decode_bytes": d["bytes"]}


def reference_programs(config: Dict, mix: Dict) -> List[Tuple]:
    """The reference's device programs at the mix's shapes: a train
    micro-batch's float32 gradients over the embedding rows the checked
    steps read, or one block of served requests."""
    import traffic_gen
    from repro.models.transformer import FRONTEND_DIM

    spec = Spec.from_config(config)
    shapes = jax.eval_shape(functools.partial(draw_params, config),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    sds = lambda tree: jax.tree.map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype), tree)
    if mix["entry"] == "train":
        batch = traffic_gen.train_batch(np.random.default_rng(0), mix, config)
        rows = reference.used_rows([batch] * mix["checked_steps"],
                                   spec.vocab)
        p32 = {k: v for k, v in shapes.items() if k != "embed"}
        p32["rows"] = jax.ShapeDtypeStruct((len(rows), spec.d), jnp.float32)
        p32 = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, jnp.float32), p32)
        mb = {k: v[:mix["rl"]["micro_batch"]] for k, v in batch.items()
              if k != "policy_version"}
        mb["ids"] = mb["obs_tokens"]
        mb["action_ids"] = mb["actions"]
        adv = (np.float32(0),) * 3
        fn = jax.jit(lambda p, m, a: reference.micro_grads(
            p, m, a, mix["rl"], spec, "f32"))
        return [("reference micro-batch grads", fn,
                 (p32, sds(mb), sds(adv)))]
    n, t = reference.SERVE_ROWS, mix["instruction_tokens"]
    args = (shapes, jax.ShapeDtypeStruct((n, t), jnp.int32),
            jax.ShapeDtypeStruct((n, spec.action_dim), jnp.int32),
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n, 1, FRONTEND_DIM), jnp.float32))
    fn = jax.jit(functools.partial(reference.serve_block, spec=spec,
                                   prec="f32"))
    return [(f"reference serve block ({n} rows)", fn, args)]
