"""A family for the harness's tests only: the dense decoder with each
layer's MLP held as the one expert of the program's ``moe`` layer (one
expert, top 1, so no token is dropped and the gate is 1; no auxiliary
losses). Its weights are the dense family's with one leaf more, the
router; its reference is the dense one with the expert in the MLP's
place; its work adds the expert's matmuls under a key of its own. Every
piece comes from this file, so a run of it shows the harness taking a
family's pieces and not the dense ones.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict

import jax
import jax.numpy as jnp

import flops
import reference
import weights
from families import dense
from flops import (seq_shape, serve_request_flops,  # noqa: F401
                   train_step_flops)
from reference import Spec  # noqa: F401

EXPERT = ("w_gate", "w_up", "w_down")


def model_config(config: Dict):
    from repro.configs.base import MoEConfig
    return dataclasses.replace(
        dense.model_config(config), arch_type="moe",
        moe=MoEConfig(num_experts=1, top_k=1,
                      d_ff=config["intermediate_size"], router_z_coef=0.0,
                      load_balance_coef=0.0))


def draw_params(config: Dict, key):
    k_dense, k_router = jax.random.split(key)
    p = weights.draw(config, k_dense)
    layers = dict(p["layers"])
    mlp = layers.pop("mlp")
    layers["moe"] = {k: mlp[k][:, None] for k in EXPERT}
    layers["moe"]["router"] = weights.stacked(
        k_router, config["num_hidden_layers"], (config["hidden_size"], 1),
        jnp.float32)
    return {**p, "layers": layers}


def decoder(params, table, ids, prefix, spec, prec):
    layers = dict(params["layers"])
    moe = layers.pop("moe")
    layers["mlp"] = {k: moe[k][:, 0] for k in EXPERT}
    return reference.decoder({**params, "layers": layers}, table, ids,
                             prefix, spec, prec)


train_reference = functools.partial(reference.train_reference,
                                    decoder=decoder)
serve_readings = functools.partial(reference.serve_readings, decoder=decoder)


def kernel_work(config: Dict, mix: Dict) -> Dict[str, float]:
    """The dense kernels' work and the expert's three matmuls over every
    token of a train step (forward and backward) or of a request."""
    s = seq_shape(config, mix["instruction_tokens"])
    per_token = 2.0 * 3 * config["hidden_size"] \
        * config["intermediate_size"] * config["num_hidden_layers"]
    if mix["entry"] == "train":
        tokens = mix["segments"] * (mix["horizon"] + 1) * s["tokens"]
        per_token *= 3
    else:
        tokens = s["tokens"]
    return {**dense.kernel_work(config, mix),
            "expert_flops": per_token * tokens,
            "expert_bytes": float(3 * config["hidden_size"]
                                  * config["intermediate_size"]
                                  * config["num_hidden_layers"]
                                  * flops.BF16)}
