"""Top-k mixture of experts over the share of the experts this device holds.

The router scores every token against all ``num_experts`` (softmax in
float32) and takes the greedy top-k; ``norm_topk_prob`` renormalises the
k gates. The layer holds experts ``held_first .. held_first + num_held -
1``: it sorts the (token, expert) assignments that land on them by
expert, runs each expert's SwiGLU as a
grouped matmul over its rows (``kernels.dispatch.grouped_matmul``), and
weights each result by its gate. Nothing is dropped, and an assignment to
an expert held elsewhere costs no matmul here: its part of the output is
the holder's to add. The shared experts, where the configuration has
them, run on every token as one SwiGLU, and every holder adds them.

The auxiliary load-balance and router-z losses, over all experts, are
returned for the RL objective with the share's counters: the fraction of
assignments that land on held experts and the load of the busiest held
expert over the mean.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig
from repro.kernels import dispatch
from repro.models.layers import Params, dense_init, mlp, mlp_init


def moe_init(key, d_model: int, cfg: MoEConfig, dtype) -> Params:
    kr, kg, ku, kd, ks = jax.random.split(key, 5)
    e, ff = cfg.num_held, cfg.d_ff
    params = {
        "router": dense_init(kr, (d_model, cfg.num_experts), jnp.float32),
        "w_gate": dense_init(kg, (e, d_model, ff), dtype),
        "w_up": dense_init(ku, (e, d_model, ff), dtype),
        "w_down": dense_init(kd, (e, ff, d_model), dtype),
    }
    if cfg.num_shared_experts:
        params["shared"] = mlp_init(ks, d_model,
                                    cfg.num_shared_experts * ff, dtype)
    return params


def route(router: jnp.ndarray, x: jnp.ndarray, cfg: MoEConfig):
    """x: [N, d] -> (logits [N, E] f32, gates [N, k] f32, experts [N, k])."""
    logits = x.astype(jnp.float32) @ router
    gates, experts = jax.lax.top_k(jax.nn.softmax(logits, axis=-1),
                                   cfg.top_k)
    if cfg.norm_topk_prob:
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
    return logits, gates, experts


@functools.partial(jax.checkpoint, static_argnums=(4,))
def held_experts(params: Params, x: jnp.ndarray, gates: jnp.ndarray,
                 experts: jnp.ndarray, cfg: MoEConfig):
    """The held experts' gated part of the output, x: [N, d] -> ([N, d],
    per-held-expert row counts [H]).

    The row buffer is sized for every assignment (all could land here),
    so its intermediates are not kept for the backward pass: it is
    recomputed from x and the routing (``jax.checkpoint``)."""
    n, d = x.shape
    k, h = cfg.top_k, cfg.num_held
    local = experts.reshape(-1) - cfg.held_first                # [N*k]
    held = (local >= 0) & (local < h)
    key = jnp.where(held, local, h)         # absent experts sort last
    order = jnp.argsort(key, stable=True)
    sizes = jnp.bincount(key, length=h + 1)[:h].astype(jnp.int32)
    token = order // k
    rows = jnp.take(x, token, axis=0)                           # [N*k, d]
    gate = jnp.where(held, gates.reshape(-1), 0.0)[order].astype(x.dtype)
    mm = lambda a, w: dispatch.grouped_matmul(a, w, sizes)     # noqa: E731
    act = jax.nn.silu(mm(rows, params["w_gate"])) * mm(rows, params["w_up"])
    # rows of absent experts are 0 out of the grouped matmul
    out = mm(act * gate[:, None], params["w_down"])
    return jnp.zeros_like(x).at[token].add(out), sizes


def moe_forward(params: Params, x: jnp.ndarray, cfg: MoEConfig
                ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """x: [B, T, d] -> (this share's part of the layer output [B, T, d],
    aux losses and counters)."""
    b, t, d = x.shape
    xf = x.reshape(b * t, d)
    with jax.named_scope("moe_route"):
        logits, gates, experts = route(params["router"], xf, cfg)
    with jax.named_scope("moe_experts"):
        out, sizes = held_experts(params, xf, gates, experts, cfg)
    if "shared" in params:
        with jax.named_scope("moe_shared"):
            out = out + mlp(params["shared"], xf)

    e = cfg.num_experts
    probs = jax.nn.softmax(logits, axis=-1)
    ce = jax.nn.one_hot(jnp.argmax(probs, axis=-1), e).mean(axis=0)
    load_balance = e * jnp.sum(probs.mean(axis=0) * ce)
    router_z = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
    held = sizes.sum()
    aux = {
        "load_balance": cfg.load_balance_coef * load_balance,
        "router_z": cfg.router_z_coef * router_z,
        "held_frac": held / experts.size,
        "load_max": sizes.max() * cfg.num_held / jnp.maximum(held, 1),
    }
    return out.reshape(b, t, d), aux
