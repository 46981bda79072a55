"""Random weights of the ``dense`` family from a key, one layer at a time.

``drive.make_params`` jits :func:`draw` and calls it once, so the weights
are made on the device in one call from the seed. The tree has the
program's leaf names, shapes and dtypes (the harness checks it against the
program's own ``jax.eval_shape`` of its init before handing it over):
matrices are drawn from a normal of standard deviation
``initializer_range`` (0.02, as the published configs state) truncated at
two deviations, norm scales are ones and biases zeros. Weights are in the
configuration's ``torch_dtype``; the value head is float32.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

INIT_STD = 0.02


def normal(key, shape, dtype):
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * INIT_STD).astype(dtype)


def stacked(key, n: int, shape, dtype):
    """[n, *shape], one layer at a time, so that no more than one layer's
    float32 draw is live on the device."""
    return jax.lax.map(lambda k: normal(k, shape, dtype),
                       jax.random.split(key, n))


def draw(c: Dict, key):
    d, h, kv, hd = (c["hidden_size"], c["num_attention_heads"],
                    c["num_key_value_heads"], c["head_dim"])
    ff, n = c["intermediate_size"], c["num_hidden_layers"]
    ph = c["policy_head"]
    w = jnp.dtype(c["torch_dtype"])
    f32 = jnp.float32
    ks = iter(jax.random.split(key, 16))
    return {
        "embed": {"table": normal(next(ks), (c["vocab_size"], d), w)},
        "final_norm": {"scale": jnp.ones((d,), w)},
        "action_head": {"w": normal(next(ks), (d, ph["action_vocab_size"]),
                                     w)},
        "prefix_proj": {"w": normal(next(ks), (ph["frontend_dim"], d), w)},
        "layers": {
            "attn_norm": {"scale": jnp.ones((n, d), w)},
            "attn": {"wq": stacked(next(ks), n, (d, h, hd), w),
                     "wk": stacked(next(ks), n, (d, kv, hd), w),
                     "wv": stacked(next(ks), n, (d, kv, hd), w),
                     "wo": stacked(next(ks), n, (h, hd, d), w)},
            "mlp_norm": {"scale": jnp.ones((n, d), w)},
            "mlp": {"w_gate": stacked(next(ks), n, (d, ff), w),
                    "w_up": stacked(next(ks), n, (d, ff), w),
                    "w_down": stacked(next(ks), n, (ff, d), w)},
        },
        "value_head": {
            "attn_proj": normal(next(ks), (d, 1), f32),
            "step_emb": normal(next(ks), (ph["max_episode_steps"], d), f32),
            "mlp_w1": normal(next(ks), (d, d), f32),
            "mlp_b1": jnp.zeros((d,), f32),
            "mlp_w2": normal(next(ks), (d, 1), f32),
            "mlp_b2": jnp.zeros((1,), f32),
        },
    }
