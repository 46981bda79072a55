"""Operations and bytes of each kernel family and of whole steps, from shapes.

Every count uses the true shapes of the work (a 20-token sequence, the
valid part of a decode cache), never the tiles a kernel pads to, so a
kernel's roofline share reads the same work whatever implements it and
padding shows as a low share. Recomputed operations are not counted: the
flash backward's replay of QK^T is left out, and so is a second dO V^T.
A multiply-add is two operations; bf16 operands are two bytes.
"""
from __future__ import annotations

from typing import Dict

BF16, F32 = 2, 4


def causal_pairs(t: int) -> int:
    """(query, key) pairs a causal attention over ``t`` tokens computes."""
    return t * (t + 1) // 2


def layer_matmul_params(c: Dict) -> int:
    """Matmul weights one decoder layer applies to every token."""
    d, hd = c["hidden_size"], c["head_dim"]
    attn = d * hd * (2 * c["num_attention_heads"]
                     + 2 * c["num_key_value_heads"])
    return attn + 3 * d * c["intermediate_size"]


def seq_shape(c: Dict, instruction_tokens: int) -> Dict[str, int]:
    h = c["policy_head"]
    return {"prefix": h["num_prefix_tokens"],
            "actions": h["action_dim"],
            "tokens": h["num_prefix_tokens"] + instruction_tokens
            + h["action_dim"]}


# ---------------------------------------------------------------------------
# flash attention (training): forward, dq and dk/dv kernels
# ---------------------------------------------------------------------------

def flash_train(c: Dict, rows: int, t: int) -> Dict[str, float]:
    """Flash forward + backward over ``rows`` sequences of ``t`` tokens in
    every layer. Forward: QK^T and PV (4·D per pair per head). Backward:
    dV, dP, dQ, dK (8·D per pair per head)."""
    h, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    layers = c["num_hidden_layers"]
    pairs = causal_pairs(t)
    flops = 12.0 * hd * pairs * h * rows * layers
    q = t * h * hd * BF16
    kvb = t * kv * hd * BF16
    row_stat = t * h * F32
    fwd = q + 2 * kvb + q + row_stat            # q, k, v in; o, lse out
    bwd_dq = q + 2 * kvb + q + 2 * row_stat + q  # q, k, v, do, lse, dd; dq
    bwd_dkv = q + 2 * kvb + q + 2 * row_stat + 2 * kvb   # ...; dk, dv
    return {"flops": flops,
            "bytes": float((fwd + bwd_dq + bwd_dkv) * rows * layers)}


# ---------------------------------------------------------------------------
# decode attention (serving): one query token against the valid cache
# ---------------------------------------------------------------------------

def decode_request(c: Dict, prompt: int, new_tokens: int) -> Dict[str, float]:
    """The decode kernel's work for one request that decodes
    ``new_tokens`` tokens after a ``prompt``-token prefill: the token fed
    at position p attends to p + 1 cache slots, in every layer."""
    h, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    layers = c["num_hidden_layers"]
    flops = by = 0.0
    for k in range(new_tokens):
        keys = prompt + k + 1
        flops += 4.0 * hd * keys * h
        by += 2 * keys * kv * hd * BF16 + 2 * h * hd * BF16 + keys * F32
    return {"flops": flops * layers, "bytes": by * layers}


# ---------------------------------------------------------------------------
# whole steps (model FLOPs)
# ---------------------------------------------------------------------------

def train_step_flops(c: Dict, segments: int, horizon: int,
                     instruction_tokens: int) -> float:
    """Model FLOPs of one optimizer step: forward and backward (3x the
    forward) of every matmul at the tokens it is applied to, attention at
    true T, over ``segments`` x (horizon + 1) teacher-forced rows."""
    s = seq_shape(c, instruction_tokens)
    d, t, a = c["hidden_size"], s["tokens"], s["actions"]
    h = c["policy_head"]
    rows = segments * (horizon + 1)
    per_row = 6.0 * layer_matmul_params(c) * t * c["num_hidden_layers"]
    per_row += 12.0 * c["head_dim"] * causal_pairs(t) \
        * c["num_attention_heads"] * c["num_hidden_layers"]
    per_row += 4.0 * h["frontend_dim"] * d * s["prefix"]   # fwd + weight grad
    per_row += 6.0 * (d * d + a * d + d)                    # value head
    loss_rows = segments * horizon                          # bootstrap excluded
    head = 6.0 * d * h["action_vocab_size"] * a * loss_rows
    return per_row * rows + head


def serve_request_flops(c: Dict, instruction_tokens: int) -> float:
    """Model FLOPs of one answered action request: prefill of the prefix
    and instruction, then one decode pass per action token; the action
    head on every position (prefill emits logits for each), the value head
    once."""
    s = seq_shape(c, instruction_tokens)
    d, a = c["hidden_size"], s["actions"]
    h = c["policy_head"]
    prompt = s["prefix"] + instruction_tokens
    layers = c["num_hidden_layers"]
    fwd = 2.0 * layer_matmul_params(c) * (prompt + a) * layers
    fwd += 4.0 * c["head_dim"] * c["num_attention_heads"] * layers * (
        causal_pairs(prompt) + sum(prompt + k + 1 for k in range(a)))
    fwd += 2.0 * h["frontend_dim"] * d * s["prefix"]
    fwd += 2.0 * d * h["action_vocab_size"] * (prompt + a)
    fwd += 2.0 * (d * d + a * d + d)
    return fwd
