"""Logical-axis partition rules for every architecture family.

Parameters are plain-dict pytrees; specs are assigned by matching the leaf
*path* (e.g. ``layers/attn/wq``) against a rules table of *candidate*
shardings. Each candidate is ``(axis_index, mesh_axis_or_tuple)``; the
first candidate whose dimension is divisible by the mesh-axis size wins,
so one rules table covers all ten assigned architectures (head counts,
KV-group counts, vocab sizes and expert counts all differ in divisibility).

Baseline layout (DESIGN.md §5):
  * ``model`` — tensor parallel: heads / d_ff / experts / vocab
  * ``data``  — batch; Adam moments additionally ZeRO-2-sharded on it;
    for >30B-param archs the expert/ff axes are *also* sharded on ``data``
    (FSDP-style) so dbrx-132b fits in 16 GB/chip.
  * ``pod``   — outermost data-parallel axis in the multi-pod mesh.
"""
from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig

Axis = Union[str, Tuple[str, ...]]
Candidate = Tuple[int, Axis]

# FSDP threshold: above this parameter count, weight matrices are also
# sharded over ``data`` (granite-20b/starcoder2/dbrx: f32 gradients at
# tensor-parallel-only sharding would alone eat 5-7 GB of the 16 GB HBM).
FSDP_PARAM_THRESHOLD = 12_000_000_000


def _axis_size(mesh: Mesh, axis: Axis) -> int:
    if isinstance(axis, tuple):
        return int(np.prod([mesh.shape[a] for a in axis]))
    return mesh.shape[axis]


def _first_fit(shape: Sequence[int], candidates: List[Candidate],
               mesh: Mesh, taken: Optional[Dict[int, Axis]] = None
               ) -> Dict[int, Axis]:
    """Greedy multi-axis assignment: place each candidate mesh axis on the
    first tensor dim that divides, skipping dims already taken."""
    out: Dict[int, Axis] = dict(taken or {})
    used_mesh = {a for ax in out.values()
                 for a in (ax if isinstance(ax, tuple) else (ax,))}
    for idx, axis in candidates:
        names = axis if isinstance(axis, tuple) else (axis,)
        if any(a in used_mesh for a in names):
            continue
        i = idx if idx >= 0 else len(shape) + idx
        if i in out or i < 0 or i >= len(shape):
            continue
        if shape[i] % _axis_size(mesh, axis) == 0 and shape[i] > 1:
            out[i] = axis
            used_mesh.update(names)
    return out


def _to_spec(shape: Sequence[int], assign: Dict[int, Axis]) -> P:
    return P(*[assign.get(i) for i in range(len(shape))])


# ---------------------------------------------------------------------------
# Rules table — matched against the '/'-joined leaf path.
# ``{S}`` marks entries whose axis indices are relative to the *unstacked*
# tensor; a leading layer-stack dim shifts them by +1 automatically.
# ---------------------------------------------------------------------------

# Hillclimb toggle (EXPERIMENTS.md §Perf, musicgen): when head count does
# not divide the model axis, prefer sharding the CONTRACTING d_model axis
# (one all-reduce per layer) over head_dim (an all-reduce per KV block).
ATTN_PREFER_DMODEL = False

# (pattern, tp_candidates, fsdp_candidates)
_RULES: List[Tuple[str, List[Candidate], List[Candidate]]] = [
    # embedding: vocab on model, fallback d_model
    (r"embed/table$", [(0, "model"), (1, "model")], [(1, "data")]),
    (r"action_head/w$", [(1, "model"), (0, "model")], []),
    (r"prefix_proj/w$", [(1, "model")], []),
    # attention
    (r"attn/wq$", [(1, "model"), (2, "model"), (0, "model")], [(0, "data")]),
    (r"attn/wk$", [(1, "model"), (2, "model"), (0, "model")], [(0, "data")]),
    (r"attn/wv$", [(1, "model"), (2, "model"), (0, "model")], [(0, "data")]),
    (r"attn/wo$", [(0, "model"), (1, "model"), (2, "model")], [(2, "data")]),
    # latent attention: the latent projection is replicated, its per-head
    # expansion takes the heads
    (r"attn/w_kva$", [], [(0, "data")]),
    (r"attn/w_kvb$", [(1, "model"), (2, "model")], [(0, "data")]),
    # dense MLP: d_ff on model
    (r"mlp/w_gate$", [(1, "model")], [(0, "data")]),
    (r"mlp/w_up$", [(1, "model")], [(0, "data")]),
    (r"mlp/w_down$", [(0, "model")], [(1, "data")]),
    # MoE: experts on model, per-expert ff on data when FSDP
    (r"moe/router$", [], []),
    (r"moe/w_gate$", [(0, "model")], [(2, "data")]),
    (r"moe/w_up$", [(0, "model")], [(2, "data")]),
    (r"moe/w_down$", [(0, "model")], [(1, "data")]),
    (r"moe/shared/w_gate$", [(1, "model")], [(0, "data")]),
    (r"moe/shared/w_up$", [(1, "model")], [(0, "data")]),
    (r"moe/shared/w_down$", [(0, "model")], [(1, "data")]),
    # Mamba2 / SSD
    (r"ssm/in_proj$", [(1, "model")], [(0, "data")]),
    (r"ssm/in_proj_z$", [(1, "model")], [(0, "data")]),
    (r"ssm/in_proj_x$", [(1, "model")], [(0, "data")]),
    (r"ssm/in_proj_dt$", [(1, "model")], [(0, "data")]),
    (r"ssm/conv_w$", [(1, "model")], []),
    (r"ssm/conv_b$", [(0, "model")], []),
    (r"ssm/out_proj$", [(0, "model")], [(1, "data")]),
    (r"ssm/norm_scale$", [(0, "model")], []),
    # value head (f32): the hidden MLP is d×d — shard its wide axis
    (r"value_head/mlp_w1$", [(1, "model")], [(0, "data")]),
    (r"value_head/step_emb$", [(1, "model")], []),
    # everything small (norm scales, A_log, D, dt_bias, biases)
    (r".*", [], []),
]

# Tensors whose *unstacked* attention variants appear inside attn/wq etc. —
# the attention head axis of wq is index 1 post-d_model? No: wq is
# [d, H, hd]; candidates above index the unstacked tensor, preferring the
# head axis (1) and falling back to d_model (0 via index 2? see note).
# NOTE: for wq [d, H, hd] the candidate list (1, 'model') = heads,
# (2, 'model') = head_dim, (0, 'model') = d_model; musicgen (H=24) falls
# through to head_dim (64 % 16 == 0).


def _match(path: str) -> Tuple[List[Candidate], List[Candidate]]:
    for pat, tp, fsdp in _RULES:
        if re.search(pat, path):
            if ATTN_PREFER_DMODEL and pat.startswith(r"attn/w"):
                if pat == r"attn/wo$":
                    tp = [(0, "model"), (2, "model"), (1, "model")]
                else:
                    tp = [(1, "model"), (0, "model"), (2, "model")]
            return tp, fsdp
    return [], []


def _leaf_path(path) -> str:
    parts = []
    for p in path:
        parts.append(str(getattr(p, "key", getattr(p, "name", p))))
    return "/".join(parts)


def _is_stacked(path: str) -> bool:
    return path.startswith(("layers/", "layers_rem/", "layers_dense/")) \
        or "/layers/" in path or "/layers_rem/" in path \
        or "/layers_dense/" in path


def param_specs(cfg: ModelConfig, param_shapes, mesh: Mesh,
                *, fsdp: Optional[bool] = None, tp: bool = True):
    """Partition-spec tree for a parameter pytree of ShapeDtypeStructs.

    ``tp=False`` (§Perf, pure data parallelism): parameters fully
    replicated — for models that fit per chip, dropping tensor parallelism
    removes every per-layer collective; only the gradient all-reduce
    remains."""
    if fsdp is None:
        fsdp = cfg.param_count() > FSDP_PARAM_THRESHOLD
    if not tp:
        return jax.tree.map(lambda leaf: P(*([None] * len(leaf.shape))),
                            param_shapes)

    def assign(path, leaf):
        pstr = _leaf_path(path)
        shape = leaf.shape
        tp, fs = _match(pstr)
        shift = 1 if _is_stacked(pstr) else 0
        cands = [(i + shift if i >= 0 else i, a) for i, a in tp]
        if fsdp:
            cands += [(i + shift if i >= 0 else i, a) for i, a in fs]
        return _to_spec(shape, _first_fit(shape, cands, mesh))

    return jax.tree_util.tree_map_with_path(assign, param_shapes)


def batch_axes(mesh: Mesh) -> Axis:
    """The (composite) data-parallel axis: ('pod','data') on multi-pod."""
    return ("pod", "data") if "pod" in mesh.shape else ("data",)


def data_spec(mesh: Mesh, global_batch: int, ndim: int,
              *, seq_axis: Optional[int] = None,
              seq_len: int = 0) -> P:
    """Sharding for a batch tensor [B, ...]. Batch goes on the composite
    data axis when divisible; otherwise (long_500k, B=1) the sequence axis
    is sharded over ``data`` instead (context parallelism)."""
    dp = batch_axes(mesh)
    dp_size = _axis_size(mesh, tuple(dp))
    entries: List[Optional[Axis]] = [None] * ndim
    if global_batch % dp_size == 0 and global_batch > 1:
        entries[0] = tuple(dp) if len(dp) > 1 else dp[0]
    elif seq_axis is not None and seq_len % mesh.shape["data"] == 0:
        entries[seq_axis] = "data"
    return P(*entries)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def cache_specs(cfg: ModelConfig, cache_shapes, mesh: Mesh,
                global_batch: int, cache_len: int,
                seq_shard_model: bool = False):
    """Sharding for the DecodeCache pytree (leaves carry a leading stacked
    layer axis, then batch). Batch shards on data when divisible; for
    batch=1 long-context the KV sequence axis shards on data (context
    parallel). Head-ish axes go on model when divisible."""
    dp = batch_axes(mesh)
    dp_size = _axis_size(mesh, tuple(dp))
    batch_ok = global_batch % dp_size == 0 and global_batch > 1

    def assign(path, leaf):
        pstr = _leaf_path(path)
        shape = leaf.shape
        assign_map: Dict[int, Axis] = {}
        if batch_ok and len(shape) >= 2:
            assign_map[1] = tuple(dp) if len(dp) > 1 else dp[0]
        if pstr.endswith((".k", ".v", "/k", "/v")) or "positions" in pstr:
            # KVCache: [L, B, S, KV, hd]
            if not batch_ok and len(shape) >= 3 \
                    and shape[2] % mesh.shape["data"] == 0 and shape[2] > 1:
                assign_map[2] = "data"
            if seq_shard_model and len(shape) >= 3 \
                    and shape[2] % mesh.shape["model"] == 0:
                # flash-decoding context parallelism (§Perf): shard the KV
                # SEQUENCE over model; softmax combines partial (max, sum)
                assign_map[2] = ("data", "model") \
                    if assign_map.get(2) == "data" else "model"
            elif len(shape) == 5:
                assign_map.update(_first_fit(
                    shape, [(3, "model"), (4, "model")], mesh,
                    taken=assign_map))
        elif "ssm" in pstr and len(shape) == 5:
            # SSMState.ssm: [L, B, H, P, N] — heads on model
            assign_map.update(_first_fit(
                shape, [(2, "model"), (3, "model")], mesh, taken=assign_map))
        elif "conv" in pstr and len(shape) == 4:
            # SSMState.conv: [L, B, K-1, C] — channels on model
            assign_map.update(_first_fit(
                shape, [(3, "model")], mesh, taken=assign_map))
        return _to_spec(shape, assign_map)

    return jax.tree_util.tree_map_with_path(assign, cache_shapes)
