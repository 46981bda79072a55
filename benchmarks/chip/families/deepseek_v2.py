"""The ``deepseek_v2`` family: DeepSeek-V2 decoder layers, multi-head
latent attention (no query LoRA) with YaRN rotary embedding, leading dense
SwiGLU layers, then mixture-of-experts layers of which this chip holds a
share, with shared experts.

The plain float32 reference of the block, written from the published
equations and importing nothing of the program:

* MLA: ``q = x W_q`` split per head into ``[q_nope | q_pe]``;
  ``[c_kv | k_pe] = x W_kva``, ``c_kv`` RMS-normed; ``[k_nope | v] =
  c_kv W_kvb`` per head; ``q_pe`` and the one shared ``k_pe`` take YaRN
  rotary embedding (rotate-half layout, as the configuration's
  ``assumed`` says); causal softmax at ``qk_head_dim ** -0.5 * m ** 2``,
  ``m = 0.1 mscale_all_dim ln(factor) + 1``; ``o W_o``;
* the expert layer: ``s = softmax(x W_g)`` over all the router's experts,
  the greedy top-k with weights ``s_i`` (renormalised only under
  ``norm_topk_prob``; a ``routed_scaling_factor`` other than 1 is
  refused, as the program has none), the held experts'
  ``sum s_i E_i(x)`` (``E_i`` a SwiGLU, computed here on every token and
  weighted by a gate that is 0 where the expert was not chosen) plus the
  shared experts ``S(x)`` as one SwiGLU.

The GIPO loss, value head, AdamW and the float8 control come from
``reference.py``. ``train_reference`` takes each micro-batch in blocks of
segments, whose losses over the micro-batch's own denominators add up to
the micro-batch's, so that the float32 step fits on the chip once the
program's state is freed.
"""
# no ``from __future__ import annotations``: ``families.load`` runs this
# file as a module outside ``sys.modules``, where a dataclass cannot
# resolve string annotations
import dataclasses
import functools
import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import flops
import reference
import weights
from flops import seq_shape  # noqa: F401
from reference import NEG_INF, mm, rmsnorm

BLOCK_SEGMENTS = 8        # segments of a micro-batch in one reference block


@dataclasses.dataclass(frozen=True)
class Spec:
    """Sizes of one configuration file."""

    d: int
    heads: int
    q_nope: int
    q_rope: int
    v_dim: int
    kv_rank: int
    d_ff: int
    moe_ff: int
    experts: int              # the router's width, as published
    held: int
    held_first: int
    top_k: int
    shared: int
    first_dense: int
    layers: int
    vocab: int
    action_vocab: int
    action_dim: int
    prefix_tokens: int
    frontend_dim: int
    eps: float
    rope_theta: float
    yarn: Tuple[float, ...]   # factor, original max positions, beta_fast,
    #                           beta_slow, mscale, mscale_all_dim
    norm_topk: bool

    @classmethod
    def from_config(cls, c: Dict) -> "Spec":
        h, y = c["policy_head"], c["rope_scaling"]
        if c["routed_scaling_factor"] != 1:
            raise ValueError("routed_scaling_factor "
                             f"{c['routed_scaling_factor']}: the program "
                             "scales no routed gate")
        return cls(
            d=c["hidden_size"], heads=c["num_attention_heads"],
            q_nope=c["qk_nope_head_dim"], q_rope=c["qk_rope_head_dim"],
            v_dim=c["v_head_dim"], kv_rank=c["kv_lora_rank"],
            d_ff=c["intermediate_size"], moe_ff=c["moe_intermediate_size"],
            experts=c["published"]["n_routed_experts"],
            held=c["n_routed_experts"], held_first=c["experts_held_from"],
            top_k=c["num_experts_per_tok"], shared=c["n_shared_experts"],
            first_dense=c["first_k_dense_replace"],
            layers=c["num_hidden_layers"], vocab=c["vocab_size"],
            action_vocab=h["action_vocab_size"], action_dim=h["action_dim"],
            prefix_tokens=h["num_prefix_tokens"],
            frontend_dim=h["frontend_dim"], eps=c["rms_norm_eps"],
            rope_theta=c["rope_theta"],
            yarn=(y["factor"], y["original_max_position_embeddings"],
                  y["beta_fast"], y["beta_slow"], y["mscale"],
                  y["mscale_all_dim"]),
            norm_topk=c["norm_topk_prob"])

    @property
    def qk_dim(self) -> int:
        return self.q_nope + self.q_rope


def model_config(config: Dict):
    """The program's ModelConfig: the registry's deepseek-v2-lite with
    every size the file states and the share of the experts it holds."""
    from repro.configs import get_config
    from repro.configs.base import MLAConfig, MoEConfig, YarnConfig
    from repro.models.transformer import FRONTEND_DIM
    ph, s = config["policy_head"], Spec.from_config(config)
    if ph["frontend_dim"] != FRONTEND_DIM:
        raise ValueError(f"frontend_dim {ph['frontend_dim']} is not the "
                         f"program's stub frontend width {FRONTEND_DIM}")
    base = get_config(config["arch"])
    return dataclasses.replace(
        base, num_layers=s.layers, d_model=s.d, num_heads=s.heads,
        num_kv_heads=config["num_key_value_heads"], d_ff=s.d_ff,
        vocab_size=s.vocab, norm_eps=s.eps, rope_theta=s.rope_theta,
        rope_scaling=YarnConfig(*s.yarn),
        mla=MLAConfig(kv_lora_rank=s.kv_rank, qk_nope_head_dim=s.q_nope,
                      qk_rope_head_dim=s.q_rope, v_head_dim=s.v_dim),
        first_k_dense=s.first_dense,
        moe=MoEConfig(num_experts=s.experts, top_k=s.top_k, d_ff=s.moe_ff,
                      router_z_coef=0.0, load_balance_coef=0.0,
                      norm_topk_prob=s.norm_topk,
                      num_shared_experts=s.shared, held_first=s.held_first,
                      held=s.held),
        action_vocab_size=ph["action_vocab_size"],
        action_dim=ph["action_dim"],
        max_episode_steps=ph["max_episode_steps"],
        num_prefix_tokens=ph["num_prefix_tokens"],
        param_dtype=config["torch_dtype"],
        compute_dtype=config["torch_dtype"], head_dim_override=None)


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def _mla_weights(ks, n: int, s: Spec, w):
    return {"wq": weights.stacked(next(ks), n, (s.d, s.heads, s.qk_dim), w),
            "w_kva": weights.stacked(next(ks), n,
                                     (s.d, s.kv_rank + s.q_rope), w),
            "kv_norm": {"scale": jnp.ones((n, s.kv_rank), w)},
            "w_kvb": weights.stacked(next(ks), n, (s.kv_rank, s.heads,
                                                   s.q_nope + s.v_dim), w),
            "wo": weights.stacked(next(ks), n, (s.heads, s.v_dim, s.d), w)}


def _swiglu_weights(ks, n: int, d: int, ff: int, w, lead=()):
    return {"w_gate": weights.stacked(next(ks), n, lead + (d, ff), w),
            "w_up": weights.stacked(next(ks), n, lead + (d, ff), w),
            "w_down": weights.stacked(next(ks), n, lead + (ff, d), w)}


def draw_params(config: Dict, key):
    """Seeded weights with the program's tree: normal of std 0.02
    truncated at 2 std, norm scales 1, biases 0; the router and the value
    head in float32."""
    s = Spec.from_config(config)
    w, f32 = jnp.dtype(config["torch_dtype"]), jnp.float32
    ks = iter(jax.random.split(key, 32))
    nd, nm = s.first_dense, s.layers - s.first_dense
    layers = lambda n: {  # noqa: E731
        "attn_norm": {"scale": jnp.ones((n, s.d), w)},
        "attn": _mla_weights(ks, n, s, w),
        "mlp_norm": {"scale": jnp.ones((n, s.d), w)}}
    moe = _swiglu_weights(ks, nm, s.d, s.moe_ff, w, lead=(s.held,))
    moe["router"] = weights.stacked(next(ks), nm, (s.d, s.experts), f32)
    if s.shared:
        moe["shared"] = _swiglu_weights(ks, nm, s.d, s.shared * s.moe_ff, w)
    params = {
        "embed": {"table": weights.normal(next(ks), (s.vocab, s.d), w)},
        "final_norm": {"scale": jnp.ones((s.d,), w)},
        "action_head": {"w": weights.normal(next(ks), (s.d, s.action_vocab),
                                            w)},
        "prefix_proj": {"w": weights.normal(next(ks), (s.frontend_dim, s.d),
                                            w)},
        "layers": {**layers(nm), "moe": moe},
        "value_head": {
            "attn_proj": weights.normal(next(ks), (s.d, 1), f32),
            "step_emb": weights.normal(
                next(ks), (config["policy_head"]["max_episode_steps"], s.d),
                f32),
            "mlp_w1": weights.normal(next(ks), (s.d, s.d), f32),
            "mlp_b1": jnp.zeros((s.d,), f32),
            "mlp_w2": weights.normal(next(ks), (s.d, 1), f32),
            "mlp_b2": jnp.zeros((1,), f32)},
    }
    if nd:
        params["layers_dense"] = {**layers(nd), "mlp": _swiglu_weights(
            ks, nd, s.d, s.d_ff, w)}
    return params


# ---------------------------------------------------------------------------
# the reference block
# ---------------------------------------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, yarn) -> np.ndarray:
    """``DeepseekV2YarnRotaryEmbedding``'s inverse frequencies."""
    factor, orig, beta_fast, beta_slow = yarn[:4]

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (
            2 * math.log(theta))
    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    freq_extra = 1.0 / theta ** (np.arange(0, dim, 2) / dim)
    freq_inter = freq_extra / factor
    return freq_inter * (1 - mask) + freq_extra * mask


def softmax_scale(spec: Spec) -> float:
    m = yarn_mscale(spec.yarn[0], spec.yarn[5])
    return spec.qk_dim ** -0.5 * m * m


def rope_yarn(x, spec: Spec):
    """x: [N, T, H, D] at positions 0..T-1, YaRN frequencies, the cos/sin
    mscale ratio, rotate-half layout."""
    t, d = x.shape[1], x.shape[-1]
    inv = jnp.asarray(yarn_inv_freq(d, spec.rope_theta, spec.yarn),
                      jnp.float32)
    ratio = yarn_mscale(spec.yarn[0], spec.yarn[4]) / yarn_mscale(
        spec.yarn[0], spec.yarn[5])
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    cos = (jnp.cos(ang) * ratio)[None, :, None]
    sin = (jnp.sin(ang) * ratio)[None, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def mla(p, x, spec: Spec, prec):
    r, nope = spec.kv_rank, spec.q_nope
    q = mm("ntd,dhk->nthk", x, p["wq"], prec)
    q = jnp.concatenate([q[..., :nope], rope_yarn(q[..., nope:], spec)], -1)
    c = mm("ntd,dr->ntr", x, p["w_kva"], prec)
    ckv = rmsnorm(c[..., :r], p["kv_norm"]["scale"], spec.eps)
    kpe = rope_yarn(c[..., None, r:], spec)                    # [N,T,1,dr]
    kv = mm("ntr,rhk->nthk", ckv, p["w_kvb"], prec)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        kpe, kv.shape[:3] + (spec.q_rope,))], -1)
    v = kv[..., nope:]
    t = x.shape[1]
    s = mm("nqhk,nshk->nhqs", q, k, prec) * softmax_scale(spec)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, NEG_INF)
    o = mm("nhqs,nshk->nqhk", jax.nn.softmax(s, axis=-1), v, prec)
    return mm("nthk,hkd->ntd", o, p["wo"], prec)


def route(p, x, spec: Spec, prec):
    """(top-k weights [N, T, k], top-k experts [N, T, k])."""
    probs = jax.nn.softmax(mm("ntd,de->nte", x, p["router"], prec), -1)
    vals, idx = jax.lax.top_k(probs, spec.top_k)
    if spec.norm_topk:
        vals = vals / jnp.sum(vals, -1, keepdims=True)
    return vals, idx


def expert_share(p, x, spec: Spec, prec, vals, idx):
    """The held experts' part, ``sum_i s_i E_i(x)``, and the shared
    experts' ``S(x)``."""
    y = reference.swiglu(p["shared"], x, prec) if spec.shared else 0.0
    for j in range(spec.held):
        gate = jnp.sum(jnp.where(idx == spec.held_first + j, vals, 0.0), -1)
        ej = {k: p[k][j] for k in ("w_gate", "w_up", "w_down")}
        y = y + gate[..., None] * reference.swiglu(ej, x, prec)
    return y


def moe(p, x, spec: Spec, prec):
    vals, idx = route(p, x, spec, prec)
    return expert_share(p, x, spec, prec, vals, idx)


def decoder(params, table, ids, prefix, spec: Spec, prec, moe=moe):
    """ids index ``table`` ([rows, d]); prefix: [N, P, F] or None.
    Returns the final-norm hidden states [N, P + T_ids, d]. ``moe`` is
    the expert layer (a planted fault swaps it)."""
    x = jnp.take(table.astype(jnp.float32), ids, axis=0)
    if prefix is not None:
        proj = mm("npf,fd->npd", prefix, params["prefix_proj"]["w"], prec)
        x = jnp.concatenate([proj, x], axis=1)
    stacks = [(params["layers_dense"], False)] if spec.first_dense else []
    stacks.append((params["layers"], True))
    for lay, is_moe in stacks:
        for i in range(jax.tree.leaves(lay)[0].shape[0]):
            li = jax.tree.map(lambda a: a[i], lay)
            x = x + mla(li["attn"], rmsnorm(x, li["attn_norm"]["scale"],
                                            spec.eps), spec, prec)
            h = rmsnorm(x, li["mlp_norm"]["scale"], spec.eps)
            x = x + (moe(li["moe"], h, spec, prec) if is_moe
                     else reference.swiglu(li["mlp"], h, prec))
    return rmsnorm(x, params["final_norm"]["scale"], spec.eps)


serve_readings = functools.partial(reference.serve_readings, decoder=decoder)


# ---------------------------------------------------------------------------
# training: the reference's step in blocks of segments
# ---------------------------------------------------------------------------

def _block_grads(acc, stored, mb, adv_state, dens, *, rl, spec, prec,
                 decoder, n_micro):
    """Add one block's gradient (over the micro-batch's denominators
    ``dens`` = (policy-token, value-row) counts) into ``acc``."""
    def loss(p32):
        body = {k: v for k, v in p32.items() if k != "rows"}
        _, (m, stats) = reference.micro_loss(body, p32["rows"], mb,
                                             adv_state, rl, spec, prec,
                                             decoder)
        # the block's terms over its own counts, put over the micro-batch's
        mask = mb["mask"]
        r_pol = jnp.maximum(jnp.sum(mask) * spec.action_dim, 1.0) / dens[0]
        r_val = jnp.maximum(jnp.sum(mask), 1.0) / dens[1]
        m = {k: v * (r_val if k == "value_loss" else r_pol)
             for k, v in m.items()}
        m["loss"] = m["pg_loss"] + rl["value_coef"] * m["value_loss"] \
            + rl["kl_coef"] * m["kl"] - rl["entropy_coef"] * m["entropy"]
        return m["loss"], (m, stats)
    with jax.default_matmul_precision("highest"):
        p32 = jax.tree.map(lambda x: x.astype(jnp.float32), stored)
        g, aux = jax.grad(loss, has_aux=True)(p32)
    return jax.tree.map(lambda a, x: a + x / n_micro, acc, g), aux


def _update(stored, mu, nu, acc, step, *, rl):
    """``reference.adamw_update``, returning the clipped gradient's leaf
    norms in place of the gradient."""
    stored, mu, nu, clipped, gnorm = reference.adamw_update(
        stored, mu, nu, acc, step, rl=rl)
    return stored, mu, nu, jax.tree.map(
        lambda g: jnp.sqrt(jnp.sum(g * g)), clipped), gnorm


def _blocks(batch, remap, n_micro: int, half_batch: bool, seg: int):
    """Each micro-batch's blocks of segments and its denominators."""
    mbsz = batch["obs_tokens"].shape[0] // n_micro
    keep = mbsz // 2 if half_batch else mbsz
    seg = min(seg, keep)
    out = []
    for i in range(n_micro):
        mask = batch["mask"][i * mbsz:i * mbsz + keep]
        a = batch["actions"].shape[-1]
        dens = (np.float32(max(mask.sum() * a, 1.0)),
                np.float32(max(mask.sum(), 1.0)))
        blocks = []
        for j in range(i * mbsz, i * mbsz + keep, seg):
            mb = {k: np.asarray(v[j:j + seg]) for k, v in batch.items()
                  if k != "policy_version"}
            mb["ids"] = remap[mb["obs_tokens"]]
            mb["action_ids"] = remap[mb["actions"]]
            blocks.append(mb)
        out.append((blocks, dens))
    return out


def train_reference(params, batches, rl: Dict, spec: Spec, *,
                    prec: str = "f32", half_batch: bool = False,
                    decoder=decoder,
                    block_segments: int = BLOCK_SEGMENTS) -> Dict:
    """``reference.train_reference``'s steps and readings, each
    micro-batch's gradient summed over blocks of ``block_segments``
    segments. The device holds the parameters, AdamW's float32 moments,
    the float32 gradient accumulator and one block's pass; the starting
    parameters wait on the host for the change of the last step."""
    ids_used = reference.used_rows(batches, spec.vocab)
    remap = np.zeros(spec.vocab, np.int32)
    remap[ids_used] = np.arange(len(ids_used), dtype=np.int32)
    stored = {k: v for k, v in params.items() if k != "embed"}
    stored["rows"] = jnp.take(params["embed"]["table"],
                              jnp.asarray(ids_used), axis=0)
    del params
    start = jax.device_get(stored)
    zeros = lambda: jax.tree.map(  # noqa: E731
        lambda x: jnp.zeros(x.shape, jnp.float32), stored)
    mu, nu = zeros(), zeros()
    n_micro = rl["grad_accum"]
    grads = jax.jit(functools.partial(
        _block_grads, rl=rl, spec=spec, prec=prec, decoder=decoder,
        n_micro=n_micro), donate_argnums=(0,))
    update = jax.jit(functools.partial(_update, rl=rl),
                     donate_argnums=(0, 1, 2))
    adv_state = (jnp.zeros(()), jnp.zeros(()), jnp.zeros(()))
    steps: List[Dict] = []
    first_grad = None
    for step, batch in enumerate(batches):
        acc, stats_acc = zeros(), jnp.zeros((3,))
        for blocks, dens in _blocks(batch, remap, n_micro, half_batch,
                                    block_segments):
            metrics = None
            for mb in blocks:
                acc, (m, stats) = grads(acc, stored, mb, adv_state, dens)
                metrics = m if metrics is None else jax.tree.map(
                    jnp.add, metrics, m)
                stats_acc = stats_acc + stats
        stored, mu, nu, norms, gnorm = update(stored, mu, nu, acc,
                                              jnp.float32(step))
        if first_grad is None:
            first_grad = reference._table_named(
                {reference._path(p): float(x) for p, x in
                 jax.tree_util.tree_flatten_with_path(norms)[0]})
        adv_state = reference._welford(adv_state, stats_acc)
        steps.append({k: float(v) for k, v in metrics.items()}
                     | {"grad_norm": float(gnorm)})
    del mu, nu
    change = jax.tree.map(
        lambda s, x: jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32) - s.astype(jnp.float32)))),
        jax.device_put(start), stored)
    return {"steps": steps, "first_grad": first_grad,
            "change": reference._table_named(reference.leaf_norms(change))}


# ---------------------------------------------------------------------------
# work
# ---------------------------------------------------------------------------

def held_share(c: Dict) -> float:
    """Expected share of the (token, expert) assignments this chip's
    experts take: k x held / experts per token."""
    return c["num_experts_per_tok"] * c["n_routed_experts"] \
        / c["published"]["n_routed_experts"]


def _mla_params(c: Dict) -> int:
    d, h, r = c["hidden_size"], c["num_attention_heads"], c["kv_lora_rank"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return (d * h * qk + d * (r + c["qk_rope_head_dim"])
            + r * h * (c["qk_nope_head_dim"] + c["v_head_dim"])
            + h * c["v_head_dim"] * d)


def token_matmul_params(c: Dict) -> float:
    """Matmul weights the layers apply per token, the routed experts at
    the expected held share."""
    d, ff = c["hidden_size"], c["moe_intermediate_size"]
    dense = c["first_k_dense_replace"]
    moe_layers = c["num_hidden_layers"] - dense
    per_moe = (_mla_params(c) + d * c["published"]["n_routed_experts"]
               + 3 * d * ff * (c["n_shared_experts"] + held_share(c)))
    return (dense * (_mla_params(c) + 3 * d * c["intermediate_size"])
            + moe_layers * per_moe)


def train_step_flops(c: Dict, segments: int, horizon: int,
                     instruction_tokens: int) -> float:
    """Model FLOPs of one optimizer step: forward and backward (3x the
    forward) of every matmul at the tokens it is applied to, the routed
    experts at the expected held share, attention at true T (QK^T over
    the q/k head dim, PV over the value head dim)."""
    s = seq_shape(c, instruction_tokens)
    d, t, a = c["hidden_size"], s["tokens"], s["actions"]
    h = c["policy_head"]
    rows = segments * (horizon + 1)
    per_row = 6.0 * token_matmul_params(c) * t
    per_row += 6.0 * flops.causal_pairs(t) * c["num_attention_heads"] * (
        c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"]) \
        * c["num_hidden_layers"]
    per_row += 4.0 * h["frontend_dim"] * d * s["prefix"]
    per_row += 6.0 * (d * d + a * d + d)
    head = 6.0 * d * h["action_vocab_size"] * a * segments * horizon
    return per_row * rows + head


def serve_request_flops(c: Dict, instruction_tokens: int) -> float:
    """Model FLOPs of one answered request: prefill, one decode pass per
    action token (keys and values expanded from the latent cache), the
    action head on every position, the value head once."""
    s = seq_shape(c, instruction_tokens)
    d, a = c["hidden_size"], s["actions"]
    h = c["policy_head"]
    prompt = s["prefix"] + instruction_tokens
    fwd = 2.0 * token_matmul_params(c) * (prompt + a)
    qkv = c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"]
    fwd += 2.0 * c["num_attention_heads"] * qkv * c["num_hidden_layers"] \
        * (flops.causal_pairs(prompt) + sum(prompt + k + 1
                                            for k in range(a)))
    fwd += 2.0 * h["frontend_dim"] * d * s["prefix"]
    fwd += 2.0 * d * h["action_vocab_size"] * (prompt + a)
    fwd += 2.0 * (d * d + a * d + d)
    return fwd


def kernel_work(config: Dict, mix: Dict) -> Dict[str, float]:
    """The grouped-matmul kernels' work in one optimizer step (forward,
    ``dlhs`` and ``tgmm`` of the gate, up and down projections of every
    expert layer) at the expected held rows, with ``gmm_rows`` those rows
    and ``gmm_assignments`` all the step's (token, expert) assignments, so
    that a reader can put the work at the rows the window really handed
    the kernels. Bytes: each of the nine calls of a layer reads its rows'
    inputs, writes its rows' outputs (``d + ff`` bf16 values a row) and
    reads or writes the held experts' weights of one projection once."""
    if mix["entry"] != "train":
        return {}
    s = seq_shape(config, mix["instruction_tokens"])
    tokens = mix["segments"] * (mix["horizon"] + 1) * s["tokens"]
    k = config["num_experts_per_tok"]
    rows = tokens * held_share(config)
    d, ff = config["hidden_size"], config["moe_intermediate_size"]
    layers = config["num_hidden_layers"] - config["first_k_dense_replace"]
    weight = 9.0 * config["n_routed_experts"] * d * ff * flops.BF16 * layers
    return {"gmm_flops": 18.0 * rows * d * ff * layers,
            "gmm_bytes": 9.0 * rows * (d + ff) * flops.BF16 * layers
            + weight,
            "gmm_weight_bytes": weight,
            "gmm_rows": rows * layers,
            "gmm_assignments": float(tokens * k * layers)}


def reference_programs(config: Dict, mix: Dict) -> List[Tuple]:
    """The reference's device programs at the mix's shapes: one block's
    float32 gradients added into the accumulator, and the AdamW step."""
    import traffic_gen

    spec = Spec.from_config(config)
    shapes = jax.eval_shape(functools.partial(draw_params, config),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    sds = lambda tree: jax.tree.map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype), tree)
    batch = traffic_gen.train_batch(np.random.default_rng(0), mix, config)
    rows = reference.used_rows([batch] * mix["checked_steps"], spec.vocab)
    stored = {k: v for k, v in shapes.items() if k != "embed"}
    stored["rows"] = jax.ShapeDtypeStruct((len(rows), spec.d),
                                          jnp.dtype(config["torch_dtype"]))
    remap = np.zeros(spec.vocab, np.int32)
    blocks, dens = _blocks(batch, remap, mix["rl"]["grad_accum"], False,
                           BLOCK_SEGMENTS)[0]
    acc = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, jnp.float32),
                       stored)
    fn = jax.jit(functools.partial(
        _block_grads, rl=mix["rl"], spec=spec, prec="f32", decoder=decoder,
        n_micro=mix["rl"]["grad_accum"]), donate_argnums=(0,))
    adv = (jax.ShapeDtypeStruct((), jnp.float32),) * 3
    update = jax.jit(functools.partial(_update, rl=mix["rl"]),
                     donate_argnums=(0, 1, 2))
    return [("reference block grads", fn,
             (acc, stored, sds(blocks[0]), adv, sds(dens))),
            ("reference AdamW update", update,
             (stored, acc, acc, acc, adv[0]))]
