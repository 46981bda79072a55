"""Plain float32 reference of the VLA policy, its GIPO loss and AdamW step.

Written from the published layer equations, in ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, and importing nothing of the
program under test: RMSNorm, rotary position embedding (rotate-half form),
causal multi-head / grouped-query attention, SwiGLU MLP, the 256-bin action
head, the attention-pooling value head, GAE, the lagged advantage
normalisation, the GIPO surrogate with the k3 KL term, and AdamW with
global-norm clipping. It reads the parameter pytree the program keeps
(same leaf names and shapes) and nothing the program computed.

The parts that do not depend on the layer's shape (``rmsnorm``, ``rope``,
``value_head``, ``gipo_loss``, ``adamw_update``, the precision control
``mm``) are what another family's reference reuses; ``train_reference``,
``serve_readings`` and ``serve_block`` take the decoder as ``decoder=``.

Parameters are stored in the dtype the configuration states (bfloat16
weights, float32 value head); every operation runs in float32 on the
upcast values, and each AdamW result is stored back in the leaf's dtype,
as ``torch_dtype`` says the weights live.

``prec="fp8"`` is the precision control: every matmul operand, and in the
backward pass every cotangent that enters a matmul, is quantised to
float8 e4m3 with a per-tensor scale, the step below the configuration's
bfloat16. Everything else stays as above.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
NEG_INF = -1e30

# AdamW constants of the published recipe (Loshchilov & Hutter; no decay)
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


@dataclasses.dataclass(frozen=True)
class Spec:
    """Sizes of one configuration file (``configs/<name>.json``)."""

    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    layers: int
    vocab: int
    action_vocab: int
    action_dim: int
    prefix_tokens: int
    frontend_dim: int
    eps: float
    rope_theta: float

    @classmethod
    def from_config(cls, c: Dict) -> "Spec":
        h = c["policy_head"]
        return cls(d=c["hidden_size"], heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                   d_ff=c["intermediate_size"],
                   layers=c["num_hidden_layers"], vocab=c["vocab_size"],
                   action_vocab=h["action_vocab_size"],
                   action_dim=h["action_dim"],
                   prefix_tokens=h["num_prefix_tokens"],
                   frontend_dim=h["frontend_dim"],
                   eps=c["rms_norm_eps"], rope_theta=c["rope_theta"])


# ---------------------------------------------------------------------------
# precision: float32, or the float8 control
# ---------------------------------------------------------------------------

def _fp8(x):
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(F8).astype(jnp.float32) * scale


@jax.custom_vjp
def _fp8_operand(x):
    return _fp8(x)


_fp8_operand.defvjp(lambda x: (_fp8(x), None), lambda _, g: (_fp8(g),))


def mm(subscripts: str, a, b, prec: str):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if prec == "fp8":
        a, b = _fp8_operand(a), _fp8_operand(b)
    return jnp.einsum(subscripts, a, b,
                      precision=jax.lax.Precision.HIGHEST)


# ---------------------------------------------------------------------------
# the decoder
# ---------------------------------------------------------------------------

def rmsnorm(x, scale, eps):
    x = x.astype(jnp.float32)
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def rope(x, theta):
    """x: [N, T, H, D] at positions 0..T-1; rotate-half (GPT-NeoX) form."""
    t, d = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(d // 2, dtype=jnp.float32) / (d // 2))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]   # [T, D/2]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(p, x, spec: Spec, prec):
    q = rope(mm("ntd,dhk->nthk", x, p["wq"], prec), spec.rope_theta)
    k = rope(mm("ntd,dhk->nthk", x, p["wk"], prec), spec.rope_theta)
    v = mm("ntd,dhk->nthk", x, p["wv"], prec)
    group = spec.heads // spec.kv_heads
    k = jnp.repeat(k, group, axis=2)          # head h reads kv head h // group
    v = jnp.repeat(v, group, axis=2)
    t = x.shape[1]
    s = mm("nqhk,nshk->nhqs", q, k, prec) / np.sqrt(spec.head_dim)
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal, s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o = mm("nhqs,nshk->nqhk", w, v, prec)
    return mm("nthk,hkd->ntd", o, p["wo"], prec)


def swiglu(p, x, prec):
    g = mm("ntd,df->ntf", x, p["w_gate"], prec)
    u = mm("ntd,df->ntf", x, p["w_up"], prec)
    return mm("ntf,fd->ntd", jax.nn.silu(g) * u, p["w_down"], prec)


def decoder(params, table, ids, prefix, spec: Spec, prec):
    """ids index ``table`` ([rows, d]); prefix: [N, P, F] or None.
    Returns the final-norm hidden states [N, P + T_ids, d]."""
    x = jnp.take(table.astype(jnp.float32), ids, axis=0)
    if prefix is not None:
        proj = mm("npf,fd->npd", prefix, params["prefix_proj"]["w"], prec)
        x = jnp.concatenate([proj, x], axis=1)
    lay = params["layers"]
    for i in range(spec.layers):
        li = jax.tree.map(lambda a: a[i], lay)
        x = x + attention(li["attn"], rmsnorm(x, li["attn_norm"]["scale"],
                                              spec.eps), spec, prec)
        x = x + swiglu(li["mlp"], rmsnorm(x, li["mlp_norm"]["scale"],
                                          spec.eps), prec)
    return rmsnorm(x, params["final_norm"]["scale"], spec.eps)


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(np.sqrt(2.0 / np.pi)
                                     * (x + 0.044715 * x ** 3)))


def value_head(p, act_hidden, steps, prec):
    """Attention pooling over the action-token hiddens (detached), plus a
    step embedding, through a GELU MLP -> V [N]."""
    h = jax.lax.stop_gradient(act_hidden)
    e = mm("nad,do->nao", h, p["attn_proj"], prec)
    alpha = jax.nn.softmax(e, axis=1)
    z = jnp.sum(alpha * h, axis=1)
    emb = p["step_emb"]
    z = z + jnp.take(emb, jnp.clip(steps, 0, emb.shape[0] - 1), axis=0)
    z = gelu_tanh(mm("nd,de->ne", z, p["mlp_w1"], prec) + p["mlp_b1"])
    return (mm("nd,do->no", z, p["mlp_w2"], prec) + p["mlp_b2"])[:, 0]


def score(params, table, ids, actions, prefix, spec: Spec, prec,
          decoder=decoder):
    """Teacher-forced pass over [prefix, instruction ids, action ids]; the
    action tokens go through the shared embedding table as ids 0..Va-1.
    ``decoder(params, table, ids, prefix, spec, prec)`` gives the
    final-norm hidden states.

    Returns (log-softmax over the Va bins at each action token [N, A, Va],
    the hiddens at the action tokens [N, A, d]). Action token k is read at
    the position before it; the value pools the hiddens at the action
    tokens' own positions."""
    a = spec.action_dim
    hid = decoder(params, table, jnp.concatenate([ids, actions], 1), prefix,
                  spec, prec)
    t = hid.shape[1]
    logits = mm("nad,dv->nav", hid[:, t - a - 1:t - 1],
                params["action_head"]["w"], prec)
    return jax.nn.log_softmax(logits, axis=-1), hid[:, t - a:]


def action_logp(logp_all, action_tokens):
    return jnp.take_along_axis(logp_all, action_tokens[..., None], -1)[..., 0]


# ---------------------------------------------------------------------------
# serving: log-probs and values of served requests
# ---------------------------------------------------------------------------

SERVE_ROWS = 64


def serve_block(params, ids, toks, steps, prefix, *, spec: Spec, prec: str,
                decoder=decoder):
    """Teacher-forced log-probs [N, A] of the action tokens ``toks`` and
    the values [N] of one block of requests."""
    with jax.default_matmul_precision("highest"):
        logp_all, act_h = score(params, params["embed"]["table"], ids,
                                toks, prefix, spec, prec, decoder)
        v = value_head(params["value_head"], act_h, steps, prec)
        return action_logp(logp_all, toks), v


def serve_readings(params, obs, actions, steps, prefix, spec: Spec,
                   prec: str = "f32", rows: int = SERVE_ROWS, *,
                   decoder=decoder):
    """Teacher-forced log-probs [N, A] of the served action tokens and the
    values [N], in blocks of ``rows`` requests (the last block padded)."""
    block = jax.jit(functools.partial(serve_block, spec=spec, prec=prec,
                                      decoder=decoder))
    n = len(obs)
    pad = -n % rows
    grow = lambda x: np.concatenate(  # noqa: E731
        [x, np.repeat(x[:1], pad, 0)]) if pad else x
    obs, actions, steps, prefix = map(grow, (obs, actions, steps, prefix))
    lps, vs = [], []
    for i in range(0, len(obs), rows):
        sl = slice(i, i + rows)
        lp, v = block(params, obs[sl], actions[sl], steps[sl], prefix[sl])
        lps.append(np.asarray(lp))
        vs.append(np.asarray(v))
    return np.concatenate(lps)[:n], np.concatenate(vs)[:n]


# ---------------------------------------------------------------------------
# training: GIPO + JIT-GAE + lagged normalisation, AdamW
# ---------------------------------------------------------------------------

def gae(values, rewards, dones, gamma, lam):
    """values [B, T+1] (bootstrap last), rewards/dones [B, T]."""
    t = rewards.shape[1]
    nonterm = 1.0 - dones
    adv, last = [], jnp.zeros_like(rewards[:, 0])
    for j in reversed(range(t)):
        delta = rewards[:, j] + gamma * nonterm[:, j] * values[:, j + 1] \
            - values[:, j]
        last = delta + gamma * lam * nonterm[:, j] * last
        adv.append(last)
    adv = jnp.stack(adv[::-1], axis=1)
    return adv, adv + values[:, :t]


def _adv_normalise(adv, state):
    count, mean, m2 = state
    var = jnp.where(count > 1, m2 / jnp.maximum(count, 1.0), 1.0)
    std = jnp.sqrt(jnp.clip(var, 1e-12, None))
    has = count > 0
    return (adv - jnp.where(has, mean, 0.0)) / (jnp.where(has, std, 1.0)
                                                 + 1e-8)


def _welford(state, stats):
    count, mean, m2 = state
    s, sq, n = stats[0], stats[1], jnp.maximum(stats[2], 1e-9)
    bm = s / n
    total = count + n
    delta = bm - mean
    return (total, mean + delta * n / total,
            m2 + (sq - n * bm * bm) + delta * delta * count * n / total)


def micro_loss(params, table, mb, adv_state, rl: Dict, spec: Spec, prec,
               decoder=decoder):
    """Loss of one micro-batch; ``mb`` holds numpy-shaped arrays with ids
    already mapped into ``table``'s rows."""
    b, tp1 = mb["obs_tokens"].shape[:2]
    flat = lambda x: x.reshape((b * tp1,) + x.shape[2:])
    logp_all, act_h = score(params, table, flat(mb["ids"]),
                            flat(mb["action_ids"]), flat(mb["prefix_embeds"]),
                            spec, prec, decoder)
    values = value_head(params["value_head"], act_h, flat(mb["steps"]),
                        prec).reshape(b, tp1)
    logp_all = logp_all.reshape(b, tp1, spec.action_dim, -1)
    return gipo_loss(logp_all, values, mb, adv_state, rl)


def gipo_loss(logp_all, values, mb, adv_state, rl: Dict):
    """The GIPO objective of one micro-batch from its teacher-forced
    log-softmax over the action bins [B, T+1, A, Va] and values [B, T+1]
    (the last row bootstraps): JIT-GAE, the lagged advantage
    normalisation, the surrogate, the k3 KL, entropy and the value loss.
    Returns (loss, (metrics, advantage statistics))."""
    t = values.shape[1] - 1
    action_dim = logp_all.shape[2]
    adv, returns = gae(jax.lax.stop_gradient(values), mb["rewards"],
                       mb["dones"], rl["discount"], rl["gae_lambda"])
    mask = mb["mask"]
    stats = jnp.stack([jnp.sum(adv * mask), jnp.sum(adv * adv * mask),
                       jnp.sum(mask)])
    adv_n = jax.lax.stop_gradient(_adv_normalise(adv, adv_state))

    logp_new = action_logp(logp_all[:, :t], mb["actions"][:, :t])
    log_ratio = logp_new - mb["behavior_logp"][:, :t]
    ratio = jnp.exp(log_ratio)
    omega = jnp.exp(-0.5 * jnp.square(jax.lax.stop_gradient(log_ratio)
                                      / rl["gipo_sigma"]))
    m = mask[..., None]
    denom = jnp.maximum(jnp.sum(m) * action_dim, 1.0)
    pg = jnp.sum(-(omega * ratio * adv_n[..., None]) * m) / denom
    pg_scale = jnp.sum(omega * ratio * jnp.abs(adv_n[..., None]) * m) / denom
    kl = jnp.sum((jnp.expm1(-log_ratio) + log_ratio) * m) / denom
    ent_tok = -jnp.sum(jnp.exp(logp_all[:, :t]) * logp_all[:, :t], -1)
    ent = jnp.sum(ent_tok * m) / denom
    v_loss = jnp.sum(0.5 * jnp.square(values[:, :t]
                                      - jax.lax.stop_gradient(returns))
                     * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    total = pg + rl["value_coef"] * v_loss + rl["kl_coef"] * kl \
        - rl["entropy_coef"] * ent
    return total, ({"loss": total, "pg_loss": pg, "value_loss": v_loss,
                    "kl": kl, "entropy": ent, "pg_scale": pg_scale}, stats)


def micro_grads(p32, mb, adv_state, rl: Dict, spec: Spec, prec: str,
                decoder=decoder):
    """Gradients of one micro-batch's loss with respect to the float32
    parameters, whose embedding is the ``rows`` the batches read."""
    def loss(p32):
        body = {k: v for k, v in p32.items() if k != "rows"}
        return micro_loss(body, p32["rows"], mb, adv_state, rl, spec, prec,
                          decoder)
    with jax.default_matmul_precision("highest"):
        return jax.grad(loss, has_aux=True)(p32)


def _lr(rl: Dict, path_keys, step):
    base = rl["lr_value"] if "value_head" in path_keys else rl["lr_policy"]
    return base * jnp.minimum((step + 1.0) / max(rl["warmup_steps"], 1), 1.0)


def adamw_update(stored, mu, nu, grads, step, *, rl: Dict):
    """One AdamW step with global-norm clipping and linear warm-up (the
    value head at its own rate) on float32 gradients; each parameter is
    stored back in its leaf's dtype. Returns (params, mu, nu, clipped
    gradients, pre-clip global norm)."""
    leaves = jax.tree.leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    clip = jnp.minimum(1.0, rl["max_grad_norm"] / jnp.maximum(gnorm, 1e-9))
    grads = jax.tree.map(lambda g: g * clip, grads)
    s1 = step + 1.0
    mu = jax.tree.map(lambda m, g: ADAM_B1 * m + (1 - ADAM_B1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: ADAM_B2 * v + (1 - ADAM_B2) * g * g,
                      nu, grads)

    def upd(path, p, m, v):
        keys = [getattr(k, "key", "") for k in path]
        delta = (m / (1 - ADAM_B1 ** s1)) / (
            jnp.sqrt(v / (1 - ADAM_B2 ** s1)) + ADAM_EPS)
        return (p.astype(jnp.float32) - _lr(rl, keys, step) * delta
                ).astype(p.dtype)

    new = jax.tree_util.tree_map_with_path(upd, stored, mu, nu)
    return new, mu, nu, grads, gnorm


def train_reference(params, batches: Sequence[Dict], rl: Dict, spec: Spec,
                    *, prec: str = "f32", half_batch: bool = False,
                    decoder=decoder) -> Dict:
    """Run ``len(batches)`` optimizer steps from ``params``.

    Returns per-step metrics, the per-leaf norms of the first step's
    clipped gradient (what AdamW receives), and the per-leaf norms of the
    parameters' change after the last step. Only the embedding rows that
    the batches use are carried (the other rows get a zero gradient and
    do not move under AdamW), so the whole step fits beside nothing else.
    ``half_batch`` is the planted fault: each micro-batch's loss is taken
    over its first half only.
    """
    ids_used = used_rows(batches, spec.vocab)
    remap = np.zeros(spec.vocab, np.int32)
    remap[ids_used] = np.arange(len(ids_used), dtype=np.int32)
    stored = {k: v for k, v in params.items() if k != "embed"}
    stored["rows"] = jnp.take(params["embed"]["table"],
                              jnp.asarray(ids_used), axis=0)
    n_micro = rl["grad_accum"]

    @jax.jit
    def grads_of(p32, mb, adv_state):
        return micro_grads(p32, mb, adv_state, rl, spec, prec, decoder)

    update = jax.jit(functools.partial(adamw_update, rl=rl),
                     donate_argnums=(0, 1, 2))

    def micro_batches(batch):
        mbsz = batch["obs_tokens"].shape[0] // n_micro
        keep = mbsz // 2 if half_batch else mbsz
        out = []
        for i in range(n_micro):
            sl = slice(i * mbsz, i * mbsz + keep)
            mb = {k: np.asarray(v[sl]) for k, v in batch.items()
                  if k != "policy_version"}
            mb["ids"] = remap[mb["obs_tokens"]]
            mb["action_ids"] = remap[mb["actions"]]
            out.append(jax.tree.map(jnp.asarray, mb))
        return out

    zeros = lambda: jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32),
                                 stored)
    mu, nu = zeros(), zeros()
    adv_state = (jnp.zeros(()), jnp.zeros(()), jnp.zeros(()))
    start = jax.tree.map(jnp.copy, stored)
    steps: List[Dict] = []
    first_grad_norms = None
    for step, batch in enumerate(batches):
        acc, stats_acc, metrics = zeros(), jnp.zeros((3,)), None
        p32 = jax.tree.map(lambda x: x.astype(jnp.float32), stored)
        for mb in micro_batches(batch):
            g, (metrics, stats) = grads_of(p32, mb, adv_state)
            acc = jax.tree.map(lambda a, x: a + x / n_micro, acc, g)
            stats_acc = stats_acc + stats
        del p32
        stored, mu, nu, clipped, gnorm = update(stored, mu, nu, acc,
                                                jnp.float32(step))
        adv_state = _welford(adv_state, stats_acc)
        if first_grad_norms is None:
            first_grad_norms = _table_named(leaf_norms(clipped))
        steps.append({k: float(v) for k, v in metrics.items()}
                     | {"grad_norm": float(gnorm)})
    change = jax.tree.map(
        lambda s, x: jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32) - s.astype(jnp.float32)))), start, stored)
    return {"steps": steps, "first_grad": first_grad_norms,
            "change": _table_named(leaf_norms(change))}


def used_rows(batches: Sequence[Dict], vocab: int) -> np.ndarray:
    """The embedding rows the batches read, padded with unread rows to a
    size fixed by the batch shapes, so every seed compiles one program."""
    ids = [np.asarray(b[k]).ravel() for b in batches
           for k in ("obs_tokens", "actions")]
    size = min(vocab, sum(len(i) for i in ids))
    used = np.unique(np.concatenate(ids))
    spare = np.setdiff1d(np.arange(vocab), used)[:size - len(used)]
    return np.concatenate([used, spare]).astype(np.int64)


def leaf_norms(tree) -> Dict[str, float]:
    """{leaf path: float32 L2 norm}."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {_path(p): float(jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32))))) for p, x in flat}


def _path(path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in path)


def _table_named(norms: Dict[str, float]) -> Dict[str, float]:
    return {("embed/table" if k == "rows" else k): v
            for k, v in norms.items()}
