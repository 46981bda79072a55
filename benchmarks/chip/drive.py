"""One runner per traffic ``entry`` (``train``, ``serve``).

A runner builds the system under test from a configuration file and a
traffic mix, warms every shape the window uses, measures for the window,
reads the device's peak memory, frees the program's state and then runs
the plain reference on what the timed path produced. Every piece that
depends on the model's layers (its ``ModelConfig``, weights, reference and
work counts) comes from the configuration's family module
(``families/``). It returns an :class:`Outcome`; ``run.py`` turns that
into the result line.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import glob
import os
import tempfile
import threading
import time
from types import ModuleType
from typing import Dict, List, Optional
from unittest import mock

import numpy as np

import families
import trace_reduce
import traffic_gen
from reference import ADAM_B1


@dataclasses.dataclass
class Ctx:
    config: Dict
    mix: Dict
    seed: int
    seconds: float
    trace: bool
    t_start: float                      # time.monotonic() at process start
    controls: bool = False              # also read the precision control
    family: Optional[ModuleType] = None  # the configuration's, when None

    def __post_init__(self):
        if self.family is None:
            self.family = families.load(self.config)


@dataclasses.dataclass
class Outcome:
    e2e: Dict[str, float]
    attempted: int
    failed: int
    readings: Dict[str, float]          # numbers compared with their limits
    window_s: float
    counters: Dict[str, float]          # counts over the window
    work: Dict[str, float]              # flops / bytes of the window's work
    memory_peak_bytes: int
    summary: Optional[trace_reduce.Summary] = None
    # the program's own readings over the window: serve, the change of each
    # counter of the tier's registry and ``<histogram>.count`` / ``.sum``;
    # train, the mean over the window's steps of each scalar that
    # ``train_on_batch`` returns
    program: Dict[str, float] = dataclasses.field(default_factory=dict)
    control: Dict[str, Dict[str, float]] = dataclasses.field(
        default_factory=dict)
    notes: List[str] = dataclasses.field(default_factory=list)


# ---------------------------------------------------------------------------
# the system under test, as the configuration file states it
# ---------------------------------------------------------------------------

def make_params(family: ModuleType, config: Dict, seed32: int):
    """The family's weights for ``config`` from a 32-bit seed, made on the
    default device in one jitted call."""
    import jax
    fn = jax.jit(functools.partial(family.draw_params, config))
    return fn(jax.random.PRNGKey(seed32))


def window_work(n: float, model_flops: float,
                kernels: Dict[str, float]) -> Dict[str, float]:
    """The work of ``n`` optimizer steps or answered requests, each of
    ``model_flops`` and of the family's per-kernel ``kernels``."""
    return {"model_flops": n * model_flops,
            **{k: n * v for k, v in kernels.items()}}


def check_params(params, cfg) -> None:
    """The benchmark's weights have the program's tree, shapes and dtypes."""
    import jax
    from repro.models.policy import init_policy_params
    want = jax.eval_shape(functools.partial(init_policy_params, cfg),
                          jax.ShapeDtypeStruct((2,), np.uint32))
    got = jax.tree.map(lambda x: (x.shape, x.dtype), params)
    want = jax.tree.map(lambda x: (x.shape, x.dtype), want)
    if got != want:
        raise ValueError(f"weights tree differs from the program's:\n{got}"
                         f"\n!=\n{want}")


def memory_peak() -> int:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


class _Tracer:
    """Profiler trace of the window (``--trace 1``) into a temporary
    directory; ``summary`` reduces it."""

    def __init__(self, on: bool):
        self.on = on
        self.dir = tempfile.mkdtemp(prefix="bench_trace_") if on else None

    def __enter__(self):
        if self.on:
            import jax
            jax.profiler.start_trace(self.dir)
        return self

    def __exit__(self, *exc):
        if self.on:
            import jax
            jax.profiler.stop_trace()

    def summary(self, texts: List[str]) -> trace_reduce.Summary:
        """The window's reduction, with kernels and stage scopes found in
        the compiled texts of the programs it ran."""
        import shutil
        sites: Dict[str, str] = {}
        scopes: Dict[str, str] = {}
        for text in texts:
            sites.update(trace_reduce.kernel_sites(text))
            scopes.update(trace_reduce.scope_sites(text))
        try:
            path = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                             recursive=True)[0]
            return trace_reduce.summarize(trace_reduce.load(path), sites,
                                          scopes)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def _span(name: str):
    import jax
    return jax.profiler.TraceAnnotation("bench." + name)


# ---------------------------------------------------------------------------
# numbers compared with the reference
# ---------------------------------------------------------------------------

def leaf_gaps(prog: Dict[str, float], ref: Dict[str, float],
              keep: Dict[str, bool]) -> List[float]:
    """|‖prog‖ - ‖ref‖| / max(‖ref‖, median ‖ref‖) for each kept leaf."""
    med = float(np.median([ref[k] for k in ref if keep[k]]))
    return [abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
            for k in ref if keep[k]]


def train_readings(prog_steps: List[Dict], prog_grad: Dict[str, float],
                   prog_change: Dict[str, float], ref: Dict,
                   rl: Dict) -> Dict[str, float]:
    grads = ref["first_grad"]
    med = float(np.median(list(grads.values())))
    keep = {k: v >= 1e-3 * med for k, v in grads.items()}
    # over the size of the loss's terms: the mean |surrogate| stands for
    # the policy-gradient term, whose sum crosses zero
    loss = [abs(p["loss"] - r["loss"]) / max(
        r["pg_scale"] + rl["value_coef"] * abs(r["value_loss"])
        + rl["kl_coef"] * abs(r["kl"]), 1e-30)
        for p, r in zip(prog_steps, ref["steps"])]
    # the policy-gradient term alone, over the reference's mean |surrogate|
    pg = [abs(p["pg_loss"] - r["pg_loss"]) / max(r["pg_scale"], 1e-30)
          for p, r in zip(prog_steps, ref["steps"])]
    # the action head's mean entropy (nats): a rounding error of the logits
    # lowers it by about half its variance, so it does not cancel over the
    # tokens as the signed policy-gradient sum does
    ent = [abs(p["entropy"] - r["entropy"])
           for p, r in zip(prog_steps, ref["steps"])]
    grad = leaf_gaps(prog_grad, grads, keep)
    change = leaf_gaps(prog_change, ref["change"], keep)
    # the worst step and leaf, and steadier readings beside them: the first
    # step alone, the median leaf
    return {"loss_gap": max(loss), "pg_gap": max(pg),
            "entropy_gap": max(ent),
            "grad_gap": max(grad), "update_gap": max(change),
            "loss_gap_first": loss[0], "pg_gap_first": pg[0],
            "grad_gap_median": float(np.median(grad)),
            "update_gap_median": float(np.median(change))}


def serve_readings(served: Dict[str, np.ndarray], ref_logp: np.ndarray,
                   ref_value: np.ndarray) -> Dict[str, float]:
    rms = float(np.sqrt(np.mean(np.square(ref_value))))
    return {"logp_gap": float(np.max(np.abs(served["logp"] - ref_logp))),
            "value_gap": float(np.max(np.abs(served["value"] - ref_value))
                               / max(rms, 1e-30))}


# ---------------------------------------------------------------------------
# train: TrainerWorker.train_on_batch in inline drive mode
# ---------------------------------------------------------------------------

def _rl(mix: Dict):
    from repro.configs.base import RLConfig
    return RLConfig(**{k: v for k, v in mix["rl"].items()
                       if k in RLConfig.__dataclass_fields__})


def _program_init(params):
    """Patch for ``TrainerWorker``'s own init: the program's
    ``init_train_state`` run jitted (one program), with the benchmark's
    weights in place of the params it draws."""
    import jax
    from repro.runtime import trainer as trainer_mod

    program_init = trainer_mod.init_train_state

    def init(cfg, key, *, mesh=None):
        state = jax.jit(functools.partial(program_init, cfg))(key)
        return state._replace(params=params)
    return mock.patch.object(trainer_mod, "init_train_state", init)


def build_trainer(cfg, mix: Dict, seed32: int, params):
    """``TrainerWorker`` as the barrier scheduler drives it, starting from
    the benchmark's weights."""
    from repro.configs.base import RuntimeConfig
    from repro.runtime.trainer import TrainerWorker
    from repro.runtime.weight_store import VersionedWeightStore

    class _NoSource:
        def pop_batch(self, n, timeout=None):
            return []

    with _program_init(params):
        return TrainerWorker(cfg, _rl(mix), RuntimeConfig(), _NoSource(),
                             VersionedWeightStore(),
                             batch_episodes=mix["segments"], seed=seed32)


@functools.lru_cache(maxsize=None)
def _norm_fns():
    import jax
    import jax.numpy as jnp

    def norms(tree):
        return jax.tree.map(
            lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))),
            tree)

    def diff_norms(a, b):
        return norms(jax.tree.map(
            lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b))
    return jax.jit(norms), jax.jit(diff_norms)


def _named(tree) -> Dict[str, float]:
    import jax
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {"/".join(str(getattr(k, "key", k)) for k in p): float(x)
            for p, x in flat}


def run_train(ctx: Ctx) -> Outcome:
    from repro.data.trajectory import TrajectoryBatch

    mix, config, fam = ctx.mix, ctx.config, ctx.family
    if mix["segments"] != mix["rl"]["micro_batch"] * mix["rl"]["grad_accum"]:
        raise ValueError("segments must be micro_batch x grad_accum")
    cfg = fam.model_config(config)
    rng = np.random.default_rng([ctx.seed, 1])
    checked = [traffic_gen.train_batch(rng, mix, config)
               for _ in range(mix["checked_steps"])]
    pool = [traffic_gen.train_batch(rng, mix, config)
            for _ in range(mix["pool_batches"])]
    s32 = traffic_gen.seed32(ctx.seed)
    params = make_params(fam, config, s32)
    check_params(params, cfg)
    trainer = build_trainer(cfg, mix, s32, params)
    del params
    trainer.begin_inline()
    norms, diff_norms = _norm_fns()

    # the first steps, through the window's own call, are the checked ones
    prog_steps, prog_grad = [], None
    for i, b in enumerate(checked):
        with _span("trainer.train_on_batch"):
            prog_steps.append(trainer.train_on_batch(TrajectoryBatch(**b)))
        if i == 0:
            prog_grad = {k: v / (1.0 - ADAM_B1) for k, v in
                         _named(norms(trainer.state.opt.mu)).items()}
    p0 = make_params(fam, config, s32)
    prog_change = _named(diff_norms(trainer.state.params, p0))
    del p0

    tracer = _Tracer(ctx.trace)
    logged = len(trainer.metrics_log)
    steps, t0 = 0, time.monotonic()
    setup_s = t0 - ctx.t_start
    with tracer:
        with _span("window"):
            t0 = time.monotonic()
            while ctx.seconds > 0:
                with _span("trainer.train_on_batch"):
                    trainer.train_on_batch(
                        TrajectoryBatch(**pool[steps % len(pool)]))
                steps += 1
                if time.monotonic() - t0 >= ctx.seconds:
                    break
            t1 = time.monotonic()
    window_s = t1 - t0
    peak = memory_peak()
    window_log = trainer.metrics_log[logged:]
    program = {k: float(np.mean([m[k] for m in window_log]))
               for k in (window_log[0] if window_log else {})}
    summary = None
    if ctx.trace:
        text = trainer._step_fn.lower(
            trainer.state, TrajectoryBatch(**pool[0])).compile().as_text()
        summary = tracer.summary([text])
    del trainer
    gc.collect()

    spec = fam.Spec.from_config(config)
    rl = mix["rl"]
    ref = fam.train_reference(make_params(fam, config, s32), checked, rl,
                              spec)
    readings = train_readings(prog_steps, prog_grad, prog_change, ref, rl)
    out_control = {}
    if ctx.controls:
        for name, kw in (("control_fp8", {"prec": "fp8"}),
                         ("fault_half_batch", {"half_batch": True})):
            got = fam.train_reference(make_params(fam, config, s32),
                                      checked, rl, spec, **kw)
            out_control[name] = train_readings(
                got["steps"], got["first_grad"], got["change"], ref, rl)

    seq = fam.seq_shape(config, mix["instruction_tokens"])
    rows = mix["segments"] * (mix["horizon"] + 1)
    step_flops = fam.train_step_flops(config, mix["segments"],
                                      mix["horizon"],
                                      mix["instruction_tokens"])
    tokens = rows * seq["tokens"]
    e2e = {"train_tokens_per_s": steps * tokens / window_s} if steps else {}
    e2e["setup_s"] = setup_s
    notes = [f"window: {steps} optimizer steps of {tokens} tokens in "
             f"{window_s:.6f} s",
             "checked steps (program): " + ", ".join(
                 f"loss {s['loss']!r} grad_norm {s['grad_norm']!r}"
                 for s in prog_steps),
             "checked steps (reference): " + ", ".join(
                 f"loss {s['loss']!r} grad_norm {s['grad_norm']!r}"
                 for s in ref["steps"])]
    return Outcome(
        e2e=e2e, attempted=steps, failed=0, readings=readings,
        window_s=window_s, counters={"steps": steps, "tokens": steps * tokens},
        work=window_work(steps, step_flops, fam.kernel_work(config, mix)),
        memory_peak_bytes=peak, summary=summary, control=out_control,
        program=program, notes=notes)


# ---------------------------------------------------------------------------
# serve: InferenceService.submit from closed-loop env clients
# ---------------------------------------------------------------------------

class Client(threading.Thread):
    """One env: observe, submit, wait for the action, step, think."""

    def __init__(self, idx: int, service, mix: Dict, config: Dict,
                 seed: int, stop: threading.Event):
        super().__init__(name=f"client-{idx}", daemon=True)
        rng = np.random.default_rng([seed, 2, idx])
        ph = config["policy_head"]
        self.env = traffic_gen.Env(mix["suite"], mix["max_episode_steps"],
                                   ph["action_vocab_size"], ph["action_dim"],
                                   rng)
        self.think = traffic_gen.lognormal_latency(
            mix["think_ms_median"], mix["think_sigma"], rng)
        self.tasks = rng.integers(0, traffic_gen.TASKS_PER_SUITE, 1 << 12)
        self.service, self.stop = service, stop
        self.records: List[tuple] = []       # (t_submit, t_done, request)
        self.failed = 0

    def run(self) -> None:
        episode = 0
        obs = self.env.reset(int(self.tasks[0]))
        while not self.stop.is_set():
            with _span("client.submit"):
                t_submit = time.monotonic()
                fut = self.service.submit(obs["tokens"], obs["frame"],
                                          obs["step"])
            with _span("client.wait"):
                try:
                    res = fut.result(timeout=60.0)
                except Exception:           # counted, never retried
                    self.failed += 1
                    return
            self.records.append((t_submit, time.monotonic(), obs, res))
            obs, done = self.env.step(res["actions"])
            with _span("client.think"):
                time.sleep(self.think())
            if done:
                episode += 1
                obs = self.env.reset(int(self.tasks[episode % len(self.tasks)]))


def check_requests(fam: ModuleType, config: Dict, params,
                   sample: List[tuple], controls: bool):
    """Readings of served (observation, result) pairs against the
    reference's teacher-forced pass with the benchmark's weights (and the
    float8 control's, with ``controls``)."""
    from repro.models.transformer import FRONTEND_DIM
    if not sample:
        nan = float("nan")
        return {"logp_gap": nan, "value_gap": nan}, {}
    obs = np.stack([o["tokens"] for o, _ in sample])
    frame = np.stack([o["frame"] for o, _ in sample])
    steps = np.array([o["step"] for o, _ in sample], np.int32)
    served = {"actions": np.stack([r["actions"] for _, r in sample]),
              "logp": np.stack([r["logp"] for _, r in sample]),
              "value": np.array([r["value"] for _, r in sample], np.float32)}
    prefix = np.zeros((len(sample), 1, FRONTEND_DIM), np.float32)
    prefix[:, 0, :frame.shape[1]] = frame
    spec = fam.Spec.from_config(config)
    ref_lp, ref_v = fam.serve_readings(
        params, obs, served["actions"], steps, prefix, spec)
    readings = serve_readings(served, ref_lp, ref_v)
    control = {}
    if controls:
        lp8, v8 = fam.serve_readings(
            params, obs, served["actions"], steps, prefix, spec, prec="fp8")
        control["control_fp8"] = serve_readings(
            {"logp": lp8, "value": v8}, ref_lp, ref_v)
    return readings, control


def _serve_counters(inf) -> Dict[str, float]:
    """Every counter of the tier's registry, and the count and sum of
    every histogram as ``<name>.count``, ``<name>.sum``, in one snapshot."""
    snap = inf.metrics.snapshot()
    out = dict(snap["counters"])
    for name, h in snap["hists"].items():
        out[f"{name}.count"] = h["count"]
        out[f"{name}.sum"] = h["sum"]
    return out


def _at_batch_end(inf, timeout: float = 60.0):
    """Wait for the tier to finish its next batch (its ``requests``
    counter moves last); the counters and the clock then. A window that
    opens and closes on finished batches counts whole batches over the
    time they took, so its rate does not jump by a batch with the phase."""
    n = inf.metrics.counter("requests")
    end = time.monotonic() + timeout
    while inf.metrics.counter("requests") == n and time.monotonic() < end:
        time.sleep(0.0005)
    return _serve_counters(inf), time.monotonic()


def run_serve(ctx: Ctx) -> Outcome:
    import jax
    from repro.configs.base import RuntimeConfig
    from repro.models.transformer import FRONTEND_DIM
    from repro.runtime.inference import InferenceService
    from repro.runtime.weight_store import VersionedWeightStore

    mix, config, fam = ctx.mix, ctx.config, ctx.family
    cfg = fam.model_config(config)
    s32 = traffic_gen.seed32(ctx.seed)
    params = make_params(fam, config, s32)
    check_params(params, cfg)
    store = VersionedWeightStore()
    store.publish(params, 1)
    buckets = tuple(mix["buckets"])
    rt = RuntimeConfig(num_inference_workers=mix["inference_workers"],
                       inference_batch=mix["inference_batch"],
                       inference_max_wait_s=mix["max_wait_ms"] / 1000.0,
                       batch_buckets=buckets)
    inf = InferenceService(cfg, store, rt, seed=s32)
    t_obs = mix["instruction_tokens"]
    args = {nb: (np.zeros((nb, t_obs), np.int32), np.zeros((nb,), np.int32),
                 np.zeros((nb, 1, FRONTEND_DIM), np.float32))
            for nb in buckets}
    for nb in buckets:                      # every bucket the window can use
        jax.block_until_ready(inf._fn(params, jax.random.PRNGKey(0),
                                      *args[nb]))
    stop = threading.Event()
    clients = [Client(i, inf, mix, config, ctx.seed, stop)
               for i in range(mix["clients"])]
    inf.start()
    for c in clients:
        c.start()
    time.sleep(mix["ramp_s"])               # every client in its loop
    tracer = _Tracer(ctx.trace)
    with tracer:
        with _span("window"):
            c0, t0 = _at_batch_end(inf)
            setup_s = t0 - ctx.t_start
            time.sleep(ctx.seconds)
            c1, t1 = _at_batch_end(inf)
    stop.set()
    for c in clients:
        c.join(timeout=90.0)
    inf.stop()
    inf.join(timeout=30.0)
    window_s = t1 - t0
    peak = memory_peak()
    summary = None
    if ctx.trace:
        summary = tracer.summary([
            inf._fn.lower(params, jax.random.PRNGKey(0),
                          *args[nb]).compile().as_text()
            for nb in buckets])
    failed = sum(c.failed for c in clients) + sum(c.is_alive()
                                                  for c in clients)
    crash = inf.error
    done = [r for c in clients for r in c.records if t0 <= r[1] <= t1]
    lat = np.array([r[1] - r[0] for r in done])
    # only the benchmark's weights stay on the device for the reference
    for c in clients:
        c.service = None
    del inf, store
    gc.collect()

    # correctness: a sample, drawn from the seed, of the answered requests
    rng = np.random.default_rng([ctx.seed, 3])
    take = rng.choice(len(done), min(mix["checked_requests"], len(done)),
                      replace=False)
    readings, out_control = check_requests(
        fam, config, params, [done[i][2:] for i in np.sort(take)],
        ctx.controls)
    del params

    program = {k: v - c0.get(k, 0.0) for k, v in c1.items()}
    d = {k: program.get(k, 0.0)
         for k in ("requests", "batches", "padded_slots")}
    new = fam.seq_shape(config, t_obs)["actions"]
    e2e = {"setup_s": setup_s}
    if d["requests"] and len(done):
        e2e["actions_per_s"] = d["requests"] / window_s
        e2e["action_latency_p95_ms"] = float(np.percentile(lat, 95)) * 1e3
    notes = [f"inference service failed: {crash!r}"] if crash else []
    notes += [f"window: {d['requests']:.0f} requests answered in "
              f"{window_s:.6f} s ({len(done)} of them timed by the clients); "
              f"service counters over the window {d}",
              f"latency ms: p50 {float(np.percentile(lat, 50)) * 1e3!r} p95 "
              f"{float(np.percentile(lat, 95)) * 1e3!r} max "
              f"{float(lat.max()) * 1e3!r}"
              if len(done) else "no request answered in the window",
              f"checked {len(take)} requests ({len(take) * new} tokens)"]
    return Outcome(
        e2e=e2e, attempted=int(d["requests"]) + failed,
        failed=failed + (crash is not None),
        readings=readings, window_s=window_s,
        counters={"answered": len(done), **d},
        work=window_work(d["requests"],
                         fam.serve_request_flops(config, t_obs),
                         fam.kernel_work(config, mix)),
        memory_peak_bytes=peak, summary=summary, control=out_control,
        program=program, notes=notes)


RUNNERS = {"train": run_train, "serve": run_serve}
