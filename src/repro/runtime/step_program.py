"""Train-step program IR: one optimizer step as a graph of named stages.

The single source of truth for *what one training step is*, consumed by
three executors that must never drift apart:

  * the fused single-mesh path (``StepProgram.fused`` → one jit, the
    default ``TrainerWorker`` step — byte-identical to the historical
    ``core.train_step.make_train_step``);
  * the pipelined executor (``runtime/pipeline_exec.py``) — jits each
    device stage separately and drives them from a static per-submesh
    RUN/SEND/RECV/FREE instruction schedule;
  * the sync/async schedulers, which only ever see
    ``TrainerWorker.train_on_batch`` and therefore inherit whichever of
    the two executors the config selected.

A stage is a named function with declared dataflow (``inputs`` →
``outputs`` buffer names) and, when a mesh is supplied, declared
PartitionSpec shardings for its pinned buffers. Stage *functions* come
from ``core.train_step`` — the fused path composes the very same
callables under ``jax.lax.scan``, so pipelined-vs-fused parity is
structural rather than asserted after the fact.

Step layout (paper §3.1 / App. C):

    collate(host) → fwd_bwd(×K micro) → grad_reduce(×K) →
        optim_update → publish(host)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, Optional, Tuple

from repro.configs.base import ModelConfig, RLConfig


@dataclasses.dataclass(frozen=True)
class StageSpec:
    """One named stage of the step program.

    ``fn`` is the stage body (None for host-side stages the runtime owns,
    e.g. publish). ``init`` optionally builds the stage's carried
    accumulator (grad_reduce). ``per_micro`` stages run once per
    micro-batch inside a gradient-accumulation window. ``specs`` maps
    buffer names to PartitionSpec trees — the declared shardings the
    executor places those buffers under when a mesh is in play.
    """

    name: str
    inputs: Tuple[str, ...]
    outputs: Tuple[str, ...]
    fn: Optional[Callable] = None
    init: Optional[Callable] = None
    kind: str = "device"                 # {"device", "host"}
    per_micro: bool = False
    specs: Optional[Dict[str, object]] = None


@dataclasses.dataclass(frozen=True)
class StepProgram:
    """Validated sequence of stages + the fused whole-step function."""

    name: str
    stages: Tuple[StageSpec, ...]
    inputs: Tuple[str, ...] = ()         # externally-fed buffer names
    fused_fn: Optional[Callable] = None
    n_micro: int = 1

    def __post_init__(self):
        names = [s.name for s in self.stages]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate stage names in {names}")
        live = set(self.inputs)
        for s in self.stages:
            missing = [b for b in s.inputs if b not in live]
            if missing:
                raise ValueError(
                    f"stage {s.name!r} reads {missing} before any stage "
                    f"produces them (live: {sorted(live)})")
            live.update(s.outputs)

    def stage(self, name: str) -> StageSpec:
        for s in self.stages:
            if s.name == name:
                return s
        raise KeyError(f"{self.name!r} has no stage {name!r}; have "
                       f"{[s.name for s in self.stages]}")

    def fused(self, *, donate: bool = False, state_shardings=None):
        """The whole step as one jit — the single-mesh default path.

        ``state_shardings`` pins the returned state to that placement (the
        ZeRO layout it came in with); without it the compiler may lay the
        updated params out like their sharded moments."""
        import jax
        if self.fused_fn is None:
            raise ValueError(f"program {self.name!r} has no fused form")
        pinned = ({} if state_shardings is None
                  else {"out_shardings": (state_shardings, None)})
        return jax.jit(self.fused_fn,
                       donate_argnums=(0,) if donate else (), **pinned)

    def describe(self) -> str:
        lines = [f"program {self.name} (K={self.n_micro}; "
                 f"feeds: {', '.join(self.inputs)})"]
        for s in self.stages:
            micro = f" ×{self.n_micro}" if s.per_micro else ""
            lines.append(
                f"  {s.name:<14}[{s.kind}]{micro:<4} "
                f"({', '.join(s.inputs)}) -> ({', '.join(s.outputs)})")
        return "\n".join(lines)


def _train_state_specs(cfg: ModelConfig, mesh):
    """Declared shardings for the TrainState buffer: params under the
    TP/FSDP rules, f32 Adam moments additionally ZeRO-sharded over
    ``data`` (optim/zero.py), scalars replicated."""
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.models.policy import init_policy_params
    from repro.optim import zero
    from repro.sharding import rules

    shapes = jax.eval_shape(functools.partial(init_policy_params, cfg),
                            jax.random.PRNGKey(0))
    pspec = rules.param_specs(cfg, shapes, mesh)
    mspec = zero.shard_moments_spec(shapes, pspec, data_axis="data",
                                    data_size=mesh.shape.get("data", 1))
    return {"params": pspec, "moments": mspec, "scalars": P()}


def build_train_step_program(cfg: ModelConfig, rl: RLConfig, *,
                             remat: bool = False, n_micro: int = 0,
                             mesh=None) -> StepProgram:
    """The GIPO train step as a StepProgram.

    Buffer conventions (what the executor's schedule names refer to):
      * ``state``   — TrainState (params frozen across the window, eq. 7)
      * ``micro``   — one contiguous micro-batch slice (App. C.1)
      * ``grads``   — one micro-batch's grads (FREEd after folding)
      * ``aux``     — (metrics, packed adv stats) from that micro-batch
      * ``acc``     — (f32 grad accumulator, stats accumulator)
    """
    import jax.numpy as jnp

    # NB: repro.core's __init__ rebinds the attribute ``train_step`` to
    # the function, shadowing the submodule for plain imports
    import importlib
    core = importlib.import_module("repro.core.train_step")

    n_micro = n_micro or rl.grad_accum
    specs = _train_state_specs(cfg, mesh) if mesh is not None else None

    def fwd_bwd(state, micro):
        return core.microbatch_grads(state.params, micro, state.adv_norm,
                                     cfg=cfg, rl=rl, remat=remat)

    def grad_init(state):
        return (core.zero_grads_like(state.params), jnp.zeros((3,)))

    def grad_reduce(acc, grads, aux):
        grads_acc, stats_acc = core.accumulate_grads(
            acc[0], grads, acc[1], aux[1], n_micro)
        return (grads_acc, stats_acc)

    def optim_update(state, acc, aux):
        return core.apply_update(state, acc[0], acc[1], aux[0], rl=rl)

    def fused(state, batch):
        return core.train_step(state, batch, cfg=cfg, rl=rl, remat=remat)

    from repro.runtime.trainer import collate_segments
    stages = (
        StageSpec("collate", inputs=("segments",), outputs=("batch",),
                  fn=collate_segments, kind="host"),
        StageSpec("fwd_bwd", inputs=("state", "micro"),
                  outputs=("grads", "aux"), fn=fwd_bwd, per_micro=True),
        StageSpec("grad_reduce", inputs=("acc", "grads", "aux"),
                  outputs=("acc",), fn=grad_reduce, init=grad_init,
                  per_micro=True),
        StageSpec("optim_update", inputs=("state", "acc", "aux"),
                  outputs=("state", "metrics"), fn=optim_update,
                  specs={"state": specs} if specs else None),
        StageSpec("publish", inputs=("state",), outputs=(), kind="host"),
    )
    return StepProgram(name="gipo_train_step", stages=stages,
                       inputs=("segments", "state", "micro", "acc"),
                       fused_fn=fused, n_micro=n_micro)
