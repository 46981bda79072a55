"""Pipelined multi-submesh training runtime (Alpa-style static schedules).

Runs the policy trainer and the world-model trainer as pipeline stages on
disjoint submeshes of one local device set. Each submesh executes a
STATIC instruction schedule — a flat tuple of RUN / SEND / RECV / FREE
instructions compiled from the :class:`~repro.runtime.step_program
.StepProgram` — on its own worker thread:

  * RUN   — invoke one jitted stage body on buffers already resident on
            the submesh (micro-batch grads fold into the f32 accumulator
            immediately after each fwd_bwd, GPipe/1F1B-style, so live
            gradient memory is bounded to ONE micro-batch regardless of
            the accumulation depth);
  * SEND / RECV — rendezvous through a tagged mailbox; cross-submesh
            transfers reshard via ``jax.device_put`` onto the receiving
            submesh (the weight-publish path out of the policy submesh is
            exactly this resharding);
  * FREE  — drop the buffer reference so XLA can reuse the allocation;
            the schedule validator proves every buffer is freed and that
            the micro-grad high-water mark is 1.

On CPU CI the submeshes are slices of the host device list (with a single
device both stages share it — schedule semantics identical, overlap nil),
so schedule correctness, parity against the fused path, and bubble
accounting are all testable without a TPU.
"""
from __future__ import annotations

import dataclasses
import enum
import functools
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.runtime import telemetry as _tel
from repro.runtime.step_program import StepProgram


class PipelineOp(enum.IntEnum):
    RUN = 0
    SEND = 1
    RECV = 2
    FREE = 3


@dataclasses.dataclass(frozen=True)
class Instruction:
    """One schedule entry. RUN names a program stage and its buffer
    bindings; SEND/RECV move ``buffer`` through the mailbox under
    ``tag``; FREE drops ``buffer``."""

    op: PipelineOp
    stage: str = ""
    inputs: Tuple[str, ...] = ()
    outputs: Tuple[str, ...] = ()
    buffer: str = ""
    micro: int = -1
    tag: str = ""

    def __repr__(self):
        if self.op == PipelineOp.RUN:
            m = f" m={self.micro}" if self.micro >= 0 else ""
            return (f"RUN {self.stage}{m} ({','.join(self.inputs)})->"
                    f"({','.join(self.outputs)})")
        if self.op == PipelineOp.FREE:
            return f"FREE {self.buffer}"
        return f"{self.op.name} {self.buffer} tag={self.tag}"


@dataclasses.dataclass(frozen=True)
class Submesh:
    """A named slice of the local device list."""

    name: str
    devices: Tuple

    @property
    def device(self):
        return self.devices[0]

    def mesh(self):
        """(n, 1) Mesh over exactly these devices, axes (data, model)."""
        from jax.sharding import Mesh
        return Mesh(np.array(self.devices).reshape(len(self.devices), 1),
                    ("data", "model"))


@dataclasses.dataclass(frozen=True)
class SubmeshLayout:
    """Policy + WM submeshes carved from one device list."""

    policy: Submesh
    wm: Submesh
    disjoint: bool

    @classmethod
    def split(cls, devices: Sequence, *, wm_devices: int = 0
              ) -> "SubmeshLayout":
        """Slice the host device list: the WM stage takes ``wm_devices``
        from the tail (default: half when >=2 devices). With one device
        both submeshes alias it — the schedules still interleave
        correctly, there is just nothing to overlap."""
        devices = tuple(devices)
        if len(devices) >= 2:
            n_wm = wm_devices or len(devices) // 2
            n_wm = max(1, min(n_wm, len(devices) - 1))
            return cls(Submesh("policy", devices[:len(devices) - n_wm]),
                       Submesh("wm", devices[len(devices) - n_wm:]),
                       disjoint=True)
        return cls(Submesh("policy", devices), Submesh("wm", devices),
                   disjoint=False)


# --------------------------------------------------------------------------
# schedule construction + static validation
# --------------------------------------------------------------------------

def _I(op, **kw):
    return Instruction(op=op, **kw)


@functools.lru_cache(maxsize=32)
def build_train_schedules(n_micro: int, wm_micro: int
                          ) -> Dict[str, Tuple[Instruction, ...]]:
    """Static per-submesh schedules for one training round.

    Policy stream: RECV state + micro feeds, fold each micro-batch's
    grads immediately (1F1B — ``g{m}`` FREEd before ``g{m+1}`` exists),
    optimizer update, SEND the updated state back to the host (the
    cross-mesh weight-publish reshard). WM stream: one RUN per WM
    micro-batch. Host-side tags are the feeds/collects of
    ``PipelineExecutor.run_round``.
    """
    pol: List[Instruction] = [
        _I(PipelineOp.RECV, buffer="state", tag="host:policy:state"),
        _I(PipelineOp.RUN, stage="grad_reduce/init", inputs=("state",),
           outputs=("acc0",)),
    ]
    for m in range(n_micro):
        pol += [
            _I(PipelineOp.RECV, buffer=f"mb{m}", tag=f"host:policy:micro{m}"),
            _I(PipelineOp.RUN, stage="fwd_bwd", micro=m,
               inputs=("state", f"mb{m}"), outputs=(f"g{m}", f"aux{m}")),
            _I(PipelineOp.RUN, stage="grad_reduce", micro=m,
               inputs=(f"acc{m}", f"g{m}", f"aux{m}"),
               outputs=(f"acc{m + 1}",)),
            _I(PipelineOp.FREE, buffer=f"g{m}"),
            _I(PipelineOp.FREE, buffer=f"mb{m}"),
            _I(PipelineOp.FREE, buffer=f"acc{m}"),
        ]
        if m < n_micro - 1:
            pol.append(_I(PipelineOp.FREE, buffer=f"aux{m}"))
    last = n_micro - 1
    pol += [
        _I(PipelineOp.RUN, stage="optim_update",
           inputs=("state", f"acc{n_micro}", f"aux{last}"),
           outputs=("state_out", "metrics")),
        _I(PipelineOp.FREE, buffer=f"acc{n_micro}"),
        _I(PipelineOp.FREE, buffer=f"aux{last}"),
        _I(PipelineOp.FREE, buffer="state"),
        _I(PipelineOp.SEND, buffer="state_out", tag="pipe:policy:state"),
        _I(PipelineOp.SEND, buffer="metrics", tag="pipe:policy:metrics"),
        _I(PipelineOp.FREE, buffer="state_out"),
        _I(PipelineOp.FREE, buffer="metrics"),
    ]

    wm: List[Instruction] = []
    for m in range(wm_micro):
        wm += [
            _I(PipelineOp.RECV, buffer=f"wmb{m}", tag=f"host:wm:micro{m}"),
            _I(PipelineOp.RUN, stage="wm_update", micro=m,
               inputs=(f"wmb{m}",), outputs=(f"wmo{m}",)),
            _I(PipelineOp.FREE, buffer=f"wmb{m}"),
        ]
        if m < wm_micro - 1:
            wm.append(_I(PipelineOp.FREE, buffer=f"wmo{m}"))
    if wm_micro:
        wm += [
            _I(PipelineOp.SEND, buffer=f"wmo{wm_micro - 1}",
               tag="pipe:wm:out"),
            _I(PipelineOp.FREE, buffer=f"wmo{wm_micro - 1}"),
        ]
    return {"policy": tuple(pol), "wm": tuple(wm)}


def validate_schedules(schedules: Dict[str, Tuple[Instruction, ...]], *,
                       feeds: Sequence[str], collects: Sequence[str]
                       ) -> Dict[str, Dict]:
    """Abstractly interpret the schedules; raise on any unsound program.

    Checks, per stream: RUN/SEND/FREE only touch live buffers, no buffer
    is redefined while live, everything is FREEd by the end. Globally:
    every RECV tag is fed exactly once (by the host or a peer SEND) and
    every SEND is consumed (host collect or peer RECV). Returns per-stream
    stats including the micro-grad high-water mark (the 1F1B bound).
    """
    sends: Dict[str, str] = {}
    recvs: Dict[str, str] = {}
    stats: Dict[str, Dict] = {}
    for name, sched in schedules.items():
        live: set = set()
        peak_grads = grads_live = 0
        for ins in sched:
            if ins.op == PipelineOp.RECV:
                if ins.tag in recvs:
                    raise ValueError(f"[{name}] duplicate RECV {ins.tag}")
                recvs[ins.tag] = name
                if ins.buffer in live:
                    raise ValueError(
                        f"[{name}] RECV redefines live {ins.buffer!r}")
                live.add(ins.buffer)
            elif ins.op == PipelineOp.RUN:
                dead = [b for b in ins.inputs if b not in live]
                if dead:
                    raise ValueError(
                        f"[{name}] {ins!r} reads dead buffers {dead}")
                clash = [b for b in ins.outputs if b in live]
                if clash:
                    raise ValueError(
                        f"[{name}] {ins!r} redefines live {clash}")
                live.update(ins.outputs)
                grads_live += sum(
                    1 for b in ins.outputs
                    if b.startswith("g") and b[1:].isdigit())
                peak_grads = max(peak_grads, grads_live)
            elif ins.op == PipelineOp.SEND:
                if ins.buffer not in live:
                    raise ValueError(
                        f"[{name}] SEND of dead buffer {ins.buffer!r}")
                if ins.tag in sends:
                    raise ValueError(f"[{name}] duplicate SEND {ins.tag}")
                sends[ins.tag] = name
            elif ins.op == PipelineOp.FREE:
                if ins.buffer not in live:
                    raise ValueError(
                        f"[{name}] FREE of dead buffer {ins.buffer!r}")
                live.discard(ins.buffer)
                if ins.buffer.startswith("g") and ins.buffer[1:].isdigit():
                    grads_live -= 1
        if live:
            raise ValueError(f"[{name}] leaks buffers {sorted(live)}")
        stats[name] = {"instructions": len(sched),
                       "peak_micro_grads": peak_grads}

    for tag, stream in recvs.items():
        if tag not in feeds and sends.get(tag, stream) == stream:
            raise ValueError(f"RECV {tag} in [{stream}] never fed")
    for tag, stream in sends.items():
        if tag not in collects and recvs.get(tag, stream) == stream:
            raise ValueError(f"SEND {tag} from [{stream}] never consumed")
    return stats


# --------------------------------------------------------------------------
# executor
# --------------------------------------------------------------------------

def host_microbatches(batch, n_micro: int) -> List:
    """Contiguous micro-batch slices (App. C.1) as host-side views —
    matches ``core.train_step._microbatches`` exactly."""
    import jax
    b = batch.obs_tokens.shape[0]
    # floor like the fused scan does (a non-divisible tail is dropped)
    mb = b // n_micro
    if mb == 0:
        raise ValueError(f"batch of {b} too small for {n_micro} "
                         f"micro-batches")
    out = []
    for i in range(n_micro):
        sl = lambda x: None if x is None else x[i * mb:(i + 1) * mb]
        out.append(jax.tree.map(sl, batch, is_leaf=lambda v: v is None))
    return out


class _Mailbox:
    """Tagged single-consumer rendezvous between host and streams."""

    def __init__(self):
        self._cv = threading.Condition()
        self._slots: Dict[str, object] = {}

    def put(self, tag: str, value) -> None:
        with self._cv:
            if tag in self._slots:
                raise RuntimeError(f"mailbox tag {tag!r} already occupied")
            self._slots[tag] = value
            self._cv.notify_all()

    def take(self, tag: str, timeout: float = 120.0):
        deadline = time.monotonic() + timeout
        with self._cv:
            while tag not in self._slots:
                left = deadline - time.monotonic()
                if left <= 0:
                    raise TimeoutError(f"RECV {tag!r} timed out")
                self._cv.wait(left)
            return self._slots.pop(tag)


def _tree_nbytes(value) -> int:
    import jax
    total = 0
    for leaf in jax.tree.leaves(value):
        total += getattr(leaf, "nbytes", 0)
    return total


class _Stream:
    """One submesh's persistent worker thread executing its schedule."""

    def __init__(self, name: str, submesh: Submesh, mailbox: _Mailbox,
                 run_fns: Dict[str, Callable], *, place: bool):
        self.name = name
        self.submesh = submesh
        self.mailbox = mailbox
        self.run_fns = run_fns
        self.place = place                       # device_put RECVs onto
                                                 # the submesh (disjoint
                                                 # layouts only)
        self.busy_s = 0.0
        self.peak_live_bytes = 0
        self.peak_grad_bytes = 0
        self._schedule: Tuple[Instruction, ...] = ()
        self._go = threading.Event()
        self._done = threading.Event()
        self._error: Optional[BaseException] = None
        self._shutdown = False
        self._thread = threading.Thread(
            target=self._loop, name=f"pipeline-{name}", daemon=True)
        self._thread.start()

    def submit(self, schedule: Tuple[Instruction, ...]) -> None:
        self._schedule = schedule
        self._error = None
        self._done.clear()
        self._go.set()

    def wait(self, timeout: float = 300.0) -> None:
        if not self._done.wait(timeout):
            raise TimeoutError(f"pipeline stream {self.name!r} wedged")
        if self._error is not None:
            raise self._error

    def close(self) -> None:
        self._shutdown = True
        self._go.set()
        self._thread.join(timeout=10.0)

    # -- instruction interpreter ------------------------------------------------
    def _loop(self) -> None:
        while True:
            self._go.wait()
            self._go.clear()
            if self._shutdown:
                return
            try:
                self._execute(self._schedule)
            except BaseException as e:  # surfaced by wait()
                self._error = e
            self._done.set()

    def _execute(self, schedule: Tuple[Instruction, ...]) -> None:
        import jax
        bufs: Dict[str, object] = {}
        live_bytes = grad_bytes = 0
        sizes: Dict[str, int] = {}
        self.busy_s = 0.0
        for ins in schedule:
            if ins.op == PipelineOp.RECV:
                value = self.mailbox.take(ins.tag)
                if self.place:
                    # cross-mesh reshard: commit the buffer to this
                    # submesh so RUNs execute here, not where the
                    # producer left it
                    value = jax.device_put(value, self.submesh.device)
                bufs[ins.buffer] = value
            elif ins.op == PipelineOp.RUN:
                fn = self.run_fns[ins.stage]
                args = tuple(bufs[b] for b in ins.inputs)
                t0 = time.perf_counter()
                with _tel.span("train.stage", cat="train",
                               args={"stage": ins.stage,
                                     "submesh": self.name,
                                     "micro": ins.micro}):
                    out = jax.block_until_ready(fn(*args))
                self.busy_s += time.perf_counter() - t0
                if len(ins.outputs) == 1:
                    out = (out,)
                for b, v in zip(ins.outputs, out):
                    bufs[b] = v
                    sizes[b] = _tree_nbytes(v)
                    live_bytes += sizes[b]
                    if b.startswith("g") and b[1:].isdigit():
                        grad_bytes += sizes[b]
                self.peak_live_bytes = max(self.peak_live_bytes, live_bytes)
                self.peak_grad_bytes = max(self.peak_grad_bytes, grad_bytes)
            elif ins.op == PipelineOp.SEND:
                self.mailbox.put(ins.tag, bufs[ins.buffer])
            elif ins.op == PipelineOp.FREE:
                bufs.pop(ins.buffer)
                freed = sizes.pop(ins.buffer, 0)
                live_bytes -= freed
                if ins.buffer.startswith("g") and ins.buffer[1:].isdigit():
                    grad_bytes -= freed


class PipelineExecutor:
    """Drives the static schedules over a :class:`SubmeshLayout`.

    ``run_round`` executes one training round: the policy stream consumes
    ``n_micro`` micro-batches and produces the updated TrainState; the WM
    stream (when a stage is attached via :meth:`set_wm_stage`) trains the
    world model on its own submesh concurrently. Per-round bubble
    fraction = 1 − busy/wall per stream, fed to the
    ``pipeline_bubble_frac`` histogram.
    """

    FEEDS = ("host:policy:state", "host:policy:micro{m}",
             "host:wm:micro{m}")
    COLLECTS = ("pipe:policy:state", "pipe:policy:metrics", "pipe:wm:out")

    def __init__(self, program: StepProgram, layout: SubmeshLayout, *,
                 n_micro: int = 0, metrics=None):
        import jax
        self.program = program
        self.layout = layout
        self.n_micro = n_micro or program.n_micro
        self.metrics = metrics
        self._wm_stage: Optional[Callable] = None
        self._wm_feed: Optional[Callable] = None
        self.wm_micro = 0
        self.last_bubble: Dict[str, float] = {}
        self.rounds = 0

        self._mailbox = _Mailbox()
        # single-device submesh: commit RECVd buffers to that device so
        # RUNs land there. Multi-device policy submeshes keep the state's
        # own (ZeRO-sharded) placement — a device_put to one device would
        # silently gather it.
        place = layout.disjoint and len(layout.policy.devices) == 1
        pol_fns = {
            "fwd_bwd": jax.jit(program.stage("fwd_bwd").fn),
            "grad_reduce/init": jax.jit(program.stage("grad_reduce").init),
            "grad_reduce": jax.jit(program.stage("grad_reduce").fn),
            "optim_update": jax.jit(program.stage("optim_update").fn),
        }
        self._policy = _Stream("policy", layout.policy, self._mailbox,
                               pol_fns, place=place)
        self._wm = _Stream("wm", layout.wm, self._mailbox,
                           {}, place=False)
        self._closed = False

    # -- WM stage attachment -----------------------------------------------------
    def set_wm_stage(self, stage_fn: Callable, feed_fn: Callable, *,
                     wm_micro: int = 1) -> None:
        """Attach the world-model stage: ``stage_fn(batch)`` runs one WM
        train cycle (host callable owning its own state, pinned to the WM
        submesh); ``feed_fn()`` returns the next WM batch or None."""
        import jax
        submesh = self.layout.wm

        def run(batch):
            with jax.default_device(submesh.device):
                return stage_fn(batch)

        self._wm.run_fns = {"wm_update": run}
        self._wm_stage = stage_fn
        self._wm_feed = feed_fn
        self.wm_micro = wm_micro

    # -- one round ---------------------------------------------------------------
    def run_round(self, state, batch):
        """One optimizer step through the pipeline. Returns
        ``(new_state, metrics_dict, wm_out)``."""
        if self._closed:
            raise RuntimeError("executor is closed")
        wm_batches = []
        if self._wm_feed is not None:
            for _ in range(self.wm_micro):
                b = self._wm_feed()
                if b is None:
                    break
                wm_batches.append(b)
        schedules = build_train_schedules(self.n_micro, len(wm_batches))

        self._mailbox.put("host:policy:state", state)
        for m, mb in enumerate(host_microbatches(batch, self.n_micro)):
            self._mailbox.put(f"host:policy:micro{m}", mb)
        for m, wb in enumerate(wm_batches):
            self._mailbox.put(f"host:wm:micro{m}", wb)

        t0 = time.perf_counter()
        self._policy.submit(schedules["policy"])
        self._wm.submit(schedules["wm"])
        self._policy.wait()
        self._wm.wait()
        wall = max(time.perf_counter() - t0, 1e-9)

        new_state = self._mailbox.take("pipe:policy:state", timeout=1.0)
        metrics = self._mailbox.take("pipe:policy:metrics", timeout=1.0)
        wm_out = (self._mailbox.take("pipe:wm:out", timeout=1.0)
                  if wm_batches else None)

        self.rounds += 1
        self.last_bubble = {
            s.name: max(0.0, 1.0 - s.busy_s / wall)
            for s in (self._policy, self._wm)
            if s is self._policy or wm_batches
        }
        if self.metrics is not None:
            for frac in self.last_bubble.values():
                self.metrics.observe("pipeline_bubble_frac", frac)
        if _tel.RECORDING:
            _tel.instant("pipeline.round", cat="train",
                         args={"round": self.rounds, "wall_s": wall,
                               **{f"bubble_{k}": v
                                  for k, v in self.last_bubble.items()}})
        return new_state, metrics, wm_out

    @property
    def peak_grad_bytes(self) -> int:
        return self._policy.peak_grad_bytes

    @property
    def peak_live_bytes(self) -> Dict[str, int]:
        return {"policy": self._policy.peak_live_bytes,
                "wm": self._wm.peak_live_bytes}

    def close(self) -> None:
        if not self._closed:
            self._closed = True
            self._policy.close()
            self._wm.close()
