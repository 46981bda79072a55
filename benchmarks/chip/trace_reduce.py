"""Reduce a JAX profiler trace of one run window to device metrics.

Layout of a TPU trace as the profiler writes it (``*.xplane.pb``): the
plane ``/device:TPU:<n>`` has a line ``XLA Modules`` (one event per
program execution) and a line ``XLA Ops`` (one event per HLO instruction,
nested inside its module; a Pallas kernel is a ``custom-call`` whose event
name is the instruction's HLO text). Host planes (``/host:CPU``) carry, one
line per thread, the benchmark's ``jax.profiler.TraceAnnotation`` spans
(``bench.<name>``) and the program's own (``repro.<name>``, opened by
``runtime/telemetry.py``). All events are in nanoseconds from the
profile's start, on one clock.

* busy: the union of module intervals on the device, inside the window;
* idle share: 1 - busy / window;
* kernel time: the summed device durations of the ops that are the
  kernel's ``tpu_custom_call`` sites (found by instruction name and result
  type in the compiled programs, see :func:`kernel_sites`);
* scope time: the summed device durations of the leaf ops of each stage
  scope (``jax.named_scope`` of the train step and the inference fn). A
  TPU profile's op events carry no scope, so each op is mapped to its
  scope through the ``op_name`` metadata of the compiled program, by
  instruction key (:func:`scope_sites`);
* idle by span: device-idle time under each program span, and under the
  inference worker's host work around a batch;
* breakdown: the ten ops that took most time, and the ten longest idle
  gaps, each named by the program span innermost over most of the gap on
  the thread that feeds the device, or, where that thread has none, by the
  benchmark span most host threads were in.

The reduction works on plain tuples (:class:`Trace`), so that a recorded
excerpt can be checked without JAX's reader.
"""
from __future__ import annotations

import base64
import bisect
import collections
import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
PROGRAM_PREFIX = "repro."
SCOPES = ("fwd_bwd", "grad_reduce", "optim_update", "prefill", "decode",
          "value_head")
UNSCOPED = "unscoped"
#: program spans that mark the thread which feeds the device
FEEDERS = ("infer.device", "trainer.step")
#: program spans inside which a device program should start
DISPATCHERS = ("infer.device", "trainer.dispatch")
#: the inference worker's host work around a batch
SERVE_HOST = ("infer.collect", "infer.swap", "infer.prepare",
              "infer.resolve")

Interval = Tuple[float, float]            # (start_ns, end_ns)
Span = Tuple[object, str, float, float]   # (thread, name, start, end)


@dataclasses.dataclass
class Trace:
    """What the reduction reads from one trace, per device."""

    modules: List[Tuple[str, float, float]]        # (name, start, end)
    ops: List[Tuple[str, float, float]]            # (HLO text, start, end)
    spans: List[Span]                              # benchmark spans
    program: List[Span] = dataclasses.field(       # program spans, their
        default_factory=list)                      # names without prefix

    def window(self) -> Interval:
        for _, name, s, e in self.spans:
            if name == WINDOW_SPAN:
                return s, e
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")


def load(path: str, device: int = 0) -> Trace:
    """Read an ``.xplane.pb`` with JAX's own reader. Program spans keep
    their thread as the host line's number."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    modules, ops, spans, program = [], [], [], []
    dev = pd.find_plane_with_name(f"/device:TPU:{device}")
    if dev is None:
        raise ValueError(f"{path}: no /device:TPU:{device} plane")
    for line in dev.lines:
        target = {"XLA Modules": modules, "XLA Ops": ops}.get(line.name)
        if target is not None:
            target.extend((e.name, e.start_ns, e.end_ns) for e in line.events)
    thread = 0
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            thread += 1
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    spans.append((line.name, e.name, e.start_ns, e.end_ns))
                elif e.name.startswith(PROGRAM_PREFIX):
                    program.append((thread, e.name[len(PROGRAM_PREFIX):],
                                    e.start_ns, e.end_ns))
    return Trace(modules=modules, ops=ops, spans=spans, program=program)


def union(intervals: Iterable[Interval], lo: float, hi: float
          ) -> List[Interval]:
    """Sorted disjoint union of the intervals, clipped to [lo, hi]."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def intersect(a: Sequence[Interval], b: Sequence[Interval]
              ) -> List[Interval]:
    """Intersection of two sorted disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _seconds(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals) * 1e-9


def busy_ns(trace: Trace) -> Tuple[float, float, List[Interval]]:
    """(busy ns, window ns, busy intervals) inside the window."""
    lo, hi = trace.window()
    busy = union(((s, e) for _, s, e in trace.modules), lo, hi)
    return sum(e - s for s, e in busy), hi - lo, busy


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def vote_label(trace: Trace, gap: Interval) -> str:
    """The benchmark span most host threads were in at the gap's middle
    (the window span itself excluded); ``host`` when none."""
    mid = (gap[0] + gap[1]) / 2
    counts = collections.Counter(
        name for _, name, s, e in trace.spans
        if name != WINDOW_SPAN and s <= mid < e)
    if not counts:
        return "host"
    return counts.most_common(1)[0][0][len(SPAN_PREFIX):]


def label_gap(trace: Trace, gap: Interval) -> str:
    """The program span innermost over most of the gap on a thread that
    feeds the device (at each instant, the latest-started span that holds
    it); :func:`vote_label` where no such span overlaps the gap. A gap
    often runs from one step's tail over the caller's loop into the next
    dispatch, so its middle alone may fall between spans."""
    feeders = {t for t, name, _, _ in trace.program if name in FEEDERS}
    lo, hi = gap
    inside = [(s, e, name) for t, name, s, e in trace.program
              if t in feeders and s < hi and e > lo]
    if not inside:
        return vote_label(trace, gap)
    cuts = sorted({lo, hi, *(min(max(x, lo), hi)
                             for s, e, _ in inside for x in (s, e))})
    held: Dict[str, float] = collections.defaultdict(float)
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        cover = [(s, name) for s, e, name in inside if s <= mid < e]
        if cover:
            held[max(cover)[1]] += b - a
    return max(held.items(), key=lambda kv: kv[1])[0]


def op_key(hlo_text: str) -> str:
    """``%name = <result type>`` of an HLO instruction: unique across the
    programs of one run."""
    head = hlo_text.split(" custom-call(", 1)[0].strip()
    return head[len("ROOT "):] if head.startswith("ROOT ") else head


def kernel_sites(compiled_text: str) -> Dict[str, str]:
    """{op key: Pallas kernel function name} of every ``tpu_custom_call``
    in a compiled program's HLO text (the Mosaic body carries the kernel's
    function name)."""
    sites = {}
    for line in compiled_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        body = re.search(r'"body":"([^"]+)"', line)
        if body is None:
            continue
        raw = base64.b64decode(body.group(1))
        names = sorted(set(re.findall(rb"(_\w*kernel\w*)", raw)))
        if names:
            sites[op_key(line)] = names[0].decode()
    return sites


def instr_key(hlo_text: str) -> str:
    """``%name = <result type>`` of any HLO instruction (a tuple type is
    read to its closing parenthesis)."""
    text = hlo_text.strip()
    if text.startswith("ROOT "):
        text = text[len("ROOT "):]
    name, _, rest = text.partition(" = ")
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                return f"{name} = {rest[:i + 1]}"
    return f"{name} = {rest.split(' ', 1)[0]}"


_OP_NAME = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')


def scope_of(op_name: str) -> str:
    """The innermost stage scope named in an op's name stack."""
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return UNSCOPED


def scope_sites(compiled_text: str) -> Dict[str, str]:
    """{instruction key: stage scope} of every instruction of a compiled
    program's HLO text that carries an ``op_name``."""
    sites = {}
    for line in compiled_text.splitlines():
        m = _OP_NAME.search(line)
        if m is not None and " = " in line:
            sites[instr_key(line)] = scope_of(m.group(1))
    return sites


def kernel_seconds(trace: Trace, sites: Dict[str, str]) -> Dict[str, float]:
    """{kernel name: device seconds} of the ops inside the window."""
    lo, hi = trace.window()
    out: Dict[str, float] = collections.defaultdict(float)
    for text, s, e in trace.ops:
        if "tpu_custom_call" not in text or s < lo or s >= hi:
            continue
        name = sites.get(op_key(text))
        if name is not None:
            out[name] += (e - s) * 1e-9
    return dict(out)


def leaf_ops(ops: List[Tuple[str, float, float]]
             ) -> List[Tuple[str, float, float]]:
    """The ops that contain no other op (a while loop's event spans its
    body's ops; only the body is kept)."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    return [o for i, o in enumerate(ops)
            if i + 1 == len(ops) or ops[i + 1][1] >= o[2]]


def top_ops(trace: Trace, sites: Dict[str, str], n: int = 10
            ) -> List[List]:
    """The ``n`` leaf ops with the most device time in the window, named
    by instruction (kernels by their function name)."""
    lo, hi = trace.window()
    tot: Dict[str, float] = collections.defaultdict(float)
    for text, s, e in leaf_ops(trace.ops):
        if s < lo or s >= hi:
            continue
        key = op_key(text)
        name = sites.get(key) or key.split(" = ")[0].lstrip("%")
        tot[name] += (e - s) * 1e-9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def scope_seconds(trace: Trace, scopes: Dict[str, str],
                  sites: Dict[str, str], n: int = 10
                  ) -> Tuple[Dict[str, float], List[List]]:
    """({stage scope: device seconds} of the leaf ops inside the window,
    the ``n`` largest unscoped leaf ops)."""
    lo, hi = trace.window()
    scope_s: Dict[str, float] = collections.defaultdict(float)
    unscoped: Dict[str, float] = collections.defaultdict(float)
    for text, s, e in leaf_ops(trace.ops):
        if s < lo or s >= hi:
            continue
        key = instr_key(text)
        scope = scopes.get(key, UNSCOPED)
        scope_s[scope] += (e - s) * 1e-9
        if scope == UNSCOPED:
            name = sites.get(op_key(text)) or key.split(" = ")[0]
            unscoped[name.lstrip("%")] += (e - s) * 1e-9
    return dict(scope_s), [[k, v] for k, v in sorted(
        unscoped.items(), key=lambda kv: -kv[1])[:n]]


def on_clock(modules: List[Tuple[str, float, float]],
             iv: List[Interval]) -> Dict[str, float]:
    """How the spans ``iv`` that launch device programs sit against them:
    the share that holds the start of a program, and the offset (ms) from
    a span's start to the start of the program that overlaps it most
    (min, median, max). A program cannot start before the span that
    launches it, so a negative offset is the profile's alignment of the
    device clock to the host's."""
    mods = sorted((s, e) for _, s, e in modules)
    starts = [s for s, _ in mods]
    held, offsets = 0, []
    for s, e in iv:
        held += bisect.bisect_left(starts, e) > bisect.bisect_left(starts, s)
        best = None
        for ms, me in mods[max(bisect.bisect_left(starts, s) - 1, 0):
                           bisect.bisect_left(starts, e)]:
            over = min(me, e) - max(ms, s)
            if over > 0 and (best is None or over > best[0]):
                best = (over, ms)
        if best is not None:
            offsets.append((best[1] - s) * 1e-6)
    offsets.sort()
    out = {"share": held / len(iv)}
    if offsets:
        out["offset_ms"] = [offsets[0], offsets[len(offsets) // 2],
                            offsets[-1]]
    return out


@dataclasses.dataclass
class Summary:
    busy_s: float
    window_s: float
    kernel_s: Dict[str, float]
    device_ops: List[List]
    idle_gaps: List[List]
    span_count: Dict[str, int]            # program spans that start in it
    scope_s: Dict[str, float]             # device s of leaf ops per scope
    unscoped_ops: List[List]              # the largest unscoped leaf ops
    idle_in_s: Dict[str, float]           # idle under each program span
    idle_attributed_s: float              # idle under any program span
    serve_host_idle_s: Optional[float]    # idle under SERVE_HOST
    dispatch_on_clock: Dict[str, Dict]    # see :func:`on_clock`

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def summarize(trace: Trace, sites: Optional[Dict[str, str]] = None,
              scopes: Optional[Dict[str, str]] = None,
              n: int = 10) -> Summary:
    """``sites`` from :func:`kernel_sites`, ``scopes`` from
    :func:`scope_sites`, of the programs the window ran."""
    sites = sites or {}
    busy, window, intervals = busy_ns(trace)
    lo, hi = trace.window()
    idle = gaps(intervals, lo, hi)
    spans = trace.program

    def idle_in(names) -> float:
        return _seconds(intersect(idle, union(
            ((s, e) for _, name, s, e in spans if name in names), lo, hi)))

    names = sorted({name for _, name, _, _ in spans})
    host = [name for name in SERVE_HOST if name in names]
    count = collections.Counter(name for _, name, s, _ in spans
                                if lo <= s < hi)
    scope_s, unscoped = scope_seconds(trace, scopes or {}, sites, n)
    clock = {}
    for name in DISPATCHERS:
        iv = [(s, e) for _, n_, s, e in spans if n_ == name and lo <= s < hi]
        if iv:
            clock[name] = on_clock(trace.modules, iv)
    longest = sorted(idle, key=lambda g: g[0] - g[1])[:n]
    return Summary(
        busy_s=busy * 1e-9, window_s=window * 1e-9,
        kernel_s=kernel_seconds(trace, sites),
        device_ops=top_ops(trace, sites, n),
        idle_gaps=[[label_gap(trace, g), (g[1] - g[0]) * 1e-9]
                   for g in longest],
        span_count=dict(sorted(count.items())), scope_s=scope_s,
        unscoped_ops=unscoped,
        idle_in_s={name: idle_in({name}) for name in names},
        idle_attributed_s=idle_in(set(names)),
        serve_host_idle_s=idle_in(set(host)) if host else None,
        dispatch_on_clock=clock)
