"""Reduce a JAX profiler trace of one run window to device metrics.

Layout of a TPU trace as the profiler writes it (``*.xplane.pb``): the
plane ``/device:TPU:<n>`` has a line ``XLA Modules`` (one event per
program execution) and a line ``XLA Ops`` (one event per HLO instruction,
nested inside its module; a Pallas kernel is a ``custom-call`` whose event
name is the instruction's HLO text). Host planes (``/host:CPU``) carry the
benchmark's ``jax.profiler.TraceAnnotation`` spans. All events are in
nanoseconds from the profile's start, on one clock.

* busy: the union of module intervals on the device, inside the window;
* idle share: 1 - busy / window;
* kernel time: the summed device durations of the ops that are the
  kernel's ``tpu_custom_call`` sites (found by instruction name and result
  type in the compiled programs, see :func:`kernel_sites`);
* breakdown: the ten ops that took most time, and the ten longest idle
  gaps, each named by the benchmark span most host threads were in.

The reduction works on plain tuples (:class:`Trace`), so that a recorded
excerpt can be checked without JAX's reader.
"""
from __future__ import annotations

import base64
import collections
import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."

Interval = Tuple[float, float]            # (start_ns, end_ns)


@dataclasses.dataclass
class Trace:
    """What the reduction reads from one trace, per device."""

    modules: List[Tuple[str, float, float]]        # (name, start, end)
    ops: List[Tuple[str, float, float]]            # (HLO text, start, end)
    spans: List[Tuple[str, str, float, float]]     # (thread, name, start, end)

    def window(self) -> Interval:
        for _, name, s, e in self.spans:
            if name == WINDOW_SPAN:
                return s, e
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")


def load(path: str, device: int = 0) -> Trace:
    """Read an ``.xplane.pb`` with JAX's own reader."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    modules, ops, spans = [], [], []
    dev = pd.find_plane_with_name(f"/device:TPU:{device}")
    if dev is None:
        raise ValueError(f"{path}: no /device:TPU:{device} plane")
    for line in dev.lines:
        target = {"XLA Modules": modules, "XLA Ops": ops}.get(line.name)
        if target is not None:
            target.extend((e.name, e.start_ns, e.end_ns) for e in line.events)
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans.extend((line.name, e.name, e.start_ns, e.end_ns)
                         for e in line.events
                         if e.name.startswith(SPAN_PREFIX))
    return Trace(modules=modules, ops=ops, spans=spans)


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


def label_gap(trace: Trace, gap: Interval) -> str:
    """The benchmark span most host threads were in at the gap's middle
    (the window span itself excluded); ``host`` when none."""
    mid = (gap[0] + gap[1]) / 2
    counts = collections.Counter(
        name for _, name, s, e in trace.spans
        if name != WINDOW_SPAN and s <= mid < e)
    if not counts:
        return "host"
    return counts.most_common(1)[0][0][len(SPAN_PREFIX):]


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


@dataclasses.dataclass
class Summary:
    busy_s: float
    window_s: float
    kernel_s: Dict[str, float]
    device_ops: List[List]
    idle_gaps: List[List]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def summarize(trace: Trace, sites: Optional[Dict[str, str]] = None,
              n: int = 10) -> Summary:
    sites = sites or {}
    busy, window, intervals = busy_ns(trace)
    lo, hi = trace.window()
    longest = sorted(gaps(intervals, lo, hi), key=lambda g: g[0] - g[1])[:n]
    return Summary(
        busy_s=busy * 1e-9, window_s=window * 1e-9,
        kernel_s=kernel_seconds(trace, sites),
        device_ops=top_ops(trace, sites, n),
        idle_gaps=[[label_gap(trace, g), (g[1] - g[0]) * 1e-9]
                   for g in longest])
