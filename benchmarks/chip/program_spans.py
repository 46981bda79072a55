"""Read the program's own spans and stage scopes out of a run's profile.

    python3 benchmarks/chip/program_spans.py --workload <name> --seed <n> \\
        --seconds <s>

runs the cell once as ``run.py --trace 1`` does, and prints, after
``run.py``'s own lines, one line ``program spans: {...}`` with what this
module reads from the same profile:

* the program's spans: host events named ``repro.<name>``, which
  ``runtime/telemetry.py`` opens as ``jax.profiler.TraceAnnotation``\\ s;
* the stage scopes: ``jax.named_scope``\\ s of the train step and the
  inference fn. A TPU profile's op events carry no scope (their stats are
  the device offset and duration), so each op is mapped to its scope
  through the ``op_name`` metadata of the compiled program, by the same
  instruction key that ``trace_reduce.kernel_sites`` matches;
* the queue wait of the inference tier's requests over the window (its
  ``queue_wait_s`` histogram).

From those it computes the per-layer numbers that the harness cannot read
yet: device-idle time inside each span, device time per scope, spans per
window, idle gaps labelled by the program span innermost over most of
the gap on the thread that feeds the device, and how the
``infer.device`` / ``trainer.dispatch`` spans sit against the device
programs they launch (the profile's clock alignment).
The reduction works on plain tuples, so a recorded excerpt can be checked
without JAX's reader.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import json
import os
import pathlib
import re
import sys
from typing import Dict, List, Optional, Sequence, Tuple

import trace_reduce as tr

PREFIX = "repro."
SCOPES = ("fwd_bwd", "grad_reduce", "optim_update", "prefill", "decode",
          "value_head")
UNSCOPED = "unscoped"
#: spans that mark the thread which feeds the device
FEEDERS = ("infer.device", "trainer.step")
#: spans inside which a device program should start
DISPATCHERS = ("infer.device", "trainer.dispatch")
#: the inference worker's host work around a batch
SERVE_HOST = ("infer.collect", "infer.swap", "infer.prepare",
              "infer.resolve")

Span = Tuple[int, str, float, float]      # (thread, name, start, end)


# ---------------------------------------------------------------------------
# reading
# ---------------------------------------------------------------------------

def load_spans(path: str) -> List[Span]:
    """The ``repro.`` host events of an ``.xplane.pb``, each thread (host
    line) numbered, names without the prefix."""
    from jax.profiler import ProfileData
    out, thread = [], 0
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            thread += 1
            out.extend((thread, e.name[len(PREFIX):], e.start_ns, e.end_ns)
                       for e in line.events if e.name.startswith(PREFIX))
    return out


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


# ---------------------------------------------------------------------------
# reduction
# ---------------------------------------------------------------------------

def intersect(a: Sequence[tr.Interval], b: Sequence[tr.Interval]
              ) -> List[tr.Interval]:
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


def _seconds(intervals: Sequence[tr.Interval]) -> float:
    return sum(e - s for s, e in intervals) * 1e-9


def _feeder_threads(spans: List[Span]) -> set:
    return {t for t, name, _, _ in spans if name in FEEDERS}


def label_gap(trace: tr.Trace, spans: List[Span], gap: tr.Interval) -> str:
    """The program span innermost over most of the gap on a thread that
    feeds the device (at each instant, the latest-started span that holds
    it); the benchmark's own label where no such span overlaps the gap.
    A gap often runs from one step's tail over the caller's loop into the
    next dispatch, so its middle alone may fall between spans."""
    feeders = _feeder_threads(spans)
    lo, hi = gap
    inside = [(s, e, name) for t, name, s, e in spans
              if t in feeders and s < hi and e > lo]
    if not inside:
        return tr.label_gap(trace, gap)
    cuts = sorted({lo, hi, *(min(max(x, lo), hi)
                             for s, e, _ in inside for x in (s, e))})
    held: Dict[str, float] = collections.defaultdict(float)
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        cover = [(s, name) for s, e, name in inside if s <= mid < e]
        if cover:
            held[max(cover)[1]] += b - a
    return max(held.items(), key=lambda kv: kv[1])[0]


def on_clock(modules: List[Tuple[str, float, float]],
             iv: List[tr.Interval]) -> Dict[str, float]:
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
class Reading:
    span_count: Dict[str, int]            # spans that start in the window
    scope_s: Dict[str, float]             # device s of leaf ops per scope
    unscoped_ops: List[List]              # the largest unscoped leaf ops
    idle_s: float
    idle_in_s: Dict[str, float]           # idle overlapping each span name
    idle_attributed_s: float              # idle overlapping any span
    serve_host_idle_s: Optional[float]    # idle overlapping SERVE_HOST
    idle_gaps: List[List]                 # the longest gaps, labelled
    dispatch_on_clock: Dict[str, Dict]    # see :func:`on_clock`


def reduce(trace: tr.Trace, spans: List[Span], scopes: Dict[str, str],
           sites: Optional[Dict[str, str]] = None, n: int = 10) -> Reading:
    """``scopes`` from :func:`scope_sites`, ``sites`` (kernel names for
    the unscoped ops) from ``trace_reduce.kernel_sites``."""
    sites = sites or {}
    lo, hi = trace.window()
    _, _, busy = tr.busy_ns(trace)
    idle = tr.gaps(busy, lo, hi)

    def idle_in(names) -> float:
        return _seconds(intersect(idle, tr.union(
            ((s, e) for _, name, s, e in spans if name in names), lo, hi)))

    names = sorted({name for _, name, _, _ in spans})
    host = [name for name in SERVE_HOST if name in names]
    count = collections.Counter(name for _, name, s, _ in spans
                                if lo <= s < hi)

    scope_s: Dict[str, float] = collections.defaultdict(float)
    unscoped: Dict[str, float] = collections.defaultdict(float)
    for text, s, e in tr.leaf_ops(trace.ops):
        if s < lo or s >= hi:
            continue
        key = instr_key(text)
        scope = scopes.get(key, UNSCOPED)
        scope_s[scope] += (e - s) * 1e-9
        if scope == UNSCOPED:
            name = sites.get(tr.op_key(text)) or key.split(" = ")[0]
            unscoped[name.lstrip("%")] += (e - s) * 1e-9

    clock = {}
    for name in DISPATCHERS:
        iv = [(s, e) for _, n_, s, e in spans if n_ == name and lo <= s < hi]
        if iv:
            clock[name] = on_clock(trace.modules, iv)

    longest = sorted(idle, key=lambda g: g[0] - g[1])[:n]
    return Reading(
        span_count=dict(sorted(count.items())), scope_s=dict(scope_s),
        unscoped_ops=[[k, v] for k, v in sorted(
            unscoped.items(), key=lambda kv: -kv[1])[:n]],
        idle_s=_seconds(idle),
        idle_in_s={name: idle_in({name}) for name in names},
        idle_attributed_s=idle_in(set(names)),
        serve_host_idle_s=idle_in(set(host)) if host else None,
        idle_gaps=[[label_gap(trace, spans, g), (g[1] - g[0]) * 1e-9]
                   for g in longest],
        dispatch_on_clock=clock)


def metrics(r: Reading, window_s: float,
            counters: Dict[str, float]) -> Dict[str, Optional[float]]:
    """The per-layer numbers, each ``None`` where what it reads is
    absent (a program without these spans, scopes or counters)."""
    def per(scope, span):
        k = r.span_count.get(span, 0)
        t = r.scope_s.get(scope)
        return None if not k or t is None else 1e3 * t / k

    n = counters.get("queue_wait_count")
    return {
        "infer.host_idle.serve": (
            None if r.serve_host_idle_s is None
            else 100.0 * r.serve_host_idle_s / window_s),
        "infer.queue_wait_ms.serve": (
            1e3 * counters["queue_wait_sum"] / n if n else None),
        "infer.prefill_ms.serve": per("prefill", "infer.device"),
        "infer.decode_ms.serve": per("decode", "infer.device"),
        "train.fwd_bwd_ms.train": per("fwd_bwd", "trainer.step"),
        "train.grad_reduce_ms.train": per("grad_reduce", "trainer.step"),
        "train.optim_ms.train": per("optim_update", "trainer.step"),
    }


# ---------------------------------------------------------------------------
# one run of a cell, read with the harness's own runner
# ---------------------------------------------------------------------------

class _Capture:
    """Wraps the harness's runner for one run: keeps the compiled texts
    it reads kernel sites from, reduces the profile before the harness
    deletes it, and adds the queue-wait histogram to the serve counters."""

    def __init__(self, drive):
        self.texts: List[str] = []
        self.reading: Optional[Reading] = None
        self.outcome = None
        kernel_sites, summary = tr.kernel_sites, drive._Tracer.summary
        counters = drive._serve_counters

        def keep_text(text):
            self.texts.append(text)
            return kernel_sites(text)

        def reduce_first(tracer, sites):
            path = glob.glob(os.path.join(tracer.dir, "**", "*.xplane.pb"),
                             recursive=True)[0]
            scopes: Dict[str, str] = {}
            for text in self.texts:
                scopes.update(scope_sites(text))
            self.reading = reduce(tr.load(path), load_spans(path), scopes,
                                  sites)
            return summary(tracer, sites)

        def with_queue_wait(inf):
            h = inf.metrics.hist("queue_wait_s") or {"count": 0, "sum": 0.0}
            return {**counters(inf), "queue_wait_count": h["count"],
                    "queue_wait_sum": h["sum"]}

        def keep_outcome(runner):
            def run(ctx):
                self.outcome = runner(ctx)
                return self.outcome
            return run

        tr.kernel_sites = keep_text
        drive._Tracer.summary = reduce_first
        drive._serve_counters = with_queue_wait
        drive.RUNNERS = {k: keep_outcome(v) for k, v in drive.RUNNERS.items()}

    def report(self) -> Dict:
        o, r = self.outcome, self.reading
        out = {"metrics": metrics(r, o.window_s, o.counters),
               "steps_or_batches": o.counters.get("steps",
                                                  o.counters.get("batches")),
               "busy_s": o.summary.busy_s, "window_s": o.summary.window_s}
        out.update(dataclasses.asdict(r))
        return out


def main(argv=None) -> int:
    here = pathlib.Path(__file__).resolve().parent
    sys.path[:0] = [str(here.parents[1] / "src"), str(here)]
    import drive
    import run
    argv = list(sys.argv[1:] if argv is None else argv) + ["--trace", "1"]
    cap = _Capture(drive)
    rc = run.main(argv)
    if rc == 0:
        print("program spans: " + json.dumps(cap.report()), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
