"""Backpressure policies under saturation (ROADMAP "Backpressure policies
under load"): sweep drop_oldest / drop_newest / block on a FifoChannel with
producers deliberately outrunning the consumer, and measure the trade each
policy makes —

  * **drop_oldest** (paper default) — producers never block, throughput is
    maximal, and staleness stays BOUNDED (the queue holds only the newest
    ``capacity`` items);
  * **drop_newest** — queued data wins, so accepted items are the OLDEST:
    staleness at pop grows with the run;
  * **block** — producer throughput is clamped to the consumer's rate
    (accept rate ≈ pop rate), buying low drop counts with idle producers.

A second section measures the WIRE data plane: the same producer payload
pushed through a real ``TransportServer`` + ``SocketChannel`` pair, one
item per round-trip (``put``) vs one codec blob per flush (``put_many``)
— the framing/RTT overhead the batched endpoint exists to amortize.

The STREAMING section (ISSUE 5) measures the pipelined data plane with
the producer in a real spawned subprocess (two interpreters — in-process
threads would serialize encode/decode on one GIL and hide the overlap
pipelining buys):

  * ``batched``        — PR 4's path: one blocking ``put_many`` RPC per
    flush, the producer idles an RTT + server decode per flush;
  * ``pipelined``      — ``PutStream``: fire-and-forget frames, windowed
    acks; producer encode overlaps server decode;
  * ``pipelined_ring`` — the same stream with payloads through the
    persistent SHM ring (``ShmRingChannel``): zero per-message segment
    churn, blobs encoded straight into the ring reservation;

plus a POP-latency comparison of the two out-of-band reply planes:
per-message SHM segments (create/attach/unlink each pop) vs the
persistent ring (one memcpy in, one out).

The RECOVERY section (ISSUE 6) prices the resilient control plane:

  * ``journal_put_ratio`` — streaming-put throughput with the hosted
    channel journaled vs plain, interleaved best-of-2. The journal's
    promise is <5% steady-state cost (one crc32 per flush, the wire
    encoding reused verbatim, writes group-committed at ack
    boundaries), asserted on ≥2-CPU hosts so the cheap-journal claim
    cannot silently rot (on one CPU the server-side cost serializes
    against the producer and the ratio measures core starvation);
  * ``t_recover_s`` — time-to-first-pop of a replacement server:
    journal resume + state replay + serve, the window workers spend
    redialing after a parent crash (the gated stability signal).

The TELEMETRY section (ISSUE 8) prices the observability plane on the
same spawned-producer put path:

  * ``off`` — REPRO_TRACE unset: the child asserts the recorder is off
    and that nothing was buffered (recording is decided once at import,
    so the off path carries one ``RECORDING`` check and a span that, in
    this jax-free child, is a null context);
  * ``on``  — REPRO_TRACE=1: every flush runs inside a ``rollout.put``
    span with a fresh trace id riding the frame headers — the full
    per-flush cost a traced rollout worker pays.

The span recorder is an append to a preallocated per-thread ring, so the
claim is <5% put-path overhead (``on_over_off_throughput >= 0.95``,
asserted on ≥2-CPU hosts); the per-item ``put_item_*_ms`` keys are
perf-gated so the hot path cannot silently grow a step-function cost.

Channel-level only — no model, no jax — so the numbers isolate the data
plane. Emits ``BENCH_backpressure.json`` (registered with the perf gate:
the committed baseline under ``experiments/bench`` is compared by CI; the
fixed-duration ``t_wall_s`` keys are the gated stability signal).
"""
from __future__ import annotations

import multiprocessing
import os
import threading
import time
from typing import Dict, List

import numpy as np

from benchmarks.common import save
from repro.runtime.experience import BACKPRESSURE_POLICIES, FifoChannel


def _drive(policy: str, *, duration_s: float, capacity: int = 64,
           producers: int = 4, produce_hz: float = 200.0,
           consume_hz: float = 25.0, batch: int = 8) -> Dict:
    """Producers push stamped items at ``producers * produce_hz``; one
    consumer pops ``batch`` at ``consume_hz`` — a deliberate ~order-of-
    magnitude oversubscription."""
    chan = FifoChannel(capacity, policy=policy, block_timeout=0.05)
    stop = threading.Event()
    accepted = [0] * producers
    offered = [0] * producers
    ages: List[float] = []
    depths: List[int] = []
    popped = [0]

    def producer(idx: int) -> None:
        period = 1.0 / produce_hz
        while not stop.is_set():
            offered[idx] += 1
            if chan.put({"t": time.monotonic(), "idx": idx}):
                accepted[idx] += 1
            time.sleep(period)

    def consumer() -> None:
        period = 1.0 / consume_hz
        while not stop.is_set():
            got = chan.pop_batch(min(batch, max(len(chan), 1)),
                                 timeout=period)
            now = time.monotonic()
            if got:
                popped[0] += len(got)
                ages.extend(now - item["t"] for item in got)
            depths.append(len(chan))
            time.sleep(period)

    threads = [threading.Thread(target=producer, args=(i,), daemon=True)
               for i in range(producers)]
    threads.append(threading.Thread(target=consumer, daemon=True))
    t0 = time.monotonic()
    for t in threads:
        t.start()
    time.sleep(duration_s)
    stop.set()
    for t in threads:
        t.join(timeout=2.0)
    wall = time.monotonic() - t0

    ages_a = np.asarray(ages) if ages else np.zeros(1)
    return {
        "policy": policy,
        "t_wall_s": round(wall, 3),
        "capacity": capacity,
        "producers": producers,
        "offered": int(sum(offered)),
        "accepted": int(sum(accepted)),
        "rejected": int(sum(offered) - sum(accepted)),
        "dropped": int(chan.total_dropped),
        "popped": int(popped[0]),
        "accept_rate": round(sum(accepted) / max(sum(offered), 1), 4),
        "accepted_per_s": round(sum(accepted) / wall, 1),
        "staleness_mean": round(float(ages_a.mean()), 4),
        "staleness_p95": round(float(np.percentile(ages_a, 95)), 4),
        "depth_mean": round(float(np.mean(depths)) if depths else 0.0, 2),
    }


def _drive_wire(batched: bool, *, duration_s: float, item_floats: int = 512,
                flush: int = 16) -> Dict:
    """One producer pushes fixed-size items over a real socket transport;
    ``batched`` flushes ``flush`` items per ``put_many`` round-trip,
    otherwise one ``put`` RPC per item. A drain thread keeps the hosted
    channel from saturating so the number isolates wire overhead."""
    from repro.runtime.transport import SocketChannel, TransportServer

    server = TransportServer()
    local = FifoChannel(8192, policy="drop_oldest")
    server.add_channel("bench", local)
    server.start()
    remote = SocketChannel(server.address, "bench")
    payload = {"x": np.zeros(item_floats, np.float32),
               "meta": {"t": 0.0, "idx": 0}}
    stop = threading.Event()

    def drain() -> None:
        while not stop.is_set():
            local.pop_batch(max(min(len(local), 256), 1), timeout=0.02)

    drainer = threading.Thread(target=drain, daemon=True)
    drainer.start()
    sent = accepted = 0
    t0 = time.monotonic()
    while time.monotonic() - t0 < duration_s:
        if batched:
            verdicts = remote.put_many([payload] * flush)
            sent += flush
            accepted += sum(verdicts)
        else:
            accepted += bool(remote.put(payload))
            sent += 1
    wall = time.monotonic() - t0
    stop.set()
    drainer.join(timeout=2.0)
    remote.close()
    server.stop()
    server.join()
    rpcs = int(server.metrics.counter("requests"))
    return {
        "mode": "batched" if batched else "single",
        "t_wall_s": round(wall, 3),
        "flush": flush if batched else 1,
        "item_bytes": int(payload["x"].nbytes),
        "items_sent": sent,
        "items_accepted": accepted,
        "rpcs": rpcs,
        "items_per_rpc": round(sent / max(rpcs, 1), 2),
        "items_per_sec": round(sent / wall, 1),
    }


def _stream_child(mode: str, address, duration_s: float, flush: int,
                  item_floats: int, window: int, q) -> None:
    """Subprocess producer body (spawn target): hammer one flush shape at
    the server for ``duration_s`` through the selected put path, then
    report counts through ``q``."""
    from repro.runtime.transport import (PutStream, ShmRingChannel,
                                         SocketChannel)

    payload = [{"x": np.zeros(item_floats, np.float32),
                "meta": {"t": 0.0, "idx": 0}}] * flush
    stream = chan = None
    if mode == "batched":
        chan = SocketChannel(tuple(address), "bench")
        put = lambda: sum(chan.put_many(payload))          # noqa: E731
    elif mode == "pipelined":
        stream = PutStream(tuple(address), "bench", window=window)
        put = lambda: sum(stream.put_many(payload))        # noqa: E731
    elif mode == "pipelined_ring":
        chan = ShmRingChannel(tuple(address), "bench", put_window=window,
                              ring_bytes=32 << 20)
        stream = chan._put_stream()
        put = lambda: sum(chan.put_many(payload))          # noqa: E731
    else:
        raise ValueError(mode)
    sent = accepted = 0
    t0 = time.monotonic()
    while time.monotonic() - t0 < duration_s:
        accepted += put()
        sent += flush
    if stream is not None:
        # throughput counts only ACKED items over the wall including the
        # drain — fire-and-forget does not get credit for unacked frames
        stream.flush(30.0)
        accepted = int(stream.stats()["items_accepted"])
    wall = time.monotonic() - t0
    q.put({"sent": sent, "accepted": accepted, "wall": wall,
           "frames": (stream.stats()["frames_sent"] if stream is not None
                      else sent // flush)})
    if chan is not None:
        chan.close()
    elif stream is not None:
        stream.close()


def _drive_stream(mode: str, *, duration_s: float, item_floats: int = 512,
                  flush: int = 4, window: int = 64) -> Dict:
    """One cross-process producer run of the streaming benchmark.

    ``flush=4`` is the realistic shape: a 30-step episode at segment
    horizon 8 flushes 4 segments — small flushes are exactly where the
    per-RPC round-trip dominates and pipelining pays.
    """
    from repro.runtime.transport import TransportServer

    server = TransportServer()
    local = FifoChannel(16384, policy="drop_oldest")
    server.add_channel("bench", local)
    server.start()
    stop = threading.Event()

    def drain() -> None:
        while not stop.is_set():
            local.pop_many(1024, timeout=0.02)

    drainer = threading.Thread(target=drain, daemon=True)
    drainer.start()
    ctx = multiprocessing.get_context("spawn")
    q = ctx.Queue()
    proc = ctx.Process(target=_stream_child,
                       args=(mode, server.address, duration_s, flush,
                             item_floats, window, q))
    proc.start()
    got = q.get(timeout=120.0)
    proc.join(timeout=30.0)
    if proc.is_alive():
        proc.kill()
    stop.set()
    drainer.join(timeout=2.0)
    server.stop()
    server.join()
    item_bytes = item_floats * 4
    return {
        "mode": mode,
        "t_wall_s": round(got["wall"], 3),
        "flush": flush,
        "window": window if mode != "batched" else 0,
        "item_bytes": item_bytes,
        "items_sent": int(got["sent"]),
        "items_accepted": int(got["accepted"]),
        "frames": int(got["frames"]),
        "items_per_sec": round(got["accepted"] / got["wall"], 1),
    }


def _telemetry_child(traced: bool, address, duration_s: float, flush: int,
                     item_floats: int, window: int, q) -> None:
    """Spawned producer for the telemetry section: the put loop of a
    traced rollout worker (per-flush span + fresh trace id on the wire)
    vs the same loop with the recorder off. Asserts the gating contract
    inside the fresh interpreter: REPRO_TRACE off means nothing is
    recorded and no ring is registered."""
    from repro.runtime import telemetry as tel
    from repro.runtime.transport import PutStream

    assert tel.RECORDING == traced, (
        "telemetry gating broken: recording "
        + ("off with" if traced else "on without") + " REPRO_TRACE")
    payload = [{"x": np.zeros(item_floats, np.float32),
                "meta": {"t": 0.0, "idx": 0}}] * flush
    stream = PutStream(tuple(address), "bench", window=window)
    sent = 0
    t0 = time.monotonic()
    while time.monotonic() - t0 < duration_s:
        with tel.span("rollout.put", cat="bench",
                      trace=tel.new_id() if tel.RECORDING else None,
                      flow="start"):
            stream.put_many(payload)
        sent += flush
    stream.flush(30.0)
    accepted = int(stream.stats()["items_accepted"])
    wall = time.monotonic() - t0
    assert bool(tel._bufs) == traced, "untraced producer registered a ring"
    q.put({"sent": sent, "accepted": accepted, "wall": wall,
           "events": len(tel.drain())})
    stream.close()


def _drive_telemetry(traced: bool, *, duration_s: float,
                     item_floats: int = 512, flush: int = 4,
                     window: int = 64) -> Dict:
    """One spawned-producer run of the tracing-overhead comparison; the
    REPRO_TRACE env the child inherits is flipped around the spawn (the
    parent server stays untraced both ways, so the delta isolates the
    PRODUCER-side cost a rollout worker pays)."""
    from repro.runtime.transport import TransportServer

    server = TransportServer()
    local = FifoChannel(1 << 14, policy="drop_oldest")
    server.add_channel("bench", local)
    server.start()
    stop = threading.Event()

    def drain() -> None:
        while not stop.is_set():
            local.pop_many(1024, timeout=0.02)

    drainer = threading.Thread(target=drain, daemon=True)
    drainer.start()
    ctx = multiprocessing.get_context("spawn")
    q = ctx.Queue()
    prior = os.environ.pop("REPRO_TRACE", None)
    if traced:
        os.environ["REPRO_TRACE"] = "1"
    try:
        proc = ctx.Process(target=_telemetry_child,
                           args=(traced, server.address, duration_s, flush,
                                 item_floats, window, q))
        proc.start()
    finally:
        os.environ.pop("REPRO_TRACE", None)
        if prior is not None:
            os.environ["REPRO_TRACE"] = prior
    got = q.get(timeout=120.0)
    proc.join(timeout=30.0)
    if proc.is_alive():
        proc.kill()
    stop.set()
    drainer.join(timeout=2.0)
    server.stop()
    server.join()
    return {
        "tracing": "on" if traced else "off",
        "t_wall_s": round(got["wall"], 3),
        "flush": flush,
        "window": window,
        "items_sent": int(got["sent"]),
        "items_accepted": int(got["accepted"]),
        "trace_events": int(got["events"]),
        "items_per_sec": round(got["accepted"] / got["wall"], 1),
    }


def _drive_pop(ring: bool, *, pops: int, batch: int = 16,
               item_floats: int = 4096) -> Dict:
    """Per-pop RPC latency of the two out-of-band reply planes: the
    channel is pre-filled, so every pop is purely data-plane work.

    256 KiB blobs (16 × 16 KiB segments) sit well above the SHM
    threshold but below memcpy dominance — the regime where the segment
    plane's per-message ``shm_open``/``mmap``/``unlink`` actually shows
    (at multi-MB blobs both planes converge on pure copy bandwidth)."""
    from repro.runtime.transport import (ShmChannel, ShmRingChannel,
                                         TransportServer)

    server = TransportServer()
    local = FifoChannel((pops + 8) * batch, policy="drop_oldest")
    server.add_channel("bench", local)
    server.start()
    item = {"x": np.zeros(item_floats, np.float32)}
    local.put_many([item] * ((pops + 4) * batch))
    if ring:
        chan = ShmRingChannel(server.address, "bench",
                              ring_bytes=64 << 20, put_window=1)
    else:
        chan = ShmChannel(server.address, "bench")
    lat = []
    for i in range(pops + 4):
        t0 = time.perf_counter()
        got = chan.pop_many(batch, timeout=10.0)
        dt = time.perf_counter() - t0
        assert got is not None and len(got) == batch
        if i >= 4:                     # warmup excluded
            lat.append(dt)
    chan.close()
    server.stop()
    server.join()
    lat_a = np.asarray(lat)
    counters = server.metrics.snapshot()["counters"]
    return {
        "plane": "ring" if ring else "segment",
        "batch": batch,
        "blob_bytes_approx": int(item_floats * 4 * batch),
        # the MEDIAN is the gated latency signal (`_ms` suffix): robust
        # to scheduler spikes on shared runners. Mean/p95 are reported
        # for the tail story but deliberately NOT gate-suffixed — one
        # preempted pop would blow a 2.5x band through no fault of the
        # data plane.
        "pop_ms_p50": round(float(np.median(lat_a) * 1e3), 3),
        "pop_mean_millis_ungated": round(float(lat_a.mean() * 1e3), 3),
        "pop_p95_millis_ungated": round(
            float(np.percentile(lat_a, 95) * 1e3), 3),
        "shm_segments_created": int(counters.get("shm_segments_created", 0)),
        "ring_records_out": int(counters.get("ring_records_out", 0)),
    }


def _put_run(journal_dir, *, duration_s: float, item_floats: int = 512,
             flush: int = 4, window: int = 64) -> float:
    """Acked-items/s of one in-process PutStream producer against a
    hosted channel — journaled into ``journal_dir`` when given, plain
    otherwise. Same thread layout both ways, so the ratio isolates the
    journal's append cost (pops journaled too: the drain is part of the
    steady state being priced)."""
    from repro.runtime.transport import (PutStream, TransportJournal,
                                         TransportServer)

    journal = (TransportJournal(journal_dir, compact_bytes=1 << 30)
               if journal_dir else None)
    chan = FifoChannel(1 << 15, policy="drop_oldest")
    if journal is not None:
        chan = journal.wrap("bench", chan)
    server = TransportServer(journal=journal)
    server.add_channel("bench", chan)
    server.start()
    payload = [{"x": np.zeros(item_floats, np.float32)}] * flush
    stop = threading.Event()

    def drain() -> None:
        while not stop.is_set():
            chan.pop_many(1024, timeout=0.02)

    drainer = threading.Thread(target=drain, daemon=True)
    drainer.start()
    stream = PutStream(server.address, "bench", window=window)
    t0 = time.monotonic()
    while time.monotonic() - t0 < duration_s:
        stream.put_many(payload)
    stream.flush(30.0)
    acked = int(stream.stats()["items_accepted"])
    wall = time.monotonic() - t0
    stream.close()
    stop.set()
    drainer.join(timeout=2.0)
    server.stop()
    server.join()
    return acked / wall


def _journal_tmpdir(prefix: str) -> str:
    """A journal scratch dir on tmpfs when the host has one: the section
    prices the journal MECHANISM (encode/crc/group-commit syscalls), and
    a slow container disk whose writeback throttles at ~100MB/s would
    price the deployment's disk instead. Real deployments journal to
    hardware whose page-cache absorption outruns the experience plane."""
    import tempfile
    base = "/dev/shm" if os.path.isdir("/dev/shm") else None
    return tempfile.mkdtemp(prefix=prefix, dir=base)


def _drive_recovery(*, duration_s: float, n_items: int = 4096,
                    item_floats: int = 256) -> Dict:
    """The recovery section: journal overhead ratio + replacement
    time-to-first-pop."""
    import shutil

    from repro.runtime.transport import (PutStream, SocketChannel,
                                         TransportJournal, TransportServer)

    # -- steady-state journal cost: interleaved best-of-2 --------------------
    plain = journaled = 0.0
    for _ in range(2):
        plain = max(plain, _put_run(None, duration_s=duration_s))
        jdir = _journal_tmpdir("acrl_bench_journal_")
        try:
            journaled = max(journaled, _put_run(jdir, duration_s=duration_s))
        finally:
            shutil.rmtree(jdir, ignore_errors=True)

    # -- time-to-first-pop of a replacement server ---------------------------
    jdir = _journal_tmpdir("acrl_bench_recover_")
    try:
        journal = TransportJournal(jdir, compact_bytes=1 << 30)
        chan = journal.wrap("bench", FifoChannel(n_items))
        server = TransportServer(journal=journal)
        server.add_channel("bench", chan)
        server.start()
        stream = PutStream(server.address, "bench", window=64)
        item = {"x": np.zeros(item_floats, np.float32)}
        for _ in range(n_items // 16):
            stream.put_many([item] * 16)
        assert stream.flush(30.0)
        stream.close()
        server.stop()                  # on_stop compacts to one snapshot
        server.join()

        t0 = time.perf_counter()
        journal2 = TransportJournal(jdir, resume=True)
        chan2 = journal2.wrap("bench", FifoChannel(n_items))
        server2 = TransportServer(journal=journal2)
        server2.add_channel("bench", chan2)
        server2.resume_from_journal()
        server2.start()
        pop = SocketChannel(server2.address, "bench")
        first = pop.pop_many(64, timeout=10.0)
        t_recover = time.perf_counter() - t0
        assert first, "replacement server never served a pop"
        recovered = int(server2.metrics.counter("journal_recovered_items"))
        assert recovered == n_items, (recovered, n_items)
        pop.close()
        server2.stop()
        server2.join()
    finally:
        shutil.rmtree(jdir, ignore_errors=True)

    return {
        "plain_put_items_per_sec": round(plain, 1),
        "journaled_put_items_per_sec": round(journaled, 1),
        "journal_put_ratio": round(journaled / max(plain, 1e-9), 4),
        "recovered_items": recovered,
        "t_recover_s": round(t_recover, 4),
    }


def run(quick: bool = True) -> Dict:
    duration = 2.0 if quick else 8.0
    result: Dict = {"duration_s_requested": duration, "sweep": []}
    for policy in BACKPRESSURE_POLICIES:
        rec = _drive(policy, duration_s=duration)
        result["sweep"].append(rec)
        print(f"  {policy:12s}: accept {rec['accept_rate']:5.1%} "
              f"dropped {rec['dropped']:5d} "
              f"staleness {rec['staleness_mean']*1e3:7.1f}ms "
              f"(p95 {rec['staleness_p95']*1e3:7.1f}ms) "
              f"depth {rec['depth_mean']:5.1f}")

    by = {r["policy"]: r for r in result["sweep"]}
    # the structural claims, asserted so a regression fails the benchmark
    # run itself (the perf gate additionally bands the committed numbers):
    # block CLAMPS producer throughput to the consumer (its accept *rate*
    # is high only because producers stall); drop_oldest keeps producers
    # at full speed; drop_newest trades throughput for maximal staleness.
    assert (by["drop_oldest"]["accepted_per_s"]
            > 1.5 * by["block"]["accepted_per_s"]), \
        "drop_oldest must out-accept the consumer-clamped block policy"
    assert (by["drop_newest"]["staleness_mean"]
            > by["drop_oldest"]["staleness_mean"]), \
        "drop_newest keeps old data: staleness must exceed drop_oldest"
    assert by["block"]["dropped"] < by["drop_oldest"]["dropped"], \
        "block must drop (time out) less than drop_oldest evicts"
    result["claims"] = {
        "drop_oldest_over_block_throughput": round(
            by["drop_oldest"]["accepted_per_s"]
            / max(by["block"]["accepted_per_s"], 1e-9), 2),
        "drop_newest_over_drop_oldest_staleness": round(
            by["drop_newest"]["staleness_mean"]
            / max(by["drop_oldest"]["staleness_mean"], 1e-9), 2),
    }

    # -- wire section: put vs put_many over a real socket transport ----------
    wire = {"single": _drive_wire(False, duration_s=duration),
            "batched": _drive_wire(True, duration_s=duration)}
    speedup = round(wire["batched"]["items_per_sec"]
                    / max(wire["single"]["items_per_sec"], 1e-9), 2)
    wire["batched_over_single_throughput"] = speedup
    for rec in (wire["single"], wire["batched"]):
        print(f"  wire/{rec['mode']:8s}: {rec['items_per_sec']:8.1f} "
              f"items/s  ({rec['items_per_rpc']:5.2f} items/rpc)")
    print(f"  wire: batched/single throughput x{speedup}")
    # put_many's whole point: fewer round-trips per item. The throughput
    # win follows but is load-sensitive (shared CI runners), so ONLY the
    # structural claim is hard-asserted; the ratio is reported data.
    assert (wire["batched"]["items_per_rpc"]
            > 4 * wire["single"]["items_per_rpc"]), \
        "put_many must amortize framing across many items per RPC"
    result["wire"] = wire

    # -- streaming section: pipelined puts + ring-vs-segment pops ------------
    # best-of-2 interleaved rounds per mode: spawned-producer throughput
    # is scheduler-noisy on shared runners, and the claim under test is
    # the data plane's CAPABILITY, not one draw of the noise
    streaming: Dict = {}
    modes = ("batched", "pipelined", "pipelined_ring")
    for _round in range(2):
        for mode in modes:
            rec = _drive_stream(mode, duration_s=duration)
            if (mode not in streaming or rec["items_per_sec"]
                    > streaming[mode]["items_per_sec"]):
                streaming[mode] = rec
    for mode in modes:
        rec = streaming[mode]
        print(f"  streaming/{rec['mode']:14s}: {rec['items_per_sec']:9.1f} "
              f"items/s  ({rec['frames']} frames, "
              f"window {rec['window']})")
    for key in ("pipelined", "pipelined_ring"):
        streaming[f"{key}_over_batched_throughput"] = round(
            streaming[key]["items_per_sec"]
            / max(streaming["batched"]["items_per_sec"], 1e-9), 2)
    print(f"  streaming: pipelined/batched "
          f"x{streaming['pipelined_over_batched_throughput']}  "
          f"ring x{streaming['pipelined_ring_over_batched_throughput']}")
    # ISSUE 5 acceptance: the pipelined put path must at least double the
    # batched request/response throughput (it removes one blocking RTT +
    # server decode per flush from the producer's critical path). Judged
    # on the best pipelined variant — which of socket/ring wins is a
    # machine property, the pipelining claim is not. The claim IS a
    # parallelism claim (producer encode overlapping server decode), so
    # on a single-CPU box there is nothing to overlap and the ratios are
    # reported data only.
    best = max(streaming["pipelined"]["items_per_sec"],
               streaming["pipelined_ring"]["items_per_sec"])
    if (multiprocessing.cpu_count() or 1) >= 2:
        assert best >= 2.0 * streaming["batched"]["items_per_sec"], \
            "pipelined put stream must be >= 2x the batched RPC path"
        # ... and the plain-socket stream must never regress to batched
        # speed, or a no-ring-path bug would hide behind a healthy ring
        assert (streaming["pipelined"]["items_per_sec"]
                >= 1.2 * streaming["batched"]["items_per_sec"]), \
            "socket-mode pipelined stream regressed to ~batched throughput"
    else:
        print("  streaming: single CPU — overlap speedup asserts skipped")

    pops = 60 if quick else 150
    pop: Dict = {}
    for _round in range(2):              # best-of-2, interleaved (noise)
        for ring_plane, key in ((False, "segment"), (True, "ring")):
            rec = _drive_pop(ring_plane, pops=pops)
            if key not in pop or rec["pop_ms_p50"] < pop[key]["pop_ms_p50"]:
                pop[key] = rec
    pop["ring_over_segment_latency"] = round(
        pop["ring"]["pop_ms_p50"]
        / max(pop["segment"]["pop_ms_p50"], 1e-9), 3)
    for rec in (pop["segment"], pop["ring"]):
        print(f"  pop/{rec['plane']:8s}: {rec['pop_ms_p50']:7.3f} ms p50 "
              f"(mean {rec['pop_mean_millis_ungated']:7.3f}, "
              f"p95 {rec['pop_p95_millis_ungated']:7.3f}, "
              f"segments {rec['shm_segments_created']}, "
              f"ring records {rec['ring_records_out']})")
    # the persistent ring must beat per-message segment churn on the pop
    # path, and must actually have carried the blobs
    assert pop["ring"]["pop_ms_p50"] < pop["segment"]["pop_ms_p50"], \
        "ring pop latency must undercut per-segment SHM"
    assert pop["ring"]["shm_segments_created"] == 0
    assert pop["ring"]["ring_records_out"] >= pops
    assert pop["segment"]["shm_segments_created"] >= pops
    streaming["pop"] = pop
    result["streaming"] = streaming

    # -- telemetry section: tracing-ON vs OFF put-path overhead --------------
    telem: Dict = {}
    for _round in range(2):              # best-of-2 interleaved (noise)
        for traced, key in ((False, "off"), (True, "on")):
            rec = _drive_telemetry(traced, duration_s=duration)
            if (key not in telem
                    or rec["items_per_sec"] > telem[key]["items_per_sec"]):
                telem[key] = rec
    ratio = round(telem["on"]["items_per_sec"]
                  / max(telem["off"]["items_per_sec"], 1e-9), 4)
    telem["on_over_off_throughput"] = ratio
    # per-item cost as gated wall-time keys, so the tracing hot path
    # cannot silently grow a step-function cost between PRs
    for key in ("off", "on"):
        telem[f"put_item_{key}_ms"] = round(
            1e3 / max(telem[key]["items_per_sec"], 1e-9), 5)
    for key in ("off", "on"):
        rec = telem[key]
        print(f"  telemetry/{rec['tracing']:3s}: "
              f"{rec['items_per_sec']:9.1f} items/s  "
              f"({rec['trace_events']} events recorded)")
    print(f"  telemetry: on/off put throughput x{ratio}")
    # tracing-OFF must be exactly inert (the child additionally asserts
    # no ring was registered); tracing-ON must have actually traced
    assert telem["off"]["trace_events"] == 0, \
        "untraced producer recorded events — REPRO_TRACE gating broken"
    assert telem["on"]["trace_events"] > 0, \
        "traced producer recorded nothing — span recorder dead"
    # ISSUE 8 acceptance: the span recorder is an append to a
    # preallocated per-thread ring + one 8-byte urandom id per flush —
    # <5% of the put path. On a single CPU the spawned producer
    # serializes against the server/drain threads and the ratio
    # measures core starvation, so it is reported data there.
    if (multiprocessing.cpu_count() or 1) >= 2:
        assert ratio >= 0.95, \
            f"tracing costs >5% put throughput: x{ratio}"
    else:
        print("  telemetry: single CPU — overhead assert skipped")
    result["telemetry"] = telem

    # -- recovery section: journal overhead + replacement warm-up ------------
    recovery = _drive_recovery(duration_s=duration)
    print(f"  recovery: journaled/plain put throughput "
          f"x{recovery['journal_put_ratio']}  "
          f"({recovery['journaled_put_items_per_sec']:.0f} vs "
          f"{recovery['plain_put_items_per_sec']:.0f} items/s)  "
          f"time-to-first-pop {recovery['t_recover_s']*1e3:.1f}ms "
          f"({recovery['recovered_items']} items replayed)")
    # ISSUE 6 acceptance: the write-ahead journal must cost <5% streaming
    # put throughput — its whole design (apply-then-append reusing the
    # wire blob, group-committed writes at ack boundaries, no fsync on
    # the hot path) exists to make parent crash-safety effectively free.
    # The cost lands on the SERVER side of the stream; with ≥2 CPUs it
    # rides a core the producer isn't using, which is the deployment
    # shape the claim is about — on a single CPU every server-side
    # cycle serializes against the producer and the ratio only measures
    # core starvation, so it is reported data there, not a gate.
    if (multiprocessing.cpu_count() or 1) >= 2:
        assert recovery["journal_put_ratio"] >= 0.95, (
            f"journal costs >5% put throughput: "
            f"x{recovery['journal_put_ratio']}")
    else:
        print("  recovery: single CPU — journal overhead assert skipped")
    result["recovery"] = recovery

    save("BENCH_backpressure", result)
    return result


if __name__ == "__main__":
    run()
