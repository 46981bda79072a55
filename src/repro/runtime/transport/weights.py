"""WeightStoreTransport: the VersionedWeightStore contract over the wire.

Remote workers pull fresh policy weights by version (the LlamaRL-style
distributed broadcast, pull-flavored): this proxy exposes the exact
surface :class:`~repro.runtime.weight_store.VersionedWeightStore` gives
the in-process inference pool —

  * ``acquire(newer_than, timeout)`` — newest ``(params, version)``,
    blocking until something newer exists (long-polled in bounded slices
    so ``close()`` always unblocks it);
  * ``draining`` / ``version()`` — the drain-protocol poll (App. D.6),
    cached for ``state_ttl`` seconds so a hot inference loop does not
    turn every iteration into an RPC;
  * ``begin_publish()`` / ``publish(params, version)`` — the trainer side,
    so a trainer could live across the wire too (transport parity with
    the in-process store is what the tests pin down).

An :class:`~repro.runtime.inference.InferenceService` constructed with
this object instead of the local store is a *remote* inference worker —
no code change on its side, which is the whole point of the seam.
"""
from __future__ import annotations

import time
from typing import Any, Optional, Tuple

from repro.runtime import telemetry as _tel
from repro.runtime.transport.channel import (ChannelClosed, WireClient,
                                             long_poll)
from repro.runtime.transport.codec import decode_pytree, encode_pytree

__all__ = ["WeightStoreTransport"]


class WeightStoreTransport:
    """Client-side remote weight store (publish/acquire over the wire)."""

    def __init__(self, address: Tuple[str, int], *, use_shm: bool = False,
                 connect_timeout: float = 20.0,
                 shm_threshold: int = 1 << 16, state_ttl: float = 0.05,
                 reconnect_attempts: int = 0,
                 reconnect_backoff_s: float = 0.1,
                 use_lane: bool = False):
        self._client = WireClient(address, connect_timeout=connect_timeout,
                                  shm_threshold=shm_threshold,
                                  reconnect_attempts=reconnect_attempts,
                                  reconnect_backoff_s=reconnect_backoff_s,
                                  on_reconnect=self._on_reconnect)
        self._use_shm = use_shm
        # broadcast lane (same-host only): acquire replies may carry the
        # blob's position in the server's persistent lane ring instead of
        # a body; this reader attaches the lane ONCE and copies blobs out
        # positionally — no per-acquire segment churn
        self._use_lane = bool(use_lane)
        self._lane = None                          # attached lane ring
        self.lane_hits = 0
        self.lane_fallbacks = 0
        self._state_ttl = state_ttl
        self._state = (-float("inf"), -1, False)   # (stamp, version, drain)

    def _on_reconnect(self) -> None:
        """A server-side drop may have hidden publishes: bust the cached
        (version, draining) so the next poll re-acquires the true newest
        version instead of serving the pre-drop state for a TTL. A
        replacement server also means a fresh lane ring, so drop the
        stale attachment (re-attached lazily by name)."""
        self._state = (-float("inf"), -1, False)
        lane, self._lane = self._lane, None
        if lane is not None:
            lane.close()

    # -- broadcast lane (positional reads) ------------------------------------
    def _lane_read(self, resp: dict) -> Optional[bytes]:
        """Copy the blob out of the server's lane ring at the advertised
        position; None on any failure (stale attachment, torn read under
        a concurrent newer publish) — the caller falls back to an
        in-band re-acquire."""
        from repro.runtime.transport.ring import RingError, ShmRing
        name = resp["lane"]
        try:
            if self._lane is None or self._lane.name != name:
                if self._lane is not None:
                    self._lane.close()
                    self._lane = None
                self._lane = ShmRing.attach(name)
            return self._lane.read_at(int(resp["lane_pos"]),
                                      int(resp["lane_seq"]),
                                      int(resp["lane_nbytes"]))
        except (RingError, OSError, ValueError):
            return None

    # -- state poll (cached) --------------------------------------------------
    def _fresh_state(self) -> Tuple[int, bool]:
        stamp, version, draining = self._state
        if time.monotonic() - stamp < self._state_ttl:
            return version, draining
        try:
            resp, _ = self._client.request({"m": "store.state"})
        except ChannelClosed:
            # shutdown is a data-plane no-op here too: keep serving the
            # last known state (acquire/put already degrade the same way);
            # the worker's control loop is what notices the parent is gone
            return version, False
        version, draining = int(resp["version"]), bool(resp["draining"])
        self._state = (time.monotonic(), version, draining)
        return version, draining

    @property
    def draining(self) -> bool:
        return self._fresh_state()[1]

    def version(self) -> int:
        return self._fresh_state()[0]

    # -- inference side -------------------------------------------------------
    def acquire(self, newer_than: int = -1,
                timeout: Optional[float] = None
                ) -> Optional[Tuple[Any, int]]:
        """Newest (params, version) with version > ``newer_than``."""
        got = long_poll(
            self._client,
            lambda t: {"m": "store.acquire", "newer_than": newer_than,
                       "timeout": t, "want_shm": self._use_shm,
                       "want_lane": self._use_lane},
            timeout)
        if got is None:
            return None
        resp, body = got
        version = int(resp["version"])
        if resp.get("lane"):
            body = self._lane_read(resp)
            if body is not None:
                self.lane_hits += 1
            else:
                # torn or stale lane read: one in-band re-acquire (the
                # version exists, so newer_than = version - 1 succeeds
                # immediately with this version or a newer one)
                self.lane_fallbacks += 1
                try:
                    resp, body = self._client.request(
                        {"m": "store.acquire", "newer_than": version - 1,
                         "timeout": 5.0, "want_shm": self._use_shm})
                except ChannelClosed:
                    return None
                if not resp.get("ok"):
                    return None
                version = int(resp["version"])
        if _tel.RECORDING:
            # wire leg of the policy-lag flow (version is the flow id):
            # a remote pool's fetch shows up on the publish timeline
            _tel.instant("weights.wire_acquire", cat="weights",
                         trace=version,
                         args={"version": version,
                               "bytes": len(body) if body else 0},
                         flow="step")
        return decode_pytree(body), version

    # -- trainer side ---------------------------------------------------------
    def begin_publish(self) -> None:
        self._client.request({"m": "store.drain"})
        self._state = (-float("inf"), *self._state[1:])   # bust the cache

    def publish(self, params: Any, version: int) -> None:
        blob = encode_pytree(params)
        with _tel.span("weights.wire_publish", cat="weights",
                       trace=int(version),
                       args={"version": int(version),
                             "bytes": len(blob)}, flow="start"):
            self._client.request({"m": "store.publish", "version": version},
                                 blob, oob=self._use_shm)
        self._state = (-float("inf"), *self._state[1:])

    # -- lifecycle ------------------------------------------------------------
    @property
    def closed(self) -> bool:
        return self._client.closed

    def close(self) -> None:
        self._client.close()
        lane, self._lane = self._lane, None
        if lane is not None:
            lane.close()                 # attachment only — server unlinks
