"""A loop turn's socket writes and egress bookkeeping, together.

PR 29 made the loop turn the unit of a listener's READS (``data_received``
lists, ``MQTTServer._serve_inbox`` serves). This is the other half. A
``StreamTransport`` keeps each connection's chunks in arrival order, as
before, but schedules nothing of its own: the first write of a turn lists
it in its broker's ``Outbox`` and the first listing of a turn schedules ONE
callback, which walks the listed transports back to back — a turn's
``send`` calls run together, which is what a socket call costs least at on
a sandboxed kernel — and first folds the turn's egress counters into
``Metrics``: whoever has seen the bytes sees the counts. Whoever filled
the outbox may also run it at the end of its own callback
(``BatchCollector._release``, ``MQTTServer._serve_inbox``): a release
chunk's writes then leave before that turn's reads instead of behind
them, and the scheduled callback is cancelled.
"""

from __future__ import annotations

import asyncio

from ..observability import histogram as obs
from ..protocol import fastpath
from .session import Transport

#: pending bytes up to which a transport's several chunks leave as ONE
#: ``write`` of their join, above which as ``writelines`` (so a large
#: shared payload is never copied per recipient). Set on the chip's host
#: (gVisor; PERF.md §6, PR 31 call 1b: 256 loopback transports, header +
#: payload, median of 9 rounds, wall µs a write, joined | ``writelines``):
#: 16 B 36.7 | 46.9, 1 KiB 34.1 | 44.5, 4 KiB 38.8 | 41.4, 8 KiB 40.0 |
#: 45.1, 16 KiB 50.2 | 48.9, 32 KiB 59.2 | 54.8 — the join wins by 3–10 µs
#: up to 8 KiB and loses from 16 KiB on; a page keeps half of that margin
#: in hand.
JOIN_MAX = 4096

#: transports one flush writes; the rest stay listed, in order, for the
#: flush of the next turn (the outbox's own callback, or the next release
#: chunk's). A socket write is ~46 µs on the chip's host and nothing a
#: flush can shorten, so a turn that wrote to 1,000 sessions — one publish
#: of the suite's fan-out case — held the loop 46 ms for its sends alone,
#: and a timer waits two such turns (PERF.md §6, PR 32, calls 2 and 3: the
#: loop 0.19–0.36 s late against ``sysmon_lag_threshold`` 0.25 s). A
#: transport that waits keeps collecting frames and sends them as one
#: write, so the cap costs no socket call. 256 is two release chunks of
#: one-row publishes: no turn of point-to-point traffic reaches it.
FLUSH_MAX = 256


class Outbox:
    """The transports written in this loop turn, in first-write order,
    and the turn's egress counters as plain integers (the wire plane's
    fanout, ``Session.send`` and the PUBACK count into them; ``flush``
    folds them into ``Metrics`` before it writes). One per broker."""

    __slots__ = ("_metrics", "_listed", "_handle", "bytes_sent",
                 "publish_sent", "puback_sent", "queue_in", "queue_out",
                 "matches_local")

    def __init__(self, metrics) -> None:
        self._metrics = metrics
        self._listed: list = []
        # the scheduled flush; not None <=> something is listed or counted
        self._handle = None
        self.bytes_sent = 0
        self.publish_sent = 0
        self.puback_sent = 0
        self.queue_in = 0
        self.queue_out = 0
        self.matches_local = 0

    def add(self, transport: "StreamTransport") -> None:
        self._listed.append(transport)
        if self._handle is None:
            self._schedule()

    def touch(self) -> None:
        """A counter was added to: see that a flush is due (a write to a
        ``StreamTransport`` has listed it already; a WebSocket's or a
        fixture's has not)."""
        if self._handle is None:
            self._schedule()

    def _schedule(self) -> None:
        self._handle = asyncio.get_event_loop().call_soon(self.flush)

    def flush(self) -> None:
        """Fold the counters, then write the listed transports, at most
        ``FLUSH_MAX`` of them (the rest: the next turn's flush, which
        this one schedules). Runs as the scheduled callback at the head
        of the next turn, or sooner from the callback that filled the
        outbox; with nothing pending it returns at once."""
        handle = self._handle
        if handle is None:
            return
        self._handle = None
        handle.cancel()  # no-op when this IS the scheduled run
        tok = obs.span_begin("stage_egress_flush_ms")
        try:
            self._fold()
            listed = self._listed
            if len(listed) > FLUSH_MAX:
                listed, self._listed = (listed[:FLUSH_MAX],
                                        listed[FLUSH_MAX:])
                self._schedule()
            else:
                self._listed = []
            writes = joined = scattered = 0
            for transport in listed:
                form = transport._flush()
                if form:
                    writes += 1
                    if form == _JOINED:
                        joined += 1
                    elif form == _SCATTERED:
                        scattered += 1
            fastpath.egress_flushes += 1
            fastpath.egress_writes += writes
            fastpath.egress_joined += joined
            fastpath.egress_scattered += scattered
        finally:
            obs.span_end("stage_egress_flush_ms", tok)

    def _fold(self) -> None:
        incr = self._metrics.incr
        if self.bytes_sent:
            incr("bytes_sent", self.bytes_sent)
            self.bytes_sent = 0
        if self.publish_sent:
            incr("mqtt_publish_sent", self.publish_sent)
            fastpath.egress_publishes += self.publish_sent
            self.publish_sent = 0
        if self.puback_sent:
            incr("mqtt_puback_sent", self.puback_sent)
            self.puback_sent = 0
        if self.queue_in:
            incr("queue_message_in", self.queue_in)
            self.queue_in = 0
        if self.queue_out:
            incr("queue_message_out", self.queue_out)
            self.queue_out = 0
        if self.matches_local:
            incr("router_matches_local", self.matches_local)
            self.matches_local = 0


# what one transport's flush sent, for the outbox's gauges
_SINGLE, _JOINED, _SCATTERED = 1, 2, 3


class StreamTransport(Transport):
    """Write-coalescing wrapper over an asyncio transport: session
    writes within one loop turn collect into ONE iovec (a chunk list),
    in order, and the broker's ``Outbox`` flushes it with that turn's
    other transports. The form of the write is chosen from the bytes
    pending: one chunk goes out as it is; several small ones (up to
    ``JOIN_MAX`` together: a delivery's header + payload, a run of
    acks) as one ``write`` of their join — a plain ``send``, where
    ``writelines`` costs a ``sendmsg`` and asyncio's buffer upkeep
    around it; anything larger as ``writelines``, so a fanout's shared
    payload object is referenced from every recipient's iovec and only
    copied once, inside the transport. Backpressure stays asyncio's:
    every byte goes through the wrapped transport's own ``write``."""

    def __init__(self, transport: asyncio.WriteTransport, outbox: Outbox):
        self._transport = transport
        self._outbox = outbox
        self._chunks: list = []
        self._listed = False
        self.closed = False

    def write(self, data: bytes) -> None:
        if self.closed:
            return
        self._chunks.append(data)
        if not self._listed:
            self._listed = True
            self._outbox.add(self)

    def write_iov(self, chunks) -> None:
        """Queue a writev-ready iovec (e.g. the native encoder's
        (header, payload) pair) without assembling a per-frame bytes
        object."""
        if self.closed:
            return
        self._chunks.extend(chunks)
        if not self._listed:
            self._listed = True
            self._outbox.add(self)

    def _flush(self) -> int:
        """Send what is pending; returns the form it took (0: nothing —
        closed, or ``close`` flushed it already). A transport that
        raises is closed, and the outbox's walk goes on."""
        self._listed = False
        chunks = self._chunks
        if self.closed or not chunks:
            return 0
        self._chunks = []
        try:
            if len(chunks) == 1:
                self._transport.write(chunks[0])
                return _SINGLE
            if sum(map(len, chunks)) <= JOIN_MAX:
                self._transport.write(b"".join(chunks))
                return _JOINED
            self._transport.writelines(chunks)
            return _SCATTERED
        except Exception:
            self.closed = True
            return 0

    def close(self) -> None:
        if self.closed:
            return
        self._flush()
        self.closed = True
        try:
            self._transport.close()
        except Exception:
            pass
