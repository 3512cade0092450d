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

Who makes the ``send`` calls. A transport over a plain socket (TCP or
PROXY listener: ``get_extra_info("socket")`` has a descriptor and there
is no ``sslcontext``) is attached, when it is made, to the outbox's
native writer (``native/egress.cc``, ``_vmq_egress.Writer``): the flush
hands every such transport's chunks to the writer in ONE call and
returns, and the writer's thread, which never takes the interpreter
lock, sends them back to back, in hand-off order per connection, on a
descriptor of its own (a ``dup``: the loop's may close and be reused at
any time). Every byte of such a connection goes through the writer, so
asyncio's own buffer stays empty and a connection's order is one FIFO.
TLS, WebSocket and any transport without a socket are written on the
loop as before; so is everything where the extension is absent
(``VMQ_NO_NATIVE``, no toolchain, a failed build). The writer starts
with the broker (``Outbox.start``) and is joined at its stop
(``Outbox.close``).
"""

from __future__ import annotations

import asyncio

from ..native import load_extension
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

    __slots__ = ("_metrics", "_listed", "_handle", "_writer", "bytes_sent",
                 "publish_sent", "puback_sent", "queue_in", "queue_out",
                 "matches_local")

    def __init__(self, metrics) -> None:
        self._metrics = metrics
        self._listed: list = []
        # the scheduled flush; not None <=> something is listed or counted
        self._handle = None
        # the native writer (``start``), or None: every write on the loop
        self._writer = None
        self.bytes_sent = 0
        self.publish_sent = 0
        self.puback_sent = 0
        self.queue_in = 0
        self.queue_out = 0
        self.matches_local = 0

    def start(self) -> None:
        """Start the writer thread, where the extension loads."""
        if self._writer is None:
            mod = load_extension("_vmq_egress", min_version=1,
                                 version_attr="EGRESS_VERSION")
            if mod is not None:
                self._writer = mod.Writer(JOIN_MAX)

    def close(self) -> None:
        """Hand what is listed to the writer, then stop and join its
        thread: it tries each backlog once more, then closes every
        descriptor it holds."""
        self.flush()
        writer, self._writer = self._writer, None
        if writer is not None:
            writer.stop()
            self._take(writer)

    def attach(self, transport) -> int:
        """The writer's id for a transport over a plain socket, or 0:
        a TLS or socketless transport, or no writer."""
        writer = self._writer
        if writer is None:
            return 0
        try:
            if (transport.is_closing()
                    or transport.get_extra_info("sslcontext") is not None):
                return 0
            fd = transport.get_extra_info("socket").fileno()
        except AttributeError:  # a fixture, or no socket
            return 0
        if fd < 0:
            return 0
        try:
            return writer.attach(fd)
        except OSError:
            return 0

    def release(self, wid: int, chunks: list, drain: bool) -> None:
        """A transport is done with the writer: ``chunks`` (its last) are
        sent, then the descriptor closes after the last byte (``drain``),
        or the backlog is dropped and it closes at once."""
        writer = self._writer
        if writer is None:
            return
        if chunks:
            writer.submit([wid, chunks])
        writer.close(wid, drain)

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
            handoff: list = []
            for transport in listed:
                form = transport._flush(handoff)
                if form:
                    writes += 1
                    if form == _JOINED:
                        joined += 1
                    elif form == _SCATTERED:
                        scattered += 1
            if handoff and self._writer is not None:  # None: stopped
                handed, j, sc = self._writer.submit(handoff)
                fastpath.egress_offload_writes += handed
                joined += j
                scattered += sc
            fastpath.egress_flushes += 1
            fastpath.egress_writes += writes
            fastpath.egress_joined += joined
            fastpath.egress_scattered += scattered
        finally:
            obs.span_end("stage_egress_flush_ms", tok)

    @staticmethod
    def _take(writer) -> None:
        sent, lag_us, dropped = writer.take()
        if sent:
            fastpath.egress_offload_sent += sent
            fastpath.egress_offload_lag_us += lag_us
        if dropped:
            fastpath.egress_offload_dropped += dropped

    def _fold(self) -> None:
        if self._writer is not None:
            self._take(self._writer)
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


# what one transport's flush sent, for the outbox's gauges (_HANDED: its
# chunks went to the writer, which counts their form)
_SINGLE, _JOINED, _SCATTERED, _HANDED = 1, 2, 3, 4


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
    copied once, inside the transport. Over a plain socket the chunks go
    to the outbox's writer instead (``_wid``, decided once, here), which
    applies the same rule to what it sends."""

    def __init__(self, transport: asyncio.WriteTransport, outbox: Outbox):
        self._transport = transport
        self._outbox = outbox
        self._chunks: list = []
        self._listed = False
        self.closed = False
        self._wid = outbox.attach(transport)  # 0: written on the loop

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

    def _flush(self, handoff: list) -> int:
        """Send what is pending, or append it to the flush's ``handoff``
        to the writer; returns the form it took (0: nothing — closed, or
        ``close`` flushed it already). A transport that raises is
        closed, and the outbox's walk goes on."""
        self._listed = False
        chunks = self._chunks
        if self.closed or not chunks:
            return 0
        self._chunks = []
        if self._wid:
            handoff.append(self._wid)
            handoff.append(chunks)
            return _HANDED
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
        wid, self._wid = self._wid, 0
        if wid:
            # the writer sends what is pending and closes its descriptor
            # after it: the FIN follows the last byte
            chunks, self._chunks = self._chunks, []
            self._outbox.release(wid, chunks, True)
        else:
            self._flush([])
        self.closed = True
        try:
            self._transport.close()
        except Exception:
            pass

    def lost(self) -> None:
        """The connection is gone (its protocol's ``connection_lost``):
        the writer drops what it still holds for it and releases its
        descriptor; nothing more is written."""
        wid, self._wid = self._wid, 0
        if wid:
            self.closed = True
            self._chunks = []
            self._outbox.release(wid, [], False)
