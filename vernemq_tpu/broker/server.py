"""asyncio TCP listeners + per-connection socket loop.

Mirrors the reference socket layer: one lightweight task per connection
(``vmq_ranch.erl:41-43`` — one Erlang process per socket), buffered reparse
of incoming bytes driving the session FSM (``vmq_ranch.erl:167-251``),
write coalescing per event-loop tick (the MSS flush-threshold batching of
``vmq_ranch.erl:253-262``; ``broker/egress.py``), and protocol detection
on the first CONNECT frame choosing the v4 or v5 FSM
(``vmq_mqtt_pre_init.erl:58-70``).

What runs where. An ``mqtt`` / ``mqtts`` listener reads its sockets at
the protocol level (``MqttProtocol``): the connection's task runs
everything that awaits — the CONNECT / enhanced-AUTH exchange, every
classic frame (``Session.handle_frame``), the waits at the run bounds,
the close — and, while it is parked at its steady-state read, the
protocol runs a chunk's wire-plane records (admitted PUBLISHes, the
2-byte ack family) itself: no stream reader, no future, no task step
for a chunk that holds nothing else. In two phases: ``data_received``
only notes the chunk, so a loop turn's socket reads run back to back,
and ONE callback of the listener (``MQTTServer._serve_inbox``), first
in the next turn, serves all of them. The first record it cannot serve
goes to the task with every byte behind it. That callback evaluates
the broker-wide half of the wire gate once for the pass
(``session.wire_broker_ready``); a chunk tests only its session's half,
and the task keeps the whole gate (``Session.wire_fast_ready``).
WebSocket and PROXY-protocol listeners need a reader of their own for
their first bytes and hand the task a ``read_chunk``; both forms walk a
frame table's fast stretch through the one ``wire_run``.

The turn is the unit of the WRITES as well (``broker/egress.py``). A
``StreamTransport`` — every ``mqtt`` / ``mqtts`` / PROXY connection's —
only collects its chunks and lists itself in the broker's ``Outbox``;
one flush walks the turn's transports back to back, one socket write
each, after folding the turn's egress counters into ``Metrics``. It
runs at the end of the callback that filled it — a release chunk of the
collector (``BatchCollector._release``: 64 deliveries and 64 PUBACKs,
each after its route returned), ``_serve_inbox`` under the trie view —
and otherwise as the outbox's own callback, first in the next turn
(a task's CONNACK, SUBACK, PINGRESP).
"""

from __future__ import annotations

import asyncio
import logging
from typing import Optional, Tuple

from ..observability import histogram as obs
from ..protocol import codec_v4, codec_v5, fastpath, wire
from ..protocol.types import (
    PROTO_5,
    RC_PACKET_TOO_LARGE,
    Connect,
    ParseError,
)
from ..utils.aio import close_server
from .broker import Broker
from .egress import StreamTransport
from .session import WIRE_OPEN, Session, Transport, wire_gate
from .websocket import WsError

log = logging.getLogger("vernemq_tpu.server")

CONNECT_TIMEOUT = 10.0
#: records one connection's reader handles before it yields to the loop,
#: and the publishes it may have out with the collector (wire plane,
#: batched view) before it waits for them to come back
FRAME_RUN = 64
MAX_FRAME_SIZE = 268435455


def parse_nodelay_option(raw: str) -> Optional[bool]:
    """Extract the ``nodelay`` flag from the tcp_listen_options knob
    (vmq_server.schema:1454, an erlang proplist string). ``nodelay`` is
    the option that matters for publish latency; the rest of the
    proplist is accepted for compatibility (asyncio owns send
    timeouts/linger). Returns None when the option is absent."""
    if "nodelay" not in raw:
        return None
    return "{nodelay,true}" in raw.replace(" ", "")


def _apply_nodelay(transport: asyncio.BaseTransport, want: bool) -> None:
    sock = transport.get_extra_info("socket")
    if sock is not None:
        import socket as _socket

        try:
            sock.setsockopt(_socket.IPPROTO_TCP, _socket.TCP_NODELAY,
                            1 if want else 0)
        except OSError:
            pass


def sniff_proto_ver(body: bytes) -> int:
    """Read the protocol level out of a CONNECT body without committing to a
    codec (vmq_mqtt_pre_init.erl:44-70)."""
    name, pos = wire.take_utf8(body, 0)
    if pos >= len(body):
        raise ParseError("malformed_connect")
    return body[pos] & 0x7F


REC_SIZE = fastpath.REC_SIZE
_unpack_rec = fastpath.REC.unpack_from
#: first bytes of the QoS1/2 PUBLISH the wire plane admits: no retain, no
#: dup — the dup retransmit and retained forms keep the classic path
#: (dedup/store edges)
_FAST_QOS_FLAGS = (0x32, 0x34)


def _owes_pause(table, end: int) -> bool:
    """Whether a frame table holds a PUBLISH: the records the governor's
    level 1 pauses a reader for (``Session.wire_pause``, the task's)."""
    kinds = table[:end:REC_SIZE]
    return fastpath.K_PUB in kinds or fastpath.K_PUB0 in kinds


def wire_run(session: Session, buf, table, off: int, end: int,
             budget: int) -> int:
    """The wire plane's record loop: serve the records of the frame
    ``table`` over ``buf`` from ``off`` on, at most ``budget`` of them,
    for as long as each is one the session takes straight from the
    table — a plain QoS0 PUBLISH, a QoS1/2 PUBLISH with fewer than
    ``FRAME_RUN`` already out with the collector, a 2-byte ack — and
    book what was admitted (``wire_fast_done``, also when a record
    raises). Returns the offset of the first record NOT served (``end``
    if none is left): another kind, one the session declined, or the
    bound. Never awaits and passes no gate: the caller has seen
    ``wire_fast_ready()`` — the connection's task between classic
    records, ``MqttProtocol._serve`` while that task is parked."""
    stop = min(end, off + budget * REC_SIZE)
    pubs = qpubs = 0
    try:
        while off < stop:
            rec = _unpack_rec(table, off)
            kind = rec[0]
            if kind == fastpath.K_PUB0:
                if rec[1] != 0x30 \
                        or not session.wire_publish_qos0(buf, rec):
                    break
                pubs += 1
            elif kind == fastpath.K_PUB:
                if rec[1] not in _FAST_QOS_FLAGS \
                        or session.wire_inflight >= FRAME_RUN \
                        or not session.wire_publish_qos(buf, rec):
                    break
                qpubs += 1
            # an ack resolves (invalid pids count *_invalid_error
            # exactly like classic) but for a PUBREL behind a publish
            # still with the collector
            elif kind != fastpath.K_ACK or not session.wire_ack(rec):
                break
            off += REC_SIZE
    finally:
        # a mid-run error must not lose the bookkeeping for fast-path
        # messages already routed and delivered
        if pubs or qpubs:
            session.wire_fast_done(pubs, qpubs)
    return off


async def mqtt_connection(
    broker: Broker,
    read_chunk,
    transport: Transport,
    peer: Tuple[str, int],
    max_frame_size: int = MAX_FRAME_SIZE,
    initial: bytes = b"",
    preauth_user: Optional[str] = None,
    mountpoint: str = "",
    allowed_protocol_versions: Optional[Tuple[int, ...]] = None,
) -> None:
    """The per-connection MQTT byte loop, transport-agnostic: ``read_chunk``
    is an awaitable returning the next bytes (b"" on EOF), ``transport``
    writes outbound frames. TCP, TLS, WebSocket and PROXY-wrapped listeners
    all drive their sockets through this one loop (the reference funnels all
    transports into the same FSM contract, vmq_ranch.erl:167-251). A
    ``read_chunk`` that is the connection's ``MqttProtocol`` is that
    awaitable and more: at the steady-state read this task parks in it
    (``MqttProtocol.park``) and the protocol runs wire-plane records where
    their bytes arrive.
    ``preauth_user`` overrides the CONNECT username (TLS client-cert CN or
    PROXY identity, vmq_ranch.erl:59-72); ``mountpoint`` is the listener's
    multitenancy prefix (per-listener mountpoint config)."""
    metrics = broker.metrics
    metrics.incr("socket_open")
    session: Optional[Session] = None
    buf = initial
    # a protocol-level reader counts its bytes where they arrive
    inline = read_chunk if isinstance(read_chunk, MqttProtocol) else None
    try:
        # ---- pre-init: wait for CONNECT, pick protocol ----------------
        first = wire.split_frame(buf, max_frame_size) if buf else None

        async def _read_connect():
            # wait_for (not asyncio.timeout) — the latter is 3.11+ and
            # this must run on the image's 3.10
            nonlocal buf
            f = first
            while f is None:
                chunk = await read_chunk()
                if not chunk:
                    return None
                if inline is None:
                    metrics.incr("bytes_received", len(chunk))
                buf += chunk
                f = wire.split_frame(buf, max_frame_size)
            return f

        first = await asyncio.wait_for(_read_connect(), CONNECT_TIMEOUT)
        if first is None:
            return
        ptype, flags, body, rest = first
        if ptype != 1:  # must be CONNECT
            return
        proto_ver = sniff_proto_ver(body)
        if (allowed_protocol_versions is not None
                and proto_ver not in allowed_protocol_versions):
            # per-listener version gate (listener.*.allowed_protocol_versions,
            # vmq_server.schema): refuse like an unknown level
            if proto_ver == PROTO_5:
                transport.write(b"\x20\x03\x00\x84\x00")  # v5 rc=0x84
            else:
                transport.write(b"\x20\x02\x00\x01")  # v4 rc=1
            metrics.incr("mqtt_connect_error")
            return
        if proto_ver == PROTO_5:
            codec = codec_v5
        elif proto_ver in (3, 4):
            codec = codec_v4
        else:
            # unknown protocol level: v4-style CONNACK rc=1
            transport.write(b"\x20\x02\x00\x01")
            return
        gov = getattr(broker, "overload", None)
        if gov is not None and gov.refuse_connects():
            # L3 admission control (robustness/overload.py): refuse
            # before any session/auth/registry cost. This is the
            # earliest protocol-aware point we control — with asyncio
            # listeners the TLS handshake has already run by the time
            # the stream reaches us, so "before TLS" is only possible
            # for plain listeners (where there is no handshake to
            # save). v5: CONNACK 0x97 Quota exceeded; v3/4: rc=3
            # Server unavailable.
            metrics.incr("mqtt_connect_error")
            if proto_ver == PROTO_5:
                transport.write(b"\x20\x03\x00\x97\x00")
            else:
                transport.write(b"\x20\x02\x00\x03")
            return
        connect_frame = codec._parse_body(ptype, flags, body)
        if preauth_user is not None:
            connect_frame.username = preauth_user
        session = Session(broker, transport, proto_ver, peer=peer,
                          mountpoint=mountpoint)
        if max_frame_size and max_frame_size < MAX_FRAME_SIZE:
            # the cap THIS listener actually parses with — what the
            # CONNACK maximum_packet_size must announce (a later config
            # change or per-listener override must not let the two lie
            # apart)
            session.max_frame_in = max_frame_size
        ok = await session.handle_connect(connect_frame)
        if not ok and not session._pending_connect:
            return

        # ---- steady-state frame loop ---------------------------------
        # The wire plane (protocol/fastpath.py): each buffered chunk is
        # batch-parsed into a packed frame table in ONE call (native
        # codec when built, bit-identical pure-Python twin otherwise).
        # Admitted PUBLISHes — QoS0 AND QoS1/2 — flow from the table
        # straight into the routing fanout without materialising
        # frame/Msg objects, and the 2-byte ack family resolves its pid
        # against the in-flight bookkeeping the same way: ``wire_run``,
        # synchronous, which a protocol-level listener also calls from
        # ``data_received`` while this task is parked. Every other
        # record — reason-code acks, retained/dup publishes, protocol
        # edges, malformed input — materialises its frame object and
        # takes the classic handler here, unchanged.
        buf = bytes(rest)
        frames_run = 0
        gov = broker.overload
        v5 = codec is codec_v5
        while not session.closed:
            if buf:
                tok = obs.span_begin("stage_wire_parse_ms")
                try:
                    table, nrec, consumed = fastpath.parse_batch(
                        buf, max_frame_size, v5)
                finally:
                    obs.span_end("stage_wire_parse_ms", tok)
                end = nrec * REC_SIZE
                off = 0
                fast_gate = nrec > 0 and session.wire_fast_ready()
                while off < end:
                    if fast_gate:
                        ran = wire_run(session, buf, table, off, end,
                                       FRAME_RUN - frames_run)
                        frames_run += (ran - off) // REC_SIZE
                        off = ran
                    elif gov is not None and gov.level == 1:
                        # the governor at level 1: the next records'
                        # publishes pay their reader pauses as one and
                        # run on the wire plane together
                        stop = await session.wire_pause(
                            table, off, min(end, off + FRAME_RUN * REC_SIZE))
                        if session.closed:
                            break
                        if stop > off:
                            ran = wire_run(session, buf, table, off, stop,
                                           FRAME_RUN)
                            frames_run = (ran - off) // REC_SIZE
                            off = ran
                            fast_gate = session.wire_fast_ready()
                            if off == stop:
                                if frames_run >= FRAME_RUN:
                                    # a full stretch that owed no pause
                                    # (acks alone): yield all the same
                                    frames_run = 0
                                    await asyncio.sleep(0)
                                continue
                    if off < end and frames_run < FRAME_RUN:
                        # the record the fast run stopped at
                        rec = _unpack_rec(table, off)
                        if (fast_gate and rec[0] == fastpath.K_PUB
                                and rec[1] in _FAST_QOS_FLAGS
                                and session.wire_inflight >= FRAME_RUN):
                            # under the batched view admitted publishes
                            # are out with the collector while the
                            # reader runs on: at the run bound it waits
                            # for them, then re-passes the gate and
                            # offers the record to the fast run again
                            await session.wire_drain()
                            if session.closed:
                                break
                            fast_gate = session.wire_fast_ready()
                            continue
                        if session.wire_inflight:
                            # what the classic handler runs must see
                            # every earlier publish routed and
                            # acknowledged, as when the task awaited
                            # each one
                            await session.wire_drain()
                            if session.closed:
                                break
                        try:
                            frame = fastpath.materialize(
                                codec, buf, rec, max_frame_size)
                        except ParseError as e:
                            if e.reason == "frame_too_large":
                                # the metric monitoring keys on, now
                                # that the parser (not the session
                                # payload check) is the enforcement
                                # point
                                metrics.incr("mqtt_invalid_msg_size_error")
                                if session.proto_ver == PROTO_5 \
                                        and not session.closed:
                                    # tell a v5 client WHY before
                                    # dropping the socket (MQTT5
                                    # 3.2.2.3.6 / DISCONNECT 0x95)
                                    await session._disconnect_v5(
                                        RC_PACKET_TOO_LARGE)
                            raise
                        await session.handle_frame(frame)
                        if session.closed:
                            break
                        # every classic frame is an await — policy
                        # (governor level, hooks, tracer) may have moved
                        # while we yielded, so the remaining fast
                        # records must re-pass the gate
                        fast_gate = fast_gate and session.wire_fast_ready()
                        off += REC_SIZE
                        frames_run += 1
                    if frames_run >= FRAME_RUN:
                        # bound the synchronous run per read chunk: a
                        # 64KB chunk can hold ~700 small PUBLISHes, and
                        # a handler that never truly awaits would
                        # process them all in ONE loop callback — a
                        # flood connection must not stall every other
                        # session's IO (and the sysmon sampler) for the
                        # whole chunk
                        frames_run = 0
                        await asyncio.sleep(0)
                        if session.closed:  # closed while yielded
                            break
                        # re-check the batch gate after yielding: the
                        # governor/hooks may have moved while we slept
                        fast_gate = fast_gate and session.wire_fast_ready()
                if session.closed:
                    break
                buf = buf[consumed:] if consumed else buf
            if inline is not None and session.connected:
                # parked here, the protocol serves whole chunks of
                # wire-plane records itself; it comes back with the
                # bytes from the first record that needs this task
                buf = await inline.park(session, buf)
                if not buf:
                    break
                continue
            if session.connected:
                chunk = await read_chunk()
            else:
                # still inside the CONNECT/enhanced-AUTH exchange: keep
                # the pre-init deadline so parked half-auth connections
                # can't pin sockets forever
                chunk = await asyncio.wait_for(read_chunk(), CONNECT_TIMEOUT)
            if not chunk:
                break
            if inline is None:
                metrics.incr("bytes_received", len(chunk))
            buf += chunk
    except (asyncio.TimeoutError, TimeoutError):
        pass
    except ParseError as e:
        log.debug("parse error from %s: %s", peer, e.reason)
        metrics.incr("socket_error")
    except WsError as e:
        log.debug("websocket error from %s: %s", peer, e)
        metrics.incr("socket_error")
    except ConnectionError:
        metrics.incr("socket_error")
    except Exception:
        log.exception("connection handler crashed")
        metrics.incr("socket_error")
    finally:
        if session is not None and not session.closed:
            await session.close("connection_lost")
        transport.close()
        metrics.incr("socket_close")


#: unread bytes a connection may hold for its task before the socket's
#: reading pauses (what asyncio's StreamReader allows: twice its 64 KiB
#: limit); it resumes when the task takes them
READ_HIGH = 2 * 65536


class MqttProtocol(asyncio.Protocol):
    """One ``mqtt`` / ``mqtts`` connection read at the protocol level. It
    owns the inbound buffer and is the ``read_chunk`` of its connection's
    task (``await proto()``: the next bytes, b"" on EOF, the socket's
    error raised). While that task is parked at its steady-state read
    (``park``) a chunk's wire-plane records run HERE (``_serve``), not in
    the task: ``data_received`` lists the connection in its listener's
    inbox and the listener's one callback serves the whole turn's chunks
    first thing in the next turn — a turn's ``recv`` calls stay back to
    back, which is what they cost least at on a sandboxed kernel. The
    task is woken only for what awaits — the first record ``wire_run``
    cannot serve and every byte behind it, a closed gate, EOF, any
    exception of the inline run. While the task runs, or has bytes
    waiting, ``data_received`` only appends: one connection's records
    run in byte order, never on two sides at once."""

    __slots__ = ("server", "transport", "_metrics", "_buf", "_tail",
                 "_session", "_waiter", "_eof", "_exc", "_paused", "_task",
                 "_closed", "_stream")

    def __init__(self, server: "MQTTServer"):
        self.server = server
        self.transport: Optional[asyncio.Transport] = None
        self._metrics = server.broker.metrics
        self._buf = b""    # bytes the task has not seen yet
        self._tail = b""   # the parked task's incomplete frame
        self._session: Optional[Session] = None  # set while parked
        self._waiter: Optional[asyncio.Future] = None
        self._eof = False
        self._exc: Optional[BaseException] = None
        self._paused = False
        self._task: Optional[asyncio.Task] = None
        self._closed: Optional[asyncio.Future] = None
        # the connection's write side, once its task made it
        self._stream: Optional[StreamTransport] = None

    # ------------------------------------------------- asyncio's side

    def connection_made(self, transport) -> None:
        self.transport = transport
        if not self.server._admit(self):
            transport.close()
            return
        loop = asyncio.get_running_loop()
        self._closed = loop.create_future()
        self._task = loop.create_task(self._run())

    def data_received(self, data: bytes) -> None:
        self._metrics.incr("bytes_received", len(data))
        if self._session is not None:
            # parked: the listener serves this turn's chunks together
            if self._buf:
                self._buf += data  # already listed (TLS may call twice)
                return
            srv = self.server
            if not srv._inbox:
                srv._loop.call_soon(srv._serve_inbox)
            srv._inbox.append(self)
            self._buf = data
            return
        self._buf += data
        self._wake()
        if not self._paused and len(self._buf) > READ_HIGH:
            self._paused = True
            self.transport.pause_reading()

    def eof_received(self) -> bool:
        self._eof = True
        self._wake()
        # keep a plain socket open for what the task still writes (it
        # closes on its way out); TLS has no half-close
        return self.transport.get_extra_info("sslcontext") is None

    def connection_lost(self, exc) -> None:
        if self._stream is not None:
            # the writer thread drops what it holds for this socket and
            # closes its descriptor: nothing more is sent
            self._stream.lost()
        self._eof = True
        if exc is not None and self._exc is None:
            self._exc = exc
        self._wake()
        if self._closed is not None and not self._closed.done():
            self._closed.set_result(None)

    # ------------------------------------------------ the inline run

    def _serve(self, session: Session, data: bytes, gate: int) -> None:
        """One recv chunk while the task is parked (from the listener's
        ``_serve_inbox``, which evaluated ``gate``, the broker-wide half
        of ``wire_fast_ready``, once for the pass): the session's half
        of the gate, the batch parse, the fast records. Served whole,
        nobody is woken; else the task gets the bytes from the first
        record this could not serve (a closed gate: all of them; the
        governor at level 1, ``WIRE_PAUSED``: all of a chunk that holds
        a PUBLISH, whose pause only a task can sleep — a chunk of acks
        is served here as ever)."""
        buf = self._tail + data if self._tail else data
        try:
            if gate and session.wire_session_ready():
                tok = obs.span_begin("stage_wire_parse_ms")
                try:
                    table, nrec, consumed = fastpath.parse_batch(
                        buf, self.server.max_frame_size,
                        session.proto_ver == PROTO_5)
                finally:
                    obs.span_end("stage_wire_parse_ms", tok)
                end = nrec * REC_SIZE
                off = 0
                if gate == WIRE_OPEN or not _owes_pause(table, end):
                    off = wire_run(session, buf, table, 0, end, FRAME_RUN)
                if off == end:
                    # an incomplete frame at the tail waits here for
                    # the next chunk
                    self._tail = buf[consumed:]
                    fastpath.inline_chunks += 1
                    return
                buf = buf[_unpack_rec(table, off)[3]:]
        except Exception as e:
            # the task ends the connection through its own handlers and
            # counters; nothing is left to asyncio's "Fatal error:
            # protocol.data_received() call failed"
            self._exc = e
        self._tail = b""
        self._buf = buf
        fastpath.task_chunks += 1
        self._wake()

    def _wake(self) -> None:
        self._session = None
        waiter = self._waiter
        if waiter is not None and not waiter.done():
            waiter.set_result(None)

    # ------------------------------------------------- the task's side

    async def park(self, session: Optional[Session] = None,
                   tail: bytes = b"") -> bytes:
        """The task's read: ``tail`` (the incomplete frame it holds)
        plus the bytes that came since, b"" on EOF. With ``session`` —
        the steady-state read — chunks are served by the protocol
        (``_serve``) for as long as this waits."""
        if not self._buf and not self._eof and self._exc is None:
            self._tail = tail
            self._waiter = asyncio.get_running_loop().create_future()
            self._session = session
            try:
                await self._waiter
            finally:
                self._waiter = self._session = None
            tail, self._tail = self._tail, b""
        if self._exc is not None:
            raise self._exc
        data, self._buf = self._buf, b""
        if not data:
            return b""  # EOF: an incomplete frame goes with the socket
        if self._paused:
            self._paused = False
            self.transport.resume_reading()
        return tail + data if tail else data

    __call__ = park

    async def _run(self) -> None:
        srv = self.server
        transport = self.transport
        try:
            if srv._nodelay is not None:
                _apply_nodelay(transport, srv._nodelay)
            from .ssl_util import preauth_from_cert

            ok, preauth = preauth_from_cert(
                transport, srv.use_identity_as_username, srv.ssl_context)
            if not ok:
                transport.close()  # cert required for identity mapping
                return
            self._stream = StreamTransport(transport, srv.broker.outbox)
            await mqtt_connection(
                srv.broker, self, self._stream,
                transport.get_extra_info("peername") or ("", 0),
                srv.max_frame_size, preauth_user=preauth,
                mountpoint=srv.mountpoint,
                allowed_protocol_versions=srv.allowed_protocol_versions)
        finally:
            if not transport.is_closing():
                transport.close()
            try:
                await self._closed
            finally:
                srv._release(self)


class MQTTServer:
    def __init__(self, broker: Broker, host: str = "127.0.0.1", port: int = 1883,
                 max_frame_size: int = 0, ssl_context=None,
                 proxy_protocol: bool = False,
                 use_identity_as_username: bool = False,
                 mountpoint: str = "",
                 allowed_protocol_versions=None,
                 max_connections: int = 0,
                 reuse_port: bool = False):
        self.broker = broker
        self.host = host
        self.port = port
        # per-listener override, else the broker-wide max_message_size
        # (the reference's semantic: vmq_parser.erl enforces it on every
        # packet type as a REMAINING-LENGTH cap — total accepted bytes
        # are at most cap + 5B of fixed header, the lenient direction
        # the spec allows relative to the announced value)
        self.max_frame_size = (max_frame_size
                               or broker.config.get("max_message_size", 0)
                               or MAX_FRAME_SIZE)
        self.ssl_context = ssl_context
        self.proxy_protocol = proxy_protocol
        self.use_identity_as_username = use_identity_as_username
        self.mountpoint = mountpoint
        self.allowed_protocol_versions = (
            tuple(allowed_protocol_versions)
            if allowed_protocol_versions else None)
        self.max_connections = int(max_connections or 0)
        self.connection_count = 0
        # SO_REUSEPORT lets N worker processes share one listen port with
        # kernel-level accept balancing (the multi-process scale-out path,
        # broker/workers.py — the vmq_ranch all-schedulers seat)
        self.reuse_port = reuse_port
        # parsed once at listener construction — the accept path only
        # applies the cached flag
        self._nodelay = parse_nodelay_option(
            str(broker.config.get("tcp_listen_options", "") or ""))
        self._server: Optional[asyncio.AbstractServer] = None
        # live accepted connections, each with the ``.transport`` that
        # close_server aborts: protocols, or a PROXY listener's writers
        self._writers: set = set()
        # parked connections that received a chunk this loop turn
        self._inbox: list = []
        self._loop: Optional[asyncio.AbstractEventLoop] = None

    async def start(self) -> None:
        self._loop = asyncio.get_running_loop()
        if self.proxy_protocol:
            # the PROXY header is read through a stream reader first
            self._server = await asyncio.start_server(
                self._handle_conn, self.host, self.port,
                ssl=self.ssl_context, reuse_port=self.reuse_port or None)
        else:
            self._server = await self._loop.create_server(
                lambda: MqttProtocol(self), self.host, self.port,
                ssl=self.ssl_context, reuse_port=self.reuse_port or None)
        if self.port == 0:
            self.port = self._server.sockets[0].getsockname()[1]
        self.broker._servers.append(self._server)

    async def stop(self) -> None:
        await close_server(self._server, self._writers)

    def _serve_inbox(self) -> None:
        """The second phase of a loop turn's reads: every chunk that
        reached a parked connection in that turn, served in arrival
        order by this one callback — scheduled by the first of them, so
        it runs ahead of the next turn's reads and timers. A connection
        woken meanwhile (EOF, a lost socket) keeps its bytes for its
        task. The broker-wide half of the wire gate is evaluated ONCE
        for the pass (``wire_broker_ready``: why nothing in the pass
        can move it); each chunk tests its session's half. What the
        pass wrote (the trie view routes and acknowledges inline)
        leaves at its end, ahead of the turn's reads."""
        inbox, self._inbox = self._inbox, []
        gate = wire_gate(self.broker)
        for proto in inbox:
            session = proto._session
            if session is not None:
                data, proto._buf = proto._buf, b""
                proto._serve(session, data, gate)
        self.broker.outbox.flush()

    def _admit(self, conn) -> bool:
        """Count an accepted connection in, unless the listener is at
        its cap (listener.*.max_connections): the caller then refuses
        it at accept like ranch's max_connections."""
        if (self.max_connections
                and self.connection_count >= self.max_connections):
            self.broker.metrics.incr("socket_error")
            return False
        self.connection_count += 1
        self._writers.add(conn)
        return True

    def _release(self, conn) -> None:
        self._writers.discard(conn)
        self.connection_count -= 1

    async def _handle_conn(
        self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter
    ) -> None:
        """A PROXY-protocol listener's connection: the header through
        the stream reader, then the shared loop over ``reader.read``."""
        if not self._admit(writer):
            writer.close()
            return
        try:
            await self._handle_conn_inner(reader, writer)
        finally:
            self._release(writer)

    async def _handle_conn_inner(
        self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter
    ) -> None:
        from .proxy_proto import ProxyProtoError, read_proxy_header

        peer = writer.get_extra_info("peername") or ("", 0)
        if self._nodelay is not None:
            _apply_nodelay(writer.transport, self._nodelay)
        preauth: Optional[str] = None
        try:
            info = await asyncio.wait_for(read_proxy_header(reader),
                                          CONNECT_TIMEOUT)
        except (ProxyProtoError, asyncio.TimeoutError, ConnectionError,
                asyncio.IncompleteReadError, asyncio.LimitOverrunError):
            writer.close()
            return
        if info.src is not None:
            peer = info.src
        if self.use_identity_as_username:
            if not info.cn:
                # identity mapping requires the PP2 SSL CN TLV — same
                # policy as the TLS path (no silent fall-through)
                writer.close()
                return
            preauth = info.cn
        try:
            await mqtt_connection(
                self.broker, lambda: reader.read(65536),
                StreamTransport(writer.transport, self.broker.outbox), peer,
                self.max_frame_size, preauth_user=preauth,
                mountpoint=self.mountpoint,
                allowed_protocol_versions=self.allowed_protocol_versions)
        finally:
            try:
                await writer.wait_closed()
            except Exception:
                pass


async def start_broker(
    config=None, host: str = "127.0.0.1", port: int = 1883,
    node_name: str = "node1",
    cluster_listen: Optional[Tuple[str, int]] = None,
    join: Optional[Tuple[str, int]] = None,
    reuse_port: bool = False,
) -> Tuple[Broker, MQTTServer]:
    """Boot a broker with one MQTT listener (vmq_test_utils:setup-style
    convenience; port=0 picks a random free port). ``cluster_listen``
    additionally starts the inter-node channel listener (the reference's
    ``vmq`` listener type, vmq_ranch_config.erl:224-227); ``join`` dials a
    seed node. ``reuse_port`` lets worker processes share the MQTT port
    (broker/workers.py)."""
    broker = Broker(config, node_name=node_name)
    await broker.start()
    from .listeners import ListenerManager

    manager = ListenerManager(broker)
    server = await manager.start_listener(
        "mqtt", host, port, {"reuse_port": reuse_port} if reuse_port else None)
    if cluster_listen is not None:
        from ..cluster import Cluster

        cluster = Cluster(broker, cluster_listen[0], cluster_listen[1])
        await cluster.start()
        if join is not None:
            cluster.join(*join)
    return broker, server


def main() -> None:  # pragma: no cover
    import argparse

    parser = argparse.ArgumentParser(description="vernemq_tpu broker")
    parser.add_argument("--conf", default=None, metavar="PATH",
                        help="vernemq.conf-style config file (broker/conf.py)")
    parser.add_argument("--allow-anonymous", action="store_true",
                        help="accept connects without an auth plugin "
                             "(allow_anonymous=on)")
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--port", type=int, default=1883)
    parser.add_argument("--reg-view", default=None, choices=["trie", "tpu"],
                        help="subscription matcher (the default_reg_view "
                             "seam); overrides --conf when given")
    parser.add_argument("--tpu-mesh", default=None, metavar="BxS",
                        help="serve matching on a device mesh (e.g. 2x4: "
                             "batch x sub axes; implies --reg-view tpu)")
    parser.add_argument("--jax-platform", default=None,
                        help="force the JAX backend (e.g. cpu)")
    parser.add_argument("--node-name", default="node1")
    parser.add_argument("--http-port", type=int, default=None,
                        help="start the HTTP endpoint (metrics/health/"
                             "status/mgmt API) on this port")
    parser.add_argument("--no-mgmt-auth", action="store_true",
                        help="disable api-key auth on the management API")
    parser.add_argument("--cluster-listen", default=None, metavar="HOST:PORT",
                        help="start the inter-node cluster listener")
    parser.add_argument("--join", default=None, metavar="HOST:PORT",
                        help="join an existing cluster via this seed node")
    args = parser.parse_args()
    if args.jax_platform:
        import jax

        jax.config.update("jax_platforms", args.jax_platform)
    if args.reg_view == "tpu" or args.tpu_mesh:
        from ..utils.compile_cache import configure_compile_cache

        configure_compile_cache()

    def _addr(s):
        h, _, p = s.rpartition(":")
        return (h or "127.0.0.1", int(p))

    async def _run():
        from .config import Config

        cfg = Config.from_file(args.conf) if args.conf else Config()
        if args.reg_view:
            cfg.set("default_reg_view", args.reg_view)
        if args.tpu_mesh:
            if args.reg_view == "trie":
                parser.error("--tpu-mesh requires the tpu reg view; "
                             "drop --reg-view trie")
            cfg.set("tpu_mesh", args.tpu_mesh)
            cfg.set("default_reg_view", "tpu")
        if args.allow_anonymous:
            cfg.set("allow_anonymous", True)
        if args.http_port is not None:
            cfg.set("http_enabled", True)
            cfg.set("http_port", args.http_port)
            cfg.set("http_host", args.host)
        if args.no_mgmt_auth:
            cfg.set("http_mgmt_api_auth", False)
        broker, server = await start_broker(
            cfg, host=args.host,
            port=args.port, node_name=args.node_name,
            cluster_listen=_addr(args.cluster_listen) if args.cluster_listen else None,
            join=_addr(args.join) if args.join else None,
        )
        print(f"vernemq_tpu broker {args.node_name} listening on "
              f"{args.host}:{server.port}", flush=True)
        if broker.http is not None:
            print(f"http endpoint on {broker.http.host}:{broker.http.port}",
                  flush=True)
        if broker.cluster is not None:
            print(f"cluster listener on {broker.cluster.listen_host}:"
                  f"{broker.cluster.listen_port}", flush=True)
        await asyncio.Event().wait()

    asyncio.run(_run())


if __name__ == "__main__":  # pragma: no cover
    main()
