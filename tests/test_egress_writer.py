"""The outbox's native writer thread (``native/egress.cc``) on real
loopback sockets: what each peer reads is what the connection's
sequential writes would have sent, through a stalled reader, a close
with bytes pending, a lost connection and a reused descriptor number;
a TLS or socketless transport stays on the loop; the broker's stop joins
the thread."""

import asyncio
import os
import random
import socket
import ssl

import pytest

from vernemq_tpu.broker.config import Config
from vernemq_tpu.broker.egress import JOIN_MAX, Outbox, StreamTransport
from vernemq_tpu.broker.metrics import Metrics
from vernemq_tpu.broker.server import start_broker
from vernemq_tpu.client import MQTTClient
from vernemq_tpu.protocol import fastpath

import test_wire_plane  # the outbox cases and SockSpy (tests dir on path)

SSL_DIR = os.path.join(os.path.dirname(__file__), "ssl")


class Conn(asyncio.Protocol):
    """A listener's side of one connection, written through a
    ``StreamTransport`` and told of its loss as ``MqttProtocol`` is."""

    def __init__(self, outbox, accepted):
        self.outbox = outbox
        self.accepted = accepted
        self.stream = None
        self.fd = -1
        self.gone = asyncio.get_running_loop().create_future()

    def connection_made(self, transport):
        sock = transport.get_extra_info("socket")
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 4096)
        self.fd = sock.fileno()
        self.stream = StreamTransport(transport, self.outbox)
        self.accepted.put_nowait(self)

    def connection_lost(self, exc):
        self.stream.lost()
        if not self.gone.done():
            self.gone.set_result(exc)


class Rig:
    """A listener whose connections write through ``outbox``, and
    non-blocking client sockets read on the loop."""

    def __init__(self, outbox):
        self.outbox = outbox
        self.accepted = asyncio.Queue()
        self.server = None
        self.port = 0

    async def __aenter__(self):
        loop = asyncio.get_running_loop()
        self.server = await loop.create_server(
            lambda: Conn(self.outbox, self.accepted), "127.0.0.1", 0)
        self.port = self.server.sockets[0].getsockname()[1]
        return self

    async def __aexit__(self, *exc):
        self.server.close()

    async def connect(self, rcvbuf=0, c=None):
        """(client socket, the listener's Conn); ``c``: a socket made
        beforehand, so that the accept takes the lowest free number."""
        loop = asyncio.get_running_loop()
        c = c or socket.socket()
        if rcvbuf:
            c.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, rcvbuf)
        c.setblocking(False)
        await loop.sock_connect(c, ("127.0.0.1", self.port))
        conn = await asyncio.wait_for(self.accepted.get(), 5)
        return c, conn


async def read_exactly(sock, n, timeout=10.0):
    loop = asyncio.get_running_loop()
    got = bytearray()
    while len(got) < n:
        chunk = await asyncio.wait_for(loop.sock_recv(sock, 1 << 16), timeout)
        assert chunk, "EOF after %d of %d bytes" % (len(got), n)
        got += chunk
    return bytes(got)


async def read_to_eof(sock, timeout=10.0):
    loop = asyncio.get_running_loop()
    got = bytearray()
    while True:
        chunk = await asyncio.wait_for(loop.sock_recv(sock, 1 << 16), timeout)
        if not chunk:
            return bytes(got)
        got += chunk


def started_outbox():
    ob = Outbox(Metrics())
    ob.start()
    assert ob._writer is not None
    return ob


def chunks_for(rng, tag):
    """One flush's worth of a connection's writes: frames of 1 B to 9 KiB
    (some past JOIN_MAX), single chunks and iovecs, bytes that name
    their connection and position."""
    out = []
    for _ in range(rng.randrange(1, 5)):
        n = rng.choice((1, 4, 30, 700, JOIN_MAX - 10, JOIN_MAX + 500, 9000))
        body = (tag * (n // len(tag) + 1))[:n]
        if rng.random() < 0.5:
            out.append(("write", body))
        else:
            cut = rng.randrange(0, n + 1)
            out.append(("iov", (body[:cut], memoryview(body)[cut:])))
    return out


def play(stream, writes):
    for kind, data in writes:
        if kind == "write":
            stream.write(data)
        else:
            stream.write_iov(data)


def expected(writes):
    return b"".join(bytes(d) if k == "write" else b"".join(map(bytes, d))
                    for k, d in writes)


async def settle(cond, timeout=5.0):
    loop = asyncio.get_running_loop()
    end = loop.time() + timeout
    while not cond():
        if loop.time() > end:
            return False
        await asyncio.sleep(0.01)
    return True


def open_fds():
    return len(os.listdir("/proc/self/fd"))


# ------------------------------------------------------------------ cases


async def _sequential_bytes_over_many_flushes():
    """(a) Eight connections, 60 flushes of mixed frames each: every peer
    reads exactly the concatenation of its connection's writes, and
    every transport of every flush went to the writer."""
    ob = started_outbox()
    rng = random.Random(37)
    try:
        async with Rig(ob) as rig:
            pairs = [await rig.connect() for _ in range(8)]
            assert all(conn.stream._wid for _, conn in pairs)
            want = [b""] * len(pairs)
            writes0 = fastpath.egress_writes
            handed0 = fastpath.egress_offload_writes
            ob._fold()
            sent0 = fastpath.egress_offload_sent
            for flush in range(60):
                for i, (_, conn) in enumerate(pairs):
                    ws = chunks_for(rng, b"%d:%d;" % (i, flush))
                    play(conn.stream, ws)
                    want[i] += expected(ws)
                ob.flush()
            readers = [read_exactly(c, len(w)) for (c, _), w in
                       zip(pairs, want)]
            got = await asyncio.gather(*readers)
            assert got == want
            assert fastpath.egress_writes - writes0 == 60 * 8
            assert fastpath.egress_offload_writes - handed0 == 60 * 8
            # every hand-off finished: the writer counted each once
            assert await settle(
                lambda: (ob._fold() or True)
                and fastpath.egress_offload_sent - sent0 == 60 * 8)
            for c, conn in pairs:
                conn.stream.close()
                c.close()
    finally:
        ob.close()


async def _backlog_builds_then_drains_in_order():
    """(b) A 4 KiB send buffer and a reader that stalls: the hand-offs
    queue in the writer (the flush still returns at once), then drain in
    order once the reader reads — nothing lost, nothing twice."""
    ob = started_outbox()
    try:
        async with Rig(ob) as rig:
            c, conn = await rig.connect(rcvbuf=4096)
            ob._fold()
            sent0 = fastpath.egress_offload_sent
            lag0 = fastpath.egress_offload_lag_us
            want = b""
            for i in range(200):
                frame = b"<%06d>" % i * 150  # 1,200 B a flush
                conn.stream.write(frame)
                want += frame
                ob.flush()
            await asyncio.sleep(0.2)
            ob._fold()
            # the peer has read nothing: the socket holds a few of the
            # 240 KB, the writer's backlog the rest
            assert fastpath.egress_offload_sent - sent0 < 100
            got = await read_exactly(c, len(want))
            assert got == want
            assert await settle(lambda: (ob._fold() or True) and
                                fastpath.egress_offload_sent - sent0 == 200)
            assert fastpath.egress_offload_lag_us - lag0 > 200 * 1000
            conn.stream.close()
            assert await read_to_eof(c) == b""
            c.close()
    finally:
        ob.close()


async def _close_with_bytes_pending_delivers_then_eof():
    """(c) ``close()`` while a stalled peer holds most of 300 KB back:
    the peer then reads every byte, in order, and after them EOF."""
    ob = started_outbox()
    try:
        async with Rig(ob) as rig:
            c, conn = await rig.connect(rcvbuf=4096)
            want = b""
            for i in range(100):
                frame = b"[%05d]" % i * 430
                conn.stream.write_iov((frame[:7], frame[7:]))
                want += frame
                ob.flush()
            conn.stream.write(b"last")
            want += b"last"
            conn.stream.close()  # pending: the last write and a backlog
            await asyncio.wait_for(conn.gone, 5)  # asyncio's side is shut
            assert await read_to_eof(c) == want
            c.close()
    finally:
        ob.close()


async def _peer_reset_drops_backlog_and_releases_descriptor():
    """(d) 200 connections, each with a backlog its stalled peer never
    reads, reset by the peer: the loss drops each backlog and releases
    the writer's descriptor — as many descriptors open after as before."""
    ob = started_outbox()
    try:
        async with Rig(ob) as rig:
            base = open_fds()
            dropped0 = fastpath.egress_offload_dropped
            for i in range(200):
                c, conn = await rig.connect(rcvbuf=4096)
                for _ in range(8):
                    conn.stream.write(b"x" * 16384)
                    ob.flush()
                c.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                             b"\x01\x00\x00\x00\x00\x00\x00\x00")
                c.close()  # RST
                await asyncio.wait_for(conn.gone, 5)
                assert conn.stream.closed and not conn.stream._wid
            assert await settle(lambda: open_fds() <= base), \
                (open_fds(), base)
            ob._fold()
            assert fastpath.egress_offload_dropped > dropped0
    finally:
        ob.close()


async def _reused_descriptor_sees_no_stale_byte():
    """(e) A connection closed with bytes pending, then new accepts that
    take its descriptor number: each new peer reads exactly its own
    bytes; the old peer, unstalled, reads all of its own, then EOF."""
    ob = started_outbox()
    reused = 0
    try:
        async with Rig(ob) as rig:
            for round_ in range(10):
                old, conn = await rig.connect(rcvbuf=4096)
                old_fd = conn.fd
                want_old = b""
                for i in range(40):
                    frame = b"A%d." % round_ * 1000
                    conn.stream.write(frame)
                    want_old += frame
                    ob.flush()
                client = socket.socket()  # its number taken first
                conn.stream.close()
                await asyncio.wait_for(conn.gone, 5)
                await asyncio.sleep(0)  # asyncio closes its socket now
                new, conn2 = await rig.connect(c=client)
                reused += conn2.fd == old_fd
                conn2.stream.write(b"B" * 5000)
                ob.flush()
                conn2.stream.close()
                assert await read_to_eof(new) == b"B" * 5000
                assert await read_to_eof(old) == want_old
                old.close()
                new.close()
            assert reused, "no accept reused a closed connection's number"
    finally:
        ob.close()


async def _no_native_writes_the_same_bytes_on_the_loop(monkeypatch):
    """(f) With ``VMQ_NO_NATIVE=1`` there is no writer: every transport
    is written on the loop, and the peers read the same bytes as (a)'s
    writes give."""
    monkeypatch.setenv("VMQ_NO_NATIVE", "1")
    ob = Outbox(Metrics())
    ob.start()
    assert ob._writer is None
    rng = random.Random(38)
    async with Rig(ob) as rig:
        pairs = [await rig.connect() for _ in range(4)]
        assert not any(conn.stream._wid for _, conn in pairs)
        handed0 = fastpath.egress_offload_writes
        want = [b""] * len(pairs)
        for flush in range(20):
            for i, (_, conn) in enumerate(pairs):
                ws = chunks_for(rng, b"%d:%d;" % (i, flush))
                play(conn.stream, ws)
                want[i] += expected(ws)
            ob.flush()
        got = await asyncio.gather(*[read_exactly(c, len(w)) for (c, _), w
                                     in zip(pairs, want)])
        assert got == want
        assert fastpath.egress_offload_writes == handed0
        for c, conn in pairs:
            conn.stream.close()
            c.close()
    ob.close()


async def _tls_and_fake_transports_stay_on_the_loop(monkeypatch):
    """(g) A TLS connection and a fixture's transport are never attached
    to a running writer, and the wire plane's seven outbox cases pass
    unchanged with a writer running in each of their outboxes."""
    ob = started_outbox()
    try:
        sctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
        sctx.load_cert_chain(os.path.join(SSL_DIR, "server.crt"),
                             os.path.join(SSL_DIR, "server.key"))
        cctx = ssl.SSLContext(ssl.PROTOCOL_TLS_CLIENT)
        cctx.load_verify_locations(os.path.join(SSL_DIR, "ca.crt"))
        cctx.check_hostname = False
        accepted = asyncio.Queue()
        loop = asyncio.get_running_loop()
        server = await loop.create_server(
            lambda: Conn(ob, accepted), "127.0.0.1", 0, ssl=sctx)
        port = server.sockets[0].getsockname()[1]
        reader, writer = await asyncio.open_connection(
            "127.0.0.1", port, ssl=cctx)
        conn = await asyncio.wait_for(accepted.get(), 5)
        assert conn.stream._wid == 0
        handed0 = fastpath.egress_offload_writes
        conn.stream.write(b"over tls")
        ob.flush()
        assert await asyncio.wait_for(reader.readexactly(8), 5) == \
            b"over tls"
        assert fastpath.egress_offload_writes == handed0
        writer.close()
        conn.stream.close()
        server.close()
        assert StreamTransport(test_wire_plane.SockSpy(), ob)._wid == 0
    finally:
        ob.close()
    made = []

    def outbox_with_writer():
        o = started_outbox()
        made.append(o)
        return o

    monkeypatch.setattr(test_wire_plane, "_outbox", outbox_with_writer)
    try:
        for name in sorted(test_wire_plane.OUTBOX_CASES):
            await test_wire_plane.OUTBOX_CASES[name]()
    finally:
        for o in made:
            o.close()
    assert made


def native_threads():
    return len(os.listdir("/proc/self/task"))


async def _broker_stop_joins_the_thread():
    """(h) The broker starts the writer with itself and joins it at its
    stop; in between a publish reaches its subscriber through it, and a
    reset client's descriptor is released."""
    before = native_threads()
    broker, server = await start_broker(
        Config(allow_anonymous=True, systree_enabled=False), port=0,
        node_name="egress")
    stopped = False
    try:
        assert broker.outbox._writer is not None
        sub = MQTTClient("127.0.0.1", server.port, client_id="ew-sub")
        await sub.connect()
        await sub.subscribe("ew/#", qos=1)
        pub = MQTTClient("127.0.0.1", server.port, client_id="ew-pub")
        await pub.connect()
        handed0 = fastpath.egress_offload_writes
        await pub.publish("ew/1", b"through the writer", qos=1)
        msg = await asyncio.wait_for(sub.messages.get(), 5)
        assert msg.payload == b"through the writer"
        assert fastpath.egress_offload_writes > handed0
        base = open_fds()
        raw = socket.create_connection(("127.0.0.1", server.port))
        await asyncio.sleep(0.05)
        raw.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                       b"\x01\x00\x00\x00\x00\x00\x00\x00")
        raw.close()
        assert await settle(lambda: open_fds() <= base), (open_fds(), base)
        await pub.disconnect()
        await sub.disconnect()
        await broker.stop()
        await server.stop()
        stopped = True
        assert broker.outbox._writer is None
        assert await settle(lambda: native_threads() <= before), \
            (native_threads(), before)
    finally:
        if not stopped:
            await broker.stop()
            await server.stop()


CASES = {
    "a_sequential_bytes": _sequential_bytes_over_many_flushes,
    "b_backlog_drains_in_order": _backlog_builds_then_drains_in_order,
    "c_close_delivers_then_eof": _close_with_bytes_pending_delivers_then_eof,
    "d_reset_releases_descriptor":
        _peer_reset_drops_backlog_and_releases_descriptor,
    "e_reused_number_no_stale_byte": _reused_descriptor_sees_no_stale_byte,
    "f_no_native_loop_path": _no_native_writes_the_same_bytes_on_the_loop,
    "g_tls_and_fake_stay_on_loop": _tls_and_fake_transports_stay_on_the_loop,
    "h_broker_stop_joins_thread": _broker_stop_joins_the_thread,
}


@pytest.mark.asyncio
@pytest.mark.parametrize("case", sorted(CASES))
async def test_egress_writer(case, monkeypatch):
    """The outbox's writer thread keeps each connection's bytes those of
    its sequential writes, releases every descriptor it takes, and lives
    exactly as long as its broker."""
    fn = CASES[case]
    if "monkeypatch" in fn.__code__.co_varnames[:fn.__code__.co_argcount]:
        await fn(monkeypatch)
    else:
        await fn()
