"""Wire plane end-to-end: the QoS0 object-free fast path, iovec
transport flush, byte identity with the native codec forcibly absent,
and the wire.parse/wire.encode fault seam degrading to the pure codec.

The frame-table/codec differential fuzz lives in test_native_codec.py;
this file covers the broker-side behaviour of the plane.
"""

import asyncio
import contextlib

import pytest

from vernemq_tpu.broker.config import Config
from vernemq_tpu.broker.egress import JOIN_MAX, Outbox
from vernemq_tpu.broker.metrics import Metrics
from vernemq_tpu.broker.server import StreamTransport, start_broker
from vernemq_tpu.broker.session import WIRE_CLOSED, WIRE_OPEN, WIRE_PAUSED
from vernemq_tpu.client import MQTTClient
from vernemq_tpu.protocol import codec_v4, codec_v5, fastpath, wire
from vernemq_tpu.protocol.types import (Connect, Puback, Publish, SubOpts,
                                        Subscribe)


@contextlib.contextmanager
def pure_mode():
    """Force the whole wire plane pure-Python — the native module
    'forcibly absent' posture the build/CI satellite asserts against."""
    saved = (codec_v4._C, codec_v5._C, fastpath._force_pure)
    codec_v4._C = None
    codec_v5._C = None
    fastpath._force_pure = True
    try:
        yield
    finally:
        codec_v4._C, codec_v5._C, fastpath._force_pure = saved


async def boot(**cfg):
    cfg.setdefault("allow_anonymous", True)
    cfg.setdefault("systree_enabled", False)
    return await start_broker(Config(**cfg), port=0, node_name="wire")


class Raw:
    """Raw-socket MQTT endpoint: scripted bytes out, captured bytes in
    (the byte-identity assertions need the exact stream, not frames)."""

    def __init__(self, reader, writer):
        self.reader = reader
        self.writer = writer
        self.buf = b""

    @classmethod
    async def connect(cls, port, client_id, ssl=None, preamble=b""):
        """``preamble``: what the listener reads before MQTT (a PROXY
        header)."""
        r, w = await asyncio.open_connection("127.0.0.1", port, ssl=ssl)
        self = cls(r, w)
        await self.send(preamble + codec_v4.serialise(Connect(
            client_id=client_id, keepalive=0, clean_start=True)))
        await self.read_frames(1)  # CONNACK
        return self

    async def send(self, data: bytes) -> None:
        self.writer.write(data)
        await self.writer.drain()

    async def read_frames(self, n, timeout=5.0):
        """Read until ``n`` complete frames are buffered; returns the
        parsed frames (pure codec) WITHOUT consuming self.buf — the
        captured stream stays intact for byte comparison."""
        deadline = asyncio.get_event_loop().time() + timeout

        def complete():
            got, rest = 0, self.buf
            while True:
                split = wire.split_frame(rest)
                if split is None:
                    return got
                got += 1
                rest = split[3]

        while complete() < n:
            t = deadline - asyncio.get_event_loop().time()
            if t <= 0:
                raise asyncio.TimeoutError(
                    f"wanted {n} frames, have {complete()}")
            chunk = await asyncio.wait_for(self.reader.read(65536), t)
            if not chunk:
                break
            self.buf += chunk
        frames, rest = [], self.buf
        saved, codec_v4._C = codec_v4._C, None
        try:
            while len(frames) < n:
                f, rest = codec_v4.parse(rest)
                assert f is not None
                frames.append(f)
        finally:
            codec_v4._C = saved
        return frames

    def close(self):
        self.writer.close()


@pytest.mark.asyncio
async def test_qos0_fast_path_delivers_with_zero_frame_objects():
    """The acceptance spot test: a 1k-frame QoS0 batch admitted through
    the fast path materialises ZERO Publish frames and ZERO Msg objects
    broker-side, counts in wire_fastpath_pubs, and every payload is
    delivered byte-correct."""
    from vernemq_tpu.broker import message as message_mod

    broker, server = await boot(observability_enabled=False)
    try:
        sub = await Raw.connect(server.port, "zsub")
        await sub.send(codec_v4.serialise(Subscribe(
            packet_id=1, topics=[("t/#", SubOpts(qos=0))])))
        await sub.read_frames(2)  # CONNACK already buffered + SUBACK
        sub_frames_before = 2

        pub = await Raw.connect(server.port, "zpub")
        n = 1000
        blob = b"".join(
            codec_v4.serialise(Publish(topic=f"t/{i % 8}",
                                       payload=b"p%04d" % i, qos=0))
            for i in range(n))
        base_fast = fastpath.fastpath_pubs

        counts = {"publish": 0, "msg": 0}
        pub_init = Publish.__init__
        msg_init = message_mod.Msg.__init__

        def counting_pub(self, *a, **k):
            counts["publish"] += 1
            return pub_init(self, *a, **k)

        def counting_msg(self, *a, **k):
            counts["msg"] += 1
            return msg_init(self, *a, **k)

        Publish.__init__ = counting_pub
        message_mod.Msg.__init__ = counting_msg
        try:
            await pub.send(blob)
            deadline = asyncio.get_event_loop().time() + 10.0
            while (fastpath.fastpath_pubs - base_fast) < n:
                assert asyncio.get_event_loop().time() < deadline, \
                    fastpath.fastpath_pubs - base_fast
                await asyncio.sleep(0.01)
        finally:
            Publish.__init__ = pub_init
            message_mod.Msg.__init__ = msg_init
        assert counts == {"publish": 0, "msg": 0}
        assert fastpath.fastpath_pubs - base_fast == n
        assert broker.metrics.value("mqtt_publish_received") >= n

        frames = await sub.read_frames(sub_frames_before + n)
        payloads = [f.payload for f in frames[sub_frames_before:]]
        assert payloads == [b"p%04d" % i for i in range(n)]
        # the gauge surface carries the counter
        assert broker.registry.stats()["wire_fastpath_pubs"] >= n
        sub.close()
        pub.close()
    finally:
        await broker.stop()
        await server.stop()


#: the two forms a connection is read by: the mqtt listener's
#: protocol-level reader (``MqttProtocol``: fast records run off the
#: task) and a ``read_chunk`` listener (PROXY protocol: the
#: connection's task runs them) — one record function, two callers
READ_FORMS = ["protocol", "read_chunk"]


async def listen(broker, server, form):
    """``(port, connect keywords)`` of a listener read by ``form``."""
    if form == "protocol":
        return server.port, {}
    from vernemq_tpu.broker import proxy_proto

    proxied = await broker.listeners.start_listener(
        "mqtt", "127.0.0.1", 0, {"proxy_protocol": True})
    return proxied.port, {"preamble": proxy_proto.build_v1(
        ("192.0.2.7", 4321), ("10.0.0.1", 1883))}


async def _conversation(port, **kw):
    """One scripted v4 conversation; returns (pub_stream, sub_stream)
    byte captures."""
    sub = await Raw.connect(port, "csub", **kw)
    await sub.send(codec_v4.serialise(Subscribe(
        packet_id=1, topics=[("t/#", SubOpts(qos=1))])))
    await sub.read_frames(2)
    pub = await Raw.connect(port, "cpub", **kw)
    script = (
        codec_v4.serialise(Publish(topic="t/a", payload=b"one", qos=0))
        + codec_v4.serialise(Publish(topic="t/b", payload=b"two",
                                     qos=0))
        + codec_v4.serialise(Publish(topic="t/a", payload=b"three",
                                     qos=1, packet_id=7))
        + b"\xc0\x00"  # PINGREQ
    )
    await pub.send(script)
    await pub.read_frames(1 + 1 + 1)  # CONNACK + PUBACK + PINGRESP
    await sub.read_frames(2 + 3)      # + three PUBLISHes
    pub_bytes, sub_bytes = pub.buf, sub.buf
    pub.close()
    sub.close()
    return pub_bytes, sub_bytes


async def _conversation_on(form, **cfg):
    """``_conversation`` against a fresh broker's ``form`` listener."""
    broker, server = await boot(**cfg)
    try:
        port, kw = await listen(broker, server, form)
        return await _conversation(port, **kw)
    finally:
        await broker.stop()
        await server.stop()


@pytest.mark.asyncio
@pytest.mark.parametrize("form", READ_FORMS)
async def test_wire_identical_with_native_forcibly_absent(form):
    """The PR 7 byte-identity guarantee extended to the codec seam:
    the same conversation yields the identical byte streams whether the
    native codec serves or the pure-Python plane does (fast path ON in
    both — the table walk itself is bit-identical)."""
    native_run = await _conversation_on(form)
    with pure_mode():
        pure_run = await _conversation_on(form)
    assert native_run == pure_run


@pytest.mark.asyncio
@pytest.mark.parametrize("form", READ_FORMS)
async def test_wire_identical_with_fastpath_disabled(form):
    """wire_fastpath_enabled=off (every frame through the classic
    handler) produces the same bytes as the fast path — and admits
    nothing through it."""
    fast_run = await _conversation_on(form)
    base = fastpath.fastpath_pubs
    classic_run = await _conversation_on(form, wire_fastpath_enabled=False)
    assert fastpath.fastpath_pubs == base  # nothing fast-admitted
    assert fast_run == classic_run


@pytest.mark.asyncio
async def test_wire_identical_across_read_forms():
    """The record function's two callers write the same bytes: the
    conversation read by the protocol (its fast records run off the
    task) and read through a ``read_chunk`` by the task."""
    def chunks():
        return fastpath.inline_chunks + fastpath.task_chunks

    base = chunks()
    by_protocol = await _conversation_on("protocol")
    # the script's chunk: three publishes served by the protocol, the
    # PINGREQ behind them handed to the task
    assert chunks() > base
    base = chunks()
    by_task = await _conversation_on("read_chunk")
    assert chunks() == base  # that form has no inline run to count
    assert by_protocol == by_task


@pytest.mark.asyncio
async def test_wire_parse_fault_degrades_to_pure_never_drops():
    """A wire.parse fault drill: native batch calls fail, the breaker
    opens, every batch re-serves through the pure codec — zero lost
    publishes, the connection survives, and the breaker recovers after
    the drill."""
    from vernemq_tpu.robustness import faults
    from vernemq_tpu.robustness.breaker import CircuitBreaker
    from vernemq_tpu.robustness.faults import FaultPlan, FaultRule

    if fastpath.load_native() is None:
        pytest.skip("native codec extension not built")
    saved_breaker = fastpath.breaker
    # test-scoped breaker: low threshold, backoff too long for a
    # half-open probe to race the assertions
    fastpath.breaker = CircuitBreaker(failure_threshold=2,
                                      backoff_initial=60.0)
    broker, server = await boot()
    try:
        sub = MQTTClient("127.0.0.1", server.port, client_id="fsub")
        await sub.connect()
        await sub.subscribe("f/#", qos=0)
        pub = MQTTClient("127.0.0.1", server.port, client_id="fpub")
        await pub.connect()
        errs_before = fastpath.native_errors
        faults.install(FaultPlan([FaultRule(point="wire.parse",
                                            kind="error", count=100)]))
        try:
            for i in range(30):
                await pub.publish("f/t", b"m%d" % i, qos=0)
                # separate recv chunks → separate batches, so the
                # failure run actually accumulates
                await asyncio.sleep(0.005)
            got = set()
            for _ in range(30):
                f = await sub.recv(5.0)
                got.add(f.payload)
            assert got == {b"m%d" % i for i in range(30)}
        finally:
            faults.clear()
        assert fastpath.native_errors - errs_before >= 2
        assert not fastpath.breaker.is_closed  # opened under the drill
        assert fastpath.degraded_batches > 0  # open → pure served
        st = broker.registry.stats()
        assert st["wire_breaker_state"] > 0
        # recovery: reset (the admin drill's exit) and the native path
        # serves again
        fastpath.breaker.reset()
        nb = fastpath.native_batches
        await pub.publish("f/t", b"back", qos=0)
        assert (await sub.recv(5.0)).payload == b"back"
        assert fastpath.native_batches > nb
        await pub.close()
        await sub.close()
    finally:
        fastpath.breaker = saved_breaker
        await broker.stop()
        await server.stop()


@pytest.mark.asyncio
async def test_complex_rows_fall_back_to_exact_msg_path():
    """A v5 session with a maximum_packet_size is fast-admissible when
    the conservative frame bound FITS the cap (wire_v5_fast_ok with
    frame_bound) — small publishes ride the batched encoder and arrive
    byte-correct. An oversize publish flips the whole fanout to the
    classic Msg path, where _plan_v5_delivery measures exactly and
    DROPS the frame for the capped client (MQTT-3.1.2-24) while the v4
    client still gets its frame — semantics over speed."""
    broker, server = await boot()
    try:
        v4sub = MQTTClient("127.0.0.1", server.port, client_id="s4")
        await v4sub.connect()
        await v4sub.subscribe("c/#", qos=0)
        v5sub = MQTTClient("127.0.0.1", server.port, client_id="s5",
                           proto_ver=5)
        await v5sub.connect()
        await v5sub.subscribe("c/#", qos=0)
        capped = await Raw5.connect(server.port, "s5cap",
                                    {"maximum_packet_size": 256})
        await capped.send(codec_v5.serialise(Subscribe(
            packet_id=1, topics=[("c/#", SubOpts(qos=0))])))
        await capped.recv5(1)  # SUBACK
        pub = MQTTClient("127.0.0.1", server.port, client_id="p4")
        await pub.connect()
        # small frame: bound <= cap, the capped session joins the batch
        base_batches = fastpath.fanout_batches
        await pub.publish("c/x", b"mixed", qos=0)
        assert (await v4sub.recv(5.0)).payload == b"mixed"
        assert (await v5sub.recv(5.0)).payload == b"mixed"
        f = (await capped.recv5(1))[0]
        assert f.payload == b"mixed" and f.topic == "c/x"  # byte parity
        assert fastpath.fanout_batches > base_batches  # batch served it
        # oversize frame: bound > cap — classic path, capped client is
        # skipped (a frame over its cap may not be sent), others served
        base_batches = fastpath.fanout_batches
        await pub.publish("c/x", b"x" * 300, qos=0)
        assert (await v4sub.recv(5.0)).payload == b"x" * 300
        assert (await v5sub.recv(5.0)).payload == b"x" * 300
        assert fastpath.fanout_batches == base_batches  # classic fanout
        with pytest.raises(asyncio.TimeoutError):
            await capped.recv5(1, timeout=0.3)
        capped.close()
        # a v5 PUBLISHER with empty props is fast-admittable too
        base = fastpath.fastpath_pubs
        pub5 = MQTTClient("127.0.0.1", server.port, client_id="p5",
                          proto_ver=5)
        await pub5.connect()
        await pub5.publish("c/y", b"from5", qos=0)
        assert (await v4sub.recv(5.0)).payload == b"from5"
        assert (await v5sub.recv(5.0)).payload == b"from5"
        assert fastpath.fastpath_pubs > base
        for c in (v4sub, v5sub, pub, pub5):
            await c.close()
    finally:
        await broker.stop()
        await server.stop()


@pytest.mark.asyncio
async def test_retained_publish_takes_classic_path():
    """The retain bit excludes a frame from the fast path (flags != 0x30):
    retained store semantics are exact."""
    broker, server = await boot()
    try:
        pub = MQTTClient("127.0.0.1", server.port, client_id="rp")
        await pub.connect()
        await pub.publish("r/t", b"keep", qos=0, retain=True)
        await asyncio.sleep(0.05)
        sub = MQTTClient("127.0.0.1", server.port, client_id="rs")
        await sub.connect()
        await sub.subscribe("r/#", qos=0)
        f = await sub.recv(5.0)
        assert f.payload == b"keep" and f.retain
        await pub.close()
        await sub.close()
    finally:
        await broker.stop()
        await server.stop()


@pytest.mark.asyncio
async def test_wire_metrics_and_stage_families_exposed():
    """stage_wire_parse_ms / stage_wire_encode_ms exposition with HELP,
    and the wire_* gauges, after real traffic."""
    broker, server = await boot()
    try:
        sub = MQTTClient("127.0.0.1", server.port, client_id="ms")
        await sub.connect()
        await sub.subscribe("m/#", qos=0)
        pub = MQTTClient("127.0.0.1", server.port, client_id="mp")
        await pub.connect()
        for i in range(5):
            await pub.publish("m/t", b"x%d" % i, qos=0)
        for _ in range(5):
            await sub.recv(5.0)
        text = broker.metrics.prometheus_text()
        assert "# HELP stage_wire_parse_ms " in text
        assert "# TYPE stage_wire_parse_ms histogram" in text
        assert "# HELP stage_wire_encode_ms " in text
        assert "# HELP wire_fastpath_pubs " in text
        assert "# HELP wire_native_batches " in text
        assert "# HELP wire_inline_chunks " in text
        assert "# HELP wire_task_chunks " in text
        snap = broker.metrics.histogram_snapshot()
        assert snap["stage_wire_parse_ms"][2] > 0  # observations landed
        assert snap["stage_wire_encode_ms"][2] > 0
        # $SYS scalar surface
        allm = broker.metrics.all_metrics()
        assert allm["stage_wire_parse_ms_count"] > 0
        await pub.close()
        await sub.close()
    finally:
        await broker.stop()
        await server.stop()


class SockSpy:
    """An asyncio transport's write side, recording each call."""

    def __init__(self, fail=False):
        self.calls = []  # ("write", data) | ("writelines", [chunks])
        self.fail = fail
        self.closed = False

    def write(self, data):
        if self.fail:
            raise OSError("broken pipe")
        self.calls.append(("write", data))

    def writelines(self, chunks):
        if self.fail:
            raise OSError("broken pipe")
        self.calls.append(("writelines", list(chunks)))

    def close(self):
        self.closed = True

    def stream(self):
        return b"".join(bytes(c) for kind, d in self.calls
                        for c in ([d] if kind == "write" else d))


def egress_counts():
    return (fastpath.egress_flushes, fastpath.egress_writes,
            fastpath.egress_joined, fastpath.egress_scattered)


def _outbox():
    return Outbox(Metrics())


async def _outbox_bytes_are_those_of_sequential_writes():
    ob = _outbox()
    socks = [SockSpy(), SockSpy()]
    ts = [StreamTransport(s, ob) for s in socks]
    ts[0].write(b"aa")
    ts[1].write(b"11")
    ts[0].write_iov((b"bb", b"cc"))
    ts[1].write_iov((b"22", memoryview(b"33")))
    ts[0].write(b"dd")
    assert [s.calls for s in socks] == [[], []]  # nothing until the flush
    ob.flush()
    assert [s.stream() for s in socks] == [b"aabbccdd", b"112233"]
    assert [len(s.calls) for s in socks] == [1, 1]  # ONE write each
    ob.flush()  # nothing pending: a no-op
    assert [len(s.calls) for s in socks] == [1, 1]
    ts[0].write(b"ee")
    ob.flush()
    assert socks[0].stream() == b"aabbccddee"
    assert socks[0].calls[-1] == ("write", b"ee")


async def _outbox_schedules_one_callback_a_turn():
    ob = _outbox()
    loop = asyncio.get_running_loop()
    scheduled = []
    call_soon = loop.call_soon

    def counting(cb, *a, **kw):
        if cb == ob.flush:  # asyncio's own task steps are not ours
            scheduled.append(cb)
        return call_soon(cb, *a, **kw)

    loop.call_soon = counting
    try:
        socks = [SockSpy() for _ in range(5)]
        ts = [StreamTransport(s, ob) for s in socks]
        flushes, writes, _, _ = egress_counts()
        for i, t in enumerate(ts):
            t.write(b"%d" % i)
            t.write(b"x")
        ob.touch()  # a counter-only caller adds no second callback
        assert len(scheduled) == 1
        await asyncio.sleep(0)  # the next turn: the callback ran
        assert [s.stream() for s in socks] == \
            [b"%dx" % i for i in range(5)]  # in listing order, each whole
        assert egress_counts()[:2] == (flushes + 1, writes + 5)
        ts[2].write(b"again")  # the next turn's first write lists anew
        assert len(scheduled) == 2
        await asyncio.sleep(0)
        assert socks[2].stream() == b"2xagain"
    finally:
        del loop.call_soon


async def _outbox_joins_small_iovecs_and_scatters_large_ones():
    ob = _outbox()
    small, large, single = SockSpy(), SockSpy(), SockSpy()
    hdr, payload = b"\x32\x0a\x00\x01t\x00\x01", b"p" * 16
    big = b"B" * JOIN_MAX  # header + this is over the bound
    StreamTransport(small, ob).write_iov((hdr, payload))
    StreamTransport(large, ob).write_iov((hdr, big))
    StreamTransport(single, ob).write(big + big)
    _, writes, joined, scattered = egress_counts()
    ob.flush()
    assert small.calls == [("write", hdr + payload)]  # one plain send
    (kind, chunks), = large.calls
    assert kind == "writelines" and chunks[1] is big  # never copied here
    assert single.calls == [("write", big + big)]  # one chunk: as it is
    assert egress_counts()[1:] == (writes + 3, joined + 1, scattered + 1)


async def _outbox_walk_survives_a_raising_transport():
    ob = _outbox()
    socks = [SockSpy(), SockSpy(fail=True), SockSpy()]
    ts = [StreamTransport(s, ob) for s in socks]
    for t in ts:
        t.write(b"one")
    ob.flush()
    assert [s.stream() for s in socks] == [b"one", b"", b"one"]
    assert [t.closed for t in ts] == [False, True, False]
    ts[1].write(b"two")  # a closed transport takes no more writes
    assert ob._handle is None and ts[1]._chunks == []


async def _outbox_close_flushes_and_a_closed_listing_is_skipped():
    ob = _outbox()
    socks = [SockSpy(), SockSpy()]
    ts = [StreamTransport(s, ob) for s in socks]
    ts[0].write_iov((b"will", b"go"))
    ts[1].write(b"stays")
    ts[0].close()  # its own chunks first, then the socket
    assert socks[0].calls == [("write", b"willgo")] and socks[0].closed
    _, writes, _, _ = egress_counts()
    ob.flush()  # still listed, now closed and empty: skipped
    assert len(socks[0].calls) == 1 and socks[1].stream() == b"stays"
    assert egress_counts()[1] == writes + 1
    ts[0].close()  # idempotent
    assert len(socks[0].calls) == 1


async def _outbox_counters_equal_per_write_accounting():
    """A QoS1 publish to one QoS1 and one QoS0 subscriber over real
    sockets: once the bytes are read, the six folded counters have
    moved by exactly what the sockets saw."""
    names = ("bytes_sent", "mqtt_publish_sent", "mqtt_puback_sent",
             "queue_message_in", "queue_message_out",
             "router_matches_local")
    broker, server = await boot()
    try:
        subs = []
        for i in range(2):
            sub = await Raw.connect(server.port, "ecsub%d" % i)
            await sub.send(codec_v4.serialise(Subscribe(
                packet_id=1, topics=[("q/#", SubOpts(qos=i))])))
            await sub.read_frames(2)
            subs.append(sub)
        pub = await Raw.connect(server.port, "ecpub")
        before = [broker.metrics.value(n) for n in names]
        seen = [len(r.buf) for r in subs + [pub]]
        await pub.send(q_publish(0) + q_publish(1))
        await pub.read_frames(1 + 2)
        for sub in subs:
            await sub.read_frames(2 + 2)
        got = sum(len(r.buf) - n for r, n in zip(subs + [pub], seen))
        n = len(q_publish(0))  # QoS1 delivery; QoS0 has no packet id
        assert got == 2 * (n + n - 2) + 2 * 4  # deliveries and PUBACKs
        assert [broker.metrics.value(n) - b
                for n, b in zip(names, before)] == [got, 4, 2, 4, 4, 4]
        ob = broker.outbox
        assert (ob.bytes_sent, ob.publish_sent, ob.puback_sent,
                ob.queue_in, ob.queue_out, ob.matches_local) == (0,) * 6
        for r in subs + [pub]:
            r.close()
    finally:
        await broker.stop()
        await server.stop()


async def _outbox_flush_writes_a_bounded_number_of_transports_a_turn():
    """More transports listed than ``FLUSH_MAX``: a flush writes the
    first ``FLUSH_MAX`` in listing order and schedules the rest for the
    next turn; a transport that waits keeps collecting frames and sends
    them as ONE write; the counters fold at the first flush."""
    from vernemq_tpu.broker.egress import FLUSH_MAX

    ob = _outbox()
    n = 2 * FLUSH_MAX + 10
    socks = [SockSpy() for _ in range(n)]
    ts = [StreamTransport(s, ob) for s in socks]
    flushes, writes, _, _ = egress_counts()
    for i, t in enumerate(ts):
        t.write(b"%d." % i)
    ob.publish_sent += n
    ob.flush()  # the callback that filled the outbox runs it
    written = [i for i, s in enumerate(socks) if s.calls]
    assert written == list(range(FLUSH_MAX))
    assert ob._metrics.value("mqtt_publish_sent") == n  # folded whole
    ts[n - 1].write(b"more")      # still listed: no second listing
    ts[0].write(b"again")         # written: listed anew, at the end
    await asyncio.sleep(0)        # the turn after: the scheduled flush
    assert [i for i, s in enumerate(socks) if s.calls] == \
        list(range(2 * FLUSH_MAX))
    await asyncio.sleep(0)
    await asyncio.sleep(0)
    assert [s.stream() for s in socks[1:n - 1]] == \
        [b"%d." % i for i in range(1, n - 1)]
    assert socks[n - 1].calls == [("write", b"%d.more" % (n - 1))]
    assert socks[0].stream() == b"0.again" and len(socks[0].calls) == 2
    assert egress_counts()[:2] == (flushes + 3, writes + n + 1)
    assert ob._handle is None and not ob._listed


OUTBOX_CASES = {
    "bounded_flush":
        _outbox_flush_writes_a_bounded_number_of_transports_a_turn,
    "sequential_bytes": _outbox_bytes_are_those_of_sequential_writes,
    "one_callback_a_turn": _outbox_schedules_one_callback_a_turn,
    "join_small_scatter_large":
        _outbox_joins_small_iovecs_and_scatters_large_ones,
    "raising_transport": _outbox_walk_survives_a_raising_transport,
    "close_and_closed_listing":
        _outbox_close_flushes_and_a_closed_listing_is_skipped,
    "counters_fold": _outbox_counters_equal_per_write_accounting,
}


@pytest.mark.asyncio
@pytest.mark.parametrize("case", sorted(OUTBOX_CASES))
async def test_stream_transport_iovec_flush(case):
    """StreamTransport collects a turn's chunks per connection and the
    broker's Outbox flushes every transport written in the turn from
    ONE callback: per-connection bytes identical to sequential writes,
    the form of the write chosen from the pending bytes, the egress
    counters folded ahead of the writes."""
    await OUTBOX_CASES[case]()


class Raw5(Raw):
    """Raw v5 endpoint: CONNECT with properties, consuming v5 reads."""

    @classmethod
    async def connect(cls, port, client_id, properties=None):
        r, w = await asyncio.open_connection("127.0.0.1", port)
        self = cls(r, w)
        await self.send(codec_v5.serialise(Connect(
            client_id=client_id, keepalive=0, clean_start=True,
            proto_ver=5, properties=properties or {})))
        await self.recv5(1)  # CONNACK
        return self

    async def recv5(self, n, timeout=5.0):
        frames = []
        while len(frames) < n:
            if self.buf:
                saved, codec_v5._C = codec_v5._C, None
                try:
                    f, rest = codec_v5.parse(self.buf)
                finally:
                    codec_v5._C = saved
                if f is not None:
                    self.buf = rest
                    frames.append(f)
                    continue
            chunk = await asyncio.wait_for(self.reader.read(65536),
                                           timeout)
            assert chunk, "peer closed"
            self.buf += chunk
        return frames


@pytest.mark.asyncio
async def test_qos1_fast_path_delivers_with_zero_frame_objects():
    """The QoS≥1 ingress acceptance spot test: a QoS1 batch admitted
    through the widened gate resolves pid + PUBACK straight from the
    frame table and — with only QoS0 recipients in the fanout —
    materialises ZERO Publish frames and ZERO Msg objects broker-side,
    counting in wire_fastpath_pubs_qos."""
    from vernemq_tpu.broker import message as message_mod

    broker, server = await boot(observability_enabled=False)
    try:
        sub = await Raw.connect(server.port, "q1sub")
        await sub.send(codec_v4.serialise(Subscribe(
            packet_id=1, topics=[("q/#", SubOpts(qos=0))])))
        await sub.read_frames(2)  # CONNACK + SUBACK

        pub = await Raw.connect(server.port, "q1pub")
        n = 500
        blob = b"".join(
            codec_v4.serialise(Publish(topic=f"q/{i % 8}",
                                       payload=b"q%04d" % i, qos=1,
                                       packet_id=(i % 1000) + 1))
            for i in range(n))
        base_fast = fastpath.fastpath_pubs_qos

        counts = {"publish": 0, "msg": 0}
        pub_init = Publish.__init__
        msg_init = message_mod.Msg.__init__

        def counting_pub(self, *a, **k):
            counts["publish"] += 1
            return pub_init(self, *a, **k)

        def counting_msg(self, *a, **k):
            counts["msg"] += 1
            return msg_init(self, *a, **k)

        Publish.__init__ = counting_pub
        message_mod.Msg.__init__ = counting_msg
        try:
            await pub.send(blob)
            deadline = asyncio.get_event_loop().time() + 10.0
            while (fastpath.fastpath_pubs_qos - base_fast) < n:
                assert asyncio.get_event_loop().time() < deadline, \
                    fastpath.fastpath_pubs_qos - base_fast
                await asyncio.sleep(0.01)
            # every publish PUBACKed from the span (read_frames keeps
            # the CONNACK in the capture buffer: skip frame 0)
            acks = (await pub.read_frames(1 + n))[1:]
        finally:
            Publish.__init__ = pub_init
            message_mod.Msg.__init__ = msg_init
        assert counts == {"publish": 0, "msg": 0}
        assert all(type(a).__name__ == "Puback" for a in acks)
        frames = await sub.read_frames(2 + n)
        payloads = [f.payload for f in frames[2:]]
        assert payloads == [b"q%04d" % i for i in range(n)]
        assert broker.registry.stats()["wire_fastpath_pubs_qos"] >= n
        sub.close()
        pub.close()
    finally:
        await broker.stop()
        await server.stop()


@pytest.mark.asyncio
async def test_wire_encode_fault_drill_batch_path():
    """A wire.encode fault drill against the batched fanout encoder:
    native batch-encode calls fail, the breaker opens, every fanout
    re-serves through the bit-identical pure twin — zero lost QoS1
    deliveries — and the drill's exit recovers the native path."""
    from vernemq_tpu.robustness import faults
    from vernemq_tpu.robustness.breaker import CircuitBreaker
    from vernemq_tpu.robustness.faults import FaultPlan, FaultRule

    if fastpath.load_native() is None:
        pytest.skip("native codec extension not built")
    saved_breaker = fastpath.breaker
    fastpath.breaker = CircuitBreaker(failure_threshold=2,
                                      backoff_initial=60.0)
    broker, server = await boot()
    try:
        # two protocol groups → TWO batch-encode calls per publish, so
        # the failure run is consecutive (the wire breaker is shared
        # with the parse seam, whose native successes between publishes
        # reset a single-failure run)
        sub = MQTTClient("127.0.0.1", server.port, client_id="esub")
        await sub.connect()
        await sub.subscribe("e/#", qos=1)
        sub5 = MQTTClient("127.0.0.1", server.port, client_id="esub5",
                          proto_ver=5)
        await sub5.connect()
        await sub5.subscribe("e/#", qos=1)
        pub = MQTTClient("127.0.0.1", server.port, client_id="epub")
        await pub.connect()
        errs_before = fastpath.native_errors
        faults.install(FaultPlan([FaultRule(point="wire.encode",
                                            kind="error", count=100)]))
        try:
            for i in range(10):
                await pub.publish("e/t", b"e%d" % i, qos=1,
                                  timeout=10.0)
            want = {b"e%d" % i for i in range(10)}
            got = set()
            got5 = set()
            for _ in range(10):
                got.add((await sub.recv(5.0)).payload)
                got5.add((await sub5.recv(5.0)).payload)
            assert got == want and got5 == want
        finally:
            faults.clear()
        assert fastpath.native_errors - errs_before >= 2
        assert not fastpath.breaker.is_closed
        assert broker.registry.stats()["wire_breaker_state"] > 0
        # recovery: the admin drill's exit resets; native serves again
        fastpath.breaker.reset()
        await pub.publish("e/t", b"back", qos=1, timeout=10.0)
        assert (await sub.recv(5.0)).payload == b"back"
        assert (await sub5.recv(5.0)).payload == b"back"
        assert fastpath.breaker.is_closed
        for c in (pub, sub, sub5):
            await c.close()
    finally:
        fastpath.breaker = saved_breaker
        await broker.stop()
        await server.stop()


@pytest.mark.asyncio
async def test_v5_alias_lru_eviction_on_wire_path():
    """Outbound topic aliases on the wire fast path: hot topics send
    alias-only headers, a full per-connection table evicts the
    least-recently-sent topic and re-establishes its alias number
    (MQTT5 3.3.2.3.4 remapping) — all through the batched encoder."""
    broker, server = await boot()
    try:
        sub = await Raw5.connect(server.port, "asub",
                                 {"topic_alias_maximum": 2})
        await sub.send(codec_v5.serialise(Subscribe(
            packet_id=1, topics=[("a/#", SubOpts(qos=0))])))
        await sub.recv5(1)  # SUBACK
        pub = await Raw.connect(server.port, "apub")
        base_batches = fastpath.fanout_batches
        script = ["a/t1", "a/t2", "a/t3", "a/t2", "a/t1"]
        blob = b"".join(
            codec_v4.serialise(Publish(topic=t, payload=b"p%d" % i,
                                       qos=0))
            for i, t in enumerate(script))
        await pub.send(blob)
        frames = await sub.recv5(5)
        got = [(f.topic, f.properties.get("topic_alias"), f.payload)
               for f in frames]
        # t1, t2 establish aliases 1, 2; t3 evicts LRU t1 and reuses
        # alias 1; t2 is alias-only (hot); t1 evicts t3, reusing 1
        assert got == [
            ("a/t1", 1, b"p0"),
            ("a/t2", 2, b"p1"),
            ("a/t3", 1, b"p2"),
            ("", 2, b"p3"),
            ("a/t1", 1, b"p4"),
        ]
        assert fastpath.fanout_batches > base_batches  # wire path served
        sub.close()
        pub.close()
    finally:
        await broker.stop()
        await server.stop()


# ------------------------------------------------- batched (device) view
#
# Under ``default_reg_view="tpu"`` a QoS1/2 publish that passes the gate
# is admitted from the frame table, handed to the collector with a
# continuation, and acknowledged from that continuation after the route.


async def boot_batched(**cfg):
    cfg.setdefault("default_reg_view", "tpu")
    cfg.setdefault("tpu_batch_window_us", 2000)
    cfg.setdefault("tpu_host_batch_threshold", 0)
    cfg.setdefault("watchdog_enabled", False)
    cfg.setdefault("sysmon_enabled", False)
    broker, server = await boot(**cfg)
    assert broker.registry.batched_view_active()
    return broker, server


class HeldFold:
    """The view's ``fold_batch`` behind a gate: a flush whose number is
    in ``hold`` waits (in its executor thread) until ``release()``; with
    ``fail`` every flush raises instead."""

    def __init__(self, broker, hold=(1,), fail=None):
        import threading

        self.view = broker.registry.reg_view("tpu")
        self.view.matcher("")  # the table loaded: no flush sheds to the trie
        self.orig = self.view.fold_batch
        self.gate = threading.Event()
        self.hold = set(hold)
        self.fail = fail
        self.calls = []
        self.view.fold_batch = self

    def __call__(self, mp, topics, lock_timeout=None, **kw):
        self.calls.append(len(topics))
        if len(self.calls) in self.hold:
            assert self.gate.wait(20.0)
        if self.fail is not None:
            raise self.fail
        return self.orig(mp, topics, lock_timeout, **kw)

    def release(self):
        self.gate.set()


async def until(pred, timeout=10.0):
    deadline = asyncio.get_event_loop().time() + timeout
    while not pred():
        assert asyncio.get_event_loop().time() < deadline, "timed out"
        await asyncio.sleep(0.02)


async def quiet(raw, seconds=0.3):
    """Nothing arrives on ``raw`` for ``seconds``."""
    try:
        chunk = await asyncio.wait_for(raw.reader.read(65536), seconds)
    except asyncio.TimeoutError:
        return True
    raw.buf += chunk
    return False


def q_publish(i, qos=1, topic="q/t", **kw):
    return codec_v4.serialise(Publish(topic=topic, payload=b"q%04d" % i,
                                      qos=qos, packet_id=i + 1, **kw))


def session_of(broker, client_id):
    return broker.sessions[("", client_id)]


@pytest.mark.asyncio
async def test_batched_qos1_builds_no_frame_no_msg_no_future():
    """500 QoS1 publishes under the batched view: zero Publish frames,
    zero inbound Msg objects (QoS0 recipients: no outbound one either),
    every submission in the continuation form — no future a publish —
    and all of them counted by wire_fastpath_pubs_qos, none classic."""
    from vernemq_tpu.broker import message as message_mod

    broker, server = await boot_batched(observability_enabled=False)
    try:
        sub = await Raw.connect(server.port, "bq1sub")
        await sub.send(codec_v4.serialise(Subscribe(
            packet_id=1, topics=[("q/#", SubOpts(qos=0))])))
        await sub.read_frames(2)
        pub = await Raw.connect(server.port, "bq1pub")
        n = 500
        blob = b"".join(q_publish(i, topic=f"q/{i % 8}") for i in range(n))
        base_fast = fastpath.fastpath_pubs_qos
        base_classic = fastpath.classic_pubs_qos

        col = broker.batch_collector()
        returned = []
        submit = col.submit

        def counting_submit(*a, **k):
            returned.append(submit(*a, **k))
            return returned[-1]

        col.submit = counting_submit
        loop = asyncio.get_event_loop()
        futures = {"n": 0}
        create_future = loop.create_future

        def counting_future():
            futures["n"] += 1
            return create_future()

        counts = {"publish": 0, "msg": 0}
        pub_init = Publish.__init__
        msg_init = message_mod.Msg.__init__

        def counting_pub(self, *a, **k):
            counts["publish"] += 1
            return pub_init(self, *a, **k)

        def counting_msg(self, *a, **k):
            counts["msg"] += 1
            return msg_init(self, *a, **k)

        Publish.__init__ = counting_pub
        message_mod.Msg.__init__ = counting_msg
        loop.create_future = counting_future
        try:
            await pub.send(blob)
            deadline = loop.time() + 20.0
            while (fastpath.fastpath_pubs_qos - base_fast) < n \
                    or session_of(broker, "bq1pub").wire_inflight:
                assert loop.time() < deadline
                await asyncio.sleep(0.05)
        finally:
            del loop.create_future
            del col.submit
            Publish.__init__ = pub_init
            message_mod.Msg.__init__ = msg_init
        assert counts == {"publish": 0, "msg": 0}
        assert len(returned) == n and all(r is None for r in returned)
        # the reader's waits at its run bound, socket reads, this test's
        # own sleeps: far fewer than one a publish
        assert futures["n"] < n // 4, futures
        assert fastpath.classic_pubs_qos == base_classic
        acks = (await pub.read_frames(1 + n))[1:]
        assert [a.packet_id for a in acks] == [i + 1 for i in range(n)]
        assert all(type(a).__name__ == "Puback" for a in acks)
        frames = await sub.read_frames(2 + n)
        assert [f.payload for f in frames[2:]] == \
            [b"q%04d" % i for i in range(n)]
        stats = broker.registry.stats()
        assert stats["wire_fastpath_pubs_qos"] >= n
        assert "wire_classic_pubs_qos" in stats
        assert "wire_inline_chunks" in stats
        assert stats["wire_task_chunks"] >= 1  # the run bound's remainder
        sub.close()
        pub.close()
    finally:
        await broker.stop()
        await server.stop()


@pytest.mark.asyncio
async def test_batched_puback_leaves_after_the_route():
    """While the fold is held neither the delivery nor the PUBACK
    exists; once the rows arrive the recipient's bytes are written
    first and the PUBACK after the route returned."""
    broker, server = await boot_batched()
    try:
        sub = await Raw.connect(server.port, "arsub")
        await sub.send(codec_v4.serialise(Subscribe(
            packet_id=1, topics=[("q/#", SubOpts(qos=1))])))
        await sub.read_frames(2)
        pub = await Raw.connect(server.port, "arpub")
        held = HeldFold(broker)
        events = []
        reg = broker.registry
        route = reg._wire_route

        def traced_route(*a, **k):
            n = route(*a, **k)
            events.append(("routed", n))
            return n

        reg._wire_route = traced_route
        psess = session_of(broker, "arpub")
        send = psess.send

        def traced_send(frame):
            events.append(("sent", type(frame).__name__))
            return send(frame)

        psess.send = traced_send
        await pub.send(q_publish(0))
        await until(lambda: held.calls)
        assert await quiet(pub) and await quiet(sub, 0.05)
        assert psess.wire_inflight == 1 and events == []
        held.release()
        delivered = (await sub.read_frames(3))[2]
        assert delivered.payload == b"q0000" and delivered.qos == 1
        ack = (await pub.read_frames(2))[1]
        assert type(ack).__name__ == "Puback" and ack.packet_id == 1
        assert events == [("routed", 1), ("sent", "Puback")]
        assert psess.wire_inflight == 0
        sub.close()
        pub.close()
    finally:
        await broker.stop()
        await server.stop()


@pytest.mark.asyncio
@pytest.mark.parametrize("form", READ_FORMS)
async def test_batched_puback_order_is_arrival_order(form):
    """Two device flushes with a trie-served one between them, the
    first held until the others have settled: the PUBACKs still leave
    in the order the publishes arrived, and so do the deliveries."""
    broker, server = await boot_batched(tpu_host_batch_threshold=2)
    try:
        port, kw = await listen(broker, server, form)
        sub = await Raw.connect(port, "orsub", **kw)
        await sub.send(codec_v4.serialise(Subscribe(
            packet_id=1, topics=[("q/#", SubOpts(qos=0))])))
        await sub.read_frames(2)
        pub = await Raw.connect(port, "orpub", **kw)
        held = HeldFold(broker)
        col = broker.batch_collector()
        await pub.send(b"".join(q_publish(i) for i in range(5)))
        await until(lambda: held.calls == [5])
        await pub.send(q_publish(5))  # <= threshold: the trie serves it
        await until(lambda: col.host_hybrid_pubs == 1)
        await pub.send(b"".join(q_publish(i) for i in range(6, 11)))
        await until(lambda: held.calls == [5, 5] and col._inflight == 1)
        assert await quiet(pub, 0.2)  # all settled behind the held head
        held.release()
        acks = (await pub.read_frames(12))[1:]
        assert [a.packet_id for a in acks] == list(range(1, 12))
        frames = await sub.read_frames(13)
        assert [f.payload for f in frames[2:]] == \
            [b"q%04d" % i for i in range(11)]
        sub.close()
        pub.close()
    finally:
        await broker.stop()
        await server.stop()


@pytest.mark.asyncio
@pytest.mark.parametrize("behind", ["subscribe", "disconnect", "pubrel"])
async def test_batched_classic_record_waits_for_the_continuation(behind):
    """A record that is neither a fast publish nor a fast ack runs only
    after the publishes before it were routed and acknowledged."""
    broker, server = await boot_batched()
    try:
        pub = await Raw.connect(server.port, "cwpub")
        held = HeldFold(broker)
        psess = session_of(broker, "cwpub")
        if behind == "subscribe":
            tail = codec_v4.serialise(Subscribe(
                packet_id=9, topics=[("q/#", SubOpts(qos=0))]))
        elif behind == "disconnect":
            from vernemq_tpu.protocol.types import Disconnect
            tail = codec_v4.serialise(Disconnect())
        else:
            from vernemq_tpu.protocol.types import Pubrel
            tail = codec_v4.serialise(Pubrel(packet_id=1))
        qos = 2 if behind == "pubrel" else 1
        await pub.send(q_publish(0, qos=qos) + tail)
        await until(lambda: held.calls)
        assert await quiet(pub)
        assert psess.wire_inflight == 1 and not psess.closed
        if behind == "pubrel":
            assert 1 in psess.awaiting_rel  # credit held at admission
        held.release()
        if behind == "disconnect":
            ack = (await pub.read_frames(2))[1]
            assert type(ack).__name__ == "Puback"
            await until(lambda: psess.closed)
            assert psess.close_reason == "client_disconnect"
        else:
            first, second = (await pub.read_frames(3))[1:]
            want = (("Pubrec", "Pubcomp") if behind == "pubrel"
                    else ("Puback", "Suback"))
            assert (type(first).__name__, type(second).__name__) == want
            if behind == "subscribe":
                # the SUBSCRIBE ran AFTER the publish was routed: the
                # publisher's own new subscription saw nothing of it
                assert await quiet(pub, 0.1)
            else:
                assert psess.awaiting_rel == {}
        pub.close()
    finally:
        await broker.stop()
        await server.stop()


@pytest.mark.asyncio
@pytest.mark.parametrize("qos", [1, 2])
async def test_batched_failed_fold_withholds_the_ack(qos):
    """A fold that raises: no PUBACK / PUBREC, the QoS2 receive credit
    comes back, and the client's DUP retry is routed and acknowledged."""
    broker, server = await boot_batched()
    try:
        sub = await Raw.connect(server.port, "ffsub")
        await sub.send(codec_v4.serialise(Subscribe(
            packet_id=1, topics=[("q/#", SubOpts(qos=0))])))
        await sub.read_frames(2)
        pub = await Raw.connect(server.port, "ffpub")
        held = HeldFold(broker, hold=(), fail=ValueError("boom"))
        psess = session_of(broker, "ffpub")
        errors = broker.metrics.value("mqtt_publish_error")
        await pub.send(q_publish(0, qos=qos))
        await until(lambda: held.calls and not psess.wire_inflight)
        assert await quiet(pub) and await quiet(sub, 0.05)
        assert broker.metrics.value("mqtt_publish_error") == errors + 1
        assert psess.awaiting_rel == {}
        held.fail = None
        await pub.send(q_publish(0, qos=qos, dup=True))
        ack = (await pub.read_frames(2))[1]
        assert type(ack).__name__ == ("Puback" if qos == 1 else "Pubrec")
        assert (await sub.read_frames(3))[2].payload == b"q0000"
        sub.close()
        pub.close()
    finally:
        await broker.stop()
        await server.stop()


@pytest.mark.asyncio
async def test_batched_v5_no_subscriber_reason_code():
    from vernemq_tpu.protocol.types import RC_NO_MATCHING_SUBSCRIBERS

    broker, server = await boot_batched()
    try:
        sub = await Raw.connect(server.port, "nmsub")
        await sub.send(codec_v4.serialise(Subscribe(
            packet_id=1, topics=[("q/yes", SubOpts(qos=0))])))
        await sub.read_frames(2)
        pub = await Raw5.connect(server.port, "nmpub")
        base = fastpath.fastpath_pubs_qos
        await pub.send(b"".join(
            codec_v5.serialise(Publish(topic=t, payload=b"x", qos=1,
                                       packet_id=i + 1))
            for i, t in enumerate(("q/none", "q/yes"))))
        none, yes = await pub.recv5(2)
        assert (none.packet_id, none.reason_code) == \
            (1, RC_NO_MATCHING_SUBSCRIBERS)
        assert (yes.packet_id, yes.reason_code) == (2, 0)
        assert fastpath.fastpath_pubs_qos - base == 2
        sub.close()
        pub.close()
    finally:
        await broker.stop()
        await server.stop()


@pytest.mark.asyncio
async def test_batched_reader_parks_at_the_run_bound():
    """One connection has at most FRAME_RUN publishes out with the
    collector: the next one parks the reader until they are back."""
    from vernemq_tpu.broker.server import FRAME_RUN

    broker, server = await boot_batched()
    try:
        pub = await Raw.connect(server.port, "cappub")
        held = HeldFold(broker)
        psess = session_of(broker, "cappub")
        col = broker.batch_collector()
        n = FRAME_RUN + 6
        await pub.send(b"".join(q_publish(i) for i in range(n)))
        await until(lambda: psess.wire_inflight == FRAME_RUN)
        assert await quiet(pub)
        assert psess.wire_inflight == FRAME_RUN
        assert len(col._order) == FRAME_RUN and not col._pending
        held.release()
        acks = (await pub.read_frames(1 + n))[1:]
        assert [a.packet_id for a in acks] == list(range(1, n + 1))
        assert psess.wire_inflight == 0
        pub.close()
    finally:
        await broker.stop()
        await server.stop()


@pytest.mark.asyncio
@pytest.mark.parametrize("edge", ["retain", "dup", "hook", "governor"])
async def test_batched_gate_leaves_edges_to_the_classic_path(edge):
    """What the gate cannot prove simple keeps the classic handler
    under the batched view too, and the two counters say which path a
    publish took."""
    broker, server = await boot_batched()
    try:
        sub = await Raw.connect(server.port, "edsub")
        await sub.send(codec_v4.serialise(Subscribe(
            packet_id=1, topics=[("q/#", SubOpts(qos=0))])))
        await sub.read_frames(2)
        pub = await Raw.connect(server.port, "edpub")
        kw = {}
        if edge == "retain":
            kw["retain"] = True
        elif edge == "dup":
            kw["dup"] = True
        elif edge == "hook":
            broker.hooks.register("on_publish", lambda *a: None)
        else:
            broker.overload.pin(2)
        fast, classic = fastpath.fastpath_pubs_qos, \
            fastpath.classic_pubs_qos
        await pub.send(q_publish(0, **kw))
        ack = (await pub.read_frames(2))[1]
        assert type(ack).__name__ == "Puback"
        assert (await sub.read_frames(3))[2].payload == b"q0000"
        assert fastpath.fastpath_pubs_qos == fast
        assert fastpath.classic_pubs_qos == classic + 1
        if edge == "governor":
            broker.overload.pin(None)
        sub.close()
        pub.close()
    finally:
        await broker.stop()
        await server.stop()


@pytest.mark.asyncio
async def test_batched_closed_session_is_routed_not_acknowledged():
    """A publisher gone while its publish is with the collector: the
    publish is still delivered, no acknowledgement is written."""
    broker, server = await boot_batched()
    try:
        sub = await Raw.connect(server.port, "clsub")
        await sub.send(codec_v4.serialise(Subscribe(
            packet_id=1, topics=[("q/#", SubOpts(qos=0))])))
        await sub.read_frames(2)
        pub = await Raw.connect(server.port, "clpub")
        held = HeldFold(broker)
        psess = session_of(broker, "clpub")
        await pub.send(q_publish(0))
        await until(lambda: held.calls)
        pub.close()
        await until(lambda: psess.closed)
        acked = broker.metrics.value("mqtt_puback_sent")
        held.release()
        assert (await sub.read_frames(3))[2].payload == b"q0000"
        await until(lambda: psess.wire_inflight == 0)
        assert broker.metrics.value("mqtt_puback_sent") == acked
        sub.close()
    finally:
        await broker.stop()
        await server.stop()


# ------------------------------------------------------------------
# The protocol-level reader (broker/server.py:MqttProtocol): while the
# connection's task is parked at its steady-state read, the protocol
# runs a chunk's fast records itself (listed by data_received, served by
# the listener's per-turn callback); the first record it cannot serve
# goes to the task with every byte behind it.


def proto_of(broker, client_id):
    """The MqttProtocol of ``client_id``'s connection."""
    return session_of(broker, client_id).transport._transport.get_protocol()


async def parked(proto):
    """The connection's task is at its steady-state read; returns the
    future it waits on (still pending later = the task took no step)."""
    await until(lambda: proto._session is not None)
    return proto._waiter


def chunk_counts():
    return fastpath.inline_chunks, fastpath.task_chunks


@contextlib.contextmanager
def counted_runs():
    """The records each ``wire_run`` call served, in call order."""
    from vernemq_tpu.broker import server as server_mod

    runs = []
    orig = server_mod.wire_run

    def counting_run(session, buf, table, off, end, budget):
        ran = orig(session, buf, table, off, end, budget)
        runs.append((ran - off) // fastpath.REC_SIZE)
        return ran

    server_mod.wire_run = counting_run
    try:
        yield runs
    finally:
        server_mod.wire_run = orig


@pytest.mark.asyncio
async def test_inline_chunk_takes_no_task_step_and_acks_after_the_route():
    """(a) A chunk of one QoS1 PUBLISH, and the subscriber's PUBACK of
    its delivery: each counted in inline_chunks, none in task_chunks,
    neither connection's task woken — and the publisher's PUBACK still
    leaves only after the route (the fold is held meanwhile)."""
    broker, server = await boot_batched()
    try:
        sub = await Raw.connect(server.port, "insub")
        await sub.send(codec_v4.serialise(Subscribe(
            packet_id=1, topics=[("q/#", SubOpts(qos=1))])))
        await sub.read_frames(2)
        pub = await Raw.connect(server.port, "inpub")
        pproto, sproto = proto_of(broker, "inpub"), proto_of(broker, "insub")
        pwait, swait = await parked(pproto), await parked(sproto)
        held = HeldFold(broker)
        inline, task = chunk_counts()
        await pub.send(q_publish(0))
        await until(lambda: held.calls)
        assert await quiet(pub) and await quiet(sub, 0.05)
        assert chunk_counts() == (inline + 1, task)
        assert session_of(broker, "inpub").wire_inflight == 1
        held.release()
        delivered = (await sub.read_frames(3))[2]
        assert delivered.payload == b"q0000" and delivered.qos == 1
        ack = (await pub.read_frames(2))[1]
        assert type(ack).__name__ == "Puback" and ack.packet_id == 1
        ssess = session_of(broker, "insub")
        assert len(ssess.waiting_acks) == 1
        await sub.send(b"\x40\x02" + delivered.packet_id.to_bytes(2, "big"))
        await until(lambda: not ssess.waiting_acks)
        assert chunk_counts() == (inline + 2, task)
        # both tasks still wait on the future they parked with
        assert pproto._waiter is pwait and not pwait.done()
        assert sproto._waiter is swait and not swait.done()
        sub.close()
        pub.close()
    finally:
        await broker.stop()
        await server.stop()


@pytest.mark.asyncio
@pytest.mark.parametrize("view", ["trie", "batched"])
async def test_inline_run_splits_the_chunk_at_a_classic_record(view):
    """(b) Fast records, a SUBSCRIBE, fast records again, ONE chunk:
    the protocol serves the first stretch, the task the rest; every
    PUBACK of the earlier publishes is on the wire before the SUBACK,
    the later ones after it."""
    broker, server = await (boot_batched() if view == "batched" else boot())
    try:
        pub = await Raw.connect(server.port, "sppub")
        proto = proto_of(broker, "sppub")
        await parked(proto)
        inline, task = chunk_counts()
        with counted_runs() as runs:
            await pub.send(
                b"".join(q_publish(i) for i in range(3))
                + codec_v4.serialise(Subscribe(
                    packet_id=9, topics=[("q/#", SubOpts(qos=0))]))
                + b"".join(q_publish(i) for i in range(3, 5)))
            # CONNACK, 3 PUBACKs, SUBACK, 2 PUBACKs, 2 own deliveries
            frames = (await pub.read_frames(1 + 3 + 1 + 2 + 2))[1:]
        names = [type(f).__name__ for f in frames]
        assert names[:4] == ["Puback", "Puback", "Puback", "Suback"]
        assert [f.packet_id for f in frames[:3]] == [1, 2, 3]
        assert sorted(f.packet_id for f in frames[4:]
                      if type(f).__name__ == "Puback") == [4, 5]
        # the subscription saw only what came after it
        assert [f.payload for f in frames[4:]
                if type(f).__name__ == "Publish"] == [b"q0003", b"q0004"]
        assert runs[0] == 3 and sum(runs) == 5  # inline run, then the task's
        assert chunk_counts() == (inline, task + 1)
        pub.close()
    finally:
        await broker.stop()
        await server.stop()


@pytest.mark.asyncio
async def test_inline_frame_cut_across_chunks_and_at_eof():
    """(c) A frame cut across two chunks waits in the protocol's buffer
    (no task step for either half); a chunk that ends mid-frame at EOF
    closes the connection as a clean EOF always did."""
    broker, server = await boot()
    try:
        pub = await Raw.connect(server.port, "cutpub")
        proto = proto_of(broker, "cutpub")
        waiter = await parked(proto)
        frame = q_publish(0)
        inline, task = chunk_counts()
        await pub.send(frame[:5])
        await until(lambda: proto._tail == frame[:5])
        assert await quiet(pub, 0.1)
        await pub.send(frame[5:])
        ack = (await pub.read_frames(2))[1]
        assert type(ack).__name__ == "Puback" and ack.packet_id == 1
        assert chunk_counts() == (inline + 2, task)
        assert proto._tail == b"" and proto._waiter is waiter
        errors = broker.metrics.value("socket_error")
        closes = broker.metrics.value("socket_close")
        psess = session_of(broker, "cutpub")
        await pub.send(q_publish(1) + q_publish(2)[:7])
        pub.writer.write_eof()
        await until(lambda: psess.closed)
        await until(
            lambda: broker.metrics.value("socket_close") == closes + 1)
        assert broker.metrics.value("socket_error") == errors
        assert psess.close_reason == "connection_lost"
        # the whole frame before the cut was served and acknowledged
        assert (await pub.read_frames(3))[2].packet_id == 2
        pub.close()
    finally:
        await broker.stop()
        await server.stop()


@pytest.mark.asyncio
async def test_inline_run_stops_at_the_run_bound():
    """(d) 200 QoS0 publishes in one chunk: the protocol serves
    FRAME_RUN of them, the task the rest in runs no longer than that;
    all 200 arrive, in order."""
    from vernemq_tpu.broker import server as server_mod

    broker, server = await boot()
    try:
        sub = await Raw.connect(server.port, "rbsub")
        await sub.send(codec_v4.serialise(Subscribe(
            packet_id=1, topics=[("t/#", SubOpts(qos=0))])))
        await sub.read_frames(2)
        pub = await Raw.connect(server.port, "rbpub")
        await parked(proto_of(broker, "rbpub"))
        n = 200
        blob = b"".join(
            codec_v4.serialise(Publish(topic="t/x", payload=b"p%04d" % i,
                                       qos=0)) for i in range(n))
        task = fastpath.task_chunks
        fast = fastpath.fastpath_pubs
        with counted_runs() as runs:
            await pub.send(blob)
            frames = (await sub.read_frames(2 + n))[2:]
        assert [f.payload for f in frames] == [b"p%04d" % i for i in range(n)]
        assert fastpath.fastpath_pubs - fast == n
        # the 2.6 KB blob is one recv chunk on loopback: the protocol's
        # run is the first, the task's follow, each bounded
        assert runs[0] == min(server_mod.FRAME_RUN, n)
        assert max(runs) <= server_mod.FRAME_RUN and sum(runs) == n
        assert fastpath.task_chunks > task  # the remainder went to the task
        sub.close()
        pub.close()
    finally:
        await broker.stop()
        await server.stop()


@pytest.mark.asyncio
@pytest.mark.parametrize("edge", ["tracer", "governor"])
async def test_inline_run_stays_off_behind_a_closed_gate(edge):
    """(e) Gate closed — a tracer — and the protocol serves nothing:
    every chunk goes to the task; the governor at level 1 — and it
    serves no chunk that holds a PUBLISH (the task sleeps its pause).
    The conversation's bytes are those of the open gate."""
    open_gate = await _conversation_on("protocol")
    broker, server = await boot(overload_l1_throttle_ms=1)
    try:
        if edge == "tracer":
            broker.start_trace("nobody-by-this-name")
        else:
            broker.overload.pin(1)
        inline, task = chunk_counts()
        pubs = fastpath.fastpath_pubs + fastpath.fastpath_pubs_qos
        closed_gate = await _conversation(server.port)
        if edge == "tracer":
            assert fastpath.inline_chunks == inline
        else:
            # paid on the task, then the wire plane all the same
            assert fastpath.fastpath_pubs + fastpath.fastpath_pubs_qos > pubs
        assert fastpath.task_chunks > task
        if edge == "governor":
            broker.overload.pin(None)
    finally:
        await broker.stop()
        await server.stop()
    assert closed_gate == open_gate


@pytest.mark.asyncio
@pytest.mark.parametrize("form", READ_FORMS)
async def test_malformed_frame_after_admitted_publishes(form):
    """(f) Three publishes and then a frame no codec accepts, one chunk:
    the publishes are delivered and booked, the connection is closed
    with socket_error — the same by either read form."""
    broker, server = await boot()
    try:
        port, kw = await listen(broker, server, form)
        sub = await Raw.connect(port, "mfsub", **kw)
        await sub.send(codec_v4.serialise(Subscribe(
            packet_id=1, topics=[("t/#", SubOpts(qos=0))])))
        await sub.read_frames(2)
        pub = await Raw.connect(port, "mfpub", **kw)
        psess = session_of(broker, "mfpub")
        m = broker.metrics
        errors, received, closes = (m.value("socket_error"),
                                    m.value("mqtt_publish_received"),
                                    m.value("socket_close"))
        await pub.send(b"".join(
            codec_v4.serialise(Publish(topic="t/x", payload=b"m%d" % i,
                                       qos=0)) for i in range(3))
            + b"\xf0\x00")  # reserved packet type 15
        await until(lambda: psess.closed)
        await until(lambda: m.value("socket_close") == closes + 1)
        assert m.value("socket_error") == errors + 1
        assert m.value("mqtt_publish_received") == received + 3
        frames = (await sub.read_frames(2 + 3))[2:]
        assert [f.payload for f in frames] == [b"m0", b"m1", b"m2"]
        assert await pub.reader.read(65536) == b""  # closed by the broker
        sub.close()
        pub.close()
    finally:
        await broker.stop()
        await server.stop()


@pytest.mark.asyncio
async def test_reading_pauses_while_the_task_is_busy_and_resumes():
    """(g) While the task waits (a SUBSCRIBE behind a publish whose fold
    is held) data_received only appends, and past READ_HIGH it pauses
    the socket's reading; the task's next read resumes it and nothing
    is lost or reordered."""
    from vernemq_tpu.broker.server import READ_HIGH

    broker, server = await boot_batched()
    try:
        pub = await Raw.connect(server.port, "pzpub")
        proto = proto_of(broker, "pzpub")
        await parked(proto)
        held = HeldFold(broker)
        await pub.send(q_publish(0) + codec_v4.serialise(Subscribe(
            packet_id=9, topics=[("big/#", SubOpts(qos=0))])))
        await until(lambda: held.calls)
        assert proto._session is None  # the task is in wire_drain
        n, size = 24, 16 * 1024
        inline = fastpath.inline_chunks
        pub.writer.write(b"".join(
            codec_v4.serialise(Publish(topic="big/x", qos=0,
                                       payload=bytes([65 + i]) * size))
            for i in range(n)))
        await until(lambda: proto._paused)
        assert len(proto._buf) > READ_HIGH
        held_bytes = len(proto._buf)
        await asyncio.sleep(0.1)
        assert len(proto._buf) == held_bytes  # nothing read while paused
        assert fastpath.inline_chunks == inline
        held.release()
        frames = (await pub.read_frames(1 + 2 + n, timeout=20.0))[1:]
        assert [type(f).__name__ for f in frames[:2]] == ["Puback", "Suback"]
        assert [f.payload[:1] for f in frames[2:]] == \
            [bytes([65 + i]) for i in range(n)]
        assert all(len(f.payload) == size for f in frames[2:])
        await until(lambda: proto._session is not None)
        assert not proto._paused and proto._buf == b""
        pub.close()
    finally:
        await broker.stop()
        await server.stop()


@pytest.mark.asyncio
async def test_mqtts_listener_is_byte_identical():
    """(h) The same conversation over an mqtts listener (the protocol
    under asyncio's TLS transport) reads and writes the plain
    listener's bytes, its fast records run off the task too."""
    import os
    import ssl

    ssl_dir = os.path.join(os.path.dirname(__file__), "ssl")
    plain = await _conversation_on("protocol")
    broker, server = await boot()
    try:
        tls = await broker.listeners.start_listener(
            "mqtts", "127.0.0.1", 0, {
                "certfile": os.path.join(ssl_dir, "server.crt"),
                "keyfile": os.path.join(ssl_dir, "server.key")})
        ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_CLIENT)
        ctx.load_verify_locations(os.path.join(ssl_dir, "ca.crt"))
        ctx.check_hostname = False
        task = fastpath.task_chunks
        over_tls = await _conversation(tls.port, ssl=ctx)
        assert fastpath.task_chunks > task  # three publishes, a PINGREQ
        await until(lambda: tls.connection_count == 0)
    finally:
        await broker.stop()
        await server.stop()
    assert over_tls == plain


@pytest.mark.asyncio
async def test_a_turns_chunks_are_served_together_by_one_callback():
    """The reads of a loop turn only list their connections; the
    listener's one callback then serves all the chunks, in arrival
    order, ahead of anything the next turn brings — and a connection
    that saw EOF in the same turn keeps its chunk for its task."""
    broker, server = await boot()
    try:
        sub = await Raw.connect(server.port, "tpsub")
        await sub.send(codec_v4.serialise(Subscribe(
            packet_id=1, topics=[("q/#", SubOpts(qos=0))])))
        await sub.read_frames(2)
        pubs = [await Raw.connect(server.port, "tppub%d" % i)
                for i in range(4)]
        protos = [proto_of(broker, "tppub%d" % i) for i in range(4)]
        for p in protos:
            await parked(p)
        last = session_of(broker, "tppub3")
        calls = []
        serve_inbox = server._serve_inbox
        server._serve_inbox = lambda: (calls.append(len(server._inbox)),
                                       serve_inbox())
        inline, task = chunk_counts()
        fast = fastpath.fastpath_pubs_qos
        # one turn's reads, as the selector would deliver them
        for i, p in enumerate(protos):
            p.data_received(q_publish(i))
        protos[3].eof_received()
        assert server._inbox == protos and calls == []
        assert fastpath.fastpath_pubs_qos == fast  # nothing served yet
        await asyncio.sleep(0)
        assert calls == [4] and server._inbox == []
        # three by the callback; the fourth's task may have run as well
        assert fastpath.fastpath_pubs_qos >= fast + 3
        assert chunk_counts() == (inline + 3, task)
        frames = (await sub.read_frames(2 + 4))[2:]
        assert [f.payload for f in frames[:3]] == \
            [b"q%04d" % i for i in range(3)]
        # the fourth went to its task with the EOF behind it: served,
        # acknowledged, then closed
        assert frames[3].payload == b"q0003"
        ack = (await pubs[3].read_frames(2))[1]
        assert type(ack).__name__ == "Puback" and ack.packet_id == 4
        await until(lambda: last.closed)
        del server._serve_inbox
        for r in pubs + [sub]:
            r.close()
    finally:
        await broker.stop()
        await server.stop()


@contextlib.contextmanager
def counted_gates():
    """Each evaluation of the broker-wide half of the wire gate by the
    listener's ``_serve_inbox``, with its verdict."""
    from vernemq_tpu.broker import server as server_mod

    verdicts = []
    orig = server_mod.wire_gate

    def counting(broker):
        verdicts.append(orig(broker))
        return verdicts[-1]

    server_mod.wire_gate = counting
    try:
        yield verdicts
    finally:
        server_mod.wire_gate = orig


async def _gate_fleet(server, broker, n=3):
    """A QoS0 subscriber and ``n`` parked publishers."""
    sub = await Raw.connect(server.port, "gtsub")
    await sub.send(codec_v4.serialise(Subscribe(
        packet_id=1, topics=[("q/#", SubOpts(qos=0))])))
    await sub.read_frames(2)
    pubs = [await Raw.connect(server.port, "gtpub%d" % i)
            for i in range(n)]
    protos = [proto_of(broker, "gtpub%d" % i) for i in range(n)]
    for p in protos:
        await parked(p)
    return sub, pubs, protos


@pytest.mark.asyncio
@pytest.mark.parametrize("edge", [None, "hook", "governor"])
async def test_a_pass_evaluates_the_broker_wide_gate_once(edge):
    """One ``_serve_inbox`` pass evaluates the broker-wide half of the
    wire gate ONCE, whatever the number of chunks. Open, every chunk is
    served inline and the pass itself — admissions, fanout writes,
    acknowledgements — leaves the verdict where it was. A hook or a
    raised governor level set BETWEEN two turns closes the gate for
    every chunk of the next pass: all go to their tasks, which serve
    them on the classic path."""
    from vernemq_tpu.broker.session import wire_broker_ready

    broker, server = await boot()
    try:
        sub, pubs, protos = await _gate_fleet(server, broker)
        # a first turn with the gate open
        with counted_gates() as verdicts:
            inline, task = chunk_counts()
            for i, p in enumerate(protos):
                p.data_received(q_publish(i))
            await asyncio.sleep(0)
            assert verdicts == [WIRE_OPEN]
            assert chunk_counts() == (inline + 3, task)
            assert wire_broker_ready(broker)  # the pass did not move it
        for r in pubs:
            await r.read_frames(2)
        if edge == "hook":
            broker.hooks.register("on_publish", lambda *a, **kw: None)
        elif edge == "governor":
            broker.overload.pin(2)
        with counted_gates() as verdicts:
            inline, task = chunk_counts()
            classic = fastpath.classic_pubs_qos
            for i, p in enumerate(protos):
                p.data_received(q_publish(3 + i))
            await asyncio.sleep(0)
            assert verdicts == [WIRE_OPEN if edge is None else WIRE_CLOSED]
            if edge is None:
                assert chunk_counts() == (inline + 3, task)
            else:
                assert chunk_counts() == (inline, task + 3)
        for r in pubs:
            acks = (await r.read_frames(3))[1:]
            assert [type(a).__name__ for a in acks] == ["Puback"] * 2
        if edge is not None:
            assert fastpath.classic_pubs_qos == classic + 3
        frames = (await sub.read_frames(2 + 6))[2:]
        assert sorted(f.payload for f in frames) == \
            [b"q%04d" % i for i in range(6)]
        if edge == "governor":
            broker.overload.pin(None)
        for r in pubs + [sub]:
            r.close()
    finally:
        await broker.stop()
        await server.stop()


@pytest.mark.asyncio
@pytest.mark.parametrize("burst", [1, 5, 14])
async def test_level_1_pauses_a_burst_once_and_keeps_it_whole(burst):
    """The governor at level 1 under the batched view: a chunk of
    ``burst`` QoS 1 publishes pays its reader pauses as ONE sleep (at
    most a second's worth at once) and then runs on the wire plane — the
    burst reaches the collector in one flush, not a publish a flush as
    on the classic path — while a chunk of acks is served inline with no
    pause; every publish is delivered and acknowledged in order."""
    from vernemq_tpu.broker.session import wire_gate

    broker, server = await boot_batched(overload_l1_throttle_ms=100)
    try:
        sub = await Raw.connect(server.port, "l1sub")
        await sub.send(codec_v4.serialise(Subscribe(
            packet_id=1, topics=[("q/#", SubOpts(qos=1))])))
        await sub.read_frames(2)
        pub = await Raw.connect(server.port, "l1pub")
        held = HeldFold(broker, hold=())  # the size of every flush
        broker.overload.pin(1)
        assert wire_gate(broker) == WIRE_PAUSED
        fast, classic = fastpath.fastpath_pubs_qos, \
            fastpath.classic_pubs_qos
        throttled = broker.metrics.value("overload_publish_throttled")
        t0 = asyncio.get_running_loop().time()
        await pub.send(b"".join(q_publish(i) for i in range(burst)))
        acks = (await pub.read_frames(1 + burst))[1:]
        waited = asyncio.get_running_loop().time() - t0
        assert [a.packet_id for a in acks] == list(range(1, burst + 1))
        got = (await sub.read_frames(2 + burst))[2:]
        assert [f.payload for f in got] == \
            [b"q%04d" % i for i in range(burst)]
        assert fastpath.fastpath_pubs_qos == fast + burst
        assert fastpath.classic_pubs_qos == classic
        assert broker.metrics.value("overload_publish_throttled") \
            == throttled + burst
        # one pause of burst x 100 ms, in pieces of at most a second
        assert waited >= 0.1 * burst - 0.02
        assert held.calls == ([10, 4] if burst == 14 else [burst])
        # the subscriber's acks owe nothing and wake nobody
        inline, task = chunk_counts()
        for f in got:
            await sub.send(codec_v4.serialise(Puback(packet_id=f.packet_id)))
        ssub = session_of(broker, "l1sub")
        await until(lambda: not ssub.waiting_acks)
        assert chunk_counts()[1] == task and chunk_counts()[0] > inline
        broker.overload.pin(None)
        sub.close()
        pub.close()
    finally:
        await broker.stop()
        await server.stop()


@pytest.mark.asyncio
async def test_level_1_leaves_a_tick_of_bursts_to_the_device():
    """Three publishers' bursts of five on one tick, the governor at
    level 1, ``tpu_host_batch_threshold`` 8: the pauses end together, the
    fifteen publishes reach the collector as flushes past the threshold
    and the device serves every one — a pause a publish would hand the
    collector flushes of three, all of them the host trie's."""
    broker, server = await boot_batched(tpu_host_batch_threshold=8,
                                        overload_l1_throttle_ms=40)
    try:
        sub = await Raw.connect(server.port, "tksub")
        await sub.send(codec_v4.serialise(Subscribe(
            packet_id=1, topics=[("q/#", SubOpts(qos=0))])))
        await sub.read_frames(2)
        pubs = [await Raw.connect(server.port, "tkpub%d" % i)
                for i in range(3)]
        held = HeldFold(broker, hold=())
        col = broker.batch_collector()
        hybrid = col.host_hybrid_pubs
        broker.overload.pin(1)
        for r in pubs:
            await r.send(b"".join(q_publish(i) for i in range(5)))
        for r in pubs:
            acks = (await r.read_frames(6))[1:]
            assert [a.packet_id for a in acks] == [1, 2, 3, 4, 5]
        assert len((await sub.read_frames(2 + 15))[2:]) == 15
        assert sum(held.calls) == 15 and min(held.calls) > 8
        assert col.host_hybrid_pubs == hybrid
        broker.overload.pin(None)
        for r in pubs + [sub]:
            r.close()
    finally:
        await broker.stop()
        await server.stop()


@pytest.mark.asyncio
@pytest.mark.parametrize("state", ["closed", "disconnected"])
async def test_a_pass_tests_the_sessions_half_per_chunk(state):
    """Inside one pass, behind an open broker-wide gate, a session that
    is closed or no longer connected still falls to its task; its
    neighbours are served inline."""
    broker, server = await boot()
    try:
        sub, pubs, protos = await _gate_fleet(server, broker)
        odd = session_of(broker, "gtpub1")
        if state == "closed":
            odd.closed = True
        else:
            odd.connected = False
        with counted_gates() as verdicts:
            inline, task = chunk_counts()
            fast = fastpath.fastpath_pubs_qos
            for i, p in enumerate(protos):
                p.data_received(q_publish(i))
            await asyncio.sleep(0)
            assert verdicts == [WIRE_OPEN]
            assert chunk_counts() == (inline + 2, task + 1)
            assert fastpath.fastpath_pubs_qos == fast + 2
        assert protos[1]._session is None  # woken: the task has the bytes
        for r in (pubs[0], pubs[2]):
            ack = (await r.read_frames(2))[1]
            assert type(ack).__name__ == "Puback"
        for r in pubs + [sub]:
            r.close()
    finally:
        await broker.stop()
        await server.stop()


@pytest.mark.asyncio
@pytest.mark.parametrize("edge", ["tracer", "governor", "hook", "rate",
                                  "disabled", "netsplit", "closed",
                                  "disconnected"])
async def test_the_task_side_gate_is_the_whole_gate(edge):
    """``Session.wire_fast_ready`` — what the connection's task checks
    per batch and after every await — is both halves: each broker-wide
    edge closes it with the session's half open, each session edge with
    the broker-wide half open."""
    from vernemq_tpu.broker.session import wire_broker_ready

    broker, server = await boot()
    try:
        pub = await Raw.connect(server.port, "wgpub")
        session = session_of(broker, "wgpub")
        assert session.wire_fast_ready()
        if edge == "tracer":
            broker.start_trace("nobody-by-this-name")
        elif edge == "governor":
            broker.overload.pin(2)
        elif edge == "hook":
            broker.hooks.register("auth_on_publish", lambda *a, **kw: None)
        elif edge == "rate":
            broker.config.set("max_message_rate", 10)
        elif edge == "disabled":
            broker.config.set("wire_fastpath_enabled", False)
        elif edge == "netsplit":
            broker._cluster_ready = False
        elif edge == "closed":
            session.closed = True
        else:
            session.connected = False
        session_edge = edge in ("closed", "disconnected")
        assert wire_broker_ready(broker) is session_edge
        assert session.wire_session_ready() is not session_edge
        assert not session.wire_fast_ready()
        if edge == "governor":
            broker.overload.pin(None)
        session.closed = False
        pub.close()
    finally:
        await broker.stop()
        await server.stop()
