"""The wire plane's one-pass fan-out against the classic path.

One publish is routed on twin brokers built alike: through the wire
plane (``Registry._wire_route``: one pass over the matched rows, the
recipients registered by ``wire_take_qos_fanout`` and queued by
``Outbox.queue_frames``) on one, through the classic ``route_rows`` (a
``Msg`` a recipient through its queue and session) on the other. Each
subscriber socket must read the same bytes, each session hold the same
in-flight entries and pending messages, each queue the same backlog, and
the six egress counters read the same. A case whose rows hold a complex
one sends the whole publish to the classic path, and is counted so.

The fan-out form of the in-flight take (``wire_take_qos_fanout``) is held
to the classic deliver path's, one message at a time: the same packet
ids, the same decisions."""

import random

import pytest

from vernemq_tpu.broker.broker import Broker
from vernemq_tpu.broker.config import Config
from vernemq_tpu.broker.egress import StreamTransport
from vernemq_tpu.broker.message import Msg
from vernemq_tpu.broker.queue import QueueOpts
from vernemq_tpu.broker.session import Session, wire_take_qos_fanout
from vernemq_tpu.protocol import fastpath
from vernemq_tpu.protocol.types import SubOpts
from tests.test_wire_plane import SockSpy

TOPIC = ("sport", "score")
PAYLOAD = b"0123456789abcdef"
#: the counters the outbox folds into Metrics, and the drop a full
#: backlog counts
COUNTERS = ("bytes_sent", "mqtt_publish_sent", "mqtt_puback_sent",
            "queue_message_in", "queue_message_out", "router_matches_local",
            "queue_message_drop")
WINDOW = 20  # max_inflight_messages
ONLINE_CAP = 4  # max_online_messages: pending past it takes the backlog


def plain(n, ver=4, qos=1, **kw):
    """``n`` subscribers alike: (version, subscription QoS, options)."""
    return [dict(ver=ver, qos=qos, **kw) for _ in range(n)]


#: case -> (publish QoS, subscribers, whether the pass serves it). A
#: subscriber: ``ver``, ``qos``; ``alias`` (v5 topic aliases the client
#: takes; one is established for another topic first), ``full`` (its
#: window holds WINDOW in-flight entries), ``pending_full`` (and its
#: pending ONLINE_CAP messages), ``no_local`` (it is the publisher),
#: ``closed``, ``sessions`` (sessions of its queue), ``share`` (a member
#: of group "g" and not a plain subscriber)
CASES = {
    "v4-qos1-x1": (1, plain(1), True),
    "v4-qos1-x3": (1, plain(3), True),
    "v4-qos1-x300": (1, plain(300), True),
    "v5-alias-qos1-x3": (1, plain(3, ver=5, alias=2), True),
    "v5-alias-qos1-x300": (1, plain(150, ver=5, alias=3) + plain(150), True),
    "qos0-v4-v5-x3": (0, plain(2) + plain(1, ver=5, alias=1), True),
    "downgrade-qos2-v4-v5": (2, [dict(ver=4, qos=0), dict(ver=5, qos=1),
                                 dict(ver=4, qos=2), dict(ver=5, qos=0,
                                                          alias=1),
                                 dict(ver=4, qos=1), dict(ver=5, qos=2)],
                             True),
    "downgrade-qos1-to-qos0-x300": (1, plain(300, qos=0), True),
    "window-full-at-k": (1, plain(2) + plain(1, full=True) + plain(3),
                         True),
    "past-online-cap": (1, plain(1) + plain(2, full=True,
                                            pending_full=True) + plain(1),
                        True),
    "no-local": (1, plain(2) + plain(1, ver=5, no_local=True), True),
    "closed-session": (1, plain(2) + plain(1, closed=True), False),
    "multi-session-queue": (1, plain(1) + plain(1, sessions=2), False),
    "share-among-plain": (1, plain(2) + plain(3, share=True) + plain(2),
                          True),
}


class Twin:
    """One broker of the pair, its subscribers built from a case."""

    def __init__(self, subs):
        self.b = Broker(Config(default_reg_view="trie",
                               systree_enabled=False, allow_anonymous=True,
                               max_inflight_messages=WINDOW,
                               max_online_messages=ONLINE_CAP))
        self.reg = self.b.registry
        self.spies = {}  # client -> its sessions' socket spies
        self.sessions = {}
        self.from_sid = None
        for i, sub in enumerate(subs):
            self._subscriber(f"c{i}", sub)

    def _subscriber(self, cid, sub):
        sid = ("", cid)
        q, _ = self.reg.register_subscriber(
            sid, True, QueueOpts(max_online_messages=ONLINE_CAP))
        for _ in range(sub.get("sessions", 1)):
            spy = SockSpy()
            s = Session(self.b, StreamTransport(spy, self.b.outbox),
                        sub["ver"])
            s.client_id, s.sid, s.connected, s.queue = cid, sid, True, q
            if sub.get("alias"):
                s.topic_alias_max_out = sub["alias"]
                s.topic_alias_out[("other",)] = 1  # established earlier
            if sub.get("full"):
                old = Msg(topic=("old",), payload=b"o", qos=1)
                s.waiting_acks = {p: ["puback", old, 0.0, False]
                                  for p in range(1, WINDOW + 1)}
                s._next_pid = WINDOW
                if sub.get("pending_full"):
                    s.pending = [old] * ONLINE_CAP
            s.closed = bool(sub.get("closed"))
            q.add_session(s, s._queue_deliver)
            self.spies.setdefault(cid, []).append(spy)
            self.sessions.setdefault(cid, []).append(s)
        if sub.get("no_local"):
            self.from_sid = sid
        if sub.get("share"):
            words = ["$share", "g", *TOPIC]
        else:
            words = list(TOPIC)
        self.reg.subscribe(sid, [(words, SubOpts(
            qos=sub["qos"], no_local=bool(sub.get("no_local"))))])

    def route(self, qos, wire):
        rows = list(self.reg.trie("").match(list(TOPIC)))
        random.seed(40)  # the share group's draw, alike on both
        if wire:
            self.reg._wire_route("", TOPIC, "/".join(TOPIC), PAYLOAD, rows,
                                 self.from_sid, qos=qos)
        else:
            msg = Msg(topic=TOPIC, payload=PAYLOAD, qos=qos)
            self.reg.route_rows(msg, self.reg._filter_rows_host(msg, rows),
                                self.from_sid)
        self.b.outbox.flush()

    def state(self):
        def m(msg):
            return (msg.topic, msg.payload, msg.qos)

        out = {}
        for cid, sessions in self.sessions.items():
            q = self.reg.queues[("", cid)]
            out[cid] = {
                "read": [spy.stream() for spy in self.spies[cid]],
                "acks": [{p: (e[0], m(e[1])) for p, e in
                          s.waiting_acks.items()} for s in sessions],
                "pending": [[m(x) for x in s.pending] for s in sessions],
                "backlog": [m(x) for x in q.backlog],
                "offline": [m(x) for x in q.offline],
            }
        out["counters"] = {n: self.b.metrics.value(n) for n in COUNTERS}
        return out


@pytest.mark.asyncio
@pytest.mark.parametrize("case", list(CASES))
async def test_the_pass_routes_a_publish_as_the_classic_path(case):
    qos, subs, served = CASES[case]
    wire, classic = Twin(subs), Twin(subs)
    before = (fastpath.fanout_pass_pubs, fastpath.fanout_classic_pubs)
    wire.route(qos, wire=True)
    moved = (fastpath.fanout_pass_pubs - before[0],
             fastpath.fanout_classic_pubs - before[1])
    assert moved == ((1, 0) if served else (0, 1))
    classic.route(qos, wire=False)
    got, want = wire.state(), classic.state()
    assert got == want
    # not a comparison of two silences: some socket read the publish
    assert any(any(st["read"]) for cid, st in want.items()
               if cid != "counters")


def _take_twin():
    """Sessions in every state the take decides on, built alike; the
    last one has no queue."""
    b = Broker(Config(default_reg_view="trie", systree_enabled=False,
                      allow_anonymous=True, max_inflight_messages=WINDOW,
                      max_online_messages=ONLINE_CAP))
    old = Msg(topic=("old",), payload=b"o", qos=1)
    full = range(1, WINDOW + 1)
    specs = [
        dict(),                                       # free window
        dict(next_pid=4, taken=(5, 6, 7)),            # ids in use skipped
        dict(next_pid=65534, taken=(65535, 1)),       # the wrap
        dict(receive_max=2, taken=(1, 2)),            # the client's window
        dict(taken=full),                             # full: pending
        dict(taken=full, pending=ONLINE_CAP - 1),     # the last pending
        dict(taken=full, pending=ONLINE_CAP),         # past it: backlog
        dict(receive_max=65535, next_pid=9),
        dict(taken=full, pending=ONLINE_CAP, queue=False),  # dropped
    ]
    sessions = []
    for i, spec in enumerate(specs):
        sid = ("", f"t{i}")
        s = Session(b, StreamTransport(SockSpy(), b.outbox), 4)
        s.client_id, s.sid, s.connected = sid[1], sid, True
        s._next_pid = spec.get("next_pid", 0)
        s.receive_max_out = spec.get("receive_max", 65535)
        s.waiting_acks = {p: ["puback", old, 0.0, False]
                          for p in spec.get("taken", ())}
        s.pending = [old] * spec.get("pending", 0)
        if spec.get("queue", True):
            s.queue, _ = b.registry.register_subscriber(
                sid, True, QueueOpts(max_online_messages=ONLINE_CAP))
            s.queue.add_session(s, s._queue_deliver)
        sessions.append(s)
    return b, sessions


@pytest.mark.asyncio
@pytest.mark.parametrize("qos", [1, 2])
async def test_the_fanout_take_is_the_classic_per_message_take(qos):
    """``wire_take_qos_fanout`` against the classic deliver path, one
    message at a time through each session's queue
    (``SubscriberQueue.enqueue`` → ``Session._queue_deliver``): the same
    packet ids, entries, pending and backlog, round after round."""
    (b1, one), (b2, many) = _take_twin(), _take_twin()

    def state(sessions):
        return [(s._next_pid,
                 {p: (e[0], e[1].topic) for p, e in s.waiting_acks.items()},
                 [m.topic for m in s.pending],
                 [m.topic for m in s.queue.backlog] if s.queue else None)
                for s in sessions]

    for r in range(3):  # the later rounds meet the earlier ones' ids
        topic = ("round", str(r))
        msg = Msg(topic=topic, payload=PAYLOAD, qos=qos)
        for s in one[:-1]:
            s.queue.enqueue(Msg(topic=topic, payload=PAYLOAD, qos=qos))
        takers, pids, pended, backlogged = wire_take_qos_fanout(
            many, msg, WINDOW, ONLINE_CAP)
        assert state(many[:-1]) == state(one[:-1])
        assert pids == [p for s in many[:-1]
                        for p, e in s.waiting_acks.items() if e[1] is msg]
        assert takers == [s for s in many
                          if any(e[1] is msg for e in s.waiting_acks.values())]
        assert (pended, backlogged) == (
            sum(s.pending.count(msg) for s in many),
            sum(list(s.queue.backlog).count(msg) for s in many if s.queue))
        entries = [e for s in many for e in s.waiting_acks.values()
                   if e[1] is msg]
        assert entries and len(entries) == len(takers)
        # one kind and one clock reading for the fan-out's entries
        assert {e[0] for e in entries} == {"puback" if qos == 1
                                           else "pubrec"}
        assert len({e[2] for e in entries}) == 1
    # with no queue to take it, a message past the pending cap is dropped
    # and counted
    assert not many[-1].pending[ONLINE_CAP:]
    assert b2.metrics.value("queue_message_drop") == 3
    assert b1.metrics.value("queue_message_drop") == 0
