"""Shared subscriptions keyed as MQTT 5.0 section 4.8.2 keys them.

A shared subscription is (ShareName, filter): "a message that matches both
Shared Subscriptions will be processed separately by both of them". So
``$share/g/a/+`` and ``$share/g/a/#`` (disjoint members) and
``$share/h/#`` are THREE shared subscriptions, and a publish to ``a/x``
owes one delivery to a member of each. What each is owed is taken from
the benchmark's plain reference (``benchmark/reference.py``: ``Shared``,
``session_trie``), which imports nothing of the program.

Held here: the wire plane's one pass and the classic ``route_rows`` make
the three deliveries, on twin brokers, byte for byte alike; the trie, the
device view and the match service each hold and fold the three rows;
unsubscribing one of the two ``g`` filters leaves the other's row, members
and deliveries; the draws for a ShareName met again under another filter
are counted; and the long match program (the dense pass over region 0
beside probe A) finds a ``#`` row and the probe-A rows as the host trie
does."""

import random

import pytest

from benchmark import reference
from benchmark.corpus import LiveSession
from tests.test_fanout_pass import TOPIC as _ASIDE
from tests.test_fanout_pass import Twin as _FanoutTwin
from tests.test_match_service import _Env
from tests.test_phase_skip import Pair
from tests.test_share_model import Program
from vernemq_tpu.broker.message import Msg
from vernemq_tpu.models import tpu_matcher as tm
from vernemq_tpu.observability.profiler import profiler
from vernemq_tpu.protocol import fastpath
from vernemq_tpu.protocol.types import SubOpts

TOPIC = ("a", "x")
PAYLOAD = b"0123456789abcdef"
#: (ShareName, filter) -> its members' client ids; disjoint
SHARED = {
    ("g", "a/+"): ["g1-0", "g1-1", "g1-2"],
    ("g", "a/#"): ["g2-0", "g2-1"],
    ("h", "#"): ["h-0", "h-1"],
}
#: a plain subscriber beside them, owed its own delivery
PLAIN = {"p-0": "a/x"}


def _words(share, filt):
    return ["$share", share, *filt.split("/")]


def owed(topic, plain=True):
    """The reference's verdict for one publish to ``topic``: the clients
    each owed one delivery of their own, and for each shared subscription
    owed one, the clients any one of which may read it."""
    live = [LiveSession(c, True, [(f"$share/{s}/{f}", 1)])
            for (s, f), cs in SHARED.items() for c in cs]
    if plain:
        live += [LiveSession(c, True, [(f, 1)]) for c, f in PLAIN.items()]
    shared = reference.Shared(live)
    own, groups = [], []
    for idx, _q in reference.session_trie(live, shared).match(list(topic)):
        if idx < shared.n_sessions:
            own.append(live[idx].client_id)
        else:
            i = idx - shared.n_sessions
            groups.append((shared.subs[i], {live[m].client_id
                                            for m in shared.members(i)}))
    return own, groups


def check_served(got, topic, plain=True):
    """``got``: client -> deliveries read. Exactly the reference's."""
    own, groups = owed(topic, plain)
    for c in own:
        assert got.get(c, 0) == 1, c
    for sub, members in groups:
        assert sum(got.get(c, 0) for c in members) == 1, sub
    assert sum(got.values()) == len(own) + len(groups)
    return len(groups)


class Twin(_FanoutTwin):
    """A broker of the pair, with the shared subscriptions above."""

    def __init__(self):
        super().__init__([])
        for (share, filt), clients in SHARED.items():
            for c in clients:
                self._member(c, _words(share, filt))
        for c, f in PLAIN.items():
            self._member(c, f.split("/"))

    def _member(self, cid, words):
        self._subscriber(cid, dict(ver=4, qos=1))
        sid = ("", cid)
        self.reg.unsubscribe(sid, [list(_ASIDE)])
        self.reg.subscribe(sid, [(words, SubOpts(qos=1))])

    def route_topic(self, topic, qos, wire, seed):
        rows = list(self.reg.trie("").match(list(topic)))
        random.seed(seed)  # the draws, alike on both
        if wire:
            self.reg._wire_route("", topic, "/".join(topic), PAYLOAD, rows,
                                 None, qos=qos)
        else:
            msg = Msg(topic=topic, payload=PAYLOAD, qos=qos)
            self.reg.route_rows(msg, self.reg._filter_rows_host(msg, rows),
                                None)
        self.b.outbox.flush()

    def reads(self):
        """Deliveries each client's sockets read since the last call."""
        got = {cid: sum(spy.stream().count(PAYLOAD) for spy in spies)
               for cid, spies in self.spies.items()}
        for spies in self.spies.values():
            for spy in spies:
                spy.calls.clear()
        return got


@pytest.mark.asyncio
@pytest.mark.parametrize("qos", [0, 1])
@pytest.mark.parametrize("seed", [41, 2147483690])
async def test_each_shared_subscription_gets_one_delivery_on_both_paths(
        seed, qos):
    wire, classic = Twin(), Twin()
    passed = fastpath.fanout_pass_pubs
    wire.route_topic(TOPIC, qos, True, seed)
    # the wire pass served it whole: a name met twice is no complex row
    assert fastpath.fanout_pass_pubs == passed + 1
    classic.route_topic(TOPIC, qos, False, seed)
    assert wire.state() == classic.state()
    assert check_served(wire.reads(), TOPIC) == 3
    assert check_served(classic.reads(), TOPIC) == 3


@pytest.mark.asyncio
@pytest.mark.parametrize("wire", [True, False], ids=["wire", "classic"])
async def test_split_picks_count_a_name_drawn_again(wire):
    twin = Twin()
    picks, split = fastpath.share_picks, fastpath.share_split_picks
    twin.route_topic(TOPIC, 1, wire, 5)
    # three draws, the second under ShareName g is a split one
    assert (fastpath.share_picks - picks,
            fastpath.share_split_picks - split) == (3, 1)
    assert check_served(twin.reads(), TOPIC) == 3
    # b/x: only $share/h/# matches; a: a/# and # match, no name twice
    for topic, want in ((("b", "x"), (1, 0)), (("a",), (2, 0))):
        picks, split = fastpath.share_picks, fastpath.share_split_picks
        twin.route_topic(topic, 1, wire, 6)
        assert (fastpath.share_picks - picks,
                fastpath.share_split_picks - split) == want
        assert check_served(twin.reads(), topic) == want[0]


def _program(view):
    """The shared subscriptions alone (no plain subscriber: the match
    service's test worker names another node than its broker's), and a
    publisher that is no member."""
    env = None
    if view == "service":
        env = _Env()
        env.start_drainer()
    prog = Program("prefer_local", view, env)
    for c in [c for cs in SHARED.values() for c in cs] + ["pub"]:
        prog.connect(c, True)
    for (share, filt), clients in SHARED.items():
        for c in clients:
            prog.reg.subscribe(("", c), [(_words(share, filt),
                                          SubOpts(qos=1))])
    return prog, env


def _group_rows(prog):
    return {where: sorted((tuple(f), k) for f, k in rows)
            for where, rows in prog.group_rows().items()}


def _served(prog, topic):
    got = {}
    for c, m in prog.publish(topic, PAYLOAD, 1, "pub"):
        assert m.payload == PAYLOAD and m.qos == 1
        got[c] = got.get(c, 0) + 1
    return got


@pytest.mark.asyncio
@pytest.mark.parametrize("view", ["trie", "device", "service"])
async def test_every_view_holds_three_rows_and_serves_three(view):
    prog, env = _program(view)
    try:
        want = sorted([(("a", "+"), ("$g", "g", None)),
                       (("a", "#"), ("$g", "g", None)),
                       (("#",), ("$g", "h", None))])
        rows = _group_rows(prog)
        assert set(rows) >= {"trie"} and all(r == want
                                             for r in rows.values())
        for seed in range(6):
            random.seed(seed)
            assert check_served(_served(prog, TOPIC), TOPIC, False) == 3
    finally:
        if env is not None:
            env.close()


@pytest.mark.asyncio
@pytest.mark.parametrize("view", ["trie", "device", "service"])
async def test_unsubscribing_one_filter_keeps_the_others(view):
    prog, env = _program(view)
    try:
        reg = prog.reg
        plus = set(reg.share_group("", "g", ("a", "+")).members)
        # one member of g/a/# leaves: the row and the other member stay
        reg.unsubscribe(("", "g2-0"), [_words("g", "a/#")])
        assert set(reg.share_group("", "g", ("a", "#")).members) \
            == {("", "g2-1")}
        random.seed(3)
        assert _served(prog, TOPIC).get("g2-1") == 1
        # the last one leaves: that row goes, g/a/+ keeps its row and
        # its members, and is still served, as is $share/h/#
        reg.unsubscribe(("", "g2-1"), [_words("g", "a/#")])
        assert reg.share_group("", "g", ("a", "#")) is None
        assert set(reg.share_group("", "g", ("a", "+")).members) == plus
        want = sorted([(("a", "+"), ("$g", "g", None)),
                       (("#",), ("$g", "h", None))])
        assert all(r == want for r in _group_rows(prog).values())
        for seed in range(4):
            random.seed(seed)
            got = _served(prog, TOPIC)
            assert not got.get("g2-0") and not got.get("g2-1")
            assert sum(got.get(c, 0) for c in SHARED[("g", "a/+")]) == 1
            assert sum(got.get(c, 0) for c in SHARED[("h", "#")]) == 1
            assert sum(got.values()) == 2
    finally:
        if env is not None:
            env.close()


def test_the_long_program_finds_region0_beside_probe_a(monkeypatch):
    """A table of probe-A rows (``bench/<k>``, ``bench/+``, ``bench/#``)
    and ONE region-0 row (``#``): every program holds the dense pass
    (``ga``), its rows are the host trie's on seeded random topics, and
    each execution counts once as a dense one."""
    monkeypatch.setattr(tm, "phase_dispatches", 0)
    monkeypatch.setattr(tm, "phase_dense_dispatches", 0)
    p = Pair(capacity=8192)
    rng = random.Random(2147483641)
    for k in range(300):
        p.add(("bench", str(k)), ("", f"s{k}"))
    p.add(("bench", "+"), ("$g", "ingest", None))
    p.add(("bench", "#"), ("$g", "ingest", None))
    p.add(("#",), ("$g", "archive", None))
    assert p.m.table.bucketed and p.m.table.live_rows[0] == 1

    def topics(n):
        return [("bench", str(rng.randrange(400))) if rng.random() < 0.8
                else rng.choice([("bench",), ("x", "y"), ("$SYS", "a"),
                                 ("bench", "1", "2")]) for _ in range(n)]

    batch = topics(24)
    p.check(batch, p.m.match_batch(batch))
    batches = [topics(16), topics(11)]
    for b, got in zip(batches, p.m.match_many(batches)):
        p.check(b, got)
    single, many = profiler().snapshot("match")[-2:]
    assert single["phases"] == many["phases"] == "ga"
    assert tm.phase_dispatches == tm.phase_dense_dispatches == 2


@pytest.mark.asyncio
@pytest.mark.parametrize("view", ["trie", "device"])
async def test_a_member_with_a_predicate_in_both_filters_of_a_name(view):
    """One session, the only member of ``$share/g/a/+`` and of
    ``$share/g/a/#``, both with the payload predicate ``$gt(v,50)``: the
    predicate phase gives it a row under EACH filter, so a publish that
    passes is delivered to it twice (once a shared subscription) and one
    that fails not at all."""
    prog = Program("prefer_local", view, payload_schemas=[
        {"mountpoint": "", "topic": "a/+", "fields": "v:number"}])
    for c in ("hot", "pub"):
        prog.connect(c, True)
    for filt in ("a/+", "a/#"):
        o = SubOpts(qos=1)
        o.filter_expr = "$gt(v,50)"
        prog.reg.subscribe(("", "hot"), [(_words("g", filt), o)])
    for v, want in ((90, 2), (10, 0), (51, 2)):
        payload = f'{{"v": {v}}}'.encode()
        got = prog.publish(("a", "1"), payload, 1, "pub")
        assert [c for c, m in got if m.payload == payload] == ["hot"] * want
