"""A seeded model of shared-subscription selection, against the program.

The model is upstream's selection written plainly
(``vmq_shared_subscriptions.erl:26-106``) and imports nothing of the
program: a publish to a shared subscription's filter goes to ONE member,
drawn from the first non-empty class of the policy — ``prefer_local``:
online local, then remote, then offline local; ``random``: online local
and remote together, then offline local; ``local_only``: online local,
then offline local — at min(publish QoS, the member's QoS); a member
subscribed with no-local never receives its own publish; a member whose
payload predicate fails, or who aggregates, is no candidate.

Random sequences of SUBSCRIBE, UNSUBSCRIBE, connect, disconnect, takeover
and publish, from a seed, over groups of 1, 3 and 300 members, run
against a broker's registry under the trie view, the device view (the
CPU backend) and the match service's rings, with a member on another
node and with members that carry a predicate. After every step the trie,
the device table and the service hold ONE row a shared subscription."""

import json
import random

import pytest

from vernemq_tpu.broker import reg as reg_mod
from vernemq_tpu.broker.broker import Broker
from vernemq_tpu.broker.config import Config
from vernemq_tpu.broker.match_service import ShmMatchView
from vernemq_tpu.broker.message import Msg
from vernemq_tpu.broker.queue import QueueOpts
from vernemq_tpu.broker.subscriber_db import SubscriberRecord
from vernemq_tpu.protocol import fastpath
from vernemq_tpu.protocol.types import SubOpts
from tests.test_match_service import _Env

LOCAL, FAR = "node1", "node2"
POLICIES = ("prefer_local", "random", "local_only")
#: group -> (filter words, a topic it matches, the clients that may join)
GROUPS = {
    "g1": (("a", "#"), ("a", "x"), 1),
    "g3": (("b", "+"), ("b", "y"), 3),
    "g300": (("c", "#"), ("c", "z", "w"), 300),
}


class Model:
    """What each publish is owed, from the members and who is online."""

    def __init__(self, policy):
        self.policy = policy
        self.subs = {g: {} for g in GROUPS}  # g -> client -> member
        self.online = set()

    def candidates(self, group, publisher, payload=None):
        mem = {c: m for c, m in self.subs[group].items()
               if not (m["no_local"] and c == publisher)
               and not m.get("agg")
               and (m.get("pred") is None or m["pred"](payload))}
        on = {c for c, m in mem.items()
              if m["node"] == LOCAL and c in self.online}
        remote = {c for c, m in mem.items() if m["node"] != LOCAL}
        off = {c for c, m in mem.items()
               if m["node"] == LOCAL and c not in self.online}
        if self.policy == "local_only":
            classes = [on, off]
        elif self.policy == "random":
            classes = [on | remote, off]
        else:
            classes = [on, remote, off]
        return next((c for c in classes if c), set())


class Program:
    """A broker's registry driven as sessions drive it, every delivery
    caught: a session's deliver callback, an offline queue's backlog, the
    cluster's enqueue to another node."""

    def __init__(self, policy, view, env=None, **cfg):
        default = "trie" if view != "device" else "tpu"
        self.b = Broker(Config(default_reg_view=default,
                               systree_enabled=False, allow_anonymous=True,
                               shared_subscription_policy=policy, **cfg))
        self.reg = self.b.registry
        self.view = view
        if env is not None:
            self.reg.reg_views["tpu"] = ShmMatchView(self.reg, env.client)
        self.env = env
        self.inbox = {}
        self.handles = {}
        self.far = []
        self.reg.remote_enqueue_nowait = (
            lambda node, sid, msgs: self.far.extend(
                (sid[1], m) for m in msgs) is None)

    def connect(self, c, clean):
        """CONNECT, or a takeover of a connected client's session."""
        old = self.handles.pop(c, None)
        q, _ = self.reg.register_subscriber(
            ("", c), clean, QueueOpts(clean_session=clean))
        h = object()
        q.add_session(h, lambda m, c=c: self.inbox[c].append(m) is None)
        if old is not None and old[0] is q:
            q.del_session(old[1])
        self.handles[c] = (q, h)
        self.inbox[c] = []   # a resumed backlog is no publish of this step

    def disconnect(self, c):
        q, h = self.handles.pop(c)
        q.del_session(h)

    def subscribe(self, c, group, opts):
        words = ["$share", group, *GROUPS[group][0]]
        self.reg.subscribe(("", c), [(words, opts)])

    def unsubscribe(self, c, group):
        self.reg.unsubscribe(("", c), [["$share", group, *GROUPS[group][0]]])

    def publish(self, topic, payload, qos, publisher):
        msg = Msg(topic=tuple(topic), payload=payload, qos=qos)
        for box in self.inbox.values():
            box.clear()
        self.far.clear()
        backlog = {sid[1]: len(q.offline)
                   for sid, q in self.reg.queues.items()}
        view = None if self.view == "trie" else "tpu"
        self.reg.publish(msg, ("", publisher), reg_view=view)
        got = [(c, m) for c, box in self.inbox.items() for m in box]
        got += [(sid[1], q.offline[-1])
                for sid, q in self.reg.queues.items()
                if len(q.offline) > backlog.get(sid[1], 0)]
        return got + self.far

    def group_rows(self):
        """Each store's rows of shared subscriptions: [(filter, key)]."""
        out = {"trie": [(f, k) for f, k, _v in self.reg.trie("").entries()
                        if isinstance(k, tuple) and k[0] == "$g"]}
        tpu = self.reg.reg_views.get("tpu")
        m = getattr(tpu, "_matchers", {}).get("")
        if m is not None:
            out["device"] = [(e[0], e[1]) for e in m.table.entries
                             if e is not None and e[1][0] == "$g"]
        if self.env is not None:
            # a fold rides the ring behind every op sent: once it is
            # answered the service has applied them all
            self.env.client.fold("", [("-",)])
            out["service"] = [(fw, k) for (_mp, fw, k) in self.env.svc._subs
                              if k[0] == "$g"]
        return out


def check_rows(prog, model):
    want = sorted((GROUPS[g][0], ("$g", g, None))
                  for g, mem in model.subs.items() if mem)
    for where, rows in prog.group_rows().items():
        assert sorted((tuple(f), k) for f, k in rows) == want, where


def run(seed, policy, view, steps=260, env=None, far=False):
    rng = random.Random(seed)
    model, prog = Model(policy), Program(policy, view, env)
    clients = {g: [f"{g}-{i}" for i in range(n)]
               for g, (_f, _t, n) in GROUPS.items()}
    everyone = [c for cs in clients.values() for c in cs] + ["p0", "p1"]
    clean = {c: rng.random() < 0.5 for c in everyone}
    group_of = {c: g for g, cs in clients.items() for c in cs}

    def join(c, g):
        no_local = rng.random() < 0.2
        qos = rng.choice((0, 1, 2) if clean[c] else (1, 2))
        prog.subscribe(c, g, SubOpts(qos=qos, no_local=no_local))
        model.subs[g][c] = {"qos": qos, "no_local": no_local, "node": LOCAL}

    for c in everyone:              # every group full at the start
        prog.connect(c, clean[c])
        model.online.add(c)
        if c in group_of:
            join(c, group_of[c])
    if far:                         # a member whose queue is on node2
        for g in GROUPS:
            opts = SubOpts(qos=1)
            prog.reg.db.store(("", f"{g}-far"), SubscriberRecord(
                FAR, True, {("$share", g, *GROUPS[g][0]): opts}))
            model.subs[g][f"{g}-far"] = {"qos": 1, "no_local": False,
                                         "node": FAR}
    check_rows(prog, model)
    picked = {g: set() for g in GROUPS}
    small = clients["g1"] + clients["g3"] + ["p0", "p1"]
    for step in range(steps):
        # the small groups' members often: their classes empty and fill
        c = rng.choice(small if rng.random() < 0.5 else everyone)
        op = rng.choices(("connect", "disconnect", "takeover", "subscribe",
                          "unsubscribe", "publish"),
                         (12, 12, 4, 14, 6, 52))[0]
        g = group_of.get(c)
        if op == "connect" and c not in model.online:
            prog.connect(c, clean[c])
            if clean[c]:
                for mem in model.subs.values():
                    mem.pop(c, None)
            model.online.add(c)
        elif op == "disconnect" and c in model.online:
            prog.disconnect(c)
            model.online.discard(c)
            if clean[c]:            # a clean session's subscriptions go
                for mem in model.subs.values():
                    mem.pop(c, None)
        elif op == "takeover" and c in model.online:
            prog.connect(c, clean[c])
            if clean[c]:
                for mem in model.subs.values():
                    mem.pop(c, None)
        elif op == "subscribe" and g and c in model.online:
            join(c, g)
        elif op == "unsubscribe" and g and c in model.online \
                and c in model.subs[g]:
            prog.unsubscribe(c, g)
            del model.subs[g][c]
        elif op == "publish" and model.online:
            tg = rng.choice(list(GROUPS))
            # half the time a member of the group publishes (no-local)
            mine = [x for x in clients[tg] if x in model.online]
            c = rng.choice(mine if mine and rng.random() < 0.5
                           else sorted(model.online))
            qos = rng.choice((1, 2))
            payload = f"{seed}:{step}".encode()
            got = prog.publish(GROUPS[tg][1], payload, qos, c)
            owed = model.candidates(tg, c)
            assert all(m.payload == payload for _r, m in got)
            if not owed:
                assert got == [], (step, tg, got)
            else:
                assert len(got) == 1, (step, tg, [r for r, _m in got])
                who, m = got[0]
                assert who in owed, (step, tg, who, owed)
                assert m.qos == min(qos, model.subs[tg][who]["qos"])
                picked[tg].add(who)
        check_rows(prog, model)
    return picked


@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("view", ["trie", "device"])
def test_every_publish_reaches_one_member_of_the_right_class(view, policy):
    picked = run(2147483001 + POLICIES.index(policy), policy, view)
    assert len(picked["g300"]) > 10      # not the same member every time


@pytest.mark.parametrize("policy", POLICIES)
def test_a_member_on_another_node_is_drawn_by_class(policy):
    picked = run(2147483101, policy, "trie", steps=160, far=True)
    far = {g for g, who in picked.items() if f"{g}-far" in who}
    if policy == "local_only":
        assert not far
    elif policy == "random":
        assert far


def test_through_the_match_service():
    env = _Env()
    env.start_drainer()
    try:
        run(2147483201, "prefer_local", "service", steps=140, env=env)
    finally:
        env.close()


def test_members_with_a_predicate_are_drawn_only_when_it_passes():
    """``$share/gf/f/+``: two plain members, one ``$gt(v,50)`` member, one
    aggregating member (``$count(3)``, fed whatever the draw). The
    predicate member is a candidate only for a publish above 50; the
    aggregating member never is, and its window still closes on its own
    three publishes; the table holds ONE row."""
    prog = Program("prefer_local", "device", payload_schemas=[
        {"mountpoint": "", "topic": "f/+", "fields": "v:number"}])
    model = Model("prefer_local")
    plain, hot, agg = ["m0", "m1"], "hot", "agg"
    for c in plain + [hot, agg, "pub"]:
        prog.connect(c, True)
        model.online.add(c)
    GROUPS["gf"] = (("f", "+"), ("f", "1"), 4)
    model.subs["gf"] = {}
    try:
        for c in plain:
            prog.subscribe(c, "gf", SubOpts(qos=1))
            model.subs["gf"][c] = {"qos": 1, "no_local": False,
                                   "node": LOCAL}
        o = SubOpts(qos=1)
        o.filter_expr = "$gt(v,50)"
        prog.subscribe(hot, "gf", o)
        model.subs["gf"][hot] = {
            "qos": 1, "no_local": False, "node": LOCAL,
            "pred": lambda p: json.loads(p)["v"] > 50}
        o = SubOpts(qos=1)
        o.filter_expr = "$count(3)"
        prog.subscribe(agg, "gf", o)
        model.subs["gf"][agg] = {"qos": 1, "no_local": False,
                                 "node": LOCAL, "agg": True}
        rng = random.Random(7)
        hot_got, aggs = 0, 0
        for i in range(60):
            v = rng.choice((10, 90))
            payload = json.dumps({"v": v}).encode()
            got = prog.publish(("f", "1"), payload, 1, "pub")
            raw = [(c, m) for c, m in got if m.payload == payload]
            aggs += len(got) - len(raw)
            assert [c for c, m in got if m.payload != payload] \
                in ([], [agg])
            assert len(raw) == 1
            assert raw[0][0] in model.candidates("gf", "pub", payload)
            hot_got += raw[0][0] == hot
            check_rows(prog, model)
        assert hot_got and aggs == 20
        prog.unsubscribe(hot, "gf")
        prog.unsubscribe(agg, "gf")
        del model.subs["gf"][hot], model.subs["gf"][agg]
        assert not prog.b.filter_engine.wants("")
        check_rows(prog, model)
    finally:
        del GROUPS["gf"]


@pytest.mark.parametrize("view", ["trie", "device"])
def test_the_drawn_members_own_options_apply_and_no_retained_replay(view):
    """``_prep_out`` for the member drawn: its QoS, its retain-as-published
    flag and its subscription identifier; a retained message is never
    replayed to a shared subscription."""
    prog = Program("prefer_local", view)
    prog.connect("pub", True)
    prog.reg.publish(Msg(topic=("r", "x"), payload=b"kept", qos=1,
                         retain=True), ("", "pub"))
    for c, rap, sub_id in (("keeps", True, 7), ("clears", False, None)):
        prog.connect(c, True)
        o = SubOpts(qos=1, rap=rap)
        o.subscription_id = sub_id
        prog.reg.subscribe(("", c), [(["$share", c, "r", "#"], o)])
    assert prog.inbox["keeps"] == prog.inbox["clears"] == []
    got = dict(prog.publish(("r", "x"), b"live", 2, "pub"))
    assert got["keeps"].qos == got["clears"].qos == 1
    # a live publish with the retain flag set: kept for rap alone
    prog.reg.publish(Msg(topic=("r", "x"), payload=b"flag", qos=2,
                         retain=True), ("", "pub"),
                     reg_view=None if view == "trie" else "tpu")
    keeps, clears = prog.inbox["keeps"][-1], prog.inbox["clears"][-1]
    assert keeps.retain is True and clears.retain is False
    assert keeps.properties["subscription_identifier"] == [7]
    assert "subscription_identifier" not in clears.properties


class _Counted(dict):
    gets = 0

    def get(self, *a):
        _Counted.gets += 1
        return super().get(*a)


@pytest.mark.parametrize("policy", POLICIES)
def test_a_draw_costs_the_same_at_500_members_as_at_3(policy, monkeypatch):
    """Queue look-ups a publish, counted: one for the draw, one for the
    delivery, whatever the group's size (the list it once built was a
    look-up a member, and its offline class a scan of the online one)."""
    looks = {}
    for n in (3, 500):
        prog = Program(policy, "trie")
        for i in range(n):
            prog.connect(f"m{i}", True)
            prog.reg.subscribe(("", f"m{i}"),
                               [(["$share", "g", "g", "#"], SubOpts(qos=1))])
        prog.connect("pub", True)
        monkeypatch.setattr(prog.reg, "queues", _Counted(prog.reg.queues))
        _Counted.gets = 0
        picks = fastpath.share_picks
        for k in range(50):
            prog.reg.publish(Msg(topic=("g", str(k)), payload=b"x", qos=1),
                             ("", "pub"))
        assert fastpath.share_picks == picks + 50
        looks[n] = _Counted.gets / 50
    assert looks[3] == looks[500] <= 3, looks
    assert reg_mod.Registry._SHARE_DRAWS < 500
