"""What a shared subscription is owed (``reference``'s rule): ONE delivery
of each matching publish, read by ONE member that holds a connection,
whichever process the member's socket is in. On hand-made corpora through
the reference's own calls; through the subscriber processes' code fed
with bytes (no sockets) up to the parent's verdict, each fault planted
and owned by its number alone; that a corpus with no ``$share`` filter is
owed what it was owed before; and end to end on the fixture
(``fixture/BENCHMARK.json``: one group of 40 members, 48 publishers,
``tick1s``) against the control and against the program on the CPU."""

import hashlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import corpus as corpus_mod
from benchmark import harness, loadgen, mqtt
from benchmark import reference as R
from benchmark.corpus import LiveSession
from benchmark.manifest import Manifest
from benchmark.refbroker import BREAKS
from benchmark.run import rehearsal_sizes
from benchmark.tests.conftest import ROOT
from benchmark.tests.test_run import KEYS

FIXTURE = os.path.join(ROOT, "benchmark", "tests", "fixture")
CELL = "share40.tick1s"
NUMBERS = ("lost_qos1", "lost_qos0", "duplicates", "strays")


# ------------------------------------------------ the rule, by hand

POOLS = [["a", "$SYS"], ["x", "y"]]
SIZES = [2, 2]


def member(cid, *filters, qos=1):
    return LiveSession(cid, True, [(f, qos) for f in filters])


def judge(live, publishes, frames, shards=2, pub_qos=1):
    """The calls the subscriber processes and the parent make, on a
    hand-made corpus. ``publishes``: ``(publisher, level indices)`` in
    sending order, sequence numbers counted per publisher; ``frames``:
    ``(index into live, publisher, sequence, QoS)`` as sockets read them.
    Returns the four counts and ``owed``."""
    seqs, levels, pub_of, seq_of = {}, [], [], []
    for p, lv in publishes:
        seq_of.append(seqs.get(p, 0))
        seqs[p] = seq_of[-1] + 1
        pub_of.append(p)
        levels.append(lv)
    levels = np.asarray(levels, np.int32).reshape(len(publishes), 2)
    pub_of, seq_of = np.asarray(pub_of), np.asarray(seq_of)
    shared = R.Shared(live)
    out = dict.fromkeys(NUMBERS + ("owed",), 0)
    share_rec, share_exp = [], None
    for k in range(shards):
        sessions = live[k::shards]
        exp = R.expected_keys(R.session_trie(sessions, shared), POOLS, SIZES,
                              levels, pub_of, seq_of, pub_qos)
        rec = np.asarray([R.key(g // shards, p, q, s)
                          for g, p, s, q in frames if g % shards == k],
                         np.int64)
        plain_exp, plain_rec, share_exp, got, _by = R.attribute(
            shared, exp, rec, np.arange(k, len(live), shards), pub_qos)
        share_rec.append(got)
        cmp = R.compare(plain_exp, plain_rec)
        for name in out:
            out[name] += cmp[name]
    cmp = R.compare(share_exp, np.concatenate(share_rec))
    assert cmp["strays"] == 0  # only what is owed is ever attributed
    for name in out:
        out[name] += cmp[name]
    return out


def clean(**over):
    return dict(dict.fromkeys(NUMBERS + ("owed",), 0), **over)


AX, AY, SYS = [0, 0], [0, 1], [1, 0]  # a/x, a/y, $SYS/x


def test_one_group_is_owed_one_delivery_whichever_process_reads_it():
    live = [member(f"m{i}", "$share/g/a/#") for i in range(3)]
    pubs = [(0, AX), (0, AX), (1, AY)]
    # members 0 and 2 are the first process's, member 1 the second's
    for reader in range(3):
        frames = [(reader, 0, 0, 1), ((reader + 1) % 3, 0, 1, 1),
                  (reader, 1, 0, 1)]
        assert judge(live, pubs, frames) == clean(owed=3), reader


def test_the_share_is_one_value_in_the_trie_not_a_value_a_member():
    live = [member(f"m{i}", "$share/g/a/#") for i in range(5)]
    trie = R.session_trie(live)
    assert trie.match(["a", "x"]) == [(5, 1)]  # owner: above every session
    assert R.session_trie(live[::2], R.Shared(live)).match(["a", "x"]) \
        == [(5, 1)]                            # the same in every process
    assert R.split_share(("$share", "g", "a", "#")) == ("g", ("a", "#"))
    assert R.split_share(("a", "#")) is None
    assert R.split_share(("$share", "g")) is None


def test_two_groups_on_overlapping_filters_are_owed_one_each():
    live = [member("m0", "$share/g1/a/#"), member("m1", "$share/g1/a/#"),
            member("m2", "$share/g2/a/+"), member("m3", "$share/g2/a/+")]
    pubs = [(0, AX)]
    assert judge(live, pubs, [(1, 0, 0, 1), (2, 0, 0, 1)]) == clean(owed=2)
    # both deliveries inside g1: g1 read twice, g2 not at all
    assert judge(live, pubs, [(0, 0, 0, 1), (1, 0, 0, 1)]) \
        == clean(owed=2, duplicates=1, lost_qos1=1)


def test_a_plain_subscription_is_owed_beside_the_membership():
    live = [member("m0", "a/x", "$share/g/a/#"), member("m1", "$share/g/a/#")]
    pubs = [(0, AX)]
    both = [(0, 0, 0, 1), (0, 0, 0, 1)]       # its own and the group's
    assert judge(live, pubs, both) == clean(owed=2)
    apart = [(0, 0, 0, 1), (1, 0, 0, 1)]      # the group's to the other
    assert judge(live, pubs, apart) == clean(owed=2)
    assert judge(live, pubs, [(0, 0, 0, 1)]) == clean(owed=2, lost_qos1=1)
    # the other member's own frame is the group's; m0's plain one is lost
    assert judge(live, pubs, [(1, 0, 0, 1)]) == clean(owed=2, lost_qos1=1)
    assert judge(live, pubs, both + [(1, 0, 0, 1)]) \
        == clean(owed=2, duplicates=1)


def test_a_group_with_no_live_member_is_owed_nothing():
    # g2's members hold no connection: they are not among the live
    live = [member("m0", "$share/g1/a/x"), member("s1", "a/y")]
    assert judge(live, [(0, AY)], [(1, 0, 0, 1)]) == clean(owed=1)
    assert judge(live, [(0, AY)], [(1, 0, 0, 1), (0, 0, 0, 1)]) \
        == clean(owed=1, strays=1)


def test_a_dollar_topic_is_not_owed_to_a_wildcard_first_share():
    live = [member("m0", "$share/g/#"), member("m1", "$share/g/+/x")]
    assert judge(live, [(0, SYS)], []) == clean(owed=0)
    assert judge(live, [(0, SYS)], [(0, 0, 0, 1)]) == clean(strays=1)
    assert judge(live, [(0, AX)], [(0, 0, 0, 1), (1, 0, 0, 1)]) \
        == clean(owed=2)


def test_delivery_qos_is_the_reading_members_own():
    live = [member("m0", "$share/g/a/#", qos=1),
            member("m1", "$share/g/a/#", qos=0)]
    pubs = [(0, AX)]
    assert judge(live, pubs, [(0, 0, 0, 1)]) == clean(owed=1)
    assert judge(live, pubs, [(1, 0, 0, 0)]) == clean(owed=1)
    # at another QoS than the member's own it is nothing the group is owed
    assert judge(live, pubs, [(1, 0, 0, 1)]) \
        == clean(owed=1, strays=1, lost_qos1=1)
    assert judge(live, pubs, [(0, 0, 0, 0)], pub_qos=0) == clean(owed=1)


@pytest.mark.parametrize("fault,number", [
    ("lost", "lost_qos1"), ("two_members", "duplicates"),
    ("one_member_twice", "duplicates"), ("stray", "strays")])
def test_each_fault_planted_is_owned_by_its_number_alone(fault, number):
    live = [member(f"m{i}", "$share/g/a/#") for i in range(4)] \
        + [member("by", "a/y")]
    pubs = [(0, AX), (1, AX), (0, AX)]
    frames = [(0, 0, 0, 1), (1, 1, 0, 1), (3, 0, 1, 1)]
    if fault == "lost":
        frames = frames[:2]
    elif fault == "two_members":
        frames.append((2, 1, 0, 1))
    elif fault == "one_member_twice":
        frames.append((1, 1, 0, 1))
    else:
        frames.append((4, 0, 1, 1))  # the bystander: neither owed it
    assert judge(live, pubs, frames) == clean(owed=3, **{number: 1})


def test_a_session_in_two_shares_that_match_one_topic_is_refused():
    live = [member("m0", "$share/g1/a/#", "$share/g2/a/+"),
            member("m1", "$share/g1/a/#")]
    with pytest.raises(ValueError, match="two shared subscriptions"):
        judge(live, [(0, AX)], [(0, 0, 0, 1)])
    # apart (no topic matches both) the same session is fine
    live = [member("m0", "$share/g1/a/x", "$share/g2/a/y")]
    assert judge(live, [(0, AX), (0, AY)], [(0, 0, 0, 1), (0, 0, 1, 1)],
                 shards=1) == clean(owed=2)


def test_member_shares_are_a_fact_of_the_run():
    by = np.asarray([[0, 7, 3], [0, 2, 1], [1, 9, 4]], np.int64)
    facts = R.member_shares([["g", "a/#", 4], ["h", "b", 1]], by)
    assert facts[0] == {"group": "g", "filter": "a/#", "members": 4,
                        "deliveries": 4, "largest_pct": 75.0,
                        "smallest_pct": 0.0}
    assert facts[1]["largest_pct"] == facts[1]["smallest_pct"] == 100.0
    assert R.member_shares([["g", "a/#", 4]], by[:0])[0]["largest_pct"] \
        is None


# ------------------------- the accepted cells are owed what they were

#: per subscriber process, count and sha256 of ``expected_keys`` for 5
#: publishes a publisher at rehearsal size, recorded from the parent
#: commit (9efa881); the keys hold no word the seed permutes
PARENT = {
    "p2p50k.tick1s": [
        (120, "d85a5562852d28ac350e47f756df16b4804c6ab515fcad5f4876803d2c00eb16"),
        (120, "99efec4f7e4c212e5ab1553930f1261b97ec9eced6434641e7a4b4d5cbc11a07")],
    "fanout1k.burst1s": [
        (4000, "fdc5febb876c892eb0164309a67508ce8dddb1423e63ffd6c0031f7e964bc416"),
        (4000, "fdc5febb876c892eb0164309a67508ce8dddb1423e63ffd6c0031f7e964bc416")],
}


@pytest.mark.parametrize("cell_name", sorted(PARENT))
@pytest.mark.parametrize("seed", [7, 2147483777])
def test_a_corpus_with_no_share_is_owed_the_parents_keys(cell_name, seed):
    cell = Manifest().cell(cell_name)
    rehearsal_sizes(cell)
    corpus = corpus_mod.build(cell["config"], seed)
    sizes = [len(p) for p in corpus.pools]
    shards = int(cell["config"]["subscriber_processes"])
    pubs = loadgen.connections(cell["mix"], corpus)
    levels = np.concatenate([corpus.topics(p, 0, 5) for p in range(pubs)])
    pub_of = np.repeat(np.arange(pubs, dtype=np.int64), 5)
    seq_of = np.tile(np.arange(5, dtype=np.int64), pubs)
    shared = R.Shared(corpus.live)
    assert len(shared) == 0
    for k, (count, digest) in enumerate(PARENT[cell_name]):
        trie = R.session_trie(corpus.live[k::shards], shared)
        exp = R.expected_keys(trie, corpus.pools, sizes, levels, pub_of,
                              seq_of, 1)
        assert len(exp) == count
        assert hashlib.sha256(exp.astype("<i8").tobytes()).hexdigest() \
            == digest
        rec = exp[::-1].copy()
        plain_exp, plain_rec, share_exp, share_rec, _by = R.attribute(
            shared, exp, rec, np.arange(k, len(corpus.live), shards), 1)
        assert plain_exp is not None and plain_rec is rec  # untouched
        assert len(plain_exp) == count and not len(share_exp) \
            and not len(share_rec)


# ------------- the processes' own code, fed with bytes, to the verdict

class _Socket:
    def send(self, data):
        return len(data)


def _shards(seed=11):
    cell = Manifest(FIXTURE).cell(CELL)
    out = []
    for k in range(2):
        sh = loadgen.SubscriberShard({"config": cell["config"], "seed": seed,
                                      "shard": k, "shards": 2})
        sh.rest = [b""] * len(sh.sessions)
        out.append(sh)
    return cell, out


def _frame(pub, seq, t_ns, qos=1, dup=False, topic=b"bench/0"):
    head = bytearray(mqtt.publish_head(topic, qos, 16))
    head[0] |= loadgen.DUP if dup else 0
    return bytes(head) + (b"\x00\x07" if qos else b"") + loadgen.STAMP.pack(
        t_ns, pub, seq, loadgen.MAGIC)


def verdict(frames, n_pubs=3, n_each=4):
    """``frames``: ``(index into live, publisher, sequence[, dup])`` in
    the order the sockets read them; every publish was stamped inside the
    window and acknowledged."""
    cell, shards = _shards()
    live = shards[0].corpus.live
    assert len(live) == 42 and live[40].client_id == "by0"
    stamps = {p: 1000 + np.arange(n_each, dtype=np.int64)
              for p in range(n_pubs)}
    for g, p, s, *dup in frames:
        shards[g % 2]._frames(g // 2, _Socket(), _frame(
            p, s, int(stamps[p][s]), dup=bool(dup and dup[0])), 5000)
    pub = {"n_sent": dict.fromkeys(range(n_pubs), n_each),
           "n_acked": dict.fromkeys(range(n_pubs), n_each), "stamps": stamps,
           "lost_connections": [], "late_ms": np.zeros(1, np.float32),
           "cpu_s": 0.1, "wall_s": 1.0}
    fin = harness._finish_request([pub], cell["mix"], (0, 10**6))
    owed = [sh.owed(fin) for sh in shards]
    assert [o["owed"] for o in owed] == [0, 0]          # to no session
    assert {o["owed_shared"] for o in owed} == {n_pubs * n_each}
    reports = [sh.finish() for sh in shards]
    for sh in shards:
        sh.sel.close()
    return harness._reduce([pub], reports, fin, {}, {}, 1.0, "reference")


def served(n_pubs=3, n_each=4):
    """Every publish to one member, a different one each time."""
    return [((5 * p + 3 * s) % 40, p, s) for p in range(n_pubs)
            for s in range(n_each)]


def numbers(run):
    return {k: v["value"] for k, v in run["compared"].items() if v["value"]}


def test_the_processes_and_the_parent_agree_on_a_clean_run():
    run = verdict(served())
    assert run["correct"] is True and numbers(run) == {}
    assert run["attempted"] == run["deliveries"] == run["owed_in_window"] == 12
    assert run["failed"] == 0
    share = run["member_shares"][0]
    assert (share["group"], share["filter"], share["members"]) \
        == ("g", "bench/#", 40)
    assert share["deliveries"] == 12 and share["smallest_pct"] == 0.0
    # a redelivery flagged DUP is the protocol's own, to whichever member
    run = verdict(served() + [(0, 0, 0, True)])
    assert run["correct"] is True and run["redelivered_with_dup"] == 1


@pytest.mark.parametrize("fault,number,failed", [
    ("lost", "lost_qos1", 1), ("two_members", "duplicates", 1),
    ("one_member_twice", "duplicates", 1), ("stray", "strays", 0),
    ("misordered", "misordered", 0)])
def test_a_fault_in_what_the_sockets_read_fails_its_number_alone(
        fault, number, failed):
    frames = served()
    if fault == "lost":
        del frames[5]
    elif fault == "two_members":
        frames.append((frames[5][0] + 1, *frames[5][1:]))  # the other process
    elif fault == "one_member_twice":
        frames.append(frames[5])
    elif fault == "stray":
        frames.append((41, 1, 1))                          # a bystander
    else:  # one member reads a publisher's publishes out of order
        frames = [(7, p, s) for p, s in ((0, 1), (0, 0), (0, 2), (0, 3))] \
            + [f for f in frames if f[1] != 0]
    run = verdict(frames)
    assert run["correct"] is False
    assert numbers(run) == {number: 1}
    assert run["failed"] == failed
    if fault == "lost":  # never seen: waited for until the verdict
        assert run["deliveries"] == 11 and run["owed_in_window"] == 12


# ------------------------------------------ end to end on the fixture

def test_the_fixture_is_the_builders_one_group():
    cell = Manifest(FIXTURE).cell(CELL)
    cfg = cell["config"]
    assert cfg["corpus_builder"] == "share_group" and cell["chips"] == 1
    a, b = corpus_mod.build(cfg, 1), corpus_mod.build(cfg, 2147484999)
    for c in (a, b):  # one structure for every seed
        assert c.n_stored == 0 and list(c.records()) == []
        assert len(c.live) == 42 and c.n_resident == 42 and c.publishers == 48
        assert [s.tcp_filters for s in c.live[:40]] \
            == [[("$share/g/bench/#", 1)]] * 40
        assert [s.tcp_filters for s in c.live[40:]] \
            == [[("bench-aside/0", 1)], [("bench-aside/1", 1)]]
        assert sorted(c.pools[1]) == sorted(str(k) for k in range(48))
        assert c.topics(5, 2, 2).tolist() == [[0, 5], [0, 5]]
        assert loadgen.connections(cell["mix"], c) == 48
    assert [s.client_id for s in a.live] != [s.client_id for s in b.live]
    assert a.pools[1] != b.pools[1]
    with pytest.raises(ValueError):
        corpus_mod.build(dict(cfg, live_publishers=49), 1)


def control(break_, every="3"):
    cmd = [sys.executable, "-m", "benchmark.control", "--root", FIXTURE,
           "--workload", CELL, "--seed", "2147483659", "--seconds", "4",
           "--rehearse", "--every", every]
    if break_:
        cmd += ["--break", break_]
    p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    assert "jax" not in p.stderr.lower()
    return json.loads(p.stdout.strip().splitlines()[-1])


OWNER = {"lose_qos1": "lost_qos1", "lose_tail": "lost_qos1",
         "duplicate": "duplicates", "stray": "strays",
         "reorder": "misordered", "no_ack": "unacked",
         "share_twice": "duplicates", "share_dead": "lost_qos1"}


@pytest.mark.parametrize("break_", [None] + sorted(OWNER))
def test_control_on_the_fixture(break_):
    assert set(OWNER) == set(BREAKS)
    out = control(break_)
    assert list(out)[-1] == "compared"
    if break_ is None:
        assert out["correct"] is True and out["failed"] == 0
        assert out["attempted"] > 0
        assert out["facts"]["deliveries"] == out["facts"]["owed"] \
            == out["attempted"]
        assert out["facts"]["member_shares"][0]["members"] == 40
    else:
        assert out["correct"] is False
        over = {k for k, v in out["compared"].items()
                if v["value"] > v.get("limit", 0)}
        assert over == {OWNER[break_]}, out["compared"]


def rehearse(module, cell=CELL):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "-m", module, "--root", FIXTURE, "--workload", cell,
         "--seed", "2147483777", "--seconds", "3", "--trace", "0",
         "--rehearse"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell,members", [
    (CELL, 40),
    ("share300.tick1s", 300)])  # past tpu_max_fanout 256 rows a publish
def test_the_program_serves_the_group_each_publish_once(cell, members):
    """The program as it stands (a row a member, ``_publish_shared``
    after the match) on the CPU backend: each publish read exactly once,
    by one member."""
    out = rehearse("benchmark.run", cell)
    assert KEYS <= set(out) and list(out)[-1] == "compared"
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert out["facts"]["owed"] == out["facts"]["deliveries"] \
        == out["attempted"]
    assert out["compared"]["strays"]["value"] == 0
    assert out["compared"]["device_served_pct"]["value"] >= 50.0
    share = out["facts"]["member_shares"][0]
    assert share["members"] == members
    assert share["deliveries"] >= out["attempted"]
    assert out["rehearsal"] is True and out["metrics"] == {}


def test_a_publish_whose_rows_are_lost_is_a_delivery_the_group_is_owed():
    out = rehearse("benchmark.tests.faulty_whole")
    assert out["correct"] is False
    assert out["compared"]["lost_qos1"]["value"] > 0
    assert out["failed"] > 0
