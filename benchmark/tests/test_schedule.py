"""Corpus and publish schedule are functions of (configuration, seed)."""

import collections

import numpy as np

from benchmark import corpus as C
from benchmark import loadgen
from benchmark.manifest import Manifest
from benchmark.run import rehearsal_sizes

CELL = "p2p50k.tick1s"


def small():
    cell = Manifest().cell(CELL)
    rehearsal_sizes(cell)
    return cell


def rows(c):
    return [(cid, tuple(f)) for cid, f in c.records()]


def test_same_seed_same_corpus_other_seed_other_corpus():
    cfg = small()["config"]
    a, b, c = C.build(cfg, 7), C.build(cfg, 7), C.build(cfg, 2**31 + 5)
    assert rows(a) == rows(b) and a.pools == b.pools
    assert [s.client_id for s in a.live] == [s.client_id for s in b.live]
    assert rows(a) != rows(c)
    assert [s.client_id for s in a.live] != [s.client_id for s in c.live]
    assert len(rows(a)) == a.n_stored == cfg["topics"] - cfg["live_pairs"]
    assert a.n_resident == cfg["topics"] and len(a.live) == cfg["live_pairs"]


def test_seeds_permute_one_structure():
    """Every seed gives the same multiset of (kind of filter, level-0
    word): the device table's geometry, and with it the program's compile
    signatures, do not depend on the seed."""
    cfg = small()["config"]

    def shape(seed):
        c = C.build(cfg, seed)
        kinds = collections.Counter()
        for _cid, filters in c.records():
            for words, qos in filters:
                kinds[(len(words), words[0], qos)] += 1
        for s in c.live:
            for words, qos in s.subscriptions().items():
                kinds[(len(words), words[0], qos)] += 1
        return sorted(kinds.items())

    assert shape(1) == shape(2) == shape(2**31 + 11)


def test_a_publisher_sends_to_the_topic_its_live_subscriber_holds():
    c = C.build(small()["config"], 9)
    for p in (0, 5, len(c.live) - 1):
        t = c.topics(p, 0, 4)
        assert t.shape == (4, 2) and (t == t[0]).all()
        topic = "/".join(pool[k] for pool, k in zip(c.pools, t[0]))
        assert c.live[p].tcp_filters == [(topic, 1)]
        assert np.array_equal(c.topics(p, 100, 4), t)


def phases(mix, seed, active):
    """Phase (ns) of every active publisher, as ``PublisherShard.run``
    draws them."""
    groups = int(mix["phase_groups"]) or active
    rng = np.random.Generator(np.random.PCG64([seed, active, 0x9A5E]))
    return rng.permutation(active) % groups * int(
        mix["interval_ms"] * 1e6) // groups


def test_every_seed_offers_the_same_arrivals_in_another_order():
    mix = dict(small()["mix"], phase_groups=8)
    a, b = phases(mix, 3, 48), phases(mix, 2**31 + 4, 48)
    assert sorted(a) == sorted(b) and not np.array_equal(a, b)
    assert np.array_equal(a, phases(mix, 3, 48))
    assert len(set(phases(dict(mix, phase_groups=0), 3, 48))) == 48
    assert set(phases(dict(mix, phase_groups=1), 3, 48)) == {0}
    assert loadgen.connections(mix, C.build(small()["config"], 1)) == 48
