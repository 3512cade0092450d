"""The plain reference on a tiny corpus, against the spec's wording applied
filter by filter; and the comparison, with each kind of fault planted."""

import numpy as np
import pytest

from benchmark import reference as R
from benchmark.corpus import LiveSession


def spec_match(filt, topic):
    """MQTT 3.1.1 section 4.7, one filter, one topic, level by level."""
    if topic[0].startswith("$") and filt[0] in ("+", "#"):
        return False
    for i, f in enumerate(filt):
        if f == "#":
            return True
        if i >= len(topic) or (f != "+" and f != topic[i]):
            return False
    return len(filt) == len(topic)


FILTERS = [("a", "b", "c"), ("a", "+", "c"), ("+", "b", "c"), ("a", "b", "#"),
           ("a", "#"), ("#",), ("+", "+", "+"), ("a", "b"), ("a", "b", "c", "#"),
           ("$SYS", "#"), ("+", "b")]
TOPICS = [("a", "b", "c"), ("a", "x", "c"), ("z", "b", "c"), ("a", "b"),
          ("a",), ("$SYS", "b"), ("a", "b", "c", "d"), ("q", "b")]


def test_trie_agrees_with_the_spec_filter_by_filter():
    trie = R.FilterTrie()
    for i, f in enumerate(FILTERS):
        trie.add(f, i)
    for t in TOPICS:
        want = sorted(i for i, f in enumerate(FILTERS) if spec_match(f, t))
        assert sorted(trie.match(t)) == want, t


def tiny():
    pools = [["a", "b"], ["x", "y"], ["m", "n"]]
    sessions = [
        LiveSession("s0", False, [], [(("a", "+", "m"), 1), (("a", "x", "m"), 0)]),
        LiveSession("s1", True, [("a/#", 0)], []),
        LiveSession("s2", False, [], [(("b", "y", "n"), 1), (("b", "y", "n"), 0)]),
    ]
    levels = np.array([[0, 0, 0], [1, 1, 1], [0, 1, 1], [0, 0, 0]], np.int32)
    pub = np.array([0, 0, 1, 1])
    seq = np.array([0, 1, 0, 1])
    return pools, sessions, levels, pub, seq


def owed(pub_qos=1):
    pools, sessions, levels, pub, seq = tiny()
    return R.expected_keys(R.session_trie(sessions), pools, [2, 2, 2],
                           levels, pub, seq, pub_qos)


key = R.key


def test_keys_pack_and_unpack_at_the_cells_sizes():
    k = R.key(np.array([49_999]), np.array([65_535]), np.array([1]),
              np.array([R.SEQ_MASK]))
    assert k[0] > 0 and (k[0] >> R.SUB_SHIFT) == 49_999
    p, s = R.pub_seq(k)
    assert (p[0], s[0], (k[0] >> R.QOS_SHIFT) & 1) == (65_535, R.SEQ_MASK, 1)


def test_deliveries_owed_on_a_tiny_corpus():
    # a/x/m -> s0 twice (both filters: QoS 1 and QoS 0), s1 once; b/y/n ->
    # s2 once (a later QoS replaces an earlier: QoS 0); a/y/n -> s1
    want = sorted([key(0, 0, 1, 0), key(0, 0, 0, 0), key(1, 0, 0, 0),
                   key(2, 0, 0, 1), key(1, 1, 0, 0),
                   key(0, 1, 1, 1), key(0, 1, 0, 1), key(1, 1, 0, 1)])
    assert sorted(owed().tolist()) == want
    # a QoS 0 publish is delivered at QoS 0 whatever the subscription
    assert all((k >> R.QOS_SHIFT) & 1 == 0 for k in owed(0).tolist())


@pytest.mark.parametrize("fault,number", [
    ("none", None), ("lose_q1", "lost_qos1"), ("lose_q0", "lost_qos0"),
    ("dup", "duplicates"), ("stray", "strays")])
def test_compare_counts_each_fault(fault, number):
    exp = owed()
    got = exp.copy()
    if fault == "lose_q1":
        got = np.delete(got, np.flatnonzero((got >> R.QOS_SHIFT) & 1)[0])
    elif fault == "lose_q0":
        got = np.delete(got, np.flatnonzero(((got >> R.QOS_SHIFT) & 1) == 0)[0])
    elif fault == "dup":
        got = np.append(got, got[3])
    elif fault == "stray":
        got = np.append(got, key(2, 0, 0, 0))
    cmp = R.compare(exp, got)
    for name in ("lost_qos1", "lost_qos0", "duplicates", "strays"):
        assert cmp[name] == (1 if name == number else 0), (fault, name)
    assert cmp["owed"] == len(exp)


def test_misordered_is_per_socket_publisher_topic_and_qos():
    z = np.zeros(4, np.int64)
    tid = np.array([5, 5, 6, 5])
    assert R.misordered(z, z, z, tid, np.array([0, 1, 0, 2])) == 0
    assert R.misordered(z, z, z, tid, np.array([1, 0, 0, 2])) == 1
    # another topic, socket, publisher or QoS in between is no reordering
    assert R.misordered(z, z, z, np.array([5, 6, 5, 6]),
                        np.array([3, 0, 4, 1])) == 0
    big = np.array([49_999, 49_999, 3, 3])
    assert R.misordered(big, np.array([60_000, 60_000, 1, 2]), z,
                        np.array([7, 7, 7, 7]), np.array([1, 0, 1, 0])) == 1


def test_decide_holds_every_number_to_its_limit():
    clean = dict.fromkeys(R.LIMITS, 0)
    floor = R.FLOORS["device_served_pct"]
    ok, table = R.decide(dict(clean, device_served_pct=floor))
    assert ok and list(table)[-1] == "device_served_pct"
    for name in R.LIMITS:
        assert not R.decide(dict(clean, device_served_pct=100.0,
                                 **{name: 1}))[0]
    assert not R.decide(dict(clean, device_served_pct=floor - 0.1))[0]
    assert R.decide(dict(clean, device_served_pct=0.0), floors=False)[0]
