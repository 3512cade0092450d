"""The plain reference and the comparison that decides ``correct``.

The reference is MQTT 3.1.1's own statement of what a broker owes
(section 4.7 topic filters, 4.3 delivery QoS, 4.6 ordering), written as a
dictionary trie walked level by level. It imports nothing of the program
and takes nothing the program made: its inputs are the corpus and the
publish schedule, both drawn from ``--seed`` by this package.

What is compared is what the subscriber sockets received for the
publishes of the run: every delivery the reference lists for a live
session, against every PUBLISH frame read from that session's socket.
The numbers, each with its limit, are in ``LIMITS``. All are counts of
broken guarantees, so all limits are 0 — an exact comparison.
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

#: the numbers compared, each beside its limit: counts of broken
#: guarantees, so every limit is 0 — an exact comparison, with nothing
#: excused: a delivery still absent when the wait for it ends is lost
LIMITS = {
    "lost_qos1": 0,    # QoS 1 deliveries owed to a live session, absent
    "lost_qos0": 0,    # QoS 0 deliveries owed to a live session, absent
    "duplicates": 0,   # deliveries beyond the number owed (a QoS 1
                       # redelivery flagged DUP is the protocol's, not one)
    "strays": 0,       # deliveries the reference does not list at all
    "misordered": 0,   # a publisher's publishes to one topic, reordered
    "unacked": 0,      # QoS 1 publishes sent and never acknowledged
}
#: a floor: the share (%) of the window's publishes that the device
#: served. Under it the run did not drive the path the cell exists for
#: (PERF.md section 2 gives the readings it stands between)
FLOORS = {"device_served_pct": 50.0}

# key = session << 43 | publisher << 25 | delivery QoS << 24 | sequence
SUB_SHIFT, PUB_SHIFT, QOS_SHIFT = 43, 25, 24
SEQ_MASK = (1 << QOS_SHIFT) - 1
PUB_MASK = (1 << (SUB_SHIFT - PUB_SHIFT)) - 1


def key(sub, pub, qos, seq):
    """One delivery: session index, publisher, delivery QoS (0 or 1) and
    the publisher's sequence number, packed (arrays or numbers)."""
    return ((sub << SUB_SHIFT) | (pub << PUB_SHIFT) | (qos << QOS_SHIFT)
            | seq)


def pub_seq(keys: np.ndarray):
    """(publisher, sequence) of packed keys."""
    keys = np.asarray(keys, np.int64)
    return (keys >> PUB_SHIFT) & PUB_MASK, keys & SEQ_MASK


class FilterTrie:
    """Topic filters as nested dicts; ``match`` returns the value of every
    filter that matches a topic name (spec 4.7.1: ``+`` one whole level,
    ``#`` the parent and every level below; 4.7.2: a filter that starts
    with a wildcard matches no topic that starts with ``$``)."""

    def __init__(self) -> None:
        self.root: dict = {}

    def add(self, words: Sequence[str], value) -> None:
        node = self.root
        for w in words:
            node = node.setdefault(w, {})
        node.setdefault(None, []).append(value)  # None: filter ends here

    def match(self, topic: Sequence[str]) -> list:
        out: list = []
        self._walk(self.root, topic, 0, out)
        return out

    def _walk(self, node: dict, topic, i: int, out: list) -> None:
        dollar = i == 0 and topic[0][:1] == "$"
        h = node.get("#")
        if h is not None and not dollar:
            out.extend(h.get(None, ()))
        if i == len(topic):
            out.extend(node.get(None, ()))
            return
        nxt = node.get(topic[i])
        if nxt is not None:
            self._walk(nxt, topic, i + 1, out)
        nxt = node.get("+")
        if nxt is not None and not dollar:
            self._walk(nxt, topic, i + 1, out)


def session_trie(sessions) -> FilterTrie:
    """Every subscription of ``sessions`` (``corpus.LiveSession``), valued
    (index into ``sessions``, subscription QoS)."""
    trie = FilterTrie()
    for idx, s in enumerate(sessions):
        for words, qos in s.subscriptions().items():
            trie.add(words, (idx, qos))
    return trie


def topic_ids(levels: np.ndarray, sizes) -> np.ndarray:
    tid = np.zeros(len(levels), np.int64)
    for k, n in enumerate(sizes):
        tid = tid * n + levels[:, k]
    return tid


def expected_keys(trie: FilterTrie, pools, sizes, levels: np.ndarray,
                  pub_of: np.ndarray, seq_of: np.ndarray, pub_qos: int
                  ) -> np.ndarray:
    """One key per delivery owed: for publish row r (publisher
    ``pub_of[r]``, sequence ``seq_of[r]``, topic ``levels[r]``), one per
    matching subscription of a session in the trie, at QoS
    min(publish, subscription) (spec 3.8.4)."""
    tid = topic_ids(levels, sizes)
    uniq, first, inv = np.unique(tid, return_index=True, return_inverse=True)
    offs = np.zeros(len(uniq) + 1, np.int64)
    subs: List[int] = []
    qoss: List[int] = []
    for u, r in enumerate(first):
        words = [pools[k][levels[r, k]] for k in range(len(sizes))]
        for idx, q in trie.match(words):
            subs.append(idx)
            qoss.append(min(pub_qos, q))
        offs[u + 1] = len(subs)
    sub_arr = np.asarray(subs, np.int64)
    q_arr = np.asarray(qoss, np.int64)
    counts = offs[inv + 1] - offs[inv]
    total = int(counts.sum())
    row = np.repeat(np.arange(len(tid)), counts)
    within = np.arange(total) - np.repeat(np.cumsum(counts) - counts, counts)
    at = offs[inv][row] + within
    return key(sub_arr[at], pub_of[row].astype(np.int64), q_arr[at],
               seq_of[row].astype(np.int64))


def compare(exp_keys: np.ndarray, rec_keys: np.ndarray) -> Dict[str, object]:
    """Owed against received, as multisets. Returns counts and, for the
    caller's per-publish accounting, the keys short and the keys over."""
    ek, ec = np.unique(exp_keys, return_counts=True)
    rk, rc = np.unique(rec_keys, return_counts=True)
    known = np.isin(rk, ek, assume_unique=True)
    got = np.zeros(len(ek), np.int64)
    got[np.searchsorted(ek, rk[known])] = rc[known]
    short = np.maximum(ec - got, 0)
    over = np.maximum(got - ec, 0)
    q1 = ((ek >> QOS_SHIFT) & 1).astype(bool)
    return {
        "owed": int(ec.sum()),
        "lost_qos1": int(short[q1].sum()),
        "lost_qos0": int(short[~q1].sum()),
        "duplicates": int(over.sum()),
        "strays": int(rc[~known].sum()),
        "short_keys": ek[short > 0],
        "over_keys": ek[over > 0],
        "stray_keys": rk[~known],
    }


def misordered(sub: np.ndarray, pub: np.ndarray, qos: np.ndarray,
               tid: np.ndarray, seq: np.ndarray) -> int:
    """Deliveries, given in arrival order per socket, that arrive after a
    later publish of the same publisher to the same topic at the same QoS
    on the same socket (spec 4.6: ordered topics)."""
    if not len(seq):
        return 0
    order = np.lexsort((np.arange(len(seq)), tid, qos, pub, sub))
    same = np.ones(len(seq) - 1, bool)
    for col in (sub, pub, qos, tid):
        c = col[order]
        same &= c[1:] == c[:-1]
    s = seq[order]
    return int(np.count_nonzero(same & (s[1:] < s[:-1])))


def decide(numbers: Dict[str, float], floors: bool = True
           ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """``correct`` and, for the last lines of the run, every number
    compared beside its limit. ``floors=False``: the stand-in broker has
    no device to serve from."""
    table: Dict[str, Dict[str, float]] = {}
    ok = True
    for name, limit in LIMITS.items():
        v = int(numbers[name])
        table[name] = {"value": v, "limit": limit}
        ok &= v <= limit
    for name, floor in (FLOORS if floors else {}).items():
        v = float(numbers[name])
        table[name] = {"value": v, "at_least": floor}
        ok &= v >= floor
    return bool(ok), table
