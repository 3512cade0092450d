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

**Shared subscriptions.** A subscription ``$share/<group>/<filter>`` (MQTT
5.0 section 4.8.2; upstream VerneMQ serves it to 3.1.1 clients too) makes
its session a MEMBER of the shared subscription (group, filter). For every
publish whose topic matches the filter under section 4.7 (the ``$``-topic
rule included) the shared subscription is owed EXACTLY ONE delivery, read
from the socket of ONE member that holds a connection, at QoS min(publish,
that member's subscription). Which member is the broker's choice and no
part of the result. A shared subscription none of whose members holds a
connection is owed nothing a socket can show (upstream queues it for an
offline member); the reference sees the sessions that hold a connection
and no others. A session's plain subscriptions are owed as they always
were, beside its memberships. Retained messages are never owed to a shared
subscription (no traffic here retains). The spec's unit is (ShareName,
filter); the program, as upstream's fold, collects the members it matched
by group NAME (``route_rows``: ``groups.setdefault(group, ...)``), so one
group with two filters that match one topic is owed two by the spec and
served one. The rule takes the spec's reading; the corpora hold one filter
a group (as the suite's fan-in case does), where the two agree.

What the rule leaves open, and why: the BALANCE between members is a fact
of the run (``member_shares``), not a guarantee — upstream's policy is
random, online members first; ORDER is held per socket, publisher and
topic as before — across the members of a group there is none to hold.
A frame carries no subscription identifier in 3.1.1, so what a socket read
is attributed: first to the session's own plain subscriptions, the rest to
the shared subscription the session is a member of that matches (at the
member's own QoS; any other QoS is a stray). A corpus in which one session
is a member of TWO shared subscriptions that match one topic cannot be
attributed frame by frame and is refused (``attribute`` raises).

A shared subscription is owed to no session, so its key carries an owner
index ABOVE every session's — ``len(live)`` + its place in the sorted list
of the corpus's shared subscriptions, the same number in every process —
and is compared where the whole group can be seen: each subscriber
process hands back the keys it attributed to shared subscriptions and the
parent compares the owed keys with those of all processes together
(``compare``, the same multiset rule): a (publish, shared subscription)
no member read is lost, one read twice — by two members, or twice by one
without DUP — is a duplicate.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

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
_PUBSEQ = ((1 << SUB_SHIFT) - 1) ^ (1 << QOS_SHIFT)  # publisher, sequence


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


def split_share(words: Sequence[str]):
    """``(group, filter words)`` of ``$share/<group>/<filter>``, None for
    a plain filter."""
    if len(words) >= 3 and words[0] == "$share":
        return words[1], tuple(words[2:])
    return None


class Shared:
    """The shared subscriptions of ``live`` — ALL the corpus's sessions
    that hold a connection, in the corpus's order — sorted, so that every
    process gives one of them the same owner index ``len(live)`` + its
    place; who its members are (as indices into ``live``) and at which
    QoS each subscribed."""

    def __init__(self, live) -> None:
        self.n_sessions = len(live)
        members: Dict[tuple, Dict[int, int]] = {}
        for g, s in enumerate(live):
            for words, qos in s.subscriptions().items():
                share = split_share(words)
                if share is not None:
                    members.setdefault(share, {})[g] = qos
        self.subs = sorted(members)
        if self.n_sessions + len(self.subs) > 1 << (63 - SUB_SHIFT):
            raise ValueError("more sessions and shared subscriptions than "
                             "a key's owner bits hold")
        #: a lost delivery of the group is lost at the highest QoS any
        #: member could have read it at
        self.qos = [max(members[sh].values()) for sh in self.subs]
        pairs = [(i * self.n_sessions + g, q)
                 for i, sh in enumerate(self.subs)
                 for g, q in members[sh].items()]
        pairs.sort()
        self.pairs = np.asarray([p for p, _q in pairs], np.int64)
        self.pair_qos = np.asarray([q for _p, q in pairs], np.int64)

    def __len__(self) -> int:
        return len(self.subs)

    def is_shared(self, keys: np.ndarray) -> np.ndarray:
        """Which keys are owed to a shared subscription, not a session."""
        return (np.asarray(keys, np.int64) >> SUB_SHIFT) >= self.n_sessions

    def member_qos(self, share: np.ndarray, session: np.ndarray
                   ) -> np.ndarray:
        """The QoS at which ``live[session]`` is a member of shared
        subscription ``share`` (its place in ``subs``), -1 if it is not."""
        want = share * self.n_sessions + session
        if not len(self.pairs):
            return np.full(len(want), -1, np.int64)
        at = np.minimum(np.searchsorted(self.pairs, want),
                        len(self.pairs) - 1)
        return np.where(self.pairs[at] == want, self.pair_qos[at], -1)

    def members(self, share: int) -> np.ndarray:
        """Indices into ``live`` of a shared subscription's members."""
        lo, hi = np.searchsorted(self.pairs, [share * self.n_sessions,
                                              (share + 1) * self.n_sessions])
        return self.pairs[lo:hi] - share * self.n_sessions


def session_trie(sessions, shared: Optional[Shared] = None) -> FilterTrie:
    """Every plain subscription of ``sessions`` (``corpus.LiveSession``),
    valued (index into ``sessions``, subscription QoS), and every shared
    subscription of ``shared`` (default: those of ``sessions`` themselves)
    as ONE value under the group's filter, (owner index, QoS) — not a
    value a member. ``sessions`` may be one process's part of the live
    sessions; ``shared`` is then built from all of them."""
    if shared is None:
        shared = Shared(sessions)
    trie = FilterTrie()
    for idx, s in enumerate(sessions):
        for words, qos in s.subscriptions().items():
            if split_share(words) is None:
                trie.add(words, (idx, qos))
    for i, (_group, words) in enumerate(shared.subs):
        trie.add(words, (shared.n_sessions + i, shared.qos[i]))
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
    matching subscription of a session in the trie and one per matching
    shared subscription (keyed by its owner index, not by a session), at
    QoS min(publish, subscription) (spec 3.8.4)."""
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


def attribute(shared: Shared, exp: np.ndarray, rec: np.ndarray,
              live_index: np.ndarray, pub_qos: int):
    """What one process's sockets read, attributed: first to each
    session's own plain subscriptions, the rest to the shared subscription
    the session is a member of that matches the publish, at the member's
    own QoS. ``exp``: ``expected_keys`` over this process's sessions and
    ``shared``; ``rec``: a key a frame read (DUP redeliveries left out),
    keyed by the index into the process's sessions; ``live_index``: where
    each of those sessions stands in ``shared``'s ``live``.

    Returns ``(plain_exp, plain_rec, share_exp, share_rec, by_member)``:
    the first pair is this process's to compare, the second the parent's,
    with every process's ``share_rec`` together; ``by_member`` rows
    (shared subscription, index into ``live``, deliveries)."""
    exp = np.asarray(exp, np.int64)
    rec = np.asarray(rec, np.int64)
    of_share = shared.is_shared(exp)
    plain_exp, share_exp = exp[~of_share], exp[of_share]
    if not len(share_exp) or not len(rec):
        return (plain_exp, rec, share_exp, np.zeros(0, np.int64),
                np.zeros((0, 3), np.int64))
    rk, rc = np.unique(rec, return_counts=True)
    ek, ec = np.unique(plain_exp, return_counts=True)
    owed_here = np.zeros(len(rk), np.int64)
    if len(ek):
        at = np.minimum(np.searchsorted(ek, rk), len(ek) - 1)
        owed_here = np.where(ek[at] == rk, ec[at], 0)
    excess = np.where(rk >= 0, np.maximum(rc - owed_here, 0), 0)
    cand = np.flatnonzero(excess)
    # the shared subscriptions each candidate's publish is owed to
    by_pub = share_exp[np.argsort(share_exp & _PUBSEQ, kind="stable")]
    lo, hi = (np.searchsorted(by_pub & _PUBSEQ, rk[cand] & _PUBSEQ, side)
              for side in ("left", "right"))
    session = live_index[rk[cand] >> SUB_SHIFT]
    qos = (rk[cand] >> QOS_SHIFT) & 1
    found = np.full(len(cand), -1, np.int64)
    for k in range(int((hi - lo).max(initial=0))):
        at = np.minimum(lo + k, len(by_pub) - 1)
        share = (by_pub[at] >> SUB_SHIFT) - shared.n_sessions
        member = shared.member_qos(share, session)
        fits = (lo + k < hi) & (member >= 0) & (
            np.minimum(member, pub_qos) == qos)
        if np.any(fits & (found >= 0)):
            raise ValueError(
                "a session is a member of two shared subscriptions that "
                "match one topic: its frames cannot be attributed")
        found = np.where(fits, by_pub[at], found)
    hit = found >= 0
    taken = np.zeros(len(rk), np.int64)
    taken[cand[hit]] = excess[cand[hit]]
    by_member = np.stack([(found[hit] >> SUB_SHIFT) - shared.n_sessions,
                          session[hit], excess[cand[hit]]], axis=1)
    return (plain_exp, np.repeat(rk, rc - taken), share_exp,
            np.repeat(found[hit], excess[cand[hit]]), by_member)


def member_shares(names, by_member: np.ndarray, most: int = 8) -> list:
    """A fact of the run, not judged: of each shared subscription
    (``names`` rows ``[group, filter, members]``), the deliveries its
    members read and the largest and the smallest member's share of them
    in per cent (a member that read none has the share 0)."""
    out = []
    for i, (group, filt, n) in enumerate(names[:most]):
        got = by_member[by_member[:, 0] == i]
        per = np.zeros(int(n), np.int64)
        if len(got):
            counts = np.bincount(got[:, 1], weights=got[:, 2])
            counts = counts[counts > 0].astype(np.int64)
            per[:len(counts)] = counts
        total = int(per.sum())
        out.append({"group": group, "filter": filt, "members": int(n),
                    "deliveries": total,
                    "largest_pct": 100.0 * int(per.max()) / total
                    if total else None,
                    "smallest_pct": 100.0 * int(per.min()) / total
                    if total else None})
    return out


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
