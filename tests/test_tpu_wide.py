"""The wide result of the device match, and the release budget in rows.

A publish that matches more rows than the flat form's caps
(``tpu_max_fanout`` a part, ``flat_avg`` a publish on average) is
answered whole by the device (``K.wide_mask_packed``): the rows are the
plain reference's (``benchmark/reference.py``'s trie, which imports
nothing of the program) for any fan-out up to every row of the publish's
regions, and ``_host_match`` is not reached. Runs on the CPU backend.
"""

import asyncio
import random

import numpy as np
import pytest

from benchmark.reference import FilterTrie
from vernemq_tpu.models import tpu_matcher as tm
from vernemq_tpu.models.tpu_matcher import (WIDE_RUNGS, BatchCollector,
                                            MatcherBusy, TpuMatcher)
from vernemq_tpu.models.tpu_table import SubscriptionTable
from vernemq_tpu.observability import histogram as obs
from vernemq_tpu.ops import match_kernel as K
from tests.test_tpu_match import spy_kernel_call

KCAP = 32  # the narrow form's per-part cap in these tables

WIDE_COUNTERS = ("wide_publishes", "wide_dispatches", "wide_topics",
                 "wide_rows", "wide_failures")


@pytest.fixture(autouse=True)
def wide_counters_from_zero(monkeypatch):
    """The wide pass's counters are the process's (module integers of
    ``tpu_matcher``): each test reads its own."""
    for name in WIDE_COUNTERS:
        monkeypatch.setattr(tm, name, 0)


class Pair:
    """A matcher and the reference's trie fed the same rows."""

    def __init__(self, capacity: int, **kw) -> None:
        self.m = TpuMatcher(max_levels=8, initial_capacity=capacity,
                            max_fanout=KCAP, **kw)
        self.rows = set()

    def add(self, words, key) -> None:
        self.m.table.add(list(words), key, None)
        self.rows.add((tuple(words), key))

    def remove(self, words, key) -> None:
        assert self.m.table.remove(list(words), key)
        self.rows.remove((tuple(words), key))

    def check(self, topics) -> None:
        ref = FilterTrie()
        for words, key in self.rows:
            ref.add(words, (words, key))
        got = self.m.match_batch(list(topics))
        for topic, rows in zip(topics, got):
            assert sorted((tuple(f), k) for f, k, _ in rows) \
                == sorted(ref.match(list(topic))), topic


def fill(p: Pair, rng) -> None:
    """Exact and wildcard rows mixed, fan-outs 0 / 1 / k / k+1 / 1,000, a
    region in which every row matches one topic, ``$``-topics."""
    for n, word in ((1, "one"), (KCAP, "cap"), (KCAP + 1, "over"),
                    (1000, "big")):
        for s in range(n):
            p.add([word, "x"], f"{word}{s}")
    for s in range(600):                 # a bucket whose every row matches
        p.add(["solo", "t"], f"s{s}")
    for s in range(50):
        p.add(["solo", "+"], f"sp{s}")
        p.add(["solo", "#"], f"sh{s}")
    for s in range(300):
        p.add(["$SYS", "x"], f"d{s}")
    for s in range(40):
        p.add(["$SYS", "#"], f"dh{s}")
        p.add(["+", "x"], f"px{s}")      # a g-bucket's rows where NG > 0
        p.add(["#"], f"h{s}")            # region 0
        p.add(["big", "+"], f"bp{s}")
        p.add(["+", "+"], f"pp{s}")
    for i in range(400):                 # a spread of one-row filters
        p.add([f"r{rng.randrange(24)}", f"d{rng.randrange(40)}"], f"u{i}")


TOPICS = [("none", "x"), ("one", "x"), ("cap", "x"), ("over", "x"),
          ("big", "x"), ("solo", "t"), ("$SYS", "x"), ("$SYS", "y"),
          ("big", "y"), ("r3", "d7"), ("big",), ("big", "x", "deep")]


@pytest.fixture(scope="module", params=[16384, 65536],
                ids=["no_gbuckets", "gbuckets"])
def pair(request):
    p = Pair(request.param)
    fill(p, random.Random(request.param))
    t = p.m.table
    assert t.bucketed and bool(t.NG) == (request.param == 65536)
    return p


def test_wide_matches_the_reference_one_and_many_a_batch(pair):
    m = pair.m
    for topic in TOPICS:                       # one publish a batch
        pair.check([topic])
    rng = random.Random(5)
    many = [rng.choice(TOPICS) for _ in range(40)]   # repeated topics
    before = tm.wide_dispatches
    pair.check(many)
    assert tm.wide_dispatches == before + 1     # identical topics: once
    assert m.host_fallbacks == 0 and tm.wide_failures == 0
    assert tm.wide_publishes > 0 and tm.wide_rows >= 1000


def test_wide_after_interleaved_subscribe_and_unsubscribe(pair):
    m = pair.m
    rng = random.Random(9)
    for round_ in range(3):
        for s in rng.sample(range(1000), 120):
            key = f"big{s}"
            if (("big", "x"), key) in pair.rows:
                pair.remove(["big", "x"], key)
            else:
                pair.add(["big", "x"], key)
        for s in range(30):
            pair.add(["solo", "t"], f"late{round_}_{s}")
            pair.add(["over", "+"], f"ow{round_}_{s}")
        pair.check([("big", "x"), ("solo", "t"), ("over", "x"),
                    ("cap", "x"), ("$SYS", "x")])
    assert m.host_fallbacks == 0 and tm.wide_failures == 0


def test_wide_under_the_cap_takes_the_narrow_form_alone(monkeypatch):
    p = Pair(16384)
    for s in range(KCAP):
        p.add(["cap", "x"], f"c{s}")
        p.add(["over", "x"], f"o{s}")
    p.add(["over", "x"], "one more")
    for s in range(10):
        p.add(["#"], f"h{s}")
    p.add(["one", "x"], "o")
    calls = spy_kernel_call(monkeypatch, "call_wide")
    p.check([("none", "x"), ("one", "x"), ("cap", "x")])
    assert not calls and tm.wide_publishes == 0
    p.check([("none", "x"), ("over", "x"), ("cap", "x")])
    assert len(calls) == 1 and tm.wide_publishes == 1
    assert p.m.host_fallbacks == 0


def test_wide_serves_super_batches_with_one_pass():
    p = Pair(16384)
    fill(p, random.Random(1))
    m = p.m
    batches = [[("big", "x"), ("one", "x"), ("solo", "t")],
               [("over", "x"), ("big", "x"), ("none", "x")]]
    ref = FilterTrie()
    for words, key in p.rows:
        ref.add(words, (words, key))
    got = m.match_many(batches)
    assert m.super_dispatches == 1 and tm.wide_dispatches == 1
    for topics, res in zip(batches, got):
        for topic, rows in zip(topics, res):
            assert sorted((tuple(f), k) for f, k, _ in rows) \
                == sorted(ref.match(list(topic))), topic
    assert m.host_fallbacks == 0 and tm.wide_publishes == 6  # 120 wildcard rows beside each


def test_wide_chunks_more_distinct_topics_than_the_largest_rung():
    p = Pair(16384)
    n = WIDE_RUNGS[-1] + 3
    for t in range(n):
        for s in range(KCAP + 1 + t % 3):
            p.add(["hot", f"t{t}"], f"k{t}_{s}")
    p.check([("hot", f"t{t}") for t in range(n)])
    assert tm.wide_dispatches == 2 and tm.wide_publishes == n
    assert p.m.host_fallbacks == 0


def test_flat_capacity_overflow_is_served_wide():
    """``pre + total > C``: the batch's total fan-out exceeds the flat
    buffer though no part clips at k."""
    p = Pair(16384, flat_avg=1)
    for t in range(16):
        for s in range(6):
            p.add(["cap", f"t{t}"], f"k{t}_{s}")
    p.check([("cap", f"t{t}") for t in range(16)])
    assert tm.wide_publishes > 0 and p.m.host_fallbacks == 0


def test_a_cold_wide_program_is_matcher_busy_never_a_compile(monkeypatch):
    p = Pair(16384)
    for s in range(KCAP + 8):
        p.add(["hot", "x"], f"k{s}")
    m = p.m
    topics = [("hot", "x")] * 9
    m.match_batch(topics, _warmup=True)        # warms narrow AND wide
    wide = {s for s in m._warm_sigs if s[0] == "wide"}
    assert len(wide) == 1
    m._warm_sigs -= wide
    calls = spy_kernel_call(monkeypatch, "call_wide")
    with pytest.raises(MatcherBusy) as e:
        m.match_batch(topics, require_warm=True)
    assert e.value.cold and not calls
    assert m._warm_wide() == len(WIDE_RUNGS)   # what ensure_warm runs
    calls.clear()
    assert len(m.match_batch(topics, require_warm=True)[0]) == KCAP + 8
    assert len(calls) == 1


def test_a_table_that_cannot_overflow_warms_no_wide_program(monkeypatch):
    """The narrow form as it was: a point-to-point table (one row a
    filter) compiles the same programs from the same statics, and none
    of the wide ones."""
    m = TpuMatcher(max_levels=8, initial_capacity=16384)
    for i in range(3000):
        m.table.add(["bench", str(i)], i, None)
    assert m.table.fanout_bound == 1
    wide = spy_kernel_call(monkeypatch, "call_wide")
    narrow = spy_kernel_call(monkeypatch, "call_packed")
    assert m.warm_ladder(max_batch=16) == 5
    assert m._warm_wide() == 0 and not wide
    assert not [s for s in m._warm_sigs if s[0] == "wide"]
    statics = narrow[-1][0][4]
    assert sorted(statics) == ["C", "gc", "glob_pad", "id_bits", "k",
                               "seg2_max", "seg_max"]
    assert statics["k"] == 256 and statics["C"] == 16 * 128
    # ...and one that can, warms them ahead of the narrow ladder's end
    for s in range(300):
        m.table.add(["bench", "hot"], f"h{s}", None)
    assert m.table.fanout_bound == 300
    m.warm_ladder(max_batch=8)
    assert len([s for s in m._warm_sigs if s[0] == "wide"]) \
        == len(WIDE_RUNGS)


def test_fanout_bound_follows_adds_and_removes():
    t = SubscriptionTable(max_levels=4, initial_capacity=64)
    assert t.fanout_bound == 0
    for s in range(5):
        t.add(["a", "b"], s)
    for s in range(3):
        t.add(["a", "c"], s)
    t.add(["a", "b"], 0, "again")           # a re-subscribe is no new row
    t.add(["+", "b"], "w1")
    t.add(["a", "#"], "w2")
    assert t.fanout_bound == 5 + 2
    for s in range(3):
        t.remove(["a", "b"], s)
    assert t.fanout_bound == 3 + 2          # a/c is the largest now
    assert not t.remove(["a", "b"], 0)
    t.remove(["+", "b"], "w1")
    for s in range(3):
        t.remove(["a", "c"], s)
    assert t.fanout_bound == 2 + 1
    for s in range(200):                    # growth re-inserts, not re-counts
        t.add(["g", str(s)], s)
    assert t.fanout_bound == 2 + 1


def test_unpack_wide_bits_maps_the_three_parts():
    words = np.zeros(3 * 2, np.uint32)       # glob_pad 64, wa 64, wb 64
    words[0] = 1 << 3
    words[3] = (1 << 31) | 1
    words[4] = 1 << 5
    got = K.unpack_wide_bits(words, 64, 64, a_start=1000, b_start=500)
    assert got.tolist() == [3, 1032, 1063, 505]


# ------------------------------------------------------ release by rows

class _RowsView:
    """A view whose every publish matches ``rows`` rows."""

    registry = None

    def __init__(self, rows: int) -> None:
        self.rows = rows

    def matcher(self, mp):
        return None

    def fold_batch(self, mp, topics, lock_timeout=None):
        return [[("row", t, i) for i in range(self.rows)] for t in topics]


async def _released_per_turn(rows: int, n: int):
    col = BatchCollector(_RowsView(rows), window_us=100, max_batch=1024,
                         host_threshold=0)
    order, turns = [], []
    flush = lambda: turns.append(len(order))   # the end of a callback
    col._after_release = flush
    for i in range(n):
        col.submit("", ("t", str(i)), cont=lambda r, e, i=i: order.append(i))
    for _ in range(1000):
        if len(order) == n:
            break
        await asyncio.sleep(0.005)
    assert order == list(range(n))             # submission order
    per_turn = np.diff([0] + turns)
    assert col.release_rows == n * max(1, rows)
    assert not col._order and not col._releasing
    return per_turn[per_turn > 0]


@pytest.mark.asyncio
@pytest.mark.parametrize("rows,per_turn", [
    (1, BatchCollector._RELEASE_CHUNK),        # a chunk is 64 as ever
    (0, BatchCollector._RELEASE_CHUNK),        # no recipient costs one
    (1000, BatchCollector._RELEASE_ROWS // 1000),  # what the rows allow
    (BatchCollector._RELEASE_ROWS + 1, 1),     # wider than the budget: whole
])
async def test_release_spends_its_budget_in_rows(rows, per_turn):
    n = 2 * BatchCollector._RELEASE_CHUNK
    before = obs.get("stage_release_turn_ms").snapshot()[2]
    got = await _released_per_turn(rows, n)
    assert got.max() == per_turn and len(got) == -(-n // per_turn)
    assert obs.get("stage_release_turn_ms").snapshot()[2] - before \
        >= len(got)
