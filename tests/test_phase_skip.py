"""A match program holds only the phases whose rows hold a live row.

The flat form's dense pass over region 0 (filters whose first two levels
are wild) and its probe B over the g-buckets (wildcard-first filters with
a concrete level 1) are compiled into a dispatch only while the table
snapshot behind its device arrays holds a live row there
(``SubscriptionTable.live_rows``, pinned by ``TpuMatcher.sync``). What is
held here: the rows are the host trie's in every combination and on every
way to the device, the flip on a first wildcard-first SUBSCRIBE is the
cold-signature case (the trie serves, the program compiles on the side)
and flips back with no compile, the counts survive relocation, rebuild and
an async install, the warm ladder asks for no more programs than it did,
and the counters and the dispatch ring say which phases ran. Runs on the
CPU backend.
"""

import asyncio
import random
import threading

import numpy as np
import pytest

from tests.test_tpu_match import spy_kernel_call
from vernemq_tpu.models import tpu_matcher as tm
from vernemq_tpu.models.tpu_matcher import (BatchCollector, MatcherBusy,
                                            RebuildInProgress, TpuMatcher,
                                            TpuRegView)
from vernemq_tpu.models.tpu_table import SubscriptionTable
from vernemq_tpu.models.trie import SubscriptionTrie
from vernemq_tpu.observability.profiler import profiler
from vernemq_tpu.ops import match_kernel as K

CAP = 65536   # NB = 32 level-0 buckets, so NG = 32 g-buckets are allotted
KCAP = 32     # the flat form's per-part cap in these tables

#: which regions hold a live row -> the phases of the program
COMBOS = {"none": "a", "gzone": "ab", "region0": "ga", "both": "gab"}

G_FILTERS = [("+", "b", "c"), ("+", "w3", "#"), ("+", "d1"), ("+", "b", "+")]
R0_FILTERS = [("+", "+", "c"), ("#",), ("+", "#"), ("+",), ("+", "+")]


class Pair:
    """A matcher and the host trie fed the same rows."""

    def __init__(self, capacity=CAP, **kw) -> None:
        self.m = TpuMatcher(max_levels=8, initial_capacity=capacity,
                            max_fanout=KCAP, **kw)
        self.trie = SubscriptionTrie()

    def add(self, words, key) -> None:
        self.m.table.add(list(words), key, None)
        self.trie.add(list(words), key, None)

    def remove(self, words, key) -> None:
        assert self.m.table.remove(list(words), key)
        self.trie.remove(list(words), key)

    def want(self, topic):
        return sorted((tuple(f), k) for f, k, _ in
                      self.trie.match(list(topic)))

    def check(self, topics, got) -> None:
        for topic, rows in zip(topics, got):
            assert sorted((tuple(f), k) for f, k, _ in rows) \
                == self.want(topic), topic


def fill(p: Pair, rng, combo: str) -> None:
    """Concrete-first rows of every shape always; wildcard-first rows by
    ``combo``; one filter past the flat form's cap (the wide pass's)."""
    for i in range(600):
        p.add((f"w{rng.randrange(24)}", f"d{rng.randrange(40)}"), f"u{i}")
    for i in range(60):
        p.add((f"w{rng.randrange(24)}", "+", "c"), f"p{i}")
        p.add((f"w{rng.randrange(24)}", "#"), f"h{i}")
    for s in range(KCAP + 8):
        p.add(("hot", "b", "c"), f"hot{s}")
    if combo in ("gzone", "both"):
        for i, f in enumerate(G_FILTERS * 3):
            p.add(f, f"g{i}")
    if combo in ("region0", "both"):
        for i, f in enumerate(R0_FILTERS * 2):
            p.add(f, f"z{i}")


def topics_for(rng, n):
    pool = [("hot", "b", "x"), ("x", "b", "c"), ("w3", "w3", "z"),
            ("q", "d1"), ("$SYS", "b", "c"), ("w1",), ("nope", "nope")]
    return [rng.choice(pool) if rng.random() < 0.4 else
            (f"w{rng.randrange(24)}", rng.choice(["b", f"d{rng.randrange(40)}"]),
             "c")[:rng.randint(2, 3)] for _ in range(n)]


@pytest.fixture(scope="module", params=list(COMBOS))
def pair(request):
    p = Pair()
    fill(p, random.Random(len(request.param)), request.param)
    t = p.m.table
    assert t.bucketed and t.NG
    live0, liveg = t.live_rows
    assert bool(live0) == (request.param in ("region0", "both"))
    assert bool(liveg) == (request.param in ("gzone", "both"))
    p.phases = COMBOS[request.param]
    return p


# -- (1) parity with the host trie, every combination, every way in -------

@pytest.mark.parametrize("way", ["match_batch", "match_many", "wide_pass"])
def test_rows_are_the_tries_in_every_combination(pair, way, monkeypatch):
    rng = random.Random(7)
    narrow = spy_kernel_call(monkeypatch, "call_packed")
    many = spy_kernel_call(monkeypatch, "call_match_many")
    wide = spy_kernel_call(monkeypatch, "call_wide")
    before = pair.m.host_fallbacks
    if way == "match_batch":
        topics = topics_for(rng, 16)
        pair.check(topics, pair.m.match_batch(topics))
        statics = narrow[-1][0][4]
    elif way == "match_many":
        batches = [topics_for(rng, 12), topics_for(rng, 16)]
        for topics, got in zip(batches, pair.m.match_many(batches)):
            pair.check(topics, got)
        statics = many[-1][0][4]
    else:
        topics = [("hot", "b", "c")] * 3 + topics_for(rng, 9)
        pair.check(topics, pair.m.match_batch(topics))
        assert len(pair.want(("hot", "b", "c"))) > KCAP and len(wide) == 1
        # the wide program follows the same counts: a second window only
        # while a g-bucket holds a live row
        assert bool(wide[0][0][8]["wb"]) == ("b" in pair.phases)
        statics = narrow[-1][0][4]
    assert pair.m.host_fallbacks == before
    assert TpuMatcher._phases(statics) == pair.phases
    assert statics["glob_pad"] >= 2048  # probe A's row guard keeps it


# -- (2) the flips ---------------------------------------------------------

class _Registry:
    """What a TpuRegView asks a registry for."""

    def __init__(self, trie):
        self._trie = trie

    def trie(self, mp):
        return self._trie

    def fold_subscriptions(self, mp):
        return self._trie.entries()


FLIPS = [(("+", "b", "c"), ("x", "b", "c"), "ab"),     # a g-bucket's row
         (("+", "+", "c"), ("x", "y", "c"), "gab"),    # region 0
         (("#",), ("x", "y", "z"), "gab")]             # region 0 again


def _plain_pair(**kw):
    p = Pair(**kw)
    for i in range(2000):
        p.add(("bench", str(i)), i)
    return p


def test_flips_direct():
    """SUBSCRIBE a wildcard-first filter on a warm wildcard-free table and
    the next dispatch holds its phase; UNSUBSCRIBE back and the short
    program is the one that was compiled before."""
    p = _plain_pair()
    m = p.m
    base = [("bench", str(i)) for i in range(12)]
    p.check(base, m.match_batch(base))
    short = set(m._warm_sigs)
    assert len(short) == 1 and m._live == (0, 0)
    for n, (filt, topic, phases) in enumerate(FLIPS):
        p.add(filt, f"w{n}")
        topics = base[:11] + [topic]
        got = m.match_batch(topics)
        p.check(topics, got)
        assert (filt, f"w{n}") in [(tuple(f), k) for f, k, _ in got[-1]]
        assert profiler().snapshot("match")[-1]["phases"] == phases
    assert m._live == (2, 1) and len(m._warm_sigs) == 3
    for n, (filt, _topic, _ph) in enumerate(FLIPS):
        p.remove(filt, f"w{n}")
    compiled = K.match_extract_windowed_flat_packed._cache_size()
    with pytest.raises(MatcherBusy):  # a cold form is refused, as ever
        m._warm_sigs -= short
        m.match_batch(base, require_warm=True)
    m._warm_sigs |= short
    p.check(base, m.match_batch(base, require_warm=True))
    assert m._live == (0, 0) and len(m._warm_sigs) == 3
    assert K.match_extract_windowed_flat_packed._cache_size() == compiled
    assert profiler().snapshot("match")[-1]["phases"] == "a"


def test_flips_through_the_collector():
    """The same through the collector (``require_warm``): the flush that
    meets the new statics is served by the host trie while the program
    compiles on the side, the next one by the device; back on an empty
    g-zone the short program serves at once."""
    p = _plain_pair()
    m = p.m
    view = TpuRegView(_Registry(p.trie), max_levels=8,
                      initial_capacity=CAP, max_fanout=KCAP)
    view._matchers[""] = m     # resident, and no ladder thread of its own
    base = [("bench", str(i)) for i in range(11)]
    m.match_batch(base + [("x", "b", "c")])         # Bpad 16 warm, "a"

    async def flush(col, topics):
        rows = await asyncio.gather(*[col.submit("", t) for t in topics])
        p.check(topics, rows)
        return rows

    async def until_warm(n_sigs):
        for _ in range(600):
            if len(m._warm_sigs) >= n_sigs and not m._warming:
                return
            await asyncio.sleep(0.05)
        raise AssertionError("the new form never came warm")

    async def scenario():
        col = BatchCollector(view, window_us=100, max_batch=64,
                             host_threshold=8)
        await flush(col, base + [("x", "b", "c")])
        assert (m.match_publishes, col.busy_host_pubs) == (24, 0)
        sigs = 1
        for n, (filt, topic, phases) in enumerate(FLIPS):
            view.on_delta("add", "", filt, f"w{n}", None)
            p.trie.add(list(filt), f"w{n}", None)
            served, shed = m.match_publishes, col.busy_host_pubs
            new_form = phases != profiler().snapshot("match")[-1]["phases"]
            rows = await flush(col, base + [topic])   # delivered either way
            assert (filt, f"w{n}") in [(tuple(f), k) for f, k, _ in rows[-1]]
            if new_form:   # cold: the trie served it
                assert col.busy_host_pubs == shed + 12
                sigs += 1
                await until_warm(sigs)
                served = m.match_publishes
                await flush(col, base + [topic])
            assert m.match_publishes == served + 12      # the device did
            assert profiler().snapshot("match")[-1]["phases"] == phases
        for n, (filt, _t, _ph) in enumerate(FLIPS):
            view.on_delta("remove", "", filt, f"w{n}", None)
            p.trie.remove(list(filt), f"w{n}")
        served, shed = m.match_publishes, col.busy_host_pubs
        compiled = K.match_extract_windowed_flat_packed._cache_size()
        await flush(col, base + [("x", "b", "c")])
        assert (m.match_publishes, col.busy_host_pubs) == (served + 12, shed)
        assert K.match_extract_windowed_flat_packed._cache_size() == compiled
        assert profiler().snapshot("match")[-1]["phases"] == "a"
        assert m.warm_failures == 0

    asyncio.run(scenario())
    m.close()


# -- (3) the counts through relocation, rebuild and an async install -------

def _brute(t: SubscriptionTable):
    reg = t._region_of_slot[np.flatnonzero(t.active)]
    return (int((reg == 0).sum()),
            int(((reg >= 1) & (reg <= t.NG)).sum()))


def test_counts_follow_relocation_and_rebuild():
    rng = random.Random(3)
    t = SubscriptionTable(max_levels=8, initial_capacity=CAP)
    live = []
    keys = iter(range(1 << 30))

    def churn(n, mk):
        for i in range(n):
            fw = mk(i)
            key = next(keys)
            t.add(list(fw), key)
            live.append((fw, key))
            if rng.random() < 0.3:
                fw2, key2 = live.pop(rng.randrange(len(live)))
                assert t.remove(list(fw2), key2)

    churn(300, lambda i: rng.choice(G_FILTERS + R0_FILTERS
                                    + [("w", str(i))]))
    assert t.live_rows == _brute(t) and all(t.live_rows)
    # one level-0 bucket outgrows its region: it moves to the spare tail
    spare, cap = t.spare_start, t.cap
    churn(4000, lambda i: ("w", str(i)))
    assert t.spare_start > spare and t.cap == cap
    assert t.live_rows == _brute(t)
    # a g-bucket outgrows its region: g-zone regions never leave the
    # zone, so the whole table is repartitioned and every row re-counted
    churn(6000, lambda i: ("+", "b", str(i)))
    assert t.cap > cap and t.NG
    assert t.live_rows == _brute(t)
    for fw, key in live:
        assert t.remove(list(fw), key)
    assert t.live_rows == (0, 0) == _brute(t)


def test_counts_travel_with_an_async_install():
    """The counts a dispatch uses are the installed snapshot's, not the
    live table's: rows added while a rebuild uploads reach the program
    with the delta that carries them."""
    rng = random.Random(5)
    p = Pair(capacity=8192)
    m = p.m
    m.async_rebuild = True
    for i in range(3000):
        p.add((f"r{rng.randrange(8)}", f"d{i}"), i)
    topics = [("r1", "d7"), ("x", "y", "z")] + \
        [(f"r{rng.randrange(8)}", f"d{rng.randrange(3000)}")
         for _ in range(10)]
    p.check(topics, m.match_batch(topics))
    assert m._live == (0, 0)
    gate = threading.Event()
    m._rebuild_barrier = gate
    i = 0
    while not m.table.resized:
        p.add((f"r{rng.randrange(8)}", f"g{i}"), ("g", i))
        i += 1
    with pytest.raises(RebuildInProgress):
        m.match_batch(topics)
    p.add(("#",), "late")          # lands while the snapshot uploads
    assert m.table.live_rows == (1, 0) and m._live == (0, 0)
    th = m._rebuild_thread
    gate.set()
    th.join(timeout=120)
    assert not th.is_alive()
    m._rebuild_barrier = None
    assert m._live == (0, 0)       # the snapshot's, as installed
    p.check(topics, m.match_batch(topics))   # sync scatters "#"
    assert m._live == m.table.live_rows == (1, 0)


# -- (4) the warm ladder asks for no more programs -------------------------

@pytest.mark.parametrize("combo", ["none", "both"])
def test_warm_ladder_compiles_one_form_a_rung(combo, monkeypatch):
    p = Pair()
    fill(p, random.Random(11), combo)
    calls = spy_kernel_call(monkeypatch, "call_packed")
    assert p.m.warm_ladder(max_batch=32) == 6          # 1, 2, ... 32
    flat = [s for s in p.m._warm_sigs if s[0] != "wide"]
    # one signature a padded batch size (8, 16, 32), as on the parent:
    # only the form the table needs, never both
    assert len(flat) == 3
    assert {TpuMatcher._phases(c[0][4]) for c in calls} == {COMBOS[combo]}
    assert len({(c[0][3][0].shape, tuple(sorted(c[0][4].items())))
                for c in calls}) == 3
    # warm-up traffic is no dispatch: nothing counted
    assert p.m.match_batches == 0


# -- (5) the counters and the ring -----------------------------------------

def test_counters_and_ring_name_the_phases(pair, monkeypatch):
    monkeypatch.setattr(tm, "phase_dispatches", 0)
    monkeypatch.setattr(tm, "phase_runs", 0)
    rng = random.Random(1)
    pair.m.match_batch(topics_for(rng, 12), _warmup=True)
    assert (tm.phase_dispatches, tm.phase_runs) == (0, 0)
    pair.m.match_batch(topics_for(rng, 12))
    pair.m.match_many([topics_for(rng, 12), topics_for(rng, 9)])
    # one count an execution, single or super, and its phases summed
    assert (tm.phase_dispatches, tm.phase_runs) \
        == (2, 2 * len(pair.phases))
    single, many = profiler().snapshot("match")[-2:]
    assert single["phases"] == many["phases"] == pair.phases
    assert (single["k"], many["k"]) == (1, 2)


def test_unbucketed_table_counts_no_phase(monkeypatch):
    monkeypatch.setattr(tm, "phase_dispatches", 0)
    m = TpuMatcher(max_levels=4, initial_capacity=64)
    m.table.add(["a", "+"], "k", None)
    assert m.match_batch([("a", "b")])[0]
    assert tm.phase_dispatches == 0
    assert "phases" not in profiler().snapshot("match")[-1]
