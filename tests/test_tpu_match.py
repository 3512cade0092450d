"""TPU match engine tests: parity against the host trie oracle on random
corpora (SURVEY.md §7.1 step 4 / §4.4 — kernel vs reference matcher), delta
updates, overflow/truncation fallbacks, and the broker wired to the tpu
reg view end-to-end. Runs on the CPU backend (conftest forces 8 virtual
devices)."""

import asyncio
import random

import pytest

from vernemq_tpu.models import tpu_matcher
from vernemq_tpu.models.tpu_matcher import TpuMatcher
from vernemq_tpu.models.trie import SubscriptionTrie
from vernemq_tpu.protocol import topic as T

WORDS = ["a", "b", "c", "d", "sensor", "dev", "x1", ""]


def rand_filter(rng, max_len=6):
    n = rng.randint(1, max_len)
    words = []
    for _ in range(n):
        r = rng.random()
        if r < 0.2:
            words.append("+")
        else:
            words.append(rng.choice(WORDS))
    if rng.random() < 0.25:
        words.append("#")
    return words


def rand_topic(rng, max_len=6):
    n = rng.randint(1, max_len)
    words = [rng.choice(WORDS) for _ in range(n)]
    if rng.random() < 0.1:
        words[0] = "$SYS"
    return tuple(words)


def norm(rows):
    return sorted((tuple(f), k) for f, k, _ in rows)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_parity_random_corpus(seed):
    rng = random.Random(seed)
    matcher = TpuMatcher(max_levels=8, initial_capacity=64, max_fanout=128)
    trie = SubscriptionTrie()
    for i in range(300):
        f = rand_filter(rng)
        matcher.table.add(f, i, None)
        trie.add(f, i, None)
    topics = [rand_topic(rng) for _ in range(100)]
    got = matcher.match_batch(topics)
    for topic, rows in zip(topics, got):
        assert norm(rows) == norm(trie.match(list(topic))), topic


def test_delta_add_remove():
    m = TpuMatcher(max_levels=4, initial_capacity=8)
    m.table.add(["a", "+"], "k1", None)
    m.table.add(["a", "b"], "k2", None)
    assert norm(m.match_batch([("a", "b")])[0]) == [(("a", "+"), "k1"), (("a", "b"), "k2")]
    # delta: remove one, add another — exercises apply_delta scatter
    m.table.remove(["a", "b"], "k2")
    m.table.add(["#"], "k3", None)
    assert norm(m.match_batch([("a", "b")])[0]) == [(("#",), "k3"), (("a", "+"), "k1")]


def test_capacity_growth():
    m = TpuMatcher(max_levels=4, initial_capacity=4)
    for i in range(100):
        m.table.add(["t", str(i)], i, None)
    rows = m.match_batch([("t", "42")])[0]
    assert norm(rows) == [(("t", "42"), 42)]
    assert m.table.cap >= 100


def test_dollar_rule_on_device():
    m = TpuMatcher(max_levels=4)
    m.table.add(["#"], "root", None)
    m.table.add(["$SYS", "#"], "sys", None)
    m.table.add(["+", "x"], "plus", None)
    assert norm(m.match_batch([("$SYS", "x")])[0]) == [(("$SYS", "#"), "sys")]
    assert norm(m.match_batch([("normal", "x")])[0]) == [
        (("#",), "root"), (("+", "x"), "plus")]


def test_hash_matches_parent_level():
    m = TpuMatcher(max_levels=4)
    m.table.add(["a", "#"], "k", None)
    assert norm(m.match_batch([("a",)])[0]) == [(("a", "#"), "k")]
    assert norm(m.match_batch([("a", "b", "c")])[0]) == [(("a", "#"), "k")]
    assert m.match_batch([("b",)])[0] == []


def test_long_filter_overflow_to_host():
    m = TpuMatcher(max_levels=4)
    m.table.add(["a", "b", "c", "d", "e", "f"], "long", None)  # > L levels
    m.table.add(["a", "#"], "short", None)
    rows = m.match_batch([("a", "b", "c", "d", "e", "f")])[0]
    assert norm(rows) == [(("a", "#"), "short"),
                          (("a", "b", "c", "d", "e", "f"), "long")]


def test_fanout_truncation_falls_back_exact():
    m = TpuMatcher(max_levels=4, max_fanout=8)
    for i in range(50):
        m.table.add(["hot", "t"], f"k{i}", None)
    rows = m.match_batch([("hot", "t")])[0]
    assert len(rows) == 50  # truncated on device, exact on host


def test_unknown_publish_words_only_match_wildcards():
    m = TpuMatcher(max_levels=4)
    m.table.add(["+"], "plus", None)
    m.table.add(["known"], "exact", None)
    assert norm(m.match_batch([("neverseen",)])[0]) == [(("+",), "plus")]


@pytest.mark.asyncio
async def test_broker_e2e_with_tpu_reg_view(event_loop):
    """Full broker with default_reg_view=tpu: real MQTT over TCP routes
    through the batched device matcher."""
    from vernemq_tpu.broker.config import Config
    from vernemq_tpu.broker.server import start_broker
    from vernemq_tpu.client import MQTTClient

    b, server = await start_broker(
        Config(systree_enabled=False, allow_anonymous=True, default_reg_view="tpu",
               tpu_batch_window_us=500, tpu_host_batch_threshold=0),
        port=0,
    )
    try:
        sub = MQTTClient(server.host, server.port, "tpu-sub")
        await sub.connect()
        await sub.subscribe("tpu/+/x", qos=1)
        pub = MQTTClient(server.host, server.port, "tpu-pub")
        await pub.connect()
        for i in range(5):
            await pub.publish(f"tpu/{i}/x", f"m{i}".encode(), qos=1)
        got = sorted([(await sub.recv()).payload for _ in range(5)])
        assert got == [f"m{i}".encode() for i in range(5)]
        # matched via the device path (hybrid dispatch disabled above).
        # Cold-shape/busy windows shed single publishes to the trie by
        # design (a loaded box stretches those windows), so keep
        # publishing until the device has served some — delivery
        # correctness was already asserted above either way.
        view = b.registry.reg_view("tpu")
        m = view.matcher("")
        for i in range(5, 60):
            if m.match_publishes >= 5:
                break
            await pub.publish(f"tpu/{i % 9}/x", b"warm", qos=0)
            await asyncio.sleep(0.05)
        assert m.match_publishes >= 5, (m.match_publishes, m.busy_sheds)
        await sub.disconnect()
        await pub.disconnect()
    finally:
        await b.stop()
        await server.stop()


@pytest.mark.asyncio
async def test_hybrid_dispatch_small_flush_serves_host_side(event_loop):
    """Flushes at or below tpu_host_batch_threshold resolve on the host
    trie (no device call, no executor hop — SURVEY §7.2 hybrid
    dispatch); the device matcher sees nothing and delivery is exact."""
    from vernemq_tpu.broker.config import Config
    from vernemq_tpu.broker.server import start_broker
    from vernemq_tpu.client import MQTTClient

    b, server = await start_broker(
        Config(systree_enabled=False, allow_anonymous=True,
               default_reg_view="tpu", tpu_batch_window_us=200,
               tpu_host_batch_threshold=8),
        port=0,
    )
    try:
        sub = MQTTClient(server.host, server.port, "hy-sub")
        await sub.connect()
        await sub.subscribe("hy/+/x", qos=1)
        pub = MQTTClient(server.host, server.port, "hy-pub")
        await pub.connect()
        for i in range(4):  # sequential QoS1: one-pub flushes
            await pub.publish(f"hy/{i}/x", f"m{i}".encode(), qos=1)
        got = sorted([(await sub.recv()).payload for _ in range(4)])
        assert got == [f"m{i}".encode() for i in range(4)]
        col = b.batch_collector()
        assert col.host_hybrid_pubs >= 4
        view = b.registry.reg_view("tpu")
        mm = view._matchers.get("")
        assert mm is None or mm.match_publishes == 0
        await sub.disconnect()
        await pub.disconnect()
    finally:
        await b.stop()
        await server.stop()


# ---------------------------------------------------------------------------
# Bucketed path (level-0 bucket narrowing — models/tpu_table.py regions +
# ops/match_kernel.match_extract_windowed_flat). A big initial capacity forces
# NB > 1 so these run the windowed device path, not the full scan.
# ---------------------------------------------------------------------------

def _bucketed_matcher(**kw):
    m = TpuMatcher(max_levels=8, initial_capacity=16384, **kw)
    assert m.table.bucketed and m.table.NB > 1
    return m


def corpus_filter(rng):
    """Bucket-realistic corpus: concrete level-0 words dominate, with
    wildcard-first and $-rooted filters mixed in."""
    w = [f"r{rng.randrange(16)}", f"d{rng.randrange(40)}", f"m{rng.randrange(16)}"]
    r = rng.random()
    if r < 0.5:
        return w
    if r < 0.65:
        return [w[0], "+", w[2]]
    if r < 0.75:
        return ["+", w[1], w[2]]
    if r < 0.85:
        return [w[0], w[1], "#"]
    if r < 0.90:
        return [w[0], "+", "#"]
    if r < 0.95:
        return ["$SYS", w[1], w[2]]
    return ["#"]


@pytest.mark.parametrize("seed", [0, 1])
def test_bucketed_parity_with_churn(seed):
    """Random corpus through add/remove churn + growth rebuilds: the tiled
    bucketed matcher agrees with the trie oracle on every topic (incl.
    $-topics, unknown words, >L topics and truncation fallbacks)."""
    rng = random.Random(seed)
    m = _bucketed_matcher(max_fanout=256)
    trie = SubscriptionTrie()
    subs = []
    for i in range(12000):
        f = corpus_filter(rng)
        m.table.add(f, i, None)
        trie.add(list(f), i, None)
        subs.append(f)
    for i in rng.sample(range(12000), 3000):
        m.table.remove(subs[i], i)
        trie.remove(list(subs[i]), i)
    topics = [(f"r{rng.randrange(16)}", f"d{rng.randrange(40)}",
               f"m{rng.randrange(16)}") for _ in range(200)]
    topics += [("$SYS", "d1", "m2"), ("unseen", "d0"), ("r1",),
               ("r1", "d1", "m1", "deep", "deeper")]
    for topic, rows in zip(topics, m.match_batch(topics)):
        assert norm(rows) == norm(trie.match(list(topic))), topic
    # delta-scatter path (no rebuild): mutate after the first sync
    for i in range(12000, 12400):
        f = corpus_filter(rng)
        m.table.add(f, i, None)
        trie.add(list(f), i, None)
    assert not m.table.resized  # stays on the scatter path
    for topic, rows in zip(topics[:50], m.match_batch(topics[:50])):
        assert norm(rows) == norm(trie.match(list(topic))), topic


def test_bucketed_rebuild_preserves_entries():
    """Region overflow triggers a repartition; every entry survives with a
    (possibly) new slot and matching still agrees with the oracle."""
    rng = random.Random(3)
    m = _bucketed_matcher()
    trie = SubscriptionTrie()
    cap_before = m.table.cap
    n = 0
    while m.table.cap == cap_before:  # insert until a rebuild fires
        f = corpus_filter(rng)
        m.table.add(f, n, None)
        trie.add(list(f), n, None)
        n += 1
        assert n < 10_000_000
    assert m.table.count == n
    topics = [(f"r{i % 16}", f"d{i % 40}", f"m{i % 16}") for i in range(64)]
    for topic, rows in zip(topics, m.match_batch(topics)):
        assert norm(rows) == norm(trie.match(list(topic))), topic


def test_prepare_windows_invariants():
    """Fixed-T windowing: every pub either lands in exactly one tile whose
    window fully covers its bucket, or is reported as a leftover; window
    starts stay inside [row_lo, row_hi - seg_max]."""
    import numpy as np

    from vernemq_tpu.models.tpu_matcher import prepare_windows

    rng = random.Random(5)
    NB = 16
    reg_cap = np.array([2048] + [256 * rng.randint(1, 8) for _ in range(NB)],
                       dtype=np.int64)
    reg_start = np.concatenate([[0], np.cumsum(reg_cap)[:-1]])
    reg_end = reg_start + reg_cap
    S = int(reg_cap.sum())
    seg_max = 4096
    n, Bpad, T = 500, 512, 4
    pb = np.array([rng.randint(1, NB) for _ in range(n)], dtype=np.int32)
    L = 4
    pw = np.zeros((Bpad, L), dtype=np.int32)
    pl = np.zeros(Bpad, dtype=np.int32)
    pd = np.zeros(Bpad, dtype=bool)
    (t_pw, t_pl, t_pd, t_start, tile_of, pos_of,
     leftovers) = prepare_windows(pw, pl, pd, pb, n, reg_start, reg_end,
                                  S, T, seg_max)
    from vernemq_tpu.models.tpu_matcher import TILE_PUBS
    assert t_pw.shape == (T, TILE_PUBS, L)
    left = set(leftovers)
    for i in range(n):
        b = int(pb[i])
        if i in left:
            assert tile_of[i] == -1
            continue
        ti = int(tile_of[i])
        start = int(t_start[ti])
        assert 0 <= start <= S - seg_max
        assert start <= reg_start[b] and reg_end[b] <= start + seg_max
    assert len(left) + int((tile_of >= 0).sum()) == n

    # sharded slice: only buckets fully inside [row_lo, row_hi) are tiled
    row_lo, row_hi = int(reg_start[8]), S
    (t_pw2, _, _, t_start2, tile_of2, _, left2) = prepare_windows(
        pw, pl, pd, pb, n, reg_start, reg_end, S, T, seg_max,
        row_lo=row_lo, row_hi=row_hi)
    for i in range(n):
        b = int(pb[i])
        if int(tile_of2[i]) >= 0:
            start = int(t_start2[int(tile_of2[i])]) + row_lo
            assert start >= row_lo
            assert start <= reg_start[b] and reg_end[b] <= start + seg_max
            assert reg_end[b] <= row_hi
        else:
            assert i in set(left2)


def test_bucketed_id_bits_crossover():
    """Interner growth past the 16-bit plane limit rebuilds operands on the
    24-bit path and matching stays exact."""
    from vernemq_tpu.models import tpu_table as TT

    old16 = TT.MAX_IDS_16
    TT.MAX_IDS_16 = 500  # force the crossover without 65k interns
    try:
        rng = random.Random(9)
        m = _bucketed_matcher()
        trie = SubscriptionTrie()
        for i in range(2000):  # ~interns 2000 distinct level-2 words
            f = [f"r{i % 8}", "x", f"unique{i}"]
            m.table.add(f, i, None)
            trie.add(list(f), i, None)
        assert m.table.id_bits == 24
        topics = [(f"r{i % 8}", "x", f"unique{i}") for i in range(0, 2000, 37)]
        for topic, rows in zip(topics, m.match_batch(topics)):
            assert norm(rows) == norm(trie.match(list(topic))), topic
    finally:
        TT.MAX_IDS_16 = old16


def test_region_relocation_no_rebuild():
    """An overflowing bucket region relocates into the spare tail — S and
    slot capacity unchanged (no device re-upload, no recompile) and
    matching stays exact (VERDICT r2 weak-1 cold-rebuild stalls)."""
    import numpy as np

    from vernemq_tpu.models.tpu_table import SubscriptionTable

    table = SubscriptionTable(max_levels=8, initial_capacity=16384)
    trie = SubscriptionTrie()
    m = TpuMatcher(max_levels=8, initial_capacity=16384)
    m.table = table
    # fill one level-0 word's bucket until its region overflows
    cap_before = None
    n = 0
    relocated = False
    for i in range(6000):
        f = ["hot", f"d{i}", f"m{i % 7}"]
        table.add(f, i, None)
        trie.add(list(f), i, None)
        n += 1
        if cap_before is None:
            cap_before = table.cap
        if not table.resized and table.cap == cap_before and \
                table.spare_start != cap_before - table.spare_cap:
            relocated = True
    # also some background filters in other buckets
    for i in range(500):
        f = [f"r{i % 20}", "x", "+"]
        table.add(f, 10_000 + i, None)
        trie.add(list(f), 10_000 + i, None)
    table.resized = True  # force first upload on the fresh matcher
    topics = [("hot", f"d{i}", f"m{i % 7}") for i in range(0, 6000, 101)]
    topics += [(f"r{i % 20}", "x", "q") for i in range(8)]
    for topic, rows in zip(topics, m.match_batch(topics)):
        assert norm(rows) == norm(trie.match(list(topic))), topic

    # now trigger relocation AFTER the matcher is warm: deltas only
    assert not table.resized
    start_cap = table.cap
    for i in range(6000, 9000):
        f = ["hot", f"d{i}", f"m{i % 7}"]
        table.add(f, i, None)
        trie.add(list(f), i, None)
        if table.resized:
            break
    # matching stays exact whether it relocated or rebuilt; if capacity
    # never changed, the growth was relocation-only (the cheap path)
    grew_in_place = not table.resized and table.cap == start_cap
    topics = [("hot", f"d{i}", f"m{i % 7}") for i in range(5900, 9000, 37)]
    for topic, rows in zip(topics, m.match_batch(topics)):
        assert norm(rows) == norm(trie.match(list(topic))), topic
    assert grew_in_place, "expected spare-tail relocation, got full rebuild"


def test_windowed_matcher_property_parity():
    """Hypothesis: random filter corpora (incl. $-prefixes, deep levels,
    unicode words, churn) stay in exact parity with the trie oracle on the
    windowed path."""
    pytest.importorskip("hypothesis")  # not in the image: skip
    from hypothesis import given, settings, strategies as st

    word = st.sampled_from(
        ["a", "b", "c", "dev", "Ω", "x-y", "0", "$SYS", "metric"])
    filt = st.lists(
        st.one_of(word, st.sampled_from(["+", "#"])),
        min_size=1, max_size=6,
    ).filter(lambda f: "#" not in f[:-1])
    topic = st.lists(word.filter(lambda w: w not in ("+", "#")),
                     min_size=1, max_size=6)

    @settings(max_examples=25, deadline=None)
    @given(st.lists(filt, min_size=1, max_size=40),
           st.lists(topic, min_size=1, max_size=12),
           st.data())
    def run(filters, topics, data):
        m = _bucketed_matcher()
        trie = SubscriptionTrie()
        # pad with bulk filler so the bucketed layout engages
        for i in range(3000):
            f = [f"fill{i % 31}", f"x{i % 11}", "+"]
            m.table.add(f, 100000 + i, None)
            trie.add(list(f), 100000 + i, None)
        for i, f in enumerate(filters):
            m.table.add(list(f), i, None)
            trie.add(list(f), i, None)
        # churn: remove a random subset
        for i, f in enumerate(filters):
            if data.draw(st.booleans()):
                m.table.remove(list(f), i)
                trie.remove(list(f), i)
        for t, rows in zip(topics, m.match_batch([tuple(t) for t in topics])):
            assert norm(rows) == norm(trie.match(list(t))), t

    run()


def test_two_level_probe_parity():
    """NG-active table (cap >= 32768 → level-1 g-buckets live): dense
    region 0 shrinks to both-levels-wild filters; probes A+B together
    stay in exact parity with the trie, including "+"/w1 filters, churn
    on them, and 1-level topics."""
    rng = random.Random(77)
    m = TpuMatcher(max_levels=8, initial_capacity=1 << 16)
    assert m.table.NG > 0
    trie = SubscriptionTrie()

    def add(f, k):
        m.table.add(list(f), k, None)
        trie.add(list(f), k, None)

    # realistic fanout corpus: mostly exact / single-wildcard filters (a
    # corpus_filter-style 5% bare-'#' rate puts EVERY pub's true fanout
    # past max_fanout, which legitimately routes all pubs to the exact
    # host path and makes the device-path assertion below meaningless)
    for i in range(20000):
        r = rng.random()
        w = [f"r{rng.randrange(16)}", f"d{rng.randrange(40)}",
             f"m{rng.randrange(16)}"]
        if r < 0.6:
            f = w
        elif r < 0.8:
            f = [w[0], "+", w[2]]
        elif r < 0.9:
            f = ["+", w[1], w[2]]
        else:
            f = [w[0], w[1], "#"]
        add(f, i)
    # heavy "+"-first population (the g-bucket zone)
    for i in range(3000):
        add(["+", f"d{rng.randrange(40)}", f"m{rng.randrange(16)}"],
            100000 + i)
    for i in range(200):
        add(["+", "+", f"m{i % 16}"], 200000 + i)  # stays dense (region 0)
        add(["#"], 300000 + i) if i == 0 else None
    topics = [(f"r{i % 16}", f"d{i % 40}", f"m{i % 16}") for i in range(64)]
    topics += [("nosub", f"d{i % 40}", "x") for i in range(8)]  # g-probe only
    topics += [("r1",), ("r1", "d2")]  # short topics
    for topic, rows in zip(topics, m.match_batch(topics)):
        assert norm(rows) == norm(trie.match(list(topic))), topic
    # churn in the g-zone: remove a slice of the "+"-first filters
    removed = 0
    for e in list(m.table.entries):
        if e is not None and isinstance(e[1], int) and \
                100000 <= e[1] < 103000 and removed % 7 == 0:
            m.table.remove(list(e[0]), e[1])
            trie.remove(list(e[0]), e[1])
        if e is not None and isinstance(e[1], int) and \
                100000 <= e[1] < 103000:
            removed += 1
    for topic, rows in zip(topics, m.match_batch(topics)):
        assert norm(rows) == norm(trie.match(list(topic))), topic
    # the DEVICE path must have served the bulk of these pubs: a kernel
    # bug that blows per-pub counts silently degrades every pub to the
    # exact host fallback and parity alone cannot see it
    assert m.host_fallbacks < m.match_publishes // 4, (
        m.host_fallbacks, m.match_publishes)


async def _boot_must_fail(config, match):
    """Broker.start() with ``config`` raises an error matching ``match``
    and leaves no tpu view (least of all the trie under that name)."""
    from vernemq_tpu.broker.broker import Broker

    b = Broker(config, node_name="noboot")
    try:
        with pytest.raises(Exception, match=match):
            await b.start()
        assert "tpu" not in b.registry.reg_views
    finally:
        await b.stop()


@pytest.mark.asyncio
async def test_tpu_view_backend_init_failure_fails_boot(monkeypatch):
    """default_reg_view=tpu with a backend that cannot initialise: the
    broker refuses to start with the backend's own error — it does not
    serve from the host trie under the tpu view's name."""
    import jax

    from vernemq_tpu.broker.config import Config

    def no_backend(*_a, **_k):
        raise RuntimeError("Unable to initialize backend 'tpu': no chip")

    monkeypatch.setattr(jax, "devices", no_backend)
    await _boot_must_fail(
        Config(systree_enabled=False, allow_anonymous=True,
               default_reg_view="tpu"),
        "Unable to initialize backend 'tpu'")


@pytest.mark.asyncio
@pytest.mark.parametrize("spec,match", [
    ("1x64", "wants 64 devices but only 8 present"),
    ("lots", "invalid tpu_mesh"),
])
async def test_tpu_mesh_unsatisfiable_fails_boot(spec, match):
    """tpu_mesh asking for more devices than exist (or not parsing) is a
    start-up error, not a single-chip boot."""
    from vernemq_tpu.broker.config import Config

    await _boot_must_fail(
        Config(systree_enabled=False, allow_anonymous=True,
               default_reg_view="tpu", tpu_mesh=spec), match)


def test_flat_capacity_overflow_falls_back_exact():
    """A batch whose total fanout exceeds the flat buffer (C =
    Bpad*flat_avg) must stay exact: overflowed pubs take the wide pass
    instead of losing matches (match_extract_windowed_flat's overflow
    contract), and none the host scan."""
    rng = random.Random(7)
    m = _bucketed_matcher(max_fanout=256, flat_avg=1)  # C == Bpad: tiny
    trie = SubscriptionTrie()
    for i in range(9000):
        f = corpus_filter(rng)
        m.table.add(f, i, None)
        trie.add(list(f), i, None)
    topics = [(f"r{rng.randrange(16)}", f"d{rng.randrange(40)}",
               f"m{rng.randrange(16)}") for _ in range(64)]
    before, wide = m.host_fallbacks, tpu_matcher.wide_publishes
    for topic, rows in zip(topics, m.match_batch(topics)):
        assert norm(rows) == norm(trie.match(list(topic))), topic
    # the tiny flat buffer did overflow
    assert tpu_matcher.wide_publishes > wide
    assert m.host_fallbacks == before


def test_flat_padded_batch_tail_is_inert():
    """Real pubs < padded batch: pad rows must contribute nothing to the
    flat prefix (a bare-'#' filter matches the zero-length pad topic —
    the n_real mask must exclude it)."""
    m = _bucketed_matcher(max_fanout=64)
    trie = SubscriptionTrie()
    rng = random.Random(8)
    m.table.add(["#"], -1, None)        # matches everything incl. pads
    trie.add(["#"], -1, None)
    for i in range(9000):
        f = corpus_filter(rng)
        m.table.add(f, i, None)
        trie.add(list(f), i, None)
    # 5 real topics in a padded batch (Bpad = 8)
    topics = [(f"r{i}", f"d{i}", f"m{i}") for i in range(5)]
    for topic, rows in zip(topics, m.match_batch(topics)):
        assert norm(rows) == norm(trie.match(list(topic))), topic


def test_flat_overflow_property_parity():
    """Hypothesis: with a deliberately starved flat buffer (flat_avg=1)
    and tiny per-part k, random corpora with heavy duplicate filters
    stay in exact parity — every clipped/overflowed pub must fall back
    to the exact host path, and the prefix math after an overflowed pub
    must not corrupt its neighbours' ranges (the clamp-to-k budget)."""
    pytest.importorskip("hypothesis")  # not in the image: skip
    from hypothesis import given, settings, strategies as st

    word = st.sampled_from(["r0", "r1", "d0", "d1", "m0"])
    filt = st.lists(
        st.one_of(word, st.sampled_from(["+", "#"])),
        min_size=1, max_size=4,
    ).filter(lambda f: "#" not in f[:-1])
    topic = st.lists(word, min_size=1, max_size=4)

    @settings(max_examples=20, deadline=None)
    @given(st.lists(filt, min_size=5, max_size=60),
           st.lists(topic, min_size=4, max_size=24))
    def run(filters, topics):
        m = _bucketed_matcher(max_fanout=16, flat_avg=1)
        trie = SubscriptionTrie()
        for i in range(9000):  # engage the bucketed layout
            f = [f"fill{i % 13}", f"x{i % 7}", "+"]
            m.table.add(f, 100000 + i, None)
            trie.add(list(f), 100000 + i, None)
        for i, f in enumerate(filters):
            # duplicates across keys force fanouts past k=16
            for dup in range(3):
                m.table.add(list(f), (i, dup), None)
                trie.add(list(f), (i, dup), None)
        got = m.match_batch([tuple(t) for t in topics])
        for t, rows in zip(topics, got):
            assert norm(rows) == norm(trie.match(list(t))), t

    run()


def spy_kernel_call(monkeypatch, name, module=None):
    """Record every call the program makes to ``<module>.<name>``
    (``ops.match_kernel`` unless given) as ``(positional args, keyword
    args, result)``: a test can then hold what was dispatched to another
    program on exactly the operands the seat built, through no private
    of the seat."""
    if module is None:
        from vernemq_tpu.ops import match_kernel as module

    calls = []
    real = getattr(module, name)

    def spy(*args, **kw):
        out = real(*args, **kw)
        calls.append((args, kw, out))
        return out

    monkeypatch.setattr(module, name, spy)
    return calls


def test_packed_variant_matches_flat_kernel(monkeypatch):
    """What ``match_batch`` dispatches —
    match_extract_windowed_flat_packed through ``call_packed``, the
    single-vector transport — parses back to exactly the plain kernel's
    (flat, pre, total, overflow) on the same operands: guards the
    flat_pack_args / pack_meta / unpack layout against drift."""
    import numpy as np

    from vernemq_tpu.ops import match_kernel as K

    rng = random.Random(22)
    m = _bucketed_matcher(max_fanout=64)
    for i in range(10000):
        m.table.add(corpus_filter(rng), i, None)
    topics = [(f"r{rng.randrange(16)}", f"d{rng.randrange(40)}",
               f"m{rng.randrange(16)}") for _ in range(64)]
    calls = spy_kernel_call(monkeypatch, "call_packed")
    m.match_batch(topics)
    ((F_t, t1, _meta, args, statics), _kw, out), = calls
    out = np.asarray(out)
    t = m.table
    flat, pre, total, ovf = (np.asarray(x) for x in
                             K.match_extract_windowed_flat(
                                 F_t, t1, t.eff_len, t.has_hash,
                                 t.first_wild, t.active, *args, **statics))
    Bpad = args[0].shape[0]
    C = statics["C"]
    assert out.shape == (C + 3 * Bpad,)
    pflat, ppre, ptotal, povf = K.unpack_flat_result(out, Bpad, C)
    assert int(total[:64].sum()) > 0
    np.testing.assert_array_equal(pflat, flat)
    np.testing.assert_array_equal(ppre, pre)
    np.testing.assert_array_equal(ptotal, total)
    np.testing.assert_array_equal(povf, ovf)


@pytest.mark.asyncio
async def test_table_load_runs_off_the_loop_and_keeps_deltas(event_loop):
    """A serving broker builds its device table in the background
    (``TpuRegView.begin_load``): the loop keeps running, a flush that
    lands meanwhile is served by the trie and counted, subscribes and
    unsubscribes that land meanwhile are replayed, and the table ends
    equal to the registry."""
    from vernemq_tpu.broker.broker import Broker
    from vernemq_tpu.broker.config import Config
    from vernemq_tpu.protocol.types import SubOpts

    b = Broker(Config(systree_enabled=False, allow_anonymous=True,
                      default_reg_view="tpu"))
    await b.start()
    try:
        reg = b.registry
        for i in range(400):
            reg.subscribe(("", f"c{i}"), [([f"a{i % 7}", "+", f"x{i}"],
                                          SubOpts(qos=i & 1))])
        view = reg.reg_view("tpu")
        view._LOAD_CHUNK = 16  # many executor hops: the load spans ticks
        assert view.begin_load("") is False
        assert view.begin_load("") is False  # started once
        with pytest.raises(Exception, match="table loading"):
            view.matcher("")
        # while it loads: the loop runs, deltas land, a flush is served
        ticks = 0
        col = b.batch_collector()
        futs = [col.submit("", ("a1", "k", f"x{1 + 7 * j}"))
                for j in range(12)]
        reg.subscribe(("", "late"), [(["a1", "#"], SubOpts(qos=1))])
        reg.unsubscribe(("", "c8"), [["a1", "+", "x8"]])
        reg.subscribe(("", "c15"), [(["a1", "+", "x15"], SubOpts(qos=2))])
        rows = await asyncio.gather(*futs)
        assert col.rebuild_host_pubs == 12 and view._loading
        # the trie's exact answer, deltas included: c8 is gone, late is in
        assert [len(r) for r in rows] == [2, 1] + [2] * 10
        while not view.begin_load(""):
            ticks += 1
            await asyncio.sleep(0)
        assert ticks > 10
        m = view.matcher("")
        have = {(fw, key): opts.qos for e in m.table.entries
                if e is not None for fw, key, opts in [e]}
        want = {(fw, key): opts.qos
                for fw, key, opts in reg.fold_subscriptions("")}
        assert have == want and len(want) == 400
        assert have[(("a1", "+", "x15"), ("", "c15"))] == 2
        assert (("a1", "+", "x8"), ("", "c8")) not in have
        # deltas after the hand-over take the normal path
        reg.subscribe(("", "after"), [(["z", "z"], SubOpts(qos=0))])
        assert m.table.count == 401 and not view._loading
    finally:
        await b.stop()
