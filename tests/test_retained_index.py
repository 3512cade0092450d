"""Retained reverse-match engine tests: oracle parity against
``RetainStore.match_filter`` on randomized topic/filter corpora (incl.
``$``-topics, ``+``/``#`` mixes, per-mountpoint isolation), delta
set/delete maintenance, growth rebuilds, per-filter host-fallback
contracts, fault-injection/breaker degradation, the replay batch
collector, retained-replay semantics through the broker (retain_handling
1/2, RAP, shared-subscription exclusion, MQTT-4.7.2-1). Runs on the CPU
backend (conftest forces it)."""

import asyncio
import random

import pytest
from hypothesis import given, settings, strategies as st

from vernemq_tpu.broker.retain import RetainStore
from vernemq_tpu.models.tpu_matcher import DeviceDegraded
from vernemq_tpu.retained.index import RetainedEngine, RetainedIndex
from vernemq_tpu.robustness import faults
from vernemq_tpu.robustness.breaker import CircuitBreaker

WORDS = ["a", "b", "c", "d", "sensor", "dev", "x1", ""]


def rand_topic(rng, max_len=6):
    n = rng.randint(1, max_len)
    words = [rng.choice(WORDS) for _ in range(n)]
    if rng.random() < 0.1:
        words[0] = "$SYS"
    return tuple(words)


def rand_filter(rng, max_len=6):
    n = rng.randint(1, max_len)
    words = []
    for _ in range(n):
        words.append("+" if rng.random() < 0.2 else rng.choice(WORDS))
    if rng.random() < 0.25:
        words.append("#")
    return tuple(words)


def norm(rows):
    return sorted((t, v) for t, v in rows)


def make_pair(max_levels=8, cap=2048, k=64, **idx_kw):
    """Wired (store, index) pair for mountpoint "": store mutations
    write through to the index exactly like the broker's dirty hook."""
    holder = {}
    store = RetainStore(
        on_dirty=lambda mp, t, v: holder["idx"].on_retain(t, v))
    idx = RetainedIndex(store, max_levels=max_levels, initial_capacity=cap,
                        max_fanout=k)
    idx.async_rebuild = False
    # exercise the device dense phase on CPU too (production "auto"
    # routes wildcard-first filters host-side there)
    idx.dense_policy = "device"
    for key, val in idx_kw.items():
        setattr(idx, key, val)
    holder["idx"] = idx
    return store, idx


def exact(store, idx, filters, mountpoint=""):
    """The production contract: device results, per-filter None escapes
    resolved against the host store."""
    out = []
    for fw, rows in zip(filters, idx.match_filters(filters)):
        if rows is None:
            rows = store.match_filter(mountpoint, list(fw))
        out.append(rows)
    return out


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_parity_random_corpus(seed):
    rng = random.Random(seed)
    store, idx = make_pair()
    for i in range(400):
        store.insert("", rand_topic(rng), b"v%d" % i)
    filters = [rand_filter(rng) for _ in range(120)]
    for fw, rows in zip(filters, exact(store, idx, filters)):
        assert norm(rows) == norm(store.match_filter("", list(fw))), fw


@pytest.mark.parametrize("dense_mode", ["coded", "compare"])
def test_dense_phase_parity_both_kernels(dense_mode):
    """Wildcard-first filters (dense full-table phase): the coded-matmul
    and levelwise-compare variants are bit-identical to the oracle,
    including the MQTT-4.7.2-1 $-skip."""
    rng = random.Random(7)
    store, idx = make_pair(dense_mode=dense_mode)
    for i in range(300):
        store.insert("", rand_topic(rng, max_len=4), i)
    store.insert("", ("$SYS", "node", "x"), "sys")
    filters = [("#",), ("+",), ("+", "#"), ("+", "b", "#"),
               ("+", "b"), ("+", "+", "+")]
    for fw, rows in zip(filters, exact(store, idx, filters)):
        oracle = store.match_filter("", list(fw))
        assert norm(rows) == norm(oracle), fw
        # root-level wildcard never reaches the $-topic
        assert all(t[0] != "$SYS" for t, _ in rows), fw
    # a concrete "$SYS"-first filter DOES reach it
    (rows,) = exact(store, idx, [("$SYS", "node", "x")])
    assert ("$SYS", "node", "x") in [t for t, _ in rows]


def test_delta_set_delete_update_parity():
    rng = random.Random(3)
    store, idx = make_pair()
    topics = [rand_topic(rng) for _ in range(300)]
    for i, t in enumerate(topics):
        store.insert("", t, b"v%d" % i)
    filters = [rand_filter(rng) for _ in range(60)]
    exact(store, idx, filters)  # first full build
    builds = idx.rebuilds
    # churn: deletes, re-inserts, payload updates — all delta scatters
    for i in range(150):
        r = rng.random()
        t = rng.choice(topics)
        if r < 0.4:
            store.delete("", t)
        else:
            store.insert("", t, b"n%d" % i)
    for fw, rows in zip(filters, exact(store, idx, filters)):
        assert norm(rows) == norm(store.match_filter("", list(fw))), fw
    assert idx.rebuilds == builds  # served by the delta path, no rebuild


def test_growth_rebuild_parity():
    rng = random.Random(4)
    store, idx = make_pair(cap=2048)
    filters = [rand_filter(rng) for _ in range(40)]
    for i in range(5000):  # overflows the 2048-slot initial layout
        store.insert("", (f"g{i % 97}", f"h{i}"), i)
    for fw, rows in zip(filters, exact(store, idx, filters)):
        assert norm(rows) == norm(store.match_filter("", list(fw))), fw
    assert idx.rebuilds >= 1
    assert idx.table.cap > 2048


def test_async_rebuild_sheds_to_host():
    """With async_rebuild on, a capacity rebuild raises
    RebuildInProgress (callers host-walk) and installs in the
    background."""
    import time

    from vernemq_tpu.models.tpu_matcher import RebuildInProgress

    store, idx = make_pair(cap=2048, k=256, extract_k=256)
    for i in range(100):
        store.insert("", ("w", str(i)), i)
    idx.match_filters([("w", "+")])  # first inline build
    idx.async_rebuild = True
    for i in range(4000):
        store.insert("", (f"z{i % 31}", f"q{i}"), i)
    with pytest.raises(RebuildInProgress):
        idx.match_filters([("w", "+")])
    deadline = time.time() + 30
    while time.time() < deadline:
        try:
            rows = idx.match_filters([("w", "+")])[0]
            break
        except RebuildInProgress:
            time.sleep(0.02)
    else:
        pytest.fail("background rebuild never installed")
    assert norm(rows) == norm(store.match_filter("", ["w", "+"]))


def test_mountpoint_isolation():
    store = RetainStore()
    eng = RetainedEngine(store)
    store._on_dirty = eng.on_retain
    store.insert("", ("t", "a"), "default")
    store.insert("mp2", ("t", "a"), "other")
    for mp, want in [("", "default"), ("mp2", "other")]:
        idx = eng.index(mp)
        idx.async_rebuild = False
        rows = idx.match_filters([("t", "+")])[0]
        assert rows is not None and [v for _, v in rows] == [want]
    stats = eng.stats()
    assert stats["retained_index_rows"] == 2
    assert stats["retained_match_dispatches"] == 2


def test_fanout_over_k_host_fallback():
    store, idx = make_pair(k=8)
    for i in range(50):
        store.insert("", ("hot", f"t{i}"), i)
    res = idx.match_filters([("hot", "+"), ("hot", "t1")])
    assert res[0] is None  # 50 matches > k=8: exact host contract
    assert res[1] is not None and len(res[1]) == 1
    assert idx.host_fallback_queries == 1
    rows = store.match_filter("", ["hot", "+"])
    assert len(rows) == 50


def test_overflow_topics_and_long_filters():
    """Topics deeper than L live host-side but a '#' filter still
    reaches them; filters with more concrete levels than L come back
    None (host)."""
    store, idx = make_pair(max_levels=4)
    deep = ("a", "b", "c", "d", "e", "f")
    store.insert("", deep, "deep")
    store.insert("", ("a", "b"), "shallow")
    res = idx.match_filters([("a", "#"), ("a", "b"), deep])
    assert norm(res[0]) == norm(store.match_filter("", ["a", "#"]))
    assert {t for t, _ in res[0]} == {deep, ("a", "b")}
    assert norm(res[1]) == [(("a", "b"), "shallow")]
    assert res[2] is None  # 6 concrete levels > L=4: host
    # delete of the overflow topic propagates
    store.delete("", deep)
    res = idx.match_filters([("a", "#")])
    assert {t for t, _ in res[0]} == {("a", "b")}


def test_payload_update_visible_without_rebuild():
    store, idx = make_pair()
    store.insert("", ("u", "t"), "old")
    assert exact(store, idx, [("u", "t")])[0][0][1] == "old"
    builds = idx.rebuilds
    store.insert("", ("u", "t"), "new")
    assert exact(store, idx, [("u", "t")])[0][0][1] == "new"
    assert idx.rebuilds == builds


def test_fault_injection_breaker_and_recovery():
    """device.retained faults: the breaker opens after the threshold,
    calls shed with DeviceDegraded (host serves — parity preserved),
    and a half-open probe recovers after the fault clears."""
    import time

    store, idx = make_pair(k=256, extract_k=256)
    idx.breaker = CircuitBreaker(failure_threshold=2, backoff_initial=0.05,
                                 backoff_max=0.2)
    for i in range(100):
        store.insert("", ("f", str(i)), i)
    fw = ("f", "+")
    assert norm(idx.match_filters([fw])[0]) == \
        norm(store.match_filter("", list(fw)))
    faults.install(faults.FaultPlan(
        [faults.FaultRule("device.retained", kind="error")], seed=5))
    try:
        fails = 0
        for _ in range(4):
            try:
                idx.match_filters([fw])
            except DeviceDegraded:
                fails += 1
                # the production caller's degraded path: exact host walk
                rows = store.match_filter("", list(fw))
                assert len(rows) == 100
        assert fails >= 2
        assert idx.breaker.state_name == "open"
        assert idx.degraded_sheds >= 1
    finally:
        faults.clear()
    deadline = time.time() + 10
    while time.time() < deadline:
        try:
            rows = idx.match_filters([fw])
            if rows[0] is not None:
                break
        except DeviceDegraded:
            time.sleep(0.02)
    assert idx.breaker.state_name == "closed"
    assert norm(rows[0]) == norm(store.match_filter("", list(fw)))


def test_breaker_counts_delta_and_build_failures():
    """device.retained covers the upload half too: a failed delta
    scatter feeds the breaker and re-arms a full rebuild, after which
    host and device re-converge."""
    store, idx = make_pair()
    for i in range(50):
        store.insert("", ("d", str(i)), i)
    idx.match_filters([("d", "+")])
    faults.install(faults.FaultPlan(
        [faults.FaultRule("device.retained", kind="error", count=1)],
        seed=6))
    try:
        store.insert("", ("d", "extra"), "x")  # dirties a slot
        with pytest.raises(DeviceDegraded):
            idx.match_filters([("d", "+")])
        assert idx.device_failures == 1
    finally:
        faults.clear()
    rows = idx.match_filters([("d", "+")])[0]
    assert norm(rows) == norm(store.match_filter("", ["d", "+"]))
    assert any(t == ("d", "extra") for t, _ in rows)


@pytest.mark.asyncio
async def test_collector_batches_and_host_threshold():
    from vernemq_tpu.retained.collector import RetainedBatchCollector

    store = RetainStore()
    eng = RetainedEngine(store)
    store._on_dirty = eng.on_retain
    for i in range(64):
        store.insert("", ("c", str(i)), i)
    eng.index("").async_rebuild = False
    col = RetainedBatchCollector(eng, store, window_us=2000,
                                 max_batch=64, host_threshold=2)
    # a lone submit stays under the host threshold: host-served
    rows = await col.submit("", ("c", "3"))
    assert [v for _, v in rows] == [3]
    assert col.host_hybrid_filters == 1
    # a burst rides one device dispatch
    futs = [col.submit("", ("c", str(i))) for i in range(16)]
    results = await asyncio.gather(*futs)
    for i, rows in enumerate(results):
        assert [v for _, v in rows] == [i]
    assert col.device_batches >= 1
    assert col.device_filters >= 16


@pytest.mark.asyncio
async def test_collector_degraded_serves_host():
    from vernemq_tpu.retained.collector import RetainedBatchCollector

    store = RetainStore()
    eng = RetainedEngine(store)
    store._on_dirty = eng.on_retain
    for i in range(32):
        store.insert("", ("g", str(i)), i)
    idx = eng.index("")
    idx.async_rebuild = False
    idx.breaker = CircuitBreaker(failure_threshold=1, backoff_initial=5.0)
    idx.breaker.trip()  # pinned open: every dispatch refuses
    col = RetainedBatchCollector(eng, store, window_us=500,
                                 max_batch=32, host_threshold=0)
    futs = [col.submit("", ("g", str(i))) for i in range(8)]
    results = await asyncio.gather(*futs)
    for i, rows in enumerate(results):
        assert [v for _, v in rows] == [i]
    assert col.degraded_filters == 8
    assert col.device_batches == 0


# ------------------------------------------------ broker-level semantics

async def _boot(**cfg):
    from vernemq_tpu.broker.config import Config
    from vernemq_tpu.broker.server import start_broker

    cfg.setdefault("sysmon_enabled", False)
    cfg.setdefault("default_reg_view", "tpu")
    cfg.setdefault("tpu_retained_host_threshold", 0)
    cfg.setdefault("tpu_retained_window_us", 100)
    return await start_broker(
        Config(systree_enabled=False, allow_anonymous=True, **cfg),
        port=0, node_name="ret-node")


async def _connected(s, client_id, **kw):
    from vernemq_tpu.client import MQTTClient

    c = MQTTClient(s.host, s.port, client_id=client_id, **kw)
    await c.connect()
    return c


@pytest.mark.asyncio
async def test_broker_replay_semantics_device_path():
    """Retained replay through the device index end-to-end:
    retain_handling 1 (existing sub) / 2 (never), shared-subscription
    exclusion, $-topic skip for root wildcards — and the replay itself
    rides the retained collector (device dispatch counted)."""
    from vernemq_tpu.protocol.types import SubOpts

    b, s = await _boot()
    try:
        pub = await _connected(s, "rp")
        # QoS1 so routing (the async batched fold) settles before the
        # subscribes below — no live-routed copies race the replay
        await pub.publish("rh/t", b"kept", qos=1, retain=True)
        await pub.publish("$SYS/stat", b"sys", qos=1, retain=True)

        c = await _connected(s, "rs", proto_ver=5)
        # rh=2: never replayed
        await c.subscribe("rh/t", opts=SubOpts(qos=0, retain_handling=2))
        with pytest.raises(asyncio.TimeoutError):
            await c.recv(0.4)
        # rh=1 on a NEW subscription: replayed
        await c.subscribe("rh/+", opts=SubOpts(qos=0, retain_handling=1))
        m = await c.recv(25)
        assert m.payload == b"kept" and m.retain
        # rh=1 on the EXISTING subscription: not replayed again
        await c.subscribe("rh/+", opts=SubOpts(qos=0, retain_handling=1))
        with pytest.raises(asyncio.TimeoutError):
            await c.recv(0.4)
        # shared subscription: no retained replay (MQTT5 4.8.2)
        await c.subscribe("$share/grp/rh/t", qos=0)
        with pytest.raises(asyncio.TimeoutError):
            await c.recv(0.4)
        # root-level wildcard skips $-topics (4.7.2-1); a concrete
        # $SYS filter replays
        await c.subscribe("#", qos=0)
        with pytest.raises(asyncio.TimeoutError):
            # the only retained msgs are rh/t (already known via rh/+?
            # '#' is a NEW subscription, so rh/t replays — consume it)
            m2 = await c.recv(10)
            assert m2.payload == b"kept"
            await c.recv(0.4)  # but never the $SYS one
        await c.subscribe("$SYS/stat", qos=0)
        m3 = await c.recv(25)
        assert m3.payload == b"sys" and m3.retain
        col = b._retained_collector
        assert col is not None
        assert col.device_batches + col.degraded_filters \
            + col.rebuild_filters >= 1
        await c.close()
        await pub.close()
    finally:
        await b.stop()
        await s.stop()


@pytest.mark.asyncio
async def test_broker_replay_degrades_through_injected_outage():
    """An injected device.retained outage must not lose or corrupt a
    replay: the collector serves the host walk while the breaker is
    open."""
    b, s = await _boot()
    try:
        pub = await _connected(s, "op")
        for i in range(5):
            await pub.publish(f"deg/{i}", b"p%d" % i, qos=1, retain=True)
        faults.install(faults.FaultPlan(
            [faults.FaultRule("device.retained", kind="error")], seed=9))
        try:
            c = await _connected(s, "os")
            await c.subscribe("deg/+", qos=0)
            got = {(await c.recv(25)).payload for _ in range(5)}
            assert got == {b"p%d" % i for i in range(5)}
        finally:
            faults.clear()
        col = b._retained_collector
        assert col is not None and (col.degraded_filters >= 1
                                    or col.rebuild_filters >= 1)
        await c.close()
        await pub.close()
    finally:
        await b.stop()
        await s.stop()


# --------------------------------------------------------- admin / QL / items

def test_retain_store_items_all_mountpoints():
    store = RetainStore()
    store.insert("", ("a", "b"), 1)
    store.insert("mp", ("c",), 2)
    # back-compat: named mountpoint yields pairs
    pairs = sorted(t for t, _ in store.items(""))
    assert pairs == [("a", "b")]
    # all mountpoints: triples
    triples = sorted(store.items(None))
    assert triples == [("", ("a", "b"), 1), ("mp", ("c",), 2)]


def test_ql_retained_index_table():
    from types import SimpleNamespace

    from vernemq_tpu.admin.ql import run_query

    store = RetainStore()
    eng = RetainedEngine(store)
    store._on_dirty = eng.on_retain
    store.insert("", ("q", "one"), 1)
    store.insert("", ("q", "two"), 2)
    idx = eng.index("")
    idx.async_rebuild = False
    idx.match_filters([("q", "+")])  # sync the device table
    broker = SimpleNamespace(retain=store, _retained_engine=eng,
                             node_name="n")
    rows = run_query(broker, "retained_index")
    assert {r["topic"] for r in rows} == {"q/one", "q/two"}
    assert all(r["synced"] for r in rows)
    retain_rows = run_query(broker, "retain")
    assert {r["mountpoint"] for r in retain_rows} == {""}


# ------------------------------------------------------------- property test

topic_word = st.sampled_from(["a", "b", "c", "$x", "dev"])
filter_word = st.sampled_from(["a", "b", "c", "$x", "dev", "+"])


@given(st.lists(st.lists(topic_word, min_size=1, max_size=5),
                min_size=0, max_size=40),
       st.lists(st.tuples(st.lists(filter_word, min_size=1, max_size=5),
                          st.booleans()),
                min_size=1, max_size=12))
@settings(max_examples=40)
def test_property_reverse_match_parity(topics, filters):
    store, idx = make_pair(max_levels=8, cap=2048)
    for i, t in enumerate(topics):
        store.insert("", tuple(t), i)
    fls = [tuple(fw) + (("#",) if hash_suffix else ())
           for fw, hash_suffix in filters]
    for fw, rows in zip(fls, exact(store, idx, fls)):
        assert norm(rows) == norm(store.match_filter("", list(fw))), fw


def test_encode_cache_survives_region_remap():
    """A growth rebuild re-ranks the dedicated word->region map even
    when the interner does not grow; cached filter encodings must not
    keep probing the OLD region (review finding: silent missed
    replays)."""
    store, idx = make_pair(cap=2048, k=1024, extract_k=1024)
    words = [f"w{i}" for i in range(40)]
    tails = [f"s{k}" for k in range(80)]
    for k, tl in enumerate(tails):
        store.insert("", ("seed", tl), k)
    # w1 starts HOT (ranks near the top of the dedicated map)
    for i, w in enumerate(words):
        for k in range(60 if i == 1 else 20):
            store.insert("", (w, tails[k]), ("a", i, k))
    with idx.lock:
        idx.table._rebuild()  # establish the dedicated layout
    fw = ("w1", "+")
    before = exact(store, idx, [fw])[0]  # encode cache fills
    assert len(before) == 60
    key_a = (len(idx.table.interner), idx.table.NBD, idx.table.NBH)
    w1_region_a = idx.table.query_region(idx.table.interner.lookup("w1"))
    # invert the ranking (w1 goes cold) and re-rank: the dedicated map
    # remaps while the interner and NBD/NBH — everything the encode
    # cache USED to key on — stay put
    for k in range(1, 60):
        store.delete("", ("w1", tails[k]))
    with idx.lock:
        idx.table._rebuild()
    assert (len(idx.table.interner), idx.table.NBD,
            idx.table.NBH) == key_a
    assert idx.table.query_region(
        idx.table.interner.lookup("w1")) != w1_region_a, \
        "scenario setup failed: w1's region did not move"
    rows = exact(store, idx, [fw])[0]
    assert norm(rows) == norm(store.match_filter("", list(fw)))
    assert len(rows) == 1


@pytest.mark.asyncio
async def test_async_warm_load_buffers_racing_deltas():
    """warm_load_async: a delete arriving mid-load for a topic the load
    has NOT inserted yet must not be resurrected, and a mid-load insert
    must land."""
    store = RetainStore()
    eng = RetainedEngine(store)
    store._on_dirty = eng.on_retain
    for i in range(200):
        store.insert("", ("wl", str(i)), i)
    idx = eng._mk("")
    eng._indexes[""] = idx
    load = asyncio.get_event_loop().create_task(
        idx.warm_load_async(chunk=16))
    await asyncio.sleep(0)  # first chunk landed, rest pending
    store.delete("", ("wl", "150"))  # not-yet-loaded topic
    store.insert("", ("wl", "fresh"), "nv")
    await load
    idx.async_rebuild = False
    idx.max_fanout = idx.extract_k = 512
    rows = idx.match_filters([("wl", "+")])[0]
    assert rows is not None
    assert norm(rows) == norm(store.match_filter("", ["wl", "+"]))
    topics = {t for t, _ in rows}
    assert ("wl", "150") not in topics
    assert ("wl", "fresh") in topics


@pytest.mark.asyncio
async def test_collector_close_settles_pending():
    """Broker-stop quiesce: close() disarms the flush timer and settles
    every pending replay from the host walk; a straggler submit after
    close is host-served too — no leaked futures, no device work."""
    from vernemq_tpu.retained.collector import RetainedBatchCollector

    store = RetainStore()
    eng = RetainedEngine(store)
    store._on_dirty = eng.on_retain
    for i in range(8):
        store.insert("", ("cl", str(i)), i)
    col = RetainedBatchCollector(eng, store, window_us=10_000_000,
                                 max_batch=64, host_threshold=0)
    futs = [col.submit("", ("cl", str(i))) for i in range(4)]
    col.close()
    results = await asyncio.gather(*futs)
    for i, rows in enumerate(results):
        assert [v for _, v in rows] == [i]
    late = await col.submit("", ("cl", "5"))
    assert [v for _, v in late] == [5]
    assert col.device_batches == 0  # nothing ever dispatched


@pytest.mark.asyncio
@pytest.mark.parametrize("queued", [False, True], ids=["idle", "queued"])
async def test_collector_pressure_latency_counts_only_while_busy(queued):
    """The replay collector's dispatch-latency EWMA folds only on a flush:
    after one slow first dispatch (a SUBSCRIBE with more filters than the
    host threshold, compiling) an idle collector reported it as pressure
    for as long as nobody subscribed again, above the governor's L1 exit.
    With replays queued it is pressure."""
    from vernemq_tpu.retained.collector import RetainedBatchCollector
    from vernemq_tpu.robustness.overload import LATENCY_SEVERITY_CAP

    store = RetainStore()
    col = RetainedBatchCollector(RetainedEngine(store), store,
                                 window_us=10_000_000, max_batch=64,
                                 host_threshold=0, latency_budget_ms=50.0)
    col.dispatch_ewma_ms = 400.0
    futs = [col.submit("", ("p", "1"))] if queued else []
    assert col.pressure() == pytest.approx(
        LATENCY_SEVERITY_CAP if queued else 0.0)
    col.close()
    await asyncio.gather(*futs)
