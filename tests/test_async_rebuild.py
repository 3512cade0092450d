"""Non-blocking device-table growth (TpuMatcher.async_rebuild).

The property under test: a capacity rebuild — the full re-upload that
used to stall matching for its whole duration (the 28.6s
sub_to_matchable_max outlier in the r3 config-5 bench) — must not stop
the publish pipeline. While the new table builds on a worker thread,
match paths shed to the host trie and keep returning CORRECT results;
after the install the device serves again, including the subscriptions
that triggered the growth.
"""

import asyncio
import random
import threading

import pytest

from vernemq_tpu.models.tpu_matcher import RebuildInProgress, TpuMatcher
from vernemq_tpu.models.trie import SubscriptionTrie


def fill(m, trie, n, tag, rng):
    for i in range(n):
        fw = [f"r{rng.randrange(8)}", f"d{rng.randrange(16)}",
              f"{tag}{i}"]
        m.table.add(fw, (tag, i), None)
        trie.add(fw, (tag, i), None)


def check_device(m, trie, topics):
    got = m.match_batch(topics)
    for t, rows in zip(topics, got):
        want = sorted(k for _, k, _ in trie.match(list(t)))
        assert sorted(k for _, k, _ in rows) == want, t


def grow_until_resize(m, trie, rng, tag):
    """Add subscriptions until the table marks a capacity change."""
    i = 0
    while not m.table.resized:
        fw = [f"r{rng.randrange(8)}", "+", f"{tag}{i}"]
        m.table.add(fw, (tag, i), None)
        trie.add(fw, (tag, i), None)
        i += 1
        assert i < 500_000, "table never resized"
    return i


def test_async_rebuild_sheds_and_recovers():
    rng = random.Random(5)
    m = TpuMatcher(max_levels=8, initial_capacity=8192)
    m.async_rebuild = True
    trie = SubscriptionTrie()
    fill(m, trie, 3000, "a", rng)
    topics = [(f"r{rng.randrange(8)}", f"d{rng.randrange(16)}",
               f"a{rng.randrange(3000)}") for _ in range(12)]
    check_device(m, trie, topics)  # first build is synchronous

    gate = threading.Event()
    m._rebuild_barrier = gate
    n_new = grow_until_resize(m, trie, rng, "g")
    # during the (gated) rebuild every match sheds
    with pytest.raises(RebuildInProgress):
        m.match_batch(topics)
    with pytest.raises(RebuildInProgress):
        m.match_batch(topics)
    assert m.rebuilds_async == 1
    th = m._rebuild_thread  # capture BEFORE the gate opens: install nulls it
    gate.set()
    th.join(timeout=60)
    m._rebuild_barrier = None
    # device serves again, and the growth-batch subscriptions match
    check_device(m, trie, topics)
    probe = [(f"r{rng.randrange(8)}", f"d{rng.randrange(16)}",
              f"g{rng.randrange(n_new)}") for _ in range(8)]
    check_device(m, trie, probe)


def test_second_resize_mid_rebuild_discards_stale_build():
    rng = random.Random(9)
    m = TpuMatcher(max_levels=8, initial_capacity=8192)
    m.async_rebuild = True
    trie = SubscriptionTrie()
    fill(m, trie, 3000, "a", rng)
    topics = [(f"r{rng.randrange(8)}", f"d{rng.randrange(16)}",
               f"a{rng.randrange(3000)}") for _ in range(8)]
    check_device(m, trie, topics)

    gate = threading.Event()
    m._rebuild_barrier = gate
    grow_until_resize(m, trie, rng, "g")
    with pytest.raises(RebuildInProgress):
        m.match_batch(topics)
    # the layout moves AGAIN while the first build is parked at the gate
    n2 = grow_until_resize(m, trie, rng, "h")
    gate.set()  # first build installs... no: it must discard + go again
    for _ in range(600):
        th = m._rebuild_thread
        if th is None or not th.is_alive():
            with m.lock:
                if m._rebuild_thread is None:
                    break
        th.join(timeout=0.1)
    m._rebuild_barrier = None
    assert m.rebuilds_async >= 2  # the stale build went around again
    check_device(m, trie, topics)
    probe = [(f"r{rng.randrange(8)}", "x", f"h{rng.randrange(n2)}")
             for _ in range(6)]
    check_device(m, trie, probe)


def test_crashed_rebuild_rearms_and_retries():
    """A worker that dies mid-build must NOT leave the matcher on the
    delta path against the stale pre-resize arrays (silently wrong
    fanout); the resize re-arms and the next sync goes again."""
    rng = random.Random(21)
    m = TpuMatcher(max_levels=8, initial_capacity=8192)
    m.async_rebuild = True
    trie = SubscriptionTrie()
    fill(m, trie, 3000, "a", rng)
    topics = [(f"r{rng.randrange(8)}", f"d{rng.randrange(16)}",
               f"a{rng.randrange(3000)}") for _ in range(8)]
    check_device(m, trie, topics)

    real_build = m._build_device
    crashes = []

    def exploding(state):
        crashes.append(1)
        raise RuntimeError("injected device failure")

    m._build_device = exploding
    n_new = grow_until_resize(m, trie, rng, "g")
    with pytest.raises(RebuildInProgress):
        m.match_batch(topics)
    m._rebuild_thread.join(timeout=60)  # dies on the injected failure
    assert crashes == [1]
    m._build_device = real_build
    # the reap re-arms the resize and spawns a fresh build
    with pytest.raises(RebuildInProgress):
        m.match_batch(topics)
    th = m._rebuild_thread
    if th is not None:
        th.join(timeout=60)
    check_device(m, trie, topics)
    probe = [(f"r{rng.randrange(8)}", f"d{rng.randrange(16)}",
              f"g{rng.randrange(n_new)}") for _ in range(6)]
    check_device(m, trie, probe)


def test_deltas_after_install_apply():
    """Mutations landing between snapshot and install must reach the
    device as normal deltas on the next sync."""
    rng = random.Random(13)
    m = TpuMatcher(max_levels=8, initial_capacity=8192)
    m.async_rebuild = True
    trie = SubscriptionTrie()
    fill(m, trie, 3000, "a", rng)
    check_device(m, trie, [("r1", "d2", "a7")])

    gate = threading.Event()
    m._rebuild_barrier = gate
    grow_until_resize(m, trie, rng, "g")
    with pytest.raises(RebuildInProgress):
        m.match_batch([("r1", "d2", "a7")])
    # a subscribe while the upload is in flight: dirty-marked in the
    # snapshot's (unchanged) layout
    m.table.add(["r1", "d2", "late-bird"], ("late", 1), None)
    trie.add(["r1", "d2", "late-bird"], ("late", 1), None)
    th = m._rebuild_thread  # capture BEFORE the gate opens: install nulls it
    gate.set()
    th.join(timeout=60)
    m._rebuild_barrier = None
    check_device(m, trie, [("r1", "d2", "late-bird"), ("r1", "d2", "a7")])


def test_delta_flush_is_single_fused_scatter(monkeypatch):
    """A delta sync must coalesce the whole dirty set into ONE packed
    upload + ONE fused scatter call — not per-array eager updates
    (each a separate executable launch and host↔device round trip) —
    and the scattered slots must match afterwards."""
    import vernemq_tpu.ops.match_kernel as K

    rng = random.Random(11)
    m = TpuMatcher(max_levels=8, initial_capacity=16384)
    assert m.table.bucketed
    trie = SubscriptionTrie()
    fill(m, trie, 3000, "a", rng)
    topics = [(f"r{rng.randrange(8)}", f"d{rng.randrange(16)}",
               f"a{rng.randrange(3000)}") for _ in range(8)]
    check_device(m, trie, topics)  # first full build

    calls = {"fused": 0, "unfused": 0}
    fused_real = K.apply_delta_fused

    def counting_fused(*a, **kw):
        calls["fused"] += 1
        return fused_real(*a, **kw)

    def forbidden(name):
        def _f(*a, **kw):
            calls["unfused"] += 1
            raise AssertionError(
                f"per-array delta path {name} used — the flush must "
                f"be ONE fused scatter")
        return _f

    monkeypatch.setattr(K, "apply_delta_fused", counting_fused)
    for name in ("apply_delta", "apply_delta_copy",
                 "apply_delta_meta", "apply_delta_meta_copy"):
        monkeypatch.setattr(K, name, forbidden(name))
    # a delta flush: adds only, no resize
    fill(m, trie, 200, "d", rng)
    assert not m.table.resized
    probe = [(f"r{rng.randrange(8)}", f"d{rng.randrange(16)}",
              f"d{rng.randrange(200)}") for _ in range(8)]
    check_device(m, trie, probe + topics)
    assert calls["fused"] == 1, calls  # ONE fused scatter per flush
    assert calls["unfused"] == 0


@pytest.mark.asyncio
async def test_busy_matcher_lock_sheds_within_bound():
    """A long matcher-lock hold (first-compile of a new shape, slow
    backend batch) must not head-block the pipeline: past
    tpu_lock_busy_shed_ms the flush serves from the trie."""
    import time

    from vernemq_tpu.broker.config import Config
    from vernemq_tpu.broker.server import start_broker
    from vernemq_tpu.client import MQTTClient

    b, server = await start_broker(
        Config(systree_enabled=False, allow_anonymous=True,
               default_reg_view="tpu", tpu_host_batch_threshold=0,
               tpu_lock_busy_shed_ms=150), port=0)
    try:
        sub = MQTTClient(server.host, server.port, client_id="bz-sub")
        await sub.connect()
        await sub.subscribe("bz/t", qos=0)
        pub = MQTTClient(server.host, server.port, client_id="bz-pub")
        await pub.connect()
        await pub.publish("bz/t", b"warm", qos=0)
        assert (await asyncio.wait_for(sub.messages.get(), 10)).payload \
            == b"warm"
        matcher = b.registry.reg_view("tpu").matcher("")
        matcher.lock.acquire()  # simulate a multi-second hold
        try:
            t0 = time.perf_counter()
            for i in range(3):
                await pub.publish("bz/t", b"b%d" % i, qos=0)
                m = await asyncio.wait_for(sub.messages.get(), 10)
                assert m.payload == b"b%d" % i
            elapsed = time.perf_counter() - t0
            # 3 deliveries, each bounded ~150ms + trie time, not the hold
            assert elapsed < 5.0, elapsed
            assert b.batch_collector().busy_host_pubs >= 3
            assert matcher.busy_sheds >= 1
        finally:
            matcher.lock.release()
        await pub.publish("bz/t", b"freed", qos=0)
        assert (await asyncio.wait_for(sub.messages.get(), 10)).payload \
            == b"freed"
        await pub.disconnect()
        await sub.disconnect()
    finally:
        await b.stop()
        await server.stop()


@pytest.mark.asyncio
async def test_broker_keeps_delivering_through_rebuild():
    """Broker-level: with default_reg_view=tpu, publishes keep being
    delivered while the device table rebuilds (collector sheds to the
    trie), and the growth subscriber becomes matchable after install."""
    from vernemq_tpu.broker.config import Config
    from vernemq_tpu.broker.server import start_broker
    from vernemq_tpu.client import MQTTClient

    b, server = await start_broker(
        Config(systree_enabled=False, allow_anonymous=True,
               default_reg_view="tpu", tpu_host_batch_threshold=0,
               tpu_initial_capacity=8192), port=0)
    try:
        sub = MQTTClient(server.host, server.port, client_id="rb-sub")
        await sub.connect()
        await sub.subscribe("rb/t", qos=0)
        pub = MQTTClient(server.host, server.port, client_id="rb-pub")
        await pub.connect()
        await pub.publish("rb/t", b"warm", qos=0)
        assert (await asyncio.wait_for(sub.messages.get(), 10)).payload \
            == b"warm"
        matcher = b.registry.reg_view("tpu").matcher("")
        gate = None
        import threading as _t

        gate = _t.Event()
        matcher._rebuild_barrier = gate
        # force a resize: grow way past the initial capacity
        with matcher.lock:
            for i in range(20000):
                matcher.table.add(["gr", "+", f"x{i}"], ("gr", i), None)
            assert matcher.table.resized
        # deliveries keep flowing while the rebuild is parked
        for i in range(5):
            await pub.publish("rb/t", b"during-%d" % i, qos=0)
            m = await asyncio.wait_for(sub.messages.get(), 10)
            assert m.payload == b"during-%d" % i
        gate.set()
        th = matcher._rebuild_thread
        if th is not None:
            await asyncio.get_event_loop().run_in_executor(
                None, th.join, 60)
        matcher._rebuild_barrier = None
        await pub.publish("rb/t", b"after", qos=0)
        assert (await asyncio.wait_for(sub.messages.get(), 10)).payload \
            == b"after"
        assert b.batch_collector().rebuild_host_pubs >= 1
        await pub.disconnect()
        await sub.disconnect()
    finally:
        await b.stop()
        await server.stop()


def test_delta_warm_ladder_pre_compiles_production_shapes():
    """warm_delta_ladder's throwaway zero-array compiles must land in
    the SAME executable cache the production delta path uses — a real
    post-warm delta may not trigger a compile (the
    sub_to_matchable_ms_max tail this warm exists to remove)."""
    import vernemq_tpu.ops.match_kernel as K

    rng = random.Random(17)
    m = TpuMatcher(max_levels=8, initial_capacity=16384)
    trie = SubscriptionTrie()
    fill(m, trie, 3000, "w", rng)
    check_device(m, trie, [("r1", "d1", "w1")])  # first build
    before = K.apply_delta_fused._cache_size()
    before_copy = K.apply_delta_fused_copy._cache_size()
    assert m.warm_delta_ladder(16) == 4  # Dpad 2,4,8,16
    assert m.delta_shapes_warmed == 4
    # >= not ==: the jit cache is process-global and another test's
    # leaked background warm can land a compile concurrently
    assert K.apply_delta_fused._cache_size() >= before + 4
    # the COPYING variant (selected while a match is in flight — the
    # common case under traffic) must be warmed too
    assert K.apply_delta_fused_copy._cache_size() >= before_copy + 4
    # THE assertion: a real 1-slot delta (Dpad=2) after the warm must
    # HIT the warmed executable, not mint a new one
    after_warm = K.apply_delta_fused._cache_size()
    fill(m, trie, 1, "zz", rng)
    check_device(m, trie, [("r1", "d1", "zz0")])
    assert K.apply_delta_fused._cache_size() == after_warm, \
        "production delta recompiled despite the warm"
