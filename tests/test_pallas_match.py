"""Pallas tile-matcher tests (ops/pallas_match.py).

Runs the fused kernel in interpret mode on the CPU backend (the module
self-selects interpret off-TPU) against the host trie oracle — the same
parity discipline as test_tpu_match.py. Alignment: the Pallas path floors
window starts to SEG_BLK, so these tests also pin that flooring strands
no pubs (leftovers stay host-free) and that the widened geometry still
covers every bucket region.
"""

import asyncio
import random
import time

import numpy as np
import pytest

from vernemq_tpu.models.tpu_matcher import TpuMatcher, window_params
from vernemq_tpu.models.trie import SubscriptionTrie
from vernemq_tpu.ops import pallas_match as P

WORDS = [f"w{i}" for i in range(150)]


def rand_filter(rng):
    n = rng.randint(1, 5)
    f = [rng.choice(WORDS + ["+"]) for _ in range(n)]
    if rng.random() < 0.2:
        f.append("#")
    return f


def rand_topic(rng):
    return [rng.choice(WORDS) for _ in range(rng.randint(1, 5))]


def norm(rows):
    return sorted((tuple(f), str(k)) for f, k, _ in rows)


def build(rng, n_subs, use_pallas=True, cap=8192):
    m = TpuMatcher(max_levels=8, initial_capacity=cap,
                   use_pallas=use_pallas)
    trie = SubscriptionTrie()
    for i in range(n_subs):
        f = rand_filter(rng)
        m.table.add(f, f"c{i}", None)
        trie.add(f, f"c{i}", None)
    return m, trie


@pytest.mark.parametrize("seed", [0, 1])
def test_pallas_parity_bucketed(seed):
    rng = random.Random(seed)
    m, trie = build(rng, 6000)
    assert m.table.bucketed  # must exercise the windowed (pallas) path
    topics = [rand_topic(rng) for _ in range(96)]
    got = m.match_batch(topics)
    for topic, rows in zip(topics, got):
        assert norm(rows) == norm(trie.match(list(topic))), topic


def test_pallas_dollar_rule_and_hash():
    m = TpuMatcher(max_levels=8, initial_capacity=8192, use_pallas=True)
    trie = SubscriptionTrie()
    rng = random.Random(3)
    for i in range(5000):  # force bucketed layout
        f = rand_filter(rng)
        m.table.add(f, f"f{i}", None)
        trie.add(f, f"f{i}", None)
    for i, f in enumerate((["#"], ["+", "x"], ["$SYS", "#"],
                           ["$SYS", "+", "x"])):
        m.table.add(list(f), f"d{i}", None)
        trie.add(list(f), f"d{i}", None)
    topics = [["$SYS", "node", "x"], ["$SYS", "a", "x"], ["a", "x"],
              ["x"], ["$SYS"]]
    got = m.match_batch(topics)
    for topic, rows in zip(topics, got):
        assert norm(rows) == norm(trie.match(list(topic))), topic


def test_pallas_delta_then_match():
    rng = random.Random(11)
    m, trie = build(rng, 5000)
    topics = [rand_topic(rng) for _ in range(32)]
    m.match_batch(topics)  # warm + upload
    # churn: removals + adds, then re-match through the delta-scatter path
    for i in range(0, 200, 2):
        m.table.remove(rand_filter(random.Random(i)), f"c{i}")  # may miss
    extra = []
    for i in range(300):
        f = rand_filter(rng)
        m.table.add(f, f"n{i}", None)
        trie.add(f, f"n{i}", None)
        extra.append(f)
    got = m.match_batch(topics)
    for topic, rows in zip(topics, got):
        want = {str(k) for _, k, _ in trie.match(list(topic))
                if str(k).startswith("n") or str(k).startswith("c")}
        have = {str(k) for _, k, _ in rows}
        # removals above may or may not hit real filters; adds must land
        assert {k for k in want if k.startswith("n")} <= have


def test_pallas_aligned_windows_no_leftovers():
    """Flooring starts to SEG_BLK must not push pubs to the host path:
    window_params widens seg_max by one block to absorb it."""
    rng = random.Random(5)
    m, _ = build(rng, 6000)
    topics = [rand_topic(rng) for _ in range(128)]
    m.match_batch(topics)
    assert m.host_fallbacks == 0
    # geometry invariant: the widened window still covers the max region
    t = m.table
    with m.lock:
        m.sync()
    reg_start, reg_end = m._reg_start, m._reg_end
    ng = m._ng
    amax = int((reg_end[1 + ng:] - reg_start[1 + ng:]).max())
    _T, seg_max, _gc = window_params(
        int(t.cap), m._glob_pad, amax, 128, zone=int(t.cap) - m._gb_end,
        align=P.SEG_BLK)
    assert seg_max >= amax + P.SEG_BLK or seg_max == int(t.cap)


def test_pallas_lowering_failure_propagates(monkeypatch):
    """tpu_use_pallas with a kernel that will not lower: the error is a
    device failure (breaker fed, cause attached; verbatim without a
    breaker) — the XLA kernel is never swapped in behind the operator's
    back."""
    from vernemq_tpu.models.tpu_matcher import DeviceDegraded
    from vernemq_tpu.ops import match_kernel as K

    rng = random.Random(9)
    m, _trie = build(rng, 5000)

    def boom(*a, **k):
        raise RuntimeError("mosaic lowering failed")

    def no_fallback(*a, **k):
        raise AssertionError("XLA kernel ran in place of the Pallas one")

    monkeypatch.setattr(P, "match_extract_windowed_flat_pallas", boom)
    monkeypatch.setattr(K, "match_extract_windowed_flat", no_fallback)
    monkeypatch.setattr(K, "call_packed", no_fallback)
    topics = [rand_topic(rng) for _ in range(32)]
    with pytest.raises(DeviceDegraded) as ei:
        m.match_batch(topics)
    assert "mosaic lowering failed" in repr(ei.value.__cause__)
    assert m.device_failures == 1 and not m._warm_sigs
    m.breaker = None
    with pytest.raises(RuntimeError, match="mosaic lowering failed"):
        m.match_batch(topics)


def test_pallas_parity_vs_xla_kernel():
    """Bit-for-bit agreement of the two kernels on identical prep."""
    rng = random.Random(21)
    mp_, trie = build(rng, 6000, use_pallas=True)
    mx, _ = build(random.Random(21), 6000, use_pallas=False)
    topics = [rand_topic(rng) for _ in range(64)]
    gp = mp_.match_batch(topics)
    gx = mx.match_batch(topics)
    for topic, rp, rx in zip(topics, gp, gx):
        assert norm(rp) == norm(rx), topic


@pytest.mark.asyncio
async def test_broker_tpu_view_pallas_bucketed(tmp_path):
    """End-to-end through the broker: a bucketed-scale subscription table
    served by the TPU reg view with the Pallas probe kernel, over real
    MQTT — registration via the registry bootstrap (6k filters would be
    slow to SUBSCRIBE one by one), then live publishes through the
    batch collector's device path."""
    from vernemq_tpu.broker.config import Config
    from vernemq_tpu.broker.server import start_broker
    from vernemq_tpu.client import MQTTClient

    broker = server = sub = pub = None
    try:
        broker, server = await start_broker(
            Config(systree_enabled=False, allow_anonymous=True,
                   default_reg_view="tpu", tpu_use_pallas=True,
                   tpu_initial_capacity=8192,  # pre-sized: bucketed layout
                   tpu_host_batch_threshold=0, tpu_batch_window_us=500,
                   # a compile that lags the loop on a crowded machine
                   # must not raise the governor: level 2 sheds QoS 0
                   sysmon_enabled=False),
            port=0)
        from vernemq_tpu.protocol.types import SubOpts

        rng = random.Random(31)
        # bucketed-scale corpus straight through the registry (the same
        # subscribe path a session uses; events feed both trie and the
        # device table)
        for i in range(5000):
            f = rand_filter(rng)
            broker.registry.subscribe(("", f"bulk{i}"),
                                      [(list(f), SubOpts(qos=0))])
        sub = MQTTClient(server.host, server.port, client_id="live-sub")
        await sub.connect()
        await sub.subscribe("w1/w2/#", qos=0)
        pub = MQTTClient(server.host, server.port, client_id="live-pub")
        await pub.connect()
        view = broker.registry.reg_view("tpu")
        matcher = view.matcher("")
        assert matcher.use_pallas
        assert matcher.table.bucketed  # the windowed (pallas) path
        # WARM-FIRST: while the ladder still compiles this batch shape
        # the cold-shape gate sheds flushes to the trie (by design), so
        # publish until one was device-served, with a bound
        deadline = time.monotonic() + 25.0
        n = 0
        while matcher.match_batches < 1:
            assert time.monotonic() < deadline, (
                "no device-served flush within the bound",
                matcher.warm_failures, matcher.busy_sheds)
            n += 1
            await pub.publish("w1/w2/w3", b"via-pallas%d" % n, qos=0)
            m = await sub.recv(10.0)
            assert m.payload == b"via-pallas%d" % n
            await asyncio.sleep(0.05)
        assert matcher.warm_failures == 0
    finally:
        # teardown in finally: a failing assert must not leak the
        # server/clients into subsequent event-loop tests
        for c in (sub, pub):
            if c is not None:
                await c.close()
        if broker is not None:
            await broker.stop()
        if server is not None:
            await server.stop()
