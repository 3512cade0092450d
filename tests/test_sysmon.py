"""Sysmon / overload-protection + CRL-refresh tests (vmq_sysmon +
vmq_crl_srv roles)."""

import asyncio
import ssl
import time

import pytest

from vernemq_tpu.broker.config import Config
from vernemq_tpu.broker.server import start_broker
from vernemq_tpu.broker.sysmon import CrlRefresher, Sysmon, rss_bytes
from vernemq_tpu.client import MQTTClient


@pytest.mark.asyncio
async def test_sysmon_detects_loop_lag_and_sheds():
    b, s = await start_broker(Config(systree_enabled=False, allow_anonymous=True,
                                     sysmon_lag_threshold=0.05),
                              port=0, node_name="sysmon-node")
    try:
        mon = b.sysmon
        assert mon is not None
        mon.stop()  # restart with a fast sampling interval for the test
        mon.interval = 0.05
        mon.start()
        # block the loop longer than the threshold (a long_schedule event)
        await asyncio.sleep(0.06)  # let the monitor take a timestamp
        time.sleep(0.2)  # synchronous block = loop lag
        await asyncio.sleep(0.15)
        assert mon.lag_events >= 1
        assert mon.overloaded  # shedding window active
        st = mon.status()
        assert st["overloaded"] and st["lag_events"] >= 1
        # a publish during overload is throttled, not rejected
        c = MQTTClient(s.host, s.port, client_id="shed")
        await c.connect()
        await c.subscribe("o/#", qos=0)
        t0 = time.monotonic()
        await c.publish("o/t", b"x", qos=0)
        msg = await c.recv(5.0)
        assert msg.payload == b"x"
        assert time.monotonic() - t0 >= 0.09  # the 0.1s shed delay applied
        await c.close()
    finally:
        await b.stop()
        await s.stop()


def test_sysmon_memory_watermark_forces_gc():
    class FakeMetrics:
        def __init__(self):
            self.counts = {}

        def incr(self, name, n=1):
            self.counts[name] = self.counts.get(name, 0) + n

    class FakeBroker:
        metrics = FakeMetrics()

    mon = Sysmon(FakeBroker(), memory_high_watermark=1)  # 1 byte → always over

    async def run_once():
        mon.interval = 0.01
        mon.start()
        await asyncio.sleep(0.05)
        mon.stop()

    asyncio.new_event_loop().run_until_complete(run_once())
    assert mon.gc_forced >= 1
    assert rss_bytes() > 0


@pytest.mark.asyncio
async def test_rate_limit_throttles_instead_of_closing():
    b, s = await start_broker(Config(systree_enabled=False, allow_anonymous=True,
                                     max_message_rate=2),
                              port=0, node_name="rl-node")
    try:
        c = MQTTClient(s.host, s.port, client_id="ratelimited")
        await c.connect()
        await c.subscribe("r/#", qos=0)
        t0 = time.monotonic()
        for i in range(4):
            await c.publish("r/t", str(i).encode(), qos=0)
        # all four eventually delivered — session survived, just slower
        got = [await c.recv(8.0) for _ in range(4)]
        assert [m.payload for m in got] == [b"0", b"1", b"2", b"3"]
        assert time.monotonic() - t0 >= 1.0  # at least one throttle pause
        assert b.metrics.value("mqtt_publish_throttled") >= 1
        await c.close()
    finally:
        await b.stop()
        await s.stop()


def test_crl_refresher_reloads_on_mtime_change(tmp_path):
    crl = tmp_path / "crl.pem"
    # self-signed CA cert is enough to exercise load_verify_locations
    crl.write_text(open("tests/ssl/ca.crt").read())

    ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)

    class FakeManager:
        def listener_records(self):
            return [{"kind": "mqtts", "opts": {"crl_file": str(crl)},
                     "ssl_context": ctx}]

    class FakeBroker:
        listeners = FakeManager()

    r = CrlRefresher(FakeBroker(), interval=999)
    assert r.refresh() == 1
    assert r.refresh() == 0  # unchanged mtime → no reload
    crl.write_text(open("tests/ssl/ca.crt").read())
    import os

    os.utime(crl, (time.time() + 5, time.time() + 5))
    assert r.refresh() == 1
    assert r.refreshes == 2
    assert ctx.verify_flags & ssl.VERIFY_CRL_CHECK_LEAF


@pytest.mark.asyncio
@pytest.mark.parametrize("generation,pause_s,frozen", [
    (2, 0.08, 1),    # a full pass that stopped every thread too long
    (2, 0.0, 0),     # a quick full pass: nothing to do
    (1, 0.08, 0),    # young generations never walk the long-lived heap
])
async def test_long_full_gc_freezes_its_survivors(monkeypatch, generation,
                                                  pause_s, frozen):
    """A full collector pass walks every tracked object with every
    thread stopped — seconds at a million subscriptions. One that paused
    past a fifth of the lag threshold has its survivors frozen out of
    later passes, on the loop, not from inside the collector."""
    import gc

    class FakeMetrics:
        counts = {}

        def incr(self, name, n=1):
            self.counts[name] = self.counts.get(name, 0) + n

    class FakeBroker:
        metrics = FakeMetrics()
        overload = None

    calls = []
    monkeypatch.setattr(gc, "freeze", lambda: calls.append("freeze"))
    monkeypatch.setattr(gc, "collect", lambda *a: calls.append("collect"))
    mon = Sysmon(FakeBroker(), lag_threshold=0.25)
    assert mon.gc_freeze_pause == pytest.approx(0.05)
    mon.start()
    try:
        assert mon._on_gc in gc.callbacks
        mon._on_gc("start", {"generation": generation})
        time.sleep(pause_s)
        mon._on_gc("stop", {"generation": generation})
        assert calls == []  # never from inside the collector
        await asyncio.sleep(0.01)
        assert calls == ["freeze", "collect"] * frozen
        assert mon.gc_freezes == frozen
        assert FakeBroker.metrics.counts.get("sysmon_long_gc", 0) == frozen
        assert mon.status()["gc_freezes"] == frozen
    finally:
        mon.stop()
    assert mon._on_gc not in gc.callbacks
