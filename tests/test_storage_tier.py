"""Million-offline-session storage tier (ISSUE 14): the unified
segment engine (storage/segment.py), the engine-generic msg store
facades, batched reconnect-storm resumption (storage/resume.py), the
budgeted compaction driver + store breaker, and the fsync group
commit."""

import asyncio
import os
import subprocess
import sys
import tempfile
import textwrap
import time

import pytest

from vernemq_tpu.broker.message import Msg
from vernemq_tpu.robustness import faults
from vernemq_tpu.storage.msg_store import (EngineMsgStore, FileMsgStore,
                                           SegmentMsgStore)
from vernemq_tpu.storage.resume import ResumeCollector
from vernemq_tpu.storage.segment import (MemEngine, SegmentLogEngine,
                                         open_engine)


def _msg(ref, payload=b"x", topic=("t", "a"), qos=1):
    return Msg(topic=topic, payload=payload, qos=qos,
               msg_ref=ref if isinstance(ref, bytes) else ref.encode())


# ----------------------------------------------------------- engine unit


def test_segment_engine_seal_scan_and_reopen(tmp_path):
    d = str(tmp_path / "eng")
    e = SegmentLogEngine(d, segment_max_bytes=300)
    for i in range(20):
        e.put_many([(b"k%02d" % i, b"v" * 40)])
    e.delete(b"k05")
    assert e.stats()["segments"] > 1  # sealed at least once
    assert e.get(b"k04") == b"v" * 40 and e.get(b"k05") is None
    assert [k for k in e.scan_keys(b"k0")] == \
        [b"k0%d" % i for i in range(10) if i != 5]
    e.close()
    e2 = SegmentLogEngine(d, segment_max_bytes=300)
    # clean close wrote a checkpoint: nothing replays on reopen
    assert e2.recover_replayed == 0 and e2.recover_fallbacks == 0
    assert e2.count() == 19 and e2.get(b"k19") == b"v" * 40
    e2.close()


def test_segment_engine_checkpoint_frontier_replay(tmp_path):
    """Recovery replays ONLY records past the checkpoint frontier —
    never the whole history (the million-session boot cost)."""
    d = str(tmp_path / "eng")
    e = SegmentLogEngine(d)
    e.put_many([(b"a%03d" % i, b"v") for i in range(500)])
    e.checkpoint()
    e.put_many([(b"post-1", b"x"), (b"post-2", b"y")])
    e.delete(b"a001")
    # crash: no close(), no fresh checkpoint
    e2 = SegmentLogEngine(d)
    assert e2.recover_replayed == 3  # 2 puts + 1 delete, NOT 500
    assert e2.get(b"post-2") == b"y" and e2.get(b"a001") is None
    assert e2.count() == 501
    e2.close()


def test_segment_engine_budgeted_compaction_reclaims(tmp_path):
    d = str(tmp_path / "eng")
    e = SegmentLogEngine(d, segment_max_bytes=400)
    for i in range(30):
        e.put_many([(b"k%02d" % i, b"v" * 50)])
    for i in range(0, 30, 2):
        e.delete(b"k%02d" % i)
    segs_before = e.stats()["segments"]
    garbage_before = e.garbage_bytes()
    assert garbage_before > 0
    # tiny budget: evacuation must take multiple steps (budgeted, not
    # stop-the-world) and eventually unlink victims
    steps = 0
    while steps < 200 and e.stats()["compactions"] < 2:
        e.compact_step(120)
        steps += 1
    assert steps > 2, "compaction finished suspiciously fast for budget"
    st = e.stats()
    assert st["compactions"] >= 2 and st["compacted_bytes"] > 0
    assert st["segments"] < segs_before
    # data intact through compaction + a crash-reopen
    assert sorted(e.scan_keys()) == sorted(
        b"k%02d" % i for i in range(1, 30, 2))
    e2 = SegmentLogEngine(d)
    assert sorted(e2.scan_keys()) == sorted(
        b"k%02d" % i for i in range(1, 30, 2))
    assert e2.get(b"k07") == b"v" * 50
    e2.close()
    e.close()


def test_segment_engine_corrupt_sealed_segment_skips(tmp_path):
    d = str(tmp_path / "eng")
    e = SegmentLogEngine(d, segment_max_bytes=256)
    for i in range(12):
        e.put_many([(b"k%02d" % i, b"v" * 40)])
    e.close()
    segs = sorted(f for f in os.listdir(d) if f.startswith("seg-"))
    assert len(segs) >= 3
    # corrupt a mid-file record of a SEALED (non-final) segment
    victim = os.path.join(d, segs[1])
    blob = open(victim, "rb").read()
    with open(victim, "wb") as fh:
        fh.write(blob[:10] + b"\xff" * 4 + blob[14:])
    os.unlink(os.path.join(d, "CHECKPOINT"))  # force the full scan
    e2 = SegmentLogEngine(d, segment_max_bytes=256)
    assert e2.recover_skipped >= 1
    # later segments' records still recovered
    assert e2.get(b"k11") == b"v" * 40
    e2.close()


def test_store_recover_fault_falls_back_to_full_scan(tmp_path):
    d = str(tmp_path / "eng")
    e = SegmentLogEngine(d)
    e.put_many([(b"a", b"1"), (b"b", b"2")])
    e.close()
    faults.install(faults.FaultPlan(
        [faults.FaultRule("store.recover", kind="error")], seed=3))
    try:
        e2 = SegmentLogEngine(d)
    finally:
        faults.clear()
    assert e2.recover_fallbacks == 1  # checkpoint load failed, injected
    assert e2.get(b"a") == b"1" and e2.get(b"b") == b"2"  # never lossy
    e2.close()


def test_kill9_mid_compaction_zero_acked_loss(tmp_path):
    """Acceptance: kill -9 mid-compaction loses zero acknowledged
    QoS>=1 messages. A child process commits (fsync) a message corpus,
    then compacts garbage in a tight loop; the parent SIGKILLs it
    mid-compaction and recovers the store."""
    d = str(tmp_path / "store")
    marker = str(tmp_path / "compacting")
    child = textwrap.dedent(f"""
        import os, sys, time
        sys.path.insert(0, {os.getcwd()!r})
        from vernemq_tpu.storage.msg_store import SegmentMsgStore
        from vernemq_tpu.broker.message import Msg
        st = SegmentMsgStore({d!r}, fsync=True,
                             segment_max_bytes=2048)
        # acked corpus: written AND fsynced (group commit flushed)
        for i in range(200):
            st.write(("", "keep%d" % (i % 20)), Msg(
                topic=("t", str(i)), payload=b"P%d" % i, qos=1,
                msg_ref=b"keep-%d" % i))
        st.commit()
        # garbage: written then deleted, so compaction has work
        for i in range(300):
            sid = ("", "junk%d" % (i % 10))
            st.write(sid, Msg(topic=("j", str(i)), payload=b"x" * 64,
                              qos=1, msg_ref=b"junk-%d" % i))
        for i in range(10):
            st.delete_all(("", "junk%d" % i))
        open({marker!r}, "w").close()
        while True:  # compact forever until SIGKILLed
            st.engine.compact_step(512)
            time.sleep(0.001)
    """)
    proc = subprocess.Popen([sys.executable, "-c", child])
    try:
        deadline = time.time() + 30
        while not os.path.exists(marker) and time.time() < deadline:
            time.sleep(0.02)
        assert os.path.exists(marker), "child never started compacting"
        time.sleep(0.15)  # let it get genuinely mid-compaction
    finally:
        proc.kill()
        proc.wait()
    st = SegmentMsgStore(d, segment_max_bytes=2048)
    for i in range(200):
        sid = ("", "keep%d" % (i % 20))
        msgs = st.read_all(sid)
        assert any(m.payload == b"P%d" % i for m in msgs), \
            f"acked message {i} lost after kill -9 mid-compaction"
    # the junk that was deleted must stay deleted
    assert st.read_all(("", "junk3")) == []
    st.close()


# ----------------------------------------------- facades share the engine


def test_engine_corpus_through_both_facades(tmp_path, monkeypatch):
    """Acceptance: spool and msg store demonstrably share the engine —
    the same SegmentLogEngine class serves both key families, with the
    same crash/recovery discipline, exercised by one corpus."""
    from vernemq_tpu.cluster import spool as spool_mod
    from vernemq_tpu.cluster.spool import ClusterSpool
    from vernemq_tpu.storage import segment as segment_mod

    # force the pure twin even where the native kvstore is built
    monkeypatch.setattr(
        segment_mod, "open_engine",
        lambda directory, filename="store", **kw: SegmentLogEngine(
            os.path.join(directory, filename + ".seg")))

    store = SegmentMsgStore(str(tmp_path / "ms"))
    sp = ClusterSpool(str(tmp_path / "sp"))
    assert type(store.engine) is SegmentLogEngine
    assert type(sp.engine) is SegmentLogEngine
    assert sp.engine_kind == store.engine_kind == "segment"

    # one corpus: N items written through each facade, some retired
    for i in range(40):
        store.write(("", "c%d" % (i % 8)), _msg("r%d" % i, b"m%d" % i))
        sp.journal("peer%d" % (i % 3), "msg", {"ref": b"r%d" % i})
    for i in range(0, 40, 4):
        store.delete(("", "c%d" % (i % 8)), b"r%d" % i)
    sp.ack("peer0", 5)  # cumulative trim through the spool facade

    # crash both (no close) and recover through fresh facades
    store2 = SegmentMsgStore(str(tmp_path / "ms"))
    sp2 = ClusterSpool(str(tmp_path / "sp"))
    remaining = sum(len(store2.read_all(("", "c%d" % c)))
                    for c in range(8))
    assert remaining == 30
    st0 = sp2.state("peer0")
    assert len(st0.pending) == 14 - 5  # 14 journaled, 5 acked away
    assert st0.next_seq == 15
    store2.close()
    sp2.close()


def test_open_engine_fallback_chain(tmp_path, monkeypatch):
    from vernemq_tpu.storage import segment as segment_mod

    assert isinstance(open_engine(""), MemEngine)
    # native unavailable -> segment twin, same interface
    monkeypatch.setattr(
        segment_mod.NativeEngine, "__init__",
        lambda self, *a, **k: (_ for _ in ()).throw(RuntimeError("no")))
    eng = open_engine(str(tmp_path), filename="x")
    assert isinstance(eng, SegmentLogEngine)
    eng.put_many([(b"k", b"v")])
    assert eng.get(b"k") == b"v"
    eng.close()


# ------------------------------------------- refcounting through recovery


@pytest.mark.parametrize("kind", ["segment", "native"])
def test_cross_subscriber_refcount_through_recovery(tmp_path, kind):
    """Satellite: two sids share one payload ref, crash, recover,
    delete from one sid — the payload must survive until the second
    delete (only the happy path was covered before)."""
    d = str(tmp_path / "store")
    if kind == "segment":
        mk = lambda: SegmentMsgStore(d)
    else:
        from vernemq_tpu.native.kvstore import available
        from vernemq_tpu.storage.msg_store import NativeMsgStore

        if not available():
            pytest.skip("native kvstore not built")
        mk = lambda: NativeMsgStore(d)
    st = mk()
    shared = _msg(b"shared-ref", b"the-payload")
    st.write(("", "s1"), shared)
    st.write(("", "s2"), shared)
    # crash (no close) and recover: refcount rebuilt from the i family
    st2 = mk()
    st2.delete(("", "s1"), b"shared-ref")
    msgs = st2.read_all(("", "s2"))
    assert [m.payload for m in msgs] == [b"the-payload"], \
        "payload freed while the second subscriber still owed a copy"
    assert st2.engine.get(b"m\x00shared-ref") is not None
    st2.delete(("", "s2"), b"shared-ref")
    assert st2.read_all(("", "s2")) == []
    assert st2.engine.get(b"m\x00shared-ref") is None  # last ref frees
    # ...and that survives one more recovery
    st3 = mk()
    assert st3.read_all(("", "s1")) == [] and st3.read_all(("", "s2")) == []
    st3.close()
    st2.close()
    st.close()


# ------------------------------------------------------ fsync group commit


def test_group_commit_coalesces_fsync(tmp_path):
    """Satellite: with fsync on, a write burst costs ONE engine sync at
    the commit boundary, not one per record — in both the segment-
    backed store and the legacy file store."""
    st = SegmentMsgStore(str(tmp_path / "a"), fsync=True)
    syncs = []
    orig = st.engine.sync
    st.engine.sync = lambda: (syncs.append(1), orig())[1]
    for i in range(7):
        st.write(("", "c"), _msg("r%d" % i))
    assert syncs == [] and st.needs_commit()
    assert st.commit() == 6  # 7 writes, 1 sync -> 6 coalesced
    assert len(syncs) == 1 and not st.needs_commit()
    assert st.commit() == 0 and len(syncs) == 1
    st.close()

    fs = FileMsgStore(str(tmp_path / "b"), fsync=True)
    for i in range(5):
        fs.write(("", "c"), _msg("f%d" % i))
    assert fs.needs_commit() and fs.commit() == 4
    fs.close()
    # group_commit off: the legacy per-write fsync posture
    st2 = SegmentMsgStore(str(tmp_path / "c"), fsync=True,
                          group_commit=False)
    st2.write(("", "c"), _msg("z"))
    assert not st2.needs_commit() and st2.commit() == 0
    st2.close()


@pytest.mark.asyncio
async def test_broker_group_commit_metric(tmp_path):
    from vernemq_tpu.broker.config import Config
    from vernemq_tpu.broker.server import start_broker

    cfg = Config(systree_enabled=False, allow_anonymous=True,
                 message_store="file",
                 message_store_dir=str(tmp_path / "ms"),
                 msg_store_fsync=True)
    broker, server = await start_broker(cfg, port=0)
    try:
        for i in range(6):
            broker.store_offline(("", "gc"), _msg("g%d" % i))
        # the commit landed via call_soon at the flush-tick boundary
        await asyncio.sleep(0)
        await asyncio.sleep(0)
        assert broker.metrics.value("msg_store_fsync_coalesced") == 5
        assert broker.metrics.value("msg_store_ops_write") == 6
    finally:
        await broker.stop()
        await server.stop()


# --------------------------------------------------- resume collector unit


class _FakeStore:
    supports_batched_read = True

    def __init__(self, data, block=None):
        self.data = data
        self.block = block
        self.read_many_calls = []
        self.read_all_calls = []

    def read_many(self, sids):
        if self.block is not None:
            self.block.wait(10)
        self.read_many_calls.append(list(sids))
        return {sid: self.data.get(sid, []) for sid in sids}

    def read_all(self, sid):
        self.read_all_calls.append(sid)
        return self.data.get(sid, [])


@pytest.mark.asyncio
async def test_resume_collector_coalesces_into_one_read():
    data = {("", "c%d" % i): [_msg("r%d" % i, b"p%d" % i)]
            for i in range(10)}
    store = _FakeStore(data)
    coll = ResumeCollector(store, window_us=2000, max_batch=64,
                           host_threshold=4)
    futs = [coll.submit(sid) for sid in data]
    results = await asyncio.gather(*futs)
    assert len(store.read_many_calls) == 1
    assert sorted(store.read_many_calls[0]) == sorted(data)
    assert store.read_all_calls == []
    for sid, msgs in zip(data, results):
        assert [m.payload for m in msgs] == \
            [m.payload for m in data[sid]]
    assert coll.batched_sessions == 10 and coll.batched_reads == 1
    coll.close()


@pytest.mark.asyncio
async def test_resume_collector_host_threshold_hybrid():
    data = {("", "a"): [_msg("r1")], ("", "b"): []}
    store = _FakeStore(data)
    coll = ResumeCollector(store, window_us=500, host_threshold=4)
    r = await asyncio.gather(coll.submit(("", "a")),
                             coll.submit(("", "b")))
    assert store.read_many_calls == []  # sub-threshold: loop-side reads
    assert len(store.read_all_calls) == 2
    assert len(r[0]) == 1 and r[1] == []
    assert coll.host_sessions == 2
    coll.close()


@pytest.mark.asyncio
async def test_resume_collector_expiry_exact_fallback():
    import threading

    block = threading.Event()
    data = {("", "c%d" % i): [_msg("e%d" % i)] for i in range(12)}
    store = _FakeStore(data, block=block)
    coll = ResumeCollector(store, window_us=200, max_batch=6,
                           host_threshold=2, item_expiry_ms=150)
    try:
        futs = [coll.submit(sid) for sid in data]
        # first batch of 6 wedges in the blocked read; the queued rest
        # must settle from the exact per-session fallback at expiry
        done, _ = await asyncio.wait(futs, timeout=3.0)
        assert coll.expired_sessions >= 1
        settled = [f for f in futs if f.done()]
        assert len(settled) >= 6
        for f in settled:
            assert len(f.result()) == 1
    finally:
        block.set()
        await asyncio.sleep(0.05)
        coll.close()


@pytest.mark.asyncio
async def test_resume_collector_defer_gate_bounded():
    data = {("", "c%d" % i): [] for i in range(8)}
    store = _FakeStore(data)
    coll = ResumeCollector(store, window_us=100, host_threshold=2)
    coll.defer_gate = lambda: True  # pinned L2+: always defer
    futs = [coll.submit(sid) for sid in data]
    await asyncio.wait_for(asyncio.gather(*futs), timeout=5.0)
    # deferral is BOUNDED: a pinned gate cannot starve resumes forever
    assert 1 <= coll.deferred_flushes <= coll.MAX_DEFERS
    coll.close()


@pytest.mark.asyncio
async def test_resume_collector_failed_batch_falls_back():
    class _Boom(_FakeStore):
        def read_many(self, sids):
            raise RuntimeError("disk gone")

    data = {("", "c%d" % i): [_msg("f%d" % i)] for i in range(6)}
    store = _Boom(data)
    coll = ResumeCollector(store, window_us=100, host_threshold=2)
    results = await asyncio.gather(*[coll.submit(s) for s in data])
    assert all(len(r) == 1 for r in results)  # exact fallback served
    assert coll.fallback_sessions == 6
    coll.close()


# ------------------------------------------------- queue resume ordering


@pytest.mark.asyncio
async def test_queue_parks_live_publishes_during_resume():
    from vernemq_tpu.broker.config import Config
    from vernemq_tpu.broker.server import start_broker

    cfg = Config(systree_enabled=False, allow_anonymous=True)
    broker, server = await start_broker(cfg, port=0)
    try:
        from vernemq_tpu.broker.queue import QueueOpts, SubscriberQueue

        q = SubscriberQueue(broker, ("", "qq"),
                            QueueOpts(clean_session=False))
        got = []
        q.add_session(object(), lambda m: (got.append(m.payload), True)[1])
        q.begin_resume()
        q.enqueue(_msg("live1", b"live1"))  # parked: resume in flight
        q.enqueue(_msg("live2", b"live2"))
        assert got == []
        q.finish_resume([_msg("old1", b"old1"), _msg("old2", b"old2")])
        assert got == [b"old1", b"old2", b"live1", b"live2"]
        # after the window, delivery is direct again
        q.enqueue(_msg("live3", b"live3"))
        assert got[-1] == b"live3"
    finally:
        await broker.stop()
        await server.stop()


@pytest.mark.asyncio
async def test_queue_resume_detach_midflight_keeps_order():
    from vernemq_tpu.broker.config import Config
    from vernemq_tpu.broker.server import start_broker

    cfg = Config(systree_enabled=False, allow_anonymous=True)
    broker, server = await start_broker(cfg, port=0)
    try:
        from vernemq_tpu.broker.queue import QueueOpts, SubscriberQueue

        q = SubscriberQueue(broker, ("", "dq"),
                            QueueOpts(clean_session=False))
        h = object()
        q.add_session(h, lambda m: True)
        q.begin_resume()
        q.enqueue(_msg("new1", b"new1"))  # parked behind the resume
        q.del_session(h)                  # detach mid-resume
        q.finish_resume([_msg("old1", b"old1")])
        # stored (older) message sits in FRONT of the parked one
        assert [m.payload for m in q.offline] == [b"old1", b"new1"]
        got = []
        q.add_session(object(),
                      lambda m: (got.append(m.payload), True)[1])
        assert got == [b"old1", b"new1"]
    finally:
        await broker.stop()
        await server.stop()


# ------------------------------------------------------------ broker e2e


@pytest.mark.asyncio
async def test_reconnect_storm_batched_resume_e2e(tmp_path):
    """Restart + reconnect storm: persistent sessions' stored backlogs
    replay through the batched collector with per-session order intact
    and zero QoS1 loss."""
    from vernemq_tpu.broker.config import Config
    from vernemq_tpu.broker.server import start_broker
    from vernemq_tpu.client import MQTTClient

    cfg = dict(systree_enabled=False, allow_anonymous=True,
               message_store="file",
               message_store_dir=str(tmp_path / "ms"),
               metadata_persistence=True,
               metadata_dir=str(tmp_path / "meta"),
               resume_window_us=20_000)
    broker, server = await start_broker(Config(**cfg), port=0)
    n = 12
    for i in range(n):
        c = MQTTClient("127.0.0.1", server.port, client_id=f"s{i}",
                       clean_start=False)
        await c.connect()
        await c.subscribe(f"st/{i}", qos=1)
        await c.disconnect()
    pub = MQTTClient("127.0.0.1", server.port, client_id="pub")
    await pub.connect()
    for i in range(n):
        for j in range(3):
            await pub.publish(f"st/{i}", b"m%d" % j, qos=1)
    await pub.disconnect()
    await asyncio.sleep(0.2)
    await broker.stop()
    await server.stop()

    broker2, server2 = await start_broker(Config(**cfg), port=0)
    try:
        # lazy boot: no queue loaded its backlog yet
        q0 = broker2.registry.queues.get(("", "s0"))
        assert q0 is not None and q0.offline_in_store \
            and len(q0.offline) == 0
        clients = [MQTTClient("127.0.0.1", server2.port,
                              client_id=f"s{i}", clean_start=False)
                   for i in range(n)]
        await asyncio.gather(*[c.connect() for c in clients])
        for i, c in enumerate(clients):
            for j in range(3):
                m = await c.recv(10)
                assert m.payload == b"m%d" % j, \
                    f"session {i} got {m.payload} at position {j}"
        # no duplicates
        with pytest.raises(asyncio.TimeoutError):
            await clients[0].recv(0.3)
        coll = broker2._resume_collector
        assert coll is not None
        st = coll.stats()
        assert st["resume_batched_sessions"] + \
            st["resume_host_sessions"] + st["resume_expired_sessions"] \
            == n
        assert st["resume_batched_sessions"] > 0  # the storm coalesced
        am = broker2.metrics.all_metrics()
        assert am.get("stage_resume_replay_ms_count", 0) >= 1
        await asyncio.gather(*[c.disconnect() for c in clients])
    finally:
        await broker2.stop()
        await server2.stop()


@pytest.mark.asyncio
async def test_store_compact_fault_drill_append_only(tmp_path):
    """Acceptance: a store.compact fault drill degrades to append-only
    (compaction paused, counter incremented) without touching
    delivery."""
    from vernemq_tpu.broker.config import Config
    from vernemq_tpu.broker.server import start_broker
    from vernemq_tpu.client import MQTTClient

    cfg = Config(systree_enabled=False, allow_anonymous=True,
                 message_store="file",
                 message_store_dir=str(tmp_path / "ms"),
                 store_compact_interval_ms=0)  # we drive ticks by hand
    broker, server = await start_broker(cfg, port=0)
    try:
        faults.install(faults.FaultPlan(
            [faults.FaultRule("store.compact", kind="error")], seed=5))
        for _ in range(3):  # failure_threshold default 3
            await broker.store_maintain_once()
        assert broker.store_breaker.state_name == "open"
        paused_tick = await broker.store_maintain_once()
        assert paused_tick == 0
        assert broker.metrics.value("store_compact_paused") >= 1
        assert broker.metrics.value("store_compact_errors") >= 3

        # delivery untouched while append-only: a live QoS1 round trip
        sub = MQTTClient("127.0.0.1", server.port, client_id="dsub")
        await sub.connect()
        await sub.subscribe("drill/#", qos=1)
        pub = MQTTClient("127.0.0.1", server.port, client_id="dpub")
        await pub.connect()
        await pub.publish("drill/x", b"through", qos=1)
        m = await sub.recv(5)
        assert m.payload == b"through"
        await sub.disconnect()
        await pub.disconnect()

        # drill ends: the half-open probe resumes compaction
        faults.clear()
        await asyncio.sleep(broker.store_breaker.backoff_initial * 2.5)
        await broker.store_maintain_once()
        assert broker.store_breaker.state_name == "closed"
    finally:
        faults.clear()
        await broker.stop()
        await server.stop()


@pytest.mark.asyncio
async def test_store_admin_and_breaker_surface(tmp_path):
    from vernemq_tpu.admin.commands import (CommandRegistry,
                                            register_core_commands)
    from vernemq_tpu.broker.config import Config
    from vernemq_tpu.broker.server import start_broker

    cfg = Config(systree_enabled=False, allow_anonymous=True,
                 message_store="file",
                 message_store_dir=str(tmp_path / "ms"))
    broker, server = await start_broker(cfg, port=0)
    try:
        reg = register_core_commands(CommandRegistry())
        show = reg.run(broker, ["store", "show"])
        assert show["table"][0]["kind"] == "segment"
        assert show["breaker"] == "closed"
        rows = reg.run(broker, ["breaker", "show"])["table"]
        assert any(r["path"] == "store" for r in rows)
        # trip pins append-only; reset recovers
        reg.run(broker, ["breaker", "trip", "path=store"])
        assert await broker.store_maintain_once() == 0
        assert broker.metrics.value("store_compact_paused") >= 1
        reg.run(broker, ["breaker", "reset", "path=store"])
        assert broker.store_breaker.state_name == "closed"
        out = reg.run(broker, ["store", "compact"])
        assert "scheduled" in out
        await asyncio.sleep(0.05)  # let the scheduled pass run
    finally:
        await broker.stop()
        await server.stop()


@pytest.mark.asyncio
async def test_lazy_boot_no_double_delivery_of_parked_publish(tmp_path):
    """Review regression: a publish arriving while a lazily-booted
    queue is parked lands in BOTH the offline deque and the store; the
    recover merge must dedup, or the reconnect delivers it twice."""
    from vernemq_tpu.broker.config import Config
    from vernemq_tpu.broker.queue import QueueOpts
    from vernemq_tpu.broker.server import start_broker

    cfg = Config(systree_enabled=False, allow_anonymous=True,
                 message_store="file",
                 message_store_dir=str(tmp_path / "ms"))
    broker, server = await start_broker(cfg, port=0)
    try:
        sid = ("", "dd")
        # stored backlog from "before the restart"
        broker.msg_store.write(sid, _msg("old-1", b"old-1"))
        q = broker.registry._start_queue(sid,
                                         QueueOpts(clean_session=False))
        broker.recover_offline(sid, q, lazy=True)
        assert q.offline_in_store and len(q.offline) == 0
        # a live publish lands while parked: deque AND store hold it
        q.enqueue(_msg("new-1", b"new-1"))
        assert len(q.offline) == 1
        got = []
        q.add_session(object(),
                      lambda m: (got.append(m.payload), True)[1])
        for _ in range(100):
            if len(got) >= 2 and not q._resuming:
                break
            await asyncio.sleep(0.01)
        assert got == [b"old-1", b"new-1"], got  # once each, in order
    finally:
        await broker.stop()
        await server.stop()


@pytest.mark.asyncio
async def test_drain_supersedes_inflight_resume(tmp_path):
    """Review regression: a migration drain during an in-flight
    batched resume must collect the STORED backlog too — the late
    collector read becomes a no-op."""
    from vernemq_tpu.broker.config import Config
    from vernemq_tpu.broker.queue import QueueOpts
    from vernemq_tpu.broker.server import start_broker

    cfg = Config(systree_enabled=False, allow_anonymous=True,
                 message_store="file",
                 message_store_dir=str(tmp_path / "ms"))
    broker, server = await start_broker(cfg, port=0)
    try:
        sid = ("", "dr")
        broker.msg_store.write(sid, _msg("st-1", b"st-1"))
        broker.msg_store.write(sid, _msg("st-2", b"st-2"))
        q = broker.registry._start_queue(sid,
                                         QueueOpts(clean_session=False))
        q.add_session(object(), lambda m: True)
        q.begin_resume()          # collector read "in flight"
        q.enqueue(_msg("live", b"live"))  # parked behind it
        drained = q.start_drain()
        payloads = [m.payload for m in drained]
        assert b"st-1" in payloads and b"st-2" in payloads \
            and b"live" in payloads
        # the late-landing read is a no-op: nothing doubles
        q.finish_resume([_msg("st-1", b"st-1"), _msg("st-2", b"st-2")])
        assert q.drain_pending() == []
    finally:
        await broker.stop()
        await server.stop()


def test_empty_checkpoint_reopens_clean(tmp_path):
    """Review regression: a drained store's empty-index checkpoint (the
    common clean state) must load — not alarm recover_fallbacks and pay
    the full scan on every reopen."""
    d = str(tmp_path / "eng")
    e = SegmentLogEngine(d)
    e.put_many([(b"k", b"v")])
    e.delete(b"k")
    e.close()  # checkpoint with ZERO index entries
    e2 = SegmentLogEngine(d)
    assert e2.recover_fallbacks == 0 and e2.recover_replayed == 0
    assert e2.count() == 0
    e2.close()


def test_sync_covers_sealed_segments(tmp_path, monkeypatch):
    """Review regression: a group commit must fsync segments SEALED
    since the last sync too — records written just before a roll were
    only page-cache durable, a hole exactly at every seal boundary."""
    d = str(tmp_path / "eng")
    e = SegmentLogEngine(d, segment_max_bytes=300)
    synced = []
    real_fsync = os.fsync
    monkeypatch.setattr(os, "fsync",
                        lambda fd: (synced.append(fd), real_fsync(fd))[1])
    for i in range(12):  # spans several seals
        e.put_many([(b"k%02d" % i, b"v" * 40)])
    sealed = len(e._sealed_unsynced)
    assert sealed >= 2
    e.sync()
    assert len(synced) == sealed + 1  # every sealed tail + the active
    assert e._sealed_unsynced == []
    synced.clear()
    e.sync()  # nothing newly sealed: one fsync only
    assert len(synced) == 1
    e.close()


def test_compact_step_concurrent_callers_serialized(tmp_path):
    """Review regression: the periodic tick and an admin-triggered pass
    must not race the shared evacuation state — the second concurrent
    caller no-ops."""
    import threading

    d = str(tmp_path / "eng")
    e = SegmentLogEngine(d, segment_max_bytes=300)
    for i in range(30):
        e.put_many([(b"k%02d" % i, b"v" * 50)])
    for i in range(0, 30, 2):
        e.delete(b"k%02d" % i)
    results = []
    gate = threading.Barrier(2)

    def run():
        gate.wait()
        total = 0
        for _ in range(50):
            total += e.compact_step(200)
        results.append(total)

    ts = [threading.Thread(target=run) for _ in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    # data intact, counters sane (no double completion of one victim):
    # every counted compaction corresponds to a real unlink
    assert sorted(e.scan_keys()) == sorted(
        b"k%02d" % i for i in range(1, 30, 2))
    st = e.stats()
    n_files = len([f for f in os.listdir(d) if f.startswith("seg-")])
    assert st["compactions"] >= 1
    assert st["compactions"] == e._active - n_files
    e.close()
    e2 = SegmentLogEngine(d)
    assert sorted(e2.scan_keys()) == sorted(
        b"k%02d" % i for i in range(1, 30, 2))
    e2.close()


def test_spool_legacy_file_journal_migrates(tmp_path):
    """Review regression: a pre-unification _FileJournal spool.log
    still holding unacked frames migrates into the segment engine
    (same record framing) instead of being silently orphaned."""
    import struct as _struct

    from vernemq_tpu.cluster.spool import ClusterSpool

    d = str(tmp_path / "spool")
    os.makedirs(d)
    # a legacy journal written by the old _FileJournal: one pending
    # frame for peer "p" at seq 1 plus its high-water key
    def rec(k, v):
        return (b"P" + _struct.pack(">I", len(k)) + k
                + _struct.pack(">I", len(v)) + v)

    pk = len(b"p").to_bytes(2, "big") + b"p"
    with open(os.path.join(d, "spool.log"), "wb") as fh:
        fh.write(rec(b"s" + pk + (1).to_bytes(8, "big"), b"frame-bytes"))
        fh.write(rec(b"h" + pk, (1).to_bytes(8, "big")))
    sp = ClusterSpool(d)
    assert sp.engine_kind == "segment"
    assert not os.path.exists(os.path.join(d, "spool.log"))
    st = sp.state("p")
    assert list(st.pending) == [1] and st.next_seq == 2
    sp.close()
    # and it KEEPS serving from the segment layout on the next open
    sp2 = ClusterSpool(d)
    assert sp2.engine_kind == "segment"
    assert list(sp2.state("p").pending) == [1]
    sp2.close()


# ------------------------------------------- TTL sweep + bucket index


def test_sweep_expired_deletes_parked_copies(tmp_path):
    """The budgeted TTL sweep removes every parked copy whose v5
    message-expiry deadline passed — across subscribers sharing the
    payload — and leaves unexpired and no-expiry messages alone."""
    import time as _time

    s = SegmentMsgStore(str(tmp_path / "ttl"))
    dead = _msg(b"dead-ref")
    dead.expires_at = _time.monotonic() - 1.0
    live = _msg(b"live-ref")
    live.expires_at = _time.monotonic() + 3600.0
    forever = _msg(b"keep-ref")
    s.write(("", "a"), dead)
    s.write(("", "b"), dead)
    s.write(("", "a"), live)
    s.write(("", "b"), forever)
    assert s.sweep_expired() == 2  # both parked copies of `dead`
    assert [m.msg_ref for m in s.read_all(("", "a"))] == [b"live-ref"]
    assert [m.msg_ref for m in s.read_all(("", "b"))] == [b"keep-ref"]
    assert s.sweep_expired() == 0  # idempotent once drained
    s.close()


def test_sweep_expired_classifies_recovered_refs_budgeted(tmp_path):
    """Refs recovered from disk carry no in-memory deadline: the sweep
    classifies at most ``budget`` per call (one point-get each), so a
    reopened store converges over ticks instead of stalling one."""
    import time as _time

    d = str(tmp_path / "ttl2")
    s = SegmentMsgStore(d)
    for i in range(6):
        m = _msg(b"r%d" % i)
        m.expires_at = _time.monotonic() - 1.0
        s.write(("", "x"), m)
    s.close()
    s2 = SegmentMsgStore(d)
    assert len(s2._exp_scan) == 6 and not s2._exp
    total = 0
    rounds = 0
    while s2._exp_scan:
        total += s2.sweep_expired(budget=2)
        rounds += 1
    total += s2.sweep_expired(budget=2)
    assert rounds == 3  # 6 refs / budget 2
    assert total == 6
    assert s2.read_all(("", "x")) == []
    s2.close()


def test_bucketed_probe_index_hits_and_misses(tmp_path):
    """The sid→bucket membership index: reads probe only member
    buckets (counted hits), a membership emptied behind the index's
    back (the per-bucket TTL sweep) is a counted miss and is cleaned,
    and reopen rebuilds the index from the recovery maps."""
    import time as _time

    from vernemq_tpu.storage.msg_store import BucketedMsgStore

    d = str(tmp_path / "buck")
    s = BucketedMsgStore(d, instances=4)
    sid = ("", "storm-client")
    for i in range(8):
        s.write(sid, _msg(b"bk-%d" % i))
    members = set(s._sid_buckets[sid])
    assert members == {s._bucket_idx(b"bk-%d" % i) for i in range(8)}
    assert [m.msg_ref for m in s.read_all(sid)] == \
        [b"bk-%d" % i for i in range(8)]
    assert s.probe_hits == len(members) and s.probe_misses == 0
    # unknown sid: no members, no probes at all
    assert s.read_all(("", "nobody")) == []
    assert s.probe_misses == 0
    # expire everything in ONE bucket behind the index's back
    victim = next(iter(members))
    doomed = _msg(b"doom")
    doomed.expires_at = _time.monotonic() - 1.0
    s.instances[victim].delete_all(sid)
    assert s.read_all(sid)  # survivors still served
    assert s.probe_misses == 1  # the emptied bucket was a counted miss
    assert victim not in s._sid_buckets[sid]  # ...and cleaned
    st = s.stats()
    assert st["bucket_probe_hits"] == s.probe_hits
    assert st["bucket_probe_misses"] == 1
    assert st["bucket_index_sids"] == 1
    s.close()
    s2 = BucketedMsgStore(d, instances=4)
    assert set(s2._sid_buckets[sid]) == members - {victim}
    assert len(s2.read_all(sid)) == 8 - \
        sum(1 for i in range(8)
            if s._bucket_idx(b"bk-%d" % i) == victim)
    s2.close()


@pytest.mark.asyncio
async def test_maintenance_tick_sweeps_ttl_and_drains_probe_counters(
        tmp_path):
    """Broker integration for the TTL sweep and the bucket-probe
    counters: the store maintenance tick deletes expired parked
    messages (msg_store_expired_swept) and drains the bucketed store's
    probe hit/miss counts into the metric surface."""
    import time as _time

    from vernemq_tpu.broker.config import Config
    from vernemq_tpu.broker.server import start_broker
    from vernemq_tpu.storage.msg_store import BucketedMsgStore

    cfg = Config(systree_enabled=False, allow_anonymous=True,
                 message_store="native", msg_store_instances=3,
                 message_store_dir=str(tmp_path / "ms"),
                 store_compact_interval_ms=0)  # ticks driven by hand
    broker, server = await start_broker(cfg, port=0)
    try:
        if not isinstance(broker.msg_store, BucketedMsgStore):
            pytest.skip("native store engine not available")
        sid = ("", "parked-client")
        gone = _msg(b"ttl-gone")
        gone.expires_at = _time.monotonic() - 1.0
        broker.msg_store.write(sid, gone)
        broker.msg_store.write(sid, _msg(b"ttl-kept"))
        assert len(broker.msg_store.read_all(sid)) == 2  # counts probes
        await broker.store_maintain_once()
        assert broker.metrics.value("msg_store_expired_swept") == 1
        assert broker.metrics.value("store_bucket_probe_hits") >= 1
        # drain is delta-based: a quiet tick (no reads between) adds
        # nothing
        hits = broker.metrics.value("store_bucket_probe_hits")
        await broker.store_maintain_once()
        assert broker.metrics.value("store_bucket_probe_hits") == hits
        assert [m.msg_ref for m in broker.msg_store.read_all(sid)] == \
            [b"ttl-kept"]
    finally:
        await broker.stop()
        await server.stop()


@pytest.mark.asyncio
async def test_bootstrap_streams_50k_subscriptions_no_record_graph(
        tmp_path):
    """Boot-time regression at 50k stored subscriptions: the registry
    warm-load streams raw terms into trie rows — ZERO SubscriberRecord
    materialisations, plain SubOpts shapes interned to a handful of
    shared objects (not one per subscription) — and persistent
    sessions still get their lazy offline queues."""
    import time as _time

    from vernemq_tpu.broker import subscriber_db as sdb
    from vernemq_tpu.broker.config import Config
    from vernemq_tpu.broker.server import start_broker
    from vernemq_tpu.protocol.types import SubOpts

    n = 50_000
    cfg = dict(systree_enabled=False, allow_anonymous=True,
               metadata_dir=str(tmp_path / "meta"),
               metadata_persistence=True,
               message_store="file",
               message_store_dir=str(tmp_path / "ms"))
    b1, s1 = await start_broker(Config(**cfg), port=0,
                                node_name="boot50k")
    node = b1.registry.node_name
    for i in range(n):
        b1.registry.db.store(
            ("", "c%d" % i),
            sdb.SubscriberRecord(node, clean_session=(i % 500 != 0),
                                 subs={("t", str(i)):
                                       SubOpts(qos=i % 2)}))
    await b1.stop()
    await s1.stop()

    counts = {"records": 0, "opts": 0}
    from_term = sdb.SubscriberRecord.from_term.__func__
    opts_init = SubOpts.__init__

    def counting_from_term(cls, t):
        counts["records"] += 1
        return from_term(cls, t)

    def counting_opts(self, *a, **k):
        counts["opts"] += 1
        return opts_init(self, *a, **k)

    sdb.SubscriberRecord.from_term = classmethod(counting_from_term)
    SubOpts.__init__ = counting_opts
    t0 = _time.perf_counter()
    try:
        b2, s2 = await start_broker(Config(**cfg), port=0,
                                    node_name="boot50k")
    finally:
        boot_s = _time.perf_counter() - t0
        sdb.SubscriberRecord.from_term = classmethod(from_term)
        SubOpts.__init__ = opts_init
    try:
        assert counts["records"] == 0  # no record-object graph at boot
        assert counts["opts"] <= 16    # interned shapes, not 50k opts
        assert boot_s < 60.0, boot_s   # ~3.5s on the 1-core smoke box
        assert len(list(b2.registry.trie("").match(["t", "7"]))) == 1
        # the 100 persistent sessions got lazy offline queues
        assert len(b2.registry.queues) == n // 500
    finally:
        await b2.stop()
        await s2.stop()
