"""Durable cross-node delivery: the store-and-forward spool
(cluster/spool.py), its wire protocol (msq/ack + hlo capability
negotiation), the receiver dedup window, crash-restart replay from disk,
and the satellite hardening (drop-accounting split, FileMsgStore
recovery, journal torn-tail discipline)."""

import asyncio
import os

import pytest

from test_cluster import (  # shared multi-node harness (tests dir on path)
    connected,
    heal,
    partition,
    start_node,
    stop_cluster,
    wait_until,
)
from vernemq_tpu.broker.metrics import Metrics
from vernemq_tpu.cluster.spool import ClusterSpool
from vernemq_tpu.storage.segment import SegmentLogEngine
from vernemq_tpu.robustness import faults


# ----------------------------------------------------------- spool units


def test_spool_journal_ack_delete(tmp_path):
    """journal → ack → delete: cumulative acks trim the journal; the
    byte accounting and per-peer seq assignment hold."""
    sp = ClusterSpool(str(tmp_path / "sp"), metrics=Metrics())
    seq1, data1 = sp.journal("peerA", "msg", {"ref": b"r1", "x": 1})
    seq2, data2 = sp.journal("peerA", "msg", {"ref": b"r2", "x": 2})
    seqb, _ = sp.journal("peerB", "msg", {"ref": b"r3"})
    assert (seq1, seq2, seqb) == (1, 2, 1)  # per-peer seq spaces
    assert data1[:3] == b"msq"
    st = sp.state("peerA")
    assert list(st.pending) == [1, 2]
    assert sp.stats()["cluster_spool_depth_frames"] == 3
    assert sp.stats()["cluster_spool_depth_bytes"] == \
        len(data1) + len(data2) + sp.state("peerB").bytes

    assert sp.ack("peerA", 1) == 1
    assert list(st.pending) == [2]
    # replay declares the stream base, then resends exactly the unacked
    # frames in order
    sent = []
    assert sp.replay("peerA", lambda d: sent.append(d) or True) == 1
    assert sent[0][:3] == b"msb"
    assert sent[1:] == [data2]
    # cumulative ack covering everything drains the peer
    sp.ack("peerA", 99)
    assert not st.pending and not st.blocked
    assert sp.replay("peerA", lambda d: True) == 0
    sp.close()


def test_spool_budgeted_replay_cursor(tmp_path):
    """Cursor-based partial replay (the retransmit watchdog's mode): at
    most ``budget`` frames ship per call, the per-peer cursor resumes
    where the previous tick stopped, a completed sweep wraps back to the
    lowest pending seq, and an ack advancing past the cursor restarts
    the sweep at the new head — so a long storm pays linear wire cost
    per tick instead of re-shipping the whole journal."""
    sp = ClusterSpool(str(tmp_path / "sp"), metrics=Metrics())
    frames = {}
    for i in range(10):
        seq, data = sp.journal("p", "msg", {"ref": b"r%d" % i})
        frames[data] = seq

    def seqs_of(sent):
        assert sent[0][:3] == b"msb"
        return [frames[d] for d in sent[1:]]

    sent = []
    assert sp.replay("p", lambda d: sent.append(d) or True, budget=4) == 4
    assert seqs_of(sent) == [1, 2, 3, 4]
    assert sp.state("p").cursor == 5
    sent = []
    assert sp.replay("p", lambda d: sent.append(d) or True, budget=4) == 4
    assert seqs_of(sent) == [5, 6, 7, 8]
    sent = []
    assert sp.replay("p", lambda d: sent.append(d) or True, budget=4) == 2
    assert seqs_of(sent) == [9, 10]
    assert sp.state("p").cursor == 0  # sweep complete: wrap
    sent = []
    assert sp.replay("p", lambda d: sent.append(d) or True, budget=4) == 4
    assert seqs_of(sent) == [1, 2, 3, 4]  # nothing acked: head again
    # a cumulative ack past the cursor restarts at the new head
    sp.ack("p", 6)
    sent = []
    assert sp.replay("p", lambda d: sent.append(d) or True, budget=4) == 4
    assert seqs_of(sent) == [7, 8, 9, 10]
    # unbudgeted (channel-up) replay still ships the whole backlog
    sent = []
    assert sp.replay("p", lambda d: sent.append(d) or True) == 4
    assert seqs_of(sent) == [7, 8, 9, 10]
    # metrics counted every shipped frame
    assert sp.metrics.value("cluster_spool_replayed") == 4 + 4 + 2 + 4 + 4 + 4
    sp.close()


def test_spool_budgeted_replay_blocked_writer_pauses(tmp_path):
    """A send refusal (writer buffer full) mid-budget pauses the stream
    blocked and restarts the sweep at the head next time — never skips."""
    sp = ClusterSpool("", metrics=Metrics())
    for i in range(5):
        sp.journal("p", "msg", {"ref": b"r%d" % i})
    calls = []

    def flaky(d):
        calls.append(d)
        return len(calls) <= 3  # msb + 2 frames, then the buffer "fills"

    assert sp.replay("p", flaky, budget=10) == 2
    st = sp.state("p")
    assert st.blocked
    assert st.cursor == 0  # restart at the head, no skipped frames
    sent = []
    assert sp.replay("p", lambda d: sent.append(d) or True, budget=10) == 5
    assert not st.blocked
    sp.close()


def test_spool_crash_replay_and_seq_continuity(tmp_path):
    """A new spool over the same directory (sender crash/restart) sees
    the unacked frames; sequence numbers never regress even after a
    full ack emptied the journal (the high-water key)."""
    d = str(tmp_path / "sp")
    sp = ClusterSpool(d, metrics=Metrics())
    _, f1 = sp.journal("n2", "msg", {"ref": b"a"})
    _, f2 = sp.journal("n2", "enq", (0, ["", "cid"], [{"ref": b"b"}], False))
    sp.close()

    sp2 = ClusterSpool(d, metrics=Metrics())
    st = sp2.state("n2")
    assert list(st.pending) == [1, 2]
    sent = []
    assert sp2.replay("n2", lambda x: sent.append(x) or True) == 2
    assert sent[0][:3] == b"msb"  # stream base precedes the frames
    assert sent[1:] == [f1, f2]   # byte-identical replay, in order
    sp2.ack("n2", 2)
    sp2.close()

    sp3 = ClusterSpool(d, metrics=Metrics())
    assert not sp3.state("n2").pending
    seq, _ = sp3.journal("n2", "msg", {"ref": b"c"})
    assert seq == 3  # continues past the acked history
    sp3.close()


def test_spool_cap_and_fault_point(tmp_path):
    """Past the byte cap (QoS0 never enters the spool — shedding starts
    below it, at the writer) and under an injected ``cluster.spool``
    journal failure, frames are refused with accounting so the caller
    falls back to best-effort sends."""
    m = Metrics()
    sp = ClusterSpool("", max_bytes=200, metrics=m)
    assert sp.journal("p", "msg", {"ref": b"r", "pay": b"x" * 64}) is not None
    assert sp.journal("p", "msg", {"ref": b"r2", "pay": b"y" * 200}) is None
    assert m.value("cluster_spool_overflow") == 1

    faults.install(faults.FaultPlan(
        [faults.FaultRule("cluster.spool", kind="error")], seed=1))
    try:
        assert sp.journal("p", "msg", {"ref": b"r3"}) is None
    finally:
        faults.clear()
    assert m.value("cluster_spool_errors") == 1
    assert m.value("cluster_spool_journaled") == 1
    sp.close()


def test_file_journal_recovers_and_truncates_torn_tail(tmp_path):
    """The pure-Python journal fallback (now the shared segment-log
    engine, storage/segment.py): state rebuilds from the log and a torn
    tail (crash mid-append) truncates to the last whole record — the
    NativeMsgStore._recover discipline."""
    d = str(tmp_path / "spool.seg")
    j = SegmentLogEngine(d)
    j.put_many([(b"k1", b"v1"), (b"k2", b"v2"), (b"k3", b"v3")])
    j.delete(b"k2")
    j.close()
    seg = sorted(f for f in os.listdir(d) if f.startswith("seg-"))[-1]
    with open(os.path.join(d, seg), "ab") as fh:
        fh.write(b"P\x00\x00\x00\x05garb")  # truncated mid-record
    j2 = SegmentLogEngine(d)
    assert j2.scan() == [(b"k1", b"v1"), (b"k3", b"v3")]
    # the torn bytes are gone: appends after recovery stay parseable
    j2.put_many([(b"k4", b"v4")])
    j2.close()
    j3 = SegmentLogEngine(d)
    assert [k for k, _ in j3.scan()] == [b"k1", b"k3", b"k4"]
    j3.close()


# ------------------------------------------------- writer drop accounting


def test_drop_accounting_split_and_qos0_shedding():
    """Satellite: frames and bytes dropped are separate counters (the
    old code counted frames in one place and bytes in the other), and a
    full buffer sheds buffered QoS0 frames before refusing QoS>=1."""
    from vernemq_tpu.cluster.node import NodeWriter

    class FakeCluster:
        metrics = Metrics()

    fc = FakeCluster()
    w = NodeWriter(fc, "peer", ("127.0.0.1", 1), max_buffer_bytes=100)
    assert w.send_frame(b"a" * 80, sheddable=True) is True
    # non-sheddable frame evicts the buffered QoS0 frame to fit
    assert w.send_frame(b"b" * 80) is True
    assert w.dropped_frames == 1 and w.dropped_bytes == 80
    assert fc.metrics.value("cluster_frames_shed_qos0") == 1
    assert fc.metrics.value("cluster_frames_dropped") == 1
    assert fc.metrics.value("cluster_bytes_dropped") == 80
    # nothing sheddable left: the next overflow drops the NEW frame
    assert w.send_frame(b"c" * 80) is False
    assert w.dropped_frames == 2 and w.dropped_bytes == 160
    assert fc.metrics.value("cluster_frames_dropped") == 2
    assert fc.metrics.value("cluster_bytes_dropped") == 160
    assert w._buf_bytes == 80  # the QoS>=1 frame kept its seat


# ------------------------------------------------- msg store recovery


def test_file_msg_store_recover_skips_corrupt_mid_file(tmp_path):
    """Satellite: a corrupt record mid-journal is skipped and counted;
    every later record still recovers. A torn tail stays silent."""
    from vernemq_tpu.broker.message import Msg
    from vernemq_tpu.storage.msg_store import FileMsgStore

    d = str(tmp_path / "store")
    s = FileMsgStore(d, fsync=True)  # fsync knob smoke too
    for i in range(3):
        s.write(("", "c1"), Msg(topic=("t", str(i)), payload=b"p%d" % i,
                                qos=1, msg_ref=b"ref%d" % i))
    s.close()
    path = os.path.join(d, "msgstore.log")
    with open(path, "rb") as fh:
        lines = fh.readlines()
    lines[1] = b'{"op": "w", "mp": CORRUPT\n'
    lines.append(b'{"torn tail')  # no trailing record — crash mid-append
    with open(path, "wb") as fh:
        fh.writelines(lines)

    s2 = FileMsgStore(d)
    msgs = s2.read_all(("", "c1"))
    assert [m.payload for m in msgs] == [b"p0", b"p2"]  # tail survived
    assert s2.recover_skipped == 1  # the torn tail is not "corrupt"
    # the torn tail was TRUNCATED: a post-crash append must not merge
    # with the partial line (which would corrupt the new record too)
    s2.write(("", "c1"), Msg(topic=("t", "new"), payload=b"post-crash",
                             qos=1, msg_ref=b"ref-new"))
    s2.close()
    s3 = FileMsgStore(d)
    assert s3.recover_skipped == 1  # still only the original corruption
    assert [m.payload for m in s3.read_all(("", "c1"))] == \
        [b"p0", b"p2", b"post-crash"]
    s3.close()


# ------------------------------------------------------------ e2e helpers


async def spool_cluster(tmp_path, n=2, **cfg):
    cfg.setdefault("cluster_spool_retransmit_ms", 100)
    cfg.setdefault("cluster_spool_ack_interval", 10)
    nodes = []
    for i in range(n):
        nodes.append(await start_node(
            f"node{i}", cluster_spool_dir=str(tmp_path / f"spool{i}"),
            **cfg))
    seed = nodes[0]
    for node in nodes[1:]:
        node.cluster.join(seed.cluster.listen_host, seed.cluster.listen_port)
    for node in nodes:
        await wait_until(lambda node=node: (
            len(node.cluster.members()) == n and node.cluster.is_ready()))
    return nodes


def spool_depth(node):
    return node.broker.metrics.all_metrics().get(
        "cluster_spool_depth_frames", 0)


# -------------------------------------------------------------- e2e tests


@pytest.mark.asyncio
async def test_partition_heal_zero_qos1_loss(tmp_path):
    """The tentpole guarantee: QoS1 publishes (plain and shared-group)
    routed to a partitioned peer journal in the spool and replay on
    heal — zero loss, acks drain the journal, admin surface works."""
    from vernemq_tpu.admin.commands import CommandRegistry, \
        register_core_commands

    nodes = await spool_cluster(tmp_path,
                                allow_publish_during_netsplit=True,
                                allow_register_during_netsplit=True)
    try:
        a, b = nodes
        sub = await connected(b, "sp-sub")
        await sub.subscribe("s/#", qos=1)
        await sub.subscribe("$share/g/sh/#", qos=1)
        await wait_until(
            lambda: len(a.broker.registry.trie("").match(["s", "x"])) == 1
            and len(a.broker.registry.trie("").match(["sh", "x"])) == 1)
        # the hlo capability exchange must have happened for spooling
        await wait_until(
            lambda: "spool" in a.cluster._peer_caps.get("node1", ()))

        pub = await connected(a, "sp-pub")
        partition(a, b)
        await wait_until(lambda: not a.cluster.is_ready())
        for i in range(10):
            await pub.publish("s/%d" % i, b"q1-%d" % i, qos=1)
        for i in range(3):
            await pub.publish("sh/%d" % i, b"g1-%d" % i, qos=1)
        await wait_until(lambda: spool_depth(a) == 13)

        # operator surface: per-peer rows while the backlog is pending
        reg = register_core_commands(CommandRegistry())
        out = reg.run(a.broker, ["cluster", "spool", "show"])
        (row,) = out["table"]
        assert row["peer"] == "node1" and row["pending_frames"] == 13
        assert row["spool_capable"] is True

        heal(a, b)
        got = [await sub.recv(15) for _ in range(13)]
        payloads = sorted(m.payload for m in got)
        assert payloads == sorted(
            [b"q1-%d" % i for i in range(10)]
            + [b"g1-%d" % i for i in range(3)])
        # no duplicates trail behind
        with pytest.raises(asyncio.TimeoutError):
            await sub.recv(timeout=0.3)
        # cumulative acks drained the journal
        await wait_until(lambda: spool_depth(a) == 0)
        assert a.broker.metrics.value("cluster_spool_replayed") >= 13
        # flush is now a no-op message path but must not error
        assert "flushed 0" in reg.run(a.broker,
                                      ["cluster", "spool", "flush"])
        await sub.disconnect()
        await pub.disconnect()
    finally:
        await stop_cluster(nodes)


@pytest.mark.asyncio
async def test_recv_fault_storm_exactly_once(tmp_path):
    """Sever the data plane via the ``cluster.recv`` fault point (frames
    AND acks drop, the channel stays up — no reconnect replay): the ack
    watchdog retransmits, the dedup window keeps QoS2 exactly-once and
    nothing is lost."""
    nodes = await spool_cluster(tmp_path)
    try:
        a, b = nodes
        sub = await connected(b, "fs-sub")
        await sub.subscribe("f/q1/#", qos=1)
        await sub.subscribe("f/q2/#", qos=2)
        await wait_until(
            lambda: len(a.broker.registry.trie("").match(["f", "q1", "x"]))
            == 1)
        await wait_until(
            lambda: "spool" in a.cluster._peer_caps.get("node1", ()))

        pub = await connected(a, "fs-pub")
        faults.install(faults.FaultPlan(
            [faults.FaultRule("cluster.recv", kind="error")], seed=11))
        try:
            for i in range(8):
                await pub.publish("f/q1/%d" % i, b"a%d" % i, qos=1)
                await pub.publish("f/q2/%d" % i, b"b%d" % i, qos=2)
            # hold the severance long enough for at least one retransmit
            await asyncio.sleep(0.5)
            assert spool_depth(a) == 16
        finally:
            faults.clear()

        got = {}
        for _ in range(16):
            m = await sub.recv(15)
            got[m.payload] = got.get(m.payload, 0) + 1
        expect = {b"a%d" % i for i in range(8)} | \
                 {b"b%d" % i for i in range(8)}
        assert set(got) == expect            # zero QoS>=1 loss
        assert all(c == 1 for c in got.values()), got  # exactly-once
        assert a.broker.metrics.value("cluster_spool_replayed") > 0
        await wait_until(lambda: spool_depth(a) == 0)
        await sub.disconnect()
        await pub.disconnect()
    finally:
        await stop_cluster(nodes)


@pytest.mark.asyncio
async def test_partial_loss_storm_no_gap_ack_loss(tmp_path):
    """PARTIAL in-channel loss (some batches through, some dropped):
    the contiguous-ack discipline must never let a delivered later
    frame ack away an undelivered earlier one — every QoS2 message
    arrives exactly once."""
    nodes = await spool_cluster(tmp_path)
    try:
        a, b = nodes
        sub = await connected(b, "pl-sub")
        await sub.subscribe("pl/#", qos=2)
        await wait_until(
            lambda: len(a.broker.registry.trie("").match(["pl", "x"])) == 1)
        await wait_until(
            lambda: "spool" in a.cluster._peer_caps.get("node1", ()))
        pub = await connected(a, "pl-pub")
        faults.install(faults.FaultPlan(
            [faults.FaultRule("cluster.recv", kind="error",
                              probability=0.5)], seed=23))
        try:
            for i in range(30):
                await pub.publish("pl/%d" % i, b"p%d" % i, qos=2)
                await asyncio.sleep(0.01)  # spread over several batches
            await asyncio.sleep(0.3)
        finally:
            faults.clear()
        got = {}
        for _ in range(30):
            m = await sub.recv(15)
            got[m.payload] = got.get(m.payload, 0) + 1
        assert set(got) == {b"p%d" % i for i in range(30)}  # zero loss
        assert all(c == 1 for c in got.values()), got      # exactly-once
        await wait_until(lambda: spool_depth(a) == 0)
        await sub.disconnect()
        await pub.disconnect()
    finally:
        await stop_cluster(nodes)


@pytest.mark.asyncio
async def test_dedup_window_suppresses_replayed_frame(tmp_path):
    """A raw re-send of an already-delivered msq frame (replay after a
    lost ack) is suppressed by the (seq, msg_ref) window — QoS2 cannot
    double-route."""
    nodes = await spool_cluster(tmp_path)
    try:
        a, b = nodes
        sub = await connected(b, "dd-sub")
        await sub.subscribe("d/#", qos=2)
        await wait_until(
            lambda: len(a.broker.registry.trie("").match(["d", "x"])) == 1)
        await wait_until(
            lambda: "spool" in a.cluster._peer_caps.get("node1", ()))

        w = a.cluster._writers["node1"]
        captured = []
        orig = w.send_frame

        def capture(data, sheddable=False):
            if data[:3] == b"msq":
                captured.append(data)
            return orig(data, sheddable)

        w.send_frame = capture
        pub = await connected(a, "dd-pub")
        await pub.publish("d/x", b"once", qos=2)
        assert (await sub.recv(10)).payload == b"once"
        assert len(captured) == 1
        before = b.broker.metrics.value("cluster_spool_deduped")
        orig(captured[0])  # the lost-ack replay, byte-identical
        await wait_until(lambda: b.broker.metrics.value(
            "cluster_spool_deduped") == before + 1)
        with pytest.raises(asyncio.TimeoutError):
            await sub.recv(timeout=0.4)  # not delivered twice
        await sub.disconnect()
        await pub.disconnect()
    finally:
        await stop_cluster(nodes)


@pytest.mark.asyncio
async def test_sender_restart_replays_disk_spool(tmp_path):
    """Sender crash/restart: a fresh cluster channel over the same spool
    directory replays the journaled backlog once the peer's capability
    handshake lands."""
    from vernemq_tpu.cluster import Cluster

    nodes = await spool_cluster(tmp_path,
                                allow_publish_during_netsplit=True,
                                allow_register_during_netsplit=True)
    try:
        a, b = nodes
        sub = await connected(b, "cr-sub")
        await sub.subscribe("c/#", qos=1)
        await wait_until(
            lambda: len(a.broker.registry.trie("").match(["c", "x"])) == 1)
        await wait_until(
            lambda: "spool" in a.cluster._peer_caps.get("node1", ()))

        pub = await connected(a, "cr-pub")
        partition(a, b)
        await wait_until(lambda: not a.cluster.is_ready())
        for i in range(5):
            await pub.publish("c/%d" % i, b"crash%d" % i, qos=1)
        await wait_until(lambda: spool_depth(a) == 5)

        # "crash": tear the channel down; the journal stays on disk. The
        # restarted channel binds the same port (a restarted broker's
        # configured cluster listener), and the peer's severed writer
        # heals back to it.
        port = a.cluster.listen_port
        await a.cluster.stop()
        assert a.broker.cluster is None
        fresh = Cluster(a.broker, "127.0.0.1", port)
        await fresh.start()
        a.cluster = fresh
        heal(a, b)
        assert spool_depth(a) == 5  # recovered from disk
        got = sorted([(await sub.recv(15)).payload for _ in range(5)])
        assert got == [b"crash%d" % i for i in range(5)]
        await wait_until(lambda: spool_depth(a) == 0)
        await sub.disconnect()
        await pub.disconnect()
    finally:
        await stop_cluster(nodes)


@pytest.mark.asyncio
async def test_old_peer_compat_falls_back_to_legacy_framing(tmp_path):
    """A peer that never advertised the spool capability (an old node)
    keeps receiving the fire-and-forget ``msg`` framing — QoS1 still
    delivers on a healthy link, nothing is journaled toward it."""
    nodes = await spool_cluster(tmp_path)
    try:
        a, b = nodes
        sub = await connected(b, "old-sub")
        await sub.subscribe("o/#", qos=1)
        await wait_until(
            lambda: len(a.broker.registry.trie("").match(["o", "x"])) == 1)
        # simulate an old peer: strip the advertised capability
        a.cluster._peer_caps["node1"] = set()
        pub = await connected(a, "old-pub")
        await pub.publish("o/x", b"legacy", qos=1)
        assert (await sub.recv(10)).payload == b"legacy"
        assert a.broker.metrics.value("cluster_spool_journaled") == 0
        assert spool_depth(a) == 0
        await sub.disconnect()
        await pub.disconnect()
    finally:
        await stop_cluster(nodes)


# ------------------------------------------------------------- chaos soak


async def _partition_storm(tmp_path, n_healthy=200, n_storm=500,
                           storm_s=5.0):
    """A QoS 1 subscriber on node B, a publisher on node A; the
    inter-node data plane severed for ``storm_s`` (``cluster.recv``
    drops frames AND acks on both nodes) under ``n_storm`` publishes,
    then healed: the spool replays. Returns how many of the storm's
    payloads never arrived, how many arrived more than once, and the
    sender's replayed-frame count."""
    import collections
    import time

    nodes = await spool_cluster(tmp_path,
                                allow_publish_during_netsplit=True,
                                cluster_spool_ack_interval=20)
    try:
        a, b = nodes
        sub = await connected(b, "storm-sub")
        await sub.subscribe("storm/#", qos=1)
        await wait_until(lambda: len(
            a.broker.registry.trie("").match(["storm", "x"])) == 1)
        pub = await connected(a, "storm-pub")
        for i in range(n_healthy):
            await pub.publish(f"storm/{i}", b"m%d" % i, qos=1)
        for _ in range(n_healthy):
            await sub.recv(5)

        faults.install(faults.FaultPlan(
            [faults.FaultRule("cluster.recv", kind="error")], seed=7))
        try:
            storm_t0 = time.perf_counter()
            for i in range(n_healthy, n_healthy + n_storm):
                await pub.publish(f"storm/{i}", b"m%d" % i, qos=1)
            while time.perf_counter() - storm_t0 < storm_s:
                await asyncio.sleep(0.05)
        finally:
            faults.clear()  # heal: the retransmit watchdog replays

        # the replay, then a quiet period: trailing duplicates still in
        # flight must land in the count
        got = collections.Counter()
        while True:
            try:
                m = await sub.recv(5 if len(got) < n_storm else 0.5)
            except asyncio.TimeoutError:
                break
            got[m.payload] += 1
        replayed = a.broker.metrics.value("cluster_spool_replayed")
        await sub.disconnect()
        await pub.disconnect()
    finally:
        await stop_cluster(nodes)
    expect = {b"m%d" % i for i in range(n_healthy, n_healthy + n_storm)}
    return {"missing": len(expect - set(got)),
            "duplicates": sum(c - 1 for c in got.values()),
            "replayed_frames": replayed}


@pytest.mark.chaos
@pytest.mark.slow
def test_partition_storm_soak(tmp_path):
    """500 QoS1 publishes through a 5s injected partition — zero loss,
    zero duplicates, spool replay engaged. (Sync test on its own loop:
    exempt from the 30s async harness timeout.)"""
    r = asyncio.run(_partition_storm(tmp_path))
    assert r["replayed_frames"] > 0, r
    assert r["missing"] == 0 and r["duplicates"] == 0, r
