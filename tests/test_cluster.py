"""Multi-node cluster tests: several in-process brokers on localhost,
joined over the real framed TCP channel — the shape of the reference's
ct_slave multi-node suites (vmq_cluster_SUITE: cross-node pub/sub, remote
enqueue, migration; vmq_cluster_netsplit_SUITE: CAP-flag behavior during
partitions induced by severing the inter-node socket)."""

import asyncio

import pytest

from vernemq_tpu.broker.config import Config
from vernemq_tpu.broker.server import start_broker
from vernemq_tpu.client import MQTTClient
from vernemq_tpu.cluster import Cluster
from vernemq_tpu.cluster.codec import decode, encode


# ------------------------------------------------------------------- codec


def test_codec_roundtrip():
    cases = [
        None, True, False, 0, -1, 1 << 62, -(1 << 62), 1 << 80, 3.14, "",
        "täxt", b"\x00\xff", [], [1, "a", None], (1, 2), {"k": [1, (2, 3)]},
        {("mp", "client"): {"qos": 1}},
        {"nested": {"deep": [{"x": b"bytes"}, ("t", 0.5)]}},
    ]
    for obj in cases:
        assert decode(encode(obj)) == obj
    # tuple/list distinction survives
    assert isinstance(decode(encode((1, 2))), tuple)
    assert isinstance(decode(encode([1, 2])), list)


def test_codec_rejects_garbage():
    with pytest.raises(ValueError):
        decode(b"\xfe\x01\x02")
    with pytest.raises(ValueError):
        decode(encode([1, 2]) + b"junk")
    with pytest.raises(TypeError):
        encode(object())


# ---------------------------------------------------------------- fixtures


async def wait_until(pred, timeout=5.0, interval=0.02):
    """Poll helper (vmq_cluster_test_utils wait_until)."""
    loop = asyncio.get_event_loop()
    deadline = loop.time() + timeout
    while loop.time() < deadline:
        if pred():
            return True
        await asyncio.sleep(interval)
    raise AssertionError(f"wait_until timed out: {pred}")


class Node:
    def __init__(self, broker, server, cluster):
        self.broker = broker
        self.server = server
        self.cluster = cluster

    @property
    def addr(self):
        return self.server.host, self.server.port


async def start_node(name, **cfg):
    config = Config(systree_enabled=False, allow_anonymous=True, **cfg)
    broker, server = await start_broker(config, port=0, node_name=name)
    broker.node_name = name
    broker.metadata.node_name = name
    broker.registry.node_name = name
    broker.registry.db.node_name = name
    cluster = Cluster(broker, "127.0.0.1", 0)
    await cluster.start()
    return Node(broker, server, cluster)


async def make_cluster(n, **cfg):
    nodes = [await start_node(f"node{i}", **cfg) for i in range(n)]
    seed = nodes[0]
    for node in nodes[1:]:
        node.cluster.join(seed.cluster.listen_host, seed.cluster.listen_port)
    for node in nodes:
        await wait_until(lambda node=node: (
            len(node.cluster.members()) == n and node.cluster.is_ready()))
    return nodes


async def stop_cluster(nodes):
    for node in nodes:
        await node.cluster.stop()
        await node.broker.stop()
        await node.server.stop()


def partition(a: Node, b: Node):
    """Sever both directions of the a<->b channel and hold it down
    (the reference's cookie-change partition, vmq_cluster_test_utils.erl:
    177-184)."""
    for x, y in ((a, b), (b, a)):
        w = x.cluster._writers.get(y.broker.node_name)
        assert w is not None
        w._real_addr = w.addr
        w.addr = ("127.0.0.1", 9)  # discard port: connect refused
        if w._writer is not None:
            w._writer.close()


def heal(a: Node, b: Node):
    for x, y in ((a, b), (b, a)):
        w = x.cluster._writers.get(y.broker.node_name)
        # a late join/member-change event may have REPLACED the severed
        # writer (addr mismatch → rebuild) with one already pointing at
        # the real address; that writer has no _real_addr marker and
        # needs no healing
        w.addr = getattr(w, "_real_addr", w.addr)


async def connected(node: Node, client_id, **kw):
    c = MQTTClient(*node.addr, client_id=client_id, **kw)
    ack = await c.connect()
    assert ack.rc == 0, ack
    return c


# ------------------------------------------------------------------- tests


def members(node, group, filter_words):
    """The members node's registry holds for ``$share/<group>/<filter>``."""
    g = node.broker.registry.share_group("", group, filter_words)
    return {} if g is None else g.members


@pytest.mark.asyncio
async def test_join_forms_full_mesh():
    nodes = await make_cluster(3)
    try:
        for node in nodes:
            assert node.cluster.members() == ["node0", "node1", "node2"]
            assert node.cluster.is_ready()
            status = dict(node.cluster.status())
            assert all(status.values())
    finally:
        await stop_cluster(nodes)


@pytest.mark.asyncio
async def test_cross_node_pubsub():
    nodes = await make_cluster(2)
    try:
        a, b = nodes
        sub = await connected(b, "sub1")
        await sub.subscribe("t/+", qos=1)
        # subscription must replicate into node a's trie as a node pointer
        await wait_until(
            lambda: len(a.broker.registry.trie("").match(["t", "x"])) == 1)
        pub = await connected(a, "pub1")
        await pub.publish("t/x", b"cross", qos=1)
        msg = await sub.recv()
        assert msg.topic == "t/x" and msg.payload == b"cross" and msg.qos == 1
        await sub.disconnect()
        await pub.disconnect()
    finally:
        await stop_cluster(nodes)


@pytest.mark.asyncio
async def test_no_duplicate_across_nodes():
    """A subscriber on the publisher's own node and one on a remote node
    each get exactly one copy (one 'msg' frame per remote node,
    vmq_reg.erl:346-353)."""
    nodes = await make_cluster(2)
    try:
        a, b = nodes
        sub_local = await connected(a, "sl")
        sub_remote = await connected(b, "sr")
        await sub_local.subscribe("d/#", qos=0)
        await sub_remote.subscribe("d/#", qos=0)
        await wait_until(
            lambda: len(a.broker.registry.trie("").match(["d", "x"])) == 2)
        pub = await connected(a, "pb")
        await pub.publish("d/x", b"one", qos=0)
        m1 = await sub_local.recv()
        m2 = await sub_remote.recv()
        assert m1.payload == m2.payload == b"one"
        with pytest.raises(asyncio.TimeoutError):
            await sub_remote.recv(timeout=0.3)
        with pytest.raises(asyncio.TimeoutError):
            await sub_local.recv(timeout=0.3)
        for c in (sub_local, sub_remote, pub):
            await c.disconnect()
    finally:
        await stop_cluster(nodes)


@pytest.mark.asyncio
async def test_retain_replicates():
    nodes = await make_cluster(2)
    try:
        a, b = nodes
        pub = await connected(a, "rp")
        await pub.publish("state/x", b"kept", qos=1, retain=True)
        await wait_until(lambda: len(b.broker.retain) == 1)
        sub = await connected(b, "rs")
        await sub.subscribe("state/#", qos=0)
        msg = await sub.recv()
        assert msg.payload == b"kept" and msg.retain is True
        await pub.disconnect()
        await sub.disconnect()
    finally:
        await stop_cluster(nodes)


@pytest.mark.asyncio
async def test_shared_subscription_cross_node():
    nodes = await make_cluster(2)
    try:
        a, b = nodes
        local = await connected(a, "m-local")
        remote = await connected(b, "m-remote")
        await local.subscribe("$share/grp/work/#", qos=0)
        await remote.subscribe("$share/grp/work/#", qos=0)
        # ONE row for the group, whatever its members: wait for both
        await wait_until(lambda: len(members(a, "grp", ["work", "#"])) == 2)
        pub = await connected(a, "sp")
        # prefer_local: the member on the publisher's node gets every message
        for i in range(5):
            await pub.publish("work/1", b"j%d" % i, qos=0)
        for i in range(5):
            msg = await local.recv()
            assert msg.payload == b"j%d" % i
        with pytest.raises(asyncio.TimeoutError):
            await remote.recv(timeout=0.3)
        # local member leaves -> remote member takes over via remote enqueue
        await local.disconnect()
        await wait_until(lambda: len(members(a, "grp", ["work", "#"])) == 1)
        await pub.publish("work/2", b"failover", qos=0)
        msg = await remote.recv()
        assert msg.payload == b"failover"
        await remote.disconnect()
        await pub.disconnect()
    finally:
        await stop_cluster(nodes)


@pytest.mark.asyncio
async def test_netsplit_gates_publish_and_detection():
    nodes = await make_cluster(2)
    try:
        a, b = nodes
        c = await connected(a, "np")
        partition(a, b)
        await wait_until(lambda: not a.cluster.is_ready())
        # allow_publish_during_netsplit=False: QoS1 publish gets no PUBACK
        # (client would retry; reference returns {error, not_ready})
        with pytest.raises(asyncio.TimeoutError):
            await c.publish("x/y", b"blocked", qos=1, timeout=0.5)
        detected, resolved = a.cluster.netsplit_statistics()
        assert detected >= 1
        heal(a, b)
        await wait_until(lambda: a.cluster.is_ready(), timeout=10)
        _, resolved = a.cluster.netsplit_statistics()
        assert resolved >= 1
        ack = await c.publish("x/y", b"flows-again", qos=1)
        assert ack is not None
        await c.disconnect()
    finally:
        await stop_cluster(nodes)


@pytest.mark.asyncio
async def test_netsplit_allow_flags():
    nodes = await make_cluster(
        2, allow_publish_during_netsplit=True,
        allow_subscribe_during_netsplit=True,
        allow_register_during_netsplit=True)
    try:
        a, b = nodes
        partition(a, b)
        await wait_until(lambda: not a.cluster.is_ready())
        c = await connected(a, "caps")  # register allowed during split
        await c.subscribe("s/#", qos=1)  # subscribe allowed
        ack = await c.publish("s/1", b"av", qos=1)  # publish allowed
        assert ack is not None
        msg = await c.recv()
        assert msg.payload == b"av"
        heal(a, b)
        await c.disconnect()
    finally:
        await stop_cluster(nodes)


@pytest.mark.asyncio
async def test_queue_migration_on_reconnect():
    """Persistent session moves nodes: offline messages drain to the new
    owner over the acked enq channel (vmq_cluster_SUITE migration case +
    vmq_reg remap, vmq_reg.erl:676-699)."""
    nodes = await make_cluster(2)
    try:
        a, b = nodes
        c1 = await connected(a, "mig", clean_start=False)
        await c1.subscribe("m/#", qos=1)
        await c1.disconnect()
        # queue now offline on node a; publish into it from node b
        pub = await connected(b, "mig-pub")
        for i in range(3):
            await pub.publish("m/%d" % i, b"off%d" % i, qos=1)
        await wait_until(
            lambda: (q := a.broker.registry.queues.get(("", "mig"))) is not None
            and len(q.offline) == 3)
        # reconnect on node b: remap + drain
        c2 = await connected(b, "mig", clean_start=False)
        assert c2.connack.session_present is True
        got = sorted([(await c2.recv()).payload for _ in range(3)])
        assert got == [b"off0", b"off1", b"off2"]
        # old owner dropped its queue; new owner has it
        await wait_until(
            lambda: ("", "mig") not in a.broker.registry.queues)
        assert ("", "mig") in b.broker.registry.queues
        rec = b.broker.registry.db.read(("", "mig"))
        assert rec is not None and rec.node == "node1"
        await c2.disconnect()
        await pub.disconnect()
    finally:
        await stop_cluster(nodes)


@pytest.mark.asyncio
async def test_cluster_channel_restart_rebuilds_writers():
    """A restarted cluster channel (vmq listener restart) must rebuild
    its outbound writers from the EXISTING member table — member-change
    events fired long ago — and keep routing both directions. Covers the
    replay in Cluster.start plus the stop() detach discipline."""
    nodes = await make_cluster(2)
    try:
        a, b = nodes
        sub = await connected(b, "rs-sub")
        await sub.subscribe("r/+", qos=1)
        await wait_until(
            lambda: len(a.broker.registry.trie("").match(["r", "x"])) == 1)
        # restart node b's cluster channel in place (same port)
        old = b.cluster
        port = old.listen_port
        await old.stop()
        assert b.broker.cluster is None  # detached, restartable
        fresh = Cluster(b.broker, "127.0.0.1", port)
        await fresh.start()
        b.cluster = fresh
        # writers rebuilt from the member table on BOTH sides
        await wait_until(lambda: dict(fresh.status()).get("node0") is True)
        await wait_until(lambda: dict(a.cluster.status()).get("node1") is True)
        # a NEW registration on a (reg_sync may coordinate via b) + publish
        pub = await connected(a, "rs-pub")
        await pub.publish("r/x", b"post-restart", qos=1)
        msg = await sub.recv()
        assert msg.payload == b"post-restart"
        await sub.disconnect()
        await pub.disconnect()
    finally:
        await stop_cluster(nodes)


@pytest.mark.asyncio
async def test_stopped_channel_keeps_cap_gate_and_counts_drops():
    """A bare channel stop (vmq listener stop, no restart) must NOT flip
    a still-clustered node to standalone: the is_ready gate stays down
    and skipped remote forwards are counted, not silent."""
    nodes = await make_cluster(2, allow_register_during_netsplit=True,
                               allow_publish_during_netsplit=True)
    try:
        a, b = nodes
        sub = await connected(a, "cap-sub")
        await sub.subscribe("c/+", qos=0)
        await wait_until(
            lambda: len(b.broker.registry.trie("").match(["c", "x"])) == 1)
        await b.cluster.stop()
        assert b.broker.cluster is None
        # still a joined member, no channel: NOT ready (CAP gates engage;
        # without the allow_* flags above, registration would be rc=3)
        assert b.broker.cluster_ready() is False
        # a's view of node1 goes down too (channel dropped)
        await wait_until(lambda: dict(a.cluster.status()).get("node1") is False)
        # publish on b toward a's remote pointer: dropped WITH accounting
        before = b.broker.metrics.value("cluster_publish_no_channel")
        pub = await connected(b, "cap-pub")
        await pub.publish("c/x", b"lost", qos=0)
        await wait_until(lambda: b.broker.metrics.value(
            "cluster_publish_no_channel") == before + 1)
        await pub.disconnect()
        await sub.disconnect()
        b.cluster = None  # stop_cluster: already stopped
    finally:
        await stop_cluster([a])
        await b.broker.stop()
        await b.server.stop()


@pytest.mark.asyncio
async def test_failed_cluster_start_detaches_and_is_retryable():
    """A vmq listener start that fails to bind must leave the broker
    restartable (detach the half-built cluster), not wedged on
    'cluster listener already running'."""
    import socket

    from vernemq_tpu.broker.listeners import ListenerManager

    config = Config(systree_enabled=False, allow_anonymous=True)
    from vernemq_tpu.broker.server import start_broker

    broker, server = await start_broker(config, port=0, node_name="fx")
    hog = socket.socket()
    hog.bind(("127.0.0.1", 0))
    hog.listen(1)
    stolen_port = hog.getsockname()[1]
    lm = ListenerManager(broker)
    try:
        with pytest.raises(OSError):
            await lm.start_listener("vmq", "127.0.0.1", stolen_port)
        assert broker.cluster is None  # detached, not wedged
        assert broker.metadata.broadcast is None
        # retry on a free port succeeds
        cluster = await lm.start_listener("vmq", "127.0.0.1", 0)
        assert broker.cluster is cluster
    finally:
        hog.close()
        await lm.stop_all()
        await broker.stop()
        await server.stop()


@pytest.mark.asyncio
async def test_cluster_leave():
    nodes = await make_cluster(3)
    try:
        a, b, c = nodes
        a.cluster.leave("node2")
        await wait_until(lambda: all(
            n.cluster.members() == ["node0", "node1"] for n in (a, b)))
        assert a.cluster.is_ready()
    finally:
        await stop_cluster(nodes)


@pytest.mark.asyncio
async def test_graceful_leave_migrates_offline_queues():
    """`vmq-admin cluster leave` on the leaving node: offline queues are
    rewritten to live peers and their backlogs drain over acked enq
    batches (vmq_reg:migrate_offline_queues, vmq_reg.erl:433-477)."""
    nodes = await make_cluster(3)
    try:
        a, b, c = nodes
        # two persistent subscribers homed on node0, then taken offline
        sids = []
        for name in ("ml1", "ml2"):
            cl = await connected(a, name, clean_start=False)
            await cl.subscribe(f"leave/{name}/#", qos=1)
            await cl.disconnect()
            sids.append(("", name))
        pub = await connected(b, "leave-pub")
        for name in ("ml1", "ml2"):
            for i in range(4):
                await pub.publish(f"leave/{name}/{i}", b"m%d" % i, qos=1)
        await wait_until(lambda: all(
            (q := a.broker.registry.queues.get(sid)) is not None
            and len(q.offline) == 4 for sid in sids))

        moved = await a.cluster.leave_gracefully()
        assert moved == 2
        # node0 out of the membership everywhere
        await wait_until(lambda: all(
            n.cluster.members() == ["node1", "node2"] for n in (b, c)))
        # queues live on the targets with the full backlog, node0 is empty
        def drained():
            for sid in sids:
                rec = b.broker.registry.db.read(sid)
                if rec is None or rec.node == "node0":
                    return False
                owner = b if rec.node == "node1" else c
                q = owner.broker.registry.queues.get(sid)
                if q is None or len(q.offline) != 4:
                    return False
            return not a.broker.registry.queues and not a.broker.migrations
        await wait_until(drained)
        # both targets used (round-robin)
        owners = {b.broker.registry.db.read(sid).node for sid in sids}
        assert owners == {"node1", "node2"}
        # clients reconnect at the new owner and receive the backlog
        rec = b.broker.registry.db.read(("", "ml1"))
        owner = b if rec.node == "node1" else c
        cl = await connected(owner, "ml1", clean_start=False)
        assert cl.connack.session_present is True
        got = sorted([(await cl.recv()).payload for _ in range(4)])
        assert got == [b"m0", b"m1", b"m2", b"m3"]
        await cl.disconnect()
        await pub.disconnect()
    finally:
        await stop_cluster(nodes)


@pytest.mark.asyncio
async def test_fix_dead_queues_repairs_routing():
    """A node dies without leaving: fix-dead-queues rewrites its persistent
    subscribers to live nodes (fresh queues there; routing repaired) and
    drops its clean-session records (vmq_reg:fix_dead_queues,
    vmq_reg.erl:479-520)."""
    nodes = await make_cluster(3)
    try:
        a, b, c = nodes
        # persistent subscriber + clean-session subscriber homed on node2
        cp = await connected(c, "dead-p", clean_start=False)
        await cp.subscribe("dead/#", qos=1)
        ccs = await connected(c, "dead-cs", clean_start=True)
        await ccs.subscribe("dead/cs", qos=1)
        # replicate records, then kill node2 without leave
        await wait_until(lambda: all(
            n.broker.registry.db.read(("", "dead-p")) is not None
            for n in (a, b)))
        await c.cluster.stop()
        await c.broker.stop()
        await c.server.stop()
        await wait_until(lambda: not a.cluster.is_ready())

        fixed = a.cluster.fix_dead_queues()
        assert fixed == 2
        # operator also removes the dead member so the cluster is ready
        # again (registration stays CAP-gated while a member is down)
        a.cluster.leave("node2")
        await wait_until(lambda: a.cluster.is_ready() and b.cluster.is_ready())
        rec = a.broker.registry.db.read(("", "dead-p"))
        assert rec is not None and rec.node in ("node0", "node1")
        assert a.broker.registry.db.read(("", "dead-cs")) is None
        # the new owner built an offline queue; publishes land in it
        owner = a if rec.node == "node0" else b
        await wait_until(
            lambda: ("", "dead-p") in owner.broker.registry.queues)
        pub = await connected(a, "dead-pub")
        await pub.publish("dead/x", b"repaired", qos=1)
        await wait_until(lambda: len(
            owner.broker.registry.queues[("", "dead-p")].offline) == 1)
        # subscriber reconnects at the new owner and gets the message
        cl = await connected(owner, "dead-p", clean_start=False)
        assert cl.connack.session_present is True
        assert (await cl.recv()).payload == b"repaired"
        await cl.disconnect()
        await pub.disconnect()
    finally:
        await stop_cluster(nodes[:2])


@pytest.mark.asyncio
async def test_drain_retry_is_bounded_and_surfaced():
    """A migration whose target never acks retries a bounded number of
    times, surfaces state via broker.migrations, and restores the backlog
    locally (VERDICT: no unbounded fire-and-forget drain loops)."""
    nodes = await make_cluster(2)
    try:
        a, b = nodes
        a.broker.config.set("migrate_drain_retries", 2)
        cl = await connected(a, "stuck", clean_start=False)
        await cl.subscribe("stuck/#", qos=1)
        await cl.disconnect()
        pub = await connected(a, "stuck-pub")
        await pub.publish("stuck/1", b"x", qos=1)
        await pub.disconnect()
        sid = ("", "stuck")
        await wait_until(lambda: (
            (q := a.broker.registry.queues.get(sid)) is not None
            and len(q.offline) == 1))
        # sever the channel a->b so enq acks never arrive, then remap the
        # record to node1 (as a reconnect there would)
        partition(a, b)
        rec = a.broker.registry.db.read(sid)
        rec.node = "node1"
        a.broker.registry.db.store(sid, rec)
        await wait_until(
            lambda: a.broker.migrations.get(sid, {}).get("state") == "failed",
            timeout=30.0)
        q = a.broker.registry.queues.get(sid)
        assert q is not None and len(q.offline) == 1  # backlog restored
        assert a.broker.metrics.value("queue_drain_failed") >= 1
    finally:
        await stop_cluster(nodes)


@pytest.mark.asyncio
async def test_leave_retargets_when_migration_target_dies():
    """Graceful leave with a migration target dying mid-drain: the
    failed queue is retried against the surviving peers (each tried at
    most once) with progress visible via `vmq-admin cluster migrations`
    — the leave neither wedges nor loses the queue."""
    from vernemq_tpu.admin.commands import CommandRegistry, \
        register_core_commands

    nodes = await make_cluster(3)
    try:
        a, b, c = nodes
        a.broker.config.set("migrate_drain_retries", 1)
        a.broker.config.set("max_drain_time", 50)
        for name in ("rt1", "rt2"):
            cl = await connected(a, name, clean_start=False)
            await cl.subscribe(f"rt/{name}/#", qos=1)
            await cl.disconnect()
        pub = await connected(b, "rt-pub")
        for name in ("rt1", "rt2"):
            for i in range(3):
                await pub.publish(f"rt/{name}/{i}", b"m%d" % i, qos=1)
        await wait_until(lambda: all(
            (q := a.broker.registry.queues.get(("", n))) is not None
            and len(q.offline) == 3 for n in ("rt1", "rt2")))

        # node1's acked enqueue path dies mid-drain; snapshot the admin
        # migrations view at the failure (partial progress is reported)
        admin = register_core_commands(CommandRegistry())
        seen = []
        orig = a.broker.cluster.remote_enqueue

        async def dying(node, sid, msgs, **kw):
            if node == "node1":
                seen.append(admin.run(a.broker, ["cluster", "migrations"]))
                raise ConnectionError("target died mid-drain")
            return await orig(node, sid, msgs, **kw)

        a.broker.cluster.remote_enqueue = dying
        moved = await a.cluster.leave_gracefully(timeout=30)
        assert moved == 2
        assert seen and any(r["target"] == "node1" and r["state"] in
                            ("draining", "failed")
                            for r in seen[0]["table"])
        assert a.broker.metrics.value("queue_drain_failed") >= 1

        # both queues survive on node2 (the only live target once node1's
        # drain path died) with their full backlogs; node0 is empty
        def settled():
            for n in ("rt1", "rt2"):
                rec = b.broker.registry.db.read(("", n))
                if rec is None or rec.node != "node2":
                    return False
                q = c.broker.registry.queues.get(("", n))
                if q is None or len(q.offline) != 3:
                    return False
            return (not a.broker.registry.queues
                    and not a.broker.migrations)
        await wait_until(settled)
        await pub.disconnect()
    finally:
        await stop_cluster(nodes)


@pytest.mark.asyncio
async def test_migration_zero_loss_mid_drain():
    """A QoS1 message racing into the queue DURING the drain follows the
    migration instead of being dropped (drain({enqueue,..}) inserts and
    re-fires drain_start, vmq_queue.erl:383-390)."""
    from vernemq_tpu.broker.message import Msg

    nodes = await make_cluster(2)
    try:
        a, b = nodes
        c1 = await connected(a, "zmig", clean_start=False)
        await c1.subscribe("z/#", qos=1)
        await c1.disconnect()
        pub = await connected(b, "zmig-pub")
        for i in range(3):
            await pub.publish("z/%d" % i, b"pre%d" % i, qos=1)
        await wait_until(
            lambda: (q := a.broker.registry.queues.get(("", "zmig")))
            is not None and len(q.offline) == 3)
        q = a.broker.registry.queues[("", "zmig")]

        # wrap node a's remote_enqueue: the FIRST drain chunk triggers an
        # in-flight publish racing into the draining queue
        orig = a.broker.cluster.remote_enqueue
        raced = []

        async def racing_enqueue(node, sid, msgs, **kw):
            if not raced:
                raced.append(True)
                assert q.state == "drain"
                q.enqueue(Msg(topic=("z", "race"), payload=b"mid-drain",
                              qos=1, mountpoint=""))
            return await orig(node, sid, msgs, **kw)

        a.broker.cluster.remote_enqueue = racing_enqueue
        c2 = await connected(b, "zmig", clean_start=False)
        assert c2.connack.session_present is True
        got = sorted([(await c2.recv()).payload for _ in range(4)])
        assert got == [b"mid-drain", b"pre0", b"pre1", b"pre2"]
        assert a.broker.metrics.value("queue_message_drop") == 0
        await c2.disconnect()
        await pub.disconnect()
    finally:
        await stop_cluster(nodes)


@pytest.mark.asyncio
async def test_concurrent_same_clientid_register_serialized():
    """Two nodes registering the same ClientId at once: RegSync serializes
    them cluster-wide (vmq_reg.erl:115-126 via vmq_reg_sync) — exactly one
    node ends up owning the record, the loser's queue is gone/migrated,
    and the losing live session is taken over."""
    nodes = await make_cluster(2)
    try:
        a, b = nodes
        ca = MQTTClient(*a.addr, client_id="dup", clean_start=False)
        cb = MQTTClient(*b.addr, client_id="dup", clean_start=False)
        acks = await asyncio.gather(ca.connect(), cb.connect())
        assert [k.rc for k in acks] == [0, 0]
        # records converge on ONE owner on both nodes
        await wait_until(lambda: (
            (ra := a.broker.registry.db.read(("", "dup"))) is not None
            and (rb := b.broker.registry.db.read(("", "dup"))) is not None
            and ra.node == rb.node))
        owner = a.broker.registry.db.read(("", "dup")).node
        loser = b if owner == "node0" else a
        winner = a if owner == "node0" else b
        # loser's queue drained away + its session taken over
        await wait_until(lambda: ("", "dup") not in loser.broker.registry.queues)
        assert ("", "dup") in winner.broker.registry.queues
        await wait_until(lambda: ("", "dup") not in loser.broker.sessions)
        assert ("", "dup") in winner.broker.sessions
        for c in (ca, cb):
            try:
                await c.close()
            except Exception:
                pass
    finally:
        await stop_cluster(nodes)


@pytest.mark.asyncio
async def test_reg_sync_lock_serializes_actions():
    """Direct RegSync property check: two nodes' actions on one key run
    strictly one-at-a-time, FIFO, across the framed channel."""
    nodes = await make_cluster(2)
    try:
        a, b = nodes
        running, order = [], []

        def action(tag):
            def _do():
                assert not running, "lock violated: overlapping actions"
                running.append(tag)
                order.append(tag)
                running.clear()
            return _do

        await asyncio.gather(
            a.cluster.reg_sync.sync(("", "k1"), action("a1")),
            b.cluster.reg_sync.sync(("", "k1"), action("b1")),
            a.cluster.reg_sync.sync(("", "k1"), action("a2")),
        )
        assert sorted(order) == ["a1", "a2", "b1"]
    finally:
        await stop_cluster(nodes)


@pytest.mark.asyncio
async def test_partial_ae_transfers_delta_not_state():
    """Reconnect reconciliation is O(delta): after a partition with a few
    writes, the digest exchange moves only mismatching buckets' entries,
    not the full 5k-key store (VERDICT r2 item 7; the
    vmq_swc_exchange_fsm.erl:34-116 shape)."""
    from vernemq_tpu.cluster import codec as ccodec

    nodes = await make_cluster(2)
    try:
        a, b = nodes
        # seed a large store and let it replicate
        for i in range(5000):
            a.broker.metadata.put("seed", ("k", i), {"v": i})
        await wait_until(
            lambda: sum(1 for _ in b.broker.metadata.fold("seed")) == 5000,
            timeout=15)

        partition(a, b)
        for i in range(10):
            a.broker.metadata.put("seed", ("k", i), {"v": i + 100000})
        b.broker.metadata.put("seed", ("post", 1), {"v": "from-b"})

        # count AE entry transfers during heal by wrapping the frames
        moved = {"entries": 0, "full": 0}
        for n in (a, b):
            orig = n.cluster.send_meta_frame

            def counting(node, cmd, term, _o=orig):
                if cmd == b"dgr":
                    moved["entries"] += len(term[1])
                elif cmd == b"dgp":
                    moved["entries"] += len(term)
                return _o(node, cmd, term)

            n.cluster.send_meta_frame = counting
        heal(a, b)
        await wait_until(
            lambda: (b.broker.metadata.get("seed", ("k", 3)) or {}).get("v")
            == 100003 and a.broker.metadata.get("seed", ("post", 1))
            is not None, timeout=15)
        # the 11 changed keys live in <= 11 buckets of 512 over 5k keys
        # (~10 keys/bucket): far fewer entries than the full state move
        assert 0 < moved["entries"] < 500, moved
    finally:
        await stop_cluster(nodes)


@pytest.mark.asyncio
async def test_cross_node_pubsub_tpu_view():
    """Cross-node fanout with default_reg_view='tpu' on both nodes: remote
    subscriptions collapse to per-node pointer rows in the DEVICE table,
    and a publish on either node reaches the remote subscriber through
    the batched matcher (the vmq_reg_trie remote-entry seam,
    vmq_reg_trie.erl:503-520, on the TPU path)."""
    nodes = await make_cluster(2, default_reg_view="tpu")
    try:
        a, b = nodes
        sub = await connected(a, "tsub")
        await sub.subscribe("tv/+/x", qos=1)
        pub = await connected(b, "tpub")
        await pub.publish("tv/1/x", b"cross", qos=1)
        m = await sub.recv()
        assert m.payload == b"cross"
        # local fanout on the same node too
        sub2 = await connected(b, "tsub2")
        await sub2.subscribe("tv/#", qos=0)
        await pub.publish("tv/2/x", b"both", qos=1)
        assert (await sub.recv()).payload == b"both"
        assert (await sub2.recv()).payload == b"both"
        # unsubscribe propagates through the device table delta stream
        await sub.unsubscribe("tv/+/x")
        await pub.publish("tv/3/x", b"only2", qos=0)
        assert (await sub2.recv()).payload == b"only2"
        assert sub.messages.empty()
        for c in (sub, sub2, pub):
            await c.disconnect()
    finally:
        await stop_cluster(nodes)


@pytest.mark.asyncio
async def test_shared_subscription_cross_node_tpu_view():
    """$share group rows through the DEVICE matcher in a 2-node cluster:
    prefer_local picks the publisher-side member; member departure fails
    over to the remote member via remote enqueue — the
    vmq_shared_subscriptions.erl:26-63 flow with the fold served by the
    TPU table's group rows."""
    nodes = await make_cluster(2, default_reg_view="tpu")
    try:
        a, b = nodes
        local = await connected(a, "s-local")
        remote = await connected(b, "s-remote")
        await local.subscribe("$share/g2/jobs/#", qos=0)
        await remote.subscribe("$share/g2/jobs/#", qos=0)
        view = a.broker.registry.reg_view("tpu")
        await wait_until(lambda: len(members(a, "g2", ["jobs", "#"])) == 2)
        # the device table holds the group as ONE row
        assert [k for _f, k, _o in view.fold("", ["jobs", "1"])] \
            == [("$g", "g2", None)]
        pub = await connected(a, "s-pub")
        for i in range(4):
            await pub.publish("jobs/1", b"t%d" % i, qos=0)
        for i in range(4):
            assert (await local.recv()).payload == b"t%d" % i
        with pytest.raises(asyncio.TimeoutError):
            await remote.recv(timeout=0.3)
        await local.disconnect()
        await wait_until(lambda: len(members(a, "g2", ["jobs", "#"])) == 1)
        await pub.publish("jobs/2", b"fo", qos=0)
        assert (await remote.recv()).payload == b"fo"
        await remote.disconnect()
        await pub.disconnect()
    finally:
        await stop_cluster(nodes)


@pytest.mark.asyncio
async def test_plumtree_eight_node_convergence(event_loop):
    """8-node cluster on the real framed channel with LWW metadata:
    subscription writes disseminate over the plumtree broadcast tree
    (eager gossip + lazy IHAVE) — every node's trie converges, cross-
    cluster delivery works, and the tree actually engaged (gossip rx on
    far nodes, lazy links exist once peers exceed the eager fanout)."""
    nodes = await make_cluster(8)
    try:
        sub = await connected(nodes[7], "pt-sub")
        await sub.subscribe("pt/+/t", qos=1)
        # subscription metadata must reach node0 through the tree
        await wait_until(lambda: len(
            nodes[0].broker.registry.trie("").match(["pt", "x", "t"])) == 1)
        pub = await connected(nodes[0], "pt-pub")
        await pub.publish("pt/x/t", b"tree", qos=1)
        got = await sub.recv(10)
        assert got.payload == b"tree"
        pt7 = nodes[7].cluster.plumtree
        assert pt7 is not None and pt7.rx > 0
        # 7 peers > eager_fanout 4: lazy links must exist on every node
        for n in nodes:
            pt = n.cluster.plumtree
            assert len(pt.eager) <= pt.eager_fanout + pt.grafts + 1
            assert pt.eager or pt.lazy
        await sub.disconnect()
        await pub.disconnect()
    finally:
        await stop_cluster(nodes)


# ------------------------------------------- migration under injected faults


@pytest.mark.asyncio
async def test_migration_survives_store_read_failure_mid_drain():
    """A store-backed offline queue whose backlog read fails mid-drain:
    the drain aborts with the LOCAL queue state restored (nothing
    shipped, nothing deleted), the migration reads `failed`, and once
    the store heals the retarget machinery completes the move with
    zero loss and the target recorded in `tried` (vmq_reg.erl's
    block_until_migrated error path)."""
    nodes = await make_cluster(3)
    try:
        a, b, c = nodes
        sid = ("", "srf")
        cl = await connected(a, "srf", clean_start=False)
        await cl.subscribe("srf/#", qos=1)
        await cl.disconnect()
        pub = await connected(b, "srf-pub")
        for i in range(3):
            await pub.publish(f"srf/{i}", b"s%d" % i, qos=1)
        await pub.disconnect()
        await wait_until(lambda: len(
            a.broker.registry.queues[sid].offline) == 3)
        q = a.broker.registry.queues[sid]
        # push the backlog fully into the store tier (cold-queue shape)
        assert len(a.broker.msg_store.read_all(sid)) == 3
        q.offline.clear()
        q.offline_in_store = True

        real_read = a.broker.msg_store.read_all
        state = {"broken": True}

        def flaky_read(s):
            if state["broken"] and s == sid:
                raise IOError("injected store read failure")
            return real_read(s)

        a.broker.msg_store.read_all = flaky_read
        # fence the record at node1: the change event fires the drain
        rec = a.broker.registry.db.read(sid)
        rec.node = "node1"
        a.broker.registry.db.store(sid, rec)
        await wait_until(lambda: a.broker.migrations.get(
            sid, {}).get("state") == "failed")
        # local state intact: queue offline, backlog safe in the store
        from vernemq_tpu.broker.queue import OFFLINE
        assert q.state == OFFLINE and q.offline_in_store is True
        assert a.broker.metrics.value("msg_store_read_errors") >= 1
        assert a.broker.metrics.value("queue_drain_failed") >= 1
        assert len(real_read(sid)) == 3  # nothing deleted
        mig = a.broker.migrations[sid]

        # store heals; the leave-loop retarget picks a fresh peer
        state["broken"] = False
        assert a.cluster._retarget_failed_migrations(
            ["node1", "node2"]) is True
        assert mig["tried"] == ["node1", "node2"]
        await wait_until(lambda: sid not in a.broker.migrations
                         and sid not in a.broker.registry.queues)
        rec = a.broker.registry.db.read(sid)
        assert rec.node == "node2"
        await wait_until(lambda: (
            (q2 := c.broker.registry.queues.get(sid)) is not None
            and len(q2.offline) == 3))
        assert sorted(m.payload for m in
                      c.broker.registry.queues[sid].offline) == \
            [b"s0", b"s1", b"s2"]
    finally:
        await stop_cluster(nodes)


@pytest.mark.asyncio
async def test_migration_survives_cluster_recv_faults():
    """migrate_offline_queues under a lossy channel: cluster.recv
    faults drop inbound `enq`/ack batches; the bounded retry loop
    re-ships the unacked tail until every message lands — QoS1
    at-least-once, zero loss."""
    from vernemq_tpu.robustness import faults
    from vernemq_tpu.robustness.faults import FaultPlan, FaultRule

    nodes = await make_cluster(2, remote_enqueue_timeout=300,
                               max_drain_time=50,
                               max_msgs_per_drain_step=3)
    try:
        a, b = nodes
        sid = ("", "lossy")
        cl = await connected(a, "lossy", clean_start=False)
        await cl.subscribe("lossy/#", qos=1)
        await cl.disconnect()
        pub = await connected(a, "lossy-pub")
        sent = {b"l%d" % i for i in range(12)}
        for i in range(12):
            await pub.publish(f"lossy/{i}", b"l%d" % i, qos=1)
        await pub.disconnect()
        await wait_until(lambda: len(
            a.broker.registry.queues[sid].offline) == 12)

        faults.install(FaultPlan([FaultRule(
            "cluster.recv", kind="error", probability=0.4, count=8)],
            seed=11))
        try:
            moved = await a.cluster.migrate_offline_queues(
                ["node1"], timeout=30.0)
        finally:
            faults.clear()
        assert moved == 1
        await wait_until(lambda: sid not in a.broker.registry.queues
                         and sid not in a.broker.migrations)
        q2 = b.broker.registry.queues[sid]
        # at-least-once across retries: every payload present, dupes OK
        assert {m.payload for m in q2.offline} == sent
        assert a.broker.metrics.value("queue_migrated") == 1
    finally:
        await stop_cluster(nodes)
