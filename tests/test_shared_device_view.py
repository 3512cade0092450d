"""Shared subscriptions under the device view, end to end.

A broker whose reg view is the device matcher (the CPU backend here), a
share group of 300 live members (more than ``tpu_max_fanout`` 256 rows a
publish would be, were a member a row) and one of 3, each with members
that left (persistent sessions whose queue stays offline): every QoS 1
publish to a group's filter reaches exactly ONE member of that group, and
an online one, under each of the three policies. Publishes go out in
bursts of more than ``tpu_host_batch_threshold``, so once the match
program is warm the device answers them."""

import asyncio
import time

import pytest

from vernemq_tpu.broker.config import Config
from vernemq_tpu.broker.server import start_broker
from vernemq_tpu.client import MQTTClient

GROUPS = {"big": 300, "small": 3}
LEFT = 2            # members of each group that disconnected
PUBLISHERS = 12     # one burst: more than the host threshold of 8
ROUNDS = 4          # bursts after the device served one


async def _client(port, cid, clean=True):
    c = MQTTClient("127.0.0.1", port, client_id=cid, clean_start=clean)
    assert (await c.connect()).rc == 0
    return c


async def _drain(members, want):
    """Every member's messages until ``want`` arrived or nothing more
    does: {payload: [member, ...]}."""
    got = {}
    deadline = time.monotonic() + 10.0
    while sum(len(v) for v in got.values()) < want \
            and time.monotonic() < deadline:
        await asyncio.sleep(0.05)
        for cid, c in members.items():
            while not c.messages.empty():
                frame = c.messages.get_nowait()
                if frame is not None:
                    got.setdefault(bytes(frame.payload), []).append(cid)
    await asyncio.sleep(0.2)    # a duplicate would land meanwhile
    for cid, c in members.items():
        while not c.messages.empty():
            frame = c.messages.get_nowait()
            if frame is not None:
                got.setdefault(bytes(frame.payload), []).append(cid)
    return got


@pytest.mark.asyncio
@pytest.mark.parametrize("policy", ["prefer_local", "random", "local_only"])
async def test_each_publish_reaches_one_online_member_of_each_group(policy):
    broker, server = await start_broker(Config(
        default_reg_view="tpu", allow_anonymous=True, systree_enabled=False,
        shared_subscription_policy=policy), port=0)
    port = server.port
    live = {g: {} for g in GROUPS}
    pubs = []
    try:
        for g, n in GROUPS.items():
            for i in range(LEFT):
                c = await _client(port, f"{g}-left{i}", clean=False)
                await c.subscribe(f"$share/{g}/{g}/#", qos=1)
                await c.disconnect()
            for i in range(n):
                c = await _client(port, f"{g}-m{i}")
                await c.subscribe(f"$share/{g}/{g}/#", qos=1)
                live[g][c.client_id] = c
        for i in range(PUBLISHERS):
            pubs.append(await _client(port, f"pub{i}"))
        view = broker.registry.reg_view("tpu")
        sent = {g: 0 for g in GROUPS}
        rounds = served = 0
        deadline = time.monotonic() + 12.0
        while rounds < ROUNDS:
            for g in GROUPS:
                acks = await asyncio.gather(*[
                    p.publish(f"{g}/{i}", f"{g}:{sent[g] + i}".encode(),
                              qos=1)
                    for i, p in enumerate(pubs)])
                assert all(a is not None for a in acks)
                sent[g] += PUBLISHERS
            m = view._matchers.get("")
            served = m.match_publishes if m is not None else 0
            if served or time.monotonic() > deadline:
                rounds += 1
            else:
                await asyncio.sleep(0.2)
        assert served > 0, "the device never answered a burst"
        for g in GROUPS:
            got = await _drain(live[g], sent[g])
            want = {f"{g}:{k}".encode() for k in range(sent[g])}
            assert set(got) == want, (g, len(got), len(want))
            assert all(len(v) == 1 for v in got.values()), g
        for g in GROUPS:
            for i in range(LEFT):
                q = broker.registry.queues[("", f"{g}-left{i}")]
                assert not q.offline, (g, i, len(q.offline))
    finally:
        for c in pubs + [c for g in live.values() for c in g.values()]:
            await c.close()
        await broker.stop()
        await server.stop()
