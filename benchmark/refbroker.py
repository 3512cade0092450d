"""The plain reference put in the program's place: a broker of a hundred
lines that routes with ``reference.FilterTrie`` and keeps MQTT 3.1.1's
delivery rules, and can be told to break ONE guarantee the configuration
states. It is the control of the comparison that decides ``correct``
(``benchmark.control`` runs a cell against it and must see ``correct``
come out false) and the stand-in the package's own tests drive the
harness with, on a machine with no accelerator. It imports nothing of the
program and nothing of JAX, and is never part of a benchmark run.

Breaks (``every`` = one in how many): ``lose_qos1`` drops a QoS 1
delivery without a trace; ``lose_tail`` serves the first ``every``
publishes of each connection and none after (acknowledged all the same);
``duplicate`` writes a delivery twice; ``stray`` hands a publish to a
session no filter of which matches; ``reorder`` holds a publish back and
serves it after the same connection's next; ``no_ack`` withholds a
PUBACK.

A subscription ``$share/<group>/<filter>`` makes its session a member of
the shared subscription (``reference``'s rule): a matching publish goes
to ONE of its members that hold a connection, drawn by a seeded
generator, at that member's QoS. Two breaks are the group's own:
``share_twice`` hands a group's publish to two of its members;
``share_dead`` hands it to nobody while a member stands connected. Under
``reorder`` the held publish goes to the member that took the one served
before it: across members a group has no order to break.
"""

from __future__ import annotations

import asyncio
import random
import struct
from typing import Dict, Optional, Tuple

from . import mqtt
from .reference import FilterTrie, split_share

BREAKS = ("lose_qos1", "lose_tail", "duplicate", "stray", "reorder",
          "no_ack", "share_twice", "share_dead")
SHARE = object()  # in the trie: a shared subscription, not a client's
#: how long ``reorder`` holds a publish for the connection's next: past a
#: tick of either mix, inside the control's wait for PUBACKs (5 s), so the
#: last tick's held publishes are served late and acknowledged, not never
HOLD_S = 2.0


class _Session(asyncio.Protocol):
    def __init__(self, broker: "ReferenceBroker") -> None:
        self.broker = broker
        self.buf = b""
        self.client_id: Optional[str] = None
        self.transport = None
        self.pid = 0
        self.published = 0
        self.held = None       # a publish held back (``reorder``)
        self.unhold = None     # the timer that serves it if no next comes

    def connection_made(self, transport) -> None:
        self.transport = transport

    def connection_lost(self, exc) -> None:
        if self.broker.online.get(self.client_id) is self:
            del self.broker.online[self.client_id]

    def data_received(self, data: bytes) -> None:
        buf = self.buf + data if self.buf else data
        packets, end = [], 0
        for b0, body, end in mqtt.frames(buf):
            packets.append((b0, buf[body:end]))
        self.buf = buf[end:]
        b = self.broker
        for b0, body in packets:
            kind = b0 >> 4
            if kind == mqtt.PUBLISH:
                if self.held is not None:
                    held, self.held = self.held, None
                    self.unhold.cancel()
                    b.publish(self, b0, body)
                    b.publish(self, *held, same_member=True)
                elif b.broken("reorder"):
                    self.held = (b0, body)
                    self.unhold = asyncio.get_running_loop().call_later(
                        HOLD_S, self._unhold)
                else:
                    b.publish(self, b0, body)
            elif kind == 1:
                self._connect(body)
            elif kind == 8:
                self._subscribe(body)
            elif kind == 12:
                self.transport.write(bytes([0xD0, 0]))
            elif kind == 14:
                self.transport.close()

    def _unhold(self) -> None:
        """No next publish came: the held one is served late, not never."""
        if self.held is not None:
            held, self.held = self.held, None
            self.broker.publish(self, *held)

    def _connect(self, body: bytes) -> None:
        n = struct.unpack_from(">H", body, 0)[0]
        flags = body[2 + n + 1]
        at = 2 + n + 4
        m = struct.unpack_from(">H", body, at)[0]
        self.client_id = body[at + 2:at + 2 + m].decode()
        b = self.broker
        stored = self.client_id in b.subs
        if flags & 0x02:
            b.subs[self.client_id] = {}
            for members in b.shares.values():
                members.pop(self.client_id, None)
            stored = False
        b.online[self.client_id] = self
        self.transport.write(bytes([0x20, 2, int(stored), 0]))

    def _subscribe(self, body: bytes) -> None:
        pid, at, granted = body[:2], 2, bytearray()
        while at < len(body):
            n = struct.unpack_from(">H", body, at)[0]
            words = tuple(body[at + 2:at + 2 + n].decode().split("/"))
            qos = body[at + 2 + n]
            self.broker.store(self.client_id, words, qos)
            granted.append(qos)
            at += 3 + n
        self.transport.write(bytes([0x90, 2 + len(granted)]) + pid
                             + bytes(granted))


class ReferenceBroker:
    def __init__(self, break_: Optional[str] = None, every: int = 97
                 ) -> None:
        if break_ is not None and break_ not in BREAKS:
            raise ValueError(f"unknown break {break_!r}")
        self.break_, self.every = break_, every
        self._n: Dict[str, int] = {}
        self.trie = FilterTrie()
        self.subs: Dict[str, Dict[Tuple[str, ...], int]] = {}
        #: (group, filter) -> member client id -> QoS
        self.shares: Dict[tuple, Dict[str, int]] = {}
        self.pick = random.Random(0x5A4E)
        self._last: Dict[tuple, str] = {}  # the member each group served
        self.online: Dict[str, _Session] = {}
        self.server = None
        self.publishes = 0

    def broken(self, what: str, applies: bool = True) -> bool:
        """True once in ``every`` chances of the break in force."""
        if self.break_ != what or not applies:
            return False
        n = self._n[what] = self._n.get(what, 0) + 1
        return n % self.every == 0

    def store(self, client_id: str, words, qos: int) -> None:
        """A subscription: a SUBSCRIBE's, or a row of the persisted
        subscriber DB. A shared one is ONE value in the trie under the
        group's filter, whoever its members are."""
        words = tuple(words)
        share = split_share(words)
        if share is not None:
            if share not in self.shares:
                self.trie.add(share[1], (SHARE, share))
            self.shares.setdefault(share, {})[client_id] = qos
            return
        mine = self.subs.setdefault(client_id, {})
        if words not in mine:
            self.trie.add(words, (client_id, words))
        mine[words] = qos

    def _members(self, share: tuple, same_member: bool) -> list:
        """The member(s) a shared subscription's publish goes to: one
        that holds a connection (``share_twice``: two; ``share_dead``:
        none)."""
        members = self.shares[share]
        online = [c for c in members if c in self.online]
        if not online or self.broken("share_dead"):
            return []
        last = self._last.get(share)
        first = last if same_member and last in online \
            else self.pick.choice(online)
        self._last[share] = first
        out = [first]
        if self.broken("share_twice", len(online) > 1):
            out.append(self.pick.choice([c for c in online if c != first]))
        return [(c, members[c]) for c in out]

    async def start(self, host: str = "127.0.0.1") -> int:
        loop = asyncio.get_running_loop()
        self.server = await loop.create_server(lambda: _Session(self),
                                               host, 0)
        return self.server.sockets[0].getsockname()[1]

    async def stop(self) -> None:
        for s in list(self.online.values()):
            s.transport.close()
        self.server.close()
        await self.server.wait_closed()

    def publish(self, src: _Session, b0: int, body: bytes,
                same_member: bool = False) -> None:
        qos = (b0 >> 1) & 3
        n = struct.unpack_from(">H", body, 0)[0]
        topic_b = body[2:2 + n]
        at = 2 + n
        if qos:
            if not self.broken("no_ack"):
                src.transport.write(b"\x40\x02" + body[at:at + 2])
            at += 2
        payload = body[at:]
        self.publishes += 1
        src.published += 1
        if self.break_ == "lose_tail" and src.published > self.every:
            return
        words = topic_b.decode().split("/")
        rows, shares = [], []
        for cid, f in self.trie.match(words):
            if cid is SHARE:
                rows += self._members(f, same_member)
                shares.append(f)
            else:
                rows.append((cid, self.subs.get(cid, {}).get(f)))
        if self.broken("stray"):
            # a session that subscribed something, nothing of it matching
            other = next((c for c in self.online
                          if c != src.client_id and self.subs.get(c)
                          and all(c not in self.shares[f] for f in shares)
                          and all(c != cid for cid, _ in rows)), None)
            if other is not None:
                rows.append((other, 0))
        for cid, sub_qos in rows:
            sess = self.online.get(cid)
            if sess is None or sub_qos is None:
                continue
            eff = min(qos, sub_qos)
            if self.broken("lose_qos1", eff == 1):
                continue
            head = mqtt.publish_head(topic_b, eff, len(payload))
            if eff:
                sess.pid = sess.pid % 65535 + 1
                head += struct.pack(">H", sess.pid)
            sess.transport.write(head + payload)
            if self.broken("duplicate"):
                sess.transport.write(head + payload)
