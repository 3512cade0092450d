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
"""

from __future__ import annotations

import asyncio
import struct
from typing import Dict, Optional, Tuple

from . import mqtt
from .reference import FilterTrie

BREAKS = ("lose_qos1", "lose_tail", "duplicate", "stray", "reorder",
          "no_ack")


class _Session(asyncio.Protocol):
    def __init__(self, broker: "ReferenceBroker") -> None:
        self.broker = broker
        self.buf = b""
        self.client_id: Optional[str] = None
        self.transport = None
        self.pid = 0
        self.published = 0
        self.held = None       # a publish held back (``reorder``)

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
                    b.publish(self, b0, body)
                    b.publish(self, *held)
                elif b.broken("reorder"):
                    self.held = (b0, body)
                    asyncio.get_running_loop().call_later(5.0, self._unhold)
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
            stored = False
        b.online[self.client_id] = self
        self.transport.write(bytes([0x20, 2, int(stored), 0]))

    def _subscribe(self, body: bytes) -> None:
        pid, at, granted = body[:2], 2, bytearray()
        mine = self.broker.subs.setdefault(self.client_id, {})
        while at < len(body):
            n = struct.unpack_from(">H", body, at)[0]
            words = tuple(body[at + 2:at + 2 + n].decode().split("/"))
            qos = body[at + 2 + n]
            if words not in mine:
                self.broker.trie.add(words, (self.client_id, words))
            mine[words] = qos
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
        """A row of the persisted subscriber DB."""
        mine = self.subs.setdefault(client_id, {})
        if words not in mine:
            self.trie.add(words, (client_id, words))
        mine[words] = qos

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

    def publish(self, src: _Session, b0: int, body: bytes) -> None:
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
        rows = [(cid, self.subs.get(cid, {}).get(f))
                for cid, f in self.trie.match(words)]
        if self.broken("stray"):
            other = next((c for c in self.online
                          if c != src.client_id
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
