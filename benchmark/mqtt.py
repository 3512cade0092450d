"""MQTT 3.1.1 on raw bytes: just the packets the load generator speaks.

Written from the specification (OASIS MQTT 3.1.1, section 2 and 3), not
from the program's codec, so the generator cannot share a fault with the
broker's parser.
"""

from __future__ import annotations

import struct
from typing import List, Tuple

CONNACK, PUBLISH, PUBACK, SUBACK, PINGRESP = 2, 3, 4, 9, 13


def _remaining(n: int) -> bytes:
    out = bytearray()
    while True:
        d, n = n & 0x7F, n >> 7
        out.append(d | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _str(s: bytes) -> bytes:
    return struct.pack(">H", len(s)) + s


def connect(client_id: str, clean_session: bool, keepalive: int = 0) -> bytes:
    body = (_str(b"MQTT") + bytes([4, 0x02 if clean_session else 0x00])
            + struct.pack(">H", keepalive) + _str(client_id.encode()))
    return bytes([0x10]) + _remaining(len(body)) + body


def subscribe(packet_id: int, filters: List[Tuple[str, int]]) -> bytes:
    body = struct.pack(">H", packet_id) + b"".join(
        _str(f.encode()) + bytes([q]) for f, q in filters)
    return bytes([0x82]) + _remaining(len(body)) + body


def publish_head(topic: bytes, qos: int, payload_len: int) -> bytes:
    """Fixed header + topic of a PUBLISH; the caller appends the packet
    id (QoS 1) and the payload."""
    n = 2 + len(topic) + (2 if qos else 0) + payload_len
    return bytes([0x30 | (qos << 1)]) + _remaining(n) + _str(topic)


def puback(packet_id: int) -> bytes:
    return struct.pack(">BBH", 0x40, 2, packet_id)


DISCONNECT = bytes([0xE0, 0])


def frames(buf: bytes, start: int = 0):
    """Yield ``(first_byte, body_offset, end_offset)`` of every whole
    packet in ``buf`` from ``start``; the caller keeps ``buf[last_end:]``.
    The general decoder (the subscriber's read loop inlines the one-byte
    length case for speed and falls back to this)."""
    i, n = start, len(buf)
    while i + 2 <= n:
        mult, length, j = 1, 0, i + 1
        while True:
            if j >= n:
                return
            d = buf[j]
            j += 1
            length += (d & 0x7F) * mult
            if not d & 0x80:
                break
            mult <<= 7
            if mult > (1 << 21):
                raise ValueError("malformed remaining length")
        if j + length > n:
            return
        yield buf[i], j, j + length
        i = j + length
