"""The load generator: JAX-free OS processes speaking MQTT over loopback.

Started (``multiprocessing`` spawn) by ``benchmark.run`` before that
process imports JAX; this module and what it imports never do. Two roles:

- a subscriber shard holds its share of the corpus's live sessions as raw
  sockets, reads every PUBLISH frame, acknowledges QoS 1, keeps the stamp
  of every delivery with the time the bytes were read, and at the end
  compares what it read with what the plain reference says those sessions
  were owed (``benchmark.reference``);
- a publisher shard holds its share of the mix's publisher connections and
  sends the schedule the mix file describes, an open loop: every
  ``interval_ms`` each connection sends ``burst`` publishes in one write,
  at its own phase of the interval: the connections fall into
  ``phase_groups`` groups, drawn from the seed, whose phases divide the
  interval evenly (1: all on the same tick; 0: a group each, the
  smoothest arrival), each publish stamped with the time it was DUE and
  sent whether or not the broker kept up. PUBACKs (QoS 1) are
  read and counted, never waited for.

Every payload starts with the stamp ``<QHIH``: nanoseconds on the
system-wide monotonic clock (the time the publish was due), publisher,
sequence number, a magic. The parent speaks to a
shard over a pipe: ``connect``, ``start``, (publishers report), ``finish``,
``exit``.
"""

from __future__ import annotations

import selectors
import socket
import struct
import time
import traceback
from typing import Dict, List

import numpy as np

from . import mqtt, reference
from .corpus import build

STAMP = struct.Struct("<QHIH")
MAGIC = 0xB3C4
RECORD = np.dtype([("t", "<u8"), ("pub", "<u2"), ("seq", "<u4"),
                   ("magic", "<u2"), ("b0", "u1")])
CONNECT_TIMEOUT_S = 60.0
CHUNK = 64              # publishes of a connection whose topics are drawn at once
DUP = 0x08              # fixed-header flag of a QoS 1 redelivery (spec 3.3.1.1)


def _open(host: str, port: int) -> socket.socket:
    s = socket.create_connection((host, port), timeout=CONNECT_TIMEOUT_S)
    s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    return s


def _read_packet(s: socket.socket, buf: bytearray):
    """Blocking: the next whole packet of a connection being set up."""
    while True:
        for b0, body, end in mqtt.frames(buf):
            pkt = bytes(buf[:end])
            del buf[:end]
            return b0, pkt[body:]
        chunk = s.recv(65536)
        if not chunk:
            raise ConnectionError("closed by the broker during set-up")
        buf += chunk


def _handshake(host, port, client_id, clean, filters=()):
    """CONNECT (+ SUBSCRIBE); returns (socket, session_present). A
    CONNACK "server unavailable" (the overload governor refuses CONNECTs
    at level 3) is tried again, as a device would, for a minute."""
    deadline = time.monotonic() + CONNECT_TIMEOUT_S
    while True:
        s = _open(host, port)
        buf = bytearray()
        s.sendall(mqtt.connect(client_id, clean))
        b0, body = _read_packet(s, buf)
        if b0 >> 4 == mqtt.CONNACK and body[1] == 0:
            break
        s.close()
        if body[1] != 3 or time.monotonic() > deadline:
            raise ConnectionError(
                f"{client_id}: CONNACK refused {bytes(body)!r}")
        time.sleep(0.5)
    present = bool(body[0] & 1)
    if filters:
        s.sendall(mqtt.subscribe(1, list(filters)))
        b0, body = _read_packet(s, buf)
        if b0 >> 4 != mqtt.SUBACK or 0x80 in body[2:]:
            raise ConnectionError(f"{client_id}: SUBACK {bytes(body)!r}")
    if buf:
        raise ConnectionError(f"{client_id}: bytes before any publish")
    return s, present


# ------------------------------------------------------------ subscribers

class SubscriberShard:
    def __init__(self, spec: dict) -> None:
        self.spec = spec
        self.config = spec["config"]
        self.corpus = build(self.config, spec["seed"])
        self.sessions = self.corpus.live[spec["shard"]::spec["shards"]]
        # shared subscriptions are owed to no process: all of them, and
        # where this process's sessions stand among the live ones
        self.shared = reference.Shared(self.corpus.live)
        self.live_index = np.arange(spec["shard"], len(self.corpus.live),
                                    spec["shards"], dtype=np.int64)
        self.sel = selectors.DefaultSelector()
        self.socks: List[socket.socket] = []
        self.rest: List[bytes] = []
        self.stamps = bytearray()      # RECORD per delivery, arrival order
        self.chunk_sub: List[int] = []  # per read: session, time, frames
        self.chunk_t: List[int] = []
        self.chunk_n: List[int] = []
        self.received = 0
        self.closed: List[str] = []
        self.last_frame = time.monotonic()

    def connect(self) -> dict:
        absent = 0
        for idx, sess in enumerate(self.sessions):
            s, present = _handshake(self.spec["host"], self.spec["port"],
                                    sess.client_id, sess.clean_session,
                                    sess.tcp_filters)
            absent += bool(sess.stored) and not present
            s.setblocking(False)
            self.sel.register(s, selectors.EVENT_READ, idx)
            self.socks.append(s)
            self.rest.append(b"")
        return {"connected": len(self.socks), "stored_session_absent": absent}

    def pump(self, timeout: float) -> None:
        for key, _ in self.sel.select(timeout):
            idx = key.data
            s = key.fileobj
            try:
                data = s.recv(1 << 18)
            except BlockingIOError:
                continue
            except OSError:
                data = b""
            if not data:
                self.closed.append(self.sessions[idx].client_id)
                self.sel.unregister(s)
                continue
            self._frames(idx, s, data, time.monotonic_ns())

    def _frames(self, idx: int, s, data: bytes, now: int) -> None:
        rest = self.rest[idx]
        if rest:
            data = rest + data
        stamps = self.stamps
        acks = bytearray()
        i, n, count = 0, len(data), 0
        while i + 2 <= n:
            b0 = data[i]
            length = data[i + 1]
            if length & 0x80:  # not ours: more than 127 bytes
                j = None
                for b0, body, end in mqtt.frames(data, i):
                    j = (body, end)
                    break
                if j is None:
                    break
                body, end = j
            else:
                body, end = i + 2, i + 2 + length
                if end > n:
                    break
            if b0 >> 4 == mqtt.PUBLISH:
                p = body + 2 + ((data[body] << 8) | data[body + 1])
                if b0 & 0x06:
                    acks += b"\x40\x02" + data[p:p + 2]
                    p += 2
                stamps += data[p:p + 16]
                stamps.append(b0)
                count += 1
            i = end
        self.rest[idx] = data[i:] if i < n else b""
        if acks:
            try:
                s.send(acks)  # 4 bytes a delivery: never fills a buffer
            except OSError:
                pass
        if count:
            self.chunk_sub.append(idx)
            self.chunk_t.append(now)
            self.chunk_n.append(count)
            self.received += count
            self.last_frame = time.monotonic()

    # ---- the comparison

    def owed(self, fin: dict) -> dict:
        """What the reference says is owed, computed once the schedule is
        known: how many deliveries to this process's own sessions, and how
        many to shared subscriptions — whichever process's sockets read
        those. The parent waits for the sum over all processes
        (``Generator.finish``) while this one goes on reading."""
        corpus = self.corpus
        sizes = [len(pool) for pool in corpus.pools]
        n_sent = fin["n_sent"]
        n_pub = max(n_sent, default=-1) + 1
        sent_of = np.zeros(n_pub + 1, np.int64)   # the last row: unknown
        base_of = np.zeros(n_pub + 1, np.int64)
        levels, pub_of, seq_of = [], [], []
        at = 0
        for p, n in sorted(n_sent.items()):
            sent_of[p], base_of[p] = n, at
            at += n
            levels.append(corpus.topics(p, 0, n))
            pub_of.append(np.full(n, p, np.int64))
            seq_of.append(np.arange(n, dtype=np.int64))
        levels = (np.concatenate(levels) if levels
                  else np.zeros((0, len(sizes)), np.int32))
        pub_of = np.concatenate(pub_of) if pub_of else np.zeros(0, np.int64)
        seq_of = np.concatenate(seq_of) if seq_of else np.zeros(0, np.int64)
        trie = reference.session_trie(self.sessions, self.shared)
        exp = reference.expected_keys(trie, corpus.pools, sizes, levels,
                                      pub_of, seq_of, fin["pub_qos"])
        self.schedule = (fin, sizes, sent_of, base_of, levels, exp)
        n_shared = int(np.count_nonzero(self.shared.is_shared(exp)))
        self.last_frame = max(self.last_frame, time.monotonic())
        return {"owed": len(exp) - n_shared, "owed_shared": n_shared}

    def progress(self) -> dict:
        return {"received": self.received,
                "quiet_s": time.monotonic() - self.last_frame}

    def finish(self) -> dict:
        """After the wait: late was late, what is still absent is lost."""
        fin, sizes, sent_of, base_of, levels, exp = self.schedule
        n_sent, n_pub = fin["n_sent"], len(sent_of) - 1
        w0, w1 = fin["window_ns"]
        t_verdict = time.monotonic_ns()
        rec = np.frombuffer(bytes(self.stamps), RECORD)
        sub = np.repeat(np.asarray(self.chunk_sub, np.int64),
                        np.asarray(self.chunk_n, np.int64))
        t_rx = np.repeat(np.asarray(self.chunk_t, np.int64),
                         np.asarray(self.chunk_n, np.int64))
        pub = rec["pub"].astype(np.int64)
        seq = rec["seq"].astype(np.int64)
        qos = ((rec["b0"] >> 1) & 3).astype(np.int64)
        ours = rec["magic"] == MAGIC
        # a QoS 1 delivery sent again with DUP set is the protocol's own
        # "at least once": counted, and left out of the multisets
        again = ours & (qos > 0) & ((rec["b0"] & DUP) != 0)
        rkeys = reference.key(sub, pub, np.minimum(qos, 1), seq)
        rkeys = np.where(ours, rkeys, -1)  # a frame without our stamp
        exp, got, share_exp, share_got, by_member = reference.attribute(
            self.shared, exp, rkeys[~again], self.live_index,
            fin["pub_qos"])
        cmp = reference.compare(exp, got)
        # ordering, over deliveries of publishes the schedule knows
        pub_c = np.minimum(pub, n_pub)
        known = ours & ~again & (seq < sent_of[pub_c])
        tid_all = reference.topic_ids(levels, sizes)
        row = (base_of[pub_c] + seq)[known]
        bad_order = reference.misordered(sub[known], pub[known], qos[known],
                                         tid_all[row], seq[known])
        # the window: publishes stamped inside it
        stamp_of = fin["stamps"]  # per publisher, ns per sequence number
        st_all = (np.concatenate([stamp_of[p] for p in sorted(n_sent)])
                  if n_sent else np.zeros(0, np.int64))
        in_w_row = (st_all >= w0) & (st_all < w1)
        t_pub = rec["t"].astype(np.int64)
        rec_in_w = known & (t_pub >= w0) & (t_pub < w1)
        lat_ms = ((t_rx[rec_in_w] - t_pub[rec_in_w]) / 1e6).astype(np.float32)
        exp_pub, exp_seq = reference.pub_seq(exp)
        exp_row = base_of[exp_pub] + exp_seq
        share_pub, share_seq = reference.pub_seq(share_exp)
        share_row = base_of[share_pub] + share_seq
        # further windows of one run (a sweep's steps): latencies and
        # counts only
        steps = []
        for a, b in fin.get("more_windows", ()):
            inside = known & (t_pub >= a) & (t_pub < b)
            steps.append({
                "lat_ms": ((t_rx[inside] - t_pub[inside]) / 1e6
                           ).astype(np.float32),
                "due_s": ((t_pub[inside] - a) / 1e9).astype(np.float32),
                "owed": int(np.count_nonzero(
                    (st_all[exp_row] >= a) & (st_all[exp_row] < b))),
                # the same in every process: the parent counts it once
                "owed_shared": int(np.count_nonzero(
                    (st_all[share_row] >= a) & (st_all[share_row] < b)))})

        def pubseq_in_window(keys: np.ndarray) -> np.ndarray:
            """(publisher, sequence) of ``keys`` whose publish lies in the
            window, packed publisher << 36 | sequence."""
            p, q = reference.pub_seq(keys)
            inside = in_w_row[base_of[p] + q]
            return np.unique((p[inside] << 36) | q[inside])

        return {
            "owed": cmp["owed"],
            "owed_in_window": int(np.count_nonzero(in_w_row[exp_row])),
            "received": int(len(rec)),
            "received_in_window": int(np.count_nonzero(rec_in_w)),
            "redelivered_with_dup": int(np.count_nonzero(again)),
            "lost_qos1": cmp["lost_qos1"], "lost_qos0": cmp["lost_qos0"],
            "duplicates": cmp["duplicates"], "strays": cmp["strays"],
            "misordered": bad_order,
            "lat_ms": lat_ms,
            "failed_pubseq": np.unique(np.concatenate([
                pubseq_in_window(cmp["short_keys"]),
                pubseq_in_window(cmp["over_keys"])])),
            "verdict_ns": t_verdict, "steps": steps,
            "closed": self.closed[:8], "n_closed": len(self.closed),
            "examples": {k: [int(x) for x in cmp[k][:4]] for k in
                         ("short_keys", "over_keys", "stray_keys")},
            # for the parent, which sees every process's: what shared
            # subscriptions are owed (the same in each process) and what
            # this one's sockets read for them
            "share_owed": share_exp, "share_received": share_got,
            "share_by_member": by_member,
            "shares": [[g, "/".join(f), len(self.shared.members(i))]
                       for i, (g, f) in enumerate(self.shared.subs)],
        }

    def close(self) -> None:
        for s in self.socks:
            try:
                s.close()
            except OSError:
                pass
        self.sel.close()


# ------------------------------------------------------------- publishers

class _Conn:
    __slots__ = ("pub", "sock", "seq", "pid", "acked", "rest", "stamps",
                 "chunk", "levels", "alive", "phase")

    def __init__(self, pub: int, sock: socket.socket) -> None:
        self.pub, self.sock = pub, sock
        self.seq = 0
        self.pid = 0
        self.acked = 0
        self.rest = 0          # bytes of a PUBACK split across reads
        self.stamps: List[int] = []
        self.chunk = -1
        self.levels = None
        self.alive = True
        self.phase = 0         # ns into the interval at which it sends


class PublisherShard:
    def __init__(self, spec: dict) -> None:
        self.spec = spec
        self.config = spec["config"]
        self.mix = spec["mix"]
        self.qos = int(self.mix["qos"])
        self.corpus = build(self.config, spec["seed"])
        self.words = [[w.encode() for w in pool]
                      for pool in self.corpus.pools]
        fill = int(self.config["payload_bytes"]) - STAMP.size
        if fill < 0:
            raise ValueError("payload_bytes is less than the stamp's 16")
        self.fill = b"x" * fill
        self.heads: Dict[tuple, bytes] = {}
        self.conns: List[_Conn] = []
        self.late_ms: List[float] = []
        self.sel = selectors.DefaultSelector()

    def connect(self) -> dict:
        n = connections(self.mix, self.corpus)
        for p in range(self.spec["shard"], n, self.spec["shards"]):
            s, _ = _handshake(self.spec["host"], self.spec["port"],
                              f"bench-pub{p}", True)
            c = _Conn(p, s)
            self.sel.register(s, selectors.EVENT_READ, c)
            self.conns.append(c)
        return {"connected": len(self.conns)}

    def _frames(self, c: _Conn, n: int, stamp_ns: int) -> bytes:
        """The next ``n`` publishes of a connection, one buffer."""
        out = []
        qos, words = self.qos, self.words
        plen = STAMP.size + len(self.fill)
        for _ in range(n):
            seq = c.seq
            if seq // CHUNK != c.chunk:
                c.chunk = seq // CHUNK
                c.levels = [tuple(r) for r in self.corpus.topics(
                    c.pub, c.chunk * CHUNK, CHUNK).tolist()]
            at = c.levels[seq % CHUNK]
            head = self.heads.get(at)
            if head is None:
                head = self.heads[at] = mqtt.publish_head(
                    b"/".join(w[k] for w, k in zip(words, at)), qos, plen)
            out.append(head)
            if qos:
                c.pid = c.pid % 65535 + 1
                out.append(struct.pack(">H", c.pid))
            out.append(STAMP.pack(stamp_ns, c.pub, seq, MAGIC))
            out.append(self.fill)
            c.stamps.append(stamp_ns)
            c.seq = seq + 1
        return b"".join(out)

    def _send(self, c: _Conn, data: bytes) -> None:
        try:
            c.sock.sendall(data)
        except OSError:
            c.alive = False  # the broker closed it (governor level 3)

    def _read(self, timeout: float) -> None:
        """PUBACKs (4 bytes each) of whatever connections have some."""
        for key, _ in self.sel.select(timeout):
            c = key.data
            try:
                data = c.sock.recv(1 << 16)
            except OSError:
                data = b""
            if not data:
                c.alive = False
                self.sel.unregister(c.sock)
                continue
            got = c.rest + len(data)
            c.acked += got // 4
            c.rest = got % 4

    def run(self, start: dict) -> dict:
        t_begin = start["t0_ns"]
        t_stop = t_begin + int((start["warm_s"] + start["seconds"]) * 1e9)
        interval = int(float(self.mix["interval_ms"]) * 1e6)
        active = int(start.get("active") or connections(self.mix,
                                                        self.corpus))
        burst = int(self.mix["burst"])
        sent0 = {c.pub: c.seq for c in self.conns}
        acked0 = {c.pub: c.acked for c in self.conns}
        # the groups' phases divide the interval evenly, and the seed
        # draws who is in which: every seed offers the same arrivals
        groups = int(self.mix["phase_groups"]) or active
        rng = np.random.Generator(np.random.PCG64(
            [int(self.spec["seed"]), active, 0x9A5E]))
        slot = rng.permutation(active) % groups
        order = []
        for c in self.conns:
            if c.pub < active and c.alive:
                c.phase = int(slot[c.pub]) * interval // groups
                order.append(c)
        order.sort(key=lambda c: (c.phase, c.pub))
        cpu0 = time.process_time()
        k, sends = 0, 0
        while order:
            base = t_begin + k * interval
            if base >= t_stop:
                break
            for c in order:
                due = base + c.phase
                if due >= t_stop:
                    break
                wait = due - time.monotonic_ns()
                while wait > 200_000:  # acks are read while there is time
                    self._read(wait / 1e9)
                    wait = due - time.monotonic_ns()
                if c.alive:
                    data = self._frames(c, burst, due)
                    self.late_ms.append((time.monotonic_ns() - due) / 1e6)
                    self._send(c, data)
                    sends += 1
                    if not sends & 63:
                        self._read(0)
            k += 1
        # every PUBACK is waited for, as long as the bound allows
        deadline = time.monotonic() + float(start["ack_wait_s"])
        while self.qos and time.monotonic() < deadline and any(
                c.alive and c.acked < c.seq for c in self.conns):
            self._read(0.05)
        cpu = time.process_time() - cpu0
        return {
            "n_sent": {c.pub: c.seq for c in self.conns},
            "n_acked": {c.pub: c.acked for c in self.conns},
            "step_sent": sum(c.seq - sent0[c.pub] for c in self.conns),
            "step_acked": sum(c.acked - acked0[c.pub] for c in self.conns),
            "stamps": {c.pub: np.asarray(c.stamps, np.int64)
                       for c in self.conns},
            "lost_connections": [c.pub for c in self.conns if not c.alive],
            "late_ms": np.asarray(self.late_ms, np.float32),
            "cpu_s": cpu,
            "wall_s": (time.monotonic_ns() - t_begin) / 1e9,
        }

    def close(self) -> None:
        for c in self.conns:
            try:
                c.sock.sendall(mqtt.DISCONNECT)
                c.sock.close()
            except OSError:
                pass
        self.sel.close()


def connections(mix: dict, corpus) -> int:
    """Publisher connections of a mix: a number, or one for each live
    session of the corpus (point to point) — for a corpus whose publishers
    are not its subscribers' pairs, as many as it says."""
    n = mix["connections"]
    if n != "one_per_live_session":
        return int(n)
    return len(corpus.live) if corpus.publishers is None \
        else corpus.publishers


# ------------------------------------------------------------- the shard

def main(conn, role: str, spec: dict) -> None:
    """Entry of a load-generator process. Every reply is ``(ok, value)``;
    an exception travels back as text and the parent fails the run."""
    shard = None
    try:
        shard = (SubscriberShard if role == "sub" else PublisherShard)(spec)
        conn.send((True, "ready"))
        while True:
            if role == "sub" and not conn.poll(0):
                if shard.socks:
                    shard.pump(0.05)
                else:
                    time.sleep(0.01)
                continue
            cmd, arg = conn.recv()
            if cmd == "connect":
                spec["port"] = arg
                conn.send((True, shard.connect()))
            elif cmd == "start":
                conn.send((True, shard.run(arg)))
            elif cmd == "owed":
                conn.send((True, shard.owed(arg)))
            elif cmd == "progress":
                conn.send((True, shard.progress()))
            elif cmd == "finish":
                conn.send((True, shard.finish()))
            elif cmd == "exit":
                break
    except BaseException:  # reported, then the process ends
        try:
            conn.send((False, traceback.format_exc()))
        except OSError:
            pass
    finally:
        if shard is not None:
            shard.close()
        conn.close()
