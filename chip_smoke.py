#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the broker still starts on the chip.

Drives the broker's main path once, through the entry points a user calls,
on ONE TPU chip at a size its users would call real:

  MQTT bytes in on a TCP listener -> session -> Registry -> BatchCollector
  -> TpuMatcher device dispatch -> resolve -> queue -> bytes out

with 1,000,000 resident subscriptions (``build_corpus``'s mix of
exact / ``+`` / ``#`` filters, made from ``--seed``) loaded through
``Registry.subscribe``, a few real MQTT clients over TCP, publish bursts
large enough to be device-served, and every device result compared with
the host trie (``models/trie.py``) on the same registry. It FAILS when the
device did not do the work: the host-served counters of the asserted
windows must stay at zero and the breaker closed.

Run it as the driver does, with no arguments, on a machine with one chip:

    python3 chip_smoke.py

The last line of stdout is then exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

and the exit code 0. Every earlier line is one JSON object of facts
(seconds to load / upload / compile, the compile cache's directory and
its hits and misses, peak device bytes, the program's own counters, the
loop lag and the overload governor's level changes of every phase). They
are facts for CHANGES.md, not metrics: no rate is printed under a
metric's name. The broker runs at its DEFAULT configuration apart from
the reg view and its capacity: no protection is loosened to get through.

A one-chip machine shares its host's cores, and the broker answers a
stalled process as it is built to: queued publishes expire to the trie,
the overload governor throttles, drops QoS0, disconnects talkers. A
burst in whose window the broker's OWN alarm rang (loop-lag alarm,
governor above level 0) is therefore void and sent again, up to five
times; what is forgiven on a void attempt is only what those
protections produce and count. Wrong rows, a stray or duplicate
delivery, a device failure or an open breaker fail the run on any
attempt, and the last attempt forgives nothing. A witness thread says
for every stall whether the loop alone was late or the whole process.
The verdict is repeated on stderr, so the end of either stream tells.

Where JAX finds no TPU the script refuses: ``"ok": false`` and a non-zero
exit. There is no CPU default. ``--rehearse`` runs the same control flow
on the CPU backend at a tiny size so the script can be rehearsed before
chip time is spent; a rehearsal NEVER prints the TPU last line — its last
line carries ``"rehearsal": true`` and ``"platform": "cpu"``.

``--chips 4`` (builder-run; the driver gives no option) runs ONLY the
four-chip phase and what it is compared with: the broker booted with
``tpu_mesh="1x4"``, same corpus, same burst, parity against the trie and
against a single-device matcher's rows; its last line carries
``"count": 4``.

One process, and it is the only one that touches JAX.
"""

from __future__ import annotations

import argparse
import asyncio
import gc
import json
import os
import random
import sys
import threading
import time
import traceback
from typing import Any, Dict, List, Optional, Sequence, Tuple

REAL_SUBS = 1_000_000
REHEARSAL_SUBS = 20_000
MAX_BATCH = 4096          # BatchCollector.max_batch (one collector window)
WARM_BOUND_S = 900.0      # bound on any wait for compiles
#: a K-window super-batch forms only while publishes arrive faster than
#: two pipeline slots serve them: six windows keep that up long enough
#: for a backlog of more than one window to stand when a slot frees
SUPER_BURST = 6 * MAX_BATCH
#: on the CPU backend the "device" is slow: a backlog forms at once
REHEARSAL_SUPER_BURST = 3 * MAX_BATCH + 1024
#: a burst comes from a fleet: it is spread over publisher connections,
#: at most this many publishes (~45 KB of frames) each, one frame per
#: write. More than that written frame by frame into ONE socket in one
#: stretch stalls in the chip machine's TCP stack, not in the broker:
#: thousands of 45-byte segments back to back and the tail arrives at
#: ~10 segments/s (measured there on the host trie view, governor at
#: level 0, nothing throttled; the same bytes as one write arrive in
#: 0.08 s — PERF.md, PR 23). One connection carrying a whole window
#: alone is its own phase (fat_connection) and writes it as one buffer.
PUBS_PER_CONNECTION = 1024
N_PUBLISHERS = -(-SUPER_BURST // PUBS_PER_CONNECTION)
HOST_FALLBACK_SHARE = 0.02  # per-publish exact host fallbacks tolerated
#: the fan-out check: one topic of the one-chip boot held by this many
#: subscribers (four times the default tpu_max_fanout of 256)
FANOUT_TOPIC = ("fanout", "all", "one")
FANOUT_ROWS = 1000
MESH_BURST = 64
MESH_FALLBACK_SHARE = 0.25  # slice-straddling buckets fall back by design
#: collector counters of publishes the host trie served in place of the
#: device — all must stay at zero across an asserted window
HOST_SERVED = ("busy_host_pubs", "degraded_host_pubs", "stalled_host_pubs",
               "expired_host_pubs", "rebuild_host_pubs",
               "overload_host_pubs")


def emit(**kw: Any) -> None:
    print(json.dumps(kw, default=str), flush=True)


class Checks:
    """Failed checks are collected, not raised: a run prints every fact
    it can reach and then fails as a whole."""

    def __init__(self) -> None:
        self.failed: List[str] = []

    def check(self, ok: bool, what: str, **detail: Any) -> bool:
        if not ok:
            self.failed.append(what)
            emit(check="FAILED", what=what, **detail)
        return bool(ok)


# ---------------------------------------------------------------- corpus

def build_corpus(rng: random.Random, n_subs: int, table):
    """Mixed subscription corpus over a 3-level topic tree: 64 x 256 x 64
    words, 60% exact filters, 20% ``w/+/w``, 10% ``+/w/w``, 10% ``w/w/#``.
    Writes into anything with ``add`` and returns the three word pools."""
    l0 = [f"region{i}" for i in range(64)]
    l1 = [f"dev{i}" for i in range(256)]
    l2 = [f"metric{i}" for i in range(64)]
    for i in range(n_subs):
        r = rng.random()
        w0, w1, w2 = rng.choice(l0), rng.choice(l1), rng.choice(l2)
        if r < 0.60:
            f = [w0, w1, w2]              # exact
        elif r < 0.80:
            f = [w0, "+", w2]             # single-level wildcard
        elif r < 0.90:
            f = ["+", w1, w2]
        else:
            f = [w0, w1, "#"]             # multi-level
        table.add(f, i, None)
    return l0, l1, l2


def zipf_topics(rng: random.Random, pools, n: int) -> List[Tuple[str, ...]]:
    """``n`` publish topics over the corpus's pools, Zipf-skewed."""
    def pick(pool):
        z = min(int(rng.paretovariate(1.2)) - 1, len(pool) - 1)
        return pool[z]
    l0, l1, l2 = pools
    return [(pick(l0), pick(l1), pick(l2)) for _ in range(n)]


class _Rows:
    """``build_corpus`` writes into anything with ``add``."""

    def __init__(self) -> None:
        self.rows: List[Tuple[List[str], int]] = []

    def add(self, f, i, _val) -> None:
        self.rows.append((f, i))


def make_corpus(seed: int, n: int):
    rows = _Rows()
    pools = build_corpus(random.Random(seed), n, rows)
    return rows.rows, pools


async def load_registry(registry, rows) -> float:
    """Every subscription goes in through ``Registry.subscribe`` — the
    call a SUBSCRIBE makes — so the host trie and the device table both
    derive from the registry. Yields to the loop so the broker's own
    timers (sysmon, watchdog) keep running."""
    from vernemq_tpu.protocol.types import SubOpts

    opts = (SubOpts(qos=0), SubOpts(qos=1))
    t0 = time.monotonic()
    for n, (f, i) in enumerate(rows):
        registry.subscribe(("", f"c{i}"), [(f, opts[i & 1])])
        if n % 500 == 499:
            await asyncio.sleep(0)
    return time.monotonic() - t0


# -------------------------------------------------- observing the device

class FoldTap:
    """Records what the device path returned for every publish it served:
    wraps the view's ``fold_batch`` / ``fold_many`` (the two calls the
    collector dispatches) and keeps (topic, rows) pairs, and per call
    (kind, publishes, seconds in the executor thread). Rows recorded
    here are exactly what the registry then routes."""

    def __init__(self, view) -> None:
        self.pairs: List[Tuple[Tuple[str, ...], list]] = []
        self.calls: List[Tuple[str, int, float]] = []
        fold_batch, fold_many = view.fold_batch, view.fold_many

        def tap_batch(mp, topics, *a, **k):
            t0 = time.monotonic()
            res = fold_batch(mp, topics, *a, **k)
            self.calls.append(("batch", len(topics),
                               round(time.monotonic() - t0, 3)))
            self.pairs.extend(zip(topics, res))
            return res

        def tap_many(mp, batches, *a, **k):
            t0 = time.monotonic()
            res = fold_many(mp, batches, *a, **k)
            self.calls.append(("many", sum(len(b) for b in batches),
                               round(time.monotonic() - t0, 3)))
            for topics, rows in zip(batches, res):
                self.pairs.extend(zip(topics, rows))
            return res

        view.fold_batch, view.fold_many = tap_batch, tap_many

    def take(self):
        pairs, self.pairs = self.pairs, []
        calls, self.calls = self.calls, []
        return pairs, calls


def row_set(rows) -> set:
    """(subscriber, qos) pairs of a fold result."""
    return {(key, getattr(opts, "qos", None)) for _f, key, opts in rows}


def counters(matcher, collector) -> Dict[str, int]:
    from vernemq_tpu.models import tpu_matcher  # the wide pass's totals

    out = {k: int(getattr(collector, k)) for k in HOST_SERVED}
    out.update(
        host_hybrid_pubs=collector.host_hybrid_pubs,
        super_batches=collector.super_batches,
        saturated_merges=collector.saturated_merges,
        match_batches=matcher.match_batches,
        match_publishes=matcher.match_publishes,
        super_dispatches=matcher.super_dispatches,
        host_fallbacks=matcher.host_fallbacks,
        wide_publishes=tpu_matcher.wide_publishes,
        wide_failures=tpu_matcher.wide_failures,
        warm_failures=matcher.warm_failures,
        device_failures=matcher.device_failures,
        busy_sheds=matcher.busy_sheds,
        degraded_sheds=matcher.degraded_sheds,
        dispatch_stalls=matcher.dispatch_stalls,
        rebuilds_async=matcher.rebuilds_async,
    )
    return out


def moved(before: Dict[str, int], after: Dict[str, int]) -> Dict[str, int]:
    return {k: after[k] - before[k] for k in after}


# ------------------------------------------------------------ loop lag

class LagMeter:
    """How late the event loop runs a 50 ms timer — the smoke's own view
    of what sysmon measures, kept as a maximum per phase — and, sampled
    on the same timer, every change of the overload governor's level
    with the signals that moved it."""

    def __init__(self, broker) -> None:
        self.max_s = 0.0
        self.levels: List[Dict[str, Any]] = []
        self._gov = broker.overload
        self._t0 = time.monotonic()
        self._task = asyncio.get_running_loop().create_task(self._run())
        # the cyclic collector stops every thread: its longest pause
        self.gc_max_s = 0.0
        self._gc_t0 = 0.0
        gc.callbacks.append(self._on_gc)
        self.witness = StallWitness()

    def _on_gc(self, phase: str, info: Dict[str, Any]) -> None:
        if phase == "start":
            self._gc_t0 = time.monotonic()
        else:
            self.gc_max_s = max(self.gc_max_s,
                                time.monotonic() - self._gc_t0)

    def take_gc(self) -> float:
        m, self.gc_max_s = self.gc_max_s, 0.0
        return round(m, 3)

    async def _run(self) -> None:
        level = 0
        while True:
            t0 = time.monotonic()
            await asyncio.sleep(0.05)
            now = self.witness.beat = time.monotonic()
            self.max_s = max(self.max_s, now - t0 - 0.05)
            gov = self._gov
            if gov is not None and gov.level != level:
                level = gov.level
                self.levels.append({
                    "at_s": round(now - self._t0, 2), "level": level,
                    "signals": {k: round(v, 3) for k, v in
                                gov._last_signals.items()}})

    def take(self) -> float:
        m, self.max_s = self.max_s, 0.0
        return round(m, 3)

    def take_levels(self) -> List[Dict[str, Any]]:
        """Governor level changes since the last take (at_s: seconds
        since boot)."""
        lv, self.levels = self.levels, []
        return lv

    def stop(self) -> None:
        self._task.cancel()
        self.witness.stop()
        gc.callbacks.remove(self._on_gc)


class StallWitness(threading.Thread):
    """A thread beside the event loop that says, for every time the loop
    ran late, whether every Python thread stood still or only the loop.
    It wakes every 50 ms and looks at the loop's last heartbeat
    (``LagMeter`` beats on its 50 ms timer). When the beat is overdue it
    notes what the loop's thread and the other threads that are not
    waiting are executing. Per stall it reports how late the loop was,
    the longest delay of this thread's own wake-ups in that stretch
    (as late as the loop = no Python thread ran: native code held the
    GIL, or the host took the cores away) and the CPU seconds the
    process used during that delay (none = the machine did not run it).
    Where this thread was late too, the frames are taken as the stall
    ends: they show what ran next, which is what had been holding on."""

    OVERDUE_S = 0.2
    IDLE = ("wait", "_worker", "get", "select")  # a thread parked there

    def __init__(self) -> None:
        super().__init__(name="smoke-stall-witness", daemon=True)
        self.beat = time.monotonic()
        self.loop_ident = threading.get_ident()
        self.stalls: List[Dict[str, Any]] = []
        self._halt = threading.Event()
        self.start()

    @staticmethod
    def _where(frame, depth: int = 4) -> List[str]:
        out = []
        while frame is not None and len(out) < depth:
            co = frame.f_code
            out.append(f"{os.path.basename(co.co_filename)}:"
                       f"{frame.f_lineno} {co.co_name}")
            frame = frame.f_back
        return out

    def run(self) -> None:
        cur: Optional[Dict[str, Any]] = None
        last, cpu = time.monotonic(), time.process_time()
        while not self._halt.wait(0.05):
            now, cpu_now = time.monotonic(), time.process_time()
            own_late, gap_cpu = now - last - 0.05, cpu_now - cpu
            last, cpu = now, cpu_now
            overdue = now - self.beat - 0.05
            if overdue > self.OVERDUE_S:
                if cur is None:
                    frames = sys._current_frames()
                    names = {t.ident: t.name for t in threading.enumerate()}
                    cur = {"loop_late_s": 0.0, "witness_late_s": 0.0,
                           "loop_at": self._where(
                               frames.get(self.loop_ident)),
                           "others": {names.get(i, str(i)): self._where(f, 3)
                                      for i, f in frames.items()
                                      if i not in (self.loop_ident,
                                                   self.ident)
                                      and f.f_code.co_name not in self.IDLE}}
                cur["loop_late_s"] = round(overdue, 3)
                if own_late > cur["witness_late_s"]:
                    cur["witness_late_s"] = round(own_late, 3)
                    cur["process_cpu_in_that_gap_s"] = round(gap_cpu, 3)
            elif cur is not None:
                self.stalls.append(cur)
                cur = None

    def take(self, top: int = 8) -> Dict[str, Any]:
        """How many stalls since the last take, and the longest few."""
        st, self.stalls = self.stalls, []
        st.sort(key=lambda s: -s["loop_late_s"])
        return {"n": len(st), "longest": st[:top]}

    def stop(self) -> None:
        self._halt.set()


# ------------------------------------------------------------ warm waits

async def wait_for(pred, bound: float, tick: float = 0.25) -> Optional[float]:
    """Seconds until ``pred()`` held, or None past the bound."""
    t0 = time.monotonic()
    while not pred():
        if time.monotonic() - t0 > bound:
            return None
        await asyncio.sleep(tick)
    return time.monotonic() - t0


async def wait_ladder(matcher, rungs: int, bound: float,
                      stall_s: float = 240.0):
    """Wait for the background warm ladder (the batch shapes up to one
    full window, then the delta-scatter rungs) and report when each new
    compile signature turned warm. Gives up past ``bound``, or when
    nothing new turned warm for ``stall_s`` (the ladder bailed). The gate
    and the watchdog stay on: this is the wait the program's own design
    asks of a cold boot. Returns (complete, per-signature log)."""
    t0 = last = time.monotonic()
    seen = (0, 0)
    shapes: List[Dict[str, Any]] = []
    while True:
        now = time.monotonic()
        state = (len(matcher._warm_sigs), matcher.delta_shapes_warmed)
        if state != seen:
            shapes.append({"warm_signatures": state[0],
                           "delta_rungs": state[1],
                           "since_previous_s": round(now - last, 2)})
            seen, last = state, now
        if (state[1] >= rungs and any(
                _sig_bpad(s) == MAX_BATCH for s in matcher._warm_sigs)):
            return True, shapes
        if now - t0 > bound or now - last > stall_s:
            return False, shapes
        await asyncio.sleep(0.25)


def delta_rungs(max_delta: int) -> int:
    n, d = 0, 2
    while d <= max_delta:
        n, d = n + 1, d * 2
    return n


# ------------------------------------------------------------- the burst

def write_coalesced(client, topics, base: int) -> None:
    """QoS0 PUBLISH frames for ``topics`` as ONE write to the client's
    socket — how a bulk publisher (a bridge flushing its spool) sends."""
    from vernemq_tpu.protocol.types import Publish

    client._writer.write(b"".join(
        client.codec.serialise(Publish(
            topic="/".join(t), payload=b"%d" % (base + i), qos=0,
            retain=False, packet_id=None, properties={}))
        for i, t in enumerate(topics)))


async def run_burst(rig: "Rig", chk: Checks, name: str,
                    topics: Sequence[Tuple[str, ...]],
                    expect_super: bool = False,
                    fallback_share: float = HOST_FALLBACK_SHARE,
                    per_connection: int = PUBS_PER_CONNECTION,
                    window_chk: Optional[Checks] = None) -> Dict[str, int]:
    """Publish ``topics`` as one QoS0 burst over TCP (``per_connection``
    publishes to a publisher connection), wait until every publish was
    folded and routed, then hold the window to the contract:
    device-served, nothing host-served, results equal to the host trie,
    TCP subscribers got exactly their messages.

    Two kinds of failure. What no protection of the broker can explain
    always goes to ``chk``: device rows that differ from the trie's, a
    message nobody published to that subscriber, a device or warm
    failure, an open breaker. What one of its protections produces BY
    DESIGN when it fires — publishes host-served, shed, throttled past
    the bound or never forming a super-batch, and with them messages
    not delivered and talkers disconnected — goes to ``window_chk``, so
    a caller can send the burst again when the broker itself said the
    window was not a calm one. Returns what the counters moved by."""
    from vernemq_tpu.protocol.topic import match_dollar_aware

    matcher, collector, tap = rig.matcher, rig.collector, rig.tap
    broker, bound = rig.broker, rig.bound
    window = window_chk if window_chk is not None else chk
    trie = broker.registry.trie("")
    metrics = broker.metrics
    # a burst is offered to a calm broker, every client connected: if
    # the previous one pushed the overload governor up, wait for it to
    # come down — above level 0 it delays every inbound PUBLISH, above
    # 1 it drops QoS0, at 3 it disconnects the heaviest talkers
    calm = await wait_for(
        lambda: broker.overload.level == 0
        and not broker.sysmon.overloaded, bound)
    if calm is None or calm > 0.5:
        emit(phase=name, waited_for_governor_s=(
            None if calm is None else round(calm, 2)),
            governor_level=broker.overload.level,
            governor_signals=broker.overload._last_signals)
    def read() -> Dict[str, int]:
        return dict(
            counters(matcher, collector),
            qos0_shed=int(metrics.value("overload_qos0_shed")),
            talkers_shed=int(metrics.value("overload_talker_disconnects")),
            lag_events=broker.sysmon.lag_events)

    before = read()
    if not chk.check(calm is not None,
                     f"{name}: overload governor back at level 0 before "
                     "the burst"):
        return dict(moved(before, before), governor_rose=0)
    revived = await rig.revive()
    if revived:
        emit(phase=name, reconnected=revived)
    tap.take()
    rig.lag.take()
    rig.lag.take_gc()
    rig.lag.take_levels()
    rig.lag.witness.take()
    base = rig.seq
    rig.seq += len(topics)
    t_send = time.monotonic()
    # the clients share the broker's loop: a connection's share of the
    # burst is written in one stretch (~20 ms for 1024 publishes), then
    # the loop runs — the broker reads it as that publisher's one chunk
    try:
        if per_connection > PUBS_PER_CONNECTION:
            for c in range(0, len(topics), per_connection):
                write_coalesced(rig.pubs[c // per_connection],
                                topics[c:c + per_connection], base + c)
        else:
            for i, t in enumerate(topics):
                await rig.pubs[i // per_connection].publish(
                    "/".join(t), b"%d" % (base + i), qos=0)
                if i % per_connection == 0 and i:
                    await asyncio.sleep(0)
        for pub in rig.pubs[:-(-len(topics) // per_connection)]:
            await pub._writer.drain()
    except (ConnectionError, OSError) as e:
        # a publisher the governor disconnected mid-burst (level 3)
        emit(phase=name, publisher_lost=f"{type(e).__name__}: {e}")

    def folded() -> bool:
        d = moved(before, read())
        return (len(tap.pairs) + d["host_hybrid_pubs"] + d["qos0_shed"]
                + sum(d[k] for k in HOST_SERVED)) >= len(topics)

    took = await wait_for(folded, bound, tick=0.02)
    took = None if took is None else time.monotonic() - t_send
    d = moved(before, read())
    pairs, calls = tap.take()
    levels = rig.lag.take_levels()
    d["governor_rose"] = int(any(lv["level"] > 0 for lv in levels))
    window.check(took is not None, f"{name}: burst folded within {bound}s")
    # the device did the work
    window.check(d["match_publishes"] > 0 and d["match_batches"] > 0,
                 f"{name}: device-served publishes moved", moved=d)
    host_served = {k: d[k] for k in HOST_SERVED if d[k]}
    window.check(not host_served and not d["qos0_shed"],
                 f"{name}: no publish host-served or shed",
                 host_served=host_served, qos0_shed=d["qos0_shed"])
    chk.check(d["warm_failures"] == 0 and d["device_failures"] == 0
              and d["dispatch_stalls"] == 0,
              f"{name}: no warm failure / device failure / stall", moved=d)
    br = matcher.breaker
    chk.check(br is None or br.is_closed, f"{name}: breaker closed")
    if took is not None:
        window.check(len(pairs) + d["host_hybrid_pubs"]
                     + sum(host_served.values()) == len(topics),
                     f"{name}: every publish accounted for",
                     device=len(pairs), hybrid=d["host_hybrid_pubs"],
                     host_served=host_served, qos0_shed=d["qos0_shed"],
                     published=len(topics))
    chk.check(d["host_fallbacks"] <= fallback_share * len(topics) + 1,
              f"{name}: per-publish host fallbacks below "
              f"{fallback_share:.0%}", host_fallbacks=d["host_fallbacks"])
    if expect_super:
        window.check(d["super_dispatches"] >= 1,
                     f"{name}: at least one fold_many super-dispatch",
                     moved=d)
    # right answers: device rows == host trie rows, publish by publish
    wrong = 0
    for n, (topic, rows) in enumerate(pairs):
        wrong += row_set(rows) != row_set(trie.match(list(topic)))
        if n % 128 == 127:
            await asyncio.sleep(0)  # the check shares the broker's loop
    chk.check(wrong == 0, f"{name}: device rows equal the trie's",
              mismatched=wrong, compared=len(pairs))
    # end to end: each TCP subscriber got exactly its messages
    late = 0
    for client, filt in rig.subs:
        fw = filt.split("/")
        want = {b"%d" % (base + i) for i, t in enumerate(topics)
                if match_dollar_aware(list(t), fw)}
        got: List[bytes] = []
        deadline = time.monotonic() + (bound / 2 if took is not None
                                       and not d["qos0_shed"] else 2.0)
        while len(got) < len(want) and time.monotonic() < deadline:
            try:
                m = await client.recv(timeout=1.0)
            except asyncio.TimeoutError:
                continue
            if m is None:
                break
            got.append(m.payload)
        await asyncio.sleep(0)
        while not client.messages.empty():
            got.append(client.messages.get_nowait().payload)
        # the tail of an earlier attempt that a protection cut short
        # (throttled past its bound) may still arrive: told by its
        # sequence number, counted, and not this window's
        fresh = [g for g in got if int(g) >= rig.void_below]
        late += len(got) - len(fresh)
        stray = sorted(set(fresh) - want)
        chk.check(not stray and len(set(fresh)) == len(fresh),
                  f"{name}: subscriber {client.client_id} received nothing "
                  "but its messages, each once", stray=stray[:8],
                  got=len(fresh))
        # a message may be missing only where the broker COUNTED why:
        # QoS0 shed at level 2+, a talker disconnected at level 3, or
        # the burst throttled past its bound
        counted = d["qos0_shed"] or d["talkers_shed"] or took is None
        (window if counted else chk).check(
            len(fresh) == len(want),
            f"{name}: subscriber {client.client_id} received all of its "
            "messages", want=len(want), got=len(fresh),
            qos0_shed=d["qos0_shed"], talkers_shed=d["talkers_shed"])
    gone = [c.client_id for c in rig.clients() if c.closed]
    (window if len(gone) <= d["talkers_shed"] else chk).check(
        not gone, f"{name}: every client still connected",
        disconnected=gone, talkers_shed=d["talkers_shed"])
    emit(phase=name, published=len(topics),
         connections=-(-len(topics) // per_connection),
         max_loop_lag_s=rig.lag.take(),
         max_gc_pause_s=rig.lag.take_gc(), device_served=len(pairs),
         dispatches=calls[:12], n_dispatches=len(calls),
         folded_within_s=None if took is None else round(took, 3),
         governor_level_changes=levels,
         publishes_throttled=int(metrics.value("mqtt_publish_throttled")),
         sysmon_lag_events=broker.sysmon.lag_events,
         gc_freezes=broker.sysmon.gc_freezes,
         dispatch_ewma_ms=round(collector.dispatch_ewma_ms, 1),
         dispatch_peak_ms=round(collector.dispatch_peak_ms, 1),
         queued_expiry_s=round(collector._expiry_s(), 3),
         late_from_a_void_attempt=late,
         stalls=rig.lag.witness.take(),
         moved={k: v for k, v in d.items() if v})
    # an alarm raised while the window's results were being checked is
    # the same stall seen late (the alarm sounds when a stall ENDS)
    d["lag_events"] = broker.sysmon.lag_events - before["lag_events"]
    d["governor_rose"] |= int(any(
        lv["level"] > 0 for lv in rig.lag.take_levels()))
    return d


async def asserted_burst(rig: "Rig", chk: Checks, name: str, make_topics,
                         attempts: int = 5, **kw: Any) -> None:
    """``run_burst`` held to the contract, sent again (fresh topics from
    ``make_topics()``) when the window was not a calm sample of the
    device path, for a reason the program itself reports:

    - a compile signature met cold is shed to the trie BY DESIGN while
      it compiles (``busy_host_pubs`` with ``busy_sheds``), or no
      backlog of more than one window formed for a super-batch — which
      K forms depends on how the bytes arrive;
    - the broker's own alarms went off inside the window: the loop-lag
      alarm, or the overload governor above level 0. The one-chip
      machine shares its host's cores; when the whole process stands
      still for a second, queued publishes expire to the trie, the
      governor delays or drops QoS0 and at level 3 disconnects talkers —
      each as designed, each counted.

    Such an attempt is void: its ``window_chk`` failures are forgiven,
    while its rows, stray deliveries, device failures and breaker are
    held to the contract like any other burst's. The last attempt
    forgives nothing."""
    matcher = rig.matcher
    for attempt in range(1, attempts + 1):
        window = Checks()
        d = await run_burst(rig, chk,
                            name if attempt == 1 else f"{name}#{attempt}",
                            make_topics(), window_chk=window, **kw)
        cold = d["busy_host_pubs"] > 0 and d["busy_sheds"] > 0
        alarmed = d["lag_events"] > 0 or d["governor_rose"] > 0
        no_backlog = bool(kw.get("expect_super")
                          and d["super_dispatches"] == 0)
        # expiry (queued past its deadline) and the collector's depth
        # shed are what a stall produces; a watchdog stall, a rebuild or
        # an open breaker are not, and never forgiven
        allowed = {"busy_host_pubs"} | (
            {"expired_host_pubs", "overload_host_pubs"} if alarmed
            else set())
        void = (window.failed and attempt < attempts
                and (cold or alarmed or no_backlog)
                and not any(d[k] for k in HOST_SERVED if k not in allowed))
        if not void:
            chk.failed.extend(window.failed)
            if attempt > 1:
                emit(phase=name, attempts=attempt, asserted=attempt)
            return
        rig.void_below = rig.seq
        took = await wait_for(lambda: not matcher._warming, WARM_BOUND_S)
        emit(phase=name, attempt=attempt, void=True,
             reason=("cold shape shed to the trie while it compiled"
                     if cold else "the broker's loop-lag alarm or overload "
                     "governor went off inside the window" if alarmed
                     else "no window backlog formed"),
             warm_wait_s=None if took is None else round(took, 2),
             forgiven_checks=window.failed)


# ------------------------------------------------------------ one boot

def make_config(capacity: int, mesh: str = ""):
    """The broker's defaults, with the reg view this script is about and
    the table pre-sized for the fleet. Every protection — overload
    governor, loop-lag alarm, queued-item expiry, warm gate, watchdog,
    breaker — runs as shipped."""
    from vernemq_tpu.broker.config import Config

    kw: Dict[str, Any] = dict(
        default_reg_view="tpu", tpu_initial_capacity=capacity,
        allow_anonymous=True, systree_enabled=False)
    if mesh:
        kw["tpu_mesh"] = mesh
    return Config(**kw)


class CacheCounter:
    """JAX's own count of what the persistent compile cache did, and the
    size of its directory: whether a ladder was compiled or read back is
    seen here, not guessed from its seconds."""

    EVENTS = {"/jax/compilation_cache/compile_requests_use_cache":
              "requests", "/jax/compilation_cache/cache_hits": "hits",
              "/jax/compilation_cache/cache_misses": "misses"}

    def __init__(self, jax, cache_dir: str) -> None:
        self.dir = cache_dir
        self.n = {"requests": 0, "hits": 0, "misses": 0}
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw: Any) -> None:
        name = self.EVENTS.get(event)
        if name is not None:
            self.n[name] += 1

    def fact(self) -> Dict[str, Any]:
        entries = size = 0
        try:
            with os.scandir(self.dir) as it:
                for e in it:
                    if e.is_file():
                        entries += 1
                        size += e.stat().st_size
        except OSError:
            pass
        return dict(self.n, dir_entries=entries, dir_bytes=size)


class Rig:
    """One booted broker and everything a burst needs of it."""

    def __init__(self, broker, server, view, matcher, subs, pubs,
                 keepalive, lag, warm: bool, bound: float) -> None:
        self.broker, self.server, self.view = broker, server, view
        self.matcher, self.subs, self.pubs, self.lag = matcher, subs, pubs, lag
        self.keepalive = keepalive  # task pinging every client
        self.warm = warm      # the warm ladder completed within its bound
        self.bound = bound    # seconds a burst may take to fold
        self.seq = 0          # payload sequence across bursts
        self.void_below = 0   # sequence numbers of void attempts end here
        self.super_burst = SUPER_BURST
        self.collector = broker.batch_collector()
        self.tap = FoldTap(view)

    def clients(self):
        return [c for c, _ in self.subs] + self.pubs

    async def revive(self) -> List[str]:
        """Connect again every client the broker disconnected (the
        governor's level 3 sheds its heaviest talkers); a subscriber
        subscribes again, which reaches the device as a delta."""
        back = []
        for i, (c, filt) in enumerate(self.subs):
            if c.closed:
                self.subs[i] = (await connect(
                    self.server, c.client_id, filt, c.smoke_qos), filt)
                back.append(c.client_id)
        for i, c in enumerate(self.pubs):
            if c.closed:
                self.pubs[i] = await connect(self.server, c.client_id)
                back.append(c.client_id)
        return back

    async def shutdown(self) -> None:
        self.lag.stop()
        self.keepalive.cancel()
        for c in self.clients():
            try:
                await c.close()
            except Exception:
                pass
        await self.broker.stop()
        await self.server.stop()


async def boot_and_warm(args, rows, tag: str, specs, mesh: str = "") -> Rig:
    """start_broker -> load the registry -> connect the TCP clients (their
    SUBSCRIBEs are part of the first device build, so the table geometry
    the ladder compiles for is the one the bursts meet) -> build the
    device view -> wait for the warm ladder."""
    from vernemq_tpu.broker.server import start_broker

    capacity = 1 << max(13, (len(rows) - 1).bit_length())
    cfg = make_config(capacity, mesh)
    t0 = time.monotonic()
    broker, server = await start_broker(cfg, port=0)
    boot_s = time.monotonic() - t0
    lag = LagMeter(broker)
    load_s = await load_registry(broker.registry, rows)
    load_lag, load_gc = lag.take(), lag.take_gc()
    subs, pubs, keepalive = await connect_clients(server, specs)
    view = broker.registry.reg_view("tpu")
    # the first flush would start this (and a boot with a persisted
    # subscriber DB does): started here so its seconds are seen. The
    # table is built off the loop thread; the trie serves meanwhile.
    t0 = time.monotonic()
    view.begin_load("")
    table_s = await wait_for(lambda: view.begin_load(""), WARM_BOUND_S,
                             tick=0.05)
    if table_s is None:
        raise RuntimeError("device table not loaded within "
                           f"{WARM_BOUND_S}s")
    matcher = view.matcher("")
    emit(phase=f"{tag}:load", subscriptions=matcher.table.count,
         through="Registry.subscribe", boot_s=round(boot_s, 2),
         registry_load_s=round(load_s, 2),
         registry_load_max_loop_lag_s=load_lag,
         registry_load_max_gc_pause_s=load_gc,
         device_table_load_s=round(table_s, 2),
         device_table_load_max_loop_lag_s=lag.take(),
         device_table_load_max_gc_pause_s=lag.take_gc(),
         tpu_initial_capacity=capacity, tpu_mesh=mesh or None,
         overload_dispatch_budget_ms=cfg.get("overload_dispatch_budget_ms"),
         sysmon_lag_threshold=cfg.get("sysmon_lag_threshold"),
         config="defaults")
    # the mesh seat warms its scatter on demand: no delta rungs to wait on
    rungs = 0 if mesh else delta_rungs(cfg.get("tpu_delta_warm_max", 128))
    t0 = time.monotonic()
    complete, shapes = await wait_ladder(matcher, rungs, WARM_BOUND_S)
    ladder_s = time.monotonic() - t0
    # the same ladder again, every executable now resident in this
    # process: what of ladder_s was running the programs, not building
    # them
    t1 = time.monotonic()
    await asyncio.get_running_loop().run_in_executor(
        None, matcher.warm_ladder)
    emit(phase=f"{tag}:warm", table_rows=int(matcher.table.cap),
         ladder_s=round(ladder_s, 2), ladder_complete=complete,
         ladder_again_resident_s=round(time.monotonic() - t1, 2),
         warm_signatures=len(matcher._warm_sigs),
         delta_shapes_warmed=matcher.delta_shapes_warmed,
         compile_cache=args.cache.fact(),
         max_loop_lag_s=lag.take(), max_gc_pause_s=lag.take_gc(),
         governor_level_changes=lag.take_levels(), per_signature=shapes,
         stalls=lag.witness.take())
    rig = Rig(broker, server, view, matcher, subs, pubs, keepalive, lag,
              complete, bound=90.0)
    if args.rehearse:
        rig.super_burst = REHEARSAL_SUPER_BURST
    return rig


def _sig_bpad(sig) -> int:
    """Padded batch of a single-batch compile signature (0 for others)."""
    first = sig[0]
    if first == "sharded":       # ("sharded", Bpad, T, seg_max, ...)
        return int(sig[1])
    if isinstance(first, tuple):  # (arg shapes, statics, pallas)
        return int(first[0][0])
    return 0                      # ("many", K, ...) / ("simple", ...)


async def connect(server, cid: str, filt: Optional[str] = None,
                  qos: int = 0):
    from vernemq_tpu.client import MQTTClient

    c = MQTTClient(server.host, server.port, client_id=cid)
    await c.connect()
    if filt is not None:
        await c.subscribe(filt, qos=qos)
        c.smoke_qos = qos
    return c


async def connect_clients(server, specs):
    subs = [(await connect(server, cid, filt, qos), filt)
            for cid, filt, qos in specs]
    pubs = [await connect(server, f"smoke-pub{i}")
            for i in range(N_PUBLISHERS)]

    async def keepalive() -> None:
        # MQTTClient sends no PINGREQ of its own; the warm ladder takes
        # minutes and the broker drops a client idle past 1.5x keepalive
        while True:
            await asyncio.sleep(20.0)
            for c in [c for c, _ in subs] + pubs:
                try:
                    if not c.closed:
                        await c.ping()
                except (ConnectionError, OSError):
                    pass  # dropped by the broker: ``Rig.revive``

    return subs, pubs, asyncio.get_running_loop().create_task(keepalive())


def memory_fact(jax) -> Dict[str, Any]:
    out = {}
    for d in jax.devices():
        try:
            st = d.memory_stats() or {}
        except Exception:
            st = {}
        out[str(d.id)] = {k: st.get(k) for k in
                          ("peak_bytes_in_use", "bytes_in_use",
                           "bytes_limit")}
    return out


def smoke_specs(pools, n: int):
    """TCP subscribers whose filters overlap the corpus and the hot end
    of the Zipf stream: (client id, filter, qos)."""
    l0, l1, l2 = pools
    return [("smoke-a", f"{l0[0]}/+/{l2[0]}", 0),
            ("smoke-b", f"{l0[0]}/{l1[0]}/#", 1),
            ("smoke-c", f"+/{l1[1]}/{l2[1]}", 0),
            ("smoke-d", f"{l0[1]}/{l1[0]}/{l2[0]}", 1)][:n]


# ------------------------------------------------------- one-chip phases

async def one_chip(args, jax, chk: Checks) -> None:
    rng = random.Random(args.seed + 1)
    t0 = time.monotonic()
    rows, pools = make_corpus(args.seed, args.subs)
    emit(phase="corpus", subscriptions=len(rows), seed=args.seed,
         mix="build_corpus: 60% exact, 20% w/+/w, 10% +/w/w, 10% w/w/#",
         build_s=round(time.monotonic() - t0, 2))
    # ...and one topic held by FANOUT_ROWS subscribers: past
    # tpu_max_fanout, what the wide pass is for (fanout_phase)
    rows += [(list(FANOUT_TOPIC), len(rows) + i) for i in range(FANOUT_ROWS)]
    specs = smoke_specs(pools, 4)
    rig = await boot_and_warm(args, rows, "boot1", specs)
    matcher = rig.matcher
    try:
        chk.check(matcher.table.count == len(rows) + len(specs),
                  "all subscriptions resident in the device table",
                  resident=matcher.table.count)
        chk.check(matcher.table.bucketed,
                  "bucketed (windowed-kernel) layout")
        chk.check(rig.warm, "warm ladder completed within the bound")
        chk.check(matcher.warm_failures == 0, "no warm failures at boot")
        # the K-window super-batch programs compile BEFORE any traffic,
        # side by side: compiling lags the loop for seconds, the governor
        # answers that at level 3 by disconnecting whoever talked most
        # in the last seconds — so nobody has yet
        ks = [2, 3, 4]
        for k in ks:
            matcher.ensure_warm_many(k, MAX_BATCH)
        took = await wait_for(lambda: not matcher._warming, WARM_BOUND_S)
        emit(phase="warm_many", ks=ks,
             compile_s=None if took is None else round(took, 2),
             warm_signatures=len(matcher._warm_sigs),
             compile_cache=args.cache.fact(),
             max_loop_lag_s=rig.lag.take(),
             max_gc_pause_s=rig.lag.take_gc(),
             governor_level_changes=rig.lag.take_levels(),
             stalls=rig.lag.witness.take())
        chk.check(took is not None and matcher.warm_failures == 0,
                  "super-batch shapes warmed")
        # single-batch path: the smallest device-served flush (9 > the
        # host threshold of 8), a mid window, one full window
        for n in (9, 300, MAX_BATCH):
            await asserted_burst(rig, chk, f"single_batch_{n}",
                                 lambda n=n: zipf_topics(rng, pools, n))
        # one connection carrying a whole window alone (~180 KB of
        # frames): the broker reads it in 64 KB chunks
        await asserted_burst(rig, chk, "fat_connection",
                             lambda: zipf_topics(rng, pools, MAX_BATCH),
                             per_connection=MAX_BATCH)
        # more than one collector window queued at once, so a flush
        # rides ONE fold_many dispatch
        await asserted_burst(
            rig, chk, "super_batch",
            lambda: zipf_topics(rng, pools, rig.super_burst),
            expect_super=True)
        await delta_phase(rig, chk, pools)
        await fanout_phase(rig, chk)
        why = ("compiles for the v5e (tests/test_tpu_compile.py); no phase "
               "here drives it yet (ROADMAP S6)")
        emit(phase="retained_replay", status="not run", why=why)
        emit(phase="payload_predicate", status="not run", why=why)
        emit(phase="counters", **counters(matcher, rig.collector),
             breaker=(matcher.breaker.state_name
                      if matcher.breaker is not None else None),
             governor_level=rig.broker.overload.level)
        emit(phase="device_memory", per_device=memory_fact(jax))
    finally:
        await rig.shutdown()


async def delta_phase(rig: Rig, chk: Checks, pools) -> None:
    """A SUBSCRIBE and an UNSUBSCRIBE after warm: the publish that must,
    then must not, reach the new subscriber — through the delta scatter
    (no rebuild), on the device path."""
    matcher = rig.matcher
    l0, l1, l2 = pools
    topic = (l0[2], l1[2], l2[2])
    filt = "/".join(topic)
    rebuilds = matcher.rebuilds_async
    rig.subs.append((await connect(rig.server, "smoke-late", filt, 0),
                     filt))
    try:
        await asserted_burst(rig, chk, "delta_subscribe",
                             lambda: [topic] * 16)
    finally:
        late = rig.subs.pop()[0]
    try:
        await late.unsubscribe(filt)
        await asserted_burst(rig, chk, "delta_unsubscribe",
                             lambda: [topic] * 16)
        await asyncio.sleep(0.5)
        chk.check(late.messages.empty(),
                  "delta_unsubscribe: nothing reached the unsubscribed "
                  "client", queued=late.messages.qsize())
        chk.check(matcher.rebuilds_async == rebuilds,
                  "delta: applied by scatter, not by a rebuild")
    finally:
        await late.close()


async def fanout_phase(rig: Rig, chk: Checks) -> None:
    """A publish that matches FANOUT_ROWS rows — four times
    ``tpu_max_fanout`` — is answered whole by the device (the wide pass):
    rows equal to the trie's, and not one publish matched again on the
    host."""
    before = counters(rig.matcher, rig.collector)
    await asserted_burst(rig, chk, "fanout_1000",
                         lambda: [FANOUT_TOPIC] * 16, fallback_share=0.0)
    d = moved(before, counters(rig.matcher, rig.collector))
    chk.check(d["host_fallbacks"] == 0 and d["wide_failures"] == 0
              and d["wide_publishes"] >= 16,
              f"fanout_1000: {FANOUT_ROWS} rows a publish answered by the "
              "device, host_fallbacks unchanged",
              moved={k: d[k] for k in ("host_fallbacks", "wide_publishes",
                                       "wide_failures", "match_publishes")})


# ------------------------------------------------------ four-chip phase

async def four_chips(args, jax, chk: Checks) -> None:
    from vernemq_tpu.models.tpu_matcher import TpuMatcher

    ndev = len(jax.devices())
    if not chk.check(ndev >= 4, "--chips 4 needs four devices",
                     present=ndev):
        return
    rng = random.Random(args.seed + 1)
    rows, pools = make_corpus(args.seed, args.subs)
    emit(phase="corpus", subscriptions=len(rows), seed=args.seed)
    rig = await boot_and_warm(args, rows, "mesh", smoke_specs(pools, 2),
                              mesh="1x4")
    matcher = rig.matcher
    try:
        chk.check(rig.warm, "mesh: warm ladder completed within the bound")
        st = rig.view.mesh_status()
        if chk.check(st is not None, "mesh: view.mesh_status() present"):
            emit(phase="mesh_status", slices=st["slices"],
                 rows_per_slice=st["rows_per_slice"],
                 addressable=st["addressable"],
                 full_scatters=st["full_scatters"])
            chk.check(st["slices"] == 4 and len(st["addressable"]) == 4,
                      "mesh: four slices on four devices", status=st)
            chk.check(len(st["rows_per_slice"]) == 4
                      and all(r > 0 for r in st["rows_per_slice"]),
                      "mesh: every device holds rows",
                      rows_per_slice=st["rows_per_slice"])
        # a publish whose bucket straddles a slice cut is served by the
        # exact per-publish host fallback BY DESIGN (counted), and that
        # fallback is a linear scan: keep the mesh burst small
        topics = zipf_topics(rng, pools, MESH_BURST)
        await asserted_burst(rig, chk, "mesh_single_batch", lambda: topics,
                             fallback_share=MESH_FALLBACK_SHARE)
        # what it is compared with: a single-device matcher over the
        # same registry, same burst
        t0 = time.monotonic()
        single = TpuMatcher(initial_capacity=matcher.table.cap,
                            max_fanout=matcher.max_fanout,
                            flat_avg=matcher.flat_avg,
                            device=jax.devices()[0])
        for fw, key, opts in rig.broker.registry.fold_subscriptions(""):
            single.table.add(list(fw), key, opts)
        loop = asyncio.get_running_loop()
        one = await loop.run_in_executor(None, single.match_batch, topics)
        mesh_rows = await loop.run_in_executor(
            None, matcher.match_batch, topics)
        wrong = sum(1 for a, b in zip(one, mesh_rows)
                    if row_set(a) != row_set(b))
        chk.check(wrong == 0, "mesh rows equal the single-device rows",
                  mismatched=wrong, compared=len(topics))
        emit(phase="mesh_vs_single", compared=len(topics),
             mismatched=wrong, single_build_and_match_s=round(
                 time.monotonic() - t0, 2))
        emit(phase="counters", **counters(matcher, rig.collector))
        emit(phase="device_memory", per_device=memory_fact(jax))
    finally:
        await rig.shutdown()


# ----------------------------------------------------------------- main

def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--subs", type=int, default=None,
                    help=f"resident subscriptions (default {REAL_SUBS:,}; "
                         f"{REHEARSAL_SUBS:,} with --rehearse)")
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4: run ONLY the four-chip mesh phase and its "
                         "single-device comparison")
    ap.add_argument("--rehearse", action="store_true",
                    help="same control flow on the CPU backend at a tiny "
                         "size; never prints the TPU last line")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.subs is None:
        args.subs = REHEARSAL_SUBS if args.rehearse else REAL_SUBS
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if (args.chips == 4
                and "xla_force_host_platform_device_count" not in flags):
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=4").strip()
    device: Dict[str, Any] = {}
    chk = Checks()
    try:
        import jax

        if args.rehearse:
            jax.config.update("jax_platforms", "cpu")
        from vernemq_tpu.utils.compile_cache import configure_compile_cache

        cache_dir = configure_compile_cache()
        devs = jax.devices()
        device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs)}
        args.cache = CacheCounter(jax, cache_dir)
        at_start = args.cache.fact()
        emit(phase="device", **device, jax=jax.__version__,
             compile_cache_dir=cache_dir,
             cache_entries_at_start=at_start["dir_entries"],
             cache_bytes_at_start=at_start["dir_bytes"],
             cache_dir_from_env=bool(os.environ.get(
                 "JAX_COMPILATION_CACHE_DIR")))
        if device["platform"] != "tpu" and not args.rehearse:
            raise RuntimeError(
                f"no TPU: jax.devices()[0].platform is "
                f"{device['platform']!r} (use --rehearse for a CPU "
                "rehearsal)")
        t0 = time.monotonic()
        phase = four_chips if args.chips == 4 else one_chip
        asyncio.run(phase(args, jax, chk))
        emit(phase="done", seconds=round(time.monotonic() - t0, 1),
             failed_checks=chk.failed)
    except BaseException as e:  # a refusal is a result too: say so
        traceback.print_exc(file=sys.stderr)
        chk.failed.append(f"{type(e).__name__}: {e}")
    if chk.failed:
        # the broker logs to stderr throughout: the verdict goes there
        # too, last, so the end of either stream says what failed
        print("chip_smoke FAILED: " + "; ".join(chk.failed),
              file=sys.stderr, flush=True)
        emit(ok=False, failed=chk.failed, device=device,
             rehearsal=bool(args.rehearse))
        return 1
    if args.rehearse:
        emit(ok=True, rehearsal=True, device=device)
    else:
        print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
