"""The system under test, and the stand-in that takes its place.

``DeviceBroker`` is the program: ``vernemq_tpu.broker.server.start_broker``
at its DEFAULT configuration but for ``default_reg_view="tpu"``,
``tpu_initial_capacity`` for the table, ``allow_anonymous`` and
``systree_enabled=False`` — no protection loosened. Its boot sequence and
its probes (``LagMeter``, ``CacheCounter``, the fold tap) are copies of
what ``chip_smoke.py`` proved on the chip in PR 23. It is the only thing in
this package that imports the program or JAX.

``ReferenceSystem`` wraps ``refbroker.ReferenceBroker`` behind the same
few calls, so ``harness.drive`` runs a cell against either.
"""

from __future__ import annotations

import asyncio
import gc
import os
import time
from typing import Any, Dict, Optional

WARM_BOUND_S = 1100.0
MAX_BATCH = 4096
#: collector counters of publishes the host trie served in the device's
#: place (``chip_smoke.HOST_SERVED``)
HOST_SERVED = ("busy_host_pubs", "degraded_host_pubs", "stalled_host_pubs",
               "expired_host_pubs", "rebuild_host_pubs", "overload_host_pubs")
STAGES = ("stage_wire_parse_ms", "stage_collector_wait_ms",
          "stage_device_dispatch_ms", "stage_queue_flush_ms",
          "stage_wire_encode_ms")
BROKER_COUNTERS = ("overload_qos0_shed", "overload_talker_disconnects",
                   "mqtt_publish_throttled", "queue_message_drop",
                   "mqtt_publish_received", "mqtt_publish_sent")


async def wait_for(pred, bound: float, tick: float = 0.25) -> Optional[float]:
    """Seconds until ``pred()`` held, or None past the bound."""
    t0 = time.monotonic()
    while not pred():
        if time.monotonic() - t0 > bound:
            return None
        await asyncio.sleep(tick)
    return time.monotonic() - t0


class LagMeter:
    """How late the event loop runs a 50 ms timer (its maximum since the
    last ``take``), the longest pause of the cyclic collector, and on the
    same timer the overload governor's level: how many samples it stood
    above 0 (copy of ``chip_smoke.LagMeter`` without the stall witness)."""

    def __init__(self, level_of) -> None:
        self.max_s = 0.0
        self.gc_max_s = 0.0
        self.samples = 0
        self.raised = 0
        self.level_max = 0
        self._level_of = level_of
        self._gc_t0 = 0.0
        self._task = asyncio.get_running_loop().create_task(self._run())
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: Dict[str, Any]) -> None:
        if phase == "start":
            self._gc_t0 = time.monotonic()
        else:
            self.gc_max_s = max(self.gc_max_s, time.monotonic() - self._gc_t0)

    async def _run(self) -> None:
        while True:
            t0 = time.monotonic()
            await asyncio.sleep(0.05)
            self.max_s = max(self.max_s, time.monotonic() - t0 - 0.05)
            level = self._level_of()
            self.samples += 1
            self.raised += level > 0
            self.level_max = max(self.level_max, level)

    def take(self) -> Dict[str, float]:
        out = {"loop_lag_max_s": self.max_s, "gc_pause_max_s": self.gc_max_s,
               "samples": self.samples, "raised": self.raised,
               "level_max": self.level_max}
        self.max_s = self.gc_max_s = 0.0
        self.samples = self.raised = self.level_max = 0
        return out

    def stop(self) -> None:
        self._task.cancel()
        gc.callbacks.remove(self._on_gc)


class CacheCounter:
    """JAX's own count of what the persistent compile cache did, and the
    size of its directory (copy of ``chip_smoke.CacheCounter``)."""

    EVENTS = {"/jax/compilation_cache/compile_requests_use_cache": "requests",
              "/jax/compilation_cache/cache_hits": "hits",
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


class DeviceBroker:
    """The program, booted as a deployment boots: persisted subscriber DB
    into the registry, device table built off the loop, warm ladder,
    governor at level 0."""

    name = "vernemq_tpu"

    def __init__(self, jax, cache: CacheCounter, note) -> None:
        self.jax, self.cache, self.note = jax, cache, note
        self.broker = self.server = self.view = self.matcher = None
        self.collector = None
        self.lag: Optional[LagMeter] = None

    async def boot(self, corpus) -> int:
        from vernemq_tpu.broker.config import Config
        from vernemq_tpu.broker.server import start_broker
        from vernemq_tpu.protocol.types import SubOpts

        capacity = 1 << max(13, (corpus.n_resident - 1).bit_length())
        cfg = Config(default_reg_view="tpu", tpu_initial_capacity=capacity,
                     allow_anonymous=True, systree_enabled=False)
        self.broker, self.server = await start_broker(cfg, port=0)
        self.lag = LagMeter(lambda: self.broker.overload.level)
        # every subscription goes in through Registry.subscribe, the call a
        # SUBSCRIBE makes: the trie and the device table derive from it
        opts = (SubOpts(qos=0), SubOpts(qos=1))
        subscribe = self.broker.registry.subscribe
        t0 = time.monotonic()
        since = 0
        for cid, filters in corpus.records():
            subscribe(("", cid), [(list(w), opts[q]) for w, q in filters])
            since += len(filters)
            if since >= 500:
                since = 0
                await asyncio.sleep(0)
        self.note(phase="registry_load", subscriptions=corpus.n_stored,
                  seconds=time.monotonic() - t0, **self.lag.take())
        return self.server.port

    async def warm(self) -> None:
        view = self.view = self.broker.registry.reg_view("tpu")
        t0 = time.monotonic()
        view.begin_load("")
        took = await wait_for(lambda: view.begin_load(""), WARM_BOUND_S, 0.05)
        if took is None:
            raise RuntimeError("device table not loaded within the bound")
        m = self.matcher = view.matcher("")
        self.collector = self.broker.batch_collector()
        self.note(phase="device_table", seconds=time.monotonic() - t0,
                  resident=m.table.count, rows=int(m.table.cap),
                  bucketed=bool(m.table.bucketed), **self.lag.take())
        t0 = time.monotonic()
        rungs, d = 0, 2
        while d <= self.broker.config.get("tpu_delta_warm_max", 128):
            rungs, d = rungs + 1, d * 2
        ok = await wait_for(
            lambda: m.delta_shapes_warmed >= rungs and any(
                _sig_bpad(s) == MAX_BATCH for s in m._warm_sigs),
            WARM_BOUND_S)
        self.note(phase="warm_ladder", seconds=time.monotonic() - t0,
                  complete=ok is not None, signatures=len(m._warm_sigs),
                  compile_cache=self.cache.fact(), **self.lag.take())
        if ok is None:
            raise RuntimeError("warm ladder not complete within the bound")
        await self.calm()

    async def calm(self) -> None:
        """Traffic is offered to a broker whose governor stands at 0."""
        b = self.broker
        took = await wait_for(
            lambda: b.overload.level == 0 and not b.sysmon.overloaded,
            120.0)
        if took is None:
            raise RuntimeError("overload governor never came back to 0")
        if took > 0.5:
            self.note(phase="calm", waited_s=took)

    def tap_folds(self) -> None:
        """Name the two calls the collector dispatches in the profiler's
        trace (as ``chip_smoke.FoldTap`` wraps them), from this side: the
        program has no spans of its own yet."""
        view, ann = self.view, self.jax.profiler.TraceAnnotation
        fold_batch, fold_many = view.fold_batch, view.fold_many

        def tap_batch(mp, topics, *a, **k):
            with ann("bench_fold_batch"):
                return fold_batch(mp, topics, *a, **k)

        def tap_many(mp, batches, *a, **k):
            with ann("bench_fold_many"):
                return fold_many(mp, batches, *a, **k)

        view.fold_batch, view.fold_many = tap_batch, tap_many

    def counters(self) -> Dict[str, float]:
        """The program's own counters and histogram totals, as they
        stand: the harness differences two readings."""
        m, c, metrics = self.matcher, self.collector, self.broker.metrics
        out: Dict[str, float] = {k: int(getattr(c, k)) for k in HOST_SERVED}
        out.update(
            host_hybrid_pubs=c.host_hybrid_pubs,
            super_batches=c.super_batches,
            match_batches=m.match_batches,
            match_publishes=m.match_publishes,
            super_dispatches=m.super_dispatches,
            host_fallbacks=m.host_fallbacks,
            device_failures=m.device_failures,
            warm_failures=m.warm_failures, busy_sheds=m.busy_sheds,
            lag_events=self.broker.sysmon.lag_events)
        for k in BROKER_COUNTERS:
            out[k] = int(metrics.value(k))
        snap = metrics.histogram_snapshot()
        for fam in STAGES:
            _b, total, count = snap.get(fam, ((), 0.0, 0))
            out[fam + ".sum"] = float(total)
            out[fam + ".count"] = int(count)
        cache = self.cache.n
        out["compile_requests"] = cache["requests"]
        out["compile_cache_misses"] = cache["misses"]
        return out

    def probes(self) -> Dict[str, float]:
        return self.lag.take()

    def device(self) -> Dict[str, Any]:
        devs = self.jax.devices()
        peak = 0
        for d in devs:
            st = d.memory_stats() or {}
            peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
        return {"platform": devs[0].platform, "kind": devs[0].device_kind,
                "count": len(devs), "memory_peak_bytes": peak}

    async def stop(self) -> None:
        if self.lag is not None:
            self.lag.stop()
        if self.broker is not None:
            await self.broker.stop()
            await self.server.stop()


def _sig_bpad(sig) -> int:
    """Padded batch of a single-batch compile signature, 0 for others
    (copy of ``chip_smoke._sig_bpad``)."""
    first = sig[0]
    if first == "sharded":
        return int(sig[1])
    if isinstance(first, tuple):
        return int(first[0][0])
    return 0


class ReferenceSystem:
    """``refbroker.ReferenceBroker`` behind ``DeviceBroker``'s calls."""

    name = "reference"

    def __init__(self, break_: Optional[str], every: int, note) -> None:
        from .refbroker import ReferenceBroker

        self.ref = ReferenceBroker(break_, every)
        self.note = note
        self.lag: Optional[LagMeter] = None

    async def boot(self, corpus) -> int:
        self.lag = LagMeter(lambda: 0)
        # only a live session can be handed a message: the reference
        # stores the rows of those, as a broker's queue lookup would find
        for s in corpus.live:
            for words, qos in s.stored:
                self.ref.store(s.client_id, words, qos)
        return await self.ref.start()

    async def warm(self) -> None:
        return None

    async def calm(self) -> None:
        return None

    def tap_folds(self) -> None:
        return None

    def counters(self) -> Dict[str, float]:
        return {"publishes": self.ref.publishes}

    def probes(self) -> Dict[str, float]:
        return self.lag.take()

    def device(self) -> Dict[str, Any]:
        return {"platform": "none", "kind": "reference broker", "count": 0,
                "memory_peak_bytes": 0}

    async def stop(self) -> None:
        if self.lag is not None:
            self.lag.stop()
        await self.ref.stop()
