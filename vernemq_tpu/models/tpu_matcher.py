"""The TPU match engine: device-resident subscription table + batched
wildcard matching, wired as a RegView behind the registry's reg-view seam.

This is the north star (BASELINE.json): the ``vmq_reg_trie`` equivalent
lives in device HBM and ``fold_subscribers`` becomes one batched kernel
call over thousands of concurrent PUBLISHes. The engine is correct on any
JAX backend (tests run it on CPU with a virtual device mesh); on TPU the
match is VPU/HBM work batched to amortise dispatch.

Pieces:
- :class:`TpuMatcher` — owns a :class:`SubscriptionTable`, mirrors it to
  the device (full upload on growth, scatter delta otherwise), and serves
  ``match_batch`` with power-of-two batch padding to bound recompiles;
- :class:`TpuRegView` — the reg-view adapter (``vmq_reg_view.erl:20-27``
  seam): synchronous ``fold`` for drop-in parity with the trie view plus
  the batch interface the collector uses;
- :class:`BatchCollector` — µs-scale publish coalescing (SURVEY.md §5.8
  host↔TPU: accumulate ≤ window, one device call, scatter to queues).
"""

from __future__ import annotations

import asyncio
import threading
import time
from typing import Any, Dict, Hashable, List, Optional, Sequence, Tuple

import numpy as np

from ..observability import histogram as obs
from ..observability.profiler import record_dispatch
from ..ops import match_kernel as K
from ..robustness import faults
from ..robustness import watchdog as watchdog_mod
from ..robustness.breaker import CircuitBreaker
from ..robustness.watchdog import StallAbandoned
from .tpu_table import SubscriptionTable

Row = Tuple[Tuple[str, ...], Hashable, Any]

#: background-rebuild threads stash their abandon token here so the
#: observability seams inside _build_device can tell a healthy build
#: from a watchdog-abandoned straggler (threading.local: concurrent
#: old-abandoned + fresh rebuild threads each see their own token)
_rebuild_tls = threading.local()

#: The wide result (``K.wide_mask_packed``), process totals as the wire
#: plane keeps its own in ``protocol/fastpath`` (one process, one device;
#: the gauges ``tpu_wide_*``): publishes the flat form flagged overflow
#: and the device answered whole, the device calls that did, the distinct
#: topics those matched, the rows they brought back, and publishes whose
#: wide answer fell short of the flat form's own count (host-matched).
#: Folded once a wide pass, under ``_totals_lock`` (an executor thread a
#: matcher).
wide_publishes = 0
wide_dispatches = 0
wide_topics = 0
wide_rows = 0
wide_failures = 0
#: The flat form's phases, process totals beside them (gauges
#: ``tpu_phase_*``): executions of a windowed match program, single or
#: super, that counted as a dispatch, and the phases compiled into each
#: (1-3: dense region 0, probe A, probe B), summed. Folded once a
#: dispatch, where its ring record is written.
phase_dispatches = 0
phase_runs = 0
_totals_lock = threading.Lock()


TILE_PUBS = 256  # pubs per window tile (MXU row-tile friendly)
FAIR_MULT = 2    # window width vs per-tile fair share of the zone (the
                 # wider the window, the fewer tiles but the more rows
                 # each tile matmuls — an on-chip tuning knob)


#: publishes a wide-pass program takes (K.wide_mask_packed's ``U``): the
#: distinct topics of a batch that overflowed the flat form, padded to
#: the smallest rung that holds them, the largest rung at a time. Two
#: compile signatures a table geometry: a fan-out burst repeats few topics
WIDE_RUNGS = (8, 64)


def _pow2ceil(n: int) -> int:
    return 1 << max(n - 1, 1).bit_length()


def _pad_pub_block(pw, pl, pd, Bpad: int):
    """Grow an encoded publish block to a larger padded batch size (the
    super-batch path pads every member batch to ONE common Bpad so all K
    share a compile signature)."""
    cur = pw.shape[0]
    if cur == Bpad:
        return pw, pl, pd
    from ..ops.match_kernel import PAD_ID

    extra = Bpad - cur
    pw = np.concatenate(
        [pw, np.full((extra, pw.shape[1]), np.int32(PAD_ID), np.int32)])
    pl = np.concatenate([pl, np.zeros(extra, np.int32)])
    pd = np.concatenate([pd, np.zeros(extra, bool)])
    return pw, pl, pd


def window_params(S: int, glob_pad: int, bucket_max: int, Bpad: int,
                  zone: Optional[int] = None, align: int = 0):
    """Static kernel geometry for a padded batch: tile count T (fixed per
    Bpad — shape-stable), window width seg_max (pow2, ≥ every bucket
    region and ≥ 2x the per-tile fair share of the zone), and the dense
    chunk gc. ``zone`` is the row span the tiles must cover (probe A: the
    level-0 buckets; probe B: the g-bucket zone) — defaults to
    S - glob_pad. Together these bound recompiles to the Bpad ladder.
    ``align`` (the Pallas path's SEG_BLK) widens seg_max by one block so
    flooring window starts to the alignment never strands a region."""
    slot_tiles = max(1, Bpad // TILE_PUBS)
    zone = (S - glob_pad) if zone is None else zone
    zone = max(zone, 4096)  # bucketed zones are >=4096 and 2048-aligned
    fair = FAIR_MULT * zone // slot_tiles
    # pow2 ≥ 4096 (so %2048 holds for the packed extraction), clamped to
    # the zone (prepare_windows row bounds) and S (dynamic_slice bound) AND
    # to a memory cap: the [TP, seg] f32 mismatch intermediate must stay
    # ~256MB or multi-million-row tables (5M+ subs) blow the compile —
    # span tiles absorb the difference (same FLOPs, bounded memory)
    SEG_CAP = 262_144
    seg_max = min(_pow2ceil(max(4096, bucket_max + align, fair)),
                  max(SEG_CAP, _pow2ceil(bucket_max + align)),
                  zone - zone % 2048, S)
    # greedy packing closes a tile when its window span fills even if pub
    # slots remain, so tiles-needed ≈ slot tiles + span tiles; budget both
    # or overflow pubs fall to the host path (VERDICT r2: those scans are
    # the perf killer)
    span_tiles = -(-zone // seg_max)
    T = slot_tiles + span_tiles + 2
    # dense-phase pub chunk: [gc, glob_pad] f32 capped at ~1GB
    gc = min(Bpad, max(256, (1 << 28) // max(glob_pad, 1)))
    return T, seg_max, gc


def prepare_windows(pw: np.ndarray, pl: np.ndarray, pd: np.ndarray,
                    pb: np.ndarray, n: int, reg_start: np.ndarray,
                    reg_end: np.ndarray, S: int, T: int, seg_max: int,
                    row_lo: int = 0, row_hi: Optional[int] = None,
                    tp: Optional[int] = None, emit: str = "rows",
                    align: int = 0):
    """Host prep for the windowed kernels: sort the n real
    publishes by bucket, pack into at most T fixed tiles of ``tp``
    (default TILE_PUBS) slots each, window each tile at its first region's
    start. Pubs that cannot be tiled (window budget exhausted, or their
    region straddles the shard slice) come back as ``leftovers`` for
    exact host matching.

    ``row_lo``/``row_hi`` restrict to a shard's row slice (the sharded
    path preps each shard against its own rows; starts are emitted
    shard-local). Returns ``(t_pw, t_pl, t_pd, t_start, tile_of, pos_of,
    leftovers)``.

    ``emit="sel"`` skips building the duplicated row tiles and instead
    returns ``(t_sel, t_start, tile_of, pos_of, leftovers)`` where
    ``t_sel`` is a [T, TP] int32 pub-index selector (pad slots point at
    pub 0) — the flat kernel gathers tile pubs on device, cutting the
    per-batch upload ~8x (match_extract_windowed_flat).
    """
    L = pw.shape[1]
    TP = tp or TILE_PUBS
    hi_cap = S if row_hi is None else row_hi
    span = hi_cap - row_lo
    assert seg_max <= span, "window wider than the row slice"
    # sort by region ADDRESS: relocation (spare tail) makes reg_start
    # non-monotone in bucket id, and windows span contiguous addresses —
    # a bucket-id sort would strand every relocated bucket's pubs in the
    # host-fallback leftovers
    pbn = pb[:n]
    rs = reg_start[pbn].astype(np.int64)
    re_ = reg_end[pbn].astype(np.int64)
    order = np.argsort(rs, kind="stable")
    rows_mode = emit == "rows"
    if rows_mode:
        t_pw = np.full((T, TP, L), np.int32(K.PAD_ID), dtype=np.int32)
        t_pl = np.zeros((T, TP), dtype=np.int32)
        t_pd = np.zeros((T, TP), dtype=bool)
    t_sel = np.zeros((T, TP), dtype=np.int32)
    t_start = np.zeros(T, dtype=np.int32)
    tile_of = np.full(n, -1, dtype=np.int32)
    pos_of = np.zeros(n, dtype=np.int32)
    leftovers: List[int] = []
    # exact greedy packing over REGION GROUPS (not per pub — O(#regions)
    # python steps, <=NB per batch): consecutive regions share a tile
    # while the window spans them and slots remain; oversubscribed
    # regions split across tiles with the same window. Leftovers occur
    # only when >T windows would be needed (or a region straddles the
    # row slice in sharded mode).
    srs = rs[order]
    sre = re_[order]
    grp_first = np.concatenate([[0], np.nonzero(np.diff(srs))[0] + 1])
    grp_count = np.diff(np.concatenate([grp_first, [n]]))
    ti = -1
    cur_start = -1
    cur_used = TP  # force a new tile for the first group
    spans: List[Tuple[int, int, int, int]] = []  # (tile, slot0, lo, cnt)
    for g in range(len(grp_first)):
        lo = int(grp_first[g])
        c = int(grp_count[g])
        s0 = int(srs[lo])
        e0 = int(sre[lo])
        if s0 < row_lo or e0 > hi_cap:
            leftovers.extend(int(x) for x in order[lo:lo + c])
            continue  # region straddles the shard slice: host path
        placed = 0
        while placed < c:
            if (cur_used >= TP or e0 - cur_start > seg_max):
                if ti + 1 >= T:
                    leftovers.extend(
                        int(x) for x in order[lo + placed:lo + c])
                    break
                ti += 1
                cur_start = max(min(s0, hi_cap - seg_max), row_lo)
                if align:
                    # Pallas windows start on SEG_BLK boundaries (block
                    # index maps). Callers must guarantee row_lo (and the
                    # hi_cap - seg_max clamp) are themselves aligned —
                    # the production gate in _match_windowed checks
                    # S/glob_pad/gb_end % 2048 — and window_params
                    # widened seg_max by one block so flooring still
                    # spans the region. The assert below turns a missed
                    # gate into a loud failure instead of silently
                    # shifted slot ids (start_blk truncation).
                    cur_start = max(cur_start - cur_start % align, row_lo)
                    assert cur_start % align == 0, (
                        "unaligned window start: caller must gate on "
                        "row_lo/table alignment before using align=")
                cur_used = 0
                t_start[ti] = cur_start - row_lo
            take = min(c - placed, TP - cur_used)
            spans.append((ti, cur_used, lo + placed, take))
            cur_used += take
            placed += take
    for tid, slot0, lo, cnt in spans:
        sel = order[lo:lo + cnt]
        sl = slice(slot0, slot0 + cnt)
        if rows_mode:
            t_pw[tid, sl] = pw[sel]
            t_pl[tid, sl] = pl[sel]
            t_pd[tid, sl] = pd[sel]
        t_sel[tid, sl] = sel
        tile_of[sel] = tid
        pos_of[sel] = np.arange(slot0, slot0 + cnt, dtype=np.int32)
    if not rows_mode:
        return t_sel, t_start, tile_of, pos_of, leftovers
    return t_pw, t_pl, t_pd, t_start, tile_of, pos_of, leftovers


class MatcherBusy(Exception):
    """The matcher can't take this batch promptly.

    Raised by ``match_batch`` when the lock did not free within the
    caller's bound (``cold=False``) or when the batch's compile
    signature has never executed (``cold=True`` — a first XLA compile
    takes tens of seconds): the collector serves the flush from the
    host trie instead, bounding worst-case publish latency at roughly
    the bound, and kicks ``ensure_warm`` only for the cold case."""

    def __init__(self, cold: bool = False):
        super().__init__("cold signature" if cold else "lock busy")
        self.cold = cold


class DeviceDegraded(Exception):
    """The device match path is unavailable (circuit breaker open, or
    this very dispatch just failed and tripped/fed the breaker).

    Raised by ``match_batch``/``match_many`` instead of surfacing raw
    device errors: callers serve the batch from the exact host trie —
    the same correctness oracle the rebuild/busy sheds use — so a TPU
    outage degrades to host-path latency, never to lost or wrong
    fanouts. The breaker's half-open probe lets one real batch through
    per backoff window; when it succeeds the matcher re-warms and the
    device path resumes without a restart."""


class RebuildInProgress(Exception):
    """The device table is re-uploading after a capacity change.

    Raised by ``sync``/``match_batch`` instead of stalling the caller
    behind a full re-upload (seconds at millions of subscriptions over
    a host link). Callers serve the publish from the host trie — the
    correctness oracle maintained from the same subscriber-db events —
    so the publish pipeline keeps flowing while the new table builds in
    the background (the reference's trie applies events synchronously,
    vmq_reg_trie.erl:198-210; the stall this removes has no analog
    there)."""


class _FoldPhases:
    """The host phases of one fold, each a span under its family's name
    in the profiler's trace. Their milliseconds are observed together at
    the fold's end and only for a dispatch that counts
    (``stage_device_dispatch_ms``'s rule: no warm-up, no abandoned
    straggler, no failure)."""

    __slots__ = ("t_in", "lock_wait_ms", "ms", "_open", "phases")

    def __init__(self) -> None:
        self.t_in = time.monotonic()
        self.lock_wait_ms = 0.0
        self.ms: Dict[str, float] = {}
        self._open = None
        # the phases of the windowed program this fold executed ("a",
        # "ab", "gab", ...); empty for the unbucketed full scan
        self.phases = ""

    def enter(self, span) -> None:
        """Close the phase that is open and open ``span``."""
        self.close()
        self._open = span.begin()

    def close(self) -> None:
        sp, self._open = self._open, None
        if sp is not None:
            # a phase entered twice (resolve, around a wide pass) sums
            self.ms[sp.family] = (self.ms.get(sp.family, 0.0)
                                  + sp.end(record=False))

    def locked(self) -> None:
        self.lock_wait_ms = (time.monotonic() - self.t_in) * 1e3

    def record(self, t_disp: float, dur: float, **fields: Any) -> None:
        """One observation a family and the dispatch ring's record
        (``dur``: what ``stage_device_dispatch_ms`` observed)."""
        global phase_dispatches, phase_runs
        self.close()
        ms = self.ms.get
        prep = ms("stage_fold_prep_ms", 0.0)
        launch = ms("stage_fold_launch_ms", 0.0)
        wait = ms("stage_fold_wait_ms", 0.0)
        resolve = ms("stage_fold_resolve_ms", 0.0)
        obs.observe("stage_fold_prep_ms", prep)
        obs.observe("stage_fold_launch_ms", launch)
        obs.observe("stage_fold_wait_ms", wait)
        obs.observe("stage_fold_resolve_ms", resolve)
        wide = ms("stage_fold_wide_ms")
        if wide is not None:  # a dispatch that had a wide pass
            obs.observe("stage_fold_wide_ms", wide)
            fields["wide_ms"] = round(wide, 4)
        if self.phases:
            fields["phases"] = self.phases
            with _totals_lock:
                phase_dispatches += 1
                phase_runs += len(self.phases)
        record_dispatch(
            "match", t_disp, dur, prep_ms=round(prep, 4),
            launch_ms=round(launch, 4), wait_ms=round(wait, 4),
            resolve_ms=round(resolve, 4),
            lock_wait_ms=round(self.lock_wait_ms, 4), **fields)


class TpuMatcher:
    def __init__(self, max_levels: int = 16, initial_capacity: int = 1024,
                 max_fanout: int = 256, device=None, flat_avg: int = 128,
                 use_pallas: bool = False):
        import threading

        import jax

        self._jax = jax
        self.table = SubscriptionTable(max_levels, initial_capacity)
        self.max_fanout = max_fanout
        # Pallas tile matcher for the probe phases (ops/pallas_match.py);
        # a lowering error is a dispatch failure like any other (it
        # propagates to the breaker), never a silent switch of kernel
        self.use_pallas = use_pallas
        # packed transport: all per-batch host args go up as ONE int32
        # vector and all results come back as ONE; the per-slot
        # metadata rides in one device-resident int32 [S] pack_meta word
        self._meta = None
        # flat-compaction capacity per pub AVERAGED over the batch (the
        # [C = Bpad*flat_avg] device result buffer); a batch whose total
        # fanout exceeds it degrades per-pub to the host path, it never
        # drops
        self.flat_avg = flat_avg
        self.device = device or jax.devices()[0]
        self._dev_arrays: Optional[Tuple] = None
        self._operands: Optional[Tuple] = None  # (F_t, t1) coded MXU operands
        self._ops_bits = 0
        self._reg_start: Optional[np.ndarray] = None
        self._reg_end: Optional[np.ndarray] = None
        self._glob_pad = 0
        # SubscriptionTable.live_rows as of the device arrays: pinned
        # with the geometry above, never read off the table at dispatch
        self._live: Tuple[int, int] = (0, 0)
        self._bucketed = False
        self.match_batches = 0
        self.match_publishes = 0
        # warm_ladder's dummy traffic counts separately so operator
        # gauges and the loadtest collector line reflect REAL publishes
        self.warmup_batches = 0
        self.warmup_publishes = 0
        self.host_fallbacks = 0  # pubs served by exact host match
        self.super_dispatches = 0  # fused K-batch match_many dispatches
        # encode cache: hot topics (zipf streams) skip per-word interner
        # lookups; invalidated when the interner or bucket layout changes
        # (a cached UNKNOWN word may since have been interned)
        self._enc_cache: Dict[Tuple[str, ...], int] = {}
        self._enc_rows = np.zeros((1024, self.table.L + 4), dtype=np.int32)
        self._enc_gen: Tuple[int, int] = (-1, -1)
        # guards table mutation (event loop) vs sync/match (executor thread)
        self.lock = threading.Lock()
        # matches currently holding the device arrays (captured under the
        # lock, used after release): while > 0, sync() must not DONATE the
        # buffers to a delta scatter or the in-flight call's args die
        self._inflight = 0
        # non-blocking growth: a capacity rebuild at scale re-uploads the
        # whole table (seconds at millions of subs — the 28.6s
        # sub_to_matchable_max outlier in the r3 config-5 run was exactly
        # this stall). With async_rebuild the re-upload runs on a worker
        # thread while callers shed to the host trie (RebuildInProgress),
        # so the publish pipeline never stops. The FIRST build stays
        # synchronous (there is no old state to serve). Default OFF for
        # bare matchers (kernel tests take the inline path);
        # TpuRegView — the production seat, where a trie stands by —
        # turns it on.
        self.async_rebuild = False
        self._rebuild_thread: Optional[threading.Thread] = None
        self._rebuild_barrier: Optional[threading.Event] = None  # tests
        self.rebuilds_async = 0
        # stall watchdog (robustness/watchdog.py), set by the production
        # seat (TpuRegView): background rebuilds register a monitored op
        # and are ABANDONED past rebuild_deadline_s — sync() reaps the
        # wedged thread like a crashed one, its late install is
        # discarded, and the breaker is fed (the PR 4 failed-rebuild
        # rule extended to wedged rebuilds). None = unmonitored.
        self.watchdog: Optional[Any] = None
        self.rebuild_deadline_s = 120.0
        self._rebuild_token: Optional[dict] = None
        self.rebuild_abandons = 0
        self.dispatch_stalls = 0  # abandoned dispatches fed via record_stall
        self.busy_sheds = 0  # match_batch lock-timeout / cold-shape sheds
        # compile-signature warmth: a (arg-shapes, statics) signature is
        # warm once one execution completed. require_warm callers (the
        # collector) never dispatch live traffic into a COLD signature —
        # a first XLA compile takes tens of seconds and would head-block
        # the release queue for its whole duration; the trie serves while
        # ensure_warm compiles the shape in the background.
        self._warm_sigs: set = set()
        self._warming: set = set()
        self.warm_failures = 0  # background shape compiles that died
        # device-path circuit breaker (robustness/breaker.py): N
        # consecutive dispatch failures flip ALL matching to the host
        # trie until a half-open probe succeeds. Always present — a raw
        # device exception escaping the matcher would fail publishes —
        # but reconfigurable (TpuRegView applies the tpu_breaker_*
        # knobs; None disables and re-raises device errors verbatim).
        self.breaker: Optional[CircuitBreaker] = CircuitBreaker(name="match")
        self.device_failures = 0   # dispatch/upload errors fed to it
        self.degraded_sheds = 0    # calls refused while open (host-served)
        self.delta_shapes_warmed = 0  # pre-compiled scatter ladder rungs
        # last real traffic shape, for the post-recovery re-warm
        self._last_shape: Optional[tuple] = None
        # set by close(): background warm loops check it between rungs
        # so a stopped broker's threads wind down instead of compiling
        # shapes into a dead matcher
        self._closed = False

    def close(self) -> None:
        """Stop background warm work (broker shutdown / view teardown).
        Idempotent; in-flight matches complete normally."""
        self._closed = True

    # ------------------------------------------------------- full (re)build

    def _snapshot_host_locked(self, copy: bool = True,
                              clear: bool = True) -> dict:
        """Consistent host-side snapshot of everything a full device
        build needs. ``copy=True`` (the background path) materialises
        copies because the live arrays keep mutating after the lock is
        released; the inline first-build path passes the live refs.
        ``clear`` consumes ``resized``/``dirty`` at snapshot time so
        mutations AFTER it re-mark in the (unchanged-by-them) layout —
        the async path needs that; the inline path clears only after a
        SUCCESSFUL install so a failed build stays retryable."""
        t = self.table
        c = (lambda a: a.copy()) if copy else (lambda a: a)
        entries = np.empty(len(t.entries), dtype=object)
        # numpy object array: resolve-side fancy indexing is ~2.5x
        # faster than per-slot list indexing (measured 120ms -> 49ms
        # per 4096x61 batch)
        entries[:] = t.entries
        state = {
            "words": c(t.words), "eff_len": c(t.eff_len),
            "has_hash": c(t.has_hash), "first_wild": c(t.first_wild),
            "active": c(t.active), "bits": t.id_bits,
            "reg_start": t.reg_start.copy(),
            "reg_end": (t.reg_start + t.reg_cap).copy(),
            "glob_pad": int(t.reg_cap[0]), "live": t.live_rows,
            "gb_end": t.gb_end if t.bucketed else int(t.reg_cap[0]),
            "ng": t.NG, "bucketed": t.bucketed, "entries": entries,
        }
        if clear:
            t.resized = False
            t.dirty.clear()
        return state

    def _build_device(self, state: dict) -> tuple:
        """Device-side half of a full build (no lock held): upload the
        snapshot and derive the coded operands + packed meta."""
        faults.inject("device.rebuild")
        t0 = time.monotonic()
        put = lambda a: self._jax.device_put(a, self.device)
        dev = (put(state["words"]), put(state["eff_len"]),
               put(state["has_hash"]), put(state["first_wild"]),
               put(state["active"]))
        t_upload = time.monotonic()
        # derived coded operands (F/t1) live device-side next to the
        # base arrays; id_bits growth (interner crossing a byte plane)
        # forces this full rebuild path too
        operands = (K.build_operands(dev[0], dev[1], state["bits"])
                    if state["bits"] else None)
        meta = K.pack_meta(*dev[1:5])
        done = time.monotonic()
        # a watchdog-abandoned build's straggler must not record its
        # wedge-inflated duration: stage_rebuild_ms is the tuning base
        # for watchdog_rebuild_deadline_s — one drill would pin its
        # max/p99.9 forever (same discard rule as the breaker verdict)
        tok = getattr(_rebuild_tls, "token", None)
        if not (tok and tok.get("abandoned")) \
                and not watchdog_mod.current_op_abandoned():
            obs.observe("stage_rebuild_ms", (done - t0) * 1e3)
            record_dispatch(
                "rebuild", t0, (done - t0) * 1e3,
                rows=int(state["words"].shape[0]),
                upload_ms=round((t_upload - t0) * 1e3, 3),
                operands_ms=round((done - t_upload) * 1e3, 3))
        return dev, operands, meta

    def ensure_warm(self, n: int) -> None:
        """Compile the pow2-padded batch shape for ``n`` publishes on a
        background thread (idempotent per shape). The collector calls
        this when a cold signature sheds, so the next flush of this size
        finds the executable ready."""
        import threading

        Bpad = self._pad_batch(n)
        if Bpad in self._warming:
            return
        self._warming.add(Bpad)

        def _w() -> None:
            try:
                topics = [("warmup", "ladder", str(i)) for i in range(Bpad)]
                self._warm_wide()  # the shed may have been the wide pass's
                self.match_batch(topics, _warmup=True)
            except (RebuildInProgress, DeviceDegraded):
                pass  # table rebuilding / breaker open — retried later
            except Exception:
                # a shape that cannot compile pins its traffic on the
                # trie forever; that must be diagnosable, not silent
                self.warm_failures += 1
                import logging

                logging.getLogger("vernemq_tpu.matcher").exception(
                    "background warm-up of batch shape %d failed "
                    "(traffic of this size keeps serving via the host "
                    "trie; will retry on the next cold shed)", Bpad)
            finally:
                self._warming.discard(Bpad)

        # vmqlint: allow(thread-lifecycle): bounded fire-and-forget —
        # one warm-up compile per cold shape, deduped by _warming, that
        # exits on its own; joining would make close() wait out XLA
        threading.Thread(target=_w, name=f"tpu-warm-{Bpad}",
                         daemon=True).start()

    # -------------------------------------------------- breaker discipline

    def _breaker_gate(self, warmup: bool) -> bool:
        """Refuse device work while the breaker is open (DeviceDegraded:
        the caller serves from the host trie). Real traffic may win the
        half-open probe slot; warmups never do — a dummy batch must not
        consume the one probe per backoff window. Returns True when THIS
        call holds the probe (the caller must hand it back via
        ``probe_aborted`` if it exits without a device verdict)."""
        br = self.breaker
        if br is None:
            return False
        if warmup:
            if not br.is_closed:
                raise DeviceDegraded("breaker not closed; warmup refused")
            return False
        if not br.allow():
            self.degraded_sheds += 1
            raise DeviceDegraded("device circuit open")
        return br.state_name == "half_open"

    def _record_device_failure(self, exc: BaseException) -> None:
        """Feed a device dispatch/upload failure to the breaker and
        re-raise as DeviceDegraded (host trie serves this batch). With
        no breaker installed the original error propagates verbatim.

        A dispatch whose waiter the stall watchdog already released
        records NOTHING: the stall was fed to the breaker as a failure
        at abandonment (``record_stall``), so a late error must not
        double-count — and a late error from a probe must not double
        the backoff the stall already applied."""
        self.device_failures += 1
        br = self.breaker
        if br is None:
            raise exc
        if watchdog_mod.current_op_abandoned():
            raise DeviceDegraded(
                f"late failure of abandoned dispatch: {exc!r}") from exc
        import logging

        if br.record_failure():
            logging.getLogger("vernemq_tpu.matcher").error(
                "device path OPENED after %d consecutive failures "
                "(last: %s); all matching degrades to the host trie",
                br.failure_threshold, exc)
        raise DeviceDegraded(f"device dispatch failed: {exc!r}") from exc

    def record_stall(self, exc: Optional[BaseException] = None) -> None:
        """An abandoned (deadline-overrun) dispatch is a device failure:
        feed the breaker so matching flips to the host trie instead of
        queueing more waiters into a wedged device. Called by the
        collector when the stall watchdog releases its waiter — the
        stalled call itself records nothing on late completion (see the
        abandoned-op guards in ``_record_device_success``/``_failure``)."""
        self.dispatch_stalls += 1
        try:
            self._record_device_failure(
                exc if exc is not None
                else RuntimeError("device dispatch stalled past deadline"))
        except Exception:
            pass  # DeviceDegraded (breaker fed) or re-raised exc (no breaker)

    def _record_device_success(self, warmup: bool = False) -> None:
        br = self.breaker
        if br is None:
            return
        if watchdog_mod.current_op_abandoned():
            # late success of an abandoned dispatch: the device may be
            # back, but this verdict raced a stall the breaker already
            # absorbed as a failure — only a LIVE probe may close it
            # (otherwise a wedge-released straggler would flip the
            # breaker shut the instant the stall opened it)
            return
        if warmup and not br.is_closed:
            # a warmup that entered dispatch BEFORE the outage landed
            # can complete after the breaker opened; its stale success
            # must not close the breaker — only a real traffic probe
            # proves the device path is back
            return
        if br.record_success():
            import logging

            logging.getLogger("vernemq_tpu.matcher").warning(
                "device path recovered (probe succeeded after %.1fs "
                "degraded); re-warming and closing the breaker",
                br.time_degraded())
            self._rewarm_after_recovery()

    def _rewarm_after_recovery(self) -> None:
        """Background-compile the last live traffic shape after the
        breaker closes, so the first post-recovery flushes of that size
        find a warm signature instead of shedding cold."""
        shape = self._last_shape
        if shape is None:
            return
        if shape[0] == "many":
            self.ensure_warm_many(shape[1], shape[2])
        else:
            self.ensure_warm(shape[1])

    def _install_built(self, built: tuple, state: dict) -> None:
        """Publish a finished build as the serving state (lock held)."""
        # new table geometry → every compiled signature is stale
        self._warm_sigs.clear()
        self._dev_arrays, self._operands, self._meta = built
        self._ops_bits = state["bits"]
        self._reg_start = state["reg_start"]
        self._reg_end = state["reg_end"]
        self._glob_pad = state["glob_pad"]
        self._live = state["live"]
        self._gb_end = state["gb_end"]
        self._ng = state["ng"]
        self._bucketed = state["bucketed"]
        self._entries_snapshot = state["entries"]

    def _abandon_rebuild(self, token: dict) -> None:
        """Stall-watchdog ``on_stall``: the background rebuild exceeded
        its deadline. Treat it exactly like a crashed one (the PR 4 rule
        extended to wedges): mark its token so sync() reaps it and its
        late install is discarded, and feed the breaker so matching
        degrades loudly NOW instead of shedding RebuildInProgress
        silently forever. Runs on the monitor thread — no matcher lock
        (the wedged holder might be inside it)."""
        if token.get("abandoned"):
            return
        token["abandoned"] = True
        self.rebuild_abandons += 1
        self.device_failures += 1
        br = self.breaker
        if br is not None and br.record_failure():
            import logging

            logging.getLogger("vernemq_tpu.matcher").error(
                "device path OPENED: background table rebuild stalled "
                "past its %.1fs deadline (abandoned; host trie serves)",
                self.rebuild_deadline_s)

    def _spawn_rebuild_locked(self) -> None:
        """Kick the background rebuild (lock held). The thread builds
        from a snapshot; at install time, if the layout moved AGAIN
        (another resize while uploading) or the stall watchdog abandoned
        this build, the stale build is discarded — installing it would
        let live-layout encodings hit an older device layout (or, for an
        abandoned build, resurrect state the table has moved past)."""
        import threading

        state = self._snapshot_host_locked(copy=True)
        self.rebuilds_async += 1
        token = {"abandoned": False}
        self._rebuild_token = token
        wd = self.watchdog
        op = (wd.register("device.rebuild", self.rebuild_deadline_s,
                          label="table-rebuild",
                          on_stall=lambda _op: self._abandon_rebuild(token))
              if wd is not None and self.rebuild_deadline_s > 0 else None)

        def _run() -> None:
            _rebuild_tls.token = token  # observability straggler guard
            try:
                try:
                    built = self._build_device(state)
                except Exception:
                    import logging

                    if token["abandoned"]:
                        wd.note_late_discard("device.rebuild",
                                             "failed after abandonment")
                        return
                    logging.getLogger(__name__).exception(
                        "background table rebuild failed; will retry "
                        "from the next sync")
                    return  # sync() reaps the dead thread, re-arms resized
                barrier = self._rebuild_barrier
                if barrier is not None:
                    barrier.wait()
                with self.lock:
                    if token["abandoned"] or self._rebuild_thread is not th:
                        # the watchdog abandoned this build (sync has
                        # reaped it and may already be running a fresh
                        # one): a late install would publish stale
                        # layout — discard, never deliver
                        if wd is not None:
                            wd.note_late_discard("device.rebuild",
                                                 "stale install discarded")
                        return
                    t = self.table
                    if t.resized or t.id_bits != state["bits"]:
                        self._spawn_rebuild_locked()
                        return
                    self._install_built(built, state)
                    self._rebuild_thread = None
            finally:
                if op is not None:
                    wd.deregister(op)

        # vmqlint: allow(thread-lifecycle): cooperative stop by design —
        # _run observes close()'s _closed flag and the watchdog abandon
        # token and DISCARDS its install; sync() reaps the handle. A
        # join would park shutdown behind a possibly-wedged device call.
        th = threading.Thread(target=_run, name="tpu-table-rebuild",
                              daemon=True)
        self._rebuild_thread = th
        th.start()

    # ------------------------------------------------------------ delta sync

    def sync(self) -> None:
        """Ship pending table mutations to the device: full upload after a
        capacity change, scatter of dirty slots otherwise. Also snapshots
        the slot->entry map so results of an in-flight device call resolve
        against the state that was actually matched (a slot freed+reused
        mid-call must not misroute to the new subscriber). Callers hold
        ``self.lock``."""
        t = self.table
        bits = t.id_bits
        if self._rebuild_thread is not None:
            tok = self._rebuild_token
            abandoned = tok is not None and tok.get("abandoned")
            if self._rebuild_thread.is_alive() and not abandoned:
                raise RebuildInProgress
            # crashed worker — or one the stall watchdog abandoned (a
            # wedged build is reaped exactly like a failed one): the
            # snapshot consumed `resized`, so re-arm it — falling
            # through to the delta path would scatter grown-region
            # slots out of bounds against the OLD arrays (silently
            # dropped) and serve wrong fanout forever. The abandoned
            # thread, if it ever completes, sees its token (or the
            # thread mismatch) and discards its install.
            self._rebuild_thread = None
            t.resized = True
        if self._dev_arrays is None or t.resized or bits != self._ops_bits:
            if self._dev_arrays is not None and self.async_rebuild:
                # non-blocking growth: snapshot host state NOW (the live
                # arrays keep mutating) and upload on a worker thread;
                # callers shed to the host trie until the install
                self._spawn_rebuild_locked()
                raise RebuildInProgress
            # clear-after-success: a failed inline build must retry
            state = self._snapshot_host_locked(copy=False, clear=False)
            self._install_built(self._build_device(state), state)
            t.resized = False
            t.dirty.clear()
            return
        if not t.dirty:
            return
        slots = np.fromiter(t.dirty, dtype=np.int32)
        t.dirty.clear()
        # pad the delta to a pow2 ladder: a distinct slot COUNT is a
        # distinct scatter shape, and uncapped counts recompile every sync
        # (each a compile of its own). Duplicate
        # last-slot writes are idempotent (same value).
        Dpad = _pow2ceil(len(slots))
        if Dpad != len(slots):
            slots = np.concatenate(
                [slots, np.full(Dpad - len(slots), slots[-1], np.int32)])
        # copy-on-write: in-flight match_batch calls hold a reference to the
        # previous snapshot array; mutating it in place would let a slot
        # freed+reused mid-call misroute to the new subscriber
        snap = self._entries_snapshot.copy()
        for s in slots:
            snap[s] = t.entries[s]
        self._entries_snapshot = snap
        try:
            self._apply_delta_device(slots)
        except Exception:
            # the dirty set is already consumed but the device scatter
            # did not land: without repair the device table serves stale
            # rows forever. Re-arm `resized` so the next sync takes the
            # full-rebuild path (host and device re-converge), and let
            # the error feed the caller's breaker.
            t.resized = True
            raise
        # region geometry may have moved WITHOUT a resize (bucket
        # relocation into the spare tail) — refresh the window view, and
        # the live counts that say which phases the scattered rows need
        self._reg_start = t.reg_start.copy()
        self._reg_end = (t.reg_start + t.reg_cap).copy()
        self._live = t.live_rows

    def _apply_delta_device(self, slots: np.ndarray) -> None:
        """Device half of a delta sync: scatter the (padded) ``slots``
        of the host table into the device arrays. Lock held; callers
        come through :meth:`sync` only (:meth:`warm_delta_ladder`
        deliberately bypasses this — it compiles the same kernels
        against throwaway zero arrays, outside the lock and without
        the fault hook). Registered with the stall watchdog when one is
        wired: a wedge here holds the matcher lock, so it cannot be
        abandoned from outside — but it IS visible (watchdog_stalls,
        `vmq-admin watchdog show`) while the lock-timeout sheds and the
        dispatch deadline bound everyone else's wait."""
        wd = self.watchdog
        if wd is None:
            return self._apply_delta_device_impl(slots)
        with wd.monitored("device.delta", 30.0,
                          label=f"scatter:{len(slots)}"):
            return self._apply_delta_device_impl(slots)

    def _apply_delta_device_impl(self, slots: np.ndarray) -> None:
        faults.inject("device.delta")
        t_obs = time.monotonic()
        self._apply_delta_device_inner(slots)
        # success-only + straggler-guarded: a failed or watchdog-
        # abandoned scatter must not feed the sub_to_matchable tuning
        # base with fault/wedge durations
        if not watchdog_mod.current_op_abandoned():
            dur = (time.monotonic() - t_obs) * 1e3
            obs.observe("stage_delta_scatter_ms", dur)
            record_dispatch("delta", t_obs, dur, dpad=int(len(slots)))

    def _apply_delta_device_inner(self, slots: np.ndarray) -> None:
        t = self.table
        sw, el, hh, fw, ac = self._dev_arrays
        # donating scatters update in place (a 128-slot delta at 5M subs
        # otherwise copies ~500MB of HBM, ~300ms measured); fall back to
        # the copying variants while a dispatched match still holds refs
        donate = self._inflight == 0
        if self._operands is not None:
            # fused transport: ONE packed upload + ONE call updates base
            # arrays, coded operands and the meta word together (the
            # unfused path takes 6 uploads + 2 dispatches per delta)
            packed = K.delta_pack_args(
                slots, t.words[slots], t.eff_len[slots],
                t.has_hash[slots], t.first_wild[slots], t.active[slots])
            fused = (K.apply_delta_fused if donate
                     else K.apply_delta_fused_copy)
            self._dev_arrays, self._operands, self._meta = fused(
                sw, el, hh, fw, ac, *self._operands, self._meta,
                self._jax.device_put(packed, self.device),
                D=len(slots), L=t.words.shape[1], id_bits=self._ops_bits)
        else:
            # no coded operands (id_bits 0: a vocabulary past 24 bits)
            slots_dev = self._jax.device_put(slots, self.device)
            w_dev = self._jax.device_put(t.words[slots], self.device)
            e_dev = self._jax.device_put(t.eff_len[slots], self.device)
            hh_dev = self._jax.device_put(t.has_hash[slots], self.device)
            fw_dev = self._jax.device_put(t.first_wild[slots], self.device)
            ac_dev = self._jax.device_put(t.active[slots], self.device)
            delta = K.apply_delta if donate else K.apply_delta_copy
            self._dev_arrays = delta(
                sw, el, hh, fw, ac, slots_dev, w_dev, e_dev,
                hh_dev, fw_dev, ac_dev,
            )
            dm = K.apply_delta_meta if donate else K.apply_delta_meta_copy
            self._meta = dm(self._meta, slots_dev, e_dev, hh_dev,
                            fw_dev, ac_dev)

    def warm_delta_ladder(self, max_delta: int = 128) -> int:
        """Pre-compile the delta-scatter shape ladder (Dpad = 2..pow2 ≤
        ``max_delta``) so the first post-subscribe flush after boot pays
        a scatter, not a compile — the ``sub_to_matchable_ms_max`` tail
        chaser (ROADMAP). Returns rungs compiled.

        The lock is held only to snapshot the table GEOMETRY; every
        compile runs against throwaway zero arrays of the live shapes
        (jit caches key on shapes/dtypes/statics, so production deltas
        hit the warmed executables) — holding the lock across a
        multi-second first-compile would shed every live flush AND
        block real delta syncs for the duration, the exact stall this
        warm exists to remove."""
        with self.lock:
            try:
                self.sync()  # first build, or bail during a rebuild
            except RebuildInProgress:
                return 0
            if self._dev_arrays is None:
                return 0
            shapes = [(a.shape, np.dtype(a.dtype))
                      for a in self._dev_arrays]
            op_shapes = ([(a.shape, np.dtype(a.dtype))
                          for a in self._operands]
                         if self._operands is not None else None)
            meta_shape = (self._meta.shape, np.dtype(self._meta.dtype))
            bits = self._ops_bits
            L = self.table.words.shape[1]
        put = lambda a: self._jax.device_put(a, self.device)

        def zeros(specs):
            return tuple(put(np.zeros(sh, dt)) for sh, dt in specs)

        done = 0
        d = 2
        while d <= max_delta:
            if self._closed:
                return done
            slots = np.zeros(d, dtype=np.int32)
            zw = np.zeros((d, L), np.int32)
            zi = np.zeros(d, np.int32)
            zb = np.zeros(d, dtype=bool)
            # warm the donating AND the copying executables: production
            # picks the *_copy variants whenever a dispatched match
            # still holds the arrays (_inflight > 0) — under continuous
            # traffic that is the COMMON case, and each variant is a
            # separate jitted program
            if op_shapes is not None:
                packed = put(K.delta_pack_args(slots, zw, zi, zb, zb, zb))
                for fn in (K.apply_delta_fused, K.apply_delta_fused_copy):
                    fn(*zeros(shapes), *zeros(op_shapes),
                       *zeros([meta_shape]), packed,
                       D=d, L=L, id_bits=bits)
            else:
                for fn in (K.apply_delta, K.apply_delta_copy):
                    fn(*zeros(shapes), put(slots), put(zw),
                       put(zi), put(zb), put(zb), put(zb))
                for fn in (K.apply_delta_meta, K.apply_delta_meta_copy):
                    fn(*zeros([meta_shape]), put(slots),
                       put(zi), put(zb), put(zb), put(zb))
            self.delta_shapes_warmed += 1
            done += 1
            d *= 2
        return done

    # ---------------------------------------------------------------- match

    def _pad_batch(self, n: int) -> int:
        b = 8
        while b < n:
            b *= 2
        return b

    def encode_batch(self, topics: Sequence[Sequence[str]]):
        B = self._pad_batch(len(topics))
        L = self.table.L
        pw = np.full((B, L), K.PAD_ID, dtype=np.int32)
        pl = np.zeros(B, dtype=np.int32)
        pd = np.zeros(B, dtype=bool)
        for i, t in enumerate(topics):
            row, n, dollar = self.table.encode_topic(t)
            pw[i], pl[i], pd[i] = row, n, dollar
        return pw, pl, pd

    def _encode_batch_ex(self, topics: Sequence[Sequence[str]]):
        """encode_batch + per-real-topic bucket ids (for the windowed
        path), through the hot-topic cache: one dict hit + a single numpy
        gather per batch instead of per-topic row building (~5x less host
        encode time on skewed streams)."""
        t = self.table
        gen = (len(t.interner), t.NB)
        if self._enc_gen != gen:
            self._enc_cache.clear()
            self._enc_gen = gen
        cache = self._enc_cache
        rows = self._enc_rows
        L = t.L
        idxs = np.empty(len(topics), dtype=np.int32)
        for i, tp in enumerate(topics):
            tp = tuple(tp)
            j = cache.get(tp)
            if j is None:
                row, n, dollar, bucket, gbucket = t.encode_topic_ex(tp)
                j = len(cache)
                if j >= rows.shape[0]:
                    if j >= 1 << 20:  # bound memory on adversarial streams
                        cache.clear()
                        rows = np.zeros((1024, L + 4), dtype=np.int32)
                        self._enc_rows = rows  # release the grown buffer too
                        self._enc_gen = (-1, -1)
                        return self._encode_batch_ex(topics)
                    rows = np.vstack([rows, np.zeros_like(rows)])
                    self._enc_rows = rows
                rows[j, :L] = row
                rows[j, L] = n
                rows[j, L + 1] = dollar
                rows[j, L + 2] = bucket
                rows[j, L + 3] = gbucket
                cache[tp] = j
            idxs[i] = j
        B = self._pad_batch(len(topics))
        sel = rows[idxs]
        pw = np.full((B, L), K.PAD_ID, dtype=np.int32)
        pl = np.zeros(B, dtype=np.int32)
        pd = np.zeros(B, dtype=bool)
        pw[:len(topics)] = sel[:, :L]
        pl[:len(topics)] = sel[:, L]
        pd[:len(topics)] = sel[:, L + 1].astype(bool)
        pb = sel[:, L + 2].copy()
        gb = sel[:, L + 3].copy()
        return pw, pl, pd, pb, gb

    def warm_ladder(self, max_batch: int = 4096) -> int:
        """Pre-compile the Bpad ladder: run one dummy match at every
        pow2 batch size up to ``max_batch`` so live traffic never pays a
        first-compile stall (tens of seconds per shape on a cold
        backend; measured as the whole p99 in broker-level runs).
        Returns the number of shapes compiled. Safe to call from an
        executor thread — match_batch takes the lock per call."""
        done = 0
        b = 1
        while b <= max_batch:
            if self._closed:
                return done
            topics = [("warmup", "ladder", str(i)) for i in range(b)]
            try:
                if b == 1:
                    # first, so that whoever waits for the last rung has
                    # the wide programs too
                    self._warm_wide()
                self.match_batch(topics, _warmup=True)
            except (RebuildInProgress, DeviceDegraded):
                return done  # rebuilding / breaker open: warm on demand
            done += 1
            b *= 2
        return done

    def match_batch(self, topics: Sequence[Sequence[str]],
                    _warmup: bool = False,
                    lock_timeout: Optional[float] = None,
                    require_warm: bool = False) -> List[List[Row]]:
        """Match a batch of publish topics; returns per-topic entry rows
        (the per-publish fold results). ``lock_timeout`` bounds the wait
        for the matcher lock (seconds): past it, MatcherBusy — the
        caller serves the batch host-side instead of head-blocking
        behind a long hold. ``require_warm`` additionally refuses a COLD
        compile signature (MatcherBusy) so a first-compile can never
        stall live traffic; ``ensure_warm`` compiles it off to the side."""
        if not topics:
            return []
        probe = self._breaker_gate(_warmup)
        try:
            return self._match_batch_impl(topics, _warmup, lock_timeout,
                                          require_warm)
        except BaseException:
            if probe:
                # the granted half-open probe exited without a device
                # verdict (lock busy / rebuild shed / cold shape, or
                # any host-side error before dispatch): hand the slot
                # back so the breaker can't wedge half-open — no-op
                # when a recorded failure already re-opened it
                self.breaker.probe_aborted()
            raise

    def _match_batch_impl(self, topics, _warmup, lock_timeout,
                          require_warm) -> List[List[Row]]:
        ph = _FoldPhases()
        ph.enter(obs.span("stage_fold_prep_ms"))
        try:
            return self._match_batch_phased(topics, _warmup, lock_timeout,
                                            require_warm, ph)
        finally:
            ph.close()

    def _match_batch_phased(self, topics, _warmup, lock_timeout,
                            require_warm, ph) -> List[List[Row]]:
        if lock_timeout is None:
            self.lock.acquire()
        elif not self.lock.acquire(timeout=lock_timeout):
            self.busy_sheds += 1
            raise MatcherBusy(cold=False)
        ph.locked()
        try:
            try:
                self.sync()
            except RebuildInProgress:
                raise
            except Exception as e:
                # a failed upload (delta scatter / inline build) is a
                # device failure: feed the breaker, serve host-side
                self._record_device_failure(e)
            dev_arrays = self._dev_arrays
            operands = self._operands
            meta = self._meta
            snapshot = self._entries_snapshot
            bucketed = self._bucketed and operands is not None
            if bucketed:
                reg_start, reg_end = self._reg_start, self._reg_end
                glob_pad, bits = self._glob_pad, self._ops_bits
                live = self._live
                pw, pl, pd, pb, gb = self._encode_batch_ex(topics)
            else:
                pw, pl, pd = self.encode_batch(topics)
            self._inflight += 1  # sync() must not donate our buffers away
        finally:
            self.lock.release()
        if _warmup:
            self.warmup_batches += 1
            self.warmup_publishes += len(topics)
        else:
            self.match_batches += 1
            self.match_publishes += len(topics)
            self._last_shape = ("batch", len(topics))
        t_disp = time.monotonic()
        warm_before = len(self._warm_sigs)
        dur = None  # set for a dispatch that counts
        try:
            if bucketed:
                idx_rows, need_host = self._match_windowed(
                    dev_arrays, operands, meta, reg_start, reg_end,
                    glob_pad, live, bits, pw, pl, pd, pb, gb, len(topics),
                    ph, require_warm=require_warm)
            else:
                chunk = 1024 if pw.shape[0] > 1024 else 0  # lax.map serialises
                # full-scan fallback: MXU matmul path needs byte-splittable
                # ids and a block-aligned table; else the VPU scan. The -1
                # keeps the top id clear of UNKNOWN_ID's byte planes
                # (-2 → 254,255,255)
                S = dev_arrays[0].shape[0]
                fast = (len(self.table.interner)
                        < (1 << 24) - K.FIRST_WORD_ID - 1
                        and S % 2048 == 0 and S >= 2048)
                sig = ("simple", pw.shape, int(S), fast, chunk,
                       self.max_fanout)
                if require_warm and sig not in self._warm_sigs:
                    self.busy_sheds += 1
                    raise MatcherBusy(cold=True)
                ph.enter(obs.span("stage_fold_launch_ms"))
                faults.inject("device.dispatch")
                matcher = K.match_extract_mxu if fast else K.match_extract
                idx, valid, count = matcher(
                    *dev_arrays, pw, pl, pd, k=self.max_fanout, chunk=chunk
                )
                ph.enter(obs.span("stage_fold_wait_ms"))
                idx = np.asarray(idx)
                valid = np.asarray(valid)
                counts = np.asarray(count)
                ph.enter(obs.span("stage_fold_resolve_ms"))
                idx_rows = [idx[i][valid[i]] for i in range(len(topics))]
                need_host = counts[:len(topics)] > self.max_fanout
                self._warm_sigs.add(sig)
        except MatcherBusy:
            raise
        except Exception as e:
            self._record_device_failure(e)
        else:
            self._record_device_success(_warmup)
            # straggler guard: a watchdog-abandoned dispatch's late
            # completion must not record its wedge-inflated duration —
            # this histogram is the tuning base for
            # watchdog_dispatch_deadline_ms (same rule as the breaker
            # verdict suppression in _record_device_success)
            if not _warmup and not watchdog_mod.current_op_abandoned():
                dur = (time.monotonic() - t_disp) * 1e3
                obs.observe("stage_device_dispatch_ms", dur)
        finally:
            with self.lock:
                self._inflight -= 1
        out = self._resolve_rows(topics, idx_rows, need_host, snapshot)
        if dur is not None:
            ph.record(
                t_disp, dur, k=1, batch=len(topics),
                bpad=int(pw.shape[0]),
                # a dispatch that grew the warm-signature set just
                # paid an XLA compile; everything else executed a
                # cached executable (compile-vs-execute detection)
                compiled=len(self._warm_sigs) > warm_before)
        return out

    def _resolve_rows(self, topics, idx_rows, need_host,
                      snapshot) -> List[List[Row]]:
        """Host-side result resolution shared by match_batch and
        match_many: device slot ids -> entry rows via the pinned
        snapshot, with the exact host fallback for pubs the device could
        not serve."""
        out: List[List[Row]] = []
        for i, topic in enumerate(topics):
            if need_host[i]:
                # truncated fanout / untiled pub: fall back to exact host
                # matching so no subscriber is silently skipped
                self.host_fallbacks += 1
                rows = self._host_match(topic, snapshot)
                out.append(rows)
                continue
            rows = [e for e in snapshot[idx_rows[i]] if e is not None]
            with self.lock:
                if len(self.table.overflow):
                    # >L-level filters live host-side; device rows stay
                    # valid for any topic length (only concrete levels
                    # <= L are compared)
                    rows = rows + self.table.overflow.match(list(topic))
            out.append(rows)
        return out

    def match_many(self, batches: Sequence[Sequence[Sequence[str]]],
                   _warmup: bool = False,
                   lock_timeout: Optional[float] = None,
                   require_warm: bool = False) -> List[List[List[Row]]]:
        """Match K publish batches in ONE device dispatch (the
        kernel-resident multi-batch pipeline): every batch is encoded and
        window-prepped against one consistent table snapshot, padded to a
        COMMON Bpad, staged as one stacked transport block and run K
        times on device via ``lax.scan`` (ops.match_kernel.match_many) —
        K round trips become one. Results are per batch, bit-identical
        to K independent :meth:`match_batch` calls at the same Bpad.

        Falls back to sequential match_batch calls when the fused path
        is unavailable (unbucketed table or K == 1).
        ``lock_timeout``/``require_warm`` follow match_batch's contract.
        """
        if not batches:
            return []
        probe = self._breaker_gate(_warmup)
        try:
            return self._match_many_impl(batches, _warmup, lock_timeout,
                                         require_warm)
        except BaseException:
            if probe:
                self.breaker.probe_aborted()  # see match_batch
            raise

    def _match_many_impl(self, batches, _warmup, lock_timeout,
                         require_warm) -> List[List[List[Row]]]:
        batches = [list(b) for b in batches]
        if not batches:
            return []
        ph = _FoldPhases()
        ph.enter(obs.span("stage_fold_prep_ms"))
        try:
            return self._match_many_phased(batches, _warmup, lock_timeout,
                                           require_warm, ph)
        finally:
            ph.close()

    def _match_many_phased(self, batches, _warmup, lock_timeout,
                           require_warm, ph) -> List[List[List[Row]]]:
        if lock_timeout is None:
            self.lock.acquire()
        elif not self.lock.acquire(timeout=lock_timeout):
            self.busy_sheds += 1
            raise MatcherBusy(cold=False)
        ph.locked()
        fast = False
        try:
            try:
                self.sync()
            except RebuildInProgress:
                raise
            except Exception as e:
                self._record_device_failure(e)
            operands = self._operands
            meta = self._meta
            snapshot = self._entries_snapshot
            dev_arrays = self._dev_arrays
            fast = (len(batches) > 1 and self._bucketed
                    and operands is not None)
            if fast:
                reg_start, reg_end = self._reg_start, self._reg_end
                glob_pad, bits = self._glob_pad, self._ops_bits
                live = self._live
                S = int(dev_arrays[0].shape[0])
                Bpad = max(self._pad_batch(len(b)) for b in batches)
                # only the encode (table interner access) needs the
                # lock; the heavy window prep (_flat_prep) runs on the
                # pinned snapshot args AFTER release, like match_batch
                encoded = []
                for topics in batches:
                    pw, pl, pd, pb, gb = self._encode_batch_ex(topics)
                    pw, pl, pd = _pad_pub_block(pw, pl, pd, Bpad)
                    encoded.append((pw, pl, pd, pb, gb))
                self._inflight += 1
        finally:
            self.lock.release()
        if not fast:
            # impl, not the public wrapper: passage through the breaker
            # gate was already granted (re-entering could eat or be
            # refused the half-open probe this call holds)
            ph.close()  # each batch is a fold of its own, with its phases
            return [self._match_batch_impl(topics, _warmup, lock_timeout,
                                           require_warm)
                    for topics in batches]
        n_pubs = sum(len(b) for b in batches)
        if _warmup:
            self.warmup_batches += len(batches)
            self.warmup_publishes += n_pubs
        else:
            self.match_batches += len(batches)
            self.match_publishes += n_pubs
            self._last_shape = ("many", len(batches),
                                max(len(b) for b in batches))
        t_disp = time.monotonic()
        warm_before = len(self._warm_sigs)
        dur = None  # set for a dispatch that counts
        try:
            preps: List[tuple] = []
            lefts: List[set] = []
            statics = None
            for topics, (pw, pl, pd, pb, gb) in zip(batches, encoded):
                args, statics, left = self._flat_prep(
                    reg_start, reg_end, glob_pad, live, bits, S,
                    pw, pl, pd, pb, gb, len(topics))
                preps.append(args)
                lefts.append(left)
            ph.phases = self._phases(statics)
            sig = ("many", len(batches),
                   tuple(a.shape for a in preps[0]),
                   tuple(sorted(statics.items())))
            if require_warm and sig not in self._warm_sigs:
                self.busy_sheds += 1
                raise MatcherBusy(cold=True)
            F_t, t1 = operands
            ph.enter(obs.span("stage_fold_launch_ms"))
            out = K.call_match_many(F_t, t1, meta, preps, statics,
                                    device=self.device)
            ph.enter(obs.span("stage_fold_wait_ms"))
            out = np.asarray(out)  # the ONE host pull
            ph.enter(obs.span("stage_fold_resolve_ms"))
            results = K.unpack_many_results(out, Bpad, statics["C"])
            self._warm_sigs.add(sig)
            if not _warmup:
                self.super_dispatches += 1
            folded: List[tuple] = []  # (idx_rows, need_host) a batch
            wide: List[tuple] = []
            for topics, enc, (flat, pre, total, overflow), left in zip(
                    batches, encoded, results, lefts):
                idx_rows, need_host, over = self._flat_views(
                    len(topics), flat, pre, total, overflow, left)
                folded.append((idx_rows, need_host))
                if len(over):
                    wide.append((*enc, over, total, idx_rows, need_host))
            if wide:  # ONE wide pass for the K batches' overflowed topics
                ph.enter(obs.span("stage_fold_wide_ms"))
                self._wide_pass(operands, meta, reg_start, reg_end,
                                glob_pad, live, bits, S, wide, require_warm)
                ph.enter(obs.span("stage_fold_resolve_ms"))
        except MatcherBusy:
            raise
        except Exception as e:
            self._record_device_failure(e)
        else:
            self._record_device_success(_warmup)
            # straggler guard — see match_batch
            if not _warmup and not watchdog_mod.current_op_abandoned():
                dur = (time.monotonic() - t_disp) * 1e3
                obs.observe("stage_device_dispatch_ms", dur)
        finally:
            with self.lock:
                self._inflight -= 1
        outs = [self._resolve_rows(topics, idx_rows, need_host, snapshot)
                for topics, (idx_rows, need_host) in zip(batches, folded)]
        if dur is not None:
            ph.record(t_disp, dur, k=len(batches), batch=n_pubs,
                      bpad=int(Bpad),
                      compiled=len(self._warm_sigs) > warm_before)
        return outs

    @property
    def supports_match_many(self) -> bool:
        """Whether the fused K-batch dispatch path is available
        (bucketed table layout + codable ids — table state, not device
        state: match_many syncs before dispatch, so a not-yet-built
        table still qualifies). The collector gates super-batching on
        this so an unbucketed matcher is never fed K windows it would
        only serialize — that would deepen the overload queue with zero
        amortization."""
        t = self.table
        return bool(t.bucketed and t.id_bits)

    def ensure_warm_many(self, n_batches: int, n: int) -> None:
        """Background-compile the K-batch super-dispatch signature for
        ``n_batches`` windows of ``n`` publishes (idempotent per shape) —
        the match_many analog of :meth:`ensure_warm`, kicked by the
        collector when a cold super-batch sheds."""
        import threading

        key = ("many", n_batches, self._pad_batch(n))
        if key in self._warming:
            return
        self._warming.add(key)

        def _w() -> None:
            try:
                Bpad = self._pad_batch(n)
                batches = [
                    [("warmup", "ladder", str(i)) for i in range(Bpad)]
                    for _ in range(n_batches)]
                self._warm_wide()
                self.match_many(batches, _warmup=True)
            except (RebuildInProgress, DeviceDegraded):
                pass  # table rebuilding / breaker open — retried later
            except Exception:
                self.warm_failures += 1
                import logging

                logging.getLogger("vernemq_tpu.matcher").exception(
                    "background warm-up of %d-batch super-dispatch "
                    "(batch %d) failed; super-batches of this shape keep "
                    "serving via the host trie", n_batches, Bpad)
            finally:
                self._warming.discard(key)

        # vmqlint: allow(thread-lifecycle): bounded fire-and-forget —
        # same contract as the single-batch warm thread above
        threading.Thread(target=_w, name=f"tpu-warm-many-{n_batches}",
                         daemon=True).start()

    def _region_maxima(self, reg_start, reg_end, live) -> Tuple[int, int]:
        """Rows of the widest level-0 bucket region and of the widest
        g-bucket region — 0 where the table has no g-buckets or, by the
        pinned ``live`` counts, none of them holds a live row: a window
        over the g-zone is then no part of any program."""
        ng = self._ng
        amax = (int((reg_end[1 + ng:] - reg_start[1 + ng:]).max())
                if len(reg_start) > 1 + ng else 0)
        gmax = (int((reg_end[1:1 + ng] - reg_start[1:1 + ng]).max())
                if ng and live[1] else 0)
        return amax, gmax

    def _geometry(self, S, glob_pad, reg_start, reg_end, live, Bpad,
                  align=0):
        """Static kernel geometry for both probes at this batch size. A
        phase whose rows hold nothing live (``live``: region 0, the
        g-buckets) gets the static that leaves it out of the program:
        ``gc`` 0 for the dense phase, ``seg2`` 0 for probe B."""
        gb_end = self._gb_end
        amax, gmax = self._region_maxima(reg_start, reg_end, live)
        T, seg_max, gc = window_params(S, glob_pad, amax, Bpad,
                                       zone=S - gb_end, align=align)
        if not live[0]:
            gc = 0
        if gmax:
            T2, seg2, _ = window_params(S, glob_pad, gmax, Bpad,
                                        zone=gb_end - glob_pad, align=align)
        else:
            T2, seg2 = 1, 0
        return T, seg_max, gc, T2, seg2, gb_end

    @staticmethod
    def _phases(statics: dict) -> str:
        """Which phases a flat program of these statics holds: ``g`` the
        dense pass over region 0, ``a`` probe A, ``b`` probe B."""
        return (("g" if statics["gc"] else "") + "a"
                + ("b" if statics["seg2_max"] else ""))

    def _flat_prep(self, reg_start, reg_end, glob_pad, live, bits, S,
                   pw, pl, pd, pb, gb, n, align=0):
        """Host prep for :func:`K.match_extract_windowed_flat`: window
        geometry, selector tiles, per-pub tile coordinates, flat
        capacity. Returns ``(args, statics, left)`` — the kernel's
        trailing positional args + static kwargs (the leading six are the
        device table arrays), and the set of host-fallback pubs (window
        overflow). Registry state (reg_start/…, the ``live`` counts) is
        passed in, not read off self, so a caller can pin the snapshot its
        device arrays were built from: a program compiled without a phase
        must never meet arrays that hold a row of it. Shared by
        match_batch and match_many."""
        Bpad = pw.shape[0]
        T, seg_max, gc, T2, seg2, gb_end = self._geometry(
            S, glob_pad, reg_start, reg_end, live, Bpad, align=align)
        (t_sel, t_start, tile_of, pos_of,
         leftovers) = prepare_windows(pw, pl, pd, pb, n, reg_start,
                                      reg_end, S, T, seg_max,
                                      row_lo=gb_end, emit="sel",
                                      align=align)
        t_start = t_start + gb_end  # starts are row_lo-relative
        a_tile = np.full(Bpad, -1, dtype=np.int32)
        a_pos = np.zeros(Bpad, dtype=np.int32)
        a_tile[:n] = tile_of
        a_pos[:n] = pos_of
        b_tile = np.full(Bpad, -1, dtype=np.int32)
        b_pos = np.zeros(Bpad, dtype=np.int32)
        if seg2:
            (t2_sel, t2_start, tile2_of, pos2_of,
             left2) = prepare_windows(pw, pl, pd, gb, n, reg_start,
                                      reg_end, S, T2, seg2,
                                      row_lo=glob_pad, row_hi=gb_end,
                                      emit="sel", align=align)
            t2_start = t2_start + glob_pad
            b_tile[:n] = tile2_of
            b_pos[:n] = pos2_of
        else:
            t2_sel = np.zeros((1, t_sel.shape[1]), np.int32)
            t2_start = np.zeros(1, np.int32)
            left2 = []
        args = (pw, pl, pd, np.int32(n), t_sel, t_start, t2_sel, t2_start,
                a_tile, a_pos, b_tile, b_pos)
        statics = dict(id_bits=bits, k=self.max_fanout, glob_pad=glob_pad,
                       seg_max=seg_max, seg2_max=seg2, gc=gc,
                       C=Bpad * self.flat_avg)
        return args, statics, set(leftovers) | set(left2)

    def _match_windowed(self, dev_arrays, operands, meta, reg_start,
                        reg_end, glob_pad, live, bits, pw, pl, pd, pb, gb,
                        n, ph, require_warm: bool = False):
        """Run the windowed device path (the production kernel, flat
        variant): probe-A (level-0 bucket) window tiles plus, where the
        pinned ``live`` counts say their rows hold something, a dense
        pass over region 0 and probe-B (level-1 g-bucket) tiles, compacted
        device-side into one flat buffer. Returns (per-pub slot index
        views, need_host bool array) in original batch order; need_host
        marks pubs the device could not serve exactly (window-overflow
        leftovers, per-part clip at k, flat-capacity overflow) for the
        exact host fallback. ``ph``: the caller's fold phases (prep is
        open on entry, resolve on return)."""
        S = int(dev_arrays[0].shape[0])
        pallas = (self.use_pallas and S % 2048 == 0 and glob_pad % 2048 == 0
                  and self._gb_end % 2048 == 0)
        args, statics, left = self._flat_prep(
            reg_start, reg_end, glob_pad, live, bits, S, pw, pl, pd, pb, gb,
            n, align=2048 if pallas else 0)
        ph.phases = self._phases(statics)
        # the full compile signature of this dispatch: arg shapes +
        # static kwargs (+ S via statics / shapes). Window geometry
        # depends on table CONTENT (amax), so a delta can mint new
        # signatures — the warm gate must see exactly what jit sees.
        sig = (tuple(a.shape for a in args),
               tuple(sorted(statics.items())), pallas)
        if require_warm and sig not in self._warm_sigs:
            self.busy_sheds += 1
            raise MatcherBusy(cold=True)
        F_t, t1 = operands
        ph.enter(obs.span("stage_fold_launch_ms"))
        if pallas:
            faults.inject("device.dispatch")
            table_args = (F_t, t1, dev_arrays[1], dev_arrays[2],
                          dev_arrays[3], dev_arrays[4])
            from ..ops import pallas_match as P
            out = P.match_extract_windowed_flat_pallas(
                *table_args, *args, **statics,
                interpret=P.use_interpret())
            ph.enter(obs.span("stage_fold_wait_ms"))
            flat, pre, total, overflow = (np.asarray(a) for a in out)
            ph.enter(obs.span("stage_fold_resolve_ms"))
        else:
            # single-upload / single-pull transport (see pack_meta /
            # flat_pack_args): one int32 vector each way
            out = K.call_packed(F_t, t1, meta, args, statics)
            ph.enter(obs.span("stage_fold_wait_ms"))
            out = np.asarray(out)
            ph.enter(obs.span("stage_fold_resolve_ms"))
            flat, pre, total, overflow = K.unpack_flat_result(
                out, args[0].shape[0], statics["C"])
        idx_rows, need_host, over = self._flat_views(
            n, flat, pre, total, overflow, left)
        self._warm_sigs.add(sig)
        if len(over):
            # what the flat form's caps cut off, the device answers whole
            ph.enter(obs.span("stage_fold_wide_ms"))
            self._wide_pass(operands, meta, reg_start, reg_end, glob_pad,
                            live, bits, S, [(pw, pl, pd, pb, gb, over, total,
                                             idx_rows, need_host)],
                            require_warm)
            ph.enter(obs.span("stage_fold_resolve_ms"))
        return idx_rows, need_host

    @staticmethod
    def _flat_views(n, flat, pre, total, overflow, left):
        """One batch's flat result as ``(idx_rows, need_host, over)``:
        per-publish VIEWS into ``flat`` (no copies), the window leftovers
        marked for the host, and the positions the flat form flagged
        ``overflow`` — the wide pass's."""
        need_host = np.zeros(n, dtype=bool)
        for i in left:
            need_host[i] = True
        idx_rows = [flat[pre[i]:pre[i] + total[i]] for i in range(n)]
        return idx_rows, need_host, np.flatnonzero(overflow[:n] & ~need_host)

    def _wide_statics(self, S, glob_pad, reg_start, reg_end, live,
                      bits) -> dict:
        """Static geometry of the wide pass: a window as wide as the
        widest region of its kind (pow2, so growth inside it keeps the
        signature), never wider than the table; no second window
        (``wb`` 0) while no g-bucket holds a live row."""
        amax, gmax = self._region_maxima(reg_start, reg_end, live)
        return dict(id_bits=bits, glob_pad=glob_pad,
                    wa=min(_pow2ceil(max(amax, 2048)), S),
                    wb=min(_pow2ceil(max(gmax, 2048)), S) if gmax else 0)

    @staticmethod
    def _wide_sig(U: int, L: int, S: int, statics: dict) -> tuple:
        return ("wide", U, L, S, tuple(sorted(statics.items())))

    def _wide_pass(self, operands, meta, reg_start, reg_end, glob_pad,
                   live, bits, S, parts, require_warm: bool) -> None:
        """Answer on the device the publishes the flat form flagged
        ``overflow``. ``parts``: one ``(pw, pl, pd, pb, gb, over, total,
        idx_rows, need_host)`` a batch of the dispatch — ``over`` the
        flagged publishes' positions; ``idx_rows[i]`` is replaced by the
        publish's whole list of slot ids. Identical topics, of one batch
        or of several, are matched once. A wide answer shorter than what
        the flat form itself counted for the publish (``total``: its
        parts' counts clamped at k) is no answer: counted, host-matched."""
        pub = np.concatenate([
            np.concatenate([pw[o], pl[o, None], pd[o, None],
                            pb[o, None], gb[o, None]], axis=1)
            for pw, pl, pd, pb, gb, o, *_ in parts])
        uniq, inv = np.unique(pub, axis=0, return_inverse=True)
        inv = inv.ravel()
        L = uniq.shape[1] - 4
        statics = self._wide_statics(S, glob_pad, reg_start, reg_end, live,
                                     bits)
        wa, wb = statics["wa"], statics["wb"]
        F_t, t1 = operands
        ids: List[np.ndarray] = []
        calls = 0
        for c0 in range(0, len(uniq), WIDE_RUNGS[-1]):
            chunk = uniq[c0:c0 + WIDE_RUNGS[-1]]
            n = len(chunk)
            U = next(r for r in WIDE_RUNGS if r >= n)
            sig = self._wide_sig(U, L, S, statics)
            if require_warm and sig not in self._warm_sigs:
                self.busy_sheds += 1
                raise MatcherBusy(cold=True)
            pw = np.full((U, L), np.int32(K.PAD_ID), np.int32)
            pl = np.zeros(U, np.int32)
            pd = np.zeros(U, np.int32)
            pw[:n], pl[:n], pd[:n] = chunk[:, :L], chunk[:, L], chunk[:, L + 1]
            wins = []
            for col, w in ((L + 2, wa), (L + 3, wb)):
                # pad publishes keep an empty region: lo == hi == 0
                win = np.zeros((U, 3), np.int64)
                if w:
                    win[:n, 1] = reg_start[chunk[:, col]]
                    win[:n, 2] = reg_end[chunk[:, col]]
                    win[:n, 0] = np.minimum(win[:n, 1], S - w)
                wins.append(win)
            out = np.asarray(K.call_wide(F_t, t1, meta, pw, pl, pd,
                                         wins[0], wins[1], statics))
            self._warm_sigs.add(sig)
            calls += 1
            ids.extend(K.unpack_wide_bits(out[u], glob_pad, wa,
                                          int(wins[0][u, 0]),
                                          int(wins[1][u, 0]))
                       for u in range(n))
        at = pubs = nrows = short = 0
        for *_, over, total, idx_rows, need_host in parts:
            for i in over:
                rows = ids[inv[at]]
                at += 1
                if len(rows) < total[i]:
                    short += 1
                    need_host[i] = True
                    continue
                idx_rows[i] = rows
                pubs += 1
                nrows += len(rows)
        global wide_publishes, wide_dispatches, wide_topics, wide_rows, \
            wide_failures
        with _totals_lock:
            wide_dispatches += calls
            wide_topics += len(uniq)
            wide_publishes += pubs
            wide_rows += nrows
            wide_failures += short

    def _warm_wide(self) -> int:
        """Compile the wide pass's rungs for the table as it stands, if
        one of its publishes can overflow the flat form at all: a table
        whose fan-out bound is under both caps (``max_fanout`` a part,
        ``flat_avg`` a publish on average) never dispatches them and
        compiles none. Returns the rungs run; raises what a warm-up
        ``match_batch`` raises."""
        with self.lock:
            try:
                self.sync()
            except RebuildInProgress:
                return 0
            if not (self._bucketed and self._operands is not None
                    and self._meta is not None
                    and self.table.fanout_bound
                    > min(self.max_fanout, self.flat_avg)):
                return 0
            operands, meta = self._operands, self._meta
            reg_start, reg_end = self._reg_start, self._reg_end
            S, L = int(self._dev_arrays[0].shape[0]), self.table.L
            statics = self._wide_statics(S, self._glob_pad, reg_start,
                                         reg_end, self._live, self._ops_bits)
            self._inflight += 1
        done = 0
        try:
            for U in WIDE_RUNGS:
                sig = self._wide_sig(U, L, S, statics)
                if self._closed or sig in self._warm_sigs:
                    continue
                z = np.zeros((U, 3), np.int64)
                np.asarray(K.call_wide(
                    *operands, meta,
                    np.full((U, L), np.int32(K.PAD_ID), np.int32),
                    np.zeros(U, np.int32), np.zeros(U, np.int32), z, z,
                    statics))
                self._warm_sigs.add(sig)
                done += 1
        except Exception as e:
            self._record_device_failure(e)  # raises DeviceDegraded
        finally:
            with self.lock:
                self._inflight -= 1
        return done

    def _host_match(self, topic: Sequence[str], snapshot=None) -> List[Row]:
        from ..protocol.topic import match_dollar_aware

        rows: List[Row] = []
        t = list(topic)
        with self.lock:
            entries = list(snapshot if snapshot is not None else self.table.entries)
            overflow_rows = self.table.overflow.match(t)
        for e in entries:
            if e is not None and match_dollar_aware(t, list(e[0])):
                rows.append(e)
        rows.extend(overflow_rows)
        return rows


class TpuRegView:
    """Reg-view adapter over per-mountpoint TpuMatchers. Non-default
    mountpoints share the same machinery (one table each). With a
    ``mesh`` (the ``tpu_mesh`` config knob) each mountpoint gets a
    :class:`parallel.sharded_match.ShardedTpuMatcher` instead — the
    serving path then matches across every device of the mesh with the
    same delta stream, rebuild shed and fallback discipline."""

    name = "tpu"

    def __init__(self, registry, max_levels: int = 16,
                 initial_capacity: int = 1024, max_fanout: int = 256,
                 flat_avg: int = 128, use_pallas: bool = False, mesh=None,
                 mesh_native: bool = True,
                 breaker_enabled: bool = True,
                 breaker_failure_threshold: int = 3,
                 breaker_backoff_initial: float = 0.2,
                 breaker_backoff_max: float = 10.0,
                 delta_warm_max: int = 128,
                 watchdog=None, rebuild_deadline_s: float = 120.0):
        self.registry = registry
        self.mesh = mesh
        self.mesh_native = mesh_native
        self.delta_warm_max = delta_warm_max
        self.watchdog = watchdog
        self.rebuild_deadline_s = rebuild_deadline_s
        self._matchers: Dict[str, TpuMatcher] = {}
        # mountpoints whose table a background load is building
        # (begin_load): the deltas that landed meanwhile, and the task
        self._loading: Dict[str, list] = {}
        self._load_tasks: Dict[str, "asyncio.Task"] = {}

        def _mk() -> TpuMatcher:
            if mesh is not None and mesh_native:
                # the mesh-native seat (parallel/mesh_match.py):
                # persistent NamedSharding state placed via partition
                # rules, slice-routed delta scatter — the default mesh
                # posture (tpu_mesh_native=false keeps the legacy
                # per-call shard_map seat below)
                from ..parallel.mesh_match import MeshTpuMatcher

                m: TpuMatcher = MeshTpuMatcher(
                    mesh, max_levels=max_levels,
                    initial_capacity=initial_capacity,
                    max_fanout=max_fanout, flat_avg=flat_avg)
            elif mesh is not None:
                from ..parallel.sharded_match import ShardedTpuMatcher

                m = ShardedTpuMatcher(
                    mesh, max_levels=max_levels,
                    initial_capacity=initial_capacity,
                    max_fanout=max_fanout, flat_avg=flat_avg)
            else:
                m = TpuMatcher(max_levels, initial_capacity, max_fanout,
                               flat_avg=flat_avg, use_pallas=use_pallas)
            # production seat: growth rebuilds run in the background
            # while the registry's trie serves (fold / _flush_async
            # catch RebuildInProgress)
            m.async_rebuild = True
            # device-path breaker per the tpu_breaker_* knobs (the
            # matcher ships a default breaker; this applies config)
            m.breaker = (CircuitBreaker(
                failure_threshold=breaker_failure_threshold,
                backoff_initial=breaker_backoff_initial,
                backoff_max=breaker_backoff_max,
                name="match")
                if breaker_enabled else None)
            # stall watchdog: background rebuilds register a monitored
            # op and are abandoned (breaker fed, late install discarded)
            # past the deadline instead of wedging the device path
            # silently behind RebuildInProgress forever
            m.watchdog = self.watchdog
            m.rebuild_deadline_s = self.rebuild_deadline_s
            return m

        self._mk = _mk

    def matcher(self, mountpoint: str = "") -> TpuMatcher:
        """Get/create the mountpoint's matcher, warm-loading it INLINE
        from the registry: the synchronous way in, for callers with no
        running loop to keep responsive (tools, unit tests). A
        serving broker never comes through the inline load — its paths
        ask :meth:`begin_load`, which builds the table off the loop
        thread. Raises RebuildInProgress while such a load is under way
        (the host trie serves meanwhile)."""
        m = self._matchers.get(mountpoint)
        if m is not None:
            return m
        if mountpoint in self._loading:
            raise RebuildInProgress("device table loading")
        m = self._mk()
        with m.lock:
            # warm-load from the registry's current state (the trie warm
            # load at boot, vmq_reg_trie.erl:144-151); publish only after
            # loading so on_delta can't interleave with the load
            for fw, key, opts in self.registry.fold_subscriptions(mountpoint):
                m.table.add(list(fw), key, opts)
        self._publish(mountpoint, m)
        return m

    def _publish(self, mountpoint: str, m: TpuMatcher) -> None:
        """Make a loaded matcher the mountpoint's, and pre-compile the
        batch-shape ladder AND the delta-scatter shape ladder in the
        background so neither live flushes nor the first post-subscribe
        delta sync block on a first compile (match_batch locks per call,
        so warmup interleaves with real batches; the delta ladder chases
        the sub_to_matchable_ms_max tail)."""
        self._matchers[mountpoint] = m

        def _warm_all() -> None:
            m.warm_ladder()
            try:
                m.warm_delta_ladder(self.delta_warm_max)
            except Exception:
                import logging

                logging.getLogger("vernemq_tpu.matcher").exception(
                    "delta-scatter shape pre-warm failed; first "
                    "deltas of each size will pay their compile")

        try:
            loop = asyncio.get_running_loop()
            loop.run_in_executor(None, _warm_all)
        except RuntimeError:
            pass  # no loop (sync/unit-test use): compile on demand

    #: rows snapshotted from the trie per loop-side step of a background
    #: load, and inserted per executor hop: the loop thread is held for
    #: the trie walk of one chunk (ms), never for the table build
    _LOAD_CHUNK = 4096

    def begin_load(self, mountpoint: str = "") -> bool:
        """True when the mountpoint's matcher is resident. Otherwise
        start (once) its warm-load OFF the loop thread and return False:
        the caller serves from the host trie until it lands. At a
        million subscriptions the table build is tens of seconds of
        Python — run inline on the loop it is one stall of that length
        for every session. Call on the event-loop thread."""
        if mountpoint in self._matchers:
            return True
        if mountpoint not in self._loading:
            self._loading[mountpoint] = []
            self._load_tasks[mountpoint] = (
                asyncio.get_running_loop().create_task(
                    self._load_async(mountpoint)))
        return False

    async def _load_async(self, mountpoint: str) -> None:
        """The background warm-load: the trie is walked on the loop in
        chunks (its mutation is loop-side, and ``Trie.entries`` copies
        per node, so yielding between chunks is safe); each chunk's rows
        go into the table in an executor thread. Subscribes and
        unsubscribes that land meanwhile are buffered by ``on_delta`` and
        replayed in order after the walk — ``table.add`` is an upsert and
        removing an absent row is a no-op, so the table ends equal to the
        registry whatever the walk saw of them."""
        import itertools
        import logging

        loop = asyncio.get_running_loop()
        buffered = self._loading[mountpoint]
        try:
            m = self._mk()

            def _apply(rows, deltas) -> None:
                with m.lock:
                    for fw, key, opts in rows:
                        m.table.add(list(fw), key, opts)
                    for op, fw, key, opts in deltas:
                        if op == "add":
                            m.table.add(list(fw), key, opts)
                        else:
                            m.table.remove(list(fw), key)

            it = iter(self.registry.fold_subscriptions(mountpoint))
            while True:
                chunk = list(itertools.islice(it, self._LOAD_CHUNK))
                if not chunk:
                    break
                await loop.run_in_executor(None, _apply, chunk, ())
            while len(buffered) > 64:
                deltas = buffered[:]
                del buffered[:len(deltas)]
                await loop.run_in_executor(None, _apply, (), deltas)
            # the tail and the hand-over run in ONE loop step: no delta
            # can fall between the replay and on_delta finding the matcher
            _apply((), buffered)
            self._publish(mountpoint, m)
        except Exception:
            logging.getLogger("vernemq_tpu.matcher").exception(
                "device table warm-load failed for mountpoint %r; the "
                "next flush starts it again", mountpoint)
        finally:
            self._loading.pop(mountpoint, None)
            self._load_tasks.pop(mountpoint, None)

    # delta feed from the registry
    def on_delta(self, op: str, mountpoint: str, filter_words, key, opts) -> None:
        m = self._matchers.get(mountpoint)
        if m is None:
            buffered = self._loading.get(mountpoint)
            if buffered is not None:
                buffered.append((op, filter_words, key, opts))
            return  # else: lazily warm-loaded on first use
        with m.lock:
            if op == "add":
                m.table.add(list(filter_words), key, opts)
            else:
                m.table.remove(list(filter_words), key)

    def fold(self, mountpoint: str, topic: Sequence[str]) -> List[Row]:
        """Synchronous single-topic fold — drop-in replacement for the trie
        view (a batch of one; the BatchCollector path amortises). During
        a background table rebuild or a breaker-open degraded window the
        host trie answers instead — as it does on a serving broker
        whose table is not resident yet (the load starts here, off the
        loop thread)."""
        if mountpoint not in self._matchers:
            try:
                asyncio.get_running_loop()
            except RuntimeError:
                pass  # no loop to keep responsive: matcher() loads inline
            else:
                if not self.begin_load(mountpoint):
                    return self.registry.trie(mountpoint).match(list(topic))
        try:
            return self.matcher(mountpoint).match_batch([tuple(topic)])[0]
        except (RebuildInProgress, DeviceDegraded):
            return self.registry.trie(mountpoint).match(list(topic))

    def fold_batch(self, mountpoint: str, topics: Sequence[Sequence[str]],
                   lock_timeout: Optional[float] = None):
        return self.matcher(mountpoint).match_batch(
            topics, lock_timeout=lock_timeout,
            require_warm=lock_timeout is not None)

    def fold_many(self, mountpoint: str,
                  batches: Sequence[Sequence[Sequence[str]]],
                  lock_timeout: Optional[float] = None):
        """K-window super-batch fold: all of ``batches`` ride ONE device
        dispatch (TpuMatcher.match_many). Returns one result list per
        batch, in order."""
        return self.matcher(mountpoint).match_many(
            batches, lock_timeout=lock_timeout,
            require_warm=lock_timeout is not None)

    def supports_many(self, mountpoint: str = "") -> bool:
        """Whether this mountpoint's matcher can amortize a K-window
        super-batch into one dispatch RIGHT NOW (the collector's gate).
        False while the matcher is uncreated — the first flush warms it
        through the normal path."""
        m = self._matchers.get(mountpoint)
        return bool(m is not None
                    and getattr(m, "supports_match_many", False))

    def breaker_status(self) -> Dict[str, Any]:
        """Per-mountpoint device-breaker status (admin/metrics surface);
        mountpoints whose breaker is disabled report None."""
        return {mp or "(default)": (m.breaker.status()
                                    if m.breaker is not None else None)
                for mp, m in self._matchers.items()}

    def mesh_status(self) -> Optional[Dict[str, Any]]:
        """Aggregated mesh-native status across mountpoints (None when
        this view is not mesh-native): summed routing counters + the
        default mountpoint's slice layout — what `vmq-admin mesh show`
        and the mesh_* gauges read."""
        if self.mesh is None or not self.mesh_native:
            return None
        agg: Dict[str, Any] = {
            "slices": int(self.mesh.shape["sub"]),
            "slice_rows": 0, "rows_per_slice": [], "addressable": [],
            "route_flushes": 0, "route_dirty_slices": 0,
            "route_gzone_flushes": 0, "route_rows": 0,
            "full_scatters": 0, "mesh_dispatches": 0,
            "slice_adoptions": 0, "last_route": {},
        }
        for mp, m in self._matchers.items():
            st = getattr(m, "mesh_status", None)
            if st is None:
                continue
            st = st()
            for k in ("route_flushes", "route_dirty_slices",
                      "route_gzone_flushes", "route_rows",
                      "full_scatters", "mesh_dispatches",
                      "slice_adoptions"):
                agg[k] += st.get(k, 0)
            if mp == "" or not agg["rows_per_slice"]:
                agg["slice_rows"] = st.get("slice_rows", 0)
                agg["rows_per_slice"] = st.get("rows_per_slice", [])
                agg["addressable"] = st.get("addressable", [])
                agg["last_route"] = st.get("last_route", {})
        return agg

    def adopt_slices(self, slice_ids, epoch) -> int:
        """Slice-map adoption fan-in: replay newly-owned slices' rows on
        every mountpoint's mesh matcher (exactly once per adoption
        token — the seat guards). Returns total rows marked."""
        total = 0
        for m in self._matchers.values():
            fn = getattr(m, "adopt_slices", None)
            if fn is not None:
                total += fn(slice_ids, epoch)
        return total

    def close(self) -> None:
        """Wind down background warm threads of every mountpoint's
        matcher and any table load still running (broker shutdown)."""
        for task in list(self._load_tasks.values()):
            task.cancel()
        for m in self._matchers.values():
            m.close()


class _Release:
    """One submission in the collector's release queue: who gets the
    rows — a future (an awaiting caller) or a continuation
    ``cont(rows, exc)`` that ``_release`` calls inline — and, once
    settled, the result held until every earlier submission left."""

    __slots__ = ("fut", "cont", "trace", "ready", "res", "exc", "t")

    def __init__(self, fut, cont, trace):
        self.fut = fut
        self.cont = cont
        self.trace = trace  # the collector stamps its settling
        self.ready = False
        self.res = None
        self.exc = None
        self.t = 0.0  # settled at


class BatchCollector:
    """Coalesce concurrent publishes into one device call.

    Publishes arriving within ``window_us`` (or until ``max_batch``) are
    matched together; each caller's future resolves to its own match rows.
    Equivalent host-side role to the NIF batching layer in the north-star
    design (BASELINE.json)."""

    #: device calls allowed in flight at once: two slots double-buffer
    #: the pipeline (batch N+1's host encode overlaps batch N's device
    #: compute — the executor thread encodes while the device runs)
    MAX_INFLIGHT = 2

    def __init__(self, view: TpuRegView, window_us: int = 200,
                 max_batch: int = 4096, host_threshold: int = 8,
                 lock_busy_shed_ms: int = 500, super_batch_k: int = 8,
                 latency_budget_ms: float = 50.0,
                 watchdog=None, dispatch_deadline_ms: float = 0.0,
                 item_expiry_ms: float = 0.0, filter_engine=None,
                 after_release=None):
        self.view = view
        # called at the end of every release chunk: the broker flushes
        # the chunk's socket writes there (broker/egress.py)
        self._after_release = after_release
        # payload-filter engine (vernemq_tpu/filters/): when set, every
        # flush's matched fanout runs the predicate phase — device
        # dispatch chained behind topic match, host evaluator on every
        # shed path — before the futures settle. None (the default, and
        # filters-disabled) touches nothing on any path.
        self.filter_engine = filter_engine
        # stall watchdog (robustness/watchdog.py): with a deadline set,
        # device flushes run as SACRIFICIAL dispatches — the await is
        # released at the deadline (StallAbandoned → host trie serves,
        # the matcher breaker is fed) and the wedged executor thread is
        # spawned around; its late result is discarded, never delivered.
        # item_expiry_ms (derived from overload_dispatch_budget_ms)
        # bounds the QUEUED tail the same way: a pending publish older
        # than its expiry is served by the exact host walk even while
        # every pipeline slot is wedged. 0 disables either bound.
        self.watchdog = watchdog
        self.dispatch_deadline = dispatch_deadline_ms / 1e3
        self.item_expiry = item_expiry_ms / 1e3
        self.stalled_host_pubs = 0  # pubs trie-served after an abandon
        self.expired_host_pubs = 0  # pubs trie-served past item expiry
        self._expiry_handle: Optional[asyncio.TimerHandle] = None
        self.window = window_us / 1e6
        self.max_batch = max_batch
        # under load (more than one full window already queued) up to
        # this many max_batch windows coalesce into ONE device dispatch
        # (TpuMatcher.match_many — K round trips become one; the
        # continuous-batching posture of Orca/vLLM applied to the match
        # pipeline). 1 disables super-batching.
        self.super_batch_k = max(1, super_batch_k)
        self.super_batches = 0      # fused multi-window dispatches
        self.super_batch_pubs = 0   # pubs that rode a super-batch
        # bounded head-of-line blocking: a device flush waits at most
        # this long for the matcher lock (a first-compile of a new batch
        # shape can hold it for tens of seconds) before the whole flush
        # serves from the host trie. 0 disables (unbounded wait).
        self.lock_busy_shed_ms = lock_busy_shed_ms
        # hybrid dispatch (SURVEY.md §7.2): a flush this small is served
        # by the host trie ON the event loop — sub-ms exact match, no
        # device round trip, no executor hop. The trie is maintained from
        # the same subscriber-db events as the device table, and on-loop
        # access is race-free (all trie mutation happens loop-side).
        # Batches above the threshold amortise the device call.
        self.host_threshold = host_threshold
        self.host_hybrid_pubs = 0
        self.saturated_merges = 0  # flushes deferred into a later batch
        self.overload_host_pubs = 0  # shed to the host trie at overload
        # dispatch-latency EWMA (ms, flush start -> results settled) and
        # the budget it is judged against: the overload governor's
        # device-path pressure signal (robustness/overload.py)
        self.latency_budget_ms = latency_budget_ms
        self.dispatch_ewma_ms = 0.0
        # slowest recent flush (ms): a peak that decays a fifth per
        # flush — what the queued-item expiry is derived from
        self.dispatch_peak_ms = 0.0
        self.rebuild_host_pubs = 0  # served by the trie during a rebuild
        self.busy_host_pubs = 0  # served by the trie past the lock bound
        self.degraded_host_pubs = 0  # trie-served while the breaker is open
        self._pending: List[tuple] = []  # (mp, topic, _Release, exp, t_sub, trace, feat)
        self._flush_handle: Optional[asyncio.TimerHandle] = None
        self._inflight = 0
        # submission-order release queue: a caller (a future's awaiter
        # or a continuation) sees its result only after every EARLIER
        # submission settled, so routing runs in submission order — the
        # per-publisher ordering contract (reg.py publish_nowait,
        # publish_wire) holds even with two device batches racing in
        # the pipeline or results coming from the host shed path
        import collections as _collections

        self._order: "_collections.deque" = _collections.deque()
        self._releasing = False  # a _release callback is scheduled
        self.release_rows = 0  # matched rows (at least 1 a submission) released

    def pressure(self) -> float:
        """Device-path pressure in [0, 1] for the overload governor:
        queue depth against the overload shed bound (K super-batch
        windows — the point submit() starts shedding to the trie) plus
        the dispatch-latency EWMA, fused by the shared
        overload.collector_pressure rule (latency caps below the L1
        gate: slow-but-covered dispatch is reduced headroom, not
        overload — only depth may escalate)."""
        from ..robustness.overload import collector_pressure

        # the EWMA only folds on a flush: with nothing queued or in
        # flight it is the memory of the last burst, not pressure — left
        # in, one slow flush holds the governor at L1 for as long as the
        # broker then stays idle (and L1 throttles publishers down to
        # flushes too small to ever reach the device and refresh it)
        idle = not self._pending and not self._inflight
        return collector_pressure(
            len(self._pending),
            self.max_batch * max(1, self.super_batch_k),
            0.0 if idle else self.dispatch_ewma_ms,
            self.latency_budget_ms)

    def _expiry_s(self) -> float:
        """Seconds a publish may stay queued before the host trie
        answers it. ``item_expiry`` (N dispatch budgets) is the floor;
        where dispatches measurably take longer than the budget it is N
        MEASURED dispatches (the slowest recent flush): a publish
        waiting for a slot behind two healthy in-flight dispatches is on
        time by the device's own clock, and expiring it sheds exactly
        the backlog a K-window super-batch is made of. The slowest
        recent flush, not the EWMA: a queued publish waits for the
        BIGGEST dispatch in flight, and the mean of mostly small
        flushes says nothing of that one. Capped at the dispatch
        deadline — a wedged dispatch is abandoned there, so the queued
        tail stays bounded by the same clock. 0: no expiry."""
        floor = self.item_expiry
        if floor <= 0 or self.latency_budget_ms <= 0:
            return floor
        measured = (floor / self.latency_budget_ms) * self.dispatch_peak_ms
        if self.dispatch_deadline > 0:
            measured = min(measured, self.dispatch_deadline)
        return max(floor, measured)

    def _many_capable(self, mountpoint: str) -> bool:
        """Can this mountpoint's flushes amortize as super-batches RIGHT
        NOW? Gated on the matcher's actual fused-path availability (not
        just the fold_many seam existing): feeding K windows to a
        matcher that would serialize them deepens the overload queue
        and the head-of-line wait for zero amortization."""
        if self.super_batch_k <= 1 or not hasattr(self.view, "fold_many"):
            return False
        probe = getattr(self.view, "supports_many", None)
        if probe is None:
            return True  # simple stand-in views: seam presence is the gate
        try:
            return bool(probe(mountpoint))
        except Exception:
            return False

    #: submissions released per loop callback. Releasing one runs its
    #: caller's routing (a continuation inline, an awaiting task on its
    #: next step): the whole fanout of that publish, on the loop. A
    #: flush settles thousands at once — released in one callback, a
    #: full window at a fanout of ~60 is seconds of routing in which no
    #: socket is read and no timer fires (measured on the v5e at 1M
    #: subscriptions: 1-5 s, which the overload governor answers by
    #: disconnecting the publishers).
    _RELEASE_CHUNK = 64
    #: ...and matched rows released per loop callback: a submission
    #: costs its rows (at least one), since routing it enters that many
    #: sessions' windows and queues that many frames (~5.4 µs a row on
    #: the v5e's host inside a 1,000-recipient fan-out; the socket writes
    #: are the outbox's, bounded there: ``egress.FLUSH_MAX``). A callback
    #: releases submissions until either budget is spent and always one,
    #: so a publish wider than the budget leaves whole (its recipients
    #: share one Msg and one header batch). 64 entries of ``tpu_flat_avg``
    #: 128 rows: ~45 ms of routing, so that a timer, which waits two
    #: callbacks, runs ~0.12 s late at most — half of
    #: ``sysmon_lag_threshold``. What the budget trades (PERF.md §6, PR 32,
    #: 1,000 subscribers a topic): a smaller one splits a tick's routing
    #: into more callbacks, each ending in a flush, so a socket gets more
    #: writes of fewer frames (2,048 rows: thirteen callbacks and ~7
    #: writes a socket for 25 publishes); a larger one holds the loop
    #: longer than the lag alarm allows (16,384 rows: the loop 0.18–0.36 s
    #: late, the alarm rang in one run of seven).
    _RELEASE_ROWS = 64 * 128

    def _settle(self, ent, res=None, exc=None) -> None:
        """Record a submission's result. Settled entries are released to
        their callers in submission order, ``_RELEASE_CHUNK`` per loop
        callback (``_release``), so the delivery fan-out of a big flush
        yields to the loop's IO and timers between chunks."""
        ent.ready = True
        ent.res = res
        ent.exc = exc
        if obs.enabled():
            ent.t = time.monotonic()
            if ent.trace is not None:
                ent.trace.stamp("settle")
        if (not self._releasing and self._order
                and self._order[0].ready):
            self._releasing = True
            asyncio.get_event_loop().call_soon(self._release)

    def _release(self) -> None:
        with obs.span("stage_release_turn_ms"):
            self._release_chunk()

    def _release_chunk(self) -> None:
        order = self._order
        budget = self._RELEASE_CHUNK
        rows_left = self._RELEASE_ROWS
        while order and order[0].ready and budget:
            ent = order[0]
            cost = len(ent.res) if ent.res else 1
            if cost > rows_left and budget < self._RELEASE_CHUNK:
                break  # the next callback's
            order.popleft()
            fut = ent.fut
            if fut is not None and fut.done():  # cancelled by the caller
                continue
            rows_left -= cost
            self.release_rows += cost
            if budget == self._RELEASE_CHUNK and ent.t:
                # how long this chunk's head stood settled while the
                # chunks before it were released and routed: a wait, so
                # a histogram family and no span
                obs.observe("stage_release_wait_ms",
                            (time.monotonic() - ent.t) * 1e3)
            budget -= 1
            if fut is None:
                # a continuation runs HERE, inline: the publish's route
                # and its acknowledgement, no future and no task step.
                # One that raises must not take the queue behind it down
                try:
                    ent.cont(ent.res, ent.exc)
                except Exception:
                    import logging

                    logging.getLogger(__name__).exception(
                        "collector continuation failed")
            elif ent.exc is not None:
                fut.set_exception(ent.exc)
            else:
                fut.set_result(ent.res)
        if order and order[0].ready:
            asyncio.get_event_loop().call_soon(self._release)
        else:
            self._releasing = False
        if self._after_release is not None:
            self._after_release()

    def _settle_via_trie(self, mp: str, topic, ent,
                         fallback_exc: Optional[BaseException] = None,
                         feat=None) -> None:
        """Serve one publish from the host trie (the correctness oracle)
        and settle its entry; without a registry the original cause —
        not a misleading AttributeError — reaches the caller. The
        payload-predicate phase applies here too (exact host evaluator):
        a shed/degraded publish must deliver the same filtered fanout
        as the device path."""
        reg = getattr(self.view, "registry", None)
        if reg is None:
            self._settle(ent, exc=fallback_exc
                         or RuntimeError("no registry for trie fallback"))
            return
        try:
            rows = reg.trie(mp).match(list(topic))
            eng = self.filter_engine
            if eng is not None and eng.wants(mp):
                rows = eng.filter_single(mp, topic, feat, list(rows))
            self._settle(ent, res=rows)
        except Exception as e:
            self._settle(ent, exc=e)

    def submit(self, mountpoint: str, topic: Sequence[str],
               trace=None, feat=None,
               cont=None) -> Optional[asyncio.Future]:
        """Queue one publish for the next flush. The rows come back
        through the future returned or, with ``cont``, through
        ``cont(rows, exc)`` called inline by ``_release`` (no future is
        made, None is returned): both forms leave in ONE submission
        order, whichever path — device, trie shed, expiry — served
        them.

        ``trace`` — an optional flight-recorder PublishTrace
        (observability/recorder.py): the sampled-at-admission context
        rides the pending item into the flush, where the collector
        stamps dequeue/match/settle and, in worker mode, attaches the
        match-service fold meta (the cross-process ring stamps).
        ``feat`` — the publish's payload feature row (filters/engine
        encode) riding the same staging into the predicate phase; None
        for unfiltered mountpoints (zero-cost)."""
        loop = asyncio.get_event_loop()
        fut = loop.create_future() if cont is None else None
        ent = _Release(fut, cont, trace)
        self._order.append(ent)
        if (self._inflight >= self.MAX_INFLIGHT
                and len(self._pending) >= self.max_batch
                and len(self._pending) >= self.max_batch * (
                    self.super_batch_k
                    if self._many_capable(mountpoint) else 1)):
            # overload: both pipeline slots busy AND a full super-batch
            # already waiting — arrival rate exceeds device service
            # rate even with K windows per dispatch. Match on the exact
            # host trie NOW instead of queueing unboundedly (the trie
            # is the correctness oracle, so results are identical); the
            # result still RELEASES in submission order via _settle, so
            # shedding never reorders deliveries. The shed bound is
            # super_batch_k windows (not one): queued pubs below it
            # coalesce into one K-window dispatch when a slot frees —
            # shedding earlier would starve the amortization path the
            # device needs to catch back up.
            if getattr(self.view, "registry", None) is not None:
                self.overload_host_pubs += 1
                self._settle_via_trie(mountpoint, topic, ent, feat=feat)
                return fut
        now_sub = time.monotonic()
        expiry = self._expiry_s()
        exp = now_sub + expiry if expiry > 0 else None
        if trace is not None:
            trace.stamp("submit")
        self._pending.append((mountpoint, tuple(topic), ent, exp,
                              now_sub, trace, feat))
        if exp is not None and self._expiry_handle is None:
            # expiry sweep: fires even when no flush can (both pipeline
            # slots wedged) — the queued-tail bound of the stall story
            self._expiry_handle = loop.call_later(expiry,
                                                  self._expire_sweep)
        if len(self._pending) >= self.max_batch:
            if self._flush_handle is not None:
                self._flush_handle.cancel()
                self._flush_handle = None
            self._flush()
        elif self._flush_handle is None:
            self._flush_handle = loop.call_later(self.window, self._flush)
        return fut

    def submit_batch(self, mountpoint: str,
                     topics: Sequence[Sequence[str]]) -> "asyncio.Future":
        """Submit a whole pre-batched group of publishes and resolve to
        the list of per-topic row lists (in submission order).

        This is the cross-process seam of the multi-process front end
        (broker/match_service.py): each SO_REUSEPORT worker ships its
        coalesced batch over a shared-memory ring, and the service-side
        drainer submits it here — the submitters become PROCESSES
        instead of tasks, but they coalesce in exactly the same pending
        queue, so K worker batches super-batch into one match_many
        dispatch like K tasks always did."""
        futs = [self.submit(mountpoint, t) for t in topics]
        return asyncio.gather(*futs)

    #: expired items settled per sweep callback: the sweep runs ON the
    #: loop, and an unbounded backlog (both slots wedged at high rates)
    #: settled in one callback would stall every session's IO — the
    #: defect class the parse-loop yield fixed. The remainder re-arms
    #: at zero delay, so the backlog drains across loop iterations.
    _EXPIRE_CHUNK = 256

    def _expire_sweep(self) -> None:
        """Deadline propagation for QUEUED items: anything pending past
        its expiry is answered by the exact host trie NOW. With a wedge
        holding both pipeline slots, a publish still waits at most
        ``item_expiry`` before the oracle serves it — release order is
        preserved by _settle, so the bound composes with the dispatch
        deadline as deadline + expiry ε, never reorders."""
        self._expiry_handle = None
        if not self._pending:
            return
        now = time.monotonic()
        settled = 0
        keep = []
        for item in self._pending:
            mp, topic, ent, exp = item[:4]
            if (exp is not None and now >= exp
                    and settled < self._EXPIRE_CHUNK):
                self.expired_host_pubs += 1
                self._settle_via_trie(mp, topic, ent, feat=item[6])
                settled += 1
            else:
                keep.append(item)
        self._pending = keep
        if self._pending and self._pending[0][3] is not None:
            delay = (0.0 if now >= self._pending[0][3]  # chunk remainder
                     else max(0.005, self._pending[0][3] - now))
            self._expiry_handle = asyncio.get_event_loop().call_later(
                delay, self._expire_sweep)

    def _flush(self) -> None:
        self._flush_handle = None
        if not self._pending:
            return
        reg = getattr(self.view, "registry", None)
        if len(self._pending) <= self.host_threshold and reg is not None:
            pending, self._pending = self._pending, []
            self.host_hybrid_pubs += len(pending)
            for mp, topic, ent, _exp, _t_sub, _trace, feat in pending:
                self._settle_via_trie(mp, topic, ent, feat=feat)
            return
        if self._inflight >= self.MAX_INFLIGHT:
            # both slots busy: DON'T queue a third task — leave the
            # items pending so late arrivals coalesce into one bigger
            # batch (self-batching backpressure: queueing depth stays
            # bounded at 2 batches + one accumulating, so worst-case
            # service latency is ~2 batch times, not an unbounded
            # executor queue). _on_done flushes the moment a slot frees.
            self.saturated_merges += 1
            return
        take = self.max_batch
        if (len(self._pending) > self.max_batch
                and self._many_capable(self._pending[0][0])):
            # load signal: more than one full window is already queued —
            # ship up to super_batch_k windows as ONE device dispatch
            # instead of serializing one dispatch per window
            take = min(len(self._pending),
                       self.max_batch * self.super_batch_k)
        pending, self._pending = self._pending[:take], \
            self._pending[take:]
        self._inflight += 1
        task = asyncio.get_event_loop().create_task(
            self._flush_async(pending))
        task.add_done_callback(self._on_done)

    def _on_done(self, task) -> None:
        self._inflight -= 1
        if task.cancelled():
            return
        exc = task.exception()
        if exc is not None:  # futures already got the error; log path
            import logging

            logging.getLogger(__name__).warning(
                "batch flush task failed: %s", exc)
        if self._pending:
            # back-to-back dispatch keeps the device busy: the waiting
            # batch goes out now instead of waiting out another window
            if self._flush_handle is not None:
                self._flush_handle.cancel()
                self._flush_handle = None
            self._flush()

    async def _flush_async(self, pending) -> None:
        """Run the device call off-loop (executor thread): a jit compile for
        a new padded batch size takes seconds, and blocking the event loop
        would stall every session's IO (the socket loop is the analog of the
        reference's per-connection process — it must never wait on the
        matcher)."""
        loop = asyncio.get_event_loop()
        flush_t0 = time.perf_counter()
        # group by mountpoint (typically one); items that expired while
        # queued (saturated merges behind a slow/wedged device) go to
        # the exact host trie instead of riding — and lengthening — a
        # device dispatch they already waited too long for
        now = time.monotonic()
        by_mp: Dict[str, List[Tuple[Tuple[str, ...], _Release,
                                    Any]]] = {}
        traces_mp: Dict[str, list] = {}
        expired: List[Tuple[str, Tuple[str, ...], _Release,
                            Any]] = []
        oldest_sub = None
        for mp, topic, ent, exp, t_sub, trace, feat in pending:
            if exp is not None and now >= exp:
                expired.append((mp, topic, ent, feat))
            else:
                by_mp.setdefault(mp, []).append((topic, ent, feat))
                if oldest_sub is None or t_sub < oldest_sub:
                    oldest_sub = t_sub
                if trace is not None:
                    trace.stamp("dequeue")
                    traces_mp.setdefault(mp, []).append(trace)
        if oldest_sub is not None:
            # head-of-flush queue wait: the max wait any publish in this
            # flush spent pending (per-flush, not per-item — one observe
            # per dispatch keeps the seam cost flat at any batch size)
            obs.observe("stage_collector_wait_ms",
                        (now - oldest_sub) * 1e3)
        for i, (mp, t_, ent, feat) in enumerate(expired):
            self.expired_host_pubs += 1
            self._settle_via_trie(mp, t_, ent, feat=feat)
            if (i + 1) % 64 == 0:
                await asyncio.sleep(0)
        for mp, items in by_mp.items():
            topics = [t for t, _, _ in items]
            lock_to = (self.lock_busy_shed_ms / 1e3
                       if self.lock_busy_shed_ms else None)
            # flight-recorder envelope: when a sampled publish rides
            # this flush and the view can report fold meta (the
            # match-service client's cross-process ring stamps), hand
            # the fold a box to fill — the executor thread writes it,
            # the loop reads it after the await
            mtraces = traces_mp.get(mp)
            meta_box = ({} if mtraces
                        and getattr(self.view, "fold_meta_capable", False)
                        else None)
            view = self.view
            if meta_box is not None:
                fold_many_fn = (lambda m, c, lt, _mb=meta_box:
                                view.fold_many(m, c, lt, meta_out=_mb))
                fold_batch_fn = (lambda m, t, lt, _mb=meta_box:
                                 view.fold_batch(m, t, lt, meta_out=_mb))
            else:
                fold_many_fn = getattr(view, "fold_many", None)
                fold_batch_fn = view.fold_batch
            # super-batch: more than one window's worth of pubs in this
            # flush rides ONE device dispatch (fold_many -> match_many)
            chunks = ([topics[i:i + self.max_batch]
                       for i in range(0, len(topics), self.max_batch)]
                      if len(topics) > self.max_batch
                      and self._many_capable(mp) else None)
            wd = self.watchdog
            sacrificial = wd is not None and self.dispatch_deadline > 0
            try:
                begin_load = getattr(view, "begin_load", None)
                if begin_load is not None and not begin_load(mp):
                    # the table is still being built off the loop thread
                    # (a boot with persisted subscriptions): the trie
                    # serves, counted with the rebuild sheds
                    raise RebuildInProgress("device table loading")
                if chunks:
                    if sacrificial:
                        nested = await wd.dispatch_async(
                            "device.dispatch",
                            lambda m=mp, c=chunks, lt=lock_to:
                                fold_many_fn(m, c, lt),
                            self.dispatch_deadline,
                            label=f"fold_many:{mp or '(default)'}")
                    else:
                        nested = await loop.run_in_executor(
                            None, fold_many_fn, mp, chunks, lock_to
                        )
                    results = [rows for batch in nested for rows in batch]
                    # counted only on success: a shed/failed super-batch
                    # served elsewhere must not read as a fused dispatch
                    self.super_batches += 1
                    self.super_batch_pubs += len(topics)
                elif sacrificial:
                    # sacrificial dispatch: the await is bounded by the
                    # deadline; a wedged device call is abandoned (host
                    # trie serves below), its thread spawned around, and
                    # its LATE result discarded — never delivered
                    results = await wd.dispatch_async(
                        "device.dispatch",
                        lambda m=mp, t=topics, lt=lock_to:
                            fold_batch_fn(m, t, lt),
                        self.dispatch_deadline,
                        label=f"fold_batch:{mp or '(default)'}")
                else:
                    results = await loop.run_in_executor(
                        None, fold_batch_fn, mp, topics, lock_to
                    )
            except StallAbandoned as sa:
                # deadline overrun: record the stall as a device failure
                # (breaker → host trie until a probe succeeds) and serve
                # THIS flush from the trie — bounded latency, identical
                # results, and the abandoned call's eventual output is
                # discarded by its token (bit-exact: no stale fanout)
                self.stalled_host_pubs += len(items)
                m = (self.view.matcher(mp)
                     if hasattr(self.view, "matcher") else None)
                if m is not None and hasattr(m, "record_stall"):
                    m.record_stall(sa)
                for i, (t_, ent, feat) in enumerate(items):
                    self._settle_via_trie(mp, t_, ent, fallback_exc=sa,
                                          feat=feat)
                    if (i + 1) % 64 == 0:
                        await asyncio.sleep(0)
                continue
            except (RebuildInProgress, MatcherBusy, DeviceDegraded) as rb:
                # the device can't take this batch promptly — table
                # re-uploading after growth, the matcher lock held past
                # the busy bound (first-compile of a new shape), or the
                # device circuit breaker open after repeated dispatch
                # failures — so serve it from the host trie (identical
                # results): the publish pipeline keeps flowing and
                # worst-case latency stays ~the bound, not the hold or
                # the outage. Trie reads must stay loop-side (mutation
                # is loop-side), so chunk the batch with yields — a
                # full 4096-pub flush of sub-ms matches must not stall
                # every session's IO for its whole duration.
                if isinstance(rb, DeviceDegraded):
                    # degraded mode: the breaker's half-open probe (a
                    # later real flush) brings the device back; no warm
                    # kick — recovery re-warms on the close edge
                    self.degraded_host_pubs += len(items)
                elif isinstance(rb, MatcherBusy):
                    self.busy_host_pubs += len(items)
                    if rb.cold:
                        # compile this batch shape off to the side so
                        # the next flush of this size serves on-device
                        # (lock-timeout sheds skip this: their shape is
                        # typically warm already — a redundant warm
                        # would steal device time while congested)
                        m = self.view.matcher(mp)
                        if (chunks and m is not None
                                and hasattr(m, "ensure_warm_many")):
                            m.ensure_warm_many(len(chunks),
                                               self.max_batch)
                        elif m is not None and hasattr(m, "ensure_warm"):
                            m.ensure_warm(len(items))
                else:
                    self.rebuild_host_pubs += len(items)
                for i, (t_, ent, feat) in enumerate(items):
                    self._settle_via_trie(mp, t_, ent, fallback_exc=rb,
                                          feat=feat)
                    if (i + 1) % 64 == 0:
                        await asyncio.sleep(0)
                continue
            except Exception as e:  # settle every entry with the error
                for _, ent, _feat in items:
                    self._settle(ent, exc=e)
                continue
            # payload-predicate phase (vernemq_tpu/filters/): the second
            # device dispatch chained behind topic match — skipped at
            # one dict probe when the mountpoint carries no predicates.
            # A wedged phase is abandoned at the same dispatch deadline
            # (host evaluator serves, breaker fed, late fold discarded);
            # any other engine failure fails open inside filter_batch.
            eng = self.filter_engine
            if eng is not None:
                if not eng.wants(mp):
                    eng.note_skip()
                else:
                    tf = [(t, feat) for t, _ent, feat in items]
                    try:
                        if sacrificial:
                            results = await wd.dispatch_async(
                                "device.predicate",
                                lambda m=mp, x=tf, r=results:
                                    eng.filter_batch(m, x, r),
                                self.dispatch_deadline,
                                label=f"predicate:{mp or '(default)'}")
                        else:
                            results = await loop.run_in_executor(
                                None, eng.filter_batch, mp, tf, results)
                    except StallAbandoned as sa:
                        eng.record_stall(sa)
                        results = await loop.run_in_executor(
                            None, eng.filter_batch_host, mp, tf, results)
            if mtraces:
                for tr in mtraces:
                    tr.stamp("match")
                    if meta_box:
                        tr.meta = meta_box
            for (_, ent, _feat), rows in zip(items, results):
                self._settle(ent, res=rows)
        # overload-signal EWMA: whole-flush service time (shed/degraded
        # paths included — a slow fallback is pressure too)
        from ..robustness.overload import fold_latency_ewma

        dt_ms = (time.perf_counter() - flush_t0) * 1e3
        self.dispatch_ewma_ms = fold_latency_ewma(self.dispatch_ewma_ms,
                                                  dt_ms)
        self.dispatch_peak_ms = max(dt_ms, 0.8 * self.dispatch_peak_ms)
