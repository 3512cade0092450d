"""Mesh-native matcher: one logical subscription table spanning a
(possibly multi-process) ``jax.sharding.Mesh``, with slice-routed delta
scatter.

This is the multi-host port of the windowed production matcher (ROADMAP
"Multi-host mesh: 10M+ resident subscriptions"): where
:class:`~vernemq_tpu.parallel.sharded_match.ShardedWindowedMatcher` placed
its 12-array state with hand-written ``device_put`` calls per sync and
shipped every delta as ONE full-table fused scatter, :class:`MeshMatcher`

- names the state arrays and places them through the shared partition
  rules (``parallel/mesh.py``: :func:`match_partition_rules` +
  :func:`make_shard_and_gather_fns` — the rule-matching pattern), so the
  same specs serve a single-process virtual CPU mesh, a TPU slice, and a
  ``jax.distributed.initialize`` runtime where each process contributes
  only its addressable shards;

- routes delta write-throughs to the OWNING SLICE: the dirty-slot set is
  grouped host-side by row→slice ownership (slice = contiguous 'sub'-axis
  row range), a packed sub-delta is built per dirty slice, and a scatter
  executable is launched only on the dirty slices' shards — the clean
  slices' device buffers are reused untouched and the global NamedSharding
  arrays are reassembled zero-copy from the per-shard buffers
  (``jax.make_array_from_single_device_arrays``). A flush touching one
  slice of 16 uploads 1/16th of the old fused scatter's operand and
  launches on 1/16th of the devices. Rows in the replicated dense g-zone
  dirty every replica by definition — counted separately
  (``route_gzone_flushes``), never against the routing hit rate;

- keeps the K-batch ``match_many`` amortization and the donated staging
  path: the seat (:class:`MeshTpuMatcher`) inherits the whole production
  discipline — matcher lock, snapshot resolution, async growth rebuilds
  with RebuildInProgress shedding, compile-signature warmth, breaker +
  watchdog + flight-recorder seams — from ShardedTpuMatcher, and the mesh
  dispatch is just another ``device.dispatch`` fault/breaker point
  (DeviceDegraded → exact host trie).

Multi-process reality check: XLA's CPU backend cannot run cross-process
computations (TPU backends can), so on a 2-process CPU mesh the global
pjit dispatch path raises and the breaker degrades matching exactly as
designed; :meth:`MeshMatcher.match_local_slices` is the per-process
device path — each process matches the publish batch against its OWN
addressable slices (coded-operand mismatch over the local shards) and the
cluster plane unions the partial fanouts. The 2-process e2e
(tests/test_mesh_distributed.py) drives both.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from ..observability import histogram as obs
from ..observability.profiler import record_dispatch
from ..ops.match_kernel import (PAD_ID, _epilogue, build_operands,
                                build_pub_operand, coded_mismatch)
from .mesh import MATCHER_STATE_NAMES, place_matcher_state
from .sharded_match import (ShardedTpuMatcher, ShardedWindowedMatcher,
                            _pow2ceil)


# ---------------------------------------------------------------------------
# per-shard scatter executables (cached by jit on shape/dtype)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_rows(data, idx, vals):
    """Row scatter into a 1-D shard [Sl] (metadata arrays)."""
    return data.at[idx].set(vals)


@jax.jit
def _scatter_rows_copy(data, idx, vals):
    return data.at[idx].set(vals)


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_cols(data, idx, vals):
    """Column scatter into a 2-D shard [K, Sl] (the coded operand —
    table rows are F_t columns)."""
    return data.at[:, idx].set(vals)


@jax.jit
def _scatter_cols_copy(data, idx, vals):
    return data.at[:, idx].set(vals)


def _shard_col_start(shard) -> int:
    """Row-axis start of a shard's index (the last axis for F_t, the
    only axis for metadata arrays)."""
    sl = shard.index[-1]
    return sl.start or 0


#: module-level jitted operand build (static id_bits) — a fresh
#: jax.jit wrapper per call would discard the dispatch cache on the
#: hot per-subscribe delta path
_build_operands_jit = jax.jit(build_operands, static_argnames=("id_bits",))


def _check_mesh_geometry(S: int, nslices: int) -> None:
    """The slice-geometry floor shared by every build path: rows must
    divide over the slices and each slice needs the windowed kernel's
    4096-row minimum."""
    if S % nslices != 0 or S // nslices < 4096:
        raise ValueError(
            f"table of {S} rows cannot shard over {nslices} mesh "
            f"slices (needs S % {nslices} == 0 and >= 4096 rows/slice)")


class MeshMatcher(ShardedWindowedMatcher):
    """The windowed production matcher as persistent NamedSharding/pjit
    state over a mesh that may span processes. Dispatch reuses the
    jitted windowed kernel (GSPMD partitions it under the mesh — the
    same executable on a virtual CPU mesh and a real slice); placement
    and delta routing are mesh-native (see module docstring)."""

    def __init__(self, table, mesh: Mesh, max_fanout: int = 128,
                 with_total: bool = False, flat_avg: int = 128,
                 merge: bool = False):
        super().__init__(table, mesh, max_fanout=max_fanout,
                         with_total=with_total, flat_avg=flat_avg,
                         merge=merge)
        # slice-routing accounting (`vmq-admin mesh show` / mesh_*
        # gauges)
        self.route_flushes = 0          # slice-routed delta flushes
        self.route_dirty_slices = 0     # dirty slices scattered, cumulative
        self.route_gzone_flushes = 0    # flushes that touched the g-zone
        self.route_rows = 0             # delta rows shipped, cumulative
        self.full_scatters = 0          # full-table placements (builds)
        self.mesh_dispatches = 0        # pulled match dispatches
        self.last_route: Dict[str, Any] = {}

    @property
    def nslices(self) -> int:
        """Slices = rows of the mesh's 'sub' axis (one name with the
        inherited ``nsub`` by construction)."""
        return self.nsub

    # ------------------------------------------------------------ placement

    def sync(self) -> None:
        """Full placement through the partition rules on (re)build;
        slice-routed delta otherwise. Mirrors the parent's sync contract
        (callers needing consistency hold their own lock)."""
        t = self.table
        self._reg_start = t.reg_start.copy()
        self._reg_end = (t.reg_start + t.reg_cap).copy()
        if self._dev is not None and not t.resized and not t.dirty:
            return
        if self._dev is not None and not t.resized:
            self._sync_delta()
            return
        assert t.bucketed and t.id_bits, \
            "mesh-native matching needs a bucketed table"
        S = t.cap
        _check_mesh_geometry(S, self.nslices)
        F_t, t1 = _build_operands_jit(t.words, t.eff_len,
                                      id_bits=t.id_bits)
        F_t = np.asarray(F_t)
        t1 = np.asarray(t1)
        glob = t.gb_end
        self._dev = place_matcher_state(
            self.mesh, F_t, t1, t.eff_len, t.has_hash, t.first_wild,
            t.active, glob)
        self.full_scatters += 1
        self._glob = glob
        self._S = S
        self._bits = t.id_bits
        t.resized = False
        t.dirty.clear()

    # --------------------------------------------------- slice-routed delta

    def slice_of_rows(self, rows: np.ndarray) -> np.ndarray:
        """Owning slice id per global table row (row-range ownership:
        slice s owns [s*Sl, (s+1)*Sl))."""
        Sl = self._S // self.nslices
        return np.minimum(rows // Sl, self.nslices - 1)

    def slice_ranges(self) -> List[Tuple[int, int]]:
        Sl = self._S // self.nslices
        return [(s * Sl, (s + 1) * Sl) for s in range(self.nslices)]

    def addressable_slices(self) -> Set[int]:
        """Slices whose shards this process holds (all of them on a
        single-process mesh; the owned subset under
        ``jax.distributed``)."""
        if self._dev is None:
            return set()
        Sl = self._S // self.nslices
        return {_shard_col_start(sh) // Sl
                for sh in self._dev[0].addressable_shards}

    def _sync_delta(self, donate: bool = True) -> None:
        """The slice-routed flush: per-slice sub-deltas scattered ONLY
        onto dirty slices' shards, clean slices' buffers reused, global
        arrays reassembled zero-copy. A flush whose dirty rows all fall
        outside the g-zone leaves every replica mirror untouched too —
        there is no full-table scatter path here at all (the routing
        guarantee ``tests/test_mesh_match.py`` asserts)."""
        t = self.table
        t0 = time.monotonic()
        slots = np.fromiter(t.dirty, dtype=np.int32)
        t.dirty.clear()
        if len(slots) == 0:
            return
        Sl = self._S // self.nslices
        owners = self.slice_of_rows(slots)
        dirty_slices = sorted(int(s) for s in set(owners.tolist()))
        # host-side operand build for JUST the dirty rows (the fused
        # scatter built these on device from a packed upload; per-slice
        # the row counts are small and the host build avoids shipping
        # the pack/unpack program to every slice)
        F_cols, t1_vals = _build_operands_jit(
            t.words[slots], t.eff_len[slots], id_bits=self._bits)
        F_cols = np.asarray(F_cols)          # [K, D]
        t1_vals = np.asarray(t1_vals)        # [D]
        row_vals = {
            "t1": t1_vals, "eff_len": t.eff_len[slots],
            "has_hash": t.has_hash[slots],
            "first_wild": t.first_wild[slots], "active": t.active[slots],
        }
        named = dict(zip(MATCHER_STATE_NAMES, self._dev))
        addressable = self.addressable_slices()

        def pad_pow2(idx: np.ndarray) -> np.ndarray:
            # pow2 ladder per slice so distinct dirty counts don't each
            # compile a fresh scatter (duplicate last-slot writes are
            # idempotent — same value)
            Dpad = _pow2ceil(len(idx))
            if Dpad != len(idx):
                idx = np.concatenate(
                    [idx, np.full(Dpad - len(idx), idx[-1], np.int32)])
            return idx

        def scatter_shards(name: str, upd, base_name: str) -> None:
            """Rebuild one named array ONCE, with every shard whose
            row-start is in ``upd`` (start -> (local idx, value idx))
            scattered in its own per-shard launch; every other shard's
            buffer rides into the reassembly untouched. One
            make_array_from_single_device_arrays per array per flush —
            not per dirty slice."""
            arr = named[name]
            two_d = name.endswith("F_t")
            fn = ((_scatter_cols if donate else _scatter_cols_copy)
                  if two_d else
                  (_scatter_rows if donate else _scatter_rows_copy))
            datas = []
            for sh in arr.addressable_shards:
                start = _shard_col_start(sh)
                if start in upd:
                    lidx, vidx = upd[start]
                    vals = (F_cols[:, vidx] if two_d
                            else row_vals[base_name][vidx])
                    datas.append(fn(sh.data, jnp.asarray(lidx),
                                    jnp.asarray(vals)))
                else:
                    datas.append(sh.data)
            named[name] = jax.make_array_from_single_device_arrays(
                arr.shape, arr.sharding, datas)

        # per-slice sub-deltas for the row-sharded arrays: start ->
        # (shard-local slot idx, delta-row idx), dirty+addressable only
        rows_shipped = 0
        upd: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
        for s in dirty_slices:
            if s not in addressable:
                # a remote process owns this slice: ITS write-through
                # applies the delta there (the cluster metadata plane
                # replicates the subscription events to every node)
                continue
            mine = np.nonzero(owners == s)[0]
            sel = slots[mine]
            upd[s * Sl] = (pad_pow2((sel - s * Sl).astype(np.int32)),
                           pad_pow2(mine.astype(np.int32)))
            rows_shipped += len(mine)
        if upd:
            for name in ("F_t", "t1", "eff_len", "has_hash",
                         "first_wild", "active"):
                scatter_shards(name, upd, name)

        # replicated g-zone mirrors: a dirty row below gb_end is in
        # every replica by definition — scatter each addressable copy
        # (separate accounting; this is replication cost, not a routing
        # miss)
        gmask = slots < self._glob
        if gmask.any():
            gsel = np.nonzero(gmask)[0]
            gidx = pad_pow2(slots[gsel].astype(np.int32))
            gvid = pad_pow2(gsel.astype(np.int32))
            # replicated arrays: every addressable shard starts at 0
            gupd = {_shard_col_start(sh): (gidx, gvid)
                    for sh in named["g/F_t"].addressable_shards}
            for name in ("g/F_t", "g/t1", "g/eff_len", "g/has_hash",
                         "g/first_wild", "g/active"):
                scatter_shards(name, gupd, name[2:])
            self.route_gzone_flushes += 1

        self._dev = tuple(named[n] for n in MATCHER_STATE_NAMES)
        self.route_flushes += 1
        self.route_dirty_slices += len(
            [s for s in dirty_slices if s in addressable])
        self.route_rows += rows_shipped
        self.last_route = {
            "rows": int(len(slots)), "dirty_slices": dirty_slices,
            "addressable": sorted(addressable),
            "total_slices": self.nslices,
            "gzone": bool(gmask.any()),
        }
        obs.observe("stage_mesh_delta_route_ms",
                    (time.monotonic() - t0) * 1e3)

    # ------------------------------------------------------------- dispatch

    def _pull(self, res):
        """Result pull for one launched batch (the blocking half of the
        async dispatch): observed as the mesh dispatch seam — exactly
        one observation per dispatched batch on both the match_batch
        and the launch-all-then-pull match_many paths."""
        t0 = time.monotonic()
        out = tuple(np.asarray(x) for x in res[:4])
        self.mesh_dispatches += 1
        dur = (time.monotonic() - t0) * 1e3
        obs.observe("stage_mesh_dispatch_ms", dur)
        record_dispatch("mesh", t0, dur, slices=self.nslices)
        return out

    # -------------------------------------------- multi-process local match

    def match_local_slices(self, topics: Sequence[Sequence[str]]
                           ) -> Tuple[List[np.ndarray], List[Tuple[int, int]]]:
        """Partial fanout over this process's ADDRESSABLE slices: the
        coded-operand mismatch evaluated per local shard (one matmul +
        epilogue per slice, device-resident operands — no cross-process
        collective, which XLA's CPU backend cannot run). Returns
        (per-topic GLOBAL slot-id arrays restricted to local rows, the
        owned row ranges) — the cluster plane unions partials across
        processes; rows outside the union are the callers' host-trie
        degradation responsibility."""
        t = self.table
        # same serve-current-state contract as match_batch: pending
        # deltas/growth ship BEFORE serving, or a fresh subscription
        # would be invisible to this path until someone else synced
        self.sync()
        n = len(topics)
        L = t.L
        pw = np.full((max(n, 1), L), np.int32(PAD_ID), dtype=np.int32)
        pl = np.zeros(max(n, 1), dtype=np.int32)
        pd = np.zeros(max(n, 1), dtype=bool)
        for i, tp in enumerate(topics):
            row, ln, dollar = t.encode_topic(tp)
            pw[i], pl[i], pd[i] = row, ln, dollar
        G = build_pub_operand(jnp.asarray(pw), self._bits)
        named = dict(zip(MATCHER_STATE_NAMES, self._dev))
        Sl = self._S // self.nslices
        by_slice = {}
        for sh in named["F_t"].addressable_shards:
            by_slice.setdefault(_shard_col_start(sh) // Sl, sh)
        meta_shards = {
            name: {_shard_col_start(sh) // Sl: sh
                   for sh in named[name].addressable_shards}
            for name in ("t1", "eff_len", "has_hash", "first_wild",
                         "active")}
        out = [[] for _ in range(n)]
        ranges: List[Tuple[int, int]] = []
        for s, fsh in sorted(by_slice.items()):
            ranges.append((s * Sl, (s + 1) * Sl))
            mm = coded_mismatch(fsh.data,
                                meta_shards["t1"][s].data, G)
            mask = (mm == 0.0) & _epilogue(
                jnp.asarray(pl), jnp.asarray(pd),
                meta_shards["eff_len"][s].data,
                meta_shards["has_hash"][s].data,
                meta_shards["first_wild"][s].data,
                meta_shards["active"][s].data)
            hits = np.asarray(mask)
            for i in range(n):
                out[i].append(np.nonzero(hits[i])[0].astype(np.int64)
                              + s * Sl)
        return ([np.concatenate(o) if o else np.empty(0, np.int64)
                 for o in out], ranges)

    # -------------------------------------------------------------- status

    def mesh_status(self) -> Dict[str, Any]:
        """Routing + residency snapshot for admin and gauges. The
        per-slice row counts are an O(S) active-mask reduction — cached
        per device generation (flush/build counters) so every metrics
        scrape and $SYS tick doesn't rescan a 10M-row table."""
        rows_per_slice: List[int] = []
        if self._dev is not None:
            gen = (self.full_scatters, self.route_flushes, self._S)
            cached = getattr(self, "_rps_cache", None)
            if cached is not None and cached[0] == gen:
                rows_per_slice = cached[1]
            else:
                act = self.table.active
                rows_per_slice = [int(act[lo:hi].sum())
                                  for lo, hi in self.slice_ranges()]
                self._rps_cache = (gen, rows_per_slice)
        return {
            "slices": self.nslices,
            "slice_rows": self._S // self.nslices if self._dev else 0,
            "rows_per_slice": rows_per_slice,
            "addressable": sorted(self.addressable_slices()),
            "route_flushes": self.route_flushes,
            "route_dirty_slices": self.route_dirty_slices,
            "route_gzone_flushes": self.route_gzone_flushes,
            "route_rows": self.route_rows,
            "full_scatters": self.full_scatters,
            "mesh_dispatches": self.mesh_dispatches,
            "last_route": dict(self.last_route),
        }


# ---------------------------------------------------------------------------
# The production seat
# ---------------------------------------------------------------------------


class MeshTpuMatcher(ShardedTpuMatcher):
    """TpuMatcher-compatible seat over :class:`MeshMatcher` — what
    ``TpuRegView`` builds when a mesh is configured (the default mesh
    seat; ``tpu_mesh_native=false`` keeps the legacy per-call shard_map
    seat). Inherits the full production discipline from
    ShardedTpuMatcher — lock, snapshots, async rebuilds, warm gates,
    breaker, watchdog — and swaps placement/delta for the mesh-native
    machinery. Growing the table past a slice's window re-partitions
    rows: the resize forces a full rebuild (async, host trie serving
    behind RebuildInProgress) whose install re-derives every slice's
    row range from the new S."""

    def __init__(self, mesh: Mesh, max_levels: int = 16,
                 initial_capacity: int = 1024, max_fanout: int = 128,
                 flat_avg: int = 128, **_ignored):
        super().__init__(mesh, max_levels=max_levels,
                         initial_capacity=initial_capacity,
                         max_fanout=max_fanout, flat_avg=flat_avg)
        # swap the device half for the mesh-native matcher (same table,
        # same merge posture as the sharded seat)
        self._swm = MeshMatcher(self.table, mesh, max_fanout=max_fanout,
                                flat_avg=flat_avg, merge=True)
        #: slice-map epochs already adopted (exactly-once replay guard)
        self._adopted_epochs: set = set()
        self.slice_adoptions = 0

    def _build_device(self, state: dict) -> tuple:
        """Background build from a host snapshot, placed through the
        partition rules (the seat's async-rebuild worker runs this off
        the lock)."""
        if not (state["bucketed"] and state["bits"]):
            raise ValueError("mesh-native matching needs a bucketed "
                             "table with MXU-codable ids")
        words, eff = state["words"], state["eff_len"]
        _check_mesh_geometry(words.shape[0], self.mesh.shape["sub"])
        S = words.shape[0]
        F_t, t1 = _build_operands_jit(words, eff, id_bits=state["bits"])
        glob = state["gb_end"]
        dev = place_matcher_state(
            self.mesh, np.asarray(F_t), np.asarray(t1), eff,
            state["has_hash"], state["first_wild"], state["active"],
            glob)
        self._swm.full_scatters += 1
        return (dev, S, glob)

    # ----------------------------------------------------- slice adoption

    def adopt_slices(self, slice_ids: Sequence[int], epoch) -> int:
        """Replay the rows of newly-owned slices into the device table
        exactly once per slice-map adoption token: the owned rows are
        marked dirty under the lock and the next sync ships them as
        per-slice sub-deltas (slice-routed, so the flush lands only on
        the adopted slices). ``epoch`` is an opaque hashable token —
        the slice map passes (claimer_node, its_epoch) so two nodes'
        colliding per-node counters cannot suppress a replay. Returns
        rows marked; 0 on a repeat token — the exactly-once guard a
        slice-map gossip storm needs."""
        key = (epoch, tuple(sorted(slice_ids)))
        with self.lock:
            if key in self._adopted_epochs:
                return 0
            self._adopted_epochs.add(key)
            t = self.table
            if self._dev_arrays is None:
                # nothing resident yet: the first build ships everything
                return 0
            Sl = self._swm._S // self._swm.nslices
            marked = 0
            for s in slice_ids:
                lo = s * Sl
                hi = min((s + 1) * Sl, len(t.entries))
                if hi <= lo:
                    continue
                # vectorized: the active mask IS the live-row set; a
                # per-slot Python loop here would hold the matcher
                # lock (on the gossip callback's event-loop thread)
                # for O(Sl) at 10M-row scale
                live = np.nonzero(t.active[lo:hi])[0]
                t.dirty.update((live + lo).tolist())
                marked += len(live)
            self.slice_adoptions += 1
        return marked

    def mesh_status(self) -> Dict[str, Any]:
        st = self._swm.mesh_status()
        st["slice_adoptions"] = self.slice_adoptions
        return st
