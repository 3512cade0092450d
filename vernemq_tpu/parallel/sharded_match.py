"""Sharded batched matching: shard_map over a ('batch', 'sub') mesh.

The multi-chip analog of the trie fold (SURVEY.md §5.7/§5.8): each device
holds an S/n_sub slice of the subscription table and matches the publish
batch slice assigned to its 'batch' row; per-shard top-k results are
concatenated along the 'sub' axis (all-gather over ICI at the output
sharding boundary) and counts are psum-reduced. Matched indices are
globalised with the shard offset so the host resolves them against the
full entry list.

This compiles and runs identically on a virtual CPU mesh (tests, the
driver's dry-run) and a real TPU slice.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.match_kernel import extract_indices, match_mask_unrolled


def build_sharded_matcher(mesh: Mesh, k: int):
    """Returns a jitted ``fn(sub_arrays..., pub_arrays...) -> (idx, valid,
    count)`` running under shard_map on ``mesh``. ``k`` is the per-shard
    fanout cap; the gathered result carries ``k * n_sub_shards`` candidate
    slots per publish."""

    def local_match(sub_words, sub_eff_len, has_hash, first_wild, active,
                    pub_words, pub_len, pub_dollar):
        # local shapes: subs [S/n, L]; pubs [B/nb, L]
        s_local = sub_words.shape[0]
        mask = match_mask_unrolled(sub_words, sub_eff_len, has_hash,
                                   first_wild, active, pub_words, pub_len,
                                   pub_dollar)
        block = 512 if s_local % 512 == 0 and s_local >= 512 else s_local
        idx, valid, count = extract_indices(mask, min(k, s_local), block)
        shard = lax.axis_index("sub")
        idx = idx + shard * s_local  # globalise slot ids
        total = lax.psum(count, "sub")
        return idx, valid, total

    fn = shard_map(
        local_match,
        mesh=mesh,
        in_specs=(
            P("sub", None), P("sub"), P("sub"), P("sub"), P("sub"),
            P("batch", None), P("batch"), P("batch"),
        ),
        out_specs=(P("batch", "sub"), P("batch", "sub"), P("batch")),
    )
    return jax.jit(fn)


def shard_table(mesh: Mesh, words, eff_len, has_hash, first_wild, active):
    """Place numpy table mirrors onto the mesh with 'sub' sharding. S must
    be a multiple of the 'sub' axis size (SubscriptionTable capacities are
    powers of two, so any pow2 mesh divides them)."""
    s1 = NamedSharding(mesh, P("sub", None))
    s2 = NamedSharding(mesh, P("sub"))
    return (
        jax.device_put(words, s1),
        jax.device_put(eff_len, s2),
        jax.device_put(has_hash, s2),
        jax.device_put(first_wild, s2),
        jax.device_put(active, s2),
    )


def shard_pubs(mesh: Mesh, pub_words, pub_len, pub_dollar):
    s1 = NamedSharding(mesh, P("batch", None))
    s2 = NamedSharding(mesh, P("batch"))
    return (
        jax.device_put(pub_words, s1),
        jax.device_put(pub_len, s2),
        jax.device_put(pub_dollar, s2),
    )


class ShardedMatcher:
    """Multi-device wrapper around a SubscriptionTable: shards the table
    over the mesh, serves batched matches, re-shards on growth. Delta
    scatter across shards arrives with the distributed metadata layer; for
    now mutations trigger a re-place of the dirty mirrors (bounded by table
    size, amortised by batching)."""

    def __init__(self, table, mesh: Mesh, max_fanout: int = 256):
        self.table = table
        self.mesh = mesh
        self.max_fanout = max_fanout
        self._dev = None
        self._fn = build_sharded_matcher(mesh, max_fanout)

    def sync(self) -> None:
        t = self.table
        if self._dev is None or t.resized or t.dirty:
            self._dev = shard_table(
                self.mesh, t.words, t.eff_len, t.has_hash, t.first_wild, t.active
            )
            t.resized = False
            t.dirty.clear()

    def match_batch(self, topics):
        import numpy as np

        if not topics:
            return []
        self.sync()
        nb = self.mesh.shape["batch"]
        B = max(nb, 1)
        while B < len(topics):
            B *= 2
        L = self.table.L
        pw = np.full((B, L), -2, dtype=np.int32)
        pl = np.zeros(B, dtype=np.int32)
        pd = np.zeros(B, dtype=bool)
        for i, t in enumerate(topics):
            row, n, dollar = self.table.encode_topic(t)
            pw[i], pl[i], pd[i] = row, n, dollar
        idx, valid, count = self._fn(*self._dev, *shard_pubs(self.mesh, pw, pl, pd))
        idx = np.asarray(idx)
        valid = np.asarray(valid)
        count = np.asarray(count)
        out = []
        for i, topic in enumerate(topics):
            rows = self.table.resolve(idx[i][valid[i]])
            if count[i] > int(valid[i].sum()):
                # per-shard top-k truncated this row: recover exactly on the
                # host so no subscriber is silently skipped (same fallback as
                # TpuMatcher.match_batch)
                rows = self._host_match(topic)
            elif len(self.table.overflow):
                rows = rows + self.table.overflow.match(list(topic))
            out.append(rows)
        return out

    def _host_match(self, topic):
        return host_match(self.table, topic)


def host_match(table, topic):
    """Exact host-side fallback over a snapshot of the entry list (slow
    path for truncated/leftover publishes; snapshot so concurrent
    mutation from the event loop can't skip entries mid-scan)."""
    from ..protocol.topic import match_dollar_aware

    t = list(topic)
    entries = list(table.entries)
    rows = [
        e for e in entries
        if e is not None and match_dollar_aware(t, list(e[0]))
    ]
    rows.extend(table.overflow.match(t))
    return rows


# ---------------------------------------------------------------------------
# v3: the windowed production kernel under shard_map
# ---------------------------------------------------------------------------

from ..models.tpu_matcher import (TILE_PUBS, _pad_pub_block, _pow2ceil,
                                  prepare_windows)
from ..ops.match_kernel import (
    _epilogue,
    _pack_mask,
    build_operands,
    build_pub_operand,
    extract_indices_packed,
)


def build_sharded_windowed(mesh: Mesh, *, id_bits: int, k: int,
                           glob_pad: int, seg_max: int, gc: int, T: int,
                           Sl: int, Cl: int, with_total: bool = False,
                           merge: bool = False):
    """The flat windowed production matcher under shard_map on a
    ('batch', 'sub') mesh — the multi-chip form of
    :func:`ops.match_kernel.match_extract_windowed_flat`.

    Sharding (SURVEY.md §5.7/§5.8): the coded operand matrix F_t is
    column-sharded over 'sub' (each device owns Sl contiguous table rows —
    the per-node trie replica seam vmq_reg_trie.erl:503-520 recast as row
    slices); the publish batch is sharded over 'batch'. The dense zone
    (region 0 + level-1 g-buckets) travels replicated and each 'sub'
    shard matches its column chunk, so no work is duplicated. Probe-A
    tiles are per-(batch,sub) DEVICE-LOCAL: [nb, nsub, T, TP] selector
    indices into the device's local pub slice, windows are shard-local
    dynamic slices. Each device flat-compacts ITS OWN matches (dense
    chunk + its probe tiles) into a [Cl] buffer with per-pub prefix
    ranges exactly like the single-chip kernel; the host concatenates a
    pub's ranges across the 'sub' row. No per-batch collective is needed
    for results — the optional psum'd total is the dryrun's ICI
    demonstration (production skips the collective latency).
    """
    import math

    nsub = mesh.shape["sub"]
    GW = glob_pad // nsub
    # packed-extraction block: must divide the per-shard region width and
    # be a multiple of 32 — GW is 2048-aligned/nsub, so gcd with 2048
    # gives the largest valid block
    gblock = math.gcd(GW, 2048)
    assert glob_pad % nsub == 0 and seg_max <= Sl and gblock >= 32

    def local(F_sh, t1_sh, eff_sh, hh_sh, fw_sh, act_sh,
              Fg, t1g, effg, hhg, fwg, actg,
              pw, pl, pd, real,
              t_sel, t_start, a_tile, a_pos, a_shard):
        Kd = F_sh.shape[0]
        t_sel, t_start = t_sel[0, 0], t_start[0, 0]
        sidx = lax.axis_index("sub")
        j = jnp.arange(seg_max, dtype=jnp.int32)

        # dense phase: this shard's column chunk of the dense zone, all
        # pubs of this batch shard, in gc-sized pub chunks
        goff = sidx * GW
        Fg_c = lax.dynamic_slice(Fg, (0, goff), (Kd, GW))
        t1g_c = lax.dynamic_slice(t1g, (goff,), (GW,))
        effg_c = lax.dynamic_slice(effg, (goff,), (GW,))
        hhg_c = lax.dynamic_slice(hhg, (goff,), (GW,))
        fwg_c = lax.dynamic_slice(fwg, (goff,), (GW,))
        actg_c = lax.dynamic_slice(actg, (goff,), (GW,))
        Bl = pw.shape[0]
        gouts = []
        for c in range(0, Bl, min(gc, Bl)):
            sl = slice(c, c + min(gc, Bl))
            G = build_pub_operand(pw[sl], id_bits)
            mm = lax.dot_general(G, Fg_c, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
            m = (mm + t1g_c[None, :] == 0.0) & _epilogue(
                pl[sl], pd[sl], effg_c, hhg_c, fwg_c, actg_c)
            i1, v1, c1 = extract_indices_packed(_pack_mask(m), k, gblock)
            gouts.append((i1 + goff, v1, c1))
        gidx = jnp.concatenate([o[0] for o in gouts], axis=0)
        gvalid = jnp.concatenate([o[1] for o in gouts], axis=0)
        gcount = jnp.concatenate([o[2] for o in gouts], axis=0)

        # probe-A tile phase against this shard's row slice: tile pubs
        # gathered from the LOCAL pub slice by selector
        touts = []
        for ti in range(T):
            sel = t_sel[ti]
            pwt = jnp.take(pw, sel, axis=0)
            plt = jnp.take(pl, sel)
            pdt = jnp.take(pd, sel)
            start = t_start[ti]
            Fseg = lax.dynamic_slice(F_sh, (0, start), (Kd, seg_max))
            t1s = lax.dynamic_slice(t1_sh, (start,), (seg_max,))
            effs = lax.dynamic_slice(eff_sh, (start,), (seg_max,))
            hhs = lax.dynamic_slice(hh_sh, (start,), (seg_max,))
            fws = lax.dynamic_slice(fw_sh, (start,), (seg_max,))
            acts = lax.dynamic_slice(act_sh, (start,), (seg_max,))
            Gt = build_pub_operand(pwt, id_bits)
            mm = lax.dot_general(Gt, Fseg, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
            abs_start = sidx * Sl + start
            rowok = (j[None, :] + abs_start) >= glob_pad
            m = (mm + t1s[None, :] == 0.0) & _epilogue(
                plt, pdt, effs, hhs, fws, acts) & rowok
            i2, v2, c2 = extract_indices_packed(_pack_mask(m), k, 2048)
            touts.append((i2 + abs_start, v2, c2))
        tidx = jnp.stack([o[0] for o in touts])
        tvalid = jnp.stack([o[1] for o in touts])
        tcount = jnp.stack([o[2] for o in touts])

        # flat compaction (single-chip contract, per device): matches of
        # this device's pubs on this shard's rows
        okA = (a_shard == sidx) & (a_tile >= 0) & real
        at = jnp.maximum(a_tile, 0)
        aidx = tidx[at, a_pos]
        avalid = tvalid[at, a_pos] & okA[:, None]
        acnt = jnp.where(okA, tcount[at, a_pos], 0)
        clip = (gcount > k) | (acnt > k)
        gcnt = jnp.minimum(jnp.where(real, gcount, 0), k)
        acnt = jnp.minimum(acnt, k)
        cnt = gcnt + acnt
        pre = jnp.cumsum(cnt) - cnt
        jk = jnp.arange(k, dtype=jnp.int32)[None, :]
        flat = jnp.zeros((Cl,), jnp.int32)

        def scat(flat, base, idx, valid, cn):
            pos = base[:, None] + jk
            p = jnp.where(valid & real[:, None] & (jk < cn[:, None]),
                          pos, Cl)
            return flat.at[p].set(idx, mode="drop")

        flat = scat(flat, pre, gidx, gvalid, gcnt)
        flat = scat(flat, pre + gcnt, aidx, avalid, acnt)
        ovf = ((pre + cnt > Cl) | clip) & real

        if merge:
            # merge across the 'sub' axis ON DEVICE (all_gather rides
            # ICI): every device of a batch row materialises the full
            # per-pub result ranges and the host pulls ONE [Cl] buffer
            # per batch row instead of nsub of them — the collective
            # costs ICI bandwidth (nsub x Cl gathered) to cut the
            # host<->device pull by nsub x, the right trade everywhere
            # ICI >> host link (SURVEY §5.8).
            g_flat = lax.all_gather(flat, "sub")          # [nsub, Cl]
            g_pre = lax.all_gather(pre, "sub")            # [nsub, Bl]
            g_cnt = lax.all_gather(cnt, "sub")
            g_ovf = lax.all_gather(ovf, "sub")
            before = jnp.cumsum(g_cnt, axis=0) - g_cnt    # [nsub, Bl]
            mcnt = g_cnt.sum(axis=0)                      # [Bl]
            mpre = jnp.cumsum(mcnt) - mcnt
            mflat = jnp.zeros((Cl,), jnp.int32)
            nsub_ = g_flat.shape[0]
            # per-shard per-pub cnt = gcnt + acnt can reach 2k (dense
            # chunk + probe tile each contribute up to k) — the copy
            # window must span 2k or the tail entries silently vanish
            jk2 = jnp.arange(2 * k, dtype=jnp.int32)[None, :]
            for s_i in range(nsub_):
                src = g_pre[s_i][:, None] + jk2           # [Bl, 2k]
                vals = jnp.take(g_flat[s_i],
                                jnp.minimum(src, Cl - 1))
                pos = (mpre + before[s_i])[:, None] + jk2
                ok = (jk2 < g_cnt[s_i][:, None]) & real[:, None]
                mflat = mflat.at[jnp.where(ok, pos, Cl)].set(
                    vals, mode="drop")
            movf = (g_ovf.any(axis=0) | (mpre + mcnt > Cl)) & real
            outs = (mflat[None], mpre[None].astype(jnp.int32),
                    mcnt[None].astype(jnp.int32), movf[None])
        else:
            outs = (flat[None, None], pre[None, None].astype(jnp.int32),
                    cnt[None, None].astype(jnp.int32), ovf[None, None])
        if with_total:
            # ICI collective: cluster-wide match total (dryrun exercises
            # it; production skips the per-batch collective latency)
            total = lax.psum(lax.psum(cnt.sum(), "sub"), "batch")
            outs = outs + (total,)
        return outs

    res_spec = (P("batch", None) if merge else P("batch", "sub", None))
    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P(None, "sub"), P("sub"), P("sub"), P("sub"), P("sub"), P("sub"),
            P(None, None), P(None), P(None), P(None), P(None), P(None),
            P("batch", None), P("batch"), P("batch"), P("batch"),
            P("batch", "sub", None, None), P("batch", "sub", None),
            P("batch"), P("batch"), P("batch"),
        ),
        out_specs=(res_spec,) * 4 + ((P(),) if with_total else ()),
        check_vma=False,
    )
    return jax.jit(fn)


class ShardedWindowedMatcher:
    """Multi-device windowed matcher over a SubscriptionTable: the
    production (bucketed/windowed) path sharded on a ('batch', 'sub')
    mesh. Host prep assigns each publish to the 'sub' shard owning its
    bucket's rows; pubs in buckets straddling a shard cut (or overflowing
    their shard's tile slots) fall back to exact host matching."""

    def __init__(self, table, mesh: Mesh, max_fanout: int = 128,
                 with_total: bool = False, flat_avg: int = 128,
                 merge: bool = False):
        self.table = table
        self.mesh = mesh
        self.nsub = mesh.shape["sub"]
        self.nb = mesh.shape["batch"]
        self.max_fanout = max_fanout
        self.with_total = with_total
        self.flat_avg = flat_avg
        #: merge results across 'sub' on device (ICI all_gather): host
        #: pulls ONE buffer per batch row instead of nsub — production
        #: posture for real pods; off by default for back-compat
        self.merge = merge
        self._dev = None
        self._fns = {}
        self._geom = None

    def sync(self) -> None:
        import numpy as np

        t = self.table
        self._reg_start = t.reg_start.copy()
        self._reg_end = (t.reg_start + t.reg_cap).copy()
        if self._dev is not None and not t.resized and not t.dirty:
            return
        if self._dev is not None and not t.resized:
            self._sync_delta()
            return
        assert t.bucketed and t.id_bits, "windowed sharding needs a bucketed table"
        S = t.cap
        assert S % self.nsub == 0
        if S // self.nsub < 4096:
            raise ValueError(
                f"table of {S} rows is too small for a {self.nsub}-way "
                f"'sub' axis (each shard needs >= 4096 rows)")
        # device-resident coded operands, column-sharded over 'sub'
        F_t, t1 = jax.jit(build_operands, static_argnames=("id_bits",))(
            t.words, t.eff_len, id_bits=t.id_bits)
        F_t = np.asarray(F_t)
        t1 = np.asarray(t1)
        # dense phase covers the whole g-zone (region 0 + level-1
        # g-buckets): the sharded path keeps one dense probe (two-level
        # probing is a single-chip optimisation for now)
        glob = t.gb_end
        sF = NamedSharding(self.mesh, P(None, "sub"))
        s1 = NamedSharding(self.mesh, P("sub"))
        rep2 = NamedSharding(self.mesh, P(None, None))
        rep1 = NamedSharding(self.mesh, P(None))
        self._dev = (
            jax.device_put(F_t, sF), jax.device_put(t1, s1),
            jax.device_put(t.eff_len, s1), jax.device_put(t.has_hash, s1),
            jax.device_put(t.first_wild, s1), jax.device_put(t.active, s1),
            jax.device_put(F_t[:, :glob], rep2),
            jax.device_put(t1[:glob], rep1),
            jax.device_put(t.eff_len[:glob], rep1),
            jax.device_put(t.has_hash[:glob], rep1),
            jax.device_put(t.first_wild[:glob], rep1),
            jax.device_put(t.active[:glob], rep1),
        )
        self._glob = glob
        self._S = S
        self._bits = t.id_bits
        t.resized = False
        t.dirty.clear()

    def _sync_delta(self, donate: bool = True) -> None:
        """Scatter dirty slots into the sharded device arrays — ONE
        packed upload + ONE fused jit scatter per flush
        (``apply_delta_windowed_fused``: full-table operands, metadata
        arrays and the replicated g-zone mirrors updated together,
        GSPMD resolving the sharded .at[].set). The per-array eager
        path this replaces dispatched up to ten scatters per flush and
        recompiled on every distinct dirty-in-zone count — the
        delta_apply_ms_p99 long pole. ``donate=False`` while a
        dispatched match still holds the buffers (the seat's in-flight
        guard): the donating scatter would delete the arrays under the
        in-flight call."""
        import numpy as np

        from ..ops.match_kernel import (apply_delta_windowed_fused,
                                        apply_delta_windowed_fused_copy,
                                        delta_pack_args)

        t = self.table
        slots = np.fromiter(t.dirty, dtype=np.int32)
        t.dirty.clear()
        # pow2-pad the delta (idempotent duplicate writes) so distinct
        # dirty counts don't each compile a fresh scatter
        Dpad = _pow2ceil(len(slots))
        if Dpad != len(slots):
            slots = np.concatenate(
                [slots, np.full(Dpad - len(slots), slots[-1], np.int32)])
        packed = delta_pack_args(
            slots, t.words[slots], t.eff_len[slots], t.has_hash[slots],
            t.first_wild[slots], t.active[slots])
        fused = (apply_delta_windowed_fused if donate
                 else apply_delta_windowed_fused_copy)
        self._dev = tuple(fused(
            *self._dev, packed, D=len(slots), L=t.words.shape[1],
            id_bits=self._bits, glob=self._glob))

    def _fn_for(self, Bpad: int, T: int, seg_max: int, gc: int, Cl: int,
                glob: Optional[int] = None, S: Optional[int] = None,
                bits: Optional[int] = None):
        # _glob (the dense width) and _S (hence Sl) are baked into the
        # compiled fn as Python constants — a rebuild can move them while
        # leaving the other dims unchanged, so they must key the cache.
        # Callers racing a background rebuild pass the glob/S/bits their
        # prep snapshot was taken against.
        glob = self._glob if glob is None else glob
        S = self._S if S is None else S
        bits = self._bits if bits is None else bits
        # bits keys the cache too: an id_bits-only rebuild (interner
        # crossing a byte plane, no resize) changes the coded-operand
        # decode width baked into the compiled fn
        key = (Bpad, T, seg_max, gc, Cl, glob, S, bits, self.merge)
        fn = self._fns.get(key)
        if fn is None:
            fn = build_sharded_windowed(
                self.mesh, id_bits=bits, k=self.max_fanout,
                glob_pad=glob, seg_max=seg_max, gc=gc, T=T,
                Sl=S // self.nsub, Cl=Cl,
                with_total=self.with_total, merge=self.merge)
            self._fns[key] = fn
        return fn

    def _prep(self, topics):
        """Host-side prep of one batch against the CURRENT table/window
        state (callers needing consistency run this under their lock):
        encode, per-shard pub assignment, window tiles. Returns everything
        :meth:`_dispatch` and result resolution need. (The seat encodes
        through TpuMatcher's cached encoder and calls
        :meth:`_prep_encoded` directly.)"""
        import numpy as np

        n = len(topics)
        nb = self.nb
        # batch padding: divisible by the batch axis and pow2-laddered
        Bpad = nb
        while Bpad < n:
            Bpad *= 2
        Bpad = max(Bpad, 8 * nb)
        L = self.table.L
        # pad rows use PAD_ID like the seat's cached encoder, so dryrun
        # and production feed the kernel identical pad bytes (pads are
        # masked by `real` either way)
        from ..ops.match_kernel import PAD_ID

        pw = np.full((Bpad, L), np.int32(PAD_ID), dtype=np.int32)
        pl = np.zeros(Bpad, dtype=np.int32)
        pd = np.zeros(Bpad, dtype=bool)
        pb = np.zeros(n, dtype=np.int32)
        for i, topic in enumerate(topics):
            row, ln, dollar, bucket, _gb = self.table.encode_topic_ex(topic)
            pw[i], pl[i], pd[i], pb[i] = row, ln, dollar, bucket
        return self._prep_encoded(pw, pl, pd, pb, n)

    def _pin_state(self) -> dict:
        """Pin every live field the window prep reads, under the
        caller's lock — so the heavy per-batch prep itself can run
        AFTER release against a consistent view (the K-batch path preps
        K batches; holding the lock K× prep time would push concurrent
        flushes past their lock_busy_shed bound)."""
        return {"S": self._S, "glob": self._glob, "bits": self._bits,
                "dev": self._dev, "reg_start": self._reg_start,
                "reg_end": self._reg_end, "ng": self.table.NG}

    def _prep_encoded(self, pw, pl, pd, pb, n: int, pinned=None):
        """Window/tile prep for an ALREADY-ENCODED padded batch (pw
        [Bpad, L]; pb holds the n real publishes' buckets). Bpad must be
        pow2-laddered and divisible by the 'batch' axis. ``pinned`` (a
        :meth:`_pin_state` snapshot) lets callers run this outside
        their lock; without it the live state is read directly (then
        run under the lock)."""
        import numpy as np

        st = pinned or self._pin_state()
        S, glob, nsub = st["S"], st["glob"], self.nsub
        nb = self.nb
        Sl = S // nsub
        Bpad = pw.shape[0]
        assert Bpad % nb == 0, \
            f"Bpad {Bpad} not divisible by the batch axis {nb}"
        Bl = Bpad // nb  # local pub slice per batch row
        real = np.zeros(Bpad, dtype=bool)
        real[:n] = True
        # per-shard pub assignment by bucket-row ownership (pads: -1)
        shard_of = np.full(Bpad, -1, dtype=np.int32)
        reg_start, reg_end = st["reg_start"], st["reg_end"]
        shard_of[:n] = np.minimum(reg_start[pb] // Sl, nsub - 1)
        slot_tiles = max(1, -(-Bl // TILE_PUBS))
        # level-0 buckets only: the g-zone (regions 1..NG) is matched
        # densely here and must not inflate the window size
        ng = st["ng"]
        bucket_max = (int((reg_end[1 + ng:]
                           - reg_start[1 + ng:]).max())
                      if len(reg_start) > 1 + ng else 0)
        # window must divide into 2048 blocks (packed extraction) and fit
        # the shard slice; Sl itself may not be 2048-aligned
        sl_cap = Sl - Sl % 2048
        seg_max = min(_pow2ceil(max(4096, bucket_max, 2 * Sl // slot_tiles)),
                      sl_cap)
        # span budget: tiles close on window overflow even with free slots
        T = slot_tiles + -(-Sl // seg_max) + 2
        gc = min(Bl, 1024)
        Cl = Bl * self.flat_avg
        TP = TILE_PUBS
        t_sel = np.zeros((nb, nsub, T, TP), dtype=np.int32)
        t_start = np.zeros((nb, nsub, T), dtype=np.int32)
        a_tile = np.full(Bpad, -1, dtype=np.int32)
        a_pos = np.zeros(Bpad, dtype=np.int32)
        leftovers = set()
        for r in range(nb):
            lo = r * Bl
            sor = shard_of[lo:lo + Bl]
            for s in range(nsub):
                mine = np.nonzero(sor == s)[0]  # row-local indices
                if len(mine) == 0:
                    continue
                sel = lo + mine
                (tsc, tss, tof, pof, left) = prepare_windows(
                    pw[sel], pl[sel], pd[sel], pb[sel],
                    len(mine), reg_start, reg_end, S, T,
                    seg_max, row_lo=s * Sl, row_hi=(s + 1) * Sl,
                    emit="sel")
                # map compact-space selectors back to row-local indices
                t_sel[r, s] = mine[tsc]
                t_start[r, s] = tss
                placed = tof >= 0
                a_tile[sel[placed]] = tof[placed]
                a_pos[sel[placed]] = pof[placed]
                for li in left:
                    leftovers.add(int(sel[li]))
        return {
            "geom": (Bpad, T, seg_max, gc, Cl),
            "glob": glob, "S": S, "bits": st["bits"], "Bl": Bl,
            "dev": st["dev"], "leftovers": leftovers,
            "args": (pw, pl, pd, real, t_sel, t_start, a_tile, a_pos,
                     shard_of),
        }

    def _dispatch_device(self, p):
        """Launch the device half of a prepped batch WITHOUT pulling the
        results — jax dispatch is async, so a caller can launch several
        prepped batches back to back (upload/compute overlapped in the
        device queue) and only then pull: the seat's pipelined
        match_many path."""
        faults.inject("device.dispatch")
        fn = self._fn_for(*p["geom"], glob=p["glob"], S=p["S"],
                          bits=p["bits"])
        return fn(*p["dev"], *p["args"])

    @staticmethod
    def _pull(res):
        import numpy as np

        return tuple(np.asarray(x) for x in res[:4])

    def _dispatch(self, p):
        """Run the device half of a prepped batch. Returns np arrays —
        layout depends on ``self.merge``: unmerged flat [nb, nsub, Cl],
        pre/cnt/ovf [nb, nsub, Bl]; merged flat [nb, Cl], pre/cnt/ovf
        [nb, Bl]. Consumers must go through :meth:`slots_for` /
        :meth:`_overflowed`, which encapsulate the layout."""
        return self._pull(self._dispatch_device(p))

    def slots_for(self, i, flat, pre, cnt, Bl):
        """Device-result slot ids for publish ``i`` under the configured
        result layout (merged: ONE contiguous range per pub; unmerged:
        one range per 'sub' shard)."""
        import numpy as np

        r, j = divmod(i, Bl)
        if self.merge:
            return flat[r, pre[r, j]:pre[r, j] + cnt[r, j]]
        return np.concatenate(
            [flat[r, s, pre[r, s, j]:pre[r, s, j] + cnt[r, s, j]]
             for s in range(self.nsub)])

    def _overflowed(self, i, ovf, Bl):
        r, j = divmod(i, Bl)
        return bool(ovf[r, j] if self.merge else ovf[r, :, j].any())

    def match_batch(self, topics):
        if not topics:
            return []
        self.sync()
        p = self._prep(topics)
        flat, pre, cnt, ovf = self._dispatch(p)
        Bl, leftovers = p["Bl"], p["leftovers"]
        out = []
        for i, topic in enumerate(topics):
            if i in leftovers or self._overflowed(i, ovf, Bl):
                out.append(self._host_match(topic))
                continue
            rows = self.table.resolve(self.slots_for(i, flat, pre, cnt, Bl))
            if len(self.table.overflow):
                rows = rows + self.table.overflow.match(list(topic))
            out.append(rows)
        return out

    def _host_match(self, topic):
        return host_match(self.table, topic)


# ---------------------------------------------------------------------------
# The production seat: TpuMatcher-compatible adapter over the sharded kernel
# ---------------------------------------------------------------------------

from ..models.tpu_matcher import MatcherBusy, RebuildInProgress, TpuMatcher
from ..robustness import faults


class ShardedTpuMatcher(TpuMatcher):
    """Multi-device seat behind the reg-view seam (SURVEY §5.7: the trie
    replica sharded across cores, ``vmq_reg_trie.erl:503-520`` recast as
    row slices on a ('batch', 'sub') mesh).

    Inherits TpuMatcher's production discipline — the mutation lock,
    entries-snapshot resolution, async growth rebuilds with
    RebuildInProgress shedding, compile-signature warmth (MatcherBusy on
    cold shapes), warm_ladder/ensure_warm — and swaps the device half for
    :class:`ShardedWindowedMatcher`'s shard_map kernel. ``TpuRegView``
    builds this instead of a single-chip matcher when a ``tpu_mesh`` is
    configured, so the broker's serving path (BatchCollector included)
    matches on every device of the mesh with the same delta stream and
    fallback story as the single-chip path."""

    def __init__(self, mesh: Mesh, max_levels: int = 16,
                 initial_capacity: int = 1024, max_fanout: int = 128,
                 flat_avg: int = 128, **_ignored):
        nsub = mesh.shape["sub"]
        # every 'sub' shard needs >= 4096 rows (window-geometry floor) and
        # S must divide over the axis: pre-size the table accordingly —
        # growth doubles, so the invariant holds for life
        cap = max(initial_capacity, 4096 * nsub, 32768)
        super().__init__(max_levels=max_levels, initial_capacity=cap,
                         max_fanout=max_fanout, flat_avg=flat_avg,
                         use_pallas=False)
        self.mesh = mesh
        # merge=True: the production posture — results merged across the
        # 'sub' axis on device (ICI all_gather), so the host pulls ONE
        # buffer per batch row instead of nsub of them
        self._swm = ShardedWindowedMatcher(
            self.table, mesh, max_fanout=max_fanout, flat_avg=flat_avg,
            merge=True)

    # ------------------------------------------------------------- building

    def _build_device(self, state: dict) -> tuple:
        """Sharded device build from a host snapshot (no lock held): the
        coded operands column-sharded over 'sub', the dense g-zone
        replicated — the sharded mirror of ShardedWindowedMatcher.sync's
        full-build path, but from a pinned snapshot so the async-rebuild
        machinery can run it on a worker thread."""
        import numpy as np

        if not (state["bucketed"] and state["bits"]):
            raise ValueError("sharded windowed matcher needs a bucketed "
                             "table with MXU-codable ids")
        words, eff = state["words"], state["eff_len"]
        S = words.shape[0]
        nsub = self.mesh.shape["sub"]
        if S % nsub != 0 or S // nsub < 4096:
            raise ValueError(
                f"table of {S} rows cannot shard over a {nsub}-way 'sub' "
                f"axis (needs S % {nsub} == 0 and >= 4096 rows/shard)")
        F_t, t1 = self._jax.jit(
            build_operands, static_argnames=("id_bits",))(
                words, eff, id_bits=state["bits"])
        F_t = np.asarray(F_t)
        t1 = np.asarray(t1)
        glob = state["gb_end"]
        mesh = self.mesh
        sF = NamedSharding(mesh, P(None, "sub"))
        s1 = NamedSharding(mesh, P("sub"))
        rep2 = NamedSharding(mesh, P(None, None))
        rep1 = NamedSharding(mesh, P(None))
        put = jax.device_put
        dev = (
            put(F_t, sF), put(t1, s1),
            put(eff, s1), put(state["has_hash"], s1),
            put(state["first_wild"], s1), put(state["active"], s1),
            put(F_t[:, :glob], rep2), put(t1[:glob], rep1),
            put(eff[:glob], rep1), put(state["has_hash"][:glob], rep1),
            put(state["first_wild"][:glob], rep1),
            put(state["active"][:glob], rep1),
        )
        return (dev, S, glob)

    def _install_built(self, built: tuple, state: dict) -> None:
        dev, S, glob = built
        self._warm_sigs.clear()
        sw = self._swm
        sw._dev = dev
        sw._S = S
        sw._glob = glob
        sw._bits = state["bits"]
        sw._reg_start = state["reg_start"]
        sw._reg_end = state["reg_end"]
        # the base-class bookkeeping the shared machinery reads
        self._dev_arrays = dev
        self._operands = None
        self._meta = None
        self._ops_bits = state["bits"]
        self._reg_start = state["reg_start"]
        self._reg_end = state["reg_end"]
        self._glob_pad = state["glob_pad"]
        self._gb_end = state["gb_end"]
        self._ng = state["ng"]
        self._bucketed = state["bucketed"]
        self._entries_snapshot = state["entries"]

    # ----------------------------------------------------------------- sync

    def sync(self) -> None:
        """Full sharded rebuild on growth (async when enabled, with the
        same RebuildInProgress shed as the single-chip seat), sharded
        delta scatter otherwise. Callers hold ``self.lock``."""
        t = self.table
        if self._rebuild_thread is not None:
            tok = self._rebuild_token
            abandoned = tok is not None and tok.get("abandoned")
            if self._rebuild_thread.is_alive() and not abandoned:
                raise RebuildInProgress
            # crashed — or watchdog-abandoned (wedged) — worker consumed
            # the flag: re-arm (same reap discipline as TpuMatcher.sync;
            # a late install discards against its token)
            self._rebuild_thread = None
            t.resized = True
        if self._dev_arrays is None or t.resized \
                or t.id_bits != self._ops_bits:
            if self._dev_arrays is not None and self.async_rebuild:
                self._spawn_rebuild_locked()
                raise RebuildInProgress
            state = self._snapshot_host_locked(copy=False, clear=False)
            self._install_built(self._build_device(state), state)
            t.resized = False
            t.dirty.clear()
            return
        sw = self._swm
        if t.dirty:
            # copy-on-write entries snapshot: in-flight resolutions keep
            # the state their device call actually matched
            snap = self._entries_snapshot.copy()
            for s in t.dirty:
                snap[s] = t.entries[s]
            self._entries_snapshot = snap
            try:
                faults.inject("device.delta")
                # donation only while NO dispatched match holds the
                # arrays — the donating scatter deletes its inputs
                # (base-class in-flight guard, tpu_matcher.sync)
                sw._sync_delta(donate=self._inflight == 0)
            except Exception:
                # scatter didn't land but the dirty set is consumed:
                # force a full sharded rebuild so host and device
                # re-converge (same repair as the single-chip seat)
                t.resized = True
                raise
            self._dev_arrays = sw._dev
        # bucket relocation (spare tail) moves regions without a resize
        self._reg_start = sw._reg_start = t.reg_start.copy()
        self._reg_end = sw._reg_end = (t.reg_start + t.reg_cap).copy()

    # ---------------------------------------------------------------- match

    def _match_batch_impl(self, topics, _warmup, lock_timeout,
                          require_warm):
        import numpy as np

        if lock_timeout is None:
            self.lock.acquire()
        elif not self.lock.acquire(timeout=lock_timeout):
            self.busy_sheds += 1
            raise MatcherBusy(cold=False)
        try:
            try:
                self.sync()
            except RebuildInProgress:
                raise
            except Exception as e:
                self._record_device_failure(e)
            sw = self._swm
            snapshot = self._entries_snapshot
            # cached encoder (hot zipf topics skip per-word interning)
            # + window prep, on a consistent table view under the lock
            pw, pl, pd, pb, _gb = self._encode_batch_ex(topics)
            p = sw._prep_encoded(pw, pl, pd, pb, len(topics))
            sig = ("sharded",) + p["geom"] + (p["glob"], p["S"])
            if require_warm and sig not in self._warm_sigs:
                self.busy_sheds += 1
                raise MatcherBusy(cold=True)
            self._inflight += 1
        finally:
            self.lock.release()
        if _warmup:
            self.warmup_batches += 1
            self.warmup_publishes += len(topics)
        else:
            self.match_batches += 1
            self.match_publishes += len(topics)
            self._last_shape = ("batch", len(topics))
        try:
            pulled = sw._dispatch(p)
            self._warm_sigs.add(sig)
        except MatcherBusy:
            raise
        except Exception as e:
            self._record_device_failure(e)
        else:
            self._record_device_success(_warmup)
        finally:
            with self.lock:
                self._inflight -= 1
        return self._resolve_sharded(topics, p, pulled, snapshot)

    def _resolve_sharded(self, topics, p, pulled, snapshot):
        """Result resolution for one pulled sharded batch (shared by
        match_batch and the pipelined match_many)."""
        sw = self._swm
        flat, pre, cnt, ovf = pulled
        Bl, leftovers = p["Bl"], p["leftovers"]
        out = []
        for i, topic in enumerate(topics):
            if i in leftovers or sw._overflowed(i, ovf, Bl):
                self.host_fallbacks += 1
                out.append(self._host_match(topic, snapshot))
                continue
            rows = [e for e in
                    snapshot[sw.slots_for(i, flat, pre, cnt, Bl)]
                    if e is not None]
            with self.lock:
                if len(self.table.overflow):
                    rows = rows + self.table.overflow.match(list(topic))
            out.append(rows)
        return out

    @property
    def supports_match_many(self) -> bool:
        """The sharded seat pipelines any bucketed table (launch-all-
        then-pull) — no packed transport requirement."""
        t = self.table
        return bool(t.bucketed and t.id_bits)

    def _match_many_impl(self, batches, _warmup, lock_timeout,
                         require_warm):
        """The sharded seat's multi-batch pipeline: all K batches are
        encoded and window-prepped against ONE consistent table snapshot
        (one lock hold, one sync), then every batch is LAUNCHED before
        any result is pulled — jax's async dispatch overlaps the K
        uploads and shard_map executions in the device queue, so the
        host pays one pipeline fill instead of K serialized round
        trips. Results per batch match K independent match_batch
        calls."""
        import numpy as np

        batches = [list(b) for b in batches]
        if not batches:
            return []
        if lock_timeout is None:
            self.lock.acquire()
        elif not self.lock.acquire(timeout=lock_timeout):
            self.busy_sheds += 1
            raise MatcherBusy(cold=False)
        try:
            try:
                self.sync()
            except RebuildInProgress:
                raise
            except Exception as e:
                self._record_device_failure(e)
            sw = self._swm
            snapshot = self._entries_snapshot
            # common Bpad: all K share one compile signature
            Bpad = max(self._pad_batch(len(b)) for b in batches)
            # only the encode (table interner) needs the lock; the heavy
            # window prep runs on the pinned state AFTER release, like
            # the base matcher — holding the lock K× prep time would
            # push concurrent flushes past their lock_busy_shed bound
            encoded = []
            for topics in batches:
                pw, pl, pd, pb, _gb = self._encode_batch_ex(topics)
                pw, pl, pd = _pad_pub_block(pw, pl, pd, Bpad)
                encoded.append((pw, pl, pd, pb))
            pinned = sw._pin_state()
            self._inflight += 1
        finally:
            self.lock.release()
        n_pubs = sum(len(b) for b in batches)
        if _warmup:
            self.warmup_batches += len(batches)
            self.warmup_publishes += n_pubs
        else:
            self.match_batches += len(batches)
            self.match_publishes += n_pubs
            self._last_shape = ("many", len(batches),
                                max(len(b) for b in batches))
        try:
            preps = [sw._prep_encoded(pw, pl, pd, pb, len(topics),
                                      pinned=pinned)
                     for topics, (pw, pl, pd, pb) in zip(batches, encoded)]
            sig = (("sharded-many", len(batches)) + preps[0]["geom"]
                   + (preps[0]["glob"], preps[0]["S"]))
            if require_warm and sig not in self._warm_sigs:
                self.busy_sheds += 1
                raise MatcherBusy(cold=True)
            # launch ALL batches, then pull — the pipelined dispatch
            refs = [sw._dispatch_device(p) for p in preps]
            pulled = [sw._pull(r) for r in refs]
            self._warm_sigs.add(sig)
            if not _warmup:
                self.super_dispatches += 1
        except MatcherBusy:
            raise
        except Exception as e:
            self._record_device_failure(e)
        else:
            self._record_device_success(_warmup)
        finally:
            with self.lock:
                self._inflight -= 1
        return [self._resolve_sharded(topics, p, pl_, snapshot)
                for topics, p, pl_ in zip(batches, preps, pulled)]

    def _pad_batch(self, n: int) -> int:
        # mirror _prep's Bpad ladder (divisible by the 'batch' axis) so
        # ensure_warm's dedup key matches the shape actually compiled
        b = 8 * self.mesh.shape["batch"]
        while b < n:
            b *= 2
        return b

    def warm_delta_ladder(self, max_delta: int = 128) -> int:
        # the sharded delta scatter (_sync_delta) compiles per dirty
        # count inside shard_map; pre-warming it needs real dirty state,
        # so the sharded seat compiles delta shapes on demand
        return 0
