"""Benchmark: the BASELINE.md config ladder against the production
windowed match path.

Prints ONE JSON line. Headline = config 3 (1M resident subscriptions,
mixed +/# wildcards, Zipf-skewed publish stream, batched match):

  {"metric": "topic-matches/sec @1M subs", "value": N, "unit": "matches/s",
   "vs_baseline": ratio-vs-10M-target, "configs": {...}, ...extras}

The reference publishes no absolute numbers (BASELINE.md); vs_baseline is
measured against the stated north-star target of 10M topic-matches/sec on
a single v5e-1 with <=2ms added p99 (BASELINE.json). Extra keys are
informational: per-config rows (1: 1k exact/host trie, 2: 100k "+"
wildcards, 4: shared subs + retained replay, 5: 5M subs + delta
streaming) and a per-batch breakdown (encode/prep/device/resolve ms).

Two per-batch times are reported: the synced round trip, and the
pipelined steady-state time ("batch_ms": dispatch is async; a checksum
derived from every batch is pulled once after the clock stops).
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import time

import numpy as np

TARGET_MATCHES_PER_SEC = 10_000_000


def note(msg):
    print(msg, file=sys.stderr, flush=True)


def init_backend(platform=None):
    """Initialise the JAX backend in THIS process (a chip belongs to one
    process: no child probe). ``platform`` forces one (``--platform
    cpu``); otherwise the default backend must be an accelerator — a
    bench that quietly ran on the CPU would be read as a device number.
    Returns (jax, devices)."""
    import jax

    from vernemq_tpu.utils.compile_cache import configure_compile_cache

    if platform:
        jax.config.update("jax_platforms", platform)
    configure_compile_cache()
    devices = jax.devices()  # raises when the backend cannot initialise
    if not platform and devices[0].platform == "cpu":
        raise SystemExit(
            "[bench] no accelerator: the default JAX backend is the CPU. "
            "Pass --platform cpu for a smoke-scale CPU run.")
    note(f"[bench] backend: {devices[0].platform} "
         f"{devices[0].device_kind} x{len(devices)}")
    return jax, devices


# ---------------------------------------------------------------- corpora

def _stage_snapshot():
    """Snapshot the process-global stage histograms (observability) —
    the 'before' half of per-config attribution."""
    from vernemq_tpu.observability import histogram as hist

    return hist.snapshot_all()


def stage_breakdown(before):
    """Per-seam p50/p99/p99.9 of the observations made SINCE
    ``before`` (families with no new observations are omitted)."""
    from vernemq_tpu.observability import histogram as hist

    out = {}
    for name, after in hist.snapshot_all().items():
        delta = hist.diff(after, before.get(name, ([0] * len(after[0]),
                                                   0.0, 0)))
        if delta[2] <= 0:
            continue
        s = hist.summary(delta)
        out[name] = {k: (round(v, 4) if isinstance(v, float) else v)
                     for k, v in s.items()}
    return out


def events_during_drill(t0_mono):
    """Control-plane journal excerpt for a drill window: every event
    emitted since ``t0_mono`` as compact (t_rel_s, code, detail) rows —
    the fault/stall storm artifacts finally record WHAT the broker did
    (breaker opened at +0.8s, watchdog abandoned at +1.1s, recovery
    closed at +4.2s), not just the resulting percentiles."""
    from vernemq_tpu.observability import events as _events

    return [{"t_rel_s": round(e["t"] - t0_mono, 4), "code": e["code"],
             "detail": e["detail"], "value": e["value"]}
            for e in _events.journal().snapshot(since=t0_mono)]


def observability_overhead_probe(wb, reps=40):
    """The acceptance overhead guard: publish p50 through the
    PRODUCTION match path (TpuMatcher.match_batch — the seam the stage
    histograms + dispatch profiler instrument) with observability ON
    vs OFF, both recorded in the artifact. The guard requires the ON
    number within 2% of OFF."""
    from vernemq_tpu.observability import histogram as hist

    topics = zipf_topics(wb.rng, wb.pools, min(wb.batch, 512))
    wb.m.match_batch(topics)  # warm the shape once for both modes
    wb.m.match_batch(topics)
    # INTERLEAVED on/off reps: two sequential blocks would attribute
    # clock drift / cache-state luck to the flag — alternating pairs
    # measure only the flag's own cost. The WITHIN-pair order also
    # alternates: a fixed off-then-on order turns any monotonic drift
    # (thermal, a co-tenant waking up mid-run) into a systematic
    # pro-"on" bias — observed as a ±10% swing on identical code on a
    # busy smoke box — whereas alternating cancels it to first order
    lat_on, lat_off = [], []
    try:
        for i in range(reps):
            order = ((False, lat_off), (True, lat_on))
            for flag, sink in (order if i % 2 == 0 else order[::-1]):
                hist.set_enabled(flag)
                t0 = time.perf_counter()
                wb.m.match_batch(topics)
                sink.append((time.perf_counter() - t0) * 1e3)
    finally:
        hist.set_enabled(True)
    off = float(np.percentile(lat_off, 50))
    on = float(np.percentile(lat_on, 50))
    return {
        "publish_ms_p50_obs_off": round(off, 4),
        "publish_ms_p50_obs_on": round(on, 4),
        "overhead_pct": round((on - off) / off * 100.0, 3) if off else 0.0,
    }


def build_corpus(rng: random.Random, n_subs: int, table, shared_frac=0.0):
    """Mixed subscription corpus over a 3-level topic tree (BASELINE
    config 2/3 shape): words chosen so wildcard fanout is realistic.
    ``shared_frac`` marks that fraction as shared-subscription rows
    (config 4): value = (group, sid) like the registry's group rows."""
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
        val = ({"group": f"g{i % 97}"} if shared_frac
               and rng.random() < shared_frac else None)
        table.add(f, i, val)
    return l0, l1, l2


def zipf_topics(rng: random.Random, pools, n: int):
    l0, l1, l2 = pools
    def pick(pool):
        z = min(int(rng.paretovariate(1.2)) - 1, len(pool) - 1)
        return pool[z]
    return [(pick(l0), pick(l1), pick(l2)) for _ in range(n)]


def host_trie_like_for_like(table, pools, seed: int, n_probe: int = 5000):
    """Single-core host-trie numbers on the SAME corpus and probe
    distribution as the device run (VERDICT r4 item 2: the device must
    beat THIS, like-for-like — vmq_reg_trie_bench_SUITE.erl:97-214 is
    the reference-side analog). Separate rng so the device run's
    topic stream is untouched."""
    from vernemq_tpu.models.trie import SubscriptionTrie

    rng = random.Random(seed)
    trie = SubscriptionTrie()
    t0 = time.perf_counter()
    for e in table.entries:
        if e is not None:
            trie.add(list(e[0]), e[1], e[2])
    build_s = time.perf_counter() - t0
    probes = [list(t) for t in zipf_topics(rng, pools, n_probe)]
    # warm one pass (branch caches, interned strings)
    for t in probes[:200]:
        trie.match(t)
    t0 = time.perf_counter()
    total = 0
    for t in probes:
        total += len(trie.match(t))
    dt = time.perf_counter() - t0
    return {"trie_pubs_per_sec": round(n_probe / dt),
            "trie_matches_per_sec": round(total / dt),
            "trie_avg_fanout": round(total / n_probe, 2),
            "trie_build_s": round(build_s, 1)}


# ----------------------------------------------------- device-path driver

class WindowedBench:
    """Drives the production flat-compaction kernel exactly the way
    TpuMatcher._match_windowed does (same prepare_windows emit="sel" +
    match_extract_windowed_flat), with pipelined submission: encode/prep
    of batch i+1 overlaps the device on batch i, and every batch's FULL
    result (flat ids + prefixes + totals + overflow) is pulled to host —
    the honest production round trip, overlapped ``depth`` batches deep."""

    def __init__(self, jax, table, pools, rng, batch, max_fanout=256,
                 flat_avg=128, depth=3, variant="flat"):
        from vernemq_tpu.models.tpu_matcher import TpuMatcher

        self.jax = jax
        self.rng = rng
        self.pools = pools
        self.batch = batch
        self.depth = depth
        self.variant = variant  # "flat" (scatter buffer) | "rows" (gather)
        self.m = TpuMatcher(max_levels=table.L, initial_capacity=16,
                            max_fanout=max_fanout, flat_avg=flat_avg)
        # the bench times raw sync/delta costs with direct sync() calls;
        # a surprise async rebuild would turn those into RebuildInProgress
        # (production serves the trie through that window — covered by
        # tests, not timed here)
        self.m.async_rebuild = False
        self.m.table = table
        table.resized = True  # force first full upload for this matcher
        t0 = time.perf_counter()
        with self.m.lock:
            self.m.sync()
        self.jax.block_until_ready(self.m._operands)
        self.upload_s = time.perf_counter() - t0
        assert self.m._bucketed and self.m._operands is not None, \
            "bench requires the bucketed windowed path"
        if variant == "pallas":
            # same alignment gate as TpuMatcher._match_windowed: the
            # Pallas block index maps truncate starts to SEG_BLK units,
            # so an unaligned (small-bucketed) table would yield shifted
            # slot ids with no error
            S = int(self.m._dev_arrays[0].shape[0])
            assert (S % 2048 == 0 and self.m._glob_pad % 2048 == 0
                    and self.m._gb_end % 2048 == 0), \
                "pallas variant requires a 2048-aligned table layout"

    def _prep(self, topics):
        """The exact production host prep (TpuMatcher._flat_prep), with
        encode/prep timed separately."""
        m = self.m
        t0 = time.perf_counter()
        pw, pl, pd, pb, gb = m._encode_batch_ex(topics)
        t1 = time.perf_counter()
        S = int(m._dev_arrays[0].shape[0])
        args, statics, left = m._flat_prep(
            m._reg_start, m._reg_end, m._glob_pad, m._ops_bits, S,
            pw, pl, pd, pb, gb, len(topics),
            align=2048 if self.variant == "pallas" else 0)
        t2 = time.perf_counter()
        return args, statics, t1 - t0, t2 - t1, len(left)

    def submit(self, prep):
        """Dispatch ONE device call; returns device refs WITHOUT sync."""
        from vernemq_tpu.ops import match_kernel as K

        m = self.m
        args, statics, _, _, _ = prep
        F_t, t1 = m._operands
        if self.variant == "packed":
            return K.call_packed(F_t, t1, m._meta, args, statics)
        if self.variant == "packed_rows":
            return K.call_packed_rows(F_t, t1, m._meta, args, statics)
        head = (F_t, t1, m._dev_arrays[1], m._dev_arrays[2],
                m._dev_arrays[3], m._dev_arrays[4])
        if self.variant == "rows":
            st = dict(statics)
            st["kf"] = st.pop("C") // args[0].shape[0]  # same bytes as flat
            return K.match_extract_windowed_rows(*head, *args, **st)
        if self.variant == "pallas":
            from vernemq_tpu.ops import pallas_match as P

            return P.match_extract_windowed_flat_pallas(
                *head, *args, **statics, interpret=P.use_interpret())
        return K.match_extract_windowed_flat(*head, *args, **statics)

    def run_kernel_only(self, n_stack=8, reps=6):
        """Device-resident kernel throughput: stage ``n_stack`` packed
        batches in HBM, run them inside ONE executable (match_packed_scan)
        ``reps`` times, pull only a checksum. Measures what the chip
        sustains with zero per-batch transport. Packed variant only."""
        import jax as _jax

        from vernemq_tpu.ops import match_kernel as K

        assert self.variant == "packed"
        m = self.m
        F_t, t1 = m._operands
        preps = [self._prep(zipf_topics(self.rng, self.pools, self.batch))
                 for _ in range(n_stack)]
        statics = preps[0][1]
        vecs = np.stack([K.flat_pack_args(p[0]) for p in preps])
        stack = _jax.device_put(vecs, m.device)
        B, L = preps[0][0][0].shape
        T, TP = preps[0][0][4].shape
        T2 = preps[0][0][6].shape[0]
        total_matches = None
        run1 = lambda: K.match_packed_scan(
            F_t, t1, m._meta, stack, B=B, L=L, T=T, TP=TP, T2=T2,
            **statics)
        for _ in range(3):  # compile + executable warm
            chk, tot = run1()
            total_matches = int(np.asarray(tot))
        t0 = time.perf_counter()
        for _ in range(reps):
            chk, tot = run1()
        np.asarray(chk)  # honest sync: one scalar pull after the clock
        np.asarray(tot)
        elapsed = time.perf_counter() - t0
        batches = n_stack * reps
        return {
            "kernel_batch_ms": round(elapsed / batches * 1e3, 3),
            "kernel_matches_per_sec": round(
                total_matches * reps / elapsed),
            "kernel_publishes_per_sec": round(self.batch * batches / elapsed),
            "staged_batches": n_stack,
        }

    def run_stacked(self, iters, n_stack=8, warmup=1):
        """Stacked transport (ROOFLINE dispatch-amortisation mode):
        groups of ``n_stack`` packed batches ride ONE executable and ONE
        result pull (K.call_packed_stack), amortising the two
        per-dispatch round trips; every result byte still reaches the
        host (production-honest). Depth-2 group pipelining overlaps the
        next group's host prep with the device/transport."""
        from vernemq_tpu.ops import match_kernel as K

        assert self.variant == "packed"
        m = self.m
        F_t, t1 = m._operands
        topics_batches = [zipf_topics(self.rng, self.pools, self.batch)
                          for _ in range(8)]
        enc_ms = prep_ms = 0.0
        leftover_total = 0

        def make_group(g, count):
            nonlocal enc_ms, prep_ms, leftover_total
            preps = []
            for i in range(n_stack):
                args, st, te, tp, left = self._prep(
                    topics_batches[(g * n_stack + i) % len(topics_batches)])
                if count:  # warmup prep stays out of the reported means
                    enc_ms += te
                    prep_ms += tp
                    leftover_total += left
                preps.append(args)
            return preps, st

        # statics/Bpad from one uncounted prep (valid even at warmup=0)
        (first, statics) = make_group(0, count=False)
        Bpad = first[0][0].shape[0]
        for w in range(warmup):  # compile + executable warm
            out = K.call_packed_stack(F_t, t1, m._meta, first, statics)
            np.asarray(out)

        def pull(out):
            o = np.asarray(out)  # ONE [N, C+3B] transfer per group
            C = Bpad * self.m.flat_avg
            tm = ov = 0
            for r in o:
                _, _, tot, ovf = K.unpack_flat_result(r, Bpad, C)
                tm += int(tot.sum(dtype=np.int64))
                ov += int(ovf.sum())
            return tm, ov

        groups = max(2, iters // n_stack)
        total_matches = overflow_pubs = 0
        inflight = []
        t_start = time.perf_counter()
        for g in range(groups):
            preps, _ = make_group(g, count=True)
            inflight.append(
                K.call_packed_stack(F_t, t1, m._meta, preps, statics))
            if len(inflight) >= 2:
                tm, ov = pull(inflight.pop(0))
                total_matches += tm
                overflow_pubs += ov
        for out in inflight:
            tm, ov = pull(out)
            total_matches += tm
            overflow_pubs += ov
        elapsed = time.perf_counter() - t_start
        batches = groups * n_stack
        n = batches
        return {
            "matches_per_sec": total_matches / elapsed,
            "publishes_per_sec": self.batch * batches / elapsed,
            "avg_fanout": total_matches / (self.batch * batches),
            "batch_ms": elapsed / batches * 1e3,
            "group_ms": elapsed / groups * 1e3,
            "n_stack": n_stack,
            "encode_ms": enc_ms / n * 1e3,
            "prep_ms": prep_ms / n * 1e3,
            "leftover_pubs": leftover_total,
            "overflow_pubs": overflow_pubs,
            "upload_s": round(self.upload_s, 3),
        }

    def run(self, iters, warmup=6, measure_resolve=True):
        from vernemq_tpu.ops import match_kernel as K

        topics_batches = [zipf_topics(self.rng, self.pools, self.batch)
                          for _ in range(min(iters, 8))]
        # warmup: compile + first-run executable warm (first executions on
        # this runtime are ~10x slower than steady state — measured)
        enc_ms = prep_ms = 0.0
        for i in range(warmup):
            p = self._prep(topics_batches[i % len(topics_batches)])
            out = self.submit(p)
            np.asarray(out[0])

        def pull(out):
            # the production round trip: every result array to host
            if self.variant == "packed":
                o = np.asarray(out)          # ONE transfer
                Bpad = (o.size // (self.m.flat_avg + 3))
                _, _, total, ovf = K.unpack_flat_result(
                    o, Bpad, Bpad * self.m.flat_avg)
                return int(total.sum(dtype=np.int64)), int(ovf.sum())
            if self.variant == "packed_rows":
                o = np.asarray(out)          # ONE transfer
                Bpad = (o.size // (self.m.flat_avg + 2))
                _, total, ovf = K.unpack_rows_result(
                    o, Bpad, self.m.flat_avg)
                return int(total.sum(dtype=np.int64)), int(ovf.sum())
            if self.variant == "rows":
                np.asarray(out[0])
                total = np.asarray(out[1])
                ovf = np.asarray(out[2])
            else:
                np.asarray(out[0])
                np.asarray(out[1])
                total = np.asarray(out[2])
                ovf = np.asarray(out[3])
            return int(total.sum(dtype=np.int64)), int(ovf.sum())

        leftover_total = 0
        total_matches = 0
        overflow_pubs = 0
        inflight = []
        t_start = time.perf_counter()
        for i in range(iters):
            p = self._prep(topics_batches[i % len(topics_batches)])
            enc_ms += p[2]
            prep_ms += p[3]
            leftover_total += p[4]
            inflight.append(self.submit(p))
            if len(inflight) >= self.depth:
                tm, ov = pull(inflight.pop(0))
                total_matches += tm
                overflow_pubs += ov
        for out in inflight:
            tm, ov = pull(out)
            total_matches += tm
            overflow_pubs += ov
        elapsed = time.perf_counter() - t_start

        # synced round-trip latency (see module doc)
        lat = []
        for i in range(min(6, iters)):
            p = self._prep(topics_batches[i % len(topics_batches)])
            t1 = time.perf_counter()
            pull(self.submit(p))
            lat.append(time.perf_counter() - t1)

        resolve_ms = None
        if measure_resolve:
            t1 = time.perf_counter()
            self.m.match_batch(topics_batches[0])
            resolve_ms = (time.perf_counter() - t1) * 1e3

        n = iters
        return {
            "matches_per_sec": total_matches / elapsed,
            "publishes_per_sec": self.batch * iters / elapsed,
            "avg_fanout": total_matches / (self.batch * iters),
            "batch_ms": elapsed / iters * 1e3,
            "encode_ms": enc_ms / n * 1e3,
            "prep_ms": prep_ms / n * 1e3,
            "synced_batch_ms_p50": 1e3 * float(np.percentile(lat, 50)),
            "synced_batch_ms_p99": 1e3 * float(np.percentile(lat, 99)),
            "full_path_batch_ms": resolve_ms,
            "leftover_pubs": leftover_total,
            "overflow_pubs": overflow_pubs,
            "upload_s": round(self.upload_s, 3),
        }


def match_many_probe(wb: "WindowedBench", ks=(1, 2, 4, 8, 16), reps=2,
                     probe_batch=None):
    """Kernel-resident multi-batch dispatch probe — the amortization
    number the round-5 VERDICT says was never measured. For each K in
    ``ks``: prep K same-geometry publish batches, stage them as ONE
    stacked transport block and run all K inside ONE scanned executable
    with donated staging (``K.call_match_many``), timing the full synced
    round trip W(K). Fitting W(K) = dispatch + K·batch_cost (least
    squares over the ladder) splits the fixed per-dispatch overhead
    (transport round trips + executable launch) from the per-batch kernel cost; ``amortized_dispatch_ms[K]
    = dispatch/K`` is the ROOFLINE.md amortization model, measured.

    ``probe_batch`` overrides the per-batch publish count (smoke runs
    use a smaller batch so the ladder stays fast); geometry is still the
    exact production prep for that batch size."""
    import time as _time

    from vernemq_tpu.ops import match_kernel as K

    m = wb.m
    F_t, t1 = m._operands
    n = probe_batch or wb.batch
    walls = {}
    for k in ks:
        full = [wb._prep(zipf_topics(wb.rng, wb.pools, n))
                for _ in range(k)]
        preps = [f[0] for f in full]
        statics = full[0][1]
        # compile + executable warm (scan length is part of the shape)
        np.asarray(K.call_match_many(F_t, t1, m._meta, preps, statics))
        best = float("inf")
        for _ in range(max(1, reps)):
            t0 = _time.perf_counter()
            out = K.call_match_many(F_t, t1, m._meta, preps, statics)
            np.asarray(out)  # honest sync: every result byte to host
            best = min(best, _time.perf_counter() - t0)
        walls[k] = best * 1e3
    # least-squares fit W(K) = a + b*K (ms): a = per-dispatch overhead
    xs = np.asarray(list(ks), dtype=np.float64)
    ys = np.asarray([walls[k] for k in ks], dtype=np.float64)
    A = np.vstack([np.ones_like(xs), xs]).T
    (a, b), *_ = np.linalg.lstsq(A, ys, rcond=None)
    a = max(float(a), 0.0)
    return {
        "ks": list(ks),
        "probe_batch": n,
        "super_batch_ms": {str(k): round(walls[k], 3) for k in ks},
        "per_batch_ms": {str(k): round(walls[k] / k, 3) for k in ks},
        "dispatch_ms_fit": round(a, 3),
        "kernel_batch_ms_fit": round(float(b), 3),
        "amortized_dispatch_ms": {str(k): round(a / k, 4) for k in ks},
    }


# ------------------------------------------------------------- the ladder

def config1_host_trie(rng):
    """1k subs, exact topics, host trie — the reference's own data
    structure shape (vmq_reg_trie_bench_SUITE ladder bottom)."""
    from vernemq_tpu.models.trie import SubscriptionTrie

    trie = SubscriptionTrie()
    topics = []
    for i in range(1000):
        t = [f"a{i % 50}", f"b{i % 20}", f"c{i}"]
        trie.add(t, i, None)
        topics.append(tuple(t))
    probe = [list(rng.choice(topics)) for _ in range(5000)]
    t0 = time.perf_counter()
    total = 0
    for t in probe:
        total += len(trie.match(t))
    dt = time.perf_counter() - t0
    return {"matches_per_sec": round(total / dt),
            "lookups_per_sec": round(len(probe) / dt)}


def config4_shared_retained(jax, rng, table, pools, batch, bench_stats):
    """Config 4 add-ons at 1M subs: shared-subscription group select on
    top of match results + retained replay on subscribe."""
    from vernemq_tpu.broker.retain import RetainStore

    # group-select: post-match policy pick over group rows (the
    # vmq_shared_subscriptions.erl:26-63 member choice, host-side)
    groups: dict = {}
    for e in table.entries:
        if e is not None and isinstance(e[2], dict) and "group" in e[2]:
            groups.setdefault(e[2]["group"], []).append(e[1])
    t0 = time.perf_counter()
    picks = 0
    for g, members in groups.items():
        for _ in range(3):
            rng.choice(members)
            picks += 1
    gs_dt = time.perf_counter() - t0

    retain = RetainStore()
    l0, l1, l2 = pools
    for i in range(100_000):
        retain.insert("", (rng.choice(l0), rng.choice(l1), rng.choice(l2)),
                      b"x" * 16)
    # wildcard replay on subscribe (vmq_retain_srv:match_fold)
    t0 = time.perf_counter()
    replayed = 0
    n_subs_ops = 300
    for i in range(n_subs_ops):
        fw = [rng.choice(l0), "+", rng.choice(l2)]
        replayed += sum(1 for _ in retain.match_filter("", fw))
    rp_dt = time.perf_counter() - t0
    return {
        "match_matches_per_sec": round(bench_stats["matches_per_sec"]),
        "shared_group_count": len(groups),
        "group_selects_per_sec": round(picks / max(gs_dt, 1e-9)),
        "retained_msgs": 100_000,
        "retained_replay_subscribes_per_sec": round(n_subs_ops / rp_dt),
        "retained_replayed_per_sec": round(replayed / rp_dt),
    }


def config6_fault_storm(jax_mod, rng, n_subs, batch, smoke):
    """Robustness config: publish service through a device outage.

    Three phases against one bucketed matcher + an exact trie oracle:
    healthy (device path), storm (persistent injected device-dispatch
    faults — the breaker opens and every batch serves from the host
    trie, parity-checked), recovery (faults cleared — time until the
    half-open probe closes the breaker and the device path serves
    again). Reports per-publish p50/p99 in each mode and the
    recovery time; `parity_ok` asserts ZERO wrong fanouts while
    degraded."""
    from vernemq_tpu.models.tpu_matcher import DeviceDegraded, TpuMatcher
    from vernemq_tpu.models.trie import SubscriptionTrie
    from vernemq_tpu.robustness import faults
    from vernemq_tpu.robustness.breaker import CircuitBreaker

    n = min(n_subs, 50_000) if smoke else min(n_subs, 500_000)
    m = TpuMatcher(max_levels=8,
                   initial_capacity=1 << (n - 1).bit_length())
    m.breaker = CircuitBreaker(failure_threshold=3, backoff_initial=0.05,
                               backoff_max=0.4, name="match")
    trie = SubscriptionTrie()
    for i in range(n):
        f = [f"r{i % 64}", f"d{i % 257}",
             "+" if i % 11 == 0 else f"m{i % 31}"]
        m.table.add(f, i, None)
        trie.add(list(f), i, None)

    def mk_topics(b):
        return [(f"r{rng.randrange(64)}", f"d{rng.randrange(257)}",
                 f"m{rng.randrange(31)}") for _ in range(b)]

    def norm(rows):
        return sorted((tuple(f), k) for f, k, _ in rows)

    b = min(batch, 256)
    iters = 8 if smoke else 30
    m.match_batch(mk_topics(b))  # build + warm the shape

    def run_phase(check_parity=False):
        lats = []
        bad = 0
        for _ in range(iters):
            topics = mk_topics(b)
            t0 = time.perf_counter()
            try:
                got = m.match_batch(topics)
            except DeviceDegraded:
                # the production degraded path: exact host-trie service
                got = [trie.match(list(t)) for t in topics]
            lats.append((time.perf_counter() - t0) / b)
            if check_parity:
                for t, rows in zip(topics, got):
                    if norm(rows) != norm(trie.match(list(t))):
                        bad += 1
        lats.sort()
        return lats, bad

    healthy, _ = run_phase()
    t_drill = time.monotonic()
    faults.install(faults.FaultPlan(
        [faults.FaultRule("device.*", kind="error")], seed=1))
    degraded, bad = run_phase(check_parity=True)
    storm_state = m.breaker.state_name

    faults.clear()
    t0 = time.perf_counter()
    recovery_s = None
    deadline = t0 + 30.0
    while time.perf_counter() < deadline:
        try:
            m.match_batch(mk_topics(b))
        except DeviceDegraded:
            pass
        if m.breaker.state_name == "closed":
            recovery_s = time.perf_counter() - t0
            break
        time.sleep(0.02)
    post, _ = run_phase()

    def pct(lats, q):
        return round(lats[min(len(lats) - 1, int(q * len(lats)))] * 1e6, 2)

    return {
        "subs": n, "batch": b,
        "healthy_publish_us_p50": pct(healthy, 0.50),
        "healthy_publish_us_p99": pct(healthy, 0.99),
        "degraded_publish_us_p50": pct(degraded, 0.50),
        "degraded_publish_us_p99": pct(degraded, 0.99),
        "post_recovery_publish_us_p99": pct(post, 0.99),
        "breaker_state_during_storm": storm_state,
        "device_failures": m.device_failures,
        "degraded_sheds": m.degraded_sheds,
        "parity_ok": bad == 0,
        "device_recovery_s": (round(recovery_s, 3)
                              if recovery_s is not None else None),
        # what the broker DID during the drill (breaker transitions on
        # this matcher's journal, time-relative to fault install)
        "events_during_drill": events_during_drill(t_drill),
    }


def config7_partition_storm(smoke):
    """Robustness config: cross-node QoS1 delivery through a partition.

    Two in-process brokers on the real framed cluster channel, a QoS 1
    subscriber on node B, a publisher on node A. Phases: healthy
    (publish→receive latency), storm (the inter-node link severed for
    ``storm_s`` via the ``cluster.recv`` fault point under continued
    publish load — QoS≥1 frames journal in the delivery spool), heal
    (faults cleared — the spool replays). Reports the degraded publish
    p99, post-heal replay throughput, and ``parity_ok``: every message
    delivered, none twice (the dedup window's exactly-once check)."""
    import asyncio
    import tempfile

    async def run():
        from vernemq_tpu.broker.config import Config
        from vernemq_tpu.broker.server import start_broker
        from vernemq_tpu.client import MQTTClient
        from vernemq_tpu.cluster import Cluster
        from vernemq_tpu.robustness import faults

        n_healthy = 50 if smoke else 200
        n_storm = 100 if smoke else 500
        storm_s = 1.5 if smoke else 5.0
        tmp = tempfile.mkdtemp(prefix="vmq-spool-bench-")
        nodes = []
        for i in range(2):
            cfg = Config(systree_enabled=False, allow_anonymous=True,
                         allow_publish_during_netsplit=True,
                         cluster_spool_dir=f"{tmp}/node{i}",
                         cluster_spool_retransmit_ms=100,
                         cluster_spool_ack_interval=20)
            broker, server = await start_broker(cfg, port=0,
                                                node_name=f"node{i}")
            broker.node_name = broker.metadata.node_name = f"node{i}"
            broker.registry.node_name = f"node{i}"
            broker.registry.db.node_name = f"node{i}"
            cluster = Cluster(broker, "127.0.0.1", 0)
            await cluster.start()
            nodes.append((broker, server, cluster))
        a, b = nodes
        b[2].join(a[2].listen_host, a[2].listen_port)
        while not (len(a[2].members()) == 2 and a[2].is_ready()
                   and b[2].is_ready()):
            await asyncio.sleep(0.02)

        sub = MQTTClient("127.0.0.1", b[1].port, client_id="storm-sub")
        await sub.connect()
        await sub.subscribe("storm/#", qos=1)
        while len(a[0].registry.trie("").match(["storm", "x"])) != 1:
            await asyncio.sleep(0.02)
        pub = MQTTClient("127.0.0.1", a[1].port, client_id="storm-pub")
        await pub.connect()

        async def publish_n(n, start, lats):
            for i in range(start, start + n):
                t0 = time.perf_counter()
                await pub.publish(f"storm/{i}", b"m%d" % i, qos=1)
                lats.append(time.perf_counter() - t0)

        healthy_lat, storm_lat = [], []
        await publish_n(n_healthy, 0, healthy_lat)
        for _ in range(n_healthy):
            await sub.recv(5)

        # storm: sever the inter-node data plane (inbound batches drop
        # on both nodes — frames AND acks) while publishing continues
        faults.install(faults.FaultPlan(
            [faults.FaultRule("cluster.recv", kind="error")], seed=7))
        storm_t0 = time.perf_counter()
        await publish_n(n_storm, n_healthy, storm_lat)
        while time.perf_counter() - storm_t0 < storm_s:
            await asyncio.sleep(0.05)
        spool_depth = a[0].metrics.all_metrics().get(
            "cluster_spool_depth_frames", 0)

        # heal: the retransmit watchdog replays the journaled backlog
        faults.clear()
        heal_t0 = time.perf_counter()
        got = {}
        while len(got) < n_storm and time.perf_counter() - heal_t0 < 30:
            try:
                m = await sub.recv(5)
            except asyncio.TimeoutError:
                break
            got[m.payload] = got.get(m.payload, 0) + 1
        drain_s = time.perf_counter() - heal_t0
        # quiet-period drain: trailing duplicate deliveries still in
        # flight must land in the dupe count or parity_ok lies
        while True:
            try:
                m = await sub.recv(0.5)
            except asyncio.TimeoutError:
                break
            got[m.payload] = got.get(m.payload, 0) + 1
        replayed = a[0].metrics.value("cluster_spool_replayed")
        deduped = b[0].metrics.value("cluster_spool_deduped")
        # which engine served the journal (native kvstore / segment-log
        # fallback / memory): replay-throughput numbers are only
        # comparable across boxes with this recorded
        journal_engine = getattr(getattr(a[2], "spool", None),
                                 "engine_kind", "memory")

        await sub.disconnect()
        await pub.disconnect()
        for broker, server, cluster in nodes:
            await cluster.stop()
            await broker.stop()
            await server.stop()

        expect = {b"m%d" % i for i in range(n_healthy, n_healthy + n_storm)}
        missing = len(expect - set(got))
        dupes = sum(c - 1 for c in got.values())

        def pct(lats, q):
            lats = sorted(lats)
            return round(lats[min(len(lats) - 1, int(q * len(lats)))] * 1e3,
                         3)

        return {
            "storm_publishes": n_storm, "storm_s": storm_s,
            "journal_engine": journal_engine,
            "healthy_publish_ms_p50": pct(healthy_lat, 0.50),
            "healthy_publish_ms_p99": pct(healthy_lat, 0.99),
            "degraded_publish_ms_p50": pct(storm_lat, 0.50),
            "degraded_publish_ms_p99": pct(storm_lat, 0.99),
            "spool_depth_at_heal": int(spool_depth),
            "replayed_frames": replayed,
            "deduped_frames": deduped,
            "replay_drain_s": round(drain_s, 3),
            "replay_msgs_per_sec": round(len(got) / max(drain_s, 1e-9)),
            "missing": missing, "duplicates": dupes,
            "parity_ok": missing == 0 and dupes == 0,
        }

    return asyncio.run(run())


def config8_retained_storm(rng, smoke, n_retained=None, batch=None,
                           iters=None, n_host=None):
    """Retained subscribe storm: wildcard SUBSCRIBE bursts against a
    large retained set, device reverse-match vs the serial host walk.

    Builds one RetainStore + one RetainedIndex (write-through, exactly
    the production wiring), measures the host-walk replay rate
    (``RetainStore.match_filter`` per subscribe — the config-4 serial
    path), then batched device replay throughput over the same filter
    distribution (80% concrete-first single-``+``, 10% trailing-``#``,
    10% wildcard-first — the dense-phase stressor). ``parity_ok``
    asserts the device results are bit-identical to the host oracle on a
    sample; per-filter device escapes resolve against the store exactly
    like the production collector. A final phase injects a persistent
    ``device.retained`` outage and verifies replays degrade to the host
    walk with zero wrong results (graceful-fallback acceptance)."""
    from vernemq_tpu.broker.retain import RetainStore
    from vernemq_tpu.models.tpu_matcher import DeviceDegraded
    from vernemq_tpu.retained.index import RetainedIndex
    from vernemq_tpu.robustness import faults
    from vernemq_tpu.robustness.breaker import CircuitBreaker

    n_ret = n_retained or (100_000 if smoke else 1_000_000)
    b = batch or (2048 if smoke else 4096)
    reps = iters or (6 if smoke else 20)
    n_host = n_host or (300 if smoke else 500)
    l0 = [f"r{i}" for i in range(64)]
    l1 = [f"d{i}" for i in range(256)]
    l2 = [f"m{i}" for i in range(64)]

    store = RetainStore()
    idx = RetainedIndex(store, max_levels=8,
                        initial_capacity=1 << (n_ret - 1).bit_length(),
                        max_fanout=256)
    idx.async_rebuild = False  # bench times the inline build, like cfg 3
    idx.breaker = CircuitBreaker(failure_threshold=3, backoff_initial=0.05,
                                 backoff_max=0.4)
    t0 = time.perf_counter()
    for i in range(n_ret):
        t = (rng.choice(l0), rng.choice(l1), rng.choice(l2))
        store.insert("", t, b"x" * 16)
        idx.on_retain(t, b"x" * 16)
    build_s = time.perf_counter() - t0

    def mk_filters(n):
        # storm mix: concrete-first single-'+' dominates (the config-4
        # shape), trailing-'#' prefixes ride the same probe windows,
        # wildcard-first filters exercise the dense phase (device on
        # accelerators; host-routed on cpu — see RetainedIndex.dense_policy)
        out = []
        for _ in range(n):
            r = rng.random()
            if r < 0.85:
                out.append((rng.choice(l0), "+", rng.choice(l2)))
            elif r < 0.95:
                out.append((rng.choice(l0), rng.choice(l1), "#"))
            else:
                out.append(("+", rng.choice(l1), rng.choice(l2)))
        return out

    # serial host walk (the config-4 path: one match_filter per subscribe)
    host_filters = mk_filters(n_host)
    t0 = time.perf_counter()
    host_replayed = 0
    for fw in host_filters:
        host_replayed += len(store.match_filter("", list(fw)))
    host_dt = time.perf_counter() - t0

    def norm(rows):
        return sorted((t, v) for t, v in rows)

    def run_batch(filters):
        """Production contract: device dispatch, per-filter escapes
        resolved against the store (what the collector does)."""
        res = idx.match_filters(filters)
        fallbacks = 0
        out = []
        for fw, rows in zip(filters, res):
            if rows is None:
                fallbacks += 1
                rows = store.match_filter("", list(fw))
            out.append(rows)
        return out, fallbacks

    batches = [mk_filters(b) for _ in range(min(reps, 6))]
    run_batch(batches[0])  # build + compile warm
    run_batch(batches[0])
    t0 = time.perf_counter()
    replayed = fallbacks = 0
    for i in range(reps):
        out, fb = run_batch(batches[i % len(batches)])
        replayed += sum(len(r) for r in out)
        fallbacks += fb
    dev_dt = time.perf_counter() - t0
    dev_rate = b * reps / dev_dt

    # parity: device vs the host oracle on one fresh batch
    parity_filters = mk_filters(min(b, 512))
    out, _fb = run_batch(parity_filters)
    bad = sum(1 for fw, rows in zip(parity_filters, out)
              if norm(rows) != norm(store.match_filter("", list(fw))))

    # graceful fallback under an injected device.retained outage
    faults.install(faults.FaultPlan(
        [faults.FaultRule("device.retained", kind="error")], seed=8))
    degraded_bad = 0
    for fw in parity_filters[:64]:
        try:
            rows = idx.match_filters([fw])[0]
            if rows is None:
                rows = store.match_filter("", list(fw))
        except DeviceDegraded:
            rows = store.match_filter("", list(fw))  # the production path
        if norm(rows) != norm(store.match_filter("", list(fw))):
            degraded_bad += 1
    breaker_state = idx.breaker.state_name
    faults.clear()

    host_rate = n_host / host_dt
    return {
        "retained_msgs": len(store),
        "batch": b,
        "build_s": round(build_s, 2),
        "retained_replay_subscribes_per_sec": round(dev_rate),
        "retained_replayed_per_sec": round(replayed / dev_dt),
        "host_replay_subscribes_per_sec": round(host_rate),
        "host_replayed_per_sec": round(host_replayed / host_dt),
        "speedup_vs_host_walk": round(dev_rate / host_rate, 2),
        "host_fallback_filters": fallbacks,
        "dispatches": idx.match_dispatches,
        "parity_ok": bad == 0 and degraded_bad == 0,
        "breaker_state_during_storm": breaker_state,
        "degraded_sheds": idx.degraded_sheds,
    }


def config10_stall_storm(smoke):
    """Stall storm: SILENT hangs (wedge faults — no exception, the call
    just never returns) at device.dispatch and cluster.recv under load.

    Segment A (device): a full broker on the tpu reg view with wedges
    injected at every device dispatch. Pre-watchdog this was an
    unbounded stall — the matcher's executor call never returned, the
    collector slot wedged forever, publishes queued without limit. With
    the deadline watchdog, every publish is answered by the exact host
    trie within `watchdog_dispatch_deadline_ms` + the collector-expiry
    ε: the bench asserts the storm p99 stays under that bound
    (`p99_bounded`), that fanouts are bit-exact with zero duplicates
    through abandon/late-discard (`parity_ok`), that the breaker opens,
    and that clearing the faults recovers the device path without a
    restart (`device_recovery_s`).

    Segment B (cluster): a half-open peer — inbound frames AND acks
    dropped via cluster.recv while the TCP channel stays up, so no
    exception ever fires. The ack-progress stall detector cycles the
    channel (`stall_reconnects`); on heal the spool replays with zero
    QoS1 loss (`cluster_zero_loss`)."""
    import asyncio
    import tempfile

    deadline_ms = 300.0
    expiry_budgets = 4
    budget_ms = 50.0

    async def device_segment():
        from vernemq_tpu.broker.config import Config
        from vernemq_tpu.broker.server import start_broker
        from vernemq_tpu.client import MQTTClient
        from vernemq_tpu.robustness import faults

        n_storm = 12 if smoke else 60
        cfg = Config(
            allow_anonymous=True, systree_enabled=False,
            default_reg_view="tpu", tpu_host_batch_threshold=0,
            tpu_lock_busy_shed_ms=0,
            watchdog_tick_ms=20,
            watchdog_dispatch_deadline_ms=deadline_ms,
            watchdog_collector_expiry_budgets=expiry_budgets,
            overload_dispatch_budget_ms=budget_ms,
            tpu_breaker_failure_threshold=2,
            tpu_breaker_backoff_initial_ms=50,
            tpu_breaker_backoff_max_ms=200)
        broker, server = await start_broker(cfg, port=0,
                                            node_name="stall-bench")
        sub = MQTTClient("127.0.0.1", server.port, client_id="st-sub")
        await sub.connect()
        await sub.subscribe("sb/+/t", qos=1)
        await sub.subscribe("sb/#", qos=1)
        pub = MQTTClient("127.0.0.1", server.port, client_id="st-pub")
        await pub.connect()

        # warm the device path first: with the cold-compile gate off
        # (lock_busy_shed_ms=0) the first dispatch carries the XLA
        # compile, which the deadline rightly abandons — the storm must
        # wedge WARM dispatches or it measures the cold abandon instead
        matcher = broker.registry.reg_view("tpu").matcher("")
        warm_deadline = time.perf_counter() + 120
        seq = 0
        while (matcher.match_batches == 0
               or matcher.breaker.state_name != "closed"):
            if time.perf_counter() > warm_deadline:
                break
            await pub.publish("sb/w/t", b"w%d" % seq, qos=0)
            for _ in range(2):
                try:
                    await sub.recv(2)
                except asyncio.TimeoutError:
                    break
            seq += 1
            await asyncio.sleep(0.05)
        healthy_lat = []
        for i in range(8):
            t0 = time.perf_counter()
            await pub.publish(f"sb/h{i}/t", b"h%d" % i, qos=1, timeout=30)
            healthy_lat.append(time.perf_counter() - t0)
        for _ in range(16):
            await sub.recv(10)

        # the storm: EVERY device dispatch wedges (probability 1); the
        # breaker gate bounds how many dispatches actually block —
        # after it opens the trie serves directly
        t_drill = time.monotonic()
        faults.install(faults.FaultPlan(
            [faults.FaultRule("device.dispatch", kind="wedge")], seed=10))
        storm_lat = []
        got = {}
        for i in range(n_storm):
            t0 = time.perf_counter()
            await pub.publish(f"sb/{i}/t", b"s%d" % i, qos=1, timeout=30)
            storm_lat.append(time.perf_counter() - t0)
            await asyncio.sleep(0.005)
        deadline_drain = time.perf_counter() + 30
        while (sum(got.values()) < 2 * n_storm
               and time.perf_counter() < deadline_drain):
            try:
                m = await sub.recv(2)
            except asyncio.TimeoutError:
                break
            if m.payload.startswith(b"s"):
                got[m.payload] = got.get(m.payload, 0) + 1
        breaker_during = matcher.breaker.state_name
        wedged = faults.active().status()["wedged"]

        # recovery: release the wedges, probes close the breaker
        faults.clear()
        rec_t0 = time.perf_counter()
        recovery_s = None
        seq = 0
        while time.perf_counter() - rec_t0 < 30:
            await pub.publish(f"sb/r{seq}/t", b"r", qos=0)
            seq += 1
            if matcher.breaker.state_name == "closed":
                recovery_s = time.perf_counter() - rec_t0
                break
            await asyncio.sleep(0.05)
        # quiet drain so trailing duplicates (there must be none from
        # abandoned dispatches) land in the counts
        while True:
            try:
                m = await sub.recv(0.5)
            except asyncio.TimeoutError:
                break
            if m.payload.startswith(b"s"):
                got[m.payload] = got.get(m.payload, 0) + 1

        wd = broker.watchdog.stats()
        col = broker.batch_collector()
        out_dev = {
            "storm_publishes": n_storm,
            "wedges_engaged": int(wedged),
            "stalls": int(wd["watchdog_stalls"]),
            "abandoned": int(wd["watchdog_abandoned"]),
            "late_discarded": int(wd["watchdog_late_discarded"]),
            "stalled_host_pubs": col.stalled_host_pubs,
            "expired_host_pubs": col.expired_host_pubs,
            "breaker_state_during_storm": breaker_during,
            "got": got,
            "healthy_lat": healthy_lat, "storm_lat": storm_lat,
            "device_recovery_s": (round(recovery_s, 3)
                                  if recovery_s is not None else None),
            # the stall storm's control-plane timeline: stall →
            # abandon → breaker open → late discard → probe → close,
            # time-relative to wedge install
            "events_during_drill": events_during_drill(t_drill),
        }
        await sub.close()
        await pub.close()
        await broker.stop()
        await server.stop()
        return out_dev

    async def cluster_segment():
        from vernemq_tpu.broker.config import Config
        from vernemq_tpu.broker.server import start_broker
        from vernemq_tpu.client import MQTTClient
        from vernemq_tpu.cluster import Cluster
        from vernemq_tpu.robustness import faults

        n_msgs = 8 if smoke else 40
        tmp = tempfile.mkdtemp(prefix="vmq-stall-bench-")
        nodes = []
        for i in range(2):
            cfg = Config(systree_enabled=False, allow_anonymous=True,
                         allow_publish_during_netsplit=True,
                         cluster_spool_dir=f"{tmp}/node{i}",
                         cluster_spool_retransmit_ms=100,
                         cluster_spool_ack_interval=20,
                         cluster_stall_timeout_s=0.5)
            broker, server = await start_broker(cfg, port=0,
                                                node_name=f"node{i}")
            broker.node_name = broker.metadata.node_name = f"node{i}"
            broker.registry.node_name = f"node{i}"
            broker.registry.db.node_name = f"node{i}"
            cluster = Cluster(broker, "127.0.0.1", 0)
            await cluster.start()
            nodes.append((broker, server, cluster))
        a, b = nodes
        b[2].join(a[2].listen_host, a[2].listen_port)
        while not (len(a[2].members()) == 2 and a[2].is_ready()
                   and b[2].is_ready()):
            await asyncio.sleep(0.02)
        sub = MQTTClient("127.0.0.1", b[1].port, client_id="as-sub")
        await sub.connect()
        await sub.subscribe("as/#", qos=1)
        while len(a[0].registry.trie("").match(["as", "x"])) != 1:
            await asyncio.sleep(0.02)
        while "spool" not in a[2]._peer_caps.get("node1", ()):
            await asyncio.sleep(0.02)
        pub = MQTTClient("127.0.0.1", a[1].port, client_id="as-pub")
        await pub.connect()

        # half-open: inbound (frames AND acks) dropped, channel "up"
        t_drill = time.monotonic()
        faults.install(faults.FaultPlan(
            [faults.FaultRule("cluster.recv", kind="error")], seed=12))
        for i in range(n_msgs):
            await pub.publish(f"as/{i}", b"c%d" % i, qos=1)
        stall_t0 = time.perf_counter()
        while (a[0].metrics.value("cluster_stall_reconnects") < 1
               and time.perf_counter() - stall_t0 < 20):
            await asyncio.sleep(0.05)
        detect_s = time.perf_counter() - stall_t0
        reconnects = a[0].metrics.value("cluster_stall_reconnects")

        faults.clear()
        got = {}
        heal_t0 = time.perf_counter()
        while (len(got) < n_msgs
               and time.perf_counter() - heal_t0 < 30):
            try:
                m = await sub.recv(5)
            except asyncio.TimeoutError:
                break
            got[m.payload] = got.get(m.payload, 0) + 1
        while True:
            try:
                m = await sub.recv(0.5)
            except asyncio.TimeoutError:
                break
            got[m.payload] = got.get(m.payload, 0) + 1
        replay_s = time.perf_counter() - heal_t0

        await sub.disconnect()
        await pub.disconnect()
        for broker, server, cluster in nodes:
            await cluster.stop()
            await broker.stop()
            await server.stop()
        expect = {b"c%d" % i for i in range(n_msgs)}
        return {
            "msgs": n_msgs,
            "stall_reconnects": int(reconnects),
            "stall_detect_s": round(detect_s, 3),
            "replay_s": round(replay_s, 3),
            "missing": len(expect - set(got)),
            "duplicates": sum(c - 1 for c in got.values()),
            # ack-stall detect → channel cycle → spool replay, on the
            # journal's clock (both in-process nodes share it)
            "events_during_drill": events_during_drill(t_drill),
        }

    dev = asyncio.run(device_segment())
    clu = asyncio.run(cluster_segment())

    def pct(lats, q):
        lats = sorted(lats)
        return round(lats[min(len(lats) - 1, int(q * len(lats)))] * 1e3, 3)

    n_storm = dev["storm_publishes"]
    got = dev.pop("got")
    healthy_lat = dev.pop("healthy_lat")
    storm_lat = dev.pop("storm_lat")
    # both filters ("sb/+/t" and "sb/#") match every storm publish:
    # exactly 2 deliveries per payload — fewer is loss, more means an
    # abandoned dispatch's stale fanout leaked through the discard
    expect = {b"s%d" % i for i in range(n_storm)}
    missing = sum(1 for p in expect if got.get(p, 0) < 2)
    dupes = sum(max(0, c - 2) for c in got.values())
    bound_ms = deadline_ms + expiry_budgets * budget_ms + 1000.0  # + slack
    p99 = pct(storm_lat, 0.99)
    return {
        **dev,
        "healthy_publish_ms_p99": pct(healthy_lat, 0.99),
        "storm_publish_ms_p50": pct(storm_lat, 0.50),
        "storm_publish_ms_p99": p99,
        "deadline_plus_eps_ms": bound_ms,
        "p99_bounded": p99 <= bound_ms,
        "missing": missing, "duplicates": dupes,
        "parity_ok": missing == 0 and dupes == 0,
        "cluster": clu,
        "cluster_zero_loss": (clu["missing"] == 0
                              and clu["duplicates"] == 0
                              and clu["stall_reconnects"] >= 1),
    }


def config9_overload_storm(smoke):
    """Overload storm: offered load past capacity, naive binary shedding
    vs the adaptive governor (robustness/overload.py).

    One in-process broker per mode (``overload_mode=binary`` — the old
    posture: sysmon flag + fixed 0.1s sleep for every producer — vs
    ``governor``). The storm combines QoS0 flood publishers offering
    load as fast as the socket accepts (several times what the throttled
    reader drains — the 3-5x offered-load regime) with a synchronous
    loop chore modelling CPU saturation, so sysmon sees genuine lag in
    both modes. A well-behaved QoS1 client publishes at a modest steady
    rate throughout; its delivered throughput ("goodput retained" — the
    useful work the broker completes under overload) and per-publish ack
    p50/p99 are the headline comparison. Also reports zero-QoS>=1-loss
    (every well-behaved publish delivered), the governor's level/shed
    accounting, and recovery time after the storm ends (the governor
    must return to level 0 within ~one hysteresis window; binary pays
    the full sysmon cooldown)."""
    import asyncio

    hold_s = 1.0

    async def run_mode(mode):
        from vernemq_tpu.broker.config import Config
        from vernemq_tpu.broker.server import start_broker
        from vernemq_tpu.client import MQTTClient

        storm_s = 2.0 if smoke else 6.0
        n_flood = 3
        cfg = Config(
            systree_enabled=False, allow_anonymous=True,
            sysmon_lag_threshold=0.01,
            overload_mode=mode,
            overload_hold_s=hold_s,
            overload_tick_ms=100,
            # wb publishes ~30/s: far under the bucket; floods far over
            overload_l2_client_rate=100,
            overload_l2_burst=50,
            # floods are the 3 heaviest talkers; the wb client must
            # never be in the shed set
            overload_l3_disconnect_top=3)
        broker, server = await start_broker(cfg, port=0,
                                            node_name=f"ov-{mode}")
        # fast lag sampling so both modes see the storm promptly
        broker.sysmon.stop()
        broker.sysmon.interval = 0.05
        broker.sysmon.start()

        # ~0.4ms of synchronous per-publish routing/auth work: the cost
        # model that makes the offered load exceed capacity (6k msgs/s
        # offered x 0.4ms = 2.4s of work per second, plus fanout). The
        # governor's QoS0 admission shed happens BEFORE this hook — so
        # shedding genuinely frees capacity, exactly the cliff the
        # broker-benchmarking literature describes. Binary mode pays the
        # hook for every flood message it reads.
        def cost_hook(user, sid, qos, topic, payload, retain):
            time.sleep(0.0004)
            return "ok"

        broker.hooks.register("auth_on_publish", cost_hook)

        sub = MQTTClient("127.0.0.1", server.port, client_id="ov-sub")
        await sub.connect()
        await sub.subscribe("ovwb/#", qos=1)
        await sub.subscribe("ovflood/#", qos=0)
        wb = MQTTClient("127.0.0.1", server.port, client_id="ov-wb")
        await wb.connect()
        floods = []
        for i in range(n_flood):
            c = MQTTClient("127.0.0.1", server.port,
                           client_id=f"ov-flood{i}")
            await c.connect()
            floods.append(c)

        storm = asyncio.Event()
        storm.set()
        flood_sent = [0]

        async def flood_loop(c, i):
            # paced bursts (~2000 msgs/s offered per publisher — several
            # times what the chore-saturated loop drains): the offered
            # load is bounded so post-storm socket backlogs stay
            # drainable, unlike an unbounded CPU-speed spin
            n = 0
            try:
                while storm.is_set():
                    for _ in range(20):
                        await c.publish(f"ovflood/{i}/{n}", b"f" * 64,
                                        qos=0)
                        n += 1
                    await asyncio.sleep(0.01)
            except Exception:
                pass  # L3 shed the talker: offered load stays gone
            flood_sent[0] += n

        wb_lat = []
        wb_sent = [0]

        async def wb_loop():
            n = 0
            while storm.is_set():
                t0 = time.perf_counter()
                try:
                    await wb.publish(f"ovwb/{n}", b"w%d" % n, qos=1,
                                     timeout=10.0)
                except asyncio.TimeoutError:
                    break
                wb_lat.append(time.perf_counter() - t0)
                n += 1
                await asyncio.sleep(0.03)
            wb_sent[0] = n

        tasks = [asyncio.get_event_loop().create_task(t) for t in (
            [wb_loop()]
            + [flood_loop(c, i) for i, c in enumerate(floods)])]
        t_storm = time.perf_counter()
        await asyncio.sleep(storm_s)
        storm.clear()
        await asyncio.gather(*tasks, return_exceptions=True)
        storm_actual = time.perf_counter() - t_storm

        # end the offered load COMPLETELY before timing recovery: the
        # flood sockets still hold an unread backlog the throttled
        # readers would keep draining — "load drops" means gone, not
        # parked (the graceful step-down path covers the parked case)
        for c in floods:
            try:
                await asyncio.wait_for(c.close(), 5.0)
            except (ConnectionError, asyncio.TimeoutError):
                pass
        await asyncio.sleep(0.1)  # let the closed handlers unwind

        # recovery: time from load stop until the shed posture clears
        gov = broker.overload
        t_rec = time.perf_counter()
        while time.perf_counter() - t_rec < 15:
            if mode == "governor":
                if gov.level == 0:
                    break
            elif not broker.sysmon.overloaded:
                break
            await asyncio.sleep(0.05)
        recovery_s = time.perf_counter() - t_rec

        # drain deliveries (wb deliveries may trail the acks)
        wb_got, flood_got = set(), 0
        while True:
            try:
                m = await sub.recv(0.5)
            except asyncio.TimeoutError:
                break
            if m is None:
                break
            if m.payload.startswith(b"w"):
                wb_got.add(m.payload)
            else:
                flood_got += 1

        metrics = broker.metrics
        lvl = gov.status()
        out = {
            "storm_s": round(storm_actual, 2),
            "wb_published": wb_sent[0],
            "wb_delivered": len(wb_got),
            "wb_goodput_msgs_per_s": round(
                len(wb_got) / storm_actual, 1),
            "wb_publish_ms_p50": _pct_ms(wb_lat, 0.50),
            "wb_publish_ms_p99": _pct_ms(wb_lat, 0.99),
            "flood_offered": flood_sent[0],
            "flood_delivered": flood_got,
            "qos1_missing": wb_sent[0] - len(wb_got),
            "throttled": metrics.value("mqtt_publish_throttled"),
            "recovery_s": round(recovery_s, 2),
        }
        if mode == "governor":
            out.update({
                "max_level_entered": max(
                    (i for i in (1, 2, 3)
                     if lvl["enters"][f"l{i}"] > 0), default=0),
                "qos0_shed": metrics.value("overload_qos0_shed"),
                "rate_limited": metrics.value("overload_rate_limited"),
                "talker_disconnects": metrics.value(
                    "overload_talker_disconnects"),
                "connects_refused": metrics.value(
                    "overload_connects_refused"),
                "level_seconds": lvl["seconds"],
                # one hold window + lag-EWMA decay, plus slack for the
                # bench sharing its loop with the draining clients
                "recovered_within_hold": recovery_s <= 2 * hold_s + 1.0,
            })

        await wb.disconnect()
        await sub.disconnect()
        await broker.stop()
        await server.stop()
        return out

    def _pct_ms(lats, q):
        if not lats:
            return None
        lats = sorted(lats)
        return round(lats[min(len(lats) - 1, int(q * len(lats)))] * 1e3, 2)

    binary = asyncio.run(run_mode("binary"))
    governor = asyncio.run(run_mode("governor"))
    return {
        "binary": binary,
        "governor": governor,
        "governor_wins_goodput": (
            governor["wb_goodput_msgs_per_s"]
            > binary["wb_goodput_msgs_per_s"]),
        "governor_wins_p99": (
            governor["wb_publish_ms_p99"] is not None
            and binary["wb_publish_ms_p99"] is not None
            and governor["wb_publish_ms_p99"]
            < binary["wb_publish_ms_p99"]),
        "zero_qos1_loss": (governor["qos1_missing"] == 0
                           and binary["qos1_missing"] == 0),
    }


def _admission_client_proc(port, n_clients, storm_s, tag,
                           connect_churn, out_q, mode="qos0"):
    """Spawn-safe load-generator entry for bench config 11. Each
    process runs its own asyncio loop with ``n_clients`` flood
    publishers — each writes a pre-serialised blob of 2048 PUBLISH
    frames per drain cycle, so the load side costs ~a memcpy per
    message and the broker's admission path (parse, auth chain, route,
    governor) is what saturates. ``mode`` picks the wire shape:
    ``qos0`` (v4 QoS0, the original storm), ``qos1`` (v4 QoS1 with
    distinct packet ids; a reader task drains the PUBACK stream so the
    broker's write buffer never wedges the A/B), or ``alias1`` (v5
    QoS1 through an established topic alias — every flooded frame is
    the alias-only hot shape). ``connect_churn`` adds a
    connect/disconnect loop recording CONNECT->CONNACK latencies (the
    connect-storm component). Admitted throughput is counted on the
    WORKER side (mqtt_publish_received via the shared stats block) —
    the client's send count only bounds the offered load."""
    import asyncio as aio
    import socket as _sck
    import time as _t

    results = {"sent": 0, "connect_s": [], "errors": 0, "refused": 0}

    def _nodelay(writer):
        sock = writer.get_extra_info("socket")
        if sock is not None:
            sock.setsockopt(_sck.IPPROTO_TCP, _sck.TCP_NODELAY, 1)

    async def publisher(i):
        from vernemq_tpu.protocol import codec_v4, codec_v5
        from vernemq_tpu.protocol.types import Connect, Publish

        codec = codec_v5 if mode == "alias1" else codec_v4
        t0 = _t.perf_counter()
        reader, writer = await aio.open_connection("127.0.0.1", port)
        _nodelay(writer)
        writer.write(codec.serialise(Connect(
            client_id=f"adm{tag}-{i}", keepalive=0,
            proto_ver=5 if mode == "alias1" else 4)))
        buf = b""
        while True:
            buf += await aio.wait_for(reader.read(1024), 15.0)
            connack, _rest = codec.parse(buf)
            if connack is not None:
                break
        results["connect_s"].append(_t.perf_counter() - t0)
        if getattr(connack, "rc", 0):
            results["refused"] += 1
            writer.close()
            return
        topic = f"adm/{tag}/{i}"
        if mode == "qos0":
            frame = codec_v4.serialise(Publish(
                topic=topic, payload=b"x" * 32, qos=0))
            blob = frame * 2048
        elif mode == "qos1":
            blob = b"".join(codec_v4.serialise(Publish(
                topic=topic, payload=b"x" * 32, qos=1, packet_id=p))
                for p in range(1, 2049))
        else:  # alias1: establish the alias, then flood alias-only
            writer.write(codec_v5.serialise(Publish(
                topic=topic, payload=b"x" * 32, qos=1, packet_id=1,
                properties={"topic_alias": 1})))
            await writer.drain()
            blob = b"".join(codec_v5.serialise(Publish(
                topic="", payload=b"x" * 32, qos=1, packet_id=p,
                properties={"topic_alias": 1}))
                for p in range(2, 2050))
        drainer = None
        if mode != "qos0":
            async def _drain_acks():
                # the broker PUBACKs every QoS1 frame: sink the stream
                # (its bytes aren't the measurement — admitted count is
                # read broker-side) so neither side's buffer wedges
                try:
                    while await reader.read(65536):
                        pass
                except (ConnectionError, OSError):
                    pass
            drainer = aio.ensure_future(_drain_acks())
        deadline = _t.monotonic() + storm_s
        sent = 0
        try:
            while _t.monotonic() < deadline:
                writer.write(blob)
                # drain() is the only pacing: TCP backpressure from the
                # broker's read rate bounds the offered load
                await writer.drain()
                sent += 2048
        except (ConnectionError, OSError):
            # L3 talker shed / worker death: offered load stays gone,
            # which is exactly the admission-control contract
            results["errors"] += 1
        results["sent"] += sent
        if drainer is not None:
            drainer.cancel()
        writer.close()

    async def churner():
        from vernemq_tpu.protocol import codec_v4
        from vernemq_tpu.protocol.types import Connect

        deadline = _t.monotonic() + storm_s
        i = 0
        while _t.monotonic() < deadline:
            t0 = _t.perf_counter()
            try:
                reader, writer = await aio.open_connection(
                    "127.0.0.1", port)
                _nodelay(writer)
                writer.write(codec_v4.serialise(
                    Connect(client_id=f"chn{tag}-{i}", keepalive=0)))
                ack = await aio.wait_for(reader.readexactly(4), 10.0)
                results["connect_s"].append(_t.perf_counter() - t0)
                if ack[3] != 0:
                    results["refused"] += 1
                writer.close()
            except (ConnectionError, OSError, aio.TimeoutError,
                    aio.IncompleteReadError):
                results["errors"] += 1
            i += 1
            await aio.sleep(0.01)

    async def amain():
        tasks = [publisher(i) for i in range(n_clients)]
        if connect_churn:
            tasks.append(churner())
        await aio.gather(*tasks, return_exceptions=True)

    aio.run(amain())
    out_q.put(results)


def config11_admission_storm(smoke):
    """Admission storm across worker counts (the multi-process session
    front end, broker/workers.py): connect churn + a QoS0 small-publish
    flood from SEPARATE load-generator processes, at workers in
    {1, 2, 4}, reporting admitted pubs/s (counted on the WORKER side:
    mqtt_publish_received deltas out of the shared stats block over a
    mid-storm window), CONNECT p99, per-worker loop-lag p99, and a
    bit-identical QoS1 fanout parity phase at every worker count. An
    in-process single-loop broker runs the same storm as the pre-PR
    baseline: workers=1 must sit within noise of it (the
    byte-identical degradation rule). ``cpu_count`` travels with the
    artifact: admission is pure Python CPU, so the worker ladder's
    ceiling is min(workers, cores - load-gen share) — on a 2-core
    smoke box the w4 number reads as the CORE ceiling, not the front
    end's."""
    import asyncio
    import multiprocessing as mp
    import socket as _socket

    storm_s = 5.0 if smoke else 10.0
    n_procs = 2
    clients_per = 4
    parity_n = 120 if smoke else 400
    ctx = mp.get_context("spawn")

    def free_port():
        s = _socket.socket()
        s.bind(("127.0.0.1", 0))
        p = s.getsockname()[1]
        s.close()
        return p

    def wait_ready(port, timeout=60.0):
        deadline = time.time() + timeout
        while time.time() < deadline:
            try:
                _socket.create_connection(("127.0.0.1", port),
                                          0.5).close()
                return True
            except OSError:
                time.sleep(0.25)
        return False

    async def storm_measure(port, tag, sampler, mode="qos0"):
        """Fan out the load processes and measure admitted throughput
        over a mid-storm window via ``sampler()`` (a monotonic admitted
        count read on the broker side). Async so the single-loop
        baseline can host the broker on THIS loop while measuring."""
        loop = asyncio.get_event_loop()
        q = ctx.Queue()
        procs = [ctx.Process(target=_admission_client_proc,
                             args=(port, clients_per, storm_s,
                                   f"{tag}{j}", j == 0, q, mode),
                             daemon=True)
                 for j in range(n_procs)]
        for p in procs:
            p.start()
        await asyncio.sleep(1.0)  # ramp: connects + first blobs
        a0, t0 = sampler(), time.perf_counter()
        await asyncio.sleep(max(1.0, storm_s - 2.0))
        a1, dt = sampler(), time.perf_counter() - t0
        folded = {"sent": 0, "connect_s": [], "errors": 0, "refused": 0}
        for _ in procs:
            r = await loop.run_in_executor(None, q.get, True,
                                           storm_s + 120)
            folded["sent"] += r["sent"]
            folded["connect_s"].extend(r["connect_s"])
            folded["errors"] += r["errors"]
            folded["refused"] += r["refused"]
        for p in procs:
            p.join(10.0)
        lat = sorted(folded["connect_s"])
        return {
            "admitted_pubs_per_s": round((a1 - a0) / dt, 1),
            "offered_pubs": folded["sent"],
            "connect_ms_p99": (round(
                lat[min(len(lat) - 1, int(0.99 * len(lat)))] * 1e3, 2)
                if lat else None),
            "connects": len(lat),
            "connects_refused": folded["refused"],
            "client_errors": folded["errors"],
        }

    async def parity_phase(port, tag):
        """Bit-identical fanout at this worker count: every distinct
        QoS1 payload published is delivered exactly once."""
        from vernemq_tpu.client import MQTTClient

        sub = MQTTClient("127.0.0.1", port, client_id=f"par-sub{tag}")
        await sub.connect()
        await sub.subscribe("par/#", qos=1)
        await asyncio.sleep(1.2)  # cross-worker replication
        pub = MQTTClient("127.0.0.1", port, client_id=f"par-pub{tag}")
        await pub.connect()
        sent = set()
        for i in range(parity_n):
            payload = b"par-%d" % i
            await pub.publish(f"par/{tag}/{i}", payload, qos=1,
                              timeout=15.0)
            sent.add(payload)
        got = set()
        dupes = 0
        deadline = time.monotonic() + 25.0
        while time.monotonic() < deadline:
            try:
                f = await sub.recv(1.0)
            except asyncio.TimeoutError:
                if len(got) >= len(sent):
                    break
                continue
            if f is None:
                break
            if f.payload in got:
                dupes += 1
            got.add(f.payload)
        await sub.disconnect()
        await pub.disconnect()
        return got == sent and dupes == 0

    def run_workers(n_workers, base):
        from vernemq_tpu.broker.workers import WorkerGroup

        port = free_port()
        # children are host-only: default_reg_view stays "trie", so no
        # worker imports JAX while this process may hold the chip
        g = WorkerGroup(n_workers, "127.0.0.1", port,
                        cluster_base=base, allow_anonymous=True,
                        systree_enabled=False,
                        sysmon_lag_threshold=30.0)
        g.start()
        try:
            if not wait_ready(port):
                raise RuntimeError(f"workers={n_workers} never came up")
            time.sleep(1.0 + 0.5 * n_workers)  # mesh formation

            def sampler():
                return sum(s["admitted_pubs"]
                           for s in g.stats_block().read_all())

            out = asyncio.run(storm_measure(port, f"w{n_workers}",
                                            sampler))
            out["parity_ok"] = asyncio.run(parity_phase(port,
                                                        n_workers))
            lag_p99 = []
            for s in g.stats_block().read_all():
                lags = sorted(s["lag_samples"])
                lag_p99.append(round(
                    lags[min(len(lags) - 1, int(0.99 * len(lags)))]
                    * 1e3, 2) if lags else None)
            out["loop_lag_ms_p99_per_worker"] = lag_p99
            out["workers_alive"] = g.alive_count()
            # scrape-point histogram aggregation, read exactly like a
            # worker's /metrics would: merge every live slot's packed
            # stage-histogram block — the artifact shows merged
            # families actually carrying observations from N processes
            try:
                from vernemq_tpu.observability import histogram as hist

                merged = {}
                ws = g.stats_block()
                # worker slots + the match service's block (the
                # device-side seams live in the service process) —
                # exactly the set Broker._peer_histograms merges
                blocks = [ws.read_hist(i) for i in range(ws.n_workers)]
                blocks.append(ws.read_service_hist())
                for flat in blocks:
                    for name, snap in hist.unpack_flat(flat).items():
                        cur = merged.get(name)
                        merged[name] = (hist.merge(cur, snap)
                                        if cur else snap)
                out["stage_latency_merged"] = {
                    name: {k: (round(v, 4) if isinstance(v, float)
                               else v)
                           for k, v in hist.summary(snap).items()}
                    for name, snap in merged.items() if snap[2] > 0}
            except Exception as e:
                out["stage_latency_merged"] = {
                    "error": f"{type(e).__name__}: {e}"}
            return out
        finally:
            g.stop()

    async def run_single_loop(tag="base", wire_fastpath=True,
                              mode="qos0"):
        """Pre-PR baseline: ONE in-process broker on this loop, same
        storm from the same external load processes.
        ``wire_fastpath=False`` pins the classic per-frame session path
        (the wire A/B's pure legs run it with the native codec forced
        off as well). ``mode`` selects the storm's wire shape (see
        ``_admission_client_proc``); every leg also records its
        wire-stage histograms and runs the QoS1 exactly-once parity
        phase against the same broker — the A/B is only meaningful if
        both legs are provably zero-loss."""
        from vernemq_tpu.broker.config import Config
        from vernemq_tpu.broker.server import start_broker
        from vernemq_tpu.observability import histogram as hist

        cfg = Config(systree_enabled=False, allow_anonymous=True,
                     sysmon_lag_threshold=30.0,
                     wire_fastpath_enabled=wire_fastpath,
                     topic_alias_max_client=16)
        broker, server = await start_broker(cfg, port=0,
                                            node_name="adm-" + tag)
        # the histogram registry is process-global and every leg runs
        # in THIS process: per-leg stage latencies are the delta
        # against a pre-storm baseline, taken after the parity phase
        # so the leg's own QoS1 fanout encodes are in its numbers
        fams = ("stage_wire_parse_ms", "stage_wire_encode_ms")
        base_snap = {f: broker.metrics.histogram_snapshot().get(f)
                     for f in fams}
        out = await storm_measure(
            server.port, tag,
            lambda: broker.metrics.value("mqtt_publish_received"),
            mode)
        out["parity_ok"] = await parity_phase(server.port, tag)
        for fam in fams:
            s1 = broker.metrics.histogram_snapshot().get(fam)
            s0 = base_snap[fam]
            if s1 and s0:
                s1 = ([a - b for a, b in zip(s1[0], s0[0])],
                      s1[1] - s0[1], s1[2] - s0[2])
            out[fam] = ({k: (round(v, 4) if isinstance(v, float)
                             else v)
                         for k, v in hist.summary(s1).items()}
                        if s1 and s1[2] > 0 else None)
        await broker.stop()
        await server.stop()
        return out

    base = asyncio.run(run_single_loop())
    # wire-plane A/B (ISSUE 12 + ISSUE 16 acceptance): the SAME storm
    # at the same (single) worker count, native batched codec + wire
    # fast path vs the pure-Python pre-wire-plane session path — one
    # leg pair per wire shape: qos0 (the original flood; its native
    # leg IS the baseline run above), qos1 (ack-bearing ingress +
    # batched fanout encode), alias1 (v5 alias-only hot frames). The
    # pure legs force the whole plane off. Every leg carries its own
    # stage_wire_* histograms and a QoS1 exactly-once parity verdict.
    from vernemq_tpu.protocol import codec_v4 as _c4
    from vernemq_tpu.protocol import codec_v5 as _c5
    from vernemq_tpu.protocol import fastpath as _fp

    native_built = _fp.load_native() is not None

    def _leg(r, native):
        return {
            "admitted_pubs_per_s": r["admitted_pubs_per_s"],
            "native_codec": native_built if native else False,
            "wire_fastpath": native,
            "stage_wire_parse_ms": r["stage_wire_parse_ms"],
            "stage_wire_encode_ms": r["stage_wire_encode_ms"],
            "parity_ok": r["parity_ok"],
        }

    def _pure_leg(tag, mode):
        saved = (_c4._C, _c5._C, _fp._force_pure)
        _c4._C = None
        _c5._C = None
        _fp._force_pure = True
        try:
            return asyncio.run(run_single_loop(
                tag, wire_fastpath=False, mode=mode))
        finally:
            _c4._C, _c5._C, _fp._force_pure = saved

    wire_ab = {}
    for mode in ("qos0", "qos1", "alias1"):
        if mode == "qos0":
            nat = base
        else:
            note(f"[bench] config11 wire-plane {mode} native leg...")
            nat = asyncio.run(run_single_loop(f"n{mode}", mode=mode))
        note(f"[bench] config11 wire-plane {mode} pure leg...")
        pure = _pure_leg(f"p{mode}", mode)
        pfx = "" if mode == "qos0" else mode + "_"
        wire_ab[pfx + "native"] = _leg(nat, True)
        wire_ab[pfx + "pure"] = _leg(pure, False)
        wire_ab[pfx + "admitted_speedup"] = (round(
            nat["admitted_pubs_per_s"] / pure["admitted_pubs_per_s"],
            2) if pure["admitted_pubs_per_s"] else None)
    per = {}
    for i, n in enumerate((1, 2, 4)):
        note(f"[bench] config11 workers={n} storm...")
        per[str(n)] = run_workers(n, 25150 + 150 * i)
    r1 = per["1"]["admitted_pubs_per_s"]
    out = {
        "storm_s": storm_s,
        "cpu_count": os.cpu_count(),
        "load_procs": n_procs,
        "publishers": n_procs * clients_per,
        "single_loop_pubs_per_s": base["admitted_pubs_per_s"],
        "single_loop_connect_ms_p99": base["connect_ms_p99"],
        # wire plane: native codec availability + the A/B at one worker
        "native_codec": native_built,
        "wire_ab": wire_ab,
        "per_workers": per,
        "speedup_w2_vs_w1": round(
            per["2"]["admitted_pubs_per_s"] / r1, 2) if r1 else None,
        "speedup_w4_vs_w1": round(
            per["4"]["admitted_pubs_per_s"] / r1, 2) if r1 else None,
        "w1_vs_single_loop": round(
            r1 / base["admitted_pubs_per_s"], 2)
        if base["admitted_pubs_per_s"] else None,
        # capacity ladder posture: the overload governor's lag gate is
        # lifted IDENTICALLY in every measured broker (threshold 30s).
        # At saturation the governor's job is to shed — a closed-loop
        # throughput probe with shedding active measures the shed
        # equilibrium (config 9's subject, and bistable around the
        # threshold), not admission capacity.
        "governor_lag_gate_lifted": True,
        "core_bound": (os.cpu_count() or 1) < 5,
        "speedup_note": (
            "admission is pure Python CPU: with cpu_count < workers + "
            "load procs, every multi-worker rung measures the machine's "
            "core ceiling, not front-end scaling — the w1 rung already "
            "saturates ~1 core and the load generators the rest. "
            "Re-run on a many-core host (ROADMAP million-session item) "
            "for the real ladder."
            if (os.cpu_count() or 1) < 5 else None),
        "parity_ok": (all(p["parity_ok"] for p in per.values())
                      and all(leg["parity_ok"]
                              for leg in wire_ab.values()
                              if isinstance(leg, dict))),
    }
    return out


def _mesh_rung_main(n_slices: int, subs: int, seed: int,
                    iters: int) -> int:
    """One rung of the mesh ladder, run in a FRESH process whose
    XLA_FLAGS forced ``n_slices`` host devices (the parent sets the
    env — device count is fixed at backend init). Builds the mesh-
    native matcher and the single-process ShardedWindowedMatcher over
    the SAME mesh + table, and prints one JSON line: per-slice rows,
    delta-routing hit rate, bit-identical parity vs the oracle (and the
    trie), amortized dispatch ms."""
    import jax

    from vernemq_tpu.models.tpu_table import SubscriptionTable
    from vernemq_tpu.models.trie import SubscriptionTrie
    from vernemq_tpu.parallel.mesh import make_mesh
    from vernemq_tpu.parallel.mesh_match import MeshMatcher
    from vernemq_tpu.parallel.sharded_match import ShardedWindowedMatcher

    rng = random.Random(seed)
    devs = jax.devices()
    assert len(devs) >= n_slices, (len(devs), n_slices)
    table = SubscriptionTable(
        max_levels=8,
        initial_capacity=max(1 << (subs - 1).bit_length(),
                             4096 * n_slices, 1 << 14))
    trie = SubscriptionTrie()
    l0 = [f"r{i}" for i in range(48)]
    l1 = [f"d{i}" for i in range(96)]
    l2 = [f"m{i}" for i in range(24)]
    for i in range(subs):
        r = rng.random()
        w = [rng.choice(l0), rng.choice(l1), rng.choice(l2)]
        if r < 0.6:
            f = w
        elif r < 0.8:
            f = [w[0], "+", w[2]]
        elif r < 0.9:
            f = ["+", w[1], w[2]]
        else:
            f = [w[0], w[1], "#"]
        table.add(f, i, None)
        trie.add(list(f), i, None)
    table.add(["$SYS", "stats", "#"], "sys", None)
    trie.add(["$SYS", "stats", "#"], "sys", None)
    mesh = make_mesh(devs[:n_slices], batch=1)
    m = MeshMatcher(table, mesh, max_fanout=256)
    oracle = ShardedWindowedMatcher(table, mesh, max_fanout=256)

    def norm(rows):
        return sorted((k for _, k, _ in rows), key=repr)

    topics = [(rng.choice(l0), rng.choice(l1), rng.choice(l2))
              for _ in range(128)]
    topics += [("$SYS", "stats", "x"), ("never", "seen", "words")]
    got = m.match_batch(topics)
    want_o = oracle.match_batch(topics)
    parity = all(norm(a) == norm(trie.match(list(tp)))
                 for tp, a in zip(topics, got))
    oracle_ok = all(norm(a) == norm(b) for a, b in zip(got, want_o))

    # delta-routing phase: R single-bucket subscribe bursts, each
    # flushed by the next match — dirty slices per flush vs total.
    flushes0 = m.route_flushes
    dirty0 = m.route_dirty_slices
    scatters0 = m.full_scatters
    rounds = 8
    for r_i in range(rounds):
        w0 = rng.choice(l0)
        for j in range(4):
            f = [w0, rng.choice(l1), f"new{r_i}x{j}"]
            table.add(f, 10_000_000 + r_i * 100 + j, None)
            trie.add(list(f), 10_000_000 + r_i * 100 + j, None)
        got = m.match_batch(topics[:8])
        if not all(norm(a) == norm(trie.match(list(tp)))
                   for tp, a in zip(topics[:8], got)):
            parity = False
    flushes = m.route_flushes - flushes0
    dirty = m.route_dirty_slices - dirty0
    # the routing guarantee: delta flushes NEVER fell back to a
    # full-table placement (full_scatters moves only on build/growth)
    assert m.full_scatters == scatters0, "delta flush fell back to a " \
        "full-table scatter"
    assert flushes == rounds, (flushes, rounds)

    # dispatch amortization: K batches launched back-to-back, pulled
    # after (the match_many posture at the mesh layer)
    bs = 256
    bench_topics = [(rng.choice(l0), rng.choice(l1), rng.choice(l2))
                    for _ in range(bs)]
    m.match_batch(bench_topics)  # warm the shape
    t0 = time.perf_counter()
    for _ in range(iters):
        m.match_batch(bench_topics)
    k1_ms = (time.perf_counter() - t0) / iters * 1e3
    K = 4
    m.sync()
    preps = [m._prep(bench_topics) for _ in range(K)]
    refs = [m._dispatch_device(p) for p in preps]  # warm
    for r in refs:
        m._pull(r)
    t0 = time.perf_counter()
    for _ in range(iters):
        refs = [m._dispatch_device(p) for p in preps]
        for r in refs:
            m._pull(r)
    k4_ms = (time.perf_counter() - t0) / (iters * K) * 1e3
    st = m.mesh_status()
    print(json.dumps({
        "slices": n_slices,
        "rows": subs,
        "per_slice_rows": st["rows_per_slice"],
        "parity_ok": bool(parity),
        "oracle_bit_identical": bool(oracle_ok),
        "routing": {
            "flushes": flushes,
            "dirty_slices": dirty,
            "total_slices": flushes * n_slices,
            "hit_rate": round(1.0 - dirty / max(flushes * n_slices, 1),
                              3),
            "gzone_flushes": st["route_gzone_flushes"],
            "full_scatter_fallbacks": m.full_scatters - scatters0,
        },
        "dispatch_ms_k1": round(k1_ms, 3),
        "amortized_dispatch_ms_k4": round(k4_ms, 3),
    }))
    return 0


def config12_mesh_ladder(smoke, seed, subs):
    """Mesh ladder: the mesh-native matcher at 1/2/4 forced-host-device
    slices (CPU smoke — device count is fixed at backend init, so each
    rung runs in a fresh subprocess with its own XLA_FLAGS). Honest
    flags: cpu_smoke travels in the artifact; virtual CPU 'slices' share
    one socket, so the ladder validates ROUTING and PARITY, not
    multi-host bandwidth (ROOFLINE.md multi-host section has the
    model)."""
    import subprocess

    rung_subs = min(subs, 20_000) if smoke else min(subs, 200_000)
    iters = 4 if smoke else 12
    rungs = {}
    for n in (1, 2, 4):
        env = os.environ.copy()
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
        note(f"[bench] config12 mesh rung slices={n}...")
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--mesh-rung", str(n), "--subs", str(rung_subs),
             "--seed", str(seed), "--iters", str(iters)],
            env=env, capture_output=True, text=True, timeout=900)
        if proc.returncode != 0:
            tail = (proc.stderr or "").strip().splitlines()[-3:]
            rungs[f"s{n}"] = {"error": " | ".join(tail) or "rung failed"}
            continue
        line = (proc.stdout or "").strip().splitlines()[-1]
        rungs[f"s{n}"] = json.loads(line)
    ok_rungs = [r for r in rungs.values() if "error" not in r]
    return {
        "cpu_smoke": True,
        "rows": rung_subs,
        "rungs": rungs,
        "parity_ok": bool(ok_rungs) and all(
            r["parity_ok"] and r["oracle_bit_identical"]
            for r in ok_rungs),
        "routing_hit_rate_s4": rungs.get("s4", {}).get(
            "routing", {}).get("hit_rate"),
        "note": ("forced-host-device CPU slices share one socket: this "
                 "ladder validates slice routing + bit-identical "
                 "parity, not multi-host bandwidth"),
    }


def config13_downsampling_storm(smoke, seed):
    """Telemetry downsampling storm (the MQTT+/edge-broker scenario):
    fan-in publishes against predicate + aggregation subscriptions.

    Builds the production wiring standalone — SchemaRegistry +
    FilterEngine — registers N ``$gt(value,T)`` predicate subscriptions
    (spread thresholds), M ``$avg(value,50)`` aggregation windows and a
    sprinkle of unrepresentable conjunctions (host escapes), then
    drives fan-in publish batches through ``filter_batch`` (the device
    phase: one dispatch evaluates every (matched-subscriber ×
    predicate) pair and folds the windows) vs the forced host
    evaluator on identical inputs. Reports pair throughput both ways
    (speedup_vs_host), filtered-row and emission counts, ``parity_ok``
    covering healthy runs AND an injected ``device.predicate`` outage
    (breaker opens, host serves bit-identically), honestly flagged
    cpu_smoke off-TPU."""
    import jax as _jax

    from vernemq_tpu.cluster.metadata import MetadataStore
    from vernemq_tpu.filters.engine import FilterEngine
    from vernemq_tpu.filters.schema_registry import SchemaRegistry
    from vernemq_tpu.protocol.types import SubOpts
    from vernemq_tpu.robustness import faults

    rng = random.Random(seed + 13)
    n_pred = 64 if smoke else 512
    n_agg = 16 if smoke else 128
    n_conj = 8 if smoke else 32
    batch = 512 if smoke else 2048
    reps = 8 if smoke else 24

    md = MetadataStore("bench13")
    sreg = SchemaRegistry(md, "bench13")
    sreg.set_schema("", "sensors/+/temp", "value:number,unit:enum(c|f)")
    eng = FilterEngine(sreg, device_gate=lambda: True, host_threshold=1,
                       window_cap=1 << 14)
    emissions = [0]
    eng.emit = lambda *_a: emissions.__setitem__(0, emissions[0] + 1)

    rows = []
    for i in range(n_pred):
        o = SubOpts()
        o.filter_expr = f"$gt(value,{rng.randrange(0, 100)})"
        eng.on_sub_delta("add", "", o)
        rows.append((("sensors", "+", "temp"), ("", f"p{i}"), o))
    for i in range(n_agg):
        o = SubOpts()
        o.filter_expr = "$avg(value,50)"
        rows.append((("sensors", "+", "temp"), ("", f"a{i}"), o))
    for i in range(n_conj):
        o = SubOpts()
        o.filter_expr = (f"$gt(value,{rng.randrange(0, 50)})"
                         f"&$eq(unit,c)")
        rows.append((("sensors", "+", "temp"), ("", f"x{i}"), o))

    sensors = [f"s{i}" for i in range(64)]

    def mk_batch():
        items = []
        for _ in range(batch):
            t = ("sensors", rng.choice(sensors), "temp")
            payload = json.dumps(
                {"value": round(rng.uniform(0, 100), 2),
                 "unit": rng.choice(["c", "f"])}).encode()
            items.append((t, eng.encode("", t, payload)))
        return items

    batches = [mk_batch() for _ in range(min(reps, 6))]
    pairs_per_pub = n_pred + n_agg + n_conj
    # warm (compile) then measure the device path
    eng.filter_batch("", batches[0], [list(rows) for _ in batches[0]])
    t0 = time.perf_counter()
    for i in range(reps):
        b = batches[i % len(batches)]
        eng.filter_batch("", b, [list(rows) for _ in b])
    dev_dt = time.perf_counter() - t0
    dev_pairs_s = reps * batch * pairs_per_pub / dev_dt
    # forced host evaluator on the same inputs
    t0 = time.perf_counter()
    for i in range(reps):
        b = batches[i % len(batches)]
        eng.filter_batch_host("", b, [list(rows) for _ in b])
    host_dt = time.perf_counter() - t0
    host_pairs_s = reps * batch * pairs_per_pub / host_dt

    # parity: device vs host on a fresh batch, then under an injected
    # persistent device.predicate outage (breaker opens, host serves)
    pb = mk_batch()
    healthy = eng.filter_batch("", pb, [list(rows) for _ in pb])
    oracle = eng.filter_batch_host("", pb, [list(rows) for _ in pb])
    bad = sum(1 for a, b2 in zip(healthy, oracle) if a != b2)
    faults.install(faults.FaultPlan(
        [faults.FaultRule("device.predicate", kind="error")], seed=13))
    degraded = eng.filter_batch("", pb, [list(rows) for _ in pb])
    eng.filter_batch("", pb, [list(rows) for _ in pb])
    eng.filter_batch("", pb, [list(rows) for _ in pb])
    degraded_bad = sum(1 for a, b2 in zip(degraded, oracle) if a != b2)
    breaker_state = eng.breaker.state_name
    faults.clear()

    return {
        "cpu_smoke": _jax.devices()[0].platform != "tpu",
        "subscriptions": {"predicate": n_pred, "aggregate": n_agg,
                          "conjunction_escapes": n_conj},
        "batch": batch,
        "pairs_per_publish": pairs_per_pub,
        "device_pairs_per_sec": round(dev_pairs_s),
        "host_pairs_per_sec": round(host_pairs_s),
        "speedup_vs_host": round(dev_pairs_s / host_pairs_s, 2),
        "device_publishes_per_sec": round(reps * batch / dev_dt),
        "predicate_dispatches": eng.dispatches,
        "rows_filtered": eng.rows_filtered,
        "pairs_escaped_host": eng.pairs_escaped,
        "aggregate_emissions": emissions[0],
        "values_folded": eng.values_folded,
        "windows_open": eng.status()["windows_open"],
        "parity_ok": bad == 0 and degraded_bad == 0,
        "breaker_state_during_outage": breaker_state,
        "degraded_sheds": eng.degraded_sheds,
    }


def config14_reconnect_storm(smoke, sessions=None, backlog=10,
                             broadcast=5):
    """Storage-tier config: a reconnect storm of persistent sessions
    with stored offline backlogs against a freshly-booted broker — the
    million-offline-session workload (ROADMAP direction 3 / ISSUE 14).

    The corpus is the IoT-benchmark paper's fan-out-notification shape:
    each session's backlog is ``broadcast`` messages shared by EVERY
    session (one refcounted payload m-record each — the broadcast that
    landed while everyone was asleep) plus ``backlog - broadcast``
    per-session messages (unique refs — per-device commands).

    Two legs on identical corpora drive the queue/store resume seam
    directly (queue create → recover → attach; registration machinery
    is identical in both and would only add constant cost):

    - ``batched``: the ResumeCollector coalesces concurrent replays
      into off-loop ``read_many`` batches (lazy boot, staged delivery,
      cross-session decode cache: a broadcast decodes once per batch)
    - ``read_all`` baseline: the pre-PR path — one synchronous
      loop-side ``read_all`` + enqueue loop per session, which pays
      every broadcast decode per session (same session count, so
      loop-lag/GC pressure is apples-to-apples)

    Reports per-session replay latency p50/p99, event-loop lag p99
    sampled through the storm, zero-QoS1-loss parity (every stored
    message delivered exactly once, in order), the batched-vs-baseline
    replay throughput speedup, and which journal engine served
    (native kvstore / segment fallback) so numbers are comparable
    across boxes."""
    import asyncio
    import shutil
    import tempfile

    n_sessions = sessions or (20_000 if smoke else 100_000)
    # equal scale in both legs: loop-lag/GC pressure must be
    # apples-to-apples, not a 10x-smaller baseline flattered by a
    # smaller heap
    n_baseline = n_sessions
    n_unique = backlog - broadcast

    async def leg(batched, n):
        from vernemq_tpu.broker.config import Config
        from vernemq_tpu.broker.message import Msg
        from vernemq_tpu.broker.queue import QueueOpts
        from vernemq_tpu.broker.server import start_broker

        tmp = tempfile.mkdtemp(prefix="vmq-resume-bench-")
        cfg = Config(systree_enabled=False, allow_anonymous=True,
                     message_store="file", message_store_dir=tmp,
                     resume_batched=batched)
        broker, server = await start_broker(cfg, port=0)
        try:
            sids = [("", f"c{i}") for i in range(n)]
            bcast = [Msg(topic=("bcast", str(j)),
                         payload=b"B%d" % j * 8, qos=1,
                         msg_ref=b"bcast-%d" % j)
                     for j in range(broadcast)]
            t0 = time.perf_counter()
            for i, sid in enumerate(sids):
                for m in bcast:  # shared ref: stored payload is ONE
                    broker.msg_store.write(sid, m)
                for j in range(n_unique):
                    broker.msg_store.write(sid, Msg(
                        topic=("r", sid[1]), payload=b"p%d" % j, qos=1,
                        msg_ref=(f"{sid[1]}-{j}").encode()))
                if (i + 1) % 1000 == 0:
                    await asyncio.sleep(0)
            populate_s = time.perf_counter() - t0
            broker.msg_store.commit()

            # loop-lag sampler through the storm (config 11 discipline)
            lags = []
            stop_probe = False

            async def lag_probe(period=0.005):
                t = time.perf_counter()
                while not stop_probe:
                    await asyncio.sleep(period)
                    now = time.perf_counter()
                    lags.append(max(0.0, now - t - period))
                    t = now

            probe = asyncio.get_event_loop().create_task(lag_probe())
            delivered = {sid: [] for sid in sids}
            done_at = {}
            opts = dict(clean_session=False)
            t_storm = time.perf_counter()

            def make_deliver(sid):
                def deliver(msg):
                    got = delivered[sid]
                    got.append(msg.payload)
                    if len(got) >= backlog and sid not in done_at:
                        done_at[sid] = time.perf_counter() - t_storm
                    return True
                return deliver

            for i, sid in enumerate(sids):
                q = broker.registry._start_queue(sid, QueueOpts(**opts))
                # lazy in the batched leg (collector loads on attach);
                # the baseline gate fails lazy and reads synchronously
                # right here — the pre-PR read_all-per-session path
                broker.recover_offline(sid, q, lazy=True)
                q.add_session(object(), make_deliver(sid))
                if (i + 1) % 200 == 0:
                    await asyncio.sleep(0)
            deadline = time.perf_counter() + 120
            while (len(done_at) < len(sids)
                   and time.perf_counter() < deadline):
                await asyncio.sleep(0.01)
            drain_s = time.perf_counter() - t_storm
            stop_probe = True
            await probe
            expect = ([b"B%d" % j * 8 for j in range(broadcast)]
                      + [b"p%d" % j for j in range(n_unique)])
            bad_order = sum(1 for sid in sids
                            if delivered[sid] != expect)
            lat = sorted(done_at.values())

            def pct(xs, q):
                return (round(xs[min(len(xs) - 1, int(q * len(xs)))]
                              * 1e3, 2) if xs else None)

            rc = broker._resume_collector
            out = {
                "sessions": n, "backlog_per_session": backlog,
                "journal_engine": getattr(broker.msg_store,
                                          "engine_kind", "?"),
                "populate_s": round(populate_s, 2),
                "drain_s": round(drain_s, 3),
                "replay_msgs_per_sec": round(
                    len(done_at) * backlog / max(drain_s, 1e-9)),
                "replay_ms_p50": pct(lat, 0.50),
                "replay_ms_p99": pct(lat, 0.99),
                "loop_lag_ms_p99": pct(sorted(lags), 0.99),
                "loop_lag_ms_max": (round(max(lags) * 1e3, 2)
                                    if lags else None),
                "sessions_resumed": len(done_at),
                "parity_ok": (len(done_at) == len(sids)
                              and bad_order == 0
                              and broker.metrics.value(
                                  "queue_message_drop") == 0),
                "resume": ({k: int(v) for k, v in rc.stats().items()}
                           if rc is not None else None),
            }
            return out
        finally:
            await broker.stop()
            await server.stop()
            shutil.rmtree(tmp, ignore_errors=True)

    async def run():
        batched = await leg(True, n_sessions)
        baseline = await leg(False, n_baseline)
        speedup = (batched["replay_msgs_per_sec"]
                   / max(1, baseline["replay_msgs_per_sec"]))
        import jax as _jax

        return {
            "cpu_smoke": _jax.devices()[0].platform != "tpu",
            "batched": batched,
            "read_all_baseline": baseline,
            "speedup_vs_read_all": round(speedup, 2),
            # bounded RELATIVE to the per-session baseline at the same
            # scale (an absolute self-referential bound would be
            # vacuous): the batched tail must not regress past it
            "replay_p99_bounded": (
                batched["replay_ms_p99"] is not None
                and baseline["replay_ms_p99"] is not None
                and batched["replay_ms_p99"]
                <= baseline["replay_ms_p99"] * 1.25),
            "loop_lag_bounded": (
                batched["loop_lag_ms_p99"] is not None
                and batched["loop_lag_ms_p99"] < 500.0),
            "parity_ok": batched["parity_ok"] and baseline["parity_ok"],
        }

    return asyncio.run(run())


def config15_elastic_storm(smoke, seed=31):
    """Robustness config: drain a node mid-QoS1-storm (ISSUE 18).

    Two clustered brokers; a fleet of persistent QoS1 subscriber
    sessions homed on node A goes offline with publish load still
    arriving. Mid-storm, `vmq-admin cluster drain-node` (library form:
    ``handoff.drain_node``) evacuates every queue to node B through
    the freeze->drain->fence->adopt FSM while publishing CONTINUES.
    Every session then reconnects at node B and replays its backlog.

    Reports zero-QoS>=1-loss parity across the move (every payload
    published before, during, and after the drain is delivered;
    duplicates counted separately — at-least-once), the per-handoff
    pause p99 (the stage_handoff_pause_ms histogram), and a wedged-
    drain drill: a wedge fault at the ``cluster.handoff`` seam hangs
    one drain, the phase deadline rolls it back, and the old owner
    still serves — rollback latency must stay within the deadline
    budget, not the 60s hang cap."""
    import asyncio
    import time as _time

    async def run():
        from vernemq_tpu.broker.config import Config
        from vernemq_tpu.broker.server import start_broker
        from vernemq_tpu.client import MQTTClient
        from vernemq_tpu.cluster import Cluster
        from vernemq_tpu.robustness import faults

        n_sessions = 8 if smoke else 40
        n_rounds = 4 if smoke else 12      # publish rounds per phase
        wedge_deadline_s = 0.5 if smoke else 1.0

        nodes = []
        for i in range(2):
            cfg = Config(systree_enabled=False, allow_anonymous=True,
                         handoff_drain_deadline_s=10.0)
            broker, server = await start_broker(cfg, port=0,
                                                node_name=f"node{i}")
            broker.node_name = broker.metadata.node_name = f"node{i}"
            broker.registry.node_name = f"node{i}"
            broker.registry.db.node_name = f"node{i}"
            cluster = Cluster(broker, "127.0.0.1", 0)
            await cluster.start()
            nodes.append((broker, server, cluster))
        a, b = nodes
        b[2].join(a[2].listen_host, a[2].listen_port)
        while not (len(a[2].members()) == 2 and a[2].is_ready()
                   and b[2].is_ready()):
            await asyncio.sleep(0.02)

        # persistent QoS1 fleet homed on node A, then offline
        for s in range(n_sessions):
            cl = MQTTClient("127.0.0.1", a[1].port, client_id=f"es{s}",
                            clean_start=False)
            await cl.connect()
            await cl.subscribe(f"es/{s}/#", qos=1)
            await cl.disconnect()

        pub = MQTTClient("127.0.0.1", a[1].port, client_id="es-pub")
        await pub.connect()
        sent = [set() for _ in range(n_sessions)]
        seq = 0

        async def publish_round():
            nonlocal seq
            for s in range(n_sessions):
                payload = b"e%d" % seq
                await pub.publish(f"es/{s}/t", payload, qos=1)
                sent[s].add(payload)
                seq += 1

        for _ in range(n_rounds):           # pre-drain storm
            await publish_round()

        # drain node A while the storm continues: publisher keeps
        # hammering the DRAINING node concurrently with the handoffs
        storm = asyncio.get_event_loop().create_task(
            _keep_publishing(publish_round, n_rounds))
        t0 = _time.perf_counter()
        summary = await a[0].handoff.drain_node()
        drain_s = _time.perf_counter() - t0
        await storm
        for _ in range(n_rounds):           # post-drain storm
            await publish_round()

        pauses = sorted(r.get("pause_ms", 0.0)
                        for r in a[0].handoff.history
                        if r.get("result") == "completed")
        pause_p99 = (pauses[min(len(pauses) - 1,
                                int(0.99 * len(pauses)))]
                     if pauses else None)

        # every session reconnects at node B and replays its backlog
        missing = dupes = received = 0
        for s in range(n_sessions):
            cl = MQTTClient("127.0.0.1", b[1].port, client_id=f"es{s}",
                            clean_start=False)
            await cl.connect()
            got = {}
            want = set(sent[s])
            deadline = _time.perf_counter() + 20
            while (set(got) < want
                   and _time.perf_counter() < deadline):
                try:
                    m = await cl.recv(2)
                except asyncio.TimeoutError:
                    break
                got[m.payload] = got.get(m.payload, 0) + 1
            await cl.disconnect()
            received += len(got)
            missing += len(want - set(got))
            dupes += sum(c - 1 for c in got.values())

        # wedged-drain drill: one fresh queue, a wedge at the handoff
        # seam; the drain deadline must roll it back with the OLD
        # owner still serving (bounded pause, not an outage)
        wcl = MQTTClient("127.0.0.1", b[1].port, client_id="es-wedge",
                         clean_start=False)
        await wcl.connect()
        await wcl.subscribe("es-wedge/#", qos=1)
        await wcl.disconnect()
        await pub.publish("es-wedge/t", b"wedged", qos=1)
        wsid = ("", "es-wedge")
        while len(b[0].registry.queues[wsid].offline) != 1:
            await asyncio.sleep(0.02)
        b[0].config.set("handoff_drain_deadline_s", wedge_deadline_s)
        faults.install(faults.FaultPlan([faults.FaultRule(
            "cluster.handoff", kind="wedge", after=1, count=1)],
            seed=seed))
        try:
            w0 = _time.perf_counter()
            ok = await b[0].handoff.handoff_session(wsid, "node0")
            wedge_rollback_s = _time.perf_counter() - w0
        finally:
            faults.clear()
        wedge_ok = (ok is False
                    and wedge_rollback_s < wedge_deadline_s + 1.0
                    and len(b[0].registry.queues[wsid].offline) == 1)

        await pub.disconnect()
        for broker, server, cluster in nodes:
            await cluster.stop()
            await broker.stop()
            await server.stop()

        published = sum(len(x) for x in sent)
        return {
            "sessions": n_sessions,
            "published": published,
            "received": received,
            "missing": missing,
            "duplicates": dupes,
            "drain_moved": summary["sessions"]["moved"],
            "drain_failed": summary["sessions"]["failed"],
            "drain_s": round(drain_s, 3),
            "handoff_pause_ms_p99": pause_p99,
            "wedge_rollback_s": round(wedge_rollback_s, 3),
            "wedge_rolled_back_in_deadline": wedge_ok,
            "parity_ok": missing == 0 and wedge_ok,
        }

    async def _keep_publishing(publish_round, rounds):
        import asyncio as _a
        for _ in range(rounds):
            await publish_round()
            await _a.sleep(0)

    return asyncio.run(run())


def config16_membership_churn_storm(smoke, seed=31):
    """Robustness config: membership churn storm (ISSUE 20).

    Three clustered brokers with the health plane tuned hot. A fleet
    of persistent QoS1 sessions is homed on a victim node; another
    fleet homed on a survivor takes continuous publish load. Three
    phases:

    1. **Kill** — the victim's links are severed (crash semantics, no
       leave). The accrual detector must declare it down and the
       quorum-gated planner auto-evacuates its sessions to the
       least-loaded survivors. Measures detection latency
       (kill -> member_down) and evacuation pause (down -> every
       record rewritten). Post-evacuation publishes to the victim
       fleet must be deliverable (memory-store loss physics: only
       payloads published after adoption count toward the audit).
    2. **Flap** — the victim is revived, then isolated/healed in
       cycles. The hysteresis + per-peer cooldown rails must hold the
       planner to the single phase-1 cycle: evacuated records never
       bounce back (ping-pong count 0).
    3. **Quorum drill** — one survivor is fully isolated: its planner
       sees every peer down but must refuse to act (no majority
       visibility), counted by handoff_auto_skipped_no_quorum.

    Ends with the zero-loss audit: every fleet session reconnects at
    its record owner and must replay every counted payload (dupes
    allowed — at-least-once; loss never)."""
    import asyncio
    import time as _time

    async def run():
        from vernemq_tpu.broker.config import Config
        from vernemq_tpu.broker.server import start_broker
        from vernemq_tpu.client import MQTTClient
        from vernemq_tpu.cluster import Cluster
        from vernemq_tpu.cluster.health import ALIVE, DOWN

        n_victim = 4 if smoke else 16
        n_keep = 4 if smoke else 16
        n_flaps = 2 if smoke else 4
        per_round = 3 if smoke else 6

        cfg_kw = dict(
            systree_enabled=False, allow_anonymous=True,
            # debounce stays at the production default (1.5s): it is the
            # correlated-failure confirmation window the phase-3 quorum
            # drill depends on — an isolated node's two DOWN verdicts
            # skew by up to the 1s ping phase and must land in ONE
            # batch so the quorum gate sees them together
            health_tick_ms=50, health_phi_down=1.0, health_hold_s=0.5,
            rebalance_cooldown_s=60.0,
            # survivors must keep serving mid-outage, and the reg-sync
            # lock coordinator may hash onto the dead member
            allow_register_during_netsplit=True,
            allow_publish_during_netsplit=True,
            allow_subscribe_during_netsplit=True,
            coordinate_registrations=False)
        nodes = []
        for i in range(3):
            broker, server = await start_broker(Config(**cfg_kw),
                                                port=0,
                                                node_name=f"node{i}")
            broker.node_name = broker.metadata.node_name = f"node{i}"
            broker.registry.node_name = f"node{i}"
            broker.registry.db.node_name = f"node{i}"
            cluster = Cluster(broker, "127.0.0.1", 0)
            await cluster.start()
            nodes.append((broker, server, cluster))
        a, b, c = nodes
        for n in (b, c):
            n[2].join(a[2].listen_host, a[2].listen_port)
        while not all(len(x[2].members()) == 3 and x[2].is_ready()
                      for x in nodes):
            await asyncio.sleep(0.02)

        async def wait_for(pred, timeout=30.0):
            deadline = _time.perf_counter() + timeout
            while _time.perf_counter() < deadline:
                if pred():
                    return True
                await asyncio.sleep(0.02)
            raise RuntimeError(f"churn-storm wait timed out: {pred}")

        def sever(x, y):
            for s, d in ((x, y), (y, x)):
                w = s[2]._writers.get(d[0].node_name)
                if w is None:
                    continue
                if not hasattr(w, "_real_addr"):
                    w._real_addr = w.addr
                w.addr = ("127.0.0.1", 9)  # discard: connect refused
                if w._writer is not None:
                    w._writer.close()

        def mend(x, y):
            for s, d in ((x, y), (y, x)):
                w = s[2]._writers.get(d[0].node_name)
                if w is not None:
                    w.addr = getattr(w, "_real_addr", w.addr)

        # let the formation-time join cycles settle, then clear the
        # per-peer cooldown windows so phase 1 starts from quiet
        await wait_for(lambda: all(
            len(x[2].planner._cooldown_until) >= 2 for x in nodes))
        for x in nodes:
            x[2].planner._cooldown_until.clear()
        cycles0 = a[2].planner.cycles

        # victim fleet homed on node2, survivor fleet on node0
        for s in range(n_victim):
            cl = MQTTClient("127.0.0.1", c[1].port, client_id=f"vs{s}",
                            clean_start=False)
            await cl.connect()
            await cl.subscribe(f"vs/{s}/#", qos=1)
            await cl.disconnect()
        for s in range(n_keep):
            cl = MQTTClient("127.0.0.1", a[1].port, client_id=f"ks{s}",
                            clean_start=False)
            await cl.connect()
            await cl.subscribe(f"ks/{s}/#", qos=1)
            await cl.disconnect()

        pub = MQTTClient("127.0.0.1", b[1].port, client_id="cs-pub")
        await pub.connect()
        sent_keep = [set() for _ in range(n_keep)]
        sent_victim = [set() for _ in range(n_victim)]
        seq = 0

        async def keep_round():
            nonlocal seq
            for s in range(n_keep):
                payload = b"k%d" % seq
                await pub.publish(f"ks/{s}/t", payload, qos=1)
                sent_keep[s].add(payload)
                seq += 1

        async def victim_round():
            nonlocal seq
            for s in range(n_victim):
                payload = b"v%d" % seq
                await pub.publish(f"vs/{s}/t", payload, qos=1)
                sent_victim[s].add(payload)
                seq += 1

        for _ in range(per_round):
            await keep_round()

        # ---- phase 1: kill the victim (no leave), auto-evacuate
        vsids = [("", f"vs{s}") for s in range(n_victim)]
        t_kill = _time.perf_counter()
        sever(a, c)
        sever(b, c)
        await wait_for(
            lambda: a[2].health.state_of("node2") == DOWN)
        detect_s = _time.perf_counter() - t_kill
        t_down = _time.perf_counter()
        for x in (a, b):  # survivors converge on the rewritten records
            await wait_for(lambda x=x: all(
                (r := x[0].registry.db.read(sid)) is not None
                and r.node in ("node0", "node1") for sid in vsids))
        evacuate_s = _time.perf_counter() - t_down
        evacuated = a[0].metrics.value("handoff_auto_evacuations")
        for _ in range(per_round):  # post-adoption: these must survive
            await victim_round()
            await keep_round()

        # ---- phase 2: revive, then flap — evacuated records must not
        # ping-pong back to the flapper
        owners = {sid: a[0].registry.db.read(sid).node for sid in vsids}
        ping_pong = 0
        mend(a, c)
        mend(b, c)
        await wait_for(
            lambda: a[2].health.state_of("node2") == ALIVE)
        for _ in range(n_flaps):
            sever(a, c)
            sever(b, c)
            await wait_for(
                lambda: a[2].health.state_of("node2") == DOWN)
            await keep_round()
            mend(a, c)
            mend(b, c)
            await wait_for(
                lambda: a[2].health.state_of("node2") == ALIVE)
            for sid in vsids:
                now_node = a[0].registry.db.read(sid).node
                if now_node != owners[sid]:
                    ping_pong += 1
                    owners[sid] = now_node
        await victim_round()
        cycles = a[2].planner.cycles - cycles0
        suppressed = a[0].metrics.value("handoff_auto_suppressed")

        # ---- zero-loss audit at the record owners (before the quorum
        # drill: the majority side legitimately evacuates the isolated
        # node's sessions there, which rewrites the keep-fleet records
        # away from where their backlogs physically live)
        by_name = {"node0": a, "node1": b, "node2": c}
        missing = dupes = received = 0

        async def replay(client_id, sid, want):
            nonlocal missing, dupes, received
            owner = by_name[a[0].registry.db.read(sid).node]
            cl = MQTTClient("127.0.0.1", owner[1].port,
                            client_id=client_id, clean_start=False)
            await cl.connect()
            got = {}
            deadline = _time.perf_counter() + 20
            while (set(got) < want
                   and _time.perf_counter() < deadline):
                try:
                    m = await cl.recv(2)
                except asyncio.TimeoutError:
                    break
                got[m.payload] = got.get(m.payload, 0) + 1
            await cl.disconnect()
            received += len(got)
            missing += len(want - set(got))
            dupes += sum(n - 1 for n in got.values())

        for s in range(n_keep):
            await replay(f"ks{s}", ("", f"ks{s}"), set(sent_keep[s]))
        for s in range(n_victim):
            await replay(f"vs{s}", ("", f"vs{s}"), set(sent_victim[s]))

        # ---- phase 3: quorum drill — an isolated minority must refuse
        sever(a, b)
        sever(a, c)
        await wait_for(lambda: a[0].metrics.value(
            "handoff_auto_skipped_no_quorum") >= 1)
        minority_acted = (a[2].planner.cycles - cycles0) > cycles
        mend(a, b)
        mend(a, c)
        await wait_for(lambda: all(
            a[2].health.state_of(n) == ALIVE
            for n in ("node1", "node2")))

        await pub.disconnect()
        for broker, server, cluster in nodes:
            await cluster.stop()
            await broker.stop()
            await server.stop()

        published = (sum(len(x) for x in sent_keep)
                     + sum(len(x) for x in sent_victim))
        return {
            "victim_sessions": n_victim,
            "keep_sessions": n_keep,
            "flaps": n_flaps,
            "detect_s": round(detect_s, 3),
            "evacuate_pause_s": round(evacuate_s, 3),
            "evacuated": evacuated,
            "planner_cycles": cycles,
            "suppressed_cycles": suppressed,
            "ping_pong": ping_pong,
            "quorum_refusals": a[0].metrics.value(
                "handoff_auto_skipped_no_quorum"),
            "minority_acted": minority_acted,
            "published": published,
            "received": received,
            "missing": missing,
            "duplicates": dupes,
            "parity_ok": (missing == 0 and ping_pong == 0
                          and evacuated >= n_victim
                          and not minority_acted),
        }

    return asyncio.run(run())


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--subs", type=int, default=1_000_000)
    ap.add_argument("--batch", type=int, default=4096)
    ap.add_argument("--iters", type=int, default=40)
    ap.add_argument("--max-fanout", type=int, default=256)
    ap.add_argument("--levels", type=int, default=8)
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--variant", default="packed",
                    choices=["packed", "packed_rows", "packed_stack",
                             "flat", "rows", "pallas"],
                    help="windowed-kernel transport/merge variant "
                    "(packed = production default: single-vector I/O; "
                    "packed_stack = N batches per executable + ONE "
                    "result pull)")
    ap.add_argument("--stack", type=int, default=8,
                    help="batches per executable for --variant "
                    "packed_stack")
    ap.add_argument("--mesh-rung", type=int, default=0,
                    help="internal: run ONE mesh-ladder rung at this "
                    "slice count in-process (config 12 spawns these "
                    "with forced host device counts)")
    ap.add_argument("--reconnect-sessions", type=int, default=0,
                    help="config 14 session count override (default: "
                         "100k, 20k on CPU smoke)")
    ap.add_argument("--configs",
                    default="1,2,3,4,5,6,7,8,9,10,11,12,13,14,15,16",
                    help="which BASELINE configs to run (3 = headline; "
                    "6 = fault-storm robustness: publish p99 while the "
                    "device path is down + breaker recovery time; "
                    "7 = partition storm: two brokers, inter-node link "
                    "severed under QoS1 load — spool replay throughput "
                    "+ zero-loss parity; 8 = retained subscribe storm: "
                    "wildcard SUBSCRIBE bursts vs 100k-1M retained — "
                    "device reverse-match rate vs the serial host walk; "
                    "9 = overload storm: offered load past capacity, "
                    "binary shedding vs the adaptive governor on "
                    "well-behaved goodput/p99 + recovery time; "
                    "11 = admission storm: SO_REUSEPORT worker scaling "
                    "at workers 1/2/4 — admitted pubs/s, CONNECT p99, "
                    "per-worker loop lag, fanout parity; "
                    "12 = mesh ladder: mesh-native matcher at 1/2/4 "
                    "forced-host-device slices — per-slice rows, "
                    "delta-routing hit rate, parity vs the "
                    "single-process sharded oracle; "
                    "16 = membership churn storm: kill/flap/quorum "
                    "drills against the accrual detector + auto-"
                    "rebalance — detection latency, evacuation pause, "
                    "ping-pong count, zero-loss audit)")
    ap.add_argument("--platform", default=None,
                    help="force a jax platform (e.g. cpu)")
    ap.add_argument("--kernel-only", action="store_true",
                    help="also run the device-resident kernel throughput "
                    "probe on CPU (always runs on an accelerator)")
    args = ap.parse_args()

    if args.mesh_rung:
        # one mesh-ladder rung inside the forced-device-count env the
        # parent set — never touches the accelerator probe machinery
        return _mesh_rung_main(args.mesh_rung, args.subs, args.seed,
                               args.iters)

    jax, devices = init_backend(args.platform)
    platform = devices[0].platform
    smoke = platform == "cpu"
    if smoke:
        # smoke-scale on CPU so the bench stays runnable anywhere
        args.subs = min(args.subs, 100_000)
        args.iters = min(args.iters, 4)
        args.batch = min(args.batch, 1024)

    from vernemq_tpu.models.tpu_table import SubscriptionTable

    want = {c.strip() for c in args.configs.split(",") if c.strip()}
    # packed_stack shares the packed kernel/prep; only config 3's run
    # loop differs (grouped dispatch)
    kernel_variant = ("packed" if args.variant == "packed_stack"
                      else args.variant)
    rng = random.Random(args.seed)
    configs: dict = {}
    note(f"[bench] platform={platform} subs={args.subs} batch={args.batch}")

    failed: list = []  # configs that raised: the run exits non-zero

    def guarded(name, fn):
        # one ladder rung failing (OOM at 5M) must not lose the other
        # rungs' numbers — record the error, keep going, and exit
        # non-zero at the end. Every
        # config also gets the per-seam stage-latency attribution: the
        # delta of the process-global stage histograms across its run
        # (p50/p99/p99.9 per instrumented seam) travels in the artifact,
        # so BENCH_*.json carries WHERE the time went, not just totals.
        before = _stage_snapshot()
        try:
            configs[name] = fn()
            breakdown = stage_breakdown(before)
            if breakdown:
                configs[name]["stage_latency"] = breakdown
            note(f"[bench] {name} {configs[name]}")
        except Exception as e:
            import traceback

            traceback.print_exc(file=sys.stderr)
            configs[name] = {"error": f"{type(e).__name__}: {e}"}
            failed.append(name)

    if "1" in want:
        guarded("1_exact_1k_host_trie", lambda: config1_host_trie(rng))

    if "2" in want:
        def _cfg2():
            n2 = 100_000 if not smoke else 20_000
            t2 = SubscriptionTable(
                max_levels=args.levels,
                initial_capacity=1 << (n2 - 1).bit_length())
            l0 = [f"r{i}" for i in range(64)]
            l1 = [f"d{i}" for i in range(128)]
            l2 = [f"m{i}" for i in range(32)]
            for i in range(n2):
                t2.add([rng.choice(l0), "+", rng.choice(l2)]
                       if i % 2 else
                       [rng.choice(l0), rng.choice(l1), rng.choice(l2)],
                       i, None)
            wb2 = WindowedBench(jax, t2, (l0, l1, l2), rng,
                                min(args.batch, 2048), args.max_fanout,
                                variant=kernel_variant)
            r2 = wb2.run(max(8, args.iters // 2), measure_resolve=False)
            try:
                r2.update(host_trie_like_for_like(t2, (l0, l1, l2),
                                                  args.seed + 101))
            except Exception as e:
                note(f"[bench] cfg2 trie baseline failed: "
                     f"{type(e).__name__}: {e}")
            return {k: round(v, 3) if isinstance(v, float) else v
                    for k, v in r2.items() if v is not None}

        guarded("2_wildcard_100k", _cfg2)

    headline = None
    table = None
    pools = None
    if "3" in want or "4" in want:
        _cfg3_stage_before = _stage_snapshot()
        shared = 0.1 if "4" in want else 0.0
        table = SubscriptionTable(
            max_levels=args.levels,
            initial_capacity=1 << (args.subs - 1).bit_length())
        t0 = time.perf_counter()
        pools = build_corpus(rng, args.subs, table, shared_frac=shared)
        build_s = time.perf_counter() - t0
        note(f"[bench] corpus built in {build_s:.1f}s")
        wb = WindowedBench(jax, table, pools, rng, args.batch,
                           args.max_fanout, variant=kernel_variant)
        note(f"[bench] upload {wb.upload_s:.1f}s; running config 3...")
        headline = (wb.run_stacked(args.iters, args.stack)
                    if args.variant == "packed_stack"
                    else wb.run(args.iters))
        headline["build_s"] = round(build_s, 2)
        try:
            headline.update(host_trie_like_for_like(table, pools,
                                                    args.seed + 103))
        except Exception as e:
            note(f"[bench] trie baseline failed: {type(e).__name__}: {e}")
        if kernel_variant == "packed" and (args.kernel_only
                                         or platform != "cpu"):
            # device-resident kernel throughput: what the chip sustains
            # vs what the transport allows (its ceiling is
            # matches/s <= bandwidth / 4B of result ids)
            try:
                headline.update(wb.run_kernel_only())
            except Exception as e:
                note(f"[bench] kernel-only probe failed: "
                     f"{type(e).__name__}: {e}")
        if kernel_variant == "packed":
            # K-batch dispatch-amortization ladder (match_many): the
            # trajectory metric for the multi-batch pipeline — dispatch
            # overhead per batch must fall ~1/K
            try:
                headline["match_many_probe"] = match_many_probe(
                    wb, reps=1 if smoke else 2,
                    probe_batch=min(args.batch, 256) if smoke
                    else args.batch)
                note(f"[bench] match_many probe "
                     f"{headline['match_many_probe']}")
            except Exception as e:
                note(f"[bench] match_many probe failed: "
                     f"{type(e).__name__}: {e}")
        # per-seam attribution of the REAL config-3 workload — captured
        # BEFORE the overhead probe below, whose synthetic interleaved
        # match_batch reps would otherwise skew the very breakdown this
        # artifact exists to carry
        _cfg3_stages = stage_breakdown(_cfg3_stage_before)
        # acceptance overhead guard: publish p50 through the
        # instrumented production path with observability on vs off —
        # both numbers (and the regression pct) travel in the artifact
        try:
            headline["observability"] = observability_overhead_probe(
                wb, reps=12 if smoke else 40)
            note(f"[bench] observability overhead "
                 f"{headline['observability']}")
        except Exception as e:
            note(f"[bench] observability probe failed: "
                 f"{type(e).__name__}: {e}")
        configs["3_mixed_1m_zipf"] = {
            k: round(v, 3) if isinstance(v, float) else v
            for k, v in headline.items() if v is not None}
        configs["3_mixed_1m_zipf"]["stage_latency"] = _cfg3_stages
        note(f"[bench] config3 {configs['3_mixed_1m_zipf']}")

    if "4" in want and table is not None and headline is not None:
        guarded("4_shared_retained_1m", lambda: config4_shared_retained(
            jax, rng, table, pools, args.batch, headline))

    def _cfg5():
        n5 = 5_000_000 if not smoke else 50_000
        t5 = SubscriptionTable(max_levels=args.levels,
                               initial_capacity=1 << (n5 - 1).bit_length())
        t0 = time.perf_counter()
        pools5 = build_corpus(rng, n5, t5)
        build5 = time.perf_counter() - t0
        wb5 = WindowedBench(jax, t5, pools5, rng,
                            min(args.batch, 2048), args.max_fanout,
                            variant=kernel_variant)
        r5 = wb5.run(max(6, args.iters // 4), measure_resolve=False)
        # delta streaming: steady-state subscribe/unsubscribe applied as
        # device scatters between batches (BASELINE config 5; multi-node
        # correctness is covered by dryrun_multichip on the virtual mesh)
        lat = []
        l0, l1, l2 = pools5
        for i in range(20):
            with wb5.m.lock:
                for j in range(100):
                    t5.add([rng.choice(l0), rng.choice(l1), f"new{i}-{j}"],
                           10_000_000 + i * 1000 + j, None)
            t1 = time.perf_counter()
            with wb5.m.lock:
                wb5.m.sync()
            # honest sync: a host transfer proves the scatter landed
            # (1-element pull)
            np.asarray(wb5.m._dev_arrays[1][:1])
            lat.append(time.perf_counter() - t1)
        # pipelined steady state: back-to-back deltas, one honest sync
        # at the end — the per-delta cost when churn batches overlap
        # (the synced number above charges a full RTT to every delta).
        # Host-side table.add time stays OUTSIDE the clock so this is
        # directly comparable to the synced loop's sync-only timing.
        pipelined_s = 0.0
        for i in range(20, 40):
            with wb5.m.lock:
                for j in range(100):
                    t5.add([rng.choice(l0), rng.choice(l1), f"new{i}-{j}"],
                           10_000_000 + i * 1000 + j, None)
            t1 = time.perf_counter()
            with wb5.m.lock:
                wb5.m.sync()
            pipelined_s += time.perf_counter() - t1
        t1 = time.perf_counter()
        np.asarray(wb5.m._dev_arrays[1][:1])
        pipelined_ms = (pipelined_s + time.perf_counter() - t1) / 20 * 1e3
        # subscribe -> first-matchable-publish latency (VERDICT r3 item
        # 4): wall time from table.add of a FRESH filter until a match
        # of its topic returns the new subscriber — covers delta encode
        # + device scatter + the match itself (the reference applies trie
        # events synchronously, vmq_reg_trie.erl:198-210: its bound is
        # one ETS insert; ours is one delta sync + one batch)
        s2m = []
        for i in range(12):
            probe_topic = (rng.choice(l0), rng.choice(l1), f"s2m{i}")
            probe_key = 20_000_000 + i
            t1 = time.perf_counter()
            with wb5.m.lock:
                t5.add(list(probe_topic), probe_key, None)
            for _ in range(50):
                rows = wb5.m.match_batch([probe_topic])[0]
                if any(r[1] == probe_key for r in rows):
                    break
            else:
                raise RuntimeError("probe sub never became matchable")
            s2m.append(time.perf_counter() - t1)
        trie5 = {}
        try:
            trie5 = host_trie_like_for_like(t5, pools5, args.seed + 105,
                                            n_probe=3000)
        except Exception as e:
            note(f"[bench] cfg5 trie baseline failed: "
                 f"{type(e).__name__}: {e}")
        return {
            "subs": n5,
            "matches_per_sec": round(r5["matches_per_sec"]),
            "publishes_per_sec": round(r5["publishes_per_sec"]),
            "batch_ms": round(r5["batch_ms"], 3),
            "build_s": round(build5, 2),
            "upload_s": r5["upload_s"],
            **trie5,
            "delta_apply_ms_p50": round(1e3 * float(np.percentile(lat, 50)), 3),
            "delta_apply_ms_p99": round(1e3 * float(np.percentile(lat, 99)), 3),
            "delta_apply_ms_pipelined": round(pipelined_ms, 3),
            "sub_to_matchable_ms_p50": round(
                1e3 * float(np.percentile(s2m, 50)), 3),
            "sub_to_matchable_ms_max": round(1e3 * max(s2m), 3),
        }

    if "5" in want:
        guarded("5_delta_stream_5m", _cfg5)

    if "6" in want:
        guarded("6_fault_storm", lambda: config6_fault_storm(
            jax, rng, args.subs, args.batch, smoke))

    if "7" in want:
        guarded("7_partition_storm",
                lambda: config7_partition_storm(smoke))

    if "8" in want:
        guarded("8_retained_storm",
                lambda: config8_retained_storm(rng, smoke))

    if "9" in want:
        guarded("9_overload_storm",
                lambda: config9_overload_storm(smoke))

    if "10" in want:
        guarded("10_stall_storm",
                lambda: config10_stall_storm(smoke))

    if "11" in want:
        guarded("11_admission_storm",
                lambda: config11_admission_storm(smoke))

    if "12" in want:
        guarded("12_mesh_ladder",
                lambda: config12_mesh_ladder(smoke, args.seed,
                                             args.subs))

    if "13" in want:
        guarded("13_downsampling_storm",
                lambda: config13_downsampling_storm(smoke, args.seed))

    if "14" in want:
        guarded("14_reconnect_storm",
                lambda: config14_reconnect_storm(
                    smoke, sessions=args.reconnect_sessions or None))

    if "15" in want:
        guarded("15_elastic_storm",
                lambda: config15_elastic_storm(smoke, args.seed))

    if "16" in want:
        guarded("16_membership_churn_storm",
                lambda: config16_membership_churn_storm(smoke, args.seed))

    if headline is not None:
        value = headline["matches_per_sec"]
    elif "2_wildcard_100k" in configs:
        value = configs["2_wildcard_100k"]["matches_per_sec"]
    else:
        value = configs.get("1_exact_1k_host_trie", {}).get(
            "matches_per_sec", 0)

    # stamp the ACTUAL scale into the metric string: a reduced-scale
    # fallback run must not read as a 1M-sub result at a glance
    if args.subs >= 1_000_000:
        scale = f"{args.subs / 1e6:g}M"
    elif args.subs >= 1000:
        scale = f"{args.subs / 1e3:g}k"
    else:
        scale = str(args.subs)
    result = {
        "metric": f"topic-matches/sec @{scale} subs (config 3: mixed "
                  "wildcards, zipf stream, windowed kernel)",
        "value": round(value),
        "unit": "matches/s",
        "vs_baseline": round(value / TARGET_MATCHES_PER_SEC, 4),
        "platform": platform,
        "subs": args.subs,
        "batch": args.batch,
        "configs": configs,
    }
    if platform == "cpu":
        result["note"] = (
            "CPU smoke run (accelerator unreachable or forced): "
            "reduced scale, not comparable to TPU numbers")
    # analytical chip ceiling at the headline geometry (ROOFLINE.md /
    # tools/roofline.py): travels with every artifact so a fallback run
    # still records what the formulation supports
    result["roofline"] = ("chip ceiling 35M-327M matches/s @1M subs "
                          "B=4096 (647MB+146GFLOP/batch; ROOFLINE.md)")
    if headline is not None:
        result.update({
            "publishes_per_sec": round(headline["publishes_per_sec"]),
            "avg_fanout": round(headline["avg_fanout"], 2),
            "batch_ms": round(headline["batch_ms"], 3),
            "encode_ms": round(headline["encode_ms"], 3),
            "prep_ms": round(headline["prep_ms"], 3),
            "table_mb": round(table.stats()["table_bytes"] / 1e6, 1),
        })
        if "synced_batch_ms_p99" in headline:  # absent in stacked mode
            result["synced_batch_ms_p99"] = round(
                headline["synced_batch_ms_p99"], 3)
        if "kernel_matches_per_sec" in headline:
            # the device-resident probe: what the chip sustains with
            # zero per-batch transport: the hardware's own ceiling,
            # reported alongside (never AS) the end-to-end figure.
            result["kernel_matches_per_sec"] = \
                headline["kernel_matches_per_sec"]
            result["kernel_batch_ms"] = headline["kernel_batch_ms"]
            result["vs_baseline_kernel"] = round(
                headline["kernel_matches_per_sec"] / TARGET_MATCHES_PER_SEC,
                4)
        if "match_many_probe" in headline:
            # dispatch amortization headline: per-batch dispatch
            # overhead at K=1 vs K=8 windows per device call — the
            # trajectory number for the multi-batch pipeline
            amort = headline["match_many_probe"]["amortized_dispatch_ms"]
            result["amortized_dispatch_ms"] = {
                "K1": amort.get("1"), "K8": amort.get("8")}
    if failed:
        result["failed_configs"] = failed
    print(json.dumps(result))
    return 1 if failed else 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Exception as e:  # never a stack trace on stdout: one JSON line
        import traceback

        traceback.print_exc(file=sys.stderr)
        print(json.dumps({
            "metric": "topic-matches/sec @1M subs (config 3)",
            "value": 0, "unit": "matches/s", "vs_baseline": 0.0,
            "error": f"{type(e).__name__}: {e}",
        }))
        sys.exit(1)
