"""Roofline arithmetic for the windowed match kernel (VERDICT r4 item 1:
"write the arithmetic: bytes touched per batch vs HBM bandwidth at the
current geometry, and state whether THIS kernel formulation can reach
10M matches/s").

Builds the bench corpus at the requested scale, derives the EXACT kernel
geometry the production matcher would use for the batch size (same
window_params/_geometry code path), and counts the HBM bytes and MXU
FLOPs each batch touches:

- dense phase: Fg [K, glob] bf16 re-streamed per pub chunk (gc pubs at a
  time), plus t1/epilogue vectors per chunk;
- probe-A/B tiles: each of T (T2) tiles streams a [K, seg_max] (seg2)
  operand window + epilogue vectors;
- intermediates: the [TP, seg] f32 mismatch block per tile and the
  [gc, glob] dense block — XLA fuses the compare+pack, so these are
  compute-layer traffic that mostly stays in VMEM/registers; the model
  counts them at a configurable reuse discount (default 0: fused);
- outputs: the packed flat result vector (Bpad*(fa+3) int32).

Ceilings: matches/s <= avg_fanout * Bpad / max(bytes/BW, flops/FLOPS).
v5e defaults: 819 GB/s HBM, 197 TFLOP/s bf16.

The measured companion is bench.py --kernel-only (match_packed_scan —
zero per-batch transport); this file is the analytical half of
ROOFLINE.md. Runs fine on CPU: it executes no kernel, it only sizes one.
"""
import argparse
import json
import os
import random
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def run_probe(args) -> None:
    """The MEASURED half of the amortization story: run the
    kernel-resident multi-batch probe (bench.match_many_probe — K
    batches per scanned executable, donated staging) standalone, at
    smoke scale on CPU or full scale on an accelerator. This is the
    empirical companion to the analytic model below: dispatch cost
    amortizes as dispatch/K + kernel_cost per batch."""
    import random as _random

    from bench import WindowedBench, build_corpus, init_backend, \
        match_many_probe
    from vernemq_tpu.models.tpu_table import SubscriptionTable

    jax_mod, devices = init_backend(args.platform)
    platform = devices[0].platform
    smoke = platform == "cpu"
    subs = min(args.subs, 100_000) if smoke else args.subs
    batch = args.probe_batch or (min(args.batch, 256) if smoke
                                 else args.batch)
    rng = _random.Random(args.seed)
    table = SubscriptionTable(
        max_levels=args.levels,
        initial_capacity=1 << (subs - 1).bit_length())
    t0 = time.perf_counter()
    pools = build_corpus(rng, subs, table)
    print(f"# corpus built in {time.perf_counter()-t0:.0f}s",
          file=sys.stderr, flush=True)
    wb = WindowedBench(jax_mod, table, pools, rng, batch,
                       variant="packed")
    ks = tuple(int(x) for x in args.probe_ks.split(",") if x.strip())
    out = match_many_probe(wb, ks=ks, reps=args.probe_reps,
                           probe_batch=batch)
    out.update({"mode": "measured_match_many_probe",
                "platform": platform,
                "subs": subs, "batch": batch})
    print(json.dumps(out, indent=1))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--subs", type=int, default=1_000_000)
    ap.add_argument("--batch", type=int, default=4096)
    ap.add_argument("--levels", type=int, default=8)
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--hbm-gbps", type=float, default=819.0)
    ap.add_argument("--bf16-tflops", type=float, default=197.0)
    ap.add_argument("--fanout", type=float, default=None,
                    help="avg matches/pub (default: measured on a "
                         "5k-topic host-trie probe of the corpus)")
    ap.add_argument("--flat-avg", type=int, default=128)
    ap.add_argument("--intermediate-factor", type=float, default=0.0,
                    help="fraction of the [pubs, seg] f32 mismatch "
                         "blocks charged to HBM (0 = fully fused)")
    ap.add_argument("--probe", action="store_true",
                    help="RUN the kernel-resident match_many dispatch-"
                         "amortization probe (K-batch ladder, measured) "
                         "instead of the analytic model; smoke-scales "
                         "on CPU")
    ap.add_argument("--platform", default=None,
                    help="--probe: force a jax platform (e.g. cpu); "
                         "without it no accelerator is an error")
    ap.add_argument("--probe-ks", default="1,2,4,8,16")
    ap.add_argument("--probe-reps", type=int, default=2)
    ap.add_argument("--probe-batch", type=int, default=None)
    args = ap.parse_args()

    if args.probe:
        run_probe(args)
        return

    import jax

    jax.config.update("jax_platforms", "cpu")
    from bench import build_corpus, host_trie_like_for_like
    from vernemq_tpu.models.tpu_matcher import TILE_PUBS, window_params
    from vernemq_tpu.models.tpu_table import SubscriptionTable

    rng = random.Random(args.seed)
    table = SubscriptionTable(
        max_levels=args.levels,
        initial_capacity=1 << (args.subs - 1).bit_length())
    t0 = time.perf_counter()
    pools = build_corpus(rng, args.subs, table)
    print(f"# corpus built in {time.perf_counter()-t0:.0f}s",
          file=sys.stderr, flush=True)

    S = table.cap
    L = table.L
    bits = table.id_bits
    K = (5 if bits == 16 else 6) * L  # build_operands planes
    glob = table.reg_cap[0]
    gb_end = table.gb_end
    ng = table.NG
    reg_start = table.reg_start
    reg_end = table.reg_start + table.reg_cap
    Bpad = args.batch
    TP = TILE_PUBS

    amax = (int((reg_end[1 + ng:] - reg_start[1 + ng:]).max())
            if len(reg_start) > 1 + ng else 0)
    T, seg_max, gc = window_params(S, int(glob), amax, Bpad,
                                   zone=S - gb_end)
    if ng:  # same guard as TpuMatcher._geometry
        gmax = int((reg_end[1:1 + ng] - reg_start[1:1 + ng]).max())
        T2, seg2, _ = window_params(S, int(glob), gmax, Bpad,
                                    zone=gb_end - int(glob))
    else:
        T2, seg2 = 0, 0

    BF, F32 = 2, 4
    epi = 4 + 1 + 1 + 1  # eff i32 + hh/fw/act bool per row
    row_bytes = K * BF + F32 + epi  # one streamed table row

    # dense phase: REGION 0 ONLY ([K, glob_pad] — the both-levels-wild
    # filters; the g-bucket zone [glob, gb_end) is served by the probe-B
    # tiles, charged below), re-streamed once per gc-chunk
    n_chunks = -(-Bpad // gc)
    dense_bytes = n_chunks * int(glob) * row_bytes
    # probe tiles: one operand window per tile
    probeA_bytes = T * seg_max * row_bytes
    probeB_bytes = T2 * seg2 * row_bytes
    out_bytes = Bpad * (args.flat_avg + 3) * F32
    pub_bytes = Bpad * (L * F32 + 16)
    inter_bytes = args.intermediate_factor * F32 * (
        n_chunks * gc * int(glob) + (T * TP * seg_max) + (T2 * TP * seg2))
    total_bytes = (dense_bytes + probeA_bytes + probeB_bytes + out_bytes
                   + pub_bytes + inter_bytes)

    flops = 2 * K * (Bpad * int(glob) + T * TP * seg_max
                     + T2 * TP * seg2)

    t_hbm = total_bytes / (args.hbm_gbps * 1e9)
    t_mxu = flops / (args.bf16_tflops * 1e12)
    t_batch = max(t_hbm, t_mxu)

    if args.fanout is None:
        probe = host_trie_like_for_like(table, pools, args.seed + 103,
                                        n_probe=5000)
        fanout = probe["trie_avg_fanout"]
    else:
        fanout = args.fanout

    pubs_per_sec = Bpad / t_batch
    matches_per_sec = fanout * pubs_per_sec
    out = {
        "subs": args.subs, "S_padded": int(S), "K": K, "id_bits": bits,
        "geometry": {"Bpad": Bpad, "gb_end": int(gb_end),
                     "glob": int(glob), "T": int(T),
                     "seg_max": int(seg_max), "gc": int(gc),
                     "T2": int(T2), "seg2": int(seg2),
                     "dense_chunks": n_chunks},
        "bytes_per_batch": {
            "dense": int(dense_bytes), "probeA": int(probeA_bytes),
            "probeB": int(probeB_bytes), "outputs": int(out_bytes),
            "pubs": int(pub_bytes), "intermediates": int(inter_bytes),
            "total": int(total_bytes)},
        "flops_per_batch": int(flops),
        "batch_ms_hbm_bound": round(t_hbm * 1e3, 3),
        "batch_ms_mxu_bound": round(t_mxu * 1e3, 3),
        "bound": "hbm" if t_hbm >= t_mxu else "mxu",
        "avg_fanout": fanout,
        "ceiling_pubs_per_sec": round(pubs_per_sec),
        "ceiling_matches_per_sec": round(matches_per_sec),
        "reaches_10M_matches": matches_per_sec >= 10e6,
        "hbm_gbps": args.hbm_gbps, "bf16_tflops": args.bf16_tflops,
    }
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
