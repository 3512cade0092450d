"""Parameter sweep for the flat windowed kernel on the real chip:
batch size x tile width x window-fairness x flat capacity. Prints one
line per config; run after any kernel change.

Usage:
  python tools/tune_windowed.py [subs] [--cpu] [--rows | --pallas]
      [--tp 128,256] [--b 2048,4096,8192] [--fm 1,2,4] [--fa 128]

Each axis takes a comma list; the grid is their product. Keep the grid
small — every distinct (TP, B, FM) geometry is a fresh compile.
"""
import random
import sys
import time

import numpy as np

import os

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def note(m):
    print(m, file=sys.stderr, flush=True)


def _axis(argv, name, default):
    flag = f"--{name}"
    if flag in argv:
        i = argv.index(flag)
        vals = [int(x) for x in argv[i + 1].split(",")]
        del argv[i:i + 2]
        return vals
    return default


def main():
    argv = sys.argv[1:]
    if "--cpu" in argv:
        argv.remove("--cpu")
        import jax

        jax.config.update("jax_platforms", "cpu")
    variant = "flat"
    if "--rows" in argv:  # gather-merge kernel instead of scatter-flat
        argv.remove("--rows")
        variant = "rows"
    if "--pallas" in argv:  # fused Pallas tile matcher (probe phases)
        argv.remove("--pallas")
        variant = "pallas"
    if "--packed" in argv:  # single-vector I/O transport (production)
        argv.remove("--packed")
        variant = "packed"
    if "--packed-rows" in argv:  # single-vector I/O over the rows kernel
        argv.remove("--packed-rows")
        variant = "packed_rows"
    tps = _axis(argv, "tp", [128, 256])
    bs = _axis(argv, "b", [2048, 4096, 8192])
    fms = _axis(argv, "fm", [2])
    fas = _axis(argv, "fa", [128])
    import jax

    from vernemq_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()

    from bench import WindowedBench, build_corpus
    from vernemq_tpu.models import tpu_matcher as TM
    from vernemq_tpu.models.tpu_table import SubscriptionTable

    subs = int(argv[0]) if argv else 1_000_000
    rng = random.Random(42)
    table = SubscriptionTable(max_levels=8,
                              initial_capacity=1 << (subs - 1).bit_length())
    t0 = time.perf_counter()
    pools = build_corpus(rng, subs, table)
    note(f"corpus {time.perf_counter()-t0:.1f}s platform="
         f"{jax.devices()[0].platform} grid: TP={tps} B={bs} FM={fms} "
         f"FA={fas}")

    best = None
    for tile_pubs in tps:
        TM.TILE_PUBS = tile_pubs
        for fm in fms:
            TM.FAIR_MULT = fm
            for B in bs:
                for fa in fas:
                    tag = f"TP={tile_pubs} FM={fm} B={B} FA={fa} V={variant}"
                    try:
                        wb = WindowedBench(jax, table, pools, rng, B, 256,
                                           flat_avg=fa, variant=variant)
                        r = wb.run(20, warmup=8, measure_resolve=False)
                        note(f"{tag}: "
                             f"{r['matches_per_sec']/1e6:.2f}M matches/s "
                             f"{r['publishes_per_sec']/1e3:.0f}k pubs/s "
                             f"batch={r['batch_ms']:.2f}ms "
                             f"enc={r['encode_ms']:.2f} "
                             f"prep={r['prep_ms']:.2f} "
                             f"sync_p50={r['synced_batch_ms_p50']:.1f} "
                             f"left={r['leftover_pubs']} "
                             f"ovf={r['overflow_pubs']}")
                        if variant == "packed":
                            # device-resident rate at this geometry: the
                            # chip's own ceiling, transport excluded
                            try:
                                k = wb.run_kernel_only()
                                note(f"{tag} KERNEL-ONLY: "
                                     f"{k['kernel_matches_per_sec']/1e6:.2f}M"
                                     f" matches/s "
                                     f"batch={k['kernel_batch_ms']:.2f}ms "
                                     f"{k['kernel_publishes_per_sec']/1e3:.0f}"
                                     f"k pubs/s")
                            except Exception as e:
                                note(f"{tag} KERNEL-ONLY FAILED: "
                                     f"{type(e).__name__}: {str(e)[:120]}")
                        if best is None or r["matches_per_sec"] > best[0]:
                            best = (r["matches_per_sec"], tag)
                    except Exception as e:
                        note(f"{tag} FAILED: {type(e).__name__}: "
                             f"{str(e)[:120]}")
    if best:
        note(f"BEST: {best[1]} {best[0]/1e6:.2f}M matches/s")


if __name__ == "__main__":
    main()
