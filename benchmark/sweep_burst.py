"""The sweep that fixes a burst cell's offered rate, once, on the chip.

``python3 -m benchmark.sweep_burst --workload <cell> --seed <n> --bursts
2,3,4,...`` boots the cell's broker once with the configuration's whole
population connected, and for each ``B`` in turn has the mix's publishers
write ``B`` publishes a tick for ``--step-seconds``, pausing between
steps (``benchmark/sweep.py`` varies how many publishers are active; this
varies what each writes, which a mix fixes at spawn: every step has
publisher processes of its own, started before JAX is imported and
connected for their step alone). Per step it prints the publishes sent and
acknowledged and the deliveries received, the latency's median, 95th and 99th percentile and
maximum, and the median over the step's first and last third: the knee is
the largest ``B`` at which no backlog grows (the latency of the last third
no higher than that of the first, every publish acknowledged and
delivered) with the broker's own protection silent (``probes.level_max``
0: a step in which the loop-lag alarm raised the governor is a load the
broker answered by pausing its publishers, not one it sustained). The
cell then runs at 0.8 of the knee (the mix's ``burst`` and
the configuration's ``msgs_per_publisher_per_s``). Not part of a benchmark
run.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time

import numpy as np


async def sweep(system, subs, pubs, cell, corpus, step_s: float,
                pause_s: float):
    from . import harness

    port = await system.boot(corpus)
    await system.calm()
    await harness._off_loop(subs.connect, port)
    await system.warm()
    windows, rows, reports = [], [], []
    for burst, gen in pubs:
        await harness._off_loop(gen.connect, port)
        await system.calm()
        before = system.counters()
        system.probes()
        t0 = time.monotonic_ns() + int(0.3e9)
        reports = await harness._off_loop(gen.run, {
            "t0_ns": t0, "warm_s": 0.0, "seconds": step_s,
            "ack_wait_s": 30.0})
        after = system.counters()
        await harness._off_loop(gen.close)
        windows.append((t0, t0 + int(step_s * 1e9)))
        rows.append({"burst": burst, "probes": system.probes(),
                     "sent": sum(r["step_sent"] for r in reports),
                     "acked": sum(r["step_acked"] for r in reports),
                     "moved": {k: after[k] - before[k] for k in after
                               if after[k] != before[k]
                               and not k.startswith("stage_")}})
        await asyncio.sleep(pause_s)
    # the subscribers' latencies by step: a stamp carries the time its
    # tick was due, so a step's deliveries are those stamped inside it
    fin = harness._finish_request(reports, cell["mix"], windows[0])
    fin["more_windows"] = windows
    got = await harness._off_loop(subs.finish, fin)
    device = system.device()
    await system.stop()
    for i, row in enumerate(rows):
        lat = np.concatenate([r["steps"][i]["lat_ms"] for r in got])
        due = np.concatenate([r["steps"][i]["due_s"] for r in got])
        row.update(received=int(len(lat)),
                   received_per_publish=len(lat) / max(1, row["sent"]))
        if len(lat):
            first, last = lat[due < step_s / 3], lat[due >= 2 * step_s / 3]
            row.update(p50_ms=float(np.percentile(lat, 50)),
                       p95_ms=float(np.percentile(lat, 95)),
                       p99_ms=float(np.percentile(lat, 99)),
                       max_ms=float(lat.max()),
                       p50_first_third_ms=float(np.median(first)),
                       p50_last_third_ms=float(np.median(last)))
        print(json.dumps(row, default=harness._plain), flush=True)
    print(json.dumps({"device": device}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--bursts", required=True)
    ap.add_argument("--interval-ms", type=float, default=None,
                    help="another tick than the mix's, for a re-sweep")
    ap.add_argument("--step-seconds", type=float, default=10.0)
    ap.add_argument("--pause-seconds", type=float, default=3.0)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args(argv)
    from . import corpus as corpus_mod
    from . import harness
    from .generator import Generator
    from .manifest import Manifest
    from .run import boot_jax, rehearsal_sizes

    cell = Manifest().cell(a.workload)
    if a.rehearse:
        rehearsal_sizes(cell)
    cfg, mix = cell["config"], cell["mix"]
    if a.interval_ms:
        mix["interval_ms"] = a.interval_ms
    # ascending: a stamp's sequence number is checked against the LAST
    # step's count, which then has to be the largest
    bursts = sorted(int(b) for b in a.bursts.split(","))
    subs = Generator(cfg, dict(mix, publisher_processes=0), a.seed)
    pubs = [(b, Generator(dict(cfg, subscriber_processes=0),
                          dict(mix, burst=b), a.seed)) for b in bursts]
    gens = [subs] + [g for _b, g in pubs]
    try:
        for g in gens:
            g.spawn()
        booted = boot_jax(a.rehearse, cell["chips"])
        if booted is None:
            return 2
        jax, cache = booted
        from .systems import DeviceBroker

        corpus = corpus_mod.build(cfg, a.seed)
        for g in gens:
            g.ready()
        asyncio.run(sweep(DeviceBroker(jax, cache, harness.note), subs,
                          pubs, cell, corpus, a.step_seconds,
                          a.pause_seconds))
    finally:
        for g in gens:
            g.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
