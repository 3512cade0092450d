"""The sweep that fixes an open-loop cell's offered rate, once, on the chip.

``python3 -m benchmark.sweep --workload <cell> --seed <n> --connect <max>
--active 1000,2000,...`` boots the cell's broker once with ``--connect``
live pairs and offers the mix from the first ``n`` publishers of each
step for ``--step-seconds``, pausing between steps. Per step it prints
the offered rate, the latency's median, 95th and 99th percentile and
maximum, and the median over the step's first and last third: the knee is
the highest rate at which no backlog grows (the latency of the last third
no higher than that of the first, every publish acknowledged and
delivered). The cell then runs at 0.8 of the knee (the configuration's
``live_pairs``). Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time

import numpy as np


async def sweep(system, gen, cell, corpus, actives, step_s: float,
                pause_s: float):
    from . import harness

    mix = cell["mix"]
    port = await system.boot(corpus)
    await system.calm()
    await harness._off_loop(gen.connect, port)
    await system.warm()
    windows, reports, rows = [], [], []
    for active in actives:
        await system.calm()
        before = system.counters()
        system.probes()
        t0 = time.monotonic_ns() + int(0.3e9)
        start = {"t0_ns": t0, "warm_s": 0.0, "seconds": step_s,
                 "active": active, "ack_wait_s": 30.0}
        reports = await harness._off_loop(gen.run, start)
        after = system.counters()
        windows.append((t0, t0 + int(step_s * 1e9)))
        rows.append({"active": active, "probes": system.probes(),
                     "sent": sum(r["step_sent"] for r in reports),
                     "acked": sum(r["step_acked"] for r in reports),
                     "moved": {k: after[k] - before[k] for k in after
                               if after[k] != before[k]
                               and not k.startswith("stage_")}})
        await asyncio.sleep(pause_s)
    fin = harness._finish_request(reports, mix, windows[0])
    fin["more_windows"] = windows
    subs = await harness._off_loop(gen.finish, fin)
    device = system.device()
    await system.stop()
    per_s = int(mix["burst"]) * 1e3 / float(mix["interval_ms"])
    for i, row in enumerate(rows):
        lat = np.concatenate([r["steps"][i]["lat_ms"] for r in subs])
        due = np.concatenate([r["steps"][i]["due_s"] for r in subs])
        row.update(offered_pubs_per_s=row["active"] * per_s,
                   owed=sum(r["steps"][i]["owed"] for r in subs)
                   + subs[0]["steps"][i]["owed_shared"],
                   received=int(len(lat)))
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
    from .run import add_root, boot_jax, rehearsal_sizes

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--connect", type=int, required=True)
    ap.add_argument("--active", required=True)
    ap.add_argument("--step-seconds", type=float, default=10.0)
    ap.add_argument("--pause-seconds", type=float, default=3.0)
    ap.add_argument("--rehearse", action="store_true")
    add_root(ap)
    a = ap.parse_args(argv)
    from . import corpus as corpus_mod
    from . import harness
    from .generator import Generator
    from .manifest import Manifest

    cell = Manifest(a.root).cell(a.workload)
    if a.rehearse:
        rehearsal_sizes(cell)
    for key in ("live_pairs", "live_publishers"):  # whichever it sizes by
        if key in cell["config"]:
            cell["config"][key] = a.connect
    gen = Generator(cell["config"], cell["mix"], a.seed)
    try:
        gen.spawn()
        booted = boot_jax(a.rehearse, cell["chips"])
        if booted is None:
            return 2
        jax, cache = booted
        from .systems import DeviceBroker

        corpus = corpus_mod.build(cell["config"], a.seed)
        gen.ready()
        asyncio.run(sweep(DeviceBroker(jax, cache, harness.note), gen, cell,
                          corpus, [int(n) for n in a.active.split(",")],
                          a.step_seconds, a.pause_seconds))
    finally:
        gen.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
