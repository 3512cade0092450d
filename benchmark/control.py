"""The control of the comparison that decides ``correct``.

``python3 -m benchmark.control --workload <cell> --seed <n> --seconds <s>
[--break <guarantee>] [--every N]`` runs the cell's traffic, at the cell's
own load and sizes, against the plain reference put in the program's place
(``refbroker.ReferenceBroker``) with ONE of the configuration's guarantees
broken, and prints the same last line as a run. ``correct`` has to come
out false; with no ``--break`` it has to come out true (the reference
keeps every guarantee, so a false there is a fault of the harness). Never
part of a benchmark run; touches neither the program nor JAX.
"""

from __future__ import annotations

import argparse
import asyncio
import sys
import time

from .manifest import ROOT


def run(workload: str, seed: int, seconds: float, break_=None, every=97,
        rehearse: bool = False, warm_s: float = 1.0, root: str = ROOT
        ) -> dict:
    from . import corpus as corpus_mod
    from . import harness
    from .generator import Generator
    from .manifest import Manifest
    from .run import rehearsal_sizes
    from .systems import ReferenceSystem

    manifest = Manifest(root)
    cell = manifest.cell(workload)
    if rehearse:
        rehearsal_sizes(cell)
    gen = Generator(cell["config"], cell["mix"], seed)
    try:
        gen.spawn()
        corpus = corpus_mod.build(cell["config"], seed)
        gen.ready()
        system = ReferenceSystem(break_, every, harness.note)
        result = asyncio.run(harness.drive(
            system, gen, manifest, cell, corpus, seconds, False,
            time.monotonic(), warm_s=warm_s, ack_wait_s=5.0))
    finally:
        gen.close()
    result["control"] = break_ or "none"
    result["metrics"] = {}  # the stand-in's speed is nobody's metric
    return result


def main(argv=None) -> int:
    from . import harness
    from .refbroker import BREAKS
    from .run import add_root

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--break", dest="break_", choices=BREAKS, default=None)
    ap.add_argument("--every", type=int, default=97)
    ap.add_argument("--rehearse", action="store_true")
    add_root(ap)
    a = ap.parse_args(argv)
    result = run(a.workload, a.seed, a.seconds, a.break_, a.every,
                 a.rehearse, root=a.root)
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
