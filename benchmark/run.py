"""``python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``: one cell, once, in a new process.

This process boots the broker and is the only one that touches JAX; the
load generator's processes are spawned before JAX is imported. Without a
TPU (or with fewer chips than the cell asks for) it exits non-zero and
prints no result. ``--rehearse`` runs the same control flow on the CPU
backend at the tiny sizes the configuration's and the mix's ``rehearse``
blocks give; its last line says ``"platform": "cpu"`` and carries counts,
never a number under a metric's name.
"""

from __future__ import annotations

import argparse
import asyncio
import os
import sys
import time
import traceback

T_PROCESS = time.monotonic()


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    add_root(ap)
    return ap.parse_args(argv)


def add_root(ap: argparse.ArgumentParser) -> None:
    """The one optional argument of every entry point: the directory whose
    ``BENCHMARK.json`` names the cell (its configurations' files lie under
    it). The package's tests and a builder's trial of a deployment that is
    no cell yet pass their own; a run of the benchmark passes none."""
    from .manifest import ROOT

    ap.add_argument("--root", default=ROOT, help=add_root.__doc__)


def rehearsal_sizes(cell: dict) -> None:
    """Overlay the ``rehearse`` blocks: same code, toy sizes."""
    cfg, mix = cell["config"], cell["mix"]
    cfg.update(cfg.get("rehearse", {}))
    mix.update(mix.get("rehearse", {}))


def compile_cache_in_checkout() -> None:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, whole: the program takes the directory the environment
    names (``vernemq_tpu/utils/compile_cache.py``), so it is named here,
    before JAX is imported. A cap on its size (the builder's chip machine
    sets one of 192 MiB) would evict the 1M ladder's programs while they
    are being written and every run would compile them anew; programs
    that compile in under a second are kept too, so a second run finds
    every program."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"


def boot_jax(rehearse: bool, chips: int):
    """Import JAX (after the generator's processes were spawned), place
    the compile cache, and refuse a machine without the chips a cell asks
    for. Returns ``(jax, CacheCounter)`` or None where it refuses."""
    from . import harness
    from .manifest import ROOT

    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    compile_cache_in_checkout()
    import jax

    if rehearse:
        jax.config.update("jax_platforms", "cpu")
    os.chdir(ROOT)
    from vernemq_tpu.utils.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    devs = jax.devices()
    if not rehearse and (devs[0].platform != "tpu" or len(devs) < chips):
        print(f"benchmark: needs {chips} TPU chip(s); JAX found "
              f"{len(devs)} x {devs[0].platform}", file=sys.stderr)
        return None
    from .systems import CacheCounter

    cache = CacheCounter(jax, cache_dir)
    harness.note(phase="device", platform=devs[0].platform,
                 kind=devs[0].device_kind, count=len(devs),
                 cpu_count=os.cpu_count(), compile_cache_dir=cache_dir,
                 **{"cache_" + k: v for k, v in cache.fact().items()
                    if k.startswith("dir_")})
    return jax, cache


def main(argv=None, system_factory=None) -> int:
    """``system_factory``: the package's own tests hand in a
    ``DeviceBroker`` with a fault planted; a run uses the program as is."""
    args = parse(argv)
    # the program must be importable before anything is started: in a
    # directory that holds only the benchmark this fails, with no result
    import vernemq_tpu  # noqa: F401

    from . import corpus as corpus_mod
    from . import harness
    from .generator import Generator
    from .manifest import Manifest

    manifest = Manifest(args.root)
    cell = manifest.cell(args.workload)
    if args.rehearse:
        rehearsal_sizes(cell)
    gen = Generator(cell["config"], cell["mix"], args.seed)
    try:
        gen.spawn()  # before JAX: a child never sees the chip held
        booted = boot_jax(args.rehearse, cell["chips"])
        if booted is None:
            return 2
        jax, cache = booted
        from .systems import DeviceBroker

        corpus = corpus_mod.build(cell["config"], args.seed)
        gen.ready()
        system = (system_factory or DeviceBroker)(jax, cache, harness.note)
        result = asyncio.run(harness.drive(
            system, gen, manifest, cell, corpus, args.seconds,
            bool(args.trace), T_PROCESS,
            warm_s=1.0 if args.rehearse else harness.WARM_S))
    except BaseException:
        traceback.print_exc(file=sys.stderr)
        print("benchmark.run: the run did not reach its end; no result",
              file=sys.stderr, flush=True)
        return 1
    finally:
        gen.close()
    if args.rehearse:
        # counts only: a CPU run gives no number under a metric's name
        result["rehearsal"] = True
        result["metrics"] = {}
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
