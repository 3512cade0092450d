"""The rest of a run without the look for a chip: ``--rehearse`` boots the
program on the CPU backend at a toy size in a child process. One case
checks the last line's keys only (no number: the rehearsal shares its
cores with whatever else runs); the other breaks the timed path underneath
— a matched row removed where the device path hands its answer to the
router — and sees ``correct`` come out false."""

import json
import os
import subprocess
import sys

from benchmark.tests.conftest import ROOT

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def rehearse(cell, module="benchmark.run", trace=0):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, "-m", module, "--workload", cell,
         "--seed", "2147483777", "--seconds", "2", "--trace", str(trace),
         "--rehearse"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=900)


def test_rehearsal_prints_the_contracts_last_line():
    p = rehearse("p2p50k.tick1s", trace=1)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert KEYS <= set(out) and list(out)[-1] == "compared"
    assert out["device"]["platform"] == "cpu" and out["rehearsal"] is True
    assert out["metrics"] == {}  # a CPU run gives counts, never a metric
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        out["device"])
    assert isinstance(out["correct"], bool) and out["attempted"] > 0


def test_an_answer_altered_where_it_is_produced_is_not_correct():
    p = rehearse("p2p50k.tick1s", "benchmark.tests.faulty")
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is False
    assert out["compared"]["lost_qos1"]["value"] > 0
    assert out["failed"] > 0
