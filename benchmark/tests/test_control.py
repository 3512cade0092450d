"""The control, at a size a test run can hold: the cell's traffic against
the plain reference put in the program's place. With every guarantee kept
``correct`` is true; with any one broken it is false, by the number that
guarantee owns. Neither the program nor JAX is imported."""

import json
import subprocess
import sys

import pytest

from benchmark.tests.conftest import ROOT

CELL = "p2p50k.tick1s"
CASES = [
    (None, None),
    ("lose_qos1", "lost_qos1"),
    ("lose_tail", "lost_qos1"),   # the tail of every connection, unserved
    ("duplicate", "duplicates"),
    ("stray", "strays"),
    ("reorder", "misordered"),
    ("no_ack", "unacked"),
]


@pytest.mark.parametrize("break_,number", CASES)
def test_control(break_, number):
    cmd = [sys.executable, "-m", "benchmark.control", "--workload", CELL,
           "--seed", "2147483659", "--seconds", "4", "--rehearse",
           "--every", "3"]
    if break_:
        cmd += ["--break", break_]
    p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert "jax" not in p.stderr.lower()
    assert list(out)[-1] == "compared"
    assert p.stderr.strip().splitlines()[-1] == \
        f"correct: {json.dumps(out['correct'])}"
    if break_ is None:
        assert out["correct"] is True and out["failed"] == 0
        assert out["attempted"] > 0 and out["facts"]["deliveries"] > 0
    else:
        assert out["correct"] is False
        assert out["compared"][number]["value"] > 0
        others = [k for k, v in out["compared"].items()
                  if k != number and v["value"] > v.get("limit", 0)]
        assert not others, others
