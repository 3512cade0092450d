"""Tests of the benchmark's own code: ``python3 -m pytest benchmark/tests``.

None needs an accelerator. Those that boot the program do so in a child
process on the CPU backend (``--rehearse``); those that drive the harness
against the stand-in broker touch neither the program nor JAX.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
