"""Mean of a histogram family that the PROGRAM's own spans observe
(``vernemq_tpu.observability.histogram``): milliseconds observed over
observations, times a scale.

Where the run's counters carry the family (``<family>.sum`` and
``<family>.count``, window end less window start) those are read.
``systems.DeviceBroker.counters`` names five families; any other is read
from the program's registry as it stands when the run ends: every
observation of the process. That is the mix's warm-up seconds, the
window and the wait for what is owed, one traffic throughout, so the
mean per observation is the window's over a few more ticks (the warm
ladder's dispatches observe nothing). A program without the family (the
parent of the PR that brought it), a process that never loaded the
program, and a family nothing observed into give nothing to read."""

import sys


def read(ctx, family, scale=1.0):
    counters = ctx["counters"]
    total = counters.get(family + ".sum")
    count = counters.get(family + ".count")
    if count is None:
        hist = sys.modules.get("vernemq_tpu.observability.histogram")
        if hist is None:
            return None
        try:
            _buckets, total, count = hist.get(family).snapshot()
        except KeyError:
            return None
    if not count or not total:
        return None
    return scale * total / count
