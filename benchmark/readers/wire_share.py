"""Share of the QoS >= 1 publishes that the program's wire plane
admitted: those it took from the frame table over those plus the ones
its gate left to the classic handler.

The program counts both in ``vernemq_tpu.protocol.fastpath`` (process
totals, like its other wire-plane counters; the broker shows them as the
gauges ``wire_<name>``). Where the run's counters carry the gauges
(window end less window start) those are read;
``systems.DeviceBroker.counters`` does not name them, so they are read
from the module as it stands when the run ends: the mix's warm-up
seconds, the window and the wait for what is owed, one traffic
throughout.

A program that has the wire counter and counts nothing on the classic
side (the parent of the PR that brought that counter), or whose two
counters saw no such publish, admitted none of the context's
``publishes`` on the wire plane: 0. A wire count with nothing to set it
against, a context without publishes and a program without the counter
give nothing to read."""

import importlib


def read(ctx, wire, classic, scale=100.0):
    try:
        fp = importlib.import_module("vernemq_tpu.protocol.fastpath")
    except ImportError:
        fp = None
    counters = ctx["counters"]
    w = counters.get("wire_" + wire, getattr(fp, wire, None))
    c = counters.get("wire_" + classic, getattr(fp, classic, None))
    if w is None:
        return None
    if c is not None and w + c:
        return scale * w / (w + c)
    if w or not ctx.get("publishes"):
        return None
    return 0.0
