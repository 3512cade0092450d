"""A sum of the PROGRAM's own counters over another, times a scale.

``num`` / ``den``: counter names. Where the run's counters carry every
one of them (``ctx['counters']``, window end less window start) those
are read. ``systems.DeviceBroker.counters`` names the matcher's older
counters only, so if any name is absent there ALL are taken from the
program as it stands when the run ends — the process's totals, never one
side from the window and the other from the process: the plain integers
the program keeps at module level, the wide pass's in
``vernemq_tpu.models.tpu_matcher`` (the gauges ``tpu_<name>``), the wire
plane's in ``vernemq_tpu.protocol.fastpath`` (the gauges
``wire_<name>``). That is the mix's warm-up seconds, the window and the
wait for what is owed, one traffic throughout (the warm ladder's dummy
batches never overflow the flat form).

A program without the counter (the parent of the PR that brought it), a
process that never loaded the program, and a denominator nothing counted
into give nothing to read."""

import sys

_MODULES = ("vernemq_tpu.models.tpu_matcher", "vernemq_tpu.protocol.fastpath")


def _program(names):
    found = {}
    for mod in map(sys.modules.get, _MODULES):
        for n in names:
            v = getattr(mod, n, None)
            if isinstance(v, int) and n not in found:
                found[n] = v
    return found


def totals(ctx, names):
    """``{name: value}`` for every name, or None where one is unknown."""
    counters = ctx["counters"]
    if all(n in counters for n in names):
        return {n: float(counters[n]) for n in names}
    found = _program(names)
    if any(n not in found for n in names):
        return None
    return {n: float(found[n]) for n in names}


def read(ctx, num, den, scale=1.0):
    got = totals(ctx, list(num) + list(den))
    if got is None:
        return None
    d = sum(got[n] for n in den)
    if not d:
        return None
    return scale * sum(got[n] for n in num) / d
