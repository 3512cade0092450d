"""The match programs' share of their roofline: the least time the chip's
memory could move what a dispatch NEEDS (``benchmark.work``), over the
device time the trace shows per dispatch. HBM-bound. The publishes per
dispatch are the program's own count over the window."""

from .. import work
from ..trace.reduce import module_seconds


def read(ctx, modules):
    t = ctx["trace"]
    if not t or not t.get("devices"):
        return None
    secs, n = module_seconds(t, modules)
    c = ctx["counters"]
    dispatches = c.get("match_batches", 0) + c.get("super_dispatches", 0)
    if not n or not secs or not dispatches:
        return None
    cfg = ctx["config"]
    least = work.match_least_seconds(
        ctx["device"]["kind"], ctx["resident"], ctx["levels"],
        c["match_publishes"] / dispatches,
        float(cfg["matched_rows_per_publish"]))
    return 100.0 * least / (secs / n)
