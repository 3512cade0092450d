"""The wide pass's share of its roofline: the least time the chip's
memory could move what a wide dispatch NEEDS (``benchmark.work_wide``)
over the device time the trace shows per execution of the program.
HBM-bound. The distinct topics a dispatch are the program's own count
(``wide_topics`` over ``wide_dispatches``), the rows a topic the
configuration's ``matched_rows_per_publish``."""

from .. import work_wide
from ..trace.reduce import module_seconds
from .program_counter import totals


def read(ctx, modules):
    t = ctx["trace"]
    if not t or not t.get("devices"):
        return None
    secs, n = module_seconds(t, modules)
    got = totals(ctx, ["wide_topics", "wide_dispatches"])
    if not n or not secs or not got or not got["wide_dispatches"]:
        return None
    least = work_wide.wide_least_seconds(
        ctx["device"]["kind"], ctx["resident"], ctx["levels"],
        got["wide_topics"] / got["wide_dispatches"],
        float(ctx["config"]["matched_rows_per_publish"]))
    return 100.0 * least / (secs / n)
