"""The work a WIDE pass needs (``ops/match_kernel.wide_mask_packed``: the
whole match mask of the publishes the flat result's caps cut off).

As in ``work.py`` the count is of the problem, never of the program's
windows: to answer ``topics`` distinct topics whole, a program has to
read the coded levels of the rows those topics can match once (never
more than the resident rows), read the topics' own coded levels, and hand
back one bit a row and topic. How much wider the program's windows are
than that (a pow2 window over a whole bucket region, read once a topic)
is what the share of the roofline is there to show. Comparisons, no
multiply-accumulate: the bound is the memory's."""

from __future__ import annotations

from . import work


def wide_bytes(resident: int, levels: int, topics: float,
               rows_per_topic: float) -> float:
    """Bytes one wide dispatch over ``topics`` distinct topics needs
    moved."""
    rows = min(float(resident), topics * rows_per_topic)
    return (rows * levels * work.ID_BYTES
            + topics * levels * work.ID_BYTES
            + topics * rows_per_topic / 8.0)


def wide_least_seconds(device_kind: str, resident: int, levels: int,
                       topics: float, rows_per_topic: float) -> float:
    return wide_bytes(resident, levels, topics, rows_per_topic) \
        / work.peaks(device_kind)["hbm_bytes_per_s"]
