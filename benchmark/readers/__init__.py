"""Per-layer metric readers: ``read(ctx, **args) -> float | None``.

``ctx`` is what one run knows: ``counters`` (the program's counters and
histogram totals, window end less window start), ``publishes`` and
``deliveries`` of the window, ``probes``, ``generator_late_ms``, ``trace``
(the reduction of the device trace, or None), ``config``, ``device``. A
reader that finds nothing to read returns None and the metric is left out
of the line; none returns 0 for a share it could not read.
"""
