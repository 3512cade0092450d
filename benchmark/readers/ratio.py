"""A sum of the run's quantities over another, times a scale.

``num`` / ``den``: names looked up in ``ctx['counters']`` first (a
histogram family's total is ``<family>.sum``, its observations
``<family>.count``), then in ``ctx`` itself (``publishes``,
``deliveries``)."""


def _sum(ctx, names):
    total = 0.0
    for n in names:
        v = ctx["counters"].get(n, ctx.get(n))
        if v is None:
            return None
        total += float(v)
    return total


def read(ctx, num, den, scale=1.0, spans=False):
    """``spans``: the numerator is time observed by spans; where nothing
    was observed (the path the cell takes carries no span) there is
    nothing to read."""
    n, d = _sum(ctx, num), _sum(ctx, den)
    if n is None or not d or (spans and not n):
        return None
    return scale * n / d
