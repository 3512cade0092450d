"""Device milliseconds of the named programs per execution, from the
trace's ``XLA Modules`` line. One execution is one dispatch of the
collector (a single batch or one K-window super-batch)."""

from ..trace.reduce import module_seconds


def read(ctx, modules):
    t = ctx["trace"]
    if not t or not t.get("devices"):
        return None
    secs, n = module_seconds(t, modules)
    return 1e3 * secs / n if n else None
