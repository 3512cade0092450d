"""A reading of the harness's own probes on the broker's loop
(``systems.LagMeter``) or of the generator's lateness."""

import numpy as np


def read(ctx, what):
    p = ctx["probes"]
    if what == "loop_lag_ms_max":
        return 1e3 * p["loop_lag_max_s"]
    if what == "governor_raised_pct":
        return 100.0 * p["raised"] / p["samples"] if p["samples"] else None
    if what == "generator_late_ms_p99":
        late = ctx["generator_late_ms"]
        return float(np.percentile(late, 99)) if len(late) else None
    raise ValueError(f"unknown probe {what!r}")
