"""The reduction from a ``jax.profiler`` trace to device numbers.

Reads the ``.xplane.pb`` with ``jax.profiler.ProfileData`` alone. A TPU
trace holds one plane per chip (``/device:TPU:<n>``) whose line ``XLA Ops``
carries one event per operation that ran on the chip and whose line ``XLA
Modules`` one event per executed program, named after the jitted function;
the host plane (``/host:CPU``) carries a line per thread with the
``TraceAnnotation`` spans the harness puts around the collector's two
dispatch calls (``bench_fold_batch`` / ``bench_fold_many``). All on one
clock.

- ``busy_s``: the union of the op intervals of a chip, averaged over the
  chips. ``idle`` is the traced window less that.
- module seconds and counts by program name, so a metric can take the
  match programs' device time per dispatch.
- ``breakdown``: the ten operations with most device time, and the ten
  longest idle gaps, each named by what the host was doing: inside a fold
  before its first operation (``fold:host_prep``), after its last
  (``fold:host_resolve``), between two of its operations
  (``fold:between_ops``), or with no fold in flight (``no_fold_in_flight``:
  the collector waiting for publishes, or the loop busy elsewhere).

Checked against the small recorded trace beside this file
(``tests/test_trace_reduce.py``).
"""

from __future__ import annotations

import glob
import os
from bisect import bisect_left, bisect_right
from typing import Any, Dict, List, Optional, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
FOLD_SPANS = ("bench_fold_batch", "bench_fold_many")


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _intervals(line) -> List[Tuple[int, int, str]]:
    return [(int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
            for e in line.events]


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def reduce(path: str, window_s: Optional[float] = None) -> Dict[str, Any]:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices, folds = [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {ln.name: ln for ln in plane.lines}
            ops = _intervals(lines[OPS_LINE]) if OPS_LINE in lines else []
            mods = (_intervals(lines[MODULES_LINE])
                    if MODULES_LINE in lines else [])
            devices.append((plane.name, ops, mods))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                folds += [(s, e, n) for s, e, n in _intervals(ln)
                          if n in FOLD_SPANS]
    return reduce_events(devices, folds, window_s)


def reduce_events(devices, folds, window_s: Optional[float] = None
                  ) -> Dict[str, Any]:
    """``devices``: (name, op intervals, module intervals) per chip;
    ``folds``: host fold spans; intervals are (start_ns, end_ns, name)."""
    if not devices:
        return {"devices": 0}
    busy, op_time, mod_time, mod_count = [], {}, {}, {}
    gaps: List[Tuple[int, int]] = []
    lo = min((s for _n, ops, _m in devices for s, _e, _x in ops),
             default=0)
    hi = max((e for _n, ops, _m in devices for _s, e, _x in ops),
             default=0)
    for _name, ops, mods in devices:
        u = union([(s, e) for s, e, _n in ops])
        busy.append(sum(e - s for s, e in u) / 1e9)
        for s, e, n in ops:
            op_time[n] = op_time.get(n, 0.0) + (e - s) / 1e9
        for s, e, n in mods:
            mod_time[n] = mod_time.get(n, 0.0) + (e - s) / 1e9
            mod_count[n] = mod_count.get(n, 0) + 1
        gaps += [(a[1], b[0]) for a, b in zip(u, u[1:])]
    span_s = (hi - lo) / 1e9
    out: Dict[str, Any] = {
        "devices": len(devices),
        "busy_s": sum(busy) / len(busy),
        "window_s": float(window_s) if window_s else span_s,
        "ops_span_s": span_s,
        "module_s": mod_time, "module_n": mod_count,
        "folds": len(folds),
        "fold_s": sum(e - s for s, e, _n in folds) / 1e9,
    }
    folds = sorted(folds)
    starts = [f[0] for f in folds]
    op_starts = sorted(s for _n, ops, _m in devices for s, _e, _x in ops)

    def op_between(a: int, b: int) -> bool:
        """Does any device operation start in [a, b)?"""
        i = bisect_left(op_starts, a)
        return i < len(op_starts) and op_starts[i] < b

    def doing(gs: int, ge: int) -> str:
        mid = (gs + ge) // 2
        i = bisect_right(starts, mid) - 1
        # the two pipeline slots overlap: look at the last few folds
        for s, e, _n in reversed(folds[max(0, i - 3):i + 1]):
            if s <= mid < e:
                first_op = gs <= s or not op_between(s, gs)
                last_op = not op_between(ge, e)
                return ("fold:host_prep" if first_op else
                        "fold:host_resolve" if last_op else
                        "fold:between_ops")
        return "no_fold_in_flight"

    gap_kind: Dict[str, float] = {}
    for gs, ge in gaps:
        k = doing(gs, ge)
        gap_kind[k] = gap_kind.get(k, 0.0) + (ge - gs) / 1e9
    out["idle_by_host_activity_s"] = gap_kind
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    out["breakdown"] = {
        "device_ops": [[n, t] for n, t in sorted(
            op_time.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[doing(gs, ge), (ge - gs) / 1e9]
                      for gs, ge in longest],
    }
    return out


def module_seconds(red: Dict[str, Any], contains) -> Tuple[float, int]:
    """Device seconds and executions of the programs whose name holds
    any of ``contains``."""
    t = n = 0
    for name, secs in red.get("module_s", {}).items():
        if any(c in name for c in contains):
            t += secs
            n += red["module_n"][name]
    return t, n
