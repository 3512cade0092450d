"""``python3 -m benchmark.traced --workload <cell> --seed <n> --seconds <s>``:
``benchmark.run --trace 1`` with what the program has shown of itself
since ISSUE 25 read in full. The harness is run as it is, with two of its
seams given more to carry:

- the trace is reduced by ``trace/spans.py`` on top of ``trace/reduce.py``,
  so the result's ``breakdown`` names idle gaps by the program's spans and
  lists device time by named scope, and a fact line on standard error
  gives ``program_span_s``, ``idle_by_program_span_s`` and
  ``device_scope_s``;
- ``DeviceBroker.counters`` also reports every histogram family the
  program's registry holds and ``loop_cpu_s``, so the window's own sums
  and counts of the new families (not the run's) and the loop's CPU
  seconds stand in the ``window`` fact line, and the ``program_span``
  reader reads the window;
- ``DeviceBroker.tap_folds`` also notes the shapes of every match program
  dispatched from then on: the v5e's trace names an operation by its HLO
  text and carries no ``op_name``, so after the run each program is
  lowered again with those shapes (a hit in the compile cache) and its
  compiled text says which named scope each instruction came from.

A ``benchmark`` PR folds these into ``harness.reduce_trace`` and
``systems.DeviceBroker`` and declares ``loop_busy_pct``; until then the
driver's command is ``benchmark.run`` and this is the builder's.
"""

from __future__ import annotations

import os
import shutil
import sys

from . import harness, run, systems
from .trace import spans

PROGRAMS = {}  # (function name, shapes, statics) -> (jitted, shapes, statics)


def note_programs() -> None:
    """Wrap the two jitted match programs ``ops.match_kernel`` dispatches
    so that each distinct signature is remembered."""
    import jax

    from vernemq_tpu.ops import match_kernel as K

    def remembering(name):
        jitted = getattr(K, name)

        def call(*args, **statics):
            key = (name, tuple((a.shape, str(a.dtype)) for a in args),
                   tuple(sorted(statics.items())))
            if key not in PROGRAMS:
                PROGRAMS[key] = (jitted, tuple(
                    jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args),
                    statics)
            return jitted(*args, **statics)

        setattr(K, name, call)

    remembering("match_extract_windowed_flat_packed")
    remembering("match_many")


def scope_tables():
    import warnings

    tables = []
    for jitted, shapes, statics in PROGRAMS.values():
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")  # donation not usable
            text = jitted.lower(*shapes, **statics).compile().as_text()
        tables.append(spans.scope_map(text))
    return tables


def reduce_trace(traced):
    if traced is None:
        return None
    tables = scope_tables()
    harness.note(phase="scope_tables", programs=len(tables),
                 instructions=[len(t) for t in tables])
    red = spans.reduce(traced["path"], traced["window_s"], tables)
    shutil.rmtree(os.path.join(harness.RUN_DIR, "trace"),
                  ignore_errors=True)
    harness.note(phase="program_spans", **{k: red.get(k) for k in (
        "program_span_s", "program_span_n", "idle_by_program_span_s",
        "idle_by_host_activity_s", "device_scope_s", "folds", "fold_s",
        "busy_s", "window_s")})
    return red


def counters(self):
    out = _counters(self)
    for fam, (_b, total, count) in \
            self.broker.metrics.histogram_snapshot().items():
        out[fam + ".sum"] = float(total)
        out[fam + ".count"] = int(count)
    out["loop_cpu_s"] = float(getattr(self.broker.sysmon, "loop_cpu_s", 0.0))
    return out


def tap_folds(self):
    _tap_folds(self)
    note_programs()


_counters = systems.DeviceBroker.counters
_tap_folds = systems.DeviceBroker.tap_folds


def main(argv=None) -> int:
    harness.reduce_trace = reduce_trace
    systems.DeviceBroker.counters = counters
    systems.DeviceBroker.tap_folds = tap_folds
    argv = list(sys.argv[1:] if argv is None else argv)
    return run.main(argv + ["--trace", "1"])


if __name__ == "__main__":
    sys.exit(main())
