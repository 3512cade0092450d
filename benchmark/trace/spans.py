"""The program's own spans and named scopes in a ``jax.profiler`` trace.

``reduce.py`` knows the two spans the harness wraps around the
collector's dispatch calls. Since ISSUE 25 the program holds a
``TraceAnnotation`` open over each of its own timed sections, named after
the histogram family the section is observed into (``stage_fold_prep_ms``,
``stage_route_ms``, ``stage_ack_in_ms``, ``stage_wire_parse_ms`` ...), and
its match programs name their phases with ``jax.named_scope``
(``dense_region0``, ``probe_a`` ...). This module reads both from the same
``.xplane.pb`` and adds to ``reduce.reduce``'s result:

- ``program_span_s`` / ``program_span_n``: seconds and count of each
  family's spans inside the trace.
- ``idle_by_program_span_s``: the seconds of device idle (the gaps between
  operations) that each family's spans overlap, and ``none``: idle under
  no span of the program at all.
- ``device_scope_s``: device seconds by named scope;
  ``breakdown.device_scopes`` lists them. The scope of an operation is
  read from its own statistics where the trace carries its ``op_name``.
  The v5e's raw trace does not (an operation has its HLO text for a name
  and two timing statistics, read on the chip in PR 25), so a caller may
  hand in, per program, the instruction → scope table that
  ``scope_map`` makes of the program's compiled HLO text; an operation
  then takes the scope of the instruction it is named after, in the
  table that knows most of the instructions its program ran. ``unscoped``
  is what neither names.
- ``breakdown.idle_gaps``: a gap ``reduce`` called ``no_fold_in_flight``
  is called ``no_fold_in_flight:<family>`` where the program's spans cover
  at least half of it between them (the family with the most of it), and
  keeps its name otherwise. The three ``fold:*`` names are ``reduce``'s.

A trace without such spans or scopes (the recorded sample, a program
older than ISSUE 25) adds empty tables and changes no name.
``benchmark.traced`` runs a cell with this reduction laid over the
harness's.
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import reduce as T

SPAN_PREFIX = "stage_"
SCOPES = ("unpack_transport", "dense_region0", "probe_a", "probe_b",
          "flat_combine", "delta_scatter")
Interval = Tuple[int, int, str]


_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?op_name=\"([^\"]*)\"", re.M)
_OP = re.compile(r"^%?([\w.\-]+) = ")


def _scope_in(op_name: str) -> Optional[str]:
    """XLA keeps JAX's name stack, ``jit(f)/probe_a/dot_general``, as an
    operation's ``op_name``: its first component that is a scope."""
    for part in op_name.split("/"):
        if part in SCOPES:
            return part
    return None


def scope_of(stats) -> str:
    """The named scope in an operation's own statistics."""
    for _key, value in stats:
        if isinstance(value, str) and "/" in value:
            scope = _scope_in(value)
            if scope:
                return scope
    return "unscoped"


def scope_map(hlo_text: str) -> Dict[str, str]:
    """Instruction name → named scope, from a compiled program's HLO text
    (``jitted.lower(...).compile().as_text()``): every instruction whose
    ``op_name`` lies under one of ``SCOPES``. A fusion carries the
    ``op_name`` of the operation it was built around."""
    out: Dict[str, str] = {}
    for name, op_name in _INSTRUCTION.findall(hlo_text):
        scope = _scope_in(op_name)
        if scope:
            out[name] = scope
    return out


def _scopes_by_program(ops: List[Interval], mods: List[Interval],
                       programs: Sequence[Dict[str, str]]) -> List[str]:
    """The scope of each operation from the instruction it is named
    after: operations are grouped by the program execution they started
    in, and a program is read with the table that knows most of the
    instructions it ran."""
    mods = sorted(mods)
    starts = np.array([m[0] for m in mods], np.int64)
    ran_in: List[Tuple[Optional[str], Optional[str]]] = []
    ran: Dict[str, set] = {}
    for s, _e, text in ops:
        k = int(np.searchsorted(starts, s, side="right")) - 1
        prog = mods[k][2] if k >= 0 and s < mods[k][1] else None
        m = _OP.match(text)
        instr = m.group(1) if m else None
        ran_in.append((prog, instr))
        if prog is not None and instr:
            ran.setdefault(prog, set()).add(instr)
    table = {prog: max(programs, key=lambda t: len(seen & t.keys()))
             for prog, seen in ran.items()}
    return [table[prog].get(instr, "unscoped") if prog in table
            else "unscoped" for prog, instr in ran_in]


def collect(path: str, programs: Sequence[Dict[str, str]] = ()):
    """``(devices, folds, spans, scoped_ops)`` of a trace file: the first
    two as ``reduce.reduce`` collects them, the program's spans from the
    host plane, and (scope, seconds) per device operation. ``programs``:
    ``scope_map`` tables of the programs that may have run."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices, folds, spans, scoped = [], [], [], []
    for plane in data.planes:
        if plane.name.startswith(T.DEVICE_PREFIX):
            lines = {ln.name: ln for ln in plane.lines}
            ops, scopes = [], []
            for e in (lines[T.OPS_LINE].events
                      if T.OPS_LINE in lines else ()):
                ops.append((int(e.start_ns),
                            int(e.start_ns + e.duration_ns), e.name))
                scopes.append(scope_of(e.stats))
            mods = (T._intervals(lines[T.MODULES_LINE])
                    if T.MODULES_LINE in lines else [])
            if programs and mods:
                named = _scopes_by_program(ops, mods, programs)
                scopes = [a if a != "unscoped" else b
                          for a, b in zip(scopes, named)]
            scoped += [(sc, (e - s) / 1e9)
                       for sc, (s, e, _n) in zip(scopes, ops)]
            devices.append((plane.name, ops, mods))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for iv in T._intervals(ln):
                    if iv[2] in T.FOLD_SPANS:
                        folds.append(iv)
                    elif iv[2].startswith(SPAN_PREFIX):
                        spans.append(iv)
    return devices, folds, spans, scoped


class _Cover:
    """Merged intervals with the length they cover up to any instant."""

    def __init__(self, intervals: Sequence[Tuple[int, int]]) -> None:
        merged = T.union(list(intervals))
        self.s = np.array([a for a, _b in merged], np.int64)
        self.e = np.array([b for _a, b in merged], np.int64)
        self.cum = np.concatenate([[0], np.cumsum(self.e - self.s)])

    def upto(self, t: np.ndarray) -> np.ndarray:
        if not len(self.s):
            return np.zeros(len(t), np.int64)
        k = np.searchsorted(self.s, t, side="right") - 1
        inside = np.clip(t - self.s[np.maximum(k, 0)], 0,
                         (self.e - self.s)[np.maximum(k, 0)])
        return np.where(k >= 0, self.cum[np.maximum(k, 0)] + inside, 0)

    def overlap(self, gs: np.ndarray, ge: np.ndarray) -> np.ndarray:
        return self.upto(ge) - self.upto(gs)


def extend(red: Dict[str, Any], devices, spans: List[Interval],
           scoped: Sequence[Tuple[str, float]] = ()) -> Dict[str, Any]:
    """Add the program's spans and scopes to ``red``, the result of
    ``reduce.reduce_events(devices, folds)`` on the same trace."""
    if not red.get("devices"):
        return red
    span_s: Dict[str, float] = {}
    span_n: Dict[str, int] = {}
    by_family: Dict[str, List[Tuple[int, int]]] = {}
    for s, e, name in spans:
        span_s[name] = span_s.get(name, 0.0) + (e - s) / 1e9
        span_n[name] = span_n.get(name, 0) + 1
        by_family.setdefault(name, []).append((s, e))
    red["program_span_s"], red["program_span_n"] = span_s, span_n
    # the gaps as reduce_events builds and orders them
    gaps: List[Tuple[int, int]] = []
    for _name, ops, _mods in devices:
        u = T.union([(s, e) for s, e, _n in ops])
        gaps += [(a[1], b[0]) for a, b in zip(u, u[1:])]
    gs = np.array([g[0] for g in gaps], np.int64)
    ge = np.array([g[1] for g in gaps], np.int64)
    over = {fam: _Cover(iv).overlap(gs, ge)
            for fam, iv in by_family.items()}
    covered = _Cover([(s, e) for s, e, _n in spans]).overlap(gs, ge)
    idle = {fam: float(o.sum()) / 1e9 for fam, o in over.items()}
    idle["none"] = float((ge - gs - covered).sum()) / 1e9
    red["idle_by_program_span_s"] = idle
    scope_s: Dict[str, float] = {}
    for scope, secs in scoped:
        scope_s[scope] = scope_s.get(scope, 0.0) + secs
    red["device_scope_s"] = scope_s
    longest = sorted(range(len(gaps)),
                     key=lambda i: gaps[i][0] - gaps[i][1])[:10]
    named = []
    for (label, secs), i in zip(red["breakdown"]["idle_gaps"], longest):
        if label == "no_fold_in_flight" and over:
            if 2 * covered[i] >= ge[i] - gs[i]:
                fam = max(over, key=lambda f: over[f][i])
                label = f"no_fold_in_flight:{fam}"
        named.append([label, secs])
    red["breakdown"] = dict(
        red["breakdown"], idle_gaps=named,
        device_scopes=[[n, t] for n, t in sorted(
            scope_s.items(), key=lambda kv: -kv[1])])
    return red


def reduce(path: str, window_s=None,
           programs: Sequence[Dict[str, str]] = ()) -> Dict[str, Any]:
    """``reduce.reduce`` with the program's spans and scopes added."""
    devices, folds, spans, scoped = collect(path, programs)
    return extend(T.reduce_events(devices, folds, window_s), devices,
                  spans, scoped)
