"""``events-registry`` pass: journal emit sites and the code registry
agree.

The control-plane event journal (``observability/events.py``) has the
same drift hazard the fault-injection surface had before the
``fault-registry`` pass: a typo'd ``events.emit("braeker_open")`` site
raises at runtime only when the transition actually fires — i.e. during
the outage the journal exists to explain — and a ``KNOWN_EVENTS`` entry
with no emit site is a documented black-box signal that can never
appear (operators grep the timeline for it and conclude "this never
happened" when in truth it was never wired).

Checks (mirroring the fault-registry pass):

1. every ``events.emit(<code>, ...)`` call's first argument is a string
   literal naming a ``KNOWN_EVENTS`` entry;
2. every ``KNOWN_EVENTS`` entry has at least one emit site somewhere in
   the scan roots (sites inside ``observability/events.py`` itself —
   the module's own machinery — don't count, same as the faults file).

Only attribute calls whose receiver is spelled ``events`` / ``_events``
are treated as emit sites: ``emit`` is too common a bare name (the
filter engine has an ``emit`` hook) to match unqualified.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from ..core import SCAN_ROOTS, Context, Finding, Pass, const_str

_EVENTS_FILE = "vernemq_tpu/observability/events.py"


def _parse_registry(tree: ast.AST, errors: List[Finding]
                    ) -> Dict[str, int]:
    """``KNOWN_EVENTS`` as a dict literal of string keys -> line."""
    out: Dict[str, int] = {}
    for node in ast.walk(tree):
        if not isinstance(node, (ast.Assign, ast.AnnAssign)):
            continue
        targets = (node.targets if isinstance(node, ast.Assign)
                   else [node.target])
        if not any(isinstance(t, ast.Name) and t.id == "KNOWN_EVENTS"
                   for t in targets):
            continue
        val = node.value
        if not isinstance(val, ast.Dict):
            errors.append(Finding(
                PASS.name, _EVENTS_FILE, node.lineno,
                "KNOWN_EVENTS is not a dict literal — cannot verify"))
            continue
        for k in val.keys:
            s = const_str(k) if k is not None else None
            if s is None:
                errors.append(Finding(
                    PASS.name, _EVENTS_FILE,
                    getattr(k, "lineno", node.lineno),
                    "KNOWN_EVENTS key is not a string literal"))
            else:
                out[s] = k.lineno
    return out


def _emit_code(node: ast.Call) -> Optional[Tuple[Optional[str], int]]:
    """Is this an ``events.emit(...)`` site? -> (code literal or None,
    line)."""
    f = node.func
    if not (isinstance(f, ast.Attribute) and f.attr == "emit"
            and isinstance(f.value, ast.Name)
            and f.value.id in ("events", "_events")):
        return None
    if not node.args:
        return (None, node.lineno)
    return (const_str(node.args[0]), node.lineno)


class EventsRegistryPass(Pass):
    name = "events-registry"
    describe = ("events.emit sites match events.KNOWN_EVENTS and every "
                "registered code has an emit site")
    defect = ("a typo'd event code raises mid-outage (exactly when the "
              "journal must work); a site-less registry entry is a "
              "black-box signal that can never appear")
    tree_scoped = True
    roots = SCAN_ROOTS

    def run(self, ctx: Context) -> List[Finding]:
        findings: List[Finding] = []
        ef = ctx.get(_EVENTS_FILE)
        if ef is None or ef.tree is None:
            return [Finding(PASS.name, _EVENTS_FILE, 0,
                            "events module missing/unparseable")]
        codes = _parse_registry(ef.tree, findings)
        if not codes:
            findings.append(Finding(
                PASS.name, _EVENTS_FILE, 0,
                "KNOWN_EVENTS registry not found — every journal event "
                "code must be registered"))
        sites: Set[str] = set()
        for f in ctx.iter_files(self.roots, respect_changed=False):
            if f.tree is None or f.rel == _EVENTS_FILE:
                continue
            for node in ast.walk(f.tree):
                if not isinstance(node, ast.Call):
                    continue
                hit = _emit_code(node)
                if hit is None:
                    continue
                code, line = hit
                if code is None:
                    findings.append(Finding(
                        PASS.name, f.rel, line,
                        "events.emit code is not a string literal — "
                        "the site cannot be checked against "
                        "KNOWN_EVENTS"))
                    continue
                sites.add(code)
                if codes and code not in codes:
                    findings.append(Finding(
                        PASS.name, f.rel, line,
                        f"event code '{code}' is not in "
                        f"events.KNOWN_EVENTS — register it or fix "
                        f"the spelling"))
        for code, line in sorted(codes.items()):
            if code not in sites:
                findings.append(Finding(
                    PASS.name, _EVENTS_FILE, line,
                    f"KNOWN_EVENTS entry '{code}' has no events.emit "
                    f"site — a documented journal signal that can "
                    f"never appear"))
        return findings


PASS = EventsRegistryPass()
