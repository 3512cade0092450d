"""``metrics`` pass: metric-registry HELP + observe()/span() family names.

Port of the original ``tools/lint_metrics.py`` (PR 8) onto the vmqlint
framework.  Two invariants, both cheap to break silently and annoying
to debug at scrape time:

1. Every registered metric has non-empty HELP text: the ``COUNTERS``
   table (broker/metrics.py), the ``STAGE_FAMILIES`` histogram table
   (observability/histogram.py), and every literal descriptions dict
   passed to ``Metrics.register_gauges``.
2. Every ``observe("name", ...)`` and ``span("name")`` (also
   ``span_begin`` / ``span_end``) call site names
   a REGISTERED histogram family — a typo'd family raises KeyError on
   the hot path, in production, at the first sampled publish, instead
   of here.

Suppress a delegation seam (Metrics.observe -> histogram.observe
forwards a dynamic name by design) with the vmqlint allow marker
naming this pass and its reason, or the legacy
``# lint: observe-passthrough``.
"""

from __future__ import annotations

import ast
from typing import List, Set

from ..core import Context, Finding, Pass, const_str

_COUNTERS_FILE = "vernemq_tpu/broker/metrics.py"
_HIST_FILE = "vernemq_tpu/observability/histogram.py"

_const_str = const_str  # shared literal probe (core.py)


def _tuple_table(tree: ast.AST, name: str, rel: str,
                 errors: List[Finding], what: str) -> Set[str]:
    """Collect (name, help) 2-tuple tables like COUNTERS /
    STAGE_FAMILIES; flag entries with empty or non-literal HELP."""
    names: Set[str] = set()
    for node in ast.walk(tree):
        if not isinstance(node, (ast.Assign, ast.AnnAssign)):
            continue
        targets = (node.targets if isinstance(node, ast.Assign)
                   else [node.target])
        if not any(isinstance(t, ast.Name) and t.id == name
                   for t in targets):
            continue
        value = node.value
        if not isinstance(value, (ast.List, ast.Tuple)):
            continue
        for elt in value.elts:
            if not isinstance(elt, ast.Tuple) or len(elt.elts) < 2:
                errors.append(Finding(
                    PASS.name, rel, elt.lineno,
                    f"{what} entry is not a (name, help) tuple"))
                continue
            metric = _const_str(elt.elts[0])
            # help may be an implicit concat of string constants — the
            # parser folds adjacent literals into one Constant, so a
            # plain _const_str covers the multi-line style used here
            help_text = _const_str(elt.elts[1])
            if metric is None:
                errors.append(Finding(
                    PASS.name, rel, elt.lineno,
                    f"{what} name is not a string literal"))
                continue
            names.add(metric)
            if not help_text or not help_text.strip():
                errors.append(Finding(
                    PASS.name, rel, elt.lineno,
                    f"{what} '{metric}' has empty HELP text"))
    return names


def _check_gauge_dicts(tree: ast.AST, rel: str,
                       errors: List[Finding]) -> None:
    """Every literal dict passed to register_gauges(...) must have
    non-empty string values (the HELP text of each gauge)."""
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        if not (isinstance(fn, ast.Attribute)
                and fn.attr == "register_gauges"):
            continue
        cands = list(node.args[1:2]) + [
            kw.value for kw in node.keywords
            if kw.arg == "descriptions"]
        for d in cands:
            if not isinstance(d, ast.Dict):
                continue  # dynamic dict: parity tests cover those names
            for k, v in zip(d.keys, d.values):
                key = _const_str(k) if k is not None else None
                val = _const_str(v)
                if key is None:
                    continue
                if not val or not val.strip():
                    errors.append(Finding(
                        PASS.name, rel, v.lineno,
                        f"gauge '{key}' registered with empty HELP "
                        f"text"))


def _check_observe_sites(tree: ast.AST, rel: str, families: Set[str],
                         errors: List[Finding]) -> None:
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or not node.args:
            continue
        fn = node.func
        # exact-name match: observe_lag and other observe-ish methods
        # fall out here without needing an exempt list
        what = (fn.attr if isinstance(fn, ast.Attribute)
                else fn.id if isinstance(fn, ast.Name) else None)
        if what not in ("observe", "span", "span_begin", "span_end"):
            continue
        fam = _const_str(node.args[0])
        if fam is None:
            errors.append(Finding(
                PASS.name, rel, node.lineno,
                f"{what}() family is not a string literal (cannot "
                "verify registration statically)"))
        elif fam not in families:
            errors.append(Finding(
                PASS.name, rel, node.lineno,
                f"{what}() names unregistered histogram family "
                f"'{fam}'"))


class MetricsPass(Pass):
    name = "metrics"
    describe = ("every counter/gauge/histogram has HELP text; every "
                "observe()/span() names a registered family")
    defect = ("an empty HELP ships a broken exposition line; a typo'd "
              "family KeyErrors on the hot path under load")
    tree_scoped = True  # the family registry lives in two fixed files

    def run(self, ctx: Context) -> List[Finding]:
        errors: List[Finding] = []
        counters = ctx.get(_COUNTERS_FILE)
        if counters is None or counters.tree is None:
            return [Finding(PASS.name, _COUNTERS_FILE, 0,
                            "COUNTERS table file missing/unparseable")]
        _tuple_table(counters.tree, "COUNTERS", _COUNTERS_FILE, errors,
                     "counter")
        hist = ctx.get(_HIST_FILE)
        if hist is None or hist.tree is None:
            return [Finding(PASS.name, _HIST_FILE, 0,
                            "STAGE_FAMILIES file missing/unparseable")]
        families = _tuple_table(hist.tree, "STAGE_FAMILIES", _HIST_FILE,
                                errors, "histogram")
        if not families:
            errors.append(Finding(PASS.name, _HIST_FILE, 0,
                                  "STAGE_FAMILIES table not found"))
        for f in ctx.iter_files(self.roots, respect_changed=False):
            if f.tree is None:
                continue
            _check_gauge_dicts(f.tree, f.rel, errors)
            _check_observe_sites(f.tree, f.rel, families, errors)
        return errors


PASS = MetricsPass()
