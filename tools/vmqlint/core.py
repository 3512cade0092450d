"""vmqlint framework: shared parse cache, pass registry, suppression.

Design contract (stable — the tier-1 gate and the shims rely on it):

- **One walk.** Every pass consumes the same :class:`SourceFile`
  objects; a file is read and ``ast.parse``\\ d at most once per run no
  matter how many passes look at it.
- **Suppression.** A finding on line N is suppressed when line N (or a
  comment-only line directly above it) carries
  ``# vmqlint: allow(<pass>[, <pass>...]): <reason>`` naming the pass
  (or ``*``).  The reason is mandatory — an allow marker with no reason
  is itself a finding, as is one naming an unknown pass.  The legacy
  markers ``# lint: allow-blocking`` and ``# lint: observe-passthrough``
  are honored as ``allow(blocking)`` / ``allow(metrics)``.
- **Scopes.** File-scoped passes are restricted by ``--changed`` (and
  by explicit path arguments) to the files in play; tree-scoped passes
  (registry diffs need the whole tree to be meaningful) always run in
  full — they are one dict lookup per call site and cost nothing.
- **Exit codes.** 0 clean, 1 findings, 2 usage/internal error.
"""

from __future__ import annotations

import ast
import json
import os
import re
import subprocess
import sys
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

REPO_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))

#: scan roots, repo-relative, shared by every pass that walks the whole
#: program. ``vernemq_tpu`` is the product tree; ``tools`` and
#: ``chip_smoke.py`` drive it from async bodies that run under the same
#: event-loop rules (the old lint_blocking hardcoded the package dir
#: and missed them).
SCAN_ROOTS: Tuple[str, ...] = ("vernemq_tpu", "tools", "chip_smoke.py")

ALLOW_RE = re.compile(
    r"#\s*vmqlint:\s*allow\(\s*([a-z0-9*][a-z0-9*,\- ]*)\)"
    r"\s*(?::\s*(\S.*))?")
#: legacy marker substring -> pass it suppresses (no reason required —
#: pre-vmqlint sites carry their reason in prose after the marker)
LEGACY_MARKS = {"lint: allow-blocking": "blocking",
                "lint: observe-passthrough": "metrics"}


def const_str(node) -> Optional[str]:
    """The string value of an ``ast.Constant`` str node, else None —
    the shared literal probe every registry pass keys on."""
    return node.value if (isinstance(node, ast.Constant)
                          and isinstance(node.value, str)) else None


@dataclass(frozen=True)
class Finding:
    pass_name: str
    rel: str
    line: int
    message: str

    def render(self) -> str:
        return f"{self.rel}:{self.line}: [{self.pass_name}] {self.message}"

    def as_dict(self) -> Dict[str, object]:
        return {"pass": self.pass_name, "file": self.rel,
                "line": self.line, "message": self.message}


class SourceFile:
    """One scanned file: text + cached AST + suppression map."""

    def __init__(self, rel: str, text: str):
        self.rel = rel
        self.text = text
        self._tree: Optional[ast.Module] = None
        self.syntax_error: Optional[SyntaxError] = None
        self._parsed = False
        # line -> pass names allowed there ('*' = every pass); a marker
        # on a comment-only line also covers the next line, so long
        # statements can carry their annotation above instead of
        # stretching past the line-length limit
        self.allow: Dict[int, Set[str]] = {}
        #: (line, passes, reason) of every vmqlint allow marker, for
        #: marker-hygiene checks
        self.markers: List[Tuple[int, Tuple[str, ...], str]] = []
        self._scan_markers()

    @property
    def tree(self) -> Optional[ast.Module]:
        if not self._parsed:
            self._parsed = True
            try:
                self._tree = ast.parse(self.text, filename=self.rel)
            except SyntaxError as e:
                self.syntax_error = e
        return self._tree

    def _scan_markers(self) -> None:
        lines = self.text.splitlines()
        for i, line in enumerate(lines, 1):
            names: Set[str] = set()
            m = ALLOW_RE.search(line)
            if m:
                passes = tuple(p.strip() for p in m.group(1).split(",")
                               if p.strip())
                self.markers.append((i, passes, (m.group(2) or "").strip()))
                names.update(passes)
            for mark, pass_name in LEGACY_MARKS.items():
                if mark in line:
                    names.add(pass_name)
            if not names:
                continue
            self.allow.setdefault(i, set()).update(names)
            # a marker inside a comment block annotates the first code
            # line after it (long reasons wrap; the statement itself
            # may be black-formatted past the marker line) — walk over
            # the remaining comment-only and blank lines to the code
            # line below
            if line.lstrip().startswith("#"):
                j = i  # 0-based index of the line after the marker
                while j < len(lines) and (
                        not lines[j].strip()
                        or lines[j].lstrip().startswith("#")):
                    j += 1
                self.allow.setdefault(j + 1, set()).update(names)

    def allows(self, pass_name: str, line: int) -> bool:
        names = self.allow.get(line)
        return bool(names) and (pass_name in names or "*" in names)


class Context:
    """What a pass sees: the file set plus the changed-file filter."""

    def __init__(self, files: Dict[str, SourceFile],
                 changed: Optional[Set[str]] = None):
        self.files = files
        self.changed = changed  # None = everything is in play

    def get(self, rel: str) -> Optional[SourceFile]:
        return self.files.get(rel)

    def iter_files(self, roots: Sequence[str],
                   respect_changed: bool = True) -> Iterable[SourceFile]:
        for rel in sorted(self.files):
            if not any(rel == r or rel.startswith(r.rstrip("/") + "/")
                       for r in roots):
                continue
            if (respect_changed and self.changed is not None
                    and rel not in self.changed):
                continue
            yield self.files[rel]


class Pass:
    """Base pass. Subclasses set ``name``/``describe``/``defect`` and
    implement :meth:`run`; ``tree_scoped`` passes ignore ``--changed``
    (their registry diffs are only meaningful over the whole tree)."""

    name: str = ""
    describe: str = ""
    #: the defect class this pass encodes (README table; --list output)
    defect: str = ""
    tree_scoped: bool = False
    roots: Tuple[str, ...] = ("vernemq_tpu",)

    def run(self, ctx: Context) -> List[Finding]:  # pragma: no cover
        raise NotImplementedError


# --------------------------------------------------------- file discovery

def _rel_ok(rel: str) -> bool:
    return rel.endswith(".py") and "__pycache__" not in rel


def collect_files(root: str = REPO_ROOT,
                  overrides: Optional[Dict[str, str]] = None,
                  ) -> Dict[str, SourceFile]:
    """Read every scannable file under :data:`SCAN_ROOTS` once.
    ``overrides`` maps repo-relative paths to replacement text (tests
    seed defects without touching the tree; an override may also add a
    file that does not exist on disk)."""
    files: Dict[str, SourceFile] = {}
    for entry in SCAN_ROOTS:
        top = os.path.join(root, entry)
        if os.path.isfile(top):
            if _rel_ok(entry):
                files[entry] = None  # type: ignore[assignment]
            continue
        for dirpath, dirs, names in os.walk(top):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for fn in names:
                rel = os.path.relpath(os.path.join(dirpath, fn), root)
                rel = rel.replace(os.sep, "/")
                if _rel_ok(rel):
                    files[rel] = None  # type: ignore[assignment]
    for rel in list(files):
        if overrides and rel in overrides:
            continue
        with open(os.path.join(root, rel), "r", encoding="utf-8") as fh:
            files[rel] = SourceFile(rel, fh.read())
    for rel, text in (overrides or {}).items():
        files[rel] = SourceFile(rel, text)
    return files


def changed_files(root: str = REPO_ROOT) -> Optional[Set[str]]:
    """Repo-relative paths changed vs HEAD (staged, unstaged, and
    untracked) — the ``--changed`` fast-iteration scope.  Returns
    ``None`` when git is unavailable/failing: that must widen the scan
    to everything, not narrow it to nothing (an empty set is the
    legitimate "working tree clean" answer; a FAILED probe producing
    the same value would make the gate vacuously green)."""
    out: Set[str] = set()
    for args in (["git", "diff", "--name-only", "HEAD", "--"],
                 ["git", "ls-files", "--others", "--exclude-standard"]):
        try:
            res = subprocess.run(args, cwd=root, capture_output=True,
                                 text=True, timeout=30)
        except (OSError, subprocess.TimeoutExpired):
            return None  # no git: scan everything
        if res.returncode != 0:
            return None
        out.update(line.strip() for line in res.stdout.splitlines()
                   if line.strip())
    return out


# ----------------------------------------------------------------- runner

def _registry() -> Dict[str, Pass]:
    from .passes import all_passes

    return {p.name: p for p in all_passes()}


def _hygiene(files: Iterable[SourceFile],
             known: Set[str]) -> List[Finding]:
    """The suppression idiom polices itself: a marker with a typo'd
    pass name silently suppresses nothing, and one with no reason
    defeats the annotate-deliberate-sites discipline."""
    out: List[Finding] = []
    for f in files:
        for line, passes, reason in f.markers:
            unknown = [p for p in passes if p != "*" and p not in known]
            if unknown:
                out.append(Finding(
                    "allow-marker", f.rel, line,
                    f"allow() names unknown pass(es) "
                    f"{', '.join(sorted(unknown))} (known: "
                    f"{', '.join(sorted(known))})"))
            if not reason:
                out.append(Finding(
                    "allow-marker", f.rel, line,
                    "allow() marker with no reason — write `# vmqlint: "
                    "allow(<pass>): <why this site is deliberate>`"))
    return out


def run(passes: Optional[Sequence[str]] = None,
        changed: bool = False,
        paths: Optional[Sequence[str]] = None,
        overrides: Optional[Dict[str, str]] = None,
        files: Optional[Dict[str, SourceFile]] = None,
        root: str = REPO_ROOT,
        ) -> Tuple[List[Finding], Dict[str, object]]:
    """Run the selected passes; returns (findings, stats).

    ``paths`` restricts file-scoped passes to those repo-relative files
    (the shim/test surface); ``changed`` restricts them to the git
    working-set.  Tree-scoped passes always see everything."""
    registry = _registry()
    if passes is None:
        selected = list(registry.values())
    else:
        missing = [p for p in passes if p not in registry]
        if missing:
            raise KeyError(f"unknown pass(es): {', '.join(missing)} "
                           f"(known: {', '.join(sorted(registry))})")
        selected = [registry[p] for p in passes]
    if files is None:
        files = collect_files(root, overrides)
    elif overrides:
        files = dict(files)
        for rel, text in overrides.items():
            files[rel] = SourceFile(rel, text)

    restrict: Optional[Set[str]] = None
    if paths is not None:
        restrict = {p.replace(os.sep, "/") for p in paths}
        unknown = {p for p in restrict if p not in files}
        if unknown:
            # a typo'd path silently scanning zero files would read as
            # "clean" — the same vacuous-green mode the --changed git
            # probe guards against
            raise KeyError(f"path(s) not in the scan set: "
                           f"{', '.join(sorted(unknown))}")
    elif changed:
        delta = changed_files(root)
        if delta is not None:  # git failure -> full scan, never "none"
            restrict = {rel for rel in delta if rel in files}
    ctx = Context(files, restrict)

    findings: List[Finding] = []
    # a file that does not parse defeats every pass — surface it once
    scanned = list(ctx.iter_files(SCAN_ROOTS, respect_changed=False))
    for f in scanned:
        if f.tree is None and f.syntax_error is not None:
            findings.append(Finding(
                "parse", f.rel, f.syntax_error.lineno or 0,
                f"syntax error: {f.syntax_error.msg}"))
    findings.extend(_hygiene(scanned, set(registry)))
    for p in selected:
        findings.extend(p.run(ctx))

    # parse and marker-hygiene findings are about the marker/file
    # itself and must not be suppressible by the very marker they
    # police (a reasonless star marker would otherwise self-suppress
    # the mandatory-reason finding along with everything on its line)
    unsuppressible = {"parse", "allow-marker"}
    kept = [f for f in findings
            if f.pass_name in unsuppressible
            or not (f.rel in files and files[f.rel].allows(f.pass_name,
                                                           f.line))]
    kept.sort(key=lambda f: (f.rel, f.line, f.pass_name))
    stats: Dict[str, object] = {
        "passes": [p.name for p in selected],
        "files_scanned": len(scanned),
        "restricted_to": sorted(restrict) if restrict is not None else None,
        "finding_count": len(kept),
        "suppressed": len(findings) - len(kept),
    }
    return kept, stats


def main(argv: Optional[Sequence[str]] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m tools.vmqlint",
        description="unified static-analysis suite (tier-1 pre-test "
                    "gate); exit 0 clean, 1 findings, 2 error")
    ap.add_argument("paths", nargs="*",
                    help="restrict file-scoped passes to these "
                         "repo-relative files")
    ap.add_argument("--pass", dest="passes", action="append",
                    metavar="NAME", help="run only this pass "
                    "(repeatable)")
    ap.add_argument("--changed", action="store_true",
                    help="file-scoped passes only look at the git "
                         "working-set (fast local iteration; "
                         "tree-scoped registry passes still run full)")
    ap.add_argument("--json", dest="as_json", action="store_true",
                    help="machine-readable findings on stdout")
    ap.add_argument("--list", dest="list_passes", action="store_true",
                    help="list registered passes and exit")
    try:
        args = ap.parse_args(argv)
    except SystemExit as e:
        return 0 if e.code in (0, None) else 2
    try:
        registry = _registry()
        if args.list_passes:
            for name in sorted(registry):
                p = registry[name]
                scope = "tree" if p.tree_scoped else "file"
                print(f"{name:18s} [{scope}] {p.describe}")
            return 0
        findings, stats = run(passes=args.passes,
                              changed=args.changed,
                              paths=args.paths or None)
    except KeyError as e:
        print(f"vmqlint: {e.args[0]}", file=sys.stderr)
        return 2
    except Exception as e:  # internal error must not read as "clean"
        print(f"vmqlint: internal error: {e!r}", file=sys.stderr)
        return 2
    if args.as_json:
        print(json.dumps({"findings": [f.as_dict() for f in findings],
                          **stats}, indent=2, sort_keys=True))
        return 1 if findings else 0
    if findings:
        print(f"vmqlint: {len(findings)} finding(s):", file=sys.stderr)
        for f in findings:
            print(f"  {f.render()}", file=sys.stderr)
        return 1
    print(f"vmqlint: clean ({len(stats['passes'])} passes, "
          f"{stats['files_scanned']} files"
          + (", changed-scope" if args.changed else "") + ")")
    return 0
