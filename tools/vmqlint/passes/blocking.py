"""``blocking`` pass: no loop-blocking calls inside ``async def``.

Port of the original ``tools/lint_blocking.py`` (PR 5/6/7/9) onto the
vmqlint framework.  The defect class is the old binary load shedder: a
synchronous stall (``time.sleep``, sync file IO, an unbounded
cross-thread wait, a sleep-poll ring helper, a process-wide mesh
barrier) sitting on the event loop inside an async path, freezing every
session's IO for its duration.  See the original module docstring —
the rules are unchanged; what changed is the scan scope (now also
``tools/`` and ``chip_smoke.py``, ``core.SCAN_ROOTS``: what drives the
broker runs under the same event-loop rules) and the suppression idiom
(``# vmqlint: allow(blocking): <reason>``; the legacy
``# lint: allow-blocking`` marker still works).
"""

from __future__ import annotations

import ast
from typing import List

from ..core import SCAN_ROOTS, Context, Finding, Pass, SourceFile

#: call spellings that block the event loop. Attribute calls match on
#: the LAST TWO components, so ``jax.distributed.initialize`` and a
#: bare ``distributed.initialize`` both hit.
_BAD_ATTR = {("time", "sleep"), ("os", "fsync"),
             ("shared_memory", "SharedMemory"),
             # mesh seams: process-wide barriers / device waits
             ("distributed", "initialize"),
             ("multihost_utils", "sync_global_devices"),
             ("multihost_utils", "process_allgather")}
_BAD_NAME = {"open", "input", "SharedMemory"}

#: method names that are ALWAYS blocking regardless of arguments: the
#: shm-ring sleep-poll helpers (parallel/shm_ring.py) and jax's
#: device-completion wait — device waits belong on executor threads
_BLOCKING_METHODS = {"pop_wait", "push_wait", "block_until_ready"}


def _call_name(node: ast.Call):
    f = node.func
    if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name):
        return (f.value.id, f.attr)
    if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Attribute):
        # dotted chain (jax.distributed.initialize): match on the last
        # two components — the prefix module alias is spelling-dependent
        return (f.value.attr, f.attr)
    if isinstance(f, ast.Name):
        return f.id
    return None


def _unbounded_wait(node: ast.Call):
    """Detect unbounded cross-thread waits by METHOD SHAPE (the receiver
    may be any expression, so typing is out of reach for an AST pass):
    ``x.acquire()`` with neither a positional ``blocking`` arg nor a
    ``timeout=``/``blocking=`` kwarg, ``x.result()`` with no arguments,
    and ``x.get()`` with no arguments at all (``dict.get(key)`` always
    has a positional arg, so it never matches).  Returns the pretty
    spelling to report, or None."""
    f = node.func
    if not isinstance(f, ast.Attribute):
        return None
    kw = {k.arg for k in node.keywords}
    if f.attr == "acquire":
        if not node.args and not ({"timeout", "blocking"} & kw):
            return ".acquire()"
    elif f.attr == "result":
        if not node.args and "timeout" not in kw:
            return ".result()"
    elif f.attr == "get":
        if not node.args and not kw:
            return ".get()"
    return None


class _AsyncBodyVisitor(ast.NodeVisitor):
    """Walk ONE async function's body without descending into nested
    function definitions (each async def gets its own visitor from the
    module walk; nested sync defs are not loop-bound)."""

    def __init__(self, findings: List[Finding], rel: str):
        self.findings = findings
        self.rel = rel
        # directly-awaited calls are loop-FRIENDLY versions of the same
        # spellings (asyncio.Queue.get, asyncio.Lock.acquire): exempt
        self._awaited = set()

    def visit_Await(self, node):  # noqa: N802
        if isinstance(node.value, ast.Call):
            self._awaited.add(id(node.value))
        self.generic_visit(node)

    def visit_FunctionDef(self, node):  # noqa: N802 — ast API
        pass  # nested sync def: not necessarily on the loop

    def visit_AsyncFunctionDef(self, node):  # noqa: N802
        pass  # visited by the module-level walk

    def visit_Call(self, node):  # noqa: N802
        name = _call_name(node)
        if name == ("asyncio", "wait_for") or name == "wait_for":
            # the wrapped awaitable is bounded by wait_for's timeout
            for a in node.args:
                if isinstance(a, ast.Call):
                    self._awaited.add(id(a))
        bad = (name in _BAD_NAME if isinstance(name, str)
               else name in _BAD_ATTR)
        if (not bad and isinstance(node.func, ast.Attribute)
                and node.func.attr in _BLOCKING_METHODS):
            # blocking helpers: any receiver spelling counts (the
            # method shape is the contract, like _unbounded_wait)
            bad, name = True, f".{node.func.attr}"
        if bad:
            pretty = name if isinstance(name, str) else ".".join(name)
            self.findings.append(Finding(
                PASS.name, self.rel, node.lineno,
                f"blocking call `{pretty}(...)` inside async def"))
        if id(node) not in self._awaited:
            unbounded = _unbounded_wait(node)
            if unbounded:
                self.findings.append(Finding(
                    PASS.name, self.rel, node.lineno,
                    f"unbounded `{unbounded}` inside async def (no "
                    f"timeout= — a wedged holder parks the loop "
                    f"forever; bound it or mark `# vmqlint: "
                    f"allow(blocking): <reason>`)"))
        self.generic_visit(node)


class BlockingPass(Pass):
    name = "blocking"
    describe = ("loop-blocking calls / unbounded waits inside async "
                "bodies")
    defect = ("a synchronous stall on the event loop freezes every "
              "session's IO (the old fixed-sleep load shedder)")
    roots = SCAN_ROOTS

    def run(self, ctx: Context) -> List[Finding]:
        findings: List[Finding] = []
        for f in ctx.iter_files(self.roots):
            self._scan(f, findings)
        return findings

    @staticmethod
    def _scan(f: SourceFile, findings: List[Finding]) -> None:
        if f.tree is None:
            return  # parse errors are reported once by the core
        for node in ast.walk(f.tree):
            if isinstance(node, ast.AsyncFunctionDef):
                v = _AsyncBodyVisitor(findings, f.rel)
                for child in node.body:
                    v.visit(child)


PASS = BlockingPass()
