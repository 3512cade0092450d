"""``ops/match_kernel.py`` holds what the program dispatches and nothing
else: there is ONE way to reach the match kernels, through the matcher
seats, and a probe or A/B kernel parked in that file is a kernel every
reader has to rule out before touching the one that runs."""

import ast
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KERNEL = os.path.join("vernemq_tpu", "ops", "match_kernel.py")


def _program_files():
    for base, _dirs, files in os.walk(os.path.join(ROOT, "vernemq_tpu")):
        for f in files:
            if f.endswith(".py"):
                yield os.path.relpath(os.path.join(base, f), ROOT)
    yield "__graft_entry__.py"


def _tree(rel):
    with open(os.path.join(ROOT, rel)) as fh:
        return ast.parse(fh.read(), rel)


def _names_taken_from_the_kernel_module(tree):
    """Names a module takes from ``match_kernel``: ``from …match_kernel
    import a, b`` and ``<alias>.attr`` for every alias the module is
    imported under (``K``, ``MK``, ``match_kernel``)."""
    aliases, taken = set(), set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            if (node.module or "").endswith("match_kernel"):
                taken.update(a.name for a in node.names)
            else:
                aliases.update(a.asname or a.name for a in node.names
                               if a.name == "match_kernel")
        elif isinstance(node, ast.Import):
            aliases.update(a.asname or a.name.split(".")[0]
                           for a in node.names
                           if a.name.endswith("match_kernel"))
    for node in ast.walk(tree):
        if (isinstance(node, ast.Attribute)
                and isinstance(node.value, ast.Name)
                and node.value.id in aliases):
            taken.add(node.attr)
    return taken


def _defined(tree):
    """Top-level callables of the kernel module -> the top-level names
    each one's definition refers to."""
    out = {}
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            name = node.name
        elif (isinstance(node, ast.Assign) and len(node.targets) == 1
              and isinstance(node.targets[0], ast.Name)
              and isinstance(node.value, ast.Call)):
            name = node.targets[0].id   # e.g. ``x_copy = jax.jit(x.__wrapped__)``
        else:
            continue
        out[name] = {n.id for n in ast.walk(node)
                     if isinstance(n, ast.Name)}
    return out


def test_every_public_kernel_is_dispatched_by_the_program():
    defined = _defined(_tree(KERNEL))
    assert "match_extract_windowed_flat_packed" in defined  # parsed at all
    reached = set()
    for rel in _program_files():
        if rel != KERNEL:
            reached |= _names_taken_from_the_kernel_module(_tree(rel))
    # what the program takes, and what those definitions call in turn
    todo = [n for n in reached if n in defined]
    reached = set(todo)
    while todo:
        for ref in defined[todo.pop()]:
            if ref in defined and ref not in reached:
                reached.add(ref)
                todo.append(ref)
    parked = sorted(n for n in defined
                    if not n.startswith("_") and n not in reached)
    assert parked == [], (
        "ops/match_kernel.py defines public callables that no module "
        f"under vernemq_tpu/ (nor __graft_entry__.py) reaches: {parked}. "
        "A kernel lives there once a matcher seat dispatches it; a "
        "candidate is measured by the benchmark from a branch, not parked")


def test_the_names_the_benchmark_and_the_trace_read_are_there():
    """What other files take BY NAME stays under that name: the traced
    run wraps two programs, the graft entry re-jits a third, and the
    trace reader selects device modules named ``*match*``."""
    defined = _defined(_tree(KERNEL))
    for name in ("match_extract_windowed_flat_packed", "match_many",
                 "match_extract_windowed_flat", "call_packed",
                 "call_match_many", "apply_delta_fused"):
        assert name in defined, name
