"""``BENCHMARK.json`` and the files it names.

A cell is found by its name in ``workloads``; its configuration is the
``file`` of the ``configs`` entry it names (the rate a sweep fixed is
part of it: the deployment's live publishers), its mix
``benchmark/traffic/<traffic>.json``. A
per-layer metric ``<base>.<suffix>`` is described by
``benchmark/metrics/<base>.<suffix>.json`` or, failing that,
``benchmark/metrics/<base>.json``: ``{"reader": <module in
benchmark/readers/>, "args": {...}}``. A later PR adds files and entries
and edits nothing here.
"""

from __future__ import annotations

import importlib
import json
import os
from typing import Any, Dict, List

PACKAGE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(PACKAGE)


def _json(path: str) -> Any:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


class Manifest:
    def __init__(self, root: str = ROOT) -> None:
        self.root = root
        self.doc = _json(os.path.join(root, "BENCHMARK.json"))

    def cell(self, name: str) -> Dict[str, Any]:
        """A cell of ``workloads`` by its name."""
        for w in self.doc["workloads"]:
            if w["name"] == name:
                break
        else:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        cfg = next(c for c in self.doc["configs"] if c["name"] == w["config"])
        return {
            "name": name, "chips": int(w["chips"]),
            "config": _json(os.path.join(self.root, cfg["file"])),
            "mix": _json(os.path.join(PACKAGE, "traffic",
                                      w["traffic"] + ".json")),
        }

    def metrics(self, group: str, cell: str) -> List[Dict[str, Any]]:
        """The ``end_to_end`` or ``per_layer`` metrics a cell reports."""
        return [m for m in self.doc[group]
                if "workloads" not in m or cell in m["workloads"]]

    @property
    def run_seconds(self) -> int:
        return int(self.doc["run_seconds"])


def metric_reader(name: str):
    """``(read, args)`` of a per-layer metric."""
    path = os.path.join(PACKAGE, "metrics", name + ".json")
    if not os.path.exists(path):
        path = os.path.join(PACKAGE, "metrics",
                            name.rsplit(".", 1)[0] + ".json")
    spec = _json(path)
    mod = importlib.import_module("benchmark.readers." + spec["reader"])
    return mod.read, spec.get("args", {})
