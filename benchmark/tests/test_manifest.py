"""``BENCHMARK.json`` against the files it names and the contract's
limits on names, units and lengths."""

import json
import os
import re

import pytest

from benchmark.manifest import PACKAGE, ROOT, Manifest, metric_reader

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def man():
    return Manifest()


def test_top_level_keys_and_sizes(man):
    assert set(man.doc) == {"command", "paths", "run_seconds", "configs",
                            "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= man.doc["run_seconds"] <= 51
    assert man.doc["paths"] == ["benchmark"]
    assert len(man.doc["command"]) <= 32


def test_every_config_is_used_and_its_file_is_under_paths(man):
    used = {w["config"] for w in man.doc["workloads"]}
    files = set()
    for c in man.doc["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert c["file"].startswith("benchmark/") and c["file"] not in files
        files.add(c["file"])
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
        doc = json.load(open(os.path.join(ROOT, c["file"])))
        for key in c["reduced"]:
            assert NAME.match(key) and key in doc["reduced"]
        assert doc["guarantees"] and doc["known_of_the_source"]
        assert os.path.exists(os.path.join(
            PACKAGE, "corpora", doc["corpus_builder"] + ".py"))


def test_every_cell_resolves(man):
    seen = set()
    for w in man.doc["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert (w["config"], w["traffic"]) not in seen
        seen.add((w["config"], w["traffic"]))
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        cell = man.cell(w["name"])
        assert cell["mix"]["loop"] == "open"
        assert float(cell["mix"]["interval_ms"]) > 0
        e2e = {m["name"] for m in man.metrics("end_to_end", w["name"])}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert man.metrics("per_layer", w["name"])


def test_metrics_are_well_formed_and_readable(man):
    cells = {w["name"] for w in man.doc["workloads"]}
    names = set()
    e2e = {m["name"]: m for m in man.doc["end_to_end"]}
    for m in man.doc["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = set()
    for m in man.doc["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and m["source"] in SOURCES
        layers.add(m["layer"])
        read, args = metric_reader(m["name"])
        assert callable(read) and isinstance(args, dict)
        # every cell that reports it reports the metric it moves
        moved = e2e[m["moves"]]
        for c in m.get("workloads", cells):
            assert c in cells
            assert c in moved.get("workloads", cells)
    for m in man.doc["end_to_end"] + man.doc["per_layer"]:
        assert NAME.match(m["name"]) and m["name"] not in names
        names.add(m["name"])
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    perf = open(os.path.join(ROOT, "PERF.md"), encoding="utf-8").read()
    for layer in layers:
        assert layer in perf, f"PERF.md's list of layers lacks {layer!r}"
