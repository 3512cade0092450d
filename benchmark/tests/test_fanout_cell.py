"""The fan-out cell ``fanout1k.burst1s`` (``mqttbs_fanout_1k`` under
``burst1s``): its files against what the cell is meant to be, its
rehearsal on the CPU, its control, and the readers it brought."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark import corpus as corpus_mod
from benchmark import reference, work, work_wide
from benchmark.manifest import ROOT, Manifest, metric_reader
from benchmark.readers import program_counter, wide_roofline
from benchmark.tests.test_run import KEYS, rehearse
from benchmark.trace import reduce as T

CELL = "fanout1k.burst1s"
MS = 1_000_000


@pytest.fixture(scope="module")
def man():
    return Manifest()


def test_the_configuration_is_the_suites_case_with_the_rate_alone_cut(man):
    entry = next(c for c in man.doc["configs"]
                 if c["name"] == "mqttbs_fanout_1k")
    cfg = man.cell(CELL)["config"]
    p2p = json.load(open(os.path.join(
        ROOT, "benchmark/configs/mqttbs_p2p_50k.json")))
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert "fanout-5-1000-5-250K" in cfg["source"]
    assert entry["reduced"] == list(cfg["reduced"]) \
        == ["msgs_per_publisher_per_s"]
    assert cfg["guarantees"] == p2p["guarantees"]      # word for word
    assert (cfg["topics"], cfg["subscribers"], cfg["publishers"]) \
        == (5, 1000, 5)
    assert cfg["qos"] == 1 and cfg["payload_bytes"] == 16
    assert cfg["matched_rows_per_publish"] == cfg["subscribers"]
    assert cfg["known_of_the_source"] and cfg["assumed"]
    mix = man.cell(CELL)["mix"]
    assert mix["connections"] == cfg["publishers"] and mix["qos"] == 1
    assert mix["phase_groups"] == 1 and mix["interval_ms"] == 1000
    # the one cut: the mix's burst IS the configuration's rate, and a
    # tick reaches the device (more than the host threshold of 8)
    assert mix["burst"] == cfg["msgs_per_publisher_per_s"] >= 2
    assert mix["burst"] * mix["connections"] > 8
    cell = next(w for w in man.doc["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and "5,000 rows" in cell["why"]


def test_every_accepted_metric_and_the_new_ones_are_declared_for_it(man):
    names = {m["name"] for m in man.metrics("per_layer", CELL)}
    first = man.doc["workloads"][0]["name"]
    for m in man.metrics("per_layer", first):
        assert m["name"] + ".fan" in names
    for base in ("host_fallback_pct", "wide_served_pct", "rows_per_pub",
                 "fold_wide_ms", "release_turn_ms", "frames_per_write",
                 "wide_kernel_ms", "wide_roofline"):
        assert base + ".fan" in names
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "metrics", base + ".json"))
    for m in man.metrics("per_layer", CELL):
        assert m["workloads"] == [CELL] and m["name"].endswith(".fan")
        read, args = metric_reader(m["name"])
        assert callable(read)
    assert not [m for m in man.metrics("per_layer", first)
                if m["name"].endswith(".fan")]


@pytest.mark.parametrize("seed", [1, 2147484999])
def test_corpus_has_one_structure_and_every_publish_owes_a_thousand(
        man, seed):
    cfg = man.cell(CELL)["config"]
    c = corpus_mod.build(cfg, seed)
    assert c.n_stored == 0 and list(c.records()) == []
    assert len(c.live) == 1000 and c.n_resident == 5000
    assert len({s.client_id for s in c.live}) == 1000
    assert sorted(c.pools[1]) == sorted(str(k) for k in range(5))
    want = sorted(f"bench/{w}" for w in c.pools[1])
    for s in c.live[::97]:
        assert sorted(f for f, _q in s.tcp_filters) == want
        assert {q for _f, q in s.tcp_filters} == {1}
    # publisher p publishes to topic p, whatever word the seed gave it
    for p in range(5):
        assert c.topics(p, 3, 2).tolist() == [[0, p], [0, p]]
    trie = reference.session_trie(c.live)
    sizes = [len(pool) for pool in c.pools]
    levels = np.concatenate([c.topics(p, 0, 2) for p in range(5)])
    exp = reference.expected_keys(
        trie, c.pools, sizes, levels, np.repeat(np.arange(5), 2),
        np.tile(np.arange(2), 5), 1)
    assert len(exp) == 10 * 1000 and len(np.unique(exp)) == len(exp)
    other = corpus_mod.build(cfg, seed + 1)
    assert [s.client_id for s in other.live] != [s.client_id for s in c.live]


def test_rehearsal_prints_a_correct_last_line():
    """The cell at rehearsal size (320 subscribers: past tpu_max_fanout,
    so the wide pass serves) through the program on the CPU backend."""
    p = rehearse(CELL, trace=1)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert KEYS <= set(out) and list(out)[-1] == "compared"
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert out["facts"]["deliveries"] == out["attempted"] * 320
    assert out["compared"]["device_served_pct"]["value"] >= 50.0
    assert out["rehearsal"] is True and out["metrics"] == {}


def test_control_losing_one_delivery_in_97_reads_lost_qos1():
    cmd = [sys.executable, "-m", "benchmark.control", "--workload", CELL,
           "--seed", "2147483659", "--seconds", "4", "--rehearse",
           "--every", "97", "--break", "lose_qos1"]
    p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert "jax" not in p.stderr.lower()
    assert out["correct"] is False
    assert out["compared"]["lost_qos1"]["value"] > 0
    others = [k for k, v in out["compared"].items()
              if k != "lost_qos1" and v["value"] > v.get("limit", 0)]
    assert not others, others


def test_wide_bytes_count_the_topics_rows_once_and_a_bit_a_row_back():
    # 5 topics x 1,000 rows x 2 levels x 2 B, the topics' own levels,
    # 5,000 bits back; never more rows than are resident
    assert work_wide.wide_bytes(5000, 2, 5, 1000) == 20000 + 20 + 625
    assert work_wide.wide_bytes(3000, 2, 5, 1000) == 12000 + 20 + 625
    assert work_wide.wide_least_seconds("TPU v5e", 5000, 2, 5, 1000) \
        == pytest.approx(20645 / 819e9)


def _program(monkeypatch, matcher, **fastpath):
    """Stand-ins for the two modules of the program the reader asks."""
    monkeypatch.setitem(sys.modules, "vernemq_tpu.models.tpu_matcher",
                        types.SimpleNamespace(**(matcher or {})))
    monkeypatch.setitem(sys.modules, "vernemq_tpu.protocol.fastpath",
                        types.SimpleNamespace(**fastpath))


def test_program_counter_reads_one_source_for_both_sides(monkeypatch):
    _program(monkeypatch, {"wide_publishes": 90, "wide_failures": 10,
                           "wide_rows": 90_000},
             egress_publishes=6000, egress_writes=3000)
    ctx = {"counters": {"wide_failures": 7.0}}
    # wide_publishes is not among the window's counters: BOTH sides are
    # then the process's totals, never 90 over the window's 7
    assert program_counter.read(ctx, ["wide_publishes"],
                                ["wide_publishes", "wide_failures"],
                                100.0) == 90.0
    assert program_counter.read(ctx, ["wide_rows"],
                                ["wide_publishes"]) == 1000.0
    assert program_counter.read(ctx, ["egress_publishes"],
                                ["egress_writes"]) == 2.0
    ctx = {"counters": {"match_publishes": 50.0, "host_fallbacks": 5.0}}
    assert program_counter.read(ctx, ["host_fallbacks"],
                                ["match_publishes"], 100.0) == 10.0


def test_a_program_without_the_counters_gives_nothing_to_read(monkeypatch):
    """The parent of the PR that brought them: the metric is left out."""
    _program(monkeypatch, None, egress_writes=10)
    ctx = {"counters": {"match_publishes": 7.0}, "trace": None}
    assert program_counter.read(ctx, ["wide_publishes"],
                                ["match_publishes"]) is None
    assert program_counter.read(ctx, ["egress_publishes"],
                                ["egress_writes"]) is None
    assert wide_roofline.read(ctx, ["wide_mask"]) is None
    _program(monkeypatch, {"wide_publishes": 0, "wide_failures": 0})
    assert program_counter.read({"counters": {}}, ["wide_publishes"],
                                ["wide_publishes", "wide_failures"]) is None


def test_wide_roofline_reads_a_share_under_a_hundred(monkeypatch, man):
    _program(monkeypatch, {"wide_topics": 150, "wide_dispatches": 30})
    ops = [(0, 1 * MS, "fusion.1")]
    mods = [(0, 1 * MS, "jit_wide_mask_packed(7)"),
            (5 * MS, 9 * MS, "jit_match_extract_windowed_flat_packed(3)")]
    trace = T.reduce_events([("/device:TPU:0", ops, mods)], [],
                            window_s=0.1)
    ctx = {"trace": trace, "counters": {}, "resident": 5000, "levels": 2,
           "config": man.cell(CELL)["config"],
           "device": {"kind": "TPU v5 lite"}}
    least = work_wide.wide_least_seconds("TPU v5 lite", 5000, 2, 5, 1000)
    assert wide_roofline.read(ctx, ["wide_mask"]) \
        == pytest.approx(100.0 * least / 1e-3)
    assert 0 < wide_roofline.read(ctx, ["wide_mask"]) < 100
    read, args = metric_reader("wide_kernel_ms.fan")
    assert read(ctx, **args) == pytest.approx(1.0)
    read, args = metric_reader("match_kernel_ms.fan")   # the narrow form's
    assert read(ctx, **args) == pytest.approx(4.0)
    assert work.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
