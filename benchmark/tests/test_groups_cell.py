"""The consumer-groups cell ``fangrp50k.tick1s``
(``mqttbs_fanin_groups_50k`` under ``tick1s``): its files against what the
cell is meant to be (three shared subscriptions over the suite's 500
subscribers, two under one ShareName, 250 / 125 / 125), its rehearsal on
the CPU (three deliveries a publish, one to a member of each, on the wire
plane, the dense phase in every program), and the readers of its two new
metrics."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import corpus as corpus_mod
from benchmark import reference
from benchmark.manifest import ROOT, Manifest, metric_reader
from benchmark.tests.test_run import KEYS

CELL = "fangrp50k.tick1s"
CONFIG = "mqttbs_fanin_groups_50k"
#: (ShareName, filter, members, clean session) of each shared subscription
SHARED = [("ingest", "bench/+", 250, True), ("ingest", "bench/#", 125, True),
          ("archive", "#", 125, False)]
NEW = ("share_split_pct", "dense_phase_pct")
#: of the fan-in cell's metrics, the ones this cell reports: the share
#: path's, the device programs', the stall witness's and the floors (the
#: benchmark holds at most 128 per-layer metrics)
FIN = ("phases_per_dispatch", "match_kernel_ms", "match_roofline",
       "share_wire_pct", "share_stale_pct", "fanout_pass_pct",
       "route_us_per_pub", "release_turn_ms", "stall_ms", "stall_gil_ms",
       "stall_host_ms", "device_served_pct", "governor_raised_pct",
       "device_idle_pct", "dispatch_ms", "loop_lag_ms_max")
WIDE = ("wide_served_pct", "rows_per_pub", "fold_wide_ms", "wide_kernel_ms",
        "wide_roofline")


@pytest.fixture(scope="module")
def man():
    return Manifest()


def test_the_configuration_is_three_consumer_groups_over_the_fanin_stream(
        man):
    entry = next(c for c in man.doc["configs"] if c["name"] == CONFIG)
    cfg = man.cell(CELL)["config"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert "4.8.2" in cfg["source"] and "fanin-50k-500-50k-50k" \
        in cfg["source"]
    assert entry["reduced"] == list(cfg["reduced"]) == ["live_publishers"]
    assert (cfg["topics"], cfg["publishers"], cfg["subscribers"]) \
        == (50000, 50000, 500)
    assert cfg["corpus_builder"] == "share_groups"
    assert [(s["share"], s["filter"], s["members"], s["clean_session"])
            for s in cfg["shared"]] == SHARED
    assert sum(s["members"] for s in cfg["shared"]) == cfg["subscribers"]
    assert cfg["qos"] == 1 and cfg["payload_bytes"] == 16
    assert cfg["msgs_per_publisher_per_s"] == 1
    assert cfg["matched_rows_per_publish"] == 3
    assert cfg["known_of_the_source"] and cfg["assumed"]
    assert "ONE delivery per matching shared subscription (ShareName, " \
        "filter)" in cfg["guarantees"]["delivery_qos"]
    live = cfg["live_publishers"]
    assert 8 < live < cfg["publishers"] and live % 100 == 0
    mix = man.cell(CELL)["mix"]
    assert mix["name"] == "tick1s" and mix["qos"] == 1
    assert mix["phase_groups"] == 1 and mix["interval_ms"] == 1000
    cell = next(w for w in man.doc["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["config"] == CONFIG
    assert f"{live:,}" in cell["why"] and len(cell["why"]) <= 200


def test_the_metrics_declared_for_it_are_the_fanin_cells_and_two_more(man):
    fin = {m["name"][:-len(".fin")]: m for m in man.doc["per_layer"]
           if m["name"].endswith(".fin")}
    names = {m["name"] for m in man.metrics("per_layer", CELL)}
    assert names == {b + ".grp" for b in FIN + NEW}
    assert not names & {b + ".grp" for b in WIDE}
    assert len(man.doc["per_layer"]) <= 128
    for m in man.metrics("per_layer", CELL):
        base = m["name"][:-len(".grp")]
        if base in fin:  # as the fan-in cell declares it
            assert m == dict(fin[base], name=m["name"], workloads=[CELL])
    layers = {m["layer"] for m in man.doc["per_layer"]
              if not m["name"].endswith(".grp")}
    for m in man.metrics("per_layer", CELL):
        assert m["workloads"] == [CELL] and m["layer"] in layers
        read, _args = metric_reader(m["name"])
        assert callable(read)
    for base in NEW:
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "metrics", base + ".json"))
    for other in ("p2p50k.tick1s", "fanout1k.burst1s", "fanin50k.tick1s"):
        assert not [m for m in man.metrics("per_layer", other)
                    if m["name"].endswith(".grp")]


@pytest.mark.parametrize("seed", [1, 2147484999])
def test_corpus_is_three_disjoint_shared_subscriptions(man, seed):
    cfg = man.cell(CELL)["config"]
    c = corpus_mod.build(cfg, seed)
    assert len(c.live) == c.n_resident == 500
    assert c.publishers == cfg["live_publishers"]
    by_sub = {}
    for s in c.live:
        subs = s.subscriptions()
        assert len(subs) == 1 and list(subs.values()) == [1]
        by_sub.setdefault(next(iter(subs)), []).append(s)
    assert sorted((w, len(ss)) for w, ss in by_sub.items()) == sorted(
        (("$share", n, *f.split("/")), k) for n, f, k, _c in SHARED)
    # the archivers stand in the persisted DB and connect persistent
    archive = by_sub[("$share", "archive", "#")]
    assert all(not s.clean_session and not s.tcp_filters for s in archive)
    assert c.n_stored == 125 and sorted(c.records()) == sorted(
        (s.client_id, s.stored) for s in archive)
    assert all(s.clean_session and not s.stored for w, ss in by_sub.items()
               if w[1] == "ingest" for s in ss)
    for p in (0, 7, cfg["live_publishers"] - 1):
        assert c.topics(p, 3, 2).tolist() == [[0, p], [0, p]]
    # the reference owes every publish three deliveries, one to a member
    # of each shared subscription (ShareName, filter)
    shared = reference.Shared(c.live)
    owed = reference.session_trie(c.live, shared).match(
        [c.pools[0][0], c.pools[1][5]])
    assert len(owed) == cfg["matched_rows_per_publish"] == len(shared) == 3
    assert all(idx >= shared.n_sessions for idx, _q in owed)


def test_corpus_structure_is_the_same_for_every_seed(man):
    cfg = man.cell(CELL)["config"]
    a, b = (corpus_mod.build(cfg, s) for s in (3, 2147484001))
    assert [len(s.subscriptions()) for s in a.live] \
        == [len(s.subscriptions()) for s in b.live]
    assert [s.client_id for s in a.live] != [s.client_id for s in b.live]
    assert sorted(s.client_id for s in a.live) \
        == sorted(s.client_id for s in b.live)


def _program(monkeypatch, **counters):
    monkeypatch.setitem(sys.modules, "vernemq_tpu.models.tpu_matcher",
                        types.SimpleNamespace(**{
                            k: v for k, v in counters.items()
                            if k.startswith("phase")}))
    monkeypatch.setitem(sys.modules, "vernemq_tpu.protocol.fastpath",
                        types.SimpleNamespace(**{
                            k: v for k, v in counters.items()
                            if k.startswith("share")}))


def test_the_new_readers_read_the_programs_counters(monkeypatch):
    _program(monkeypatch, share_picks=3000, share_split_picks=1000,
             phase_dispatches=40, phase_dense_dispatches=40)
    ctx = {"counters": {"match_publishes": 1000.0}}
    for name, want in (("share_split_pct.grp", 100 / 3),
                       ("dense_phase_pct.grp", 100.0)):
        read, args = metric_reader(name)
        assert read(ctx, **args) == pytest.approx(want)
    # the parent, which has neither counter: no reading, no error
    _program(monkeypatch, share_picks=3000, phase_dispatches=40)
    for name in ("share_split_pct.grp", "dense_phase_pct.grp"):
        read, args = metric_reader(name)
        assert read(ctx, **args) is None


@pytest.fixture(scope="module")
def rehearsal():
    """The cell at rehearsal size (20 / 10 / 10 members, 48 publishers)
    through the program on the CPU backend."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.tests.counted_groups",
         "--workload", CELL, "--seed", "2147483778", "--seconds", "2",
         "--trace", "0", "--rehearse"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    facts = [json.loads(line) for line in p.stderr.splitlines()
             if line.startswith("{")]
    return (json.loads(p.stdout.strip().splitlines()[-1]),
            {k: v for f in facts if f.get("phase") == "program_totals"
             for k, v in f.items()})


def test_rehearsal_serves_three_deliveries_a_publish(rehearsal):
    out, totals = rehearsal
    assert KEYS <= set(out) and list(out)[-1] == "compared"
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert out["facts"]["owed"] == out["facts"]["deliveries"]
    shares = {(s["group"], s["filter"]): s
              for s in out["facts"]["member_shares"]}
    assert {k: s["members"] for k, s in shares.items()} == {
        ("ingest", "bench/+"): 20, ("ingest", "bench/#"): 10,
        ("archive", "#"): 10}
    pubs = totals["publishes_received"]
    assert {s["deliveries"] for s in shares.values()} == {pubs}
    # the window's: three a publish stamped in it
    assert out["facts"]["deliveries"] == 3 * out["attempted"]
    # three draws a publish, the second ingest one a split draw, every
    # one on the wire plane; no wide pass
    assert totals["share_picks"] == totals["share_wire_picks"] == 3 * pubs
    assert totals["share_split_picks"] == pubs
    assert totals["share_offline_picks"] == 0
    assert totals["wide_publishes"] == 0
    # the archivers' # row is in region 0: every program held the dense
    # pass
    assert totals["phase_dispatches"] > 0
    assert totals["phase_dense_dispatches"] == totals["phase_dispatches"]
    assert out["compared"]["device_served_pct"]["value"] >= 50.0
