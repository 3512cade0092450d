"""The fan-in cell ``fanin50k.tick1s`` (``mqttbs_fanin_50k`` under
``tick1s``): its files against what the cell is meant to be, its
rehearsal on the CPU (one draw a publish, on the wire plane, no wide
pass), the fault that loses a publish's answer, and the readers it
brought."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import corpus as corpus_mod
from benchmark.manifest import ROOT, Manifest, metric_reader
from benchmark.tests.test_run import KEYS

CELL = "fanin50k.tick1s"
FIXTURE = os.path.join(ROOT, "benchmark", "tests", "fixture")
#: the accepted metrics the cell reports, by base name
BASES = ("ingress_us_per_pub", "admit_us_per_pub", "collector_wait_ms",
         "release_wait_ms", "release_turn_ms", "device_served_pct",
         "pubs_per_dispatch", "dispatch_ms", "fold_prep_ms",
         "fold_launch_ms", "fold_wait_ms", "fold_resolve_ms",
         "match_kernel_ms", "match_roofline", "phases_per_dispatch",
         "device_idle_pct", "host_fallback_pct", "route_us_per_pub",
         "ack_in_us_per_msg", "egress_flush_ms", "egress_joined_pct",
         "frames_per_write", "wire_qos_pct", "wire_inline_pct",
         "governor_raised_pct", "loop_lag_ms_max", "generator_late_ms_p99")
NEW = ("share_wire_pct", "share_stale_pct")
#: the wide pass's: with the group one row it does not run here
WIDE = ("wide_served_pct", "rows_per_pub", "fold_wide_ms", "wide_kernel_ms",
        "wide_roofline")


@pytest.fixture(scope="module")
def man():
    return Manifest()


def test_the_configuration_is_the_suites_case_with_the_rate_alone_cut(man):
    entry = next(c for c in man.doc["configs"]
                 if c["name"] == "mqttbs_fanin_50k")
    cfg = man.cell(CELL)["config"]
    fixture = json.load(open(os.path.join(FIXTURE, "share_group_300.json")))
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert "fanin-50k-500-50k-50k" in cfg["source"]
    assert entry["reduced"] == list(cfg["reduced"]) == ["live_publishers"]
    assert cfg["guarantees"] == fixture["guarantees"]     # word for word
    assert (cfg["topics"], cfg["publishers"], cfg["subscribers"]) \
        == (50000, 50000, 500)
    assert cfg["corpus_builder"] == "share_group" and cfg["group"] == "g"
    assert cfg["qos"] == 1 and cfg["payload_bytes"] == 16
    assert cfg["msgs_per_publisher_per_s"] == 1 and cfg["bystanders"] == 0
    # a publish matches the group's ONE row on the device
    assert cfg["matched_rows_per_publish"] == 1
    assert cfg["known_of_the_source"] and cfg["assumed"]
    # the one cut: the swept rate, a multiple of 100, that reaches the
    # device (a tick of more than the host threshold of 8)
    live = cfg["live_publishers"]
    assert 8 < live < cfg["publishers"] and live % 100 == 0
    mix = man.cell(CELL)["mix"]
    assert mix["name"] == "tick1s" and mix["qos"] == 1
    assert mix["phase_groups"] == 1 and mix["interval_ms"] == 1000
    cell = next(w for w in man.doc["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and f"{live:,}" in cell["why"]
    assert len(cell["why"]) <= 200


def test_the_metrics_declared_for_it_and_no_wide_ones(man):
    names = {m["name"] for m in man.metrics("per_layer", CELL)}
    assert names == {b + ".fin" for b in BASES + NEW}
    assert not names & {b + ".fin" for b in WIDE}
    for m in man.metrics("per_layer", CELL):
        assert m["workloads"] == [CELL] and m["moves"] == "deliver_p50_ms"
        read, _args = metric_reader(m["name"])
        assert callable(read)
    for base in NEW:
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "metrics", base + ".json"))
    layers = {m["layer"] for m in man.doc["per_layer"]}
    assert {m["layer"] for m in man.metrics("per_layer", CELL)} <= layers
    for other in ("p2p50k.tick1s", "fanout1k.burst1s"):
        assert not [m for m in man.metrics("per_layer", other)
                    if m["name"].endswith(".fin")]


@pytest.mark.parametrize("seed", [1, 2147484999])
def test_corpus_is_one_group_of_500_live_members(man, seed):
    cfg = man.cell(CELL)["config"]
    c = corpus_mod.build(cfg, seed)
    assert c.n_stored == 0 and list(c.records()) == []
    assert len(c.live) == 500 and c.publishers == cfg["live_publishers"]
    assert {tuple(s.tcp_filters) for s in c.live} \
        == {(("$share/g/bench/#", 1),)}
    for p in (0, 7, cfg["live_publishers"] - 1):
        assert c.topics(p, 3, 2).tolist() == [[0, p], [0, p]]


def _program(monkeypatch, **fastpath):
    monkeypatch.setitem(sys.modules, "vernemq_tpu.models.tpu_matcher",
                        types.SimpleNamespace())
    monkeypatch.setitem(sys.modules, "vernemq_tpu.protocol.fastpath",
                        types.SimpleNamespace(**fastpath))


def test_the_share_readers_read_the_programs_draws(monkeypatch):
    _program(monkeypatch, share_picks=4000, share_wire_picks=3990,
             share_stale_picks=4)
    ctx = {"counters": {"match_publishes": 3000.0}}
    for name, want in (("share_wire_pct.fin", 99.75),
                       ("share_stale_pct.fin", 0.1)):
        read, args = metric_reader(name)
        assert read(ctx, **args) == pytest.approx(want)
    # the parent of the counters, or a run that drew nothing: no reading
    _program(monkeypatch)
    read, args = metric_reader("share_wire_pct.fin")
    assert read(ctx, **args) is None
    _program(monkeypatch, share_picks=0, share_wire_picks=0,
             share_stale_picks=0)
    assert read(ctx, **args) is None


@pytest.fixture(scope="module")
def rehearsals():
    """The cell at rehearsal size (40 members, 48 publishers) through the
    program on the CPU backend, and the same with every fifth answer of
    the device path lost: both at once."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    procs = {m: subprocess.Popen(
        [sys.executable, "-m", m, "--workload", CELL, "--seed", "2147483777",
         "--seconds", "2", "--trace", "0", "--rehearse"], cwd=ROOT, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for m in ("benchmark.tests.counted", "benchmark.tests.faulty_whole")}
    out = {}
    for m, p in procs.items():
        stdout, stderr = p.communicate(timeout=600)
        assert p.returncode == 0, stderr[-3000:]
        facts = [json.loads(line) for line in stderr.splitlines()
                 if line.startswith("{")]
        out[m] = (json.loads(stdout.strip().splitlines()[-1]),
                  {k: v for f in facts if f.get("phase") == "program_totals"
                   for k, v in f.items()})
    return out


def test_rehearsal_draws_one_member_a_publish_on_the_wire_plane(rehearsals):
    out, totals = rehearsals["benchmark.tests.counted"]
    assert KEYS <= set(out) and list(out)[-1] == "compared"
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert out["facts"]["owed"] == out["facts"]["deliveries"] \
        == out["attempted"]
    share = out["facts"]["member_shares"][0]
    assert share["members"] == 40
    # the group is one row: no publish past the flat form's caps
    assert totals["wide_publishes"] == 0
    # one draw a publish, every publish of the run; on the wire plane
    assert totals["share_picks"] == share["deliveries"] \
        == totals["publishes_received"]
    assert totals["share_wire_picks"] > 0
    assert totals["share_offline_picks"] == 0
    assert out["compared"]["device_served_pct"]["value"] >= 50.0


def test_a_publish_whose_answer_is_lost_reads_lost_qos1(rehearsals):
    out, _totals = rehearsals["benchmark.tests.faulty_whole"]
    assert out["correct"] is False
    assert out["compared"]["lost_qos1"]["value"] > 0
    assert out["failed"] > 0
