"""The program's own spans and named scopes in the trace
(``trace/spans.py``), the reader of the families they are observed into
(``readers/program_span.py``), and the run that lays both over the
harness (``benchmark.traced``)."""

import json
import os
import sys

import pytest

from benchmark.manifest import Manifest, metric_reader
from benchmark.readers import program_span
from benchmark.tests.test_run import rehearse
from benchmark.trace import reduce as T
from benchmark.trace import spans as S

HERE = os.path.dirname(os.path.abspath(T.__file__))
MS = 1_000_000
NEW = {"admit_us_per_pub": ("stage_pub_admit_ms", 1000.0),
       "release_wait_ms": ("stage_release_wait_ms", 1.0),
       "fold_prep_ms": ("stage_fold_prep_ms", 1.0),
       "fold_launch_ms": ("stage_fold_launch_ms", 1.0),
       "fold_wait_ms": ("stage_fold_wait_ms", 1.0),
       "fold_resolve_ms": ("stage_fold_resolve_ms", 1.0),
       "route_us_per_pub": ("stage_route_ms", 1000.0),
       "ack_in_us_per_msg": ("stage_ack_in_ms", 1000.0)}


def _trace():
    """One chip, three bursts of work; a fold around the first two, the
    loop routing and taking acknowledgements in the long gap after."""
    ops = [(0, 10 * MS, "fusion.1"), (60 * MS, 70 * MS, "fusion.1"),
           (400 * MS, 410 * MS, "fusion.1"), (1000 * MS, 1010 * MS, "f.2")]
    mods = [(0, 10 * MS, "jit_match_a(1)")]
    folds = [(0, 100 * MS, "bench_fold_batch")]
    spans = (
        # the fold's own phases, inside the harness's span
        [(0, 5 * MS, "stage_fold_launch_ms"),
         (5 * MS, 72 * MS, "stage_fold_wait_ms"),
         (72 * MS, 100 * MS, "stage_fold_resolve_ms")]
        # 100..400: routing 200 ms of it, acks 60 ms, 40 ms of nothing
        + [(100 * MS + i * 10 * MS, 110 * MS + i * 10 * MS, "stage_route_ms")
           for i in range(20)]
        + [(300 * MS, 360 * MS, "stage_ack_in_ms")]
        # 410..1000: a tenth of it parsing; the rest under no span
        + [(500 * MS, 559 * MS, "stage_wire_parse_ms")])
    return [("/device:TPU:0", ops, mods)], folds, spans


def test_idle_is_attributed_to_the_programs_spans_and_gaps_named_by_them():
    devices, folds, spans = _trace()
    base = T.reduce_events(devices, folds, window_s=1.1)
    r = S.extend(T.reduce_events(devices, folds, window_s=1.1), devices,
                 spans, [("probe_a", 0.02), ("flat_combine", 0.005),
                         ("probe_a", 0.01), ("unscoped", 0.005)])
    assert r["program_span_n"] == {
        "stage_fold_launch_ms": 1, "stage_fold_wait_ms": 1,
        "stage_fold_resolve_ms": 1, "stage_route_ms": 20,
        "stage_ack_in_ms": 1, "stage_wire_parse_ms": 1}
    assert r["program_span_s"]["stage_route_ms"] == pytest.approx(0.2)
    idle = r["idle_by_program_span_s"]
    # gaps: 10..60 (wait), 70..400 (wait 2, resolve 28, route 200, ack
    # 60), 410..1000 (parse 59)
    assert idle["stage_fold_wait_ms"] == pytest.approx(0.052)
    assert idle["stage_fold_resolve_ms"] == pytest.approx(0.028)
    assert idle["stage_route_ms"] == pytest.approx(0.2)
    assert idle["stage_ack_in_ms"] == pytest.approx(0.06)
    assert idle["stage_wire_parse_ms"] == pytest.approx(0.059)
    assert "stage_fold_launch_ms" in idle and not idle["stage_fold_launch_ms"]
    total_idle = 0.05 + 0.33 + 0.59
    assert idle["none"] == pytest.approx(
        total_idle - 0.052 - 0.028 - 0.2 - 0.06 - 0.059)
    # reduce's names: the longest gap (410..1000) and the second
    # (70..400, its middle at 235) have no fold in flight
    assert [k for k, _s in base["breakdown"]["idle_gaps"]] == [
        "no_fold_in_flight", "no_fold_in_flight", "fold:between_ops"]
    # named by the program: spans cover a tenth of the longest (bare)
    # and nearly all of the second, most of it routing
    assert r["breakdown"]["idle_gaps"] == [
        ["no_fold_in_flight", pytest.approx(0.59)],
        ["no_fold_in_flight:stage_route_ms", pytest.approx(0.33)],
        ["fold:between_ops", pytest.approx(0.05)]]
    assert r["device_scope_s"] == {"probe_a": pytest.approx(0.03),
                                   "flat_combine": 0.005, "unscoped": 0.005}
    assert r["breakdown"]["device_scopes"][0] == ["probe_a",
                                                  pytest.approx(0.03)]
    # what reduce gave stays as it was
    for key in base:
        if key != "breakdown":
            assert r[key] == base[key], key
    assert r["breakdown"]["device_ops"] == base["breakdown"]["device_ops"]


def test_a_trace_without_spans_or_scopes_keeps_every_name():
    devices, folds, _spans = _trace()
    base = T.reduce_events(devices, folds)
    r = S.extend(T.reduce_events(devices, folds), devices, [])
    assert r["breakdown"]["idle_gaps"] == base["breakdown"]["idle_gaps"]
    assert r["program_span_s"] == {} and r["device_scope_s"] == {}
    assert r["idle_by_program_span_s"] == {"none": pytest.approx(0.97)}
    assert S.extend(T.reduce_events([], []), [], []) == {"devices": 0}


def test_the_recorded_trace_reduces_as_before_with_empty_tables():
    path = os.path.join(HERE, "sample.xplane.pb")
    want = json.load(open(os.path.join(HERE, "sample.expected.json")))
    base = T.reduce(path, window_s=want["window_s"])
    r = S.reduce(path, window_s=want["window_s"])
    for key in base:
        if key != "breakdown":
            assert r[key] == base[key], key
    assert r["breakdown"]["idle_gaps"] == base["breakdown"]["idle_gaps"]
    assert r["breakdown"]["device_ops"] == base["breakdown"]["device_ops"]
    assert r["program_span_s"] == {}  # recorded before the program's spans
    assert set(r["device_scope_s"]) == {"unscoped"}  # statistics dropped
    assert r["device_scope_s"]["unscoped"] == pytest.approx(
        sum(t for _n, t in _all_ops(path)), rel=1e-6)


def _all_ops(path):
    devices, _folds, _spans, _scoped = S.collect(path)
    return [(n, (e - s) / 1e9) for _d, ops, _m in devices for s, e, n in ops]


def test_scope_of_reads_the_name_stack_from_any_string_statistic():
    assert S.scope_of([("flops", 12), ("tf_op", "jit(f)/jit(main)/"
                                       "probe_a/dot_general")]) == "probe_a"
    assert S.scope_of([("name", "jit(apply_delta_fused)/delta_scatter/"
                        "scatter")]) == "delta_scatter"
    assert S.scope_of([("tf_op", "jit(f)/probe_a_not/dot"),
                       ("bytes", 4)]) == "unscoped"
    assert S.scope_of([]) == "unscoped"


HLO = """HloModule jit_match_extract_windowed_flat_packed, entry_computation_layout={...}

%fused_computation.184 (param_0: s32[8]) -> s32[8] {
  %param_0 = s32[8]{0} parameter(0)
  ROOT %scatter.9 = s32[8]{0} scatter(%param_0), metadata={op_name="jit(f)/jit(main)/flat_combine/scatter" stack_frame_id=5}
}

ENTRY %main.1 (p: s32[8]) -> s32[8] {
  %p = s32[8]{0:T(1024)} parameter(0), metadata={op_name="packed"}
  %fusion.183 = u32[524288]{0:T(1024)S(1)} fusion(%p), kind=kCustom, calls=%fused_computation.183, metadata={op_name="jit(f)/jit(main)/probe_a/gather" stack_frame_id=7}
  %fusion.184 = s32[8]{0} fusion(%p), kind=kCustom, calls=%fused_computation.184, metadata={op_name="jit(f)/jit(main)/flat_combine/scatter"}
  %copy.3 = s32[8]{0} copy(%fusion.184)
  ROOT %slice-start.60 = s32[8]{0} slice(%copy.3), metadata={op_name="jit(f)/jit(main)/unpack_transport/slice"}
}
"""


def test_scope_map_reads_a_compiled_programs_text():
    assert S.scope_map(HLO) == {
        "scatter.9": "flat_combine", "fusion.183": "probe_a",
        "fusion.184": "flat_combine", "slice-start.60": "unpack_transport"}
    assert S.scope_map("") == {}


def test_operations_take_the_scope_of_their_instruction_in_their_program():
    """Two programs number their fusions alike; an operation is read with
    the table that knows most of what its program execution ran."""
    a = {"fusion.1": "probe_a", "fusion.2": "probe_a", "fusion.3":
         "flat_combine"}
    b = {"fusion.1": "delta_scatter", "scatter.7": "delta_scatter"}
    mods = [(0, 100, "jit_match(1)"), (200, 260, "jit_apply_delta(2)")]
    ops = [(0, 40, "%fusion.1 = s32[8]{0} fusion(%p), kind=kCustom"),
           (40, 70, "%fusion.2 = s32[8]{0} fusion(%p)"),
           (70, 95, "%fusion.3 = s32[8]{0} fusion(%p)"),
           (95, 99, "%copy.9 = s32[8]{0} copy(%q)"),
           (200, 230, "%fusion.1 = s32[4]{0} fusion(%p)"),
           (230, 260, "%scatter.7 = s32[4]{0} scatter(%p)"),
           (300, 310, "%fusion.1 = s32[4]{0} fusion(%p)")]  # in no program
    assert S._scopes_by_program(ops, mods, [a, b]) == [
        "probe_a", "probe_a", "flat_combine", "unscoped",
        "delta_scatter", "delta_scatter", "unscoped"]


@pytest.mark.parametrize("name", sorted(NEW))
def test_each_new_metric_reads_the_window_or_the_programs_registry(name):
    from vernemq_tpu.observability import histogram

    family, scale = NEW[name]
    man = Manifest()
    declared = {m["name"]: m for m in man.doc["per_layer"]}[name]
    assert declared["source"] == "program_span"
    assert declared["workloads"] == ["p2p50k.tick1s"]
    read, args = metric_reader(name)
    assert read is program_span.read and args["family"] == family
    # the window's own sums, where the run's counters carry the family
    ctx = {"counters": {family + ".sum": 6.0, family + ".count": 4}}
    assert read(ctx, **args) == pytest.approx(1.5 * scale)
    assert read({"counters": {family + ".sum": 0.0,
                              family + ".count": 0}}, **args) is None
    # else the run's, from the program's registry
    before = histogram.get(family).snapshot()
    histogram.observe(family, 3.0)
    _b, total, count = histogram.get(family).snapshot()
    assert count == before[2] + 1
    assert read({"counters": {}}, **args) == pytest.approx(
        scale * total / count)


def test_a_program_without_the_family_gives_nothing_to_read(monkeypatch):
    ctx = {"counters": {}}
    # the parent of the PR that brought the span: the registry lacks it
    assert program_span.read(ctx, "stage_not_in_this_program_ms") is None
    # a process that never loaded the program
    monkeypatch.delitem(sys.modules, "vernemq_tpu.observability.histogram")
    assert program_span.read(ctx, "stage_route_ms") is None


def test_traced_rehearsal_reports_the_windows_spans_and_the_loops_cpu():
    p = rehearse("p2p50k.tick1s", "benchmark.traced")
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["rehearsal"] is True
    facts = [json.loads(ln) for ln in p.stderr.splitlines()
             if ln.startswith('{"phase"')]
    window = next(f for f in facts if f["phase"] == "window")["counters"]
    assert window["stage_route_ms.count"] == out["attempted"]
    assert window["stage_ack_in_ms.count"] == out["facts"]["deliveries"]
    for fam in ("prep", "launch", "wait", "resolve"):
        assert window[f"stage_fold_{fam}_ms.count"] == \
            window["stage_device_dispatch_ms.count"] > 0
    assert window["loop_cpu_s"] > 0
    assert any(f["phase"] == "program_spans" for f in facts)
