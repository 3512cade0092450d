"""The reduction from trace events to device numbers: on intervals written
out by hand, and on the small trace recorded on the v5e that is kept
beside the reducer."""

import json
import os

import pytest

from benchmark import work
from benchmark.trace import reduce as T

HERE = os.path.dirname(os.path.abspath(T.__file__))
MS = 1_000_000


def test_busy_is_the_union_and_gaps_are_named_by_the_host_span():
    ops = [(0, 10 * MS, "fusion.1"), (5 * MS, 20 * MS, "copy.2"),
           (60 * MS, 70 * MS, "fusion.1"), (75 * MS, 80 * MS, "sort.3"),
           (200 * MS, 210 * MS, "fusion.1")]
    mods = [(0, 20 * MS, "jit_match_a(1)"), (60 * MS, 80 * MS, "jit_match_a(1)"),
            (200 * MS, 210 * MS, "jit_other(2)")]
    folds = [(40 * MS, 100 * MS, "bench_fold_batch")]
    r = T.reduce_events([("/device:TPU:0", ops, mods)], folds, window_s=0.5)
    assert r["busy_s"] == pytest.approx(0.045)
    assert r["window_s"] == 0.5 and r["folds"] == 1
    assert T.module_seconds(r, ["match"]) == (pytest.approx(0.040), 2)
    assert r["breakdown"]["device_ops"][0] == ["fusion.1", pytest.approx(0.030)]
    gaps = dict((k, round(v, 3)) for k, v in r["breakdown"]["idle_gaps"])
    # 80 -> 200 ms: mid 140, no fold in flight; 20 -> 60: inside the fold,
    # before its first operation; 70 -> 75: between two of its operations
    assert gaps == {"no_fold_in_flight": 0.12, "fold:host_prep": 0.04,
                    "fold:between_ops": 0.005}
    assert len(r["breakdown"]["idle_gaps"]) <= 10


def test_no_device_plane_gives_nothing_to_read():
    from benchmark.readers import match_roofline, trace_idle, trace_kernel

    r = T.reduce_events([], [])
    ctx = {"trace": r, "counters": {}, "config": {}, "device": {}}
    assert trace_idle.read(ctx) is None
    assert trace_kernel.read(ctx, ["match"]) is None
    assert match_roofline.read(ctx, ["match"]) is None


def test_peaks_are_keyed_by_device_kind_and_unknown_is_an_error():
    assert work.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("TPU v9")
    # 1M subscriptions x 3 levels x 2 bytes, plus the batch in and rows out
    assert work.match_bytes(1_000_064, 3, 512, 62.6) == pytest.approx(
        6_000_384 + 3_072 + 128_204.8)


def test_recorded_trace_reduces_to_the_numbers_read_by_hand():
    path = os.path.join(HERE, "sample.xplane.pb")
    want = json.load(open(os.path.join(HERE, "sample.expected.json")))
    r = T.reduce(path, window_s=want["window_s"])
    assert r["devices"] == want["devices"]
    # ProfileData rounds starts and durations to whole nanoseconds
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-4)
    secs, n = T.module_seconds(r, ["match"])
    assert n == want["match_modules"]
    assert secs == pytest.approx(want["match_module_s"], rel=1e-4)
    assert r["folds"] == want["folds"]
    assert r["breakdown"]["device_ops"][0][0] == want["top_op"]


def test_every_declared_reader_reads_a_traced_runs_context():
    """What a --trace 1 run on the chip hands the readers, made by hand:
    each metric ``BENCHMARK.json`` declares comes out as a number."""
    import numpy as np

    from benchmark.manifest import Manifest, metric_reader

    man = Manifest()
    cell = man.doc["workloads"][0]["name"]
    ops = [(0, 10 * MS, "fusion.1"), (60 * MS, 70 * MS, "fusion.1")]
    mods = [(0, 10 * MS, "jit_match_a(1)"), (60 * MS, 70 * MS, "jit_match_a(1)")]
    trace = T.reduce_events([("/device:TPU:0", ops, mods)],
                            [(0, 80 * MS, "bench_fold_batch")], window_s=0.1)
    counters = {k: 10.0 for k in (
        "match_publishes", "match_batches", "super_dispatches",
        "host_hybrid_pubs", "busy_host_pubs", "degraded_host_pubs",
        "stalled_host_pubs", "expired_host_pubs", "rebuild_host_pubs",
        "overload_host_pubs", "phase_runs", "phase_dispatches")}
    for fam in ("wire_parse", "collector_wait", "device_dispatch",
                "queue_flush", "wire_encode"):
        counters[f"stage_{fam}_ms.sum"] = 5.0
        counters[f"stage_{fam}_ms.count"] = 2
    ctx = {"trace": trace, "counters": counters, "publishes": 100,
           "deliveries": 100, "resident": 50_000, "levels": 2,
           "config": man.cell(cell)["config"],
           "device": {"kind": "TPU v5 lite"},
           "probes": {"loop_lag_max_s": 0.01, "raised": 1, "samples": 10},
           "generator_late_ms": np.ones(4, np.float32)}
    for m in man.metrics("per_layer", cell):
        read, args = metric_reader(m["name"])
        value = read(ctx, **args)
        assert isinstance(value, float) and value >= 0, m["name"]
        if m["name"].endswith("_roofline"):
            assert 0 < value < 100
