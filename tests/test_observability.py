"""Observability subsystem: stage histograms, flight recorder, dispatch
profiler, trace-event export, and the worker-mode fold-envelope path.

The histogram registry and profiler are process-global (like the fault
registry), so every test resets them first — counts asserted here are
counts THIS test produced.
"""

import asyncio
import json
import os
import re
import threading
import time

import pytest

from vernemq_tpu.observability import chrome_trace, events, \
    histogram as hist
from vernemq_tpu.observability.profiler import profiler
from vernemq_tpu.observability.recorder import ClockSync, FlightRecorder, \
    PublishTrace


@pytest.fixture(autouse=True)
def _clean_registry():
    hist.set_enabled(True)
    hist.reset_all()
    profiler().reset()
    events.journal().reset()
    yield
    hist.set_enabled(True)


def _poll(cond, timeout=5.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return cond()


# ---------------------------------------------------------------- histogram


def test_histogram_sum_count_consistent_with_observations():
    h = hist.get("stage_device_dispatch_ms")
    vals = [0.05, 1.2, 1.3, 40.0, 9000.0]
    for v in vals:
        h.observe(v)
    counts, s, n = h.snapshot()
    assert n == len(vals)
    assert s == pytest.approx(sum(vals))
    assert sum(counts) == len(vals)
    # each observation landed in the first bucket whose bound >= value
    for v in vals:
        i = hist.bucket_index(v)
        assert counts[i] >= 1
        assert v <= hist.BUCKET_BOUNDS_MS[i]
        if i:
            assert v > hist.BUCKET_BOUNDS_MS[i - 1]


def test_histogram_cross_thread_buffers_visible_without_flush():
    """The counter-block pattern: a writer thread's buffered (not yet
    folded) observations are visible to a reader immediately, and a
    dead thread's residuals fold exactly once."""
    h = hist.get("stage_queue_flush_ms")
    t = threading.Thread(target=lambda: [h.observe(2.0)
                                         for _ in range(10)])
    t.start()
    t.join()
    counts, s, n = h.snapshot()
    assert n == 10 and s == pytest.approx(20.0)
    # second read after the dead-thread sweep: no double count
    counts2, s2, n2 = h.snapshot()
    assert (n2, s2) == (10, pytest.approx(20.0))
    assert sum(counts2) == 10


def test_histogram_disabled_is_a_noop():
    hist.set_enabled(False)
    hist.observe("stage_device_dispatch_ms", 5.0)
    hist.set_enabled(True)
    assert hist.get("stage_device_dispatch_ms").snapshot()[2] == 0


def test_quantile_interpolation_and_overflow_clamp():
    counts = [0] * (hist.N_BUCKETS + 1)
    # 100 observations in the bucket (2.048, 4.096]
    i = hist.bucket_index(3.0)
    counts[i] = 100
    q50 = hist.quantile(counts, 0.5)
    assert hist.BUCKET_BOUNDS_MS[i - 1] < q50 <= hist.BUCKET_BOUNDS_MS[i]
    # overflow bucket clamps to the top bound
    counts = [0] * (hist.N_BUCKETS + 1)
    counts[hist.N_BUCKETS] = 10
    assert hist.quantile(counts, 0.99) == hist.BUCKET_BOUNDS_MS[-1]
    assert hist.quantile([0] * (hist.N_BUCKETS + 1), 0.5) is None


def test_pack_unpack_merge_roundtrip():
    hist.observe("stage_device_dispatch_ms", 1.0)
    hist.observe("stage_ring_rtt_ms", 2.0)
    flat = hist.pack_all()
    assert len(flat) == len(hist.STAGE_FAMILIES) * hist.FLAT_WIDTH
    snap = hist.unpack_flat(flat)
    assert snap["stage_device_dispatch_ms"][2] == 1
    assert snap["stage_ring_rtt_ms"][1] == pytest.approx(2.0)
    merged = hist.merge(snap["stage_ring_rtt_ms"],
                        snap["stage_ring_rtt_ms"])
    assert merged[2] == 2 and merged[1] == pytest.approx(4.0)
    # short/empty blocks (a worker that never heartbeated) are tolerated
    assert hist.unpack_flat([]) == {}


# ----------------------------------------------------------- recorder unit


def test_recorder_sampling_is_deterministic_one_in_n():
    rec = FlightRecorder(sample_n=4, capacity=64)
    traces = [rec.admit("c", "t", 0) for _ in range(16)]
    got = [t for t in traces if t is not None]
    assert len(got) == 4
    # exactly every 4th admission samples
    assert [i for i, t in enumerate(traces) if t is not None] == \
        [3, 7, 11, 15]
    # observability off: no sampling at all
    hist.set_enabled(False)
    assert FlightRecorder(sample_n=1).admit("c", "t", 0) is None
    hist.set_enabled(True)
    assert FlightRecorder(sample_n=0).admit("c", "t", 0) is None


def test_recorder_stage_deltas_match_injected_sleeps():
    rec = FlightRecorder(sample_n=1)
    tr = rec.admit("cid", "a/b", 1)
    time.sleep(0.03)
    tr.stamp("admit")
    time.sleep(0.05)
    tr.stamp("route")
    out = rec.finish(tr)
    st = out["stages"]
    assert st["admission_ms"] == pytest.approx(30.0, abs=20.0)
    assert st["route_ms"] == pytest.approx(50.0, abs=20.0)
    assert out["total_ms"] >= 70.0
    assert out["client"] == "cid" and out["qos"] == 1
    # the sampled total feeds the parse->route histogram
    assert hist.get("stage_parse_route_ms").snapshot()[2] == 1
    assert len(rec.records) == 1


def test_recorder_service_meta_splits_ring_round_trip():
    rec = FlightRecorder(sample_n=1)
    tr = rec.admit("c", "t", 0)
    t = tr.t0
    tr.stamp("submit")
    tr.marks[-1] = ("submit", t + 0.001)
    tr.stamp("match")
    tr.marks[-1] = ("match", t + 0.011)
    tr.meta = {"send_t": t + 0.001, "svc_recv": t + 0.003,
               "svc_done": t + 0.009, "recv_t": t + 0.010,
               "svc_pid": 777}
    out = rec.finish(tr)
    st = out["stages"]
    assert st["ring_request_ms"] == pytest.approx(2.0, abs=0.01)
    assert st["service_ms"] == pytest.approx(6.0, abs=0.01)
    assert st["ring_reply_ms"] == pytest.approx(1.0, abs=0.01)
    assert out["svc_pid"] == 777
    assert out["svc_span"] == (t + 0.003, t + 0.009)


# ------------------------------------------------------------- trace export


def test_chrome_trace_json_well_formed():
    rec = FlightRecorder(sample_n=1)
    tr = rec.admit("c1", "x/y", 1)
    tr.stamp("admit")
    tr.stamp("route")
    tr.meta = {"send_t": tr.t0, "svc_recv": tr.t0 + 0.001,
               "svc_done": tr.t0 + 0.002, "recv_t": tr.t0 + 0.003,
               "svc_pid": os.getpid() + 1}
    rec.finish(tr)
    profiler().record("match", time.monotonic(), 3.5, k=2, batch=64,
                      bpad=64, compiled=True)
    trace = chrome_trace(rec.snapshot(), profiler().snapshot(),
                         node="n1")
    blob = json.dumps(trace)  # must be JSON-serializable as-is
    parsed = json.loads(blob)
    events = parsed["traceEvents"]
    assert events, "no events emitted"
    x_events = [e for e in events if e["ph"] == "X"]
    for e in x_events:
        assert {"name", "ts", "dur", "pid", "tid"} <= set(e)
        assert e["dur"] > 0
    # spans land in SEPARATE pid tracks: worker + service
    pids = {e["pid"] for e in x_events}
    assert len(pids) >= 2, "worker and service spans share one pid"
    svc = [e for e in x_events if e["name"] == "service_fold"]
    assert svc and svc[0]["pid"] == os.getpid() + 1
    dev = [e for e in x_events if e["name"] == "device.match"]
    assert dev and dev[0]["args"]["k"] == 2


# --------------------------------------------------------------- profiler


def test_profiler_records_and_summary():
    p = profiler()
    t = time.monotonic()
    p.record("match", t, 5.0, k=1, batch=32, bpad=32, compiled=True)
    p.record("match", t, 1.0, k=8, batch=256, bpad=512, compiled=False)
    p.record("delta", t, 0.5, dpad=16)
    assert len(p.snapshot("match")) == 2
    assert p.snapshot("delta")[0]["dpad"] == 16
    s = p.summary()
    assert s["match"]["count"] == 2 and s["match"]["compiles"] == 1
    assert s["match"]["max_ms"] == 5.0
    assert "ring_p50_ms" in s["match"]
    # disabled: nothing records
    hist.set_enabled(False)
    p.record("match", t, 9.0)
    hist.set_enabled(True)
    assert len(p.snapshot("match")) == 2


# -------------------------------------------------------- broker e2e (tpu)


@pytest.mark.asyncio
async def test_broker_e2e_sampled_publishes_record_collector_stages():
    """Single-process tpu-view broker: sampled publishes yield one
    record each with collector/dispatch stage deltas, the device seams
    feed the stage histograms, and `vmq-admin timeline|profile` render
    them."""
    from vernemq_tpu.admin.commands import CommandRegistry, \
        register_core_commands
    from vernemq_tpu.broker.config import Config
    from vernemq_tpu.broker.server import start_broker
    from vernemq_tpu.client import MQTTClient

    cfg = Config(systree_enabled=False, allow_anonymous=True,
                 default_reg_view="tpu", flight_recorder_sample_n=2,
                 tpu_host_batch_threshold=0)
    broker, server = await start_broker(cfg, port=0)
    try:
        c = MQTTClient("127.0.0.1", server.port, client_id="obs-e2e")
        assert (await c.connect()).rc == 0
        await c.subscribe("a/b")
        # publish in waves until a sampled record rides a real device
        # dispatch: the first flushes shed to the trie while the cold
        # batch shape background-compiles (ensure_warm), and those shed
        # records legitimately carry no match stage
        n_pub = 0
        deadline = time.monotonic() + 30.0
        full = []
        while not full and time.monotonic() < deadline:
            for _ in range(10):
                await c.publish("a/b", b"p", qos=1)
            n_pub += 10
            await asyncio.sleep(0.1)
            full = [r for r in broker.recorder.snapshot()
                    if "match_ms" in r["stages"]]
        assert full, "no record captured the device dispatch stage"
        assert _poll(lambda: broker.recorder.finished
                     == broker.recorder.sampled)
        assert broker.recorder.sampled == n_pub // 2
        recs = broker.recorder.snapshot()
        assert len(recs) == n_pub // 2  # ONE record per sampled publish
        assert "collector_wait_ms" in full[-1]["stages"]
        # device dispatches observed + profiled
        assert hist.get("stage_device_dispatch_ms").snapshot()[2] > 0
        assert hist.get("stage_collector_wait_ms").snapshot()[2] > 0
        assert any(r["kind"] == "match" for r in profiler().snapshot())
        # admin surface renders
        reg = register_core_commands(CommandRegistry())
        out = reg.run(broker, ["timeline", "show", "n=5"])
        assert out["recorder"]["flight_sampled"] == n_pub // 2
        assert out["table"][0]["total_ms"] >= 0
        prof = reg.run(broker, ["profile", "device"])
        assert "match" in prof["summary"]
        await c.disconnect()
    finally:
        await broker.stop()
        await server.stop()


@pytest.mark.asyncio
async def test_timeline_dump_writes_valid_chrome_trace(tmp_path):
    from vernemq_tpu.admin.commands import CommandRegistry, \
        register_core_commands
    from vernemq_tpu.broker.config import Config
    from vernemq_tpu.broker.server import start_broker
    from vernemq_tpu.client import MQTTClient

    cfg = Config(systree_enabled=False, allow_anonymous=True,
                 flight_recorder_sample_n=1)
    broker, server = await start_broker(cfg, port=0)
    try:
        c = MQTTClient("127.0.0.1", server.port, client_id="dmp")
        assert (await c.connect()).rc == 0
        for _ in range(5):
            await c.publish("q/r", b"x", qos=1)
        assert _poll(lambda: broker.recorder.finished >= 5)
        reg = register_core_commands(CommandRegistry())
        path = str(tmp_path / "tl.json")
        out = reg.run(broker, ["timeline", "dump", f"path={path}"])
        assert out["writing"] == path and out["events"] > 0
        # the file write runs off-loop (a slow disk must not stall
        # session IO); the tmp->rename publish makes it atomic
        assert _poll(lambda: os.path.exists(path))
        with open(path) as fh:
            trace = json.load(fh)
        assert isinstance(trace["traceEvents"], list)
        assert all("ph" in e and "pid" in e
                   for e in trace["traceEvents"])
        await c.disconnect()
    finally:
        await broker.stop()
        await server.stop()


# ------------------------------------------------- worker-mode fold envelope


@pytest.mark.asyncio
async def test_worker_mode_one_record_per_sampled_publish_with_ring_meta():
    """Worker-mode e2e over REAL shared-memory rings (service core
    drained by a thread, as in test_match_service): every sampled
    publish yields exactly ONE record whose stages include the
    cross-process ring split (request transit / service residency /
    reply transit) carried back in the fold envelope."""
    from vernemq_tpu.broker.config import Config
    from vernemq_tpu.broker.match_service import MatchService
    from vernemq_tpu.broker.server import start_broker
    from vernemq_tpu.client import MQTTClient
    from vernemq_tpu.parallel.shm_ring import ShmRing, WorkerStatsBlock

    tag = f"obs{os.getpid() % 100000}"
    stats = WorkerStatsBlock.create(tag + "s", 1)
    req = ShmRing.create(tag + "q", 1 << 16)
    resp = ShmRing.create(tag + "r", 1 << 16)
    svc = MatchService(stats, [(ShmRing.attach(req.name),
                                ShmRing.attach(resp.name))])
    stats.set_service(1, os.getpid())
    stop = threading.Event()

    def drain():
        while not stop.is_set():
            if not svc.poll_once():
                time.sleep(0.0005)

    th = threading.Thread(target=drain, daemon=True)
    th.start()
    broker = server = None
    try:
        cfg = Config(systree_enabled=False, allow_anonymous=True,
                     default_reg_view="tpu", flight_recorder_sample_n=2,
                     tpu_host_batch_threshold=0,
                     worker_stats_block=stats.name, worker_index=0,
                     workers_total=1,
                     match_service_req_ring=req.name,
                     match_service_resp_ring=resp.name)
        broker, server = await start_broker(cfg, port=0,
                                            node_name="w0")
        client = broker.match_client
        assert client is not None
        # wait out the first-boot resync so folds ride the rings
        # instead of the ordering-fence local-trie path
        assert _poll(lambda: not client._need_resync
                     and client._resync_rows is None)
        c = MQTTClient("127.0.0.1", server.port, client_id="wm")
        assert (await c.connect()).rc == 0
        await c.subscribe("w/t")
        n_pub = 20
        for _ in range(n_pub):
            await c.publish("w/t", b"z", qos=1)
        assert _poll(lambda: broker.recorder.finished >= n_pub // 2)
        recs = broker.recorder.snapshot()
        assert len(recs) == n_pub // 2  # ONE record per sampled publish
        ringed = [r for r in recs if "ring_request_ms" in r["stages"]]
        assert ringed, "no record carried the fold-envelope ring split"
        st = ringed[-1]["stages"]
        assert st["service_ms"] >= 0 and st["ring_reply_ms"] >= 0
        assert ringed[-1]["svc_pid"] == os.getpid()
        assert ringed[-1]["svc_span"][1] >= ringed[-1]["svc_span"][0]
        # the ring RTT seam observed on the worker side
        assert hist.get("stage_ring_rtt_ms").snapshot()[2] > 0
        # the dump spans both "processes" (worker pid + service pid
        # tracks — same OS pid here, distinct metadata tracks in a
        # real deployment where the service is its own process)
        trace = chrome_trace(recs, profiler().snapshot(), node="w0")
        assert any(e["name"] == "service_fold"
                   for e in trace["traceEvents"])
        await c.disconnect()
    finally:
        stop.set()
        th.join(2.0)
        if broker is not None:
            await broker.stop()
        if server is not None:
            await server.stop()
        svc.close()
        for h in (req, resp):
            h.close()
            h.unlink()
        stats.close()
        stats.unlink()


# -------------------------------------------------------- tracer satellite


@pytest.mark.asyncio
async def test_tracer_rate_limit_counts_and_marks_suppressed_frames():
    """Satellite: the tracer's rate limiter counts what it drops
    (trace_rate_limited) and prints the '... N frames suppressed'
    marker when the window reopens — a traced storm reads as visibly
    truncated."""
    from vernemq_tpu.broker.config import Config
    from vernemq_tpu.broker.server import start_broker
    from vernemq_tpu.client import MQTTClient

    cfg = Config(systree_enabled=False, allow_anonymous=True)
    broker, server = await start_broker(cfg, port=0)
    try:
        tracer = broker.start_trace("storm", max_rate=(2, 0.2))
        c = MQTTClient("127.0.0.1", server.port, client_id="storm")
        assert (await c.connect()).rc == 0
        for _ in range(10):
            await c.publish("s/t", b"x", qos=1)
        assert tracer.suppressed_frames > 0
        assert broker.metrics.value("trace_rate_limited") == \
            tracer.suppressed_frames
        before = tracer.suppressed_frames
        await asyncio.sleep(0.25)  # window rolls over
        await c.publish("s/t", b"x", qos=1)  # reopens the window
        await asyncio.sleep(0.05)
        lines = tracer.drain()
        assert any(re.match(r"\.\.\. \d+ frames suppressed", ln)
                   for ln in lines), lines
        marker = next(ln for ln in lines
                      if ln.endswith("frames suppressed"))
        assert int(marker.split()[1]) == before
        assert tracer.info()["suppressed_frames"] >= before
        await c.disconnect()
    finally:
        await broker.stop()
        await server.stop()


# --------------------------------------------------- graphite percentiles


@pytest.mark.asyncio
async def test_graphite_lines_include_histogram_percentiles():
    """Satellite: the graphite reporter derives <family>.p50/p99/p999
    lines from the bucket snapshot — same data the Prometheus _bucket
    surface carries."""
    from vernemq_tpu.broker.config import Config
    from vernemq_tpu.broker.server import start_broker

    received = []
    done = asyncio.Event()

    async def sink(reader, writer):
        while not done.is_set():
            data = await reader.read(1 << 16)
            if not data:
                break
            received.append(data)
            if b".p999 " in b"".join(received):
                done.set()
        writer.close()

    gserver = await asyncio.start_server(sink, "127.0.0.1", 0)
    gport = gserver.sockets[0].getsockname()[1]
    cfg = Config(systree_enabled=False, allow_anonymous=True,
                 graphite_enabled=True, graphite_host="127.0.0.1",
                 graphite_port=gport, graphite_interval=0.1)
    broker, server = await start_broker(cfg, port=0)
    try:
        for v in (1.0, 2.0, 3.0, 50.0):
            broker.metrics.observe("stage_queue_flush_ms", v)
        await asyncio.wait_for(done.wait(), 10.0)
        text = b"".join(received).decode()
        assert re.search(
            r"vmq\.node1\.stage_queue_flush_ms\.p50 [\d.]+ \d+", text)
        assert ".stage_queue_flush_ms.p99 " in text
        assert ".stage_queue_flush_ms.p999 " in text
    finally:
        await broker.stop()
        await server.stop()
        gserver.close()
        await gserver.wait_closed()


# ------------------------------------------------------------ event journal


def test_event_journal_emit_snapshot_filters_and_bound():
    j = events.journal()
    j.emit("breaker_open", detail="match", value=3.0)
    j.emit("breaker_close", detail="match")
    j.emit("overload_level_enter", detail="throttle", value=1.0)
    evs = j.snapshot()
    assert [e["code"] for e in evs] == [
        "breaker_open", "breaker_close", "overload_level_enter"]
    assert evs[0]["detail"] == "match" and evs[0]["value"] == 3.0
    assert evs[0]["pid"] == os.getpid()
    # code filter + since cursor (the tail-follow contract)
    assert len(j.snapshot(code="breaker_open")) == 1
    cursor = evs[1]["t"]
    tail = j.snapshot(since=cursor)
    assert [e["code"] for e in tail] == ["overload_level_enter"]
    # per-code counters + totals
    st = j.stats()
    assert st["event_breaker_open"] == 1.0
    assert st["events_emitted"] == 3.0 and st["events_dropped"] == 0.0
    # unregistered codes raise — the registry contract the vmqlint
    # events-registry pass enforces statically
    with pytest.raises(KeyError):
        j.emit("not_a_registered_code")
    # the ring is bounded: evictions are counted, oldest drop first
    j.reset()
    j.set_capacity(64)
    for i in range(70):
        j.emit("watchdog_stall", value=float(i))
    assert len(j.snapshot()) == 64
    assert j.dropped == 6
    assert j.snapshot()[0]["value"] == 6.0
    j.set_capacity(2048)


def test_events_show_tail_follow_catches_up_oldest_first():
    """A since= follow past a bursty window must return the OLDEST n
    beyond the cursor (catch-up), not the newest n (which would jump
    the cursor over the burst and silently lose it); a plain show
    keeps newest-n semantics."""
    from vernemq_tpu.admin.commands import _events_show

    for i in range(8):
        events.emit("watchdog_stall", value=float(i))
    plain = _events_show(None, {"n": 3})
    assert [r["value"] for r in plain["table"]] == [5.0, 6.0, 7.0]
    cur = 0.0
    seen = []
    for _ in range(4):
        out = _events_show(None, {"n": 3, "since": cur})
        rows = [r for r in out["table"] if r["code"] != "(no events)"]
        if not rows:
            break
        seen.extend(r["value"] for r in rows)
        cur = out["cursor"]
    assert seen == [float(i) for i in range(8)]  # nothing skipped


def test_event_emit_disabled_is_noop_and_gated():
    hist.set_enabled(False)
    events.emit("breaker_open", detail="x")
    hist.set_enabled(True)
    assert events.journal().snapshot() == []
    events.emit("breaker_open", detail="x")
    assert len(events.journal().snapshot()) == 1


def test_event_pack_unpack_roundtrip_and_torn_entry():
    j = events.journal()
    j.emit("spool_replay_start", detail="node1", value=13.0)
    j.emit("spool_replay_end", detail="node1", value=13.0)
    flat = j.pack()
    assert len(flat) == events.PACK_WIDTH
    out = events.unpack(flat, pid=777)
    assert [e["code"] for e in out] == ["spool_replay_start",
                                       "spool_replay_end"]
    assert out[0]["value"] == 13.0 and out[0]["pid"] == 777
    # detail strings do not cross the shm boundary (by design)
    assert out[0]["detail"] == ""
    # a torn entry (garbage code index) is skipped, not crashed on
    flat[3] = 9999.0
    out = events.unpack(flat)
    assert [e["code"] for e in out] == ["spool_replay_end"]
    assert events.unpack([]) == []


def test_state_machines_emit_registered_events():
    """The live emitters: a breaker open/half-open/close cycle and a
    watchdog stall/abandon/late-discard cycle land in the journal with
    their registered codes."""
    from vernemq_tpu.robustness.breaker import CircuitBreaker
    from vernemq_tpu.robustness.watchdog import StallAbandoned, \
        StallWatchdog

    b = CircuitBreaker(failure_threshold=2, backoff_initial=0.01,
                       name="match")
    b.record_failure()
    b.record_failure()  # opens
    time.sleep(0.05)
    assert b.allow()    # grants the half-open probe
    b.record_success()  # closes
    codes = [e["code"] for e in events.journal().snapshot()]
    assert codes == ["breaker_open", "breaker_half_open", "breaker_close"]
    assert all(e["detail"] == "match"
               for e in events.journal().snapshot())

    events.journal().reset()
    wd = StallWatchdog(tick_s=0.01)
    release = threading.Event()
    with pytest.raises(StallAbandoned):
        wd.dispatch("device.dispatch", release.wait, deadline_s=0.05)
    release.set()
    assert _poll(lambda: events.journal().counts.get(
        "watchdog_late_discard", 0) >= 1)
    counts = events.journal().counts
    assert counts.get("watchdog_abandon", 0) >= 1
    assert counts.get("watchdog_stall", 0) >= 1


def test_chrome_trace_interleaves_instant_events():
    rec = FlightRecorder(sample_n=1)
    tr = rec.admit("c", "t", 0)
    tr.stamp("admit")
    tr.stamp("route")
    rec.finish(tr)
    events.emit("breaker_open", detail="match")
    trace = chrome_trace(rec.snapshot(), node="n1",
                         journal_events=events.journal().snapshot())
    json.dumps(trace)
    inst = [e for e in trace["traceEvents"] if e["ph"] == "i"]
    assert len(inst) == 1
    assert inst[0]["name"] == "breaker_open"
    assert inst[0]["cat"] == "events"
    assert inst[0]["args"]["detail"] == "match"
    # the instant lands on the emitting process's track
    spans = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert inst[0]["pid"] == spans[0]["pid"]


# ------------------------------------------------- cross-node trace resume


def test_clock_sync_offset_estimation():
    cs = ClockSync()
    assert cs.offset("peer") == 0.0
    # remote clock 10s behind local, 20ms RTT: delta samples land at
    # +10.01 (offset + one-way), rtt halves out the transit
    for _ in range(20):
        cs.observe_delta("peer", 100.0, 110.01)
        cs.observe_rtt("peer", 20.0)
    assert cs.offset("peer") == pytest.approx(10.0, abs=0.005)
    assert cs.peers()["peer"]["rtt_ms"] == pytest.approx(20.0, rel=0.01)
    # REPLAY immunity (the windowed-min filter): a spool-replayed
    # traced frame carries its original export-time send stamp, so its
    # delta is inflated by the whole outage — it must not move the
    # offset the way a mean/EWMA would
    cs.observe_delta("peer", 100.0, 170.01)  # +60s replay delay
    assert cs.offset("peer") == pytest.approx(10.0, abs=0.005)


def test_resume_carries_origin_and_transit_stage():
    a = FlightRecorder(sample_n=1, node="nodeA")
    tr = a.admit("pub-1", "x/y", 1)
    tr.stamp("admit")
    ctx = tr.export_wire("nodeA")
    assert ctx["n"] == "nodeA" and ctx["c"] == "pub-1"
    b = FlightRecorder(sample_n=1, node="nodeB")
    tr2 = b.resume(ctx, "nodeA")
    assert b.resumed == 1
    tr2.stamp("route")
    rec = b.finish(tr2)
    assert rec["node"] == "nodeB"
    assert rec["origin"]["node"] == "nodeA"
    assert rec["origin"]["marks"] == [("admit", pytest.approx(
        tr.marks[0][1]))]
    assert "cluster_transit_ms" in rec["stages"]
    assert "cluster_ingress_ms" in rec["stages"]
    # a malformed peer context resumes to None, never a crash — a
    # resume failure on the spooled path would otherwise abort the
    # dispatch AFTER the seq was accepted (QoS1 loss)
    assert b.resume({"t0": "garbage", "q": "x"}, "nodeA") is None
    assert b.resume(["not", "a", "dict"], "nodeA") is None
    assert b.resume({"m": [("x",)]}, "nodeA") is None  # torn marks
    # observability off: no resume at all
    hist.set_enabled(False)
    assert b.resume(ctx, "nodeA") is None
    hist.set_enabled(True)


def test_chrome_trace_renders_origin_node_track_and_flow():
    a = FlightRecorder(sample_n=1, node="nodeA")
    tr = a.admit("c", "t", 1)
    tr.stamp("admit")
    ctx = tr.export_wire("nodeA")
    b = FlightRecorder(sample_n=1, node="nodeB")
    tr2 = b.resume(ctx, "nodeA")
    tr2.stamp("route")
    b.finish(tr2)
    trace = chrome_trace(b.snapshot(), node="nodeB")
    json.dumps(trace)
    names = {e["args"]["name"]: e["pid"]
             for e in trace["traceEvents"] if e["ph"] == "M"}
    node_tracks = [n for n in names if n.startswith(("nodeA-worker",
                                                     "nodeB-worker"))]
    assert len(node_tracks) == 2, names
    # origin spans landed on the origin node's (synthesized-pid) track
    a_pid = next(p for n, p in names.items()
                 if n.startswith("nodeA-worker"))
    b_pid = next(p for n, p in names.items()
                 if n.startswith("nodeB-worker"))
    assert a_pid != b_pid
    origin_spans = [e for e in trace["traceEvents"]
                    if e["ph"] == "X" and e["pid"] == a_pid]
    assert any(e["name"] == "admission" for e in origin_spans)
    # the cluster hop renders as a flow arrow between the two tracks
    flows = {e["ph"]: e for e in trace["traceEvents"]
             if e.get("name") == "cluster_hop"}
    assert flows["s"]["pid"] == a_pid and flows["f"]["pid"] == b_pid


# ---------------------------------------------------------- canary probe


@pytest.mark.asyncio
async def test_canary_probe_e2e_histogram_slo_and_isolation():
    """The canary SLO probe: loopback probes ride the full publish path
    into the e2e_canary_ms histogram, SLO breaches burn the counter and
    journal an event, the admin/QL surfaces render, and the $-topic
    keeps the probe invisible to wildcard subscribers."""
    from vernemq_tpu.admin.commands import CommandRegistry, \
        register_core_commands
    from vernemq_tpu.broker.config import Config
    from vernemq_tpu.broker.server import start_broker
    from vernemq_tpu.client import MQTTClient

    cfg = Config(systree_enabled=False, allow_anonymous=True,
                 canary_enabled=True, canary_interval_ms=40,
                 canary_slo_ms=10_000.0, flight_recorder_sample_n=0)
    broker, server = await start_broker(cfg, port=0)
    try:
        assert broker.canary is not None
        deadline = time.monotonic() + 15
        while broker.canary.received < 3 and time.monotonic() < deadline:
            await asyncio.sleep(0.05)
        assert broker.canary.received >= 3
        assert broker.canary.timeouts == 0
        assert hist.get("e2e_canary_ms").snapshot()[2] >= 3
        am = broker.metrics.all_metrics()
        assert am["canary_probes"] >= 3
        assert am["canary_received"] >= 3
        assert am["canary_slo_breaches"] == 0
        assert am["canary_last_e2e_ms"] >= 0
        # HELP present for the canary gauges and event counters
        text = broker.metrics.prometheus_text(node=broker.node_name)
        assert "# HELP canary_slo_breaches " in text
        assert "# HELP event_canary_slo_breach " in text
        assert "# HELP events_emitted " in text
        # an impossible SLO burns the counter and journals the breach
        broker.canary.slo_ms = 0.0
        deadline = time.monotonic() + 15
        while (broker.canary.slo_breaches < 1
               and time.monotonic() < deadline):
            await asyncio.sleep(0.05)
        assert broker.canary.slo_breaches >= 1
        assert events.journal().counts.get("canary_slo_breach", 0) >= 1
        # admin + QL surfaces
        reg = register_core_commands(CommandRegistry())
        out = reg.run(broker, ["events", "show", "code=canary_slo_breach"])
        assert out["table"][0]["code"] == "canary_slo_breach"
        assert out["journal"]["events_emitted"] >= 1
        ql = reg.run(broker, ["ql", "query",
                              "q=SELECT code, subsystem FROM events "
                              "WHERE code = 'canary_slo_breach' LIMIT 1"])
        assert ql["table"][0]["subsystem"] == "observability/canary"
        # the tail-follow cursor: a since= past the last event is empty
        cur = out["cursor"]
        again = reg.run(broker, ["events", "show", f"since={cur + 1000}"])
        assert again["table"][0]["code"] == "(no events)"
        # $-topic isolation: a # wildcard subscriber never sees probes
        c = MQTTClient("127.0.0.1", server.port, client_id="canary-spy")
        assert (await c.connect()).rc == 0
        await c.subscribe("#")
        with pytest.raises(asyncio.TimeoutError):
            await c.recv(0.5)
        await c.disconnect()
    finally:
        await broker.stop()
        await server.stop()


@pytest.mark.asyncio
async def test_canary_not_ready_rolls_back_and_never_counts_timeout():
    """A netsplit CAP gate tick must not inject a probe NOR leave a
    phantom inflight entry that the sweep later burns as a
    path-dropped timeout."""
    from vernemq_tpu.observability.canary import CanaryProbe

    class _Reg:
        def batched_view_active(self):
            return False

        def publish(self, msg):
            raise RuntimeError("not_ready")

    class _Broker:
        node_name = "n0"
        registry = _Reg()

    probe = CanaryProbe(_Broker(), interval_ms=10)
    await probe._probe_once()
    assert probe.probes == 0 and probe._inflight == {}
    probe._sweep_timeouts()
    assert probe.timeouts == 0


# ------------------------------------------- cross-node cluster trace e2e


@pytest.mark.asyncio
async def test_cross_node_trace_two_brokers_one_perfetto_trace(tmp_path):
    """The tentpole acceptance: a sampled publish crossing two
    in-process brokers over the cluster plane produces ONE
    Perfetto-loadable trace with both nodes' tracks, stage spans, and
    interleaved instant events — under an injected device.dispatch
    fault whose breaker transitions land in the same timeline."""
    from test_cluster import connected, start_node, stop_cluster, \
        wait_until
    from vernemq_tpu.robustness import faults

    a = await start_node(
        "node0", default_reg_view="tpu", tpu_host_batch_threshold=0,
        flight_recorder_sample_n=1, tpu_breaker_failure_threshold=2,
        tpu_breaker_backoff_initial_ms=50,
        tpu_breaker_backoff_max_ms=200)
    b = await start_node("node1", flight_recorder_sample_n=1)
    nodes = [a, b]
    try:
        b.cluster.join(a.cluster.listen_host, a.cluster.listen_port)
        for n in nodes:
            await wait_until(lambda n=n: (len(n.cluster.members()) == 2
                                          and n.cluster.is_ready()))
        sub = await connected(b, "xn-sub")
        await sub.subscribe("xn/#", qos=1)
        await wait_until(lambda: len(
            a.broker.registry.trie("").match(["xn", "x"])) == 1)
        # both capabilities must have exchanged: spool (QoS1 envelope)
        # and trace (the propagation opt-in)
        await wait_until(lambda: {"spool", "trace"} <= set(
            a.cluster._peer_caps.get("node1", ())))
        pub = await connected(a, "xn-pub")

        await pub.publish("xn/1", b"m1", qos=1)
        m = await sub.recv(15)
        assert m.payload == b"m1"
        # the receiving node RESUMED the origin's trace
        await wait_until(lambda: b.broker.recorder.resumed >= 1)
        resumed = [r for r in b.broker.recorder.snapshot()
                   if r.get("origin")]
        assert resumed, "no resumed record on the receiving node"
        rec = resumed[-1]
        assert rec["origin"]["node"] == "node0"
        assert rec["client"] == "xn-pub" and rec["topic"] == "xn/1"
        assert any(l == "admit" for l, _ in rec["origin"]["marks"])
        assert "cluster_transit_ms" in rec["stages"]
        assert "cluster_ingress_ms" in rec["stages"]

        # device.dispatch fault storm on the origin: the breaker opens
        # (journaled) while delivery continues via the host trie, and
        # the trace keeps propagating
        faults.install(faults.FaultPlan(
            [faults.FaultRule("device.dispatch", kind="error")], seed=3))
        for i in range(6):
            await pub.publish(f"xn/f{i}", b"f%d" % i, qos=1)
            await sub.recv(15)
        assert _poll(lambda: events.journal().counts.get(
            "breaker_open", 0) >= 1)
        faults.clear()

        # ONE merged Perfetto trace from both recorders + the journal
        recs = (a.broker.recorder.snapshot()
                + b.broker.recorder.snapshot())
        evs = events.journal().snapshot()
        trace = chrome_trace(recs, node="node0", journal_events=evs)
        blob = json.dumps(trace)  # Perfetto-loadable as-is
        parsed = json.loads(blob)
        tracks = {e["args"]["name"]: e["pid"]
                  for e in parsed["traceEvents"] if e["ph"] == "M"}
        node0 = [p for n, p in tracks.items()
                 if n.startswith("node0-worker")]
        node1 = [p for n, p in tracks.items()
                 if n.startswith("node1-worker")]
        assert node0 and node1, tracks
        assert set(node0).isdisjoint(node1)
        spans = [e for e in parsed["traceEvents"] if e["ph"] == "X"]
        span_pids = {e["pid"] for e in spans}
        assert span_pids & set(node0) and span_pids & set(node1), \
            "stage spans missing on one node's track"
        # instant events interleave on the same axis, in stamp order,
        # inside the trace's span window
        inst = [e for e in parsed["traceEvents"] if e["ph"] == "i"]
        assert any(e["name"] == "breaker_open" for e in inst)
        ts = [e["ts"] for e in inst]
        assert ts == sorted(ts)
        lo = min(e["ts"] for e in spans)
        hi = max(e["ts"] + e["dur"] for e in spans)
        open_ts = next(e["ts"] for e in inst
                       if e["name"] == "breaker_open")
        assert lo <= open_ts <= hi
        # the cluster hop rendered as flow arrows between the tracks
        assert any(e.get("name") == "cluster_hop" and e["ph"] == "s"
                   for e in parsed["traceEvents"])
        await sub.disconnect()
        await pub.disconnect()
    finally:
        faults.clear()
        await stop_cluster(nodes)


@pytest.mark.asyncio
async def test_trace_cap_negotiation_keeps_envelope_byte_identical():
    """The acceptance guard: without the negotiated "trace" cap (old
    peer) or with observability off, the cluster envelope is
    byte-identical to pre-trace framing on BOTH the legacy msg path
    and the spooled msq path — and cluster-ingress publishes still hit
    the receiver's own 1-in-N admission (the remote-path sampling
    fix)."""
    from test_cluster import start_node, stop_cluster, wait_until
    from vernemq_tpu.broker.message import Msg
    from vernemq_tpu.cluster.node import frame, msg_to_term

    a = await start_node("node0", flight_recorder_sample_n=1)
    b = await start_node("node1", flight_recorder_sample_n=1)
    nodes = [a, b]
    try:
        b.cluster.join(a.cluster.listen_host, a.cluster.listen_port)
        for n in nodes:
            await wait_until(lambda n=n: (len(n.cluster.members()) == 2
                                          and n.cluster.is_ready()))
        await wait_until(lambda: {"spool", "trace"} <= set(
            a.cluster._peer_caps.get("node1", ())))
        w = a.cluster._writers["node1"]
        sent = []
        real_send = w.send_frame

        def capture(data, sheddable=False):
            sent.append(bytes(data))
            return real_send(data, sheddable)

        w.send_frame = capture

        def mk(ref, qos=0):
            return Msg(topic=("nt", "1"), payload=b"x", qos=qos,
                       mountpoint="", msg_ref=ref)

        # capability present + observability on: the context rides
        tr = a.broker.recorder.admit("ntc", "nt/1", 0)
        assert a.cluster.publish("node1", mk(b"r1"), trace=tr)
        assert any(b"trc" in d for d in sent)

        # old peer (no cap): byte-identical legacy framing
        a.cluster._peer_caps["node1"].discard("trace")
        sent.clear()
        msg2 = mk(b"r2")
        tr = a.broker.recorder.admit("ntc", "nt/1", 0)
        assert a.cluster.publish("node1", msg2, trace=tr)
        assert sent == [frame(b"msg", msg_to_term(msg2))]

        # old peer, spooled QoS1: byte-identical msq framing
        seq = a.cluster.spool.state("node1").next_seq
        sent.clear()
        msgq = mk(b"r3", qos=1)
        tr = a.broker.recorder.admit("ntc", "nt/1", 1)
        assert a.cluster.publish("node1", msgq, trace=tr)
        expected = frame(b"msq", (seq, "msg", msg_to_term(msgq)))
        assert expected in sent

        # capability present but observability OFF: same guarantee
        a.cluster._peer_caps["node1"].add("trace")
        hist.set_enabled(False)
        sent.clear()
        msg4 = mk(b"r4")
        forced = PublishTrace(("c", "nt/1", 0))
        assert a.cluster.publish("node1", msg4, trace=forced)
        assert sent == [frame(b"msg", msg_to_term(msg4))]
        hist.set_enabled(True)

        # the remote-path admission fix: an un-traced cluster-ingress
        # publish is sampled by the RECEIVER's own 1-in-N decision
        a.cluster._peer_caps["node1"].discard("trace")
        before = len(b.broker.recorder.records)
        assert a.cluster.publish("node1", mk(b"r5"))
        await wait_until(lambda: any(
            r["client"] == "(cluster)" and r["topic"] == "nt/1"
            for r in list(b.broker.recorder.records)[before:]))
        remote_rec = next(r for r in b.broker.recorder.snapshot()
                          if r["client"] == "(cluster)")
        assert "origin" not in remote_rec  # locally admitted, not resumed
        assert "cluster_ingress_ms" in remote_rec["stages"]
    finally:
        await stop_cluster(nodes)


# ----------------------------------------- worker-slot event aggregation


@pytest.mark.asyncio
async def test_merged_events_fold_worker_slots_and_dump_merge(tmp_path):
    """--merge aggregation: a broker attached as worker 0 of 3 folds
    the OTHER live slots' packed event rings (and the foreign-pid match
    service's) into one interleaved timeline; `events dump --merge` and
    `timeline dump --merge` write it as one artifact."""
    from vernemq_tpu.admin.commands import CommandRegistry, \
        register_core_commands
    from vernemq_tpu.broker.config import Config
    from vernemq_tpu.broker.server import start_broker
    from vernemq_tpu.parallel.shm_ring import WorkerStatsBlock

    def fake_block(code, value, dt=0.0):
        return [1.0, time.monotonic() + dt, time.time() + dt,
                float(events.EVENT_CODES.index(code)), value]

    stats = WorkerStatsBlock.create(f"evm{os.getpid() % 100000}", 3)
    try:
        broker, server = await start_broker(
            Config(systree_enabled=False, allow_anonymous=True,
                   worker_stats_block=stats.name, worker_index=0,
                   workers_total=3),
            port=0, node_name="w0")
        try:
            events.journal().reset()
            events.emit("breaker_open", detail="match")
            # slot 1: live peer with one packed event
            stats.write_health(1, pid=111, sessions=0, admitted=0)
            stats.write_events(1, fake_block("supervisor_restart", 2.0))
            # slot 2: data but NO heartbeat — excluded
            stats.write_events(2, fake_block("supervisor_escalation", 1.0))
            merged = broker.merged_journal_events(merge=True)
            codes = [e["code"] for e in merged]
            assert "breaker_open" in codes
            assert "supervisor_restart" in codes
            assert "supervisor_escalation" not in codes
            assert [e["t"] for e in merged] == sorted(
                e["t"] for e in merged)
            assert next(e for e in merged
                        if e["code"] == "supervisor_restart")["pid"] == 111
            # merge=False: the local journal only
            assert [e["code"] for e in
                    broker.merged_journal_events(merge=False)] == \
                ["breaker_open"]
            # a foreign-pid match service's events merge too
            stats.set_service(1, os.getpid() + 1)
            stats.write_service_events(
                fake_block("mesh_slice_claim", 4.0))
            merged = broker.merged_journal_events(merge=True)
            assert "mesh_slice_claim" in [e["code"] for e in merged]
            # merging twice does not duplicate (the (t, code, pid) key)
            assert len(broker.merged_journal_events(merge=True)) \
                == len(merged)

            reg = register_core_commands(CommandRegistry())
            path = str(tmp_path / "ev.json")
            out = reg.run(broker, ["events", "dump", f"path={path}",
                                   "--merge"])
            assert out["events"] == len(merged)
            assert _poll(lambda: os.path.exists(path))
            with open(path) as fh:
                dump = json.load(fh)
            assert dump["merged"] is True
            assert len(dump["events"]) == len(merged)
            assert dump["codes"]["breaker_open"] == "robustness/breaker"
            # timeline dump --merge interleaves the same stream as
            # instant events
            tpath = str(tmp_path / "tl.json")
            reg.run(broker, ["timeline", "dump", f"path={tpath}",
                             "--merge"])
            assert _poll(lambda: os.path.exists(tpath))
            with open(tpath) as fh:
                tl = json.load(fh)
            inst = [e for e in tl["traceEvents"] if e["ph"] == "i"]
            assert {e["name"] for e in inst} >= {
                "breaker_open", "supervisor_restart", "mesh_slice_claim"}
        finally:
            await broker.stop()
            await server.stop()
    finally:
        stats.close()
        stats.unlink()


# ------------------------------------------------------------------- spans
# (ISSUE 25: one name for the histogram family and the profiler's
# annotation, and the publish's journey under such spans)


class _FakeAnnotation:
    """Stands where ``jax.profiler.TraceAnnotation`` stands."""

    log = []
    session = True  # a profiler session is running

    @classmethod
    def is_enabled(cls):
        return cls.session

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        _FakeAnnotation.log.append(("enter", self.name))

    def __exit__(self, *exc):
        _FakeAnnotation.log.append(("exit", self.name))


@pytest.fixture
def fake_annotation(monkeypatch):
    _FakeAnnotation.log = []
    monkeypatch.setattr(_FakeAnnotation, "session", True)
    monkeypatch.setattr(hist, "_ANNOTATION", _FakeAnnotation)
    monkeypatch.setattr(hist, "_TRACING", _FakeAnnotation.is_enabled)
    return _FakeAnnotation.log


def _count(family):
    return hist.get(family).snapshot()[2]


def _sum(family):
    return hist.get(family).snapshot()[1]


@pytest.mark.parametrize("form", ["with", "begin_end"])
def test_span_observes_once_under_one_name(fake_annotation, form):
    if form == "with":
        with hist.span("stage_route_ms"):
            time.sleep(0.002)
    else:
        sp = hist.span("stage_route_ms").begin()
        time.sleep(0.002)
        assert sp.end() >= 2.0
    assert _count("stage_route_ms") == 1
    assert 2.0 <= _sum("stage_route_ms") < 500.0
    assert fake_annotation == [("enter", "stage_route_ms"),
                               ("exit", "stage_route_ms")]


def test_span_makes_no_annotation_while_no_profiler_session_runs(
        fake_annotation, monkeypatch):
    monkeypatch.setattr(_FakeAnnotation, "session", False)
    with hist.span("stage_route_ms"):
        pass
    assert _count("stage_route_ms") == 1 and fake_annotation == []


def test_span_begin_end_is_the_start_time_alone_outside_a_session(
        fake_annotation, monkeypatch):
    """The per-publish form: no object while no profiler session runs, a
    span like any other while one does, nothing when observability is
    off, and one observation either way."""
    monkeypatch.setattr(_FakeAnnotation, "session", False)
    tok = hist.span_begin("stage_ack_in_ms")
    assert isinstance(tok, float)
    hist.span_end("stage_ack_in_ms", tok)
    assert _count("stage_ack_in_ms") == 1 and fake_annotation == []
    monkeypatch.setattr(_FakeAnnotation, "session", True)
    tok = hist.span_begin("stage_ack_in_ms")
    assert isinstance(tok, hist.Span)
    try:
        raise ValueError("the section failed")
    except ValueError:
        pass
    finally:
        hist.span_end("stage_ack_in_ms", tok)
    assert _count("stage_ack_in_ms") == 2
    assert fake_annotation == [("enter", "stage_ack_in_ms"),
                               ("exit", "stage_ack_in_ms")]
    hist.set_enabled(False)
    tok = hist.span_begin("stage_ack_in_ms")
    assert tok is None
    hist.span_end("stage_ack_in_ms", tok)
    hist.set_enabled(True)
    assert _count("stage_ack_in_ms") == 2
    with pytest.raises(KeyError):
        hist.span_end("stage_no_such_family_ms", 1.0)


def test_span_end_without_record_leaves_the_family_alone(fake_annotation):
    sp = hist.span("stage_fold_wait_ms").begin()
    assert sp.end(record=False) >= 0.0
    assert _count("stage_fold_wait_ms") == 0
    assert fake_annotation[-1] == ("exit", "stage_fold_wait_ms")


def test_span_disabled_is_one_shared_noop(fake_annotation):
    hist.set_enabled(False)
    sp = hist.span("stage_route_ms")
    assert sp is hist.span("stage_ack_in_ms")  # nothing is allocated
    with sp:
        pass
    assert sp.begin().end() == 0.0
    hist.set_enabled(True)
    assert _count("stage_route_ms") == 0 and fake_annotation == []


def test_span_survives_an_exception_in_the_body(fake_annotation):
    with pytest.raises(ValueError):
        with hist.span("stage_route_ms"):
            raise ValueError("routing failed")
    # the section ended: observed once, the annotation closed
    assert _count("stage_route_ms") == 1
    assert fake_annotation[-1] == ("exit", "stage_route_ms")
    with hist.span("stage_route_ms"):
        pass
    assert _count("stage_route_ms") == 2


def test_span_names_a_registered_family_or_raises():
    with pytest.raises(KeyError):
        hist.span("stage_no_such_family_ms")


def test_span_imports_no_jax_in_a_process_without_it():
    """Workers and load generators observe without JAX: the span is the
    histogram alone there and JAX stays unimported."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "from vernemq_tpu.observability import histogram as h\n"
        "with h.span('stage_route_ms'):\n"
        "    pass\n"
        "assert 'jax' not in sys.modules, 'span imported jax'\n"
        "assert h._ANNOTATION is None\n"
        "print(h.get('stage_route_ms').snapshot()[2])\n")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    p = subprocess.run([sys.executable, "-c", code], cwd=root,
                       capture_output=True, text=True, timeout=60)
    assert p.returncode == 0, p.stderr[-2000:]
    assert p.stdout.strip() == "1"


def test_span_uses_jax_own_annotation_once_jax_is_loaded():
    import jax

    hist._ANNOTATION, hist._TRACING = None, hist._find_session
    with hist.span("stage_route_ms"):
        pass
    assert hist._ANNOTATION is jax.profiler.TraceAnnotation
    assert hist._TRACING == jax.profiler.TraceAnnotation.is_enabled
    assert hist._TRACING() is False  # no profiler session here
    assert _count("stage_route_ms") == 1


FOLD_FAMILIES = ("stage_fold_prep_ms", "stage_fold_launch_ms",
                 "stage_fold_wait_ms", "stage_fold_resolve_ms")
FOLD_FIELDS = ("prep_ms", "launch_ms", "wait_ms", "resolve_ms",
               "lock_wait_ms")


@pytest.fixture(scope="module")
def fold_matchers():
    """A bucketed table (the windowed kernels) and a flat one (the
    full-scan kernel), each with its shapes compiled."""
    from vernemq_tpu.models.tpu_matcher import TpuMatcher

    out = {}
    for name, cap in (("windowed", 8192), ("flat", 1024)):
        m = TpuMatcher(max_levels=8, initial_capacity=cap)
        for i in range(300):
            m.table.add(("bench", str(i)), i, None)
        assert m.table.bucketed == (name == "windowed")
        m.match_batch(_fold_topics(20), _warmup=True)
        out[name] = m
    out["windowed"].match_many([_fold_topics(20)] * 2, _warmup=True)
    return out


def _fold_topics(n):
    return [("bench", str(i)) for i in range(n)]


@pytest.mark.parametrize("call,table", [("batch", "windowed"),
                                        ("many", "windowed"),
                                        ("batch", "flat")])
def test_fold_puts_one_observation_in_each_of_its_four_phases(
        fold_matchers, fake_annotation, call, table):
    m = fold_matchers[table]
    t0 = time.monotonic()
    if call == "batch":
        rows = m.match_batch(_fold_topics(20))
        assert [len(r) for r in rows] == [1] * 20
    else:
        rows = m.match_many([_fold_topics(20)] * 2)
        assert [len(r) for b in rows for r in b] == [1] * 40
    wall_ms = (time.monotonic() - t0) * 1e3
    assert [_count(f) for f in FOLD_FAMILIES] == [1, 1, 1, 1]
    assert _count("stage_device_dispatch_ms") == 1
    total = sum(_sum(f) for f in FOLD_FAMILIES)
    assert 0.0 < total <= wall_ms
    # the dispatch span of old starts after the encode and ends before
    # the resolve: it lies inside the four
    assert _sum("stage_device_dispatch_ms") <= total
    rec = profiler().snapshot("match")[-1]
    for field in FOLD_FIELDS:
        assert rec[field] >= 0.0, field
    assert sum(rec[f] for f in FOLD_FIELDS[:4]) == pytest.approx(
        total, abs=0.01)
    assert rec["lock_wait_ms"] <= rec["prep_ms"]
    # each phase is a span of its own in the profiler's trace, in order
    entered = [n for what, n in fake_annotation if what == "enter"]
    assert entered == list(FOLD_FAMILIES)
    assert len(fake_annotation) == 8


@pytest.mark.parametrize("call", ["batch", "many"])
def test_a_warmup_fold_observes_nothing(fold_matchers, call):
    m = fold_matchers["windowed"]
    if call == "batch":
        m.match_batch(_fold_topics(20), _warmup=True)
    else:
        m.match_many([_fold_topics(20)] * 2, _warmup=True)
    assert [_count(f) for f in FOLD_FAMILIES] == [0, 0, 0, 0]
    assert _count("stage_device_dispatch_ms") == 0
    assert profiler().snapshot("match") == []


def test_a_fold_with_observability_off_observes_nothing(fold_matchers):
    hist.set_enabled(False)
    rows = fold_matchers["windowed"].match_batch(_fold_topics(20))
    hist.set_enabled(True)
    assert [len(r) for r in rows] == [1] * 20
    assert [_count(f) for f in FOLD_FAMILIES] == [0, 0, 0, 0]
    assert profiler().snapshot("match") == []


async def _apoll(cond, timeout=5.0, interval=0.02):
    """``_poll`` for a condition the running loop itself brings about."""
    deadline = time.monotonic() + timeout
    while not cond() and time.monotonic() < deadline:
        await asyncio.sleep(interval)
    return cond()


class _InstantView:
    registry = None

    def matcher(self, mp):
        return None

    def fold_batch(self, mp, topics, lock_timeout=None):
        return [[("row", t)] for t in topics]


@pytest.mark.asyncio
async def test_release_wait_is_observed_once_a_chunk_of_64():
    from vernemq_tpu.models.tpu_matcher import BatchCollector

    col = BatchCollector(_InstantView(), window_us=200, max_batch=4096,
                         host_threshold=0)
    released = []
    futs = [col.submit("", ("t", str(i))) for i in range(200)]
    for i, f in enumerate(futs):
        f.add_done_callback(lambda _f, i=i: released.append(i))
    await asyncio.gather(*futs)
    assert released == list(range(200))  # submission order, as before
    # 200 futures of one flush leave in chunks of 64, 64, 64 and 8
    assert _count("stage_release_wait_ms") == 4
    assert _sum("stage_release_wait_ms") >= 0.0
    hist.reset_all()
    hist.set_enabled(False)
    await asyncio.gather(*[col.submit("", ("t", str(i)))
                           for i in range(200)])
    hist.set_enabled(True)
    assert _count("stage_release_wait_ms") == 0


def test_recorder_separates_the_release_queue_from_routing(monkeypatch):
    """A sampled publish's record: ``release_wait_ms`` is the settled
    future's wait for its turn, ``route_ms`` what came after it; and
    the publish's admission (start to collector submit) is observed."""
    from vernemq_tpu.observability import recorder as recorder_mod

    # the stamps' clock stepped by hand: a sleep on a crowded machine
    # overshoots by more than the stages differ
    import types

    now = [100.0]
    monkeypatch.setattr(recorder_mod, "time", types.SimpleNamespace(
        monotonic=lambda: now[0], time=time.time))
    rec = FlightRecorder(sample_n=1)
    tr = rec.admit("c", "a/b", 1)
    for label, pause in (("admit", 0.002), ("submit", 0.003),
                         ("dequeue", 0.0), ("match", 0.0),
                         ("settle", 0.0), ("release", 0.03),
                         ("route", 0.005)):
        now[0] += pause
        tr.stamp(label)
    st = rec.finish(tr)["stages"]
    assert set(st) >= {"settle_ms", "release_wait_ms", "route_ms"}
    assert st["release_wait_ms"] >= 30.0
    assert 4.0 <= st["route_ms"] < 30.0  # the wait is not in it
    assert _count("stage_pub_admit_ms") == 1
    assert 4.0 <= _sum("stage_pub_admit_ms") < 30.0
    # a publish that never reached the collector has no admission span
    tr2 = rec.admit("c", "a/b", 0)
    tr2.stamp("admit")
    tr2.stamp("route")
    rec.finish(tr2)
    assert _count("stage_pub_admit_ms") == 1


@pytest.mark.asyncio
@pytest.mark.parametrize("fastpath_on", [True, False])
async def test_broker_qos1_journey_is_under_spans(fastpath_on):
    """QoS 1 through the batched view on the CPU: one route span a
    publish routed, one ack span a PUBACK received (wire plane and
    classic handler alike), sampled records that carry the release
    queue apart from routing, and the loop's CPU seconds rising."""
    from vernemq_tpu.broker.config import Config
    from vernemq_tpu.broker.server import start_broker
    from vernemq_tpu.client import MQTTClient

    cfg = Config(systree_enabled=False, allow_anonymous=True,
                 default_reg_view="tpu", flight_recorder_sample_n=2,
                 wire_fastpath_enabled=fastpath_on)
    broker, server = await start_broker(cfg, port=0)
    broker.sysmon.interval = 0.05  # from its second tick on
    try:
        sub = MQTTClient("127.0.0.1", server.port, client_id="span-sub")
        pub = MQTTClient("127.0.0.1", server.port, client_id="span-pub")
        assert (await sub.connect()).rc == 0
        assert (await pub.connect()).rc == 0
        await sub.subscribe("a/b", qos=1)
        hist.reset_all()
        n_pub = 24
        for _ in range(n_pub):
            await pub.publish("a/b", b"p", qos=1)
        for _ in range(n_pub):
            assert (await sub.recv(timeout=10.0)).topic == "a/b"
        m = broker.metrics
        assert await _apoll(
            lambda: m.value("mqtt_puback_received") >= n_pub)
        assert _count("stage_route_ms") == n_pub
        assert _count("stage_ack_in_ms") == m.value("mqtt_puback_received")
        assert await _apoll(lambda: broker.recorder.finished
                            == broker.recorder.sampled)
        recs = broker.recorder.snapshot()
        assert len(recs) == n_pub // 2
        for r in recs:
            assert {"settle_ms", "release_wait_ms",
                    "route_ms"} <= set(r["stages"])
        assert _count("stage_pub_admit_ms") == len(recs)
        assert await _apoll(lambda: broker.sysmon.loop_cpu_s > 0)
        c0 = broker.sysmon.loop_cpu_s
        t_end = time.thread_time() + 0.2
        while time.thread_time() < t_end:
            pass  # the loop's thread on the CPU, however crowded it is
        await asyncio.sleep(0.2)
        assert broker.sysmon.loop_cpu_s >= c0 + 0.15
        assert broker._gauges()["loop_cpu_s"] >= c0 + 0.15
        await pub.disconnect()
        await sub.disconnect()
    finally:
        await broker.stop()
        await server.stop()
