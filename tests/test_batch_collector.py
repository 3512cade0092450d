"""BatchCollector pipelining tests: double-buffered dispatch, bounded
in-flight, self-batching backpressure under saturation (the pipelined
collector of VERDICT r3 item 2)."""

import asyncio
import functools
import time

import pytest

from vernemq_tpu.models.tpu_matcher import BatchCollector


class _SlowView:
    """Stand-in TpuRegView whose device call takes device_ms and records
    concurrency."""

    registry = None  # no host-hybrid path

    def __init__(self, device_ms: float = 30.0):
        self.device_ms = device_ms
        self.active = 0
        self.max_active = 0
        self.batches = []

    def matcher(self, mp):
        return None

    def fold_batch(self, mp, topics, lock_timeout=None):
        self.active += 1
        self.max_active = max(self.max_active, self.active)
        time.sleep(self.device_ms / 1000.0)
        self.active -= 1
        self.batches.append(len(topics))
        return [[("row", t)] for t in topics]


@pytest.mark.asyncio
async def test_collector_bounded_inflight_and_merge():
    view = _SlowView(device_ms=40)
    col = BatchCollector(view, window_us=200, max_batch=64,
                         host_threshold=0)
    # 40 waves of submissions while the device is busy
    futs = []
    for wave in range(20):
        for i in range(16):
            futs.append(col.submit("", ("t", f"w{wave}", f"i{i}")))
        await asyncio.sleep(0.005)
    rows = await asyncio.gather(*futs)
    assert len(rows) == 320 and all(r for r in rows)
    # never more than the two pipeline slots on the "device"
    assert view.max_active <= BatchCollector.MAX_INFLIGHT
    # saturation coalesced waves into bigger batches instead of queueing
    assert col.saturated_merges > 0
    assert max(view.batches) > 16
    assert col._inflight == 0 and not col._pending


@pytest.mark.asyncio
async def test_collector_back_to_back_dispatch():
    """A batch waiting on a busy slot goes out the moment the slot
    frees — not after another window."""
    view = _SlowView(device_ms=20)
    col = BatchCollector(view, window_us=100_000,  # 100ms window
                         max_batch=8, host_threshold=0)
    futs = [col.submit("", ("a", str(i))) for i in range(8)]  # full: flush
    await asyncio.sleep(0.002)
    late = [col.submit("", ("b", str(i))) for i in range(8)]  # full: flush
    extra = [col.submit("", ("c",))]  # sub-batch: would wait 100ms window
    t0 = time.perf_counter()
    await asyncio.gather(*futs, *late, *extra)
    took = time.perf_counter() - t0
    # 3 batches × 20ms device, two slots: without the on-done flush the
    # partial batch waits out a full extra 100ms window (≥120ms total),
    # so finishing inside one window proves it went out immediately.
    # (Bound = the window itself: the old 90ms margin flaked under
    # full-suite load.)
    assert took < 0.1, took


@pytest.mark.asyncio
async def test_collector_device_error_resolves_futures():
    class _Boom(_SlowView):
        def fold_batch(self, mp, topics, lock_timeout=None):
            raise RuntimeError("device on fire")

    col = BatchCollector(_Boom(), window_us=100, max_batch=8,
                         host_threshold=0)
    futs = [col.submit("", ("x", str(i))) for i in range(12)]
    res = await asyncio.gather(*futs, return_exceptions=True)
    assert all(isinstance(r, RuntimeError) for r in res)
    assert col._inflight == 0


@pytest.mark.asyncio
async def test_collector_overload_sheds_to_host_trie():
    """Arrival rate above device service rate: once both slots are busy
    and a full batch waits, submits are matched on the host trie instead
    of queueing unboundedly — and still RELEASE in submission order (no
    reordering past earlier in-flight batches)."""

    class _Reg:
        class _T:
            @staticmethod
            def match(topic):
                return [("host-row", tuple(topic))]

        def trie(self, mp):
            return self._T

    view = _SlowView(device_ms=100)
    view.registry = _Reg()
    col = BatchCollector(view, window_us=100, max_batch=8,
                         host_threshold=0)
    futs = [col.submit("", ("x", str(i))) for i in range(40)]
    assert col.overload_host_pubs > 0
    # FIFO release: shed results must NOT resolve before the earlier
    # device batches they follow
    assert not any(f.done() for f in futs[24:])
    order = []
    for i, f in enumerate(futs):
        f.add_done_callback(lambda f, i=i: order.append(i))
    rows = await asyncio.gather(*futs)
    assert order == sorted(order), "futures released out of order"
    assert rows[-1][0][0] == "host-row"  # tail was host-shed
    assert view.max_active <= BatchCollector.MAX_INFLIGHT


@pytest.mark.asyncio
async def test_per_publisher_order_preserved_under_slow_device():
    """Broker-level FIFO: one publisher streams QoS0 publishes through
    the batched device view (nowait path) while device batches are
    artificially slow and racing in the two pipeline slots; the
    subscriber must see every message in publish order."""
    from vernemq_tpu.broker.config import Config
    from vernemq_tpu.broker.server import start_broker
    from vernemq_tpu.client import MQTTClient

    b, s = await start_broker(
        Config(systree_enabled=False, allow_anonymous=True,
               default_reg_view="tpu", sysmon_enabled=False,
               tpu_batch_window_us=2000, tpu_host_batch_threshold=2,
               # the point of this test is racing REAL device batches in
               # both slots; the busy/cold-shape shed would divert them
               tpu_lock_busy_shed_ms=0),
        port=0)
    try:
        view = b.registry.reg_view("tpu")
        assert hasattr(view, "fold_batch")  # real device view (cpu)
        m = view.matcher("")
        orig = m.match_batch
        calls = []

        def slow_match(topics, _warmup=False, lock_timeout=None,
                       require_warm=False):
            if not _warmup:
                calls.append(len(topics))
                # VARIABLE latency: odd-numbered batches are much slower
                # than even ones, so with both pipeline slots racing, a
                # newer batch finishes BEFORE an older one — exactly the
                # reorder window the FIFO release must absorb
                time.sleep(0.08 if len(calls) % 2 else 0.005)
            return orig(topics, _warmup=_warmup)

        m.match_batch = slow_match
        sub = MQTTClient(s.host, s.port, "ord-sub")
        await sub.connect()
        await sub.subscribe("ord/#", qos=0)
        await asyncio.sleep(0.2)
        pub = MQTTClient(s.host, s.port, "ord-pub")
        await pub.connect()
        n = 120
        for i in range(n):
            await pub.publish("ord/t", b"%04d" % i, qos=0)
            if i % 10 == 0:
                await asyncio.sleep(0.005)  # spread across batch windows
        got = []
        for _ in range(n):
            f = await sub.recv(10.0)
            assert f is not None
            got.append(int(f.payload))
        assert got == list(range(n)), (
            f"reordered: first bad at {next(i for i, (a, b2) in enumerate(zip(got, range(n))) if a != b2)}")
        await sub.disconnect()
        await pub.disconnect()
    finally:
        await b.stop()
        await s.stop()


@pytest.mark.asyncio
@pytest.mark.parametrize("state,expect_latency", [
    ("idle", False), ("queued", True), ("inflight", True)])
async def test_pressure_latency_counts_only_while_busy(state,
                                                       expect_latency):
    """The dispatch-latency EWMA folds only on a flush, so on an idle
    collector it is the memory of the last burst: reported as pressure
    it latched the overload governor at L1 (100 ms per inbound PUBLISH)
    on an idle broker. With work queued or in flight it is pressure."""
    from vernemq_tpu.robustness.overload import LATENCY_SEVERITY_CAP

    view = _SlowView(device_ms=200)
    col = BatchCollector(view, window_us=100_000, max_batch=64,
                         host_threshold=0, latency_budget_ms=50.0)
    col.dispatch_ewma_ms = 400.0  # one slow flush, eight budgets long
    futs = []
    if state == "queued":
        futs = [col.submit("", ("q", "1"))]  # waits out the 100ms window
        assert col._pending and not col._inflight
    elif state == "inflight":
        futs = [col.submit("", ("f", str(i))) for i in range(64)]
        await asyncio.sleep(0.01)  # full window: flushed, device busy
        assert col._inflight and not col._pending
    p = col.pressure()
    if expect_latency:
        assert p == pytest.approx(LATENCY_SEVERITY_CAP)
    else:
        assert p == 0.0
    await asyncio.gather(*futs)


@pytest.mark.asyncio
async def test_release_is_chunked_and_in_submission_order():
    """A flush settles thousands of futures at once; releasing one runs
    its caller's whole fanout on the loop. They are released
    _RELEASE_CHUNK per loop callback, in submission order, so IO and
    timers run in between."""
    view = _SlowView(device_ms=1)
    col = BatchCollector(view, window_us=100, max_batch=1024,
                         host_threshold=0)
    n = 1000
    released = []          # submission index, in release order
    per_iteration = []     # releases seen between two loop iterations

    async def ticker():
        seen = 0
        while len(released) < n:
            await asyncio.sleep(0)
            per_iteration.append(len(released) - seen)
            seen = len(released)

    futs = [col.submit("", ("t", str(i))) for i in range(n)]
    for i, f in enumerate(futs):
        f.add_done_callback(lambda _f, i=i: released.append(i))
    await asyncio.gather(ticker(), *futs)
    assert released == list(range(n))
    assert max(per_iteration) <= 2 * col._RELEASE_CHUNK
    assert not col._order and not col._releasing


def test_queued_expiry_follows_the_measured_dispatch_time():
    """N budgets is the floor; N measured dispatches where those take
    longer than the budget; never past the dispatch deadline (a wedged
    dispatch is abandoned there)."""
    col = BatchCollector(_SlowView(), latency_budget_ms=50.0,
                         item_expiry_ms=200.0, dispatch_deadline_ms=5000.0,
                         watchdog=object())
    assert col._expiry_s() == pytest.approx(0.2)
    col.dispatch_peak_ms = 30.0       # faster than the budget: the floor
    assert col._expiry_s() == pytest.approx(0.2)
    col.dispatch_peak_ms = 400.0      # four measured dispatches
    assert col._expiry_s() == pytest.approx(1.6)
    col.dispatch_peak_ms = 3000.0     # capped at the dispatch deadline
    assert col._expiry_s() == pytest.approx(5.0)
    off = BatchCollector(_SlowView(), item_expiry_ms=0.0)
    off.dispatch_peak_ms = 400.0
    assert off._expiry_s() == 0.0     # expiry off stays off


@pytest.mark.asyncio
async def test_slowest_recent_flush_is_a_decaying_peak():
    view = _SlowView(device_ms=120)
    col = BatchCollector(view, window_us=100, max_batch=8,
                         host_threshold=0, latency_budget_ms=50.0,
                         item_expiry_ms=200.0)
    await asyncio.gather(*[col.submit("", ("s", str(i)))
                           for i in range(8)])
    slow = col.dispatch_peak_ms
    assert slow >= 120 and col._expiry_s() >= 0.48
    view.device_ms = 1
    for _ in range(3):
        await asyncio.gather(*[col.submit("", ("f", str(i)))
                               for i in range(8)])
    # three quick flushes: the peak has decayed, not vanished
    assert 0.4 * slow < col.dispatch_peak_ms < 0.6 * slow


class _TrieReg:
    """The registry a shed path asks for the host trie."""

    class _T:
        @staticmethod
        def match(topic):
            return [("host-row", tuple(topic))]

    def trie(self, mp):
        return self._T


@pytest.mark.asyncio
@pytest.mark.parametrize("path", ["device", "hybrid", "overload", "expiry"])
async def test_continuations_and_futures_leave_in_one_submission_order(
        path):
    """A submission made with ``cont`` gets its rows by an inline call
    from the release queue and makes no future; mixed with awaited
    submissions, whichever path served them — the device, the host
    trie below the threshold, the overload shed, the queued-item
    expiry — all leave in ONE order, the order of submission."""
    view = _SlowView(device_ms=60 if path in ("overload", "expiry") else 5)
    view.registry = _TrieReg()
    kw = dict(window_us=100, max_batch=8, host_threshold=0)
    n = 40
    if path == "hybrid":
        kw.update(max_batch=64, host_threshold=64)
    elif path == "device":
        kw.update(max_batch=64)
    elif path == "expiry":
        kw.update(item_expiry_ms=20, latency_budget_ms=5.0)
        n = 20  # two dispatches in flight, the rest waits and expires
    col = BatchCollector(view, **kw)
    order = []
    futs = {}
    misplaced = []

    def cont(rows, exc, i):
        # a future is done the moment the queue released it (its
        # callbacks run a loop step later): every awaited submission
        # before this one has left, none after it has
        misplaced.extend(j for j, f in futs.items()
                         if f.done() != (j < i))
        order.append((i, rows[0][0]))

    for i in range(n):
        if i % 3 == 0:
            futs[i] = col.submit("", ("x", str(i)))
            futs[i].add_done_callback(
                lambda f, i=i: order.append((i, f.result()[0][0])))
        else:
            r = col.submit("", ("x", str(i)),
                           cont=functools.partial(cont, i=i))
            assert r is None
    assert len(col._order) == n
    await asyncio.gather(*futs.values())
    for _ in range(200):
        if len(order) == n:
            break
        await asyncio.sleep(0.01)
    assert misplaced == []
    for form in (0, 1):  # each form alone is in order too
        idx = [i for i, _ in order if (i % 3 == 0) == (form == 0)]
        assert idx == sorted(idx)
    assert len(order) == n
    served = {row for _, row in order}
    if path == "device":
        assert served == {"row"}
    elif path == "hybrid":
        assert served == {"host-row"} and col.host_hybrid_pubs == n
    elif path == "overload":
        assert served == {"row", "host-row"} and col.overload_host_pubs
    else:
        assert served == {"row", "host-row"} and col.expired_host_pubs
    assert not col._order and not col._releasing


@pytest.mark.asyncio
async def test_continuation_gets_the_folds_error_and_one_that_raises_is_contained():
    """A fold that raises reaches a continuation as ``exc``; a
    continuation that raises itself does not take the queue behind it
    down."""

    class _Boom(_SlowView):
        def fold_batch(self, mp, topics, lock_timeout=None):
            raise RuntimeError("device on fire")

    col = BatchCollector(_Boom(), window_us=100, max_batch=8,
                         host_threshold=0)
    seen = []

    def bad(rows, exc):
        raise ValueError("continuation bug")

    col.submit("", ("x", "0"), cont=bad)
    col.submit("", ("x", "1"), cont=lambda rows, exc: seen.append(exc))
    fut = col.submit("", ("x", "2"))
    with pytest.raises(RuntimeError):
        await fut
    assert len(seen) == 1 and isinstance(seen[0], RuntimeError)
    assert not col._order and col._inflight == 0


@pytest.mark.asyncio
async def test_release_wait_is_observed_for_continuations_too():
    """``stage_release_wait_ms``: one observation a release chunk,
    whichever form its head has."""
    from vernemq_tpu.observability import histogram as obs

    view = _SlowView(device_ms=1)
    col = BatchCollector(view, window_us=100, max_batch=1024,
                         host_threshold=0)
    n = 3 * col._RELEASE_CHUNK
    done = []
    before = obs.get("stage_release_wait_ms").snapshot()[2]
    for i in range(n):
        col.submit("", ("t", str(i)), cont=lambda r, e: done.append(r))
    for _ in range(500):
        if len(done) == n:
            break
        await asyncio.sleep(0.01)
    assert len(done) == n
    assert obs.get("stage_release_wait_ms").snapshot()[2] - before == 3
