"""The kernel-resident multi-batch pipeline (match_many): K publish
batches per device dispatch.

Covers the ISSUE-1 tentpole contract end to end: oracle equivalence vs
the host trie for K ∈ {1, 4, 8} with mixed +/# filters, bit-identical
results vs K independent match_batch calls, byte-identical kernel
output vs per-batch packed calls, BatchCollector super-batches
(per-future ordering + error propagation when a super-batch fails) and
the sharded seat's pipelined match_many."""

import asyncio
import random
import time

import numpy as np
import pytest

from vernemq_tpu.models.tpu_matcher import BatchCollector, TpuMatcher
from vernemq_tpu.models.trie import SubscriptionTrie

from tests.test_tpu_match import corpus_filter, norm, spy_kernel_call


def _corpus(seed: int, n: int = 8000):
    rng = random.Random(seed)
    m = TpuMatcher(max_levels=8, initial_capacity=16384)
    assert m.table.bucketed
    trie = SubscriptionTrie()
    for i in range(n):
        f = corpus_filter(rng)
        m.table.add(f, i, None)
        trie.add(list(f), i, None)
    return m, trie, rng


def _topics(rng, n):
    return [(f"r{rng.randrange(16)}", f"d{rng.randrange(40)}",
             f"m{rng.randrange(16)}") for _ in range(n)]


@pytest.fixture(scope="module")
def corpus():
    return _corpus(101)


@pytest.mark.parametrize("k", [1, 4, 8])
def test_match_many_oracle_parity(corpus, k):
    """match_many results must equal the host trie oracle for every
    topic of every batch — mixed +/# wildcard corpus, K ∈ {1, 4, 8}."""
    m, trie, rng = corpus
    batches = [_topics(rng, 64) for _ in range(k)]
    results = m.match_many(batches)
    assert len(results) == k
    for topics, rows_per_topic in zip(batches, results):
        assert len(rows_per_topic) == len(topics)
        for t, rows in zip(topics, rows_per_topic):
            assert norm(rows) == norm(trie.match(list(t))), t


def test_match_many_bit_identical_to_match_batch(corpus):
    """The fused K-batch dispatch must return the SAME row lists (same
    order, same entries) as K independent match_batch calls."""
    m, trie, rng = corpus
    batches = [_topics(rng, 64) for _ in range(4)]
    before = m.super_dispatches
    many = m.match_many(batches)
    assert m.super_dispatches == before + 1  # ONE fused device dispatch
    singles = [m.match_batch(b) for b in batches]
    for b_many, b_single in zip(many, singles):
        for rows_m, rows_s in zip(b_many, b_single):
            assert [(tuple(f), key) for f, key, _ in rows_m] == \
                [(tuple(f), key) for f, key, _ in rows_s]


def test_match_many_mixed_batch_sizes(corpus):
    """Batches of different sizes pad to ONE common Bpad and still
    match the oracle (the collector's tail chunk is usually partial)."""
    m, trie, rng = corpus
    batches = [_topics(rng, 10), _topics(rng, 64), _topics(rng, 33)]
    for topics, rows_per_topic in zip(batches, m.match_many(batches)):
        for t, rows in zip(topics, rows_per_topic):
            assert norm(rows) == norm(trie.match(list(t))), t


def test_match_many_single_batch_falls_back(corpus):
    """K == 1 serves through the plain match_batch path (no scan
    overhead) with identical results."""
    m, trie, rng = corpus
    topics = _topics(rng, 32)
    before = m.super_dispatches
    res = m.match_many([topics])
    assert m.super_dispatches == before  # no fused dispatch for K=1
    for t, rows in zip(topics, res[0]):
        assert norm(rows) == norm(trie.match(list(t))), t


def test_match_many_kernel_byte_identical_to_packed_calls(corpus,
                                                          monkeypatch):
    """What ``TpuMatcher.match_many`` dispatches —
    ops.match_kernel.match_many (scan + donated staging) through
    ``call_match_many`` — returns byte-identical result vectors to K
    separate packed calls on the same operands: the multi-batch pipeline
    loses nothing vs the per-batch transport."""
    from vernemq_tpu.ops import match_kernel as K

    m, _, rng = corpus
    calls = spy_kernel_call(monkeypatch, "call_match_many")
    m.match_many([_topics(rng, 64) for _ in range(3)])
    ((F_t, t1, meta, preps, statics), _kw, stacked), = calls
    stacked = np.asarray(stacked)
    singles = [np.asarray(K.call_packed(F_t, t1, meta, args, statics))
               for args in preps]
    assert stacked.shape == (3,) + singles[0].shape
    for i, single in enumerate(singles):
        np.testing.assert_array_equal(stacked[i], single)
    # unpack helper agrees with the per-batch decoder
    Bpad = preps[0][0].shape[0]
    decoded = K.unpack_many_results(stacked, Bpad, statics["C"])
    for i, (flat, pre, total, ovf) in enumerate(decoded):
        f2, p2, t2, o2 = K.unpack_flat_result(singles[i], Bpad,
                                              statics["C"])
        np.testing.assert_array_equal(flat, f2)
        np.testing.assert_array_equal(total, t2)


# ---------------------------------------------------------------------------
# BatchCollector super-batches
# ---------------------------------------------------------------------------

class _ManyView:
    """Stand-in TpuRegView with a fold_many seam: records the chunking
    of every super-batch and serves deterministic per-topic rows."""

    registry = None

    def __init__(self, device_ms: float = 20.0, fail_super: bool = False):
        self.device_ms = device_ms
        self.fail_super = fail_super
        self.batches = []       # fold_batch sizes
        self.super_calls = []   # fold_many chunk-size lists

    def matcher(self, mp):
        return None

    def fold_batch(self, mp, topics, lock_timeout=None):
        self.batches.append(len(topics))
        time.sleep(self.device_ms / 1000.0)
        return [[("row", t)] for t in topics]

    def fold_many(self, mp, batches, lock_timeout=None):
        self.super_calls.append([len(b) for b in batches])
        if self.fail_super:
            raise RuntimeError("super-batch device failure")
        time.sleep(self.device_ms / 1000.0)
        return [[[("row", t)] for t in topics] for topics in batches]


@pytest.mark.asyncio
async def test_collector_coalesces_super_batches_under_load():
    """With both pipeline slots busy and multiple windows queued, the
    collector ships up to super_batch_k windows as ONE fold_many call,
    chunks them at max_batch, and every future resolves to ITS topic's
    rows in submission order."""
    view = _ManyView(device_ms=40)
    col = BatchCollector(view, window_us=200, max_batch=8,
                         host_threshold=0, super_batch_k=4)
    futs = []
    for wave in range(10):
        for i in range(16):
            futs.append(col.submit("", ("t", f"w{wave}", f"i{i}")))
        await asyncio.sleep(0.004)
    order = []
    for i, f in enumerate(futs):
        f.add_done_callback(lambda f, i=i: order.append(i))
    rows = await asyncio.gather(*futs)
    assert col.super_batches > 0 and view.super_calls
    for chunks in view.super_calls:
        assert len(chunks) >= 2          # a super-batch is >1 window
        assert all(c <= 8 for c in chunks)
        assert sum(chunks) <= 8 * col.super_batch_k
    # each future got its own topic's result, released in order
    for i, r in enumerate(rows):
        assert r == [("row", ("t", f"w{i // 16}", f"i{i % 16}"))]
    assert order == sorted(order), "futures released out of order"
    assert col._inflight == 0 and not col._pending


@pytest.mark.asyncio
async def test_collector_super_batch_error_propagates():
    """A device failure inside a super-batch must error every future of
    that super-batch — and ONLY those — still releasing in submission
    order."""
    view = _ManyView(device_ms=60, fail_super=True)
    col = BatchCollector(view, window_us=200, max_batch=8,
                         host_threshold=0, super_batch_k=4)
    # two single-window flushes occupy both pipeline slots (fold_batch
    # succeeds) ...
    ok_futs = [col.submit("", ("ok", str(i))) for i in range(16)]
    # ... so this burst queues past one window and ships as a
    # super-batch (fold_many) when a slot frees — and fails
    bad_futs = [col.submit("", ("bad", str(i))) for i in range(24)]
    res_ok = await asyncio.gather(*ok_futs, return_exceptions=True)
    res_bad = await asyncio.gather(*bad_futs, return_exceptions=True)
    assert all(not isinstance(r, Exception) for r in res_ok)
    assert view.super_calls, "no super-batch formed"
    assert all(isinstance(r, RuntimeError) for r in res_bad)
    assert col._inflight == 0


# ---------------------------------------------------------------------------
# Sharded seat
# ---------------------------------------------------------------------------

def test_sharded_seat_match_many_parity():
    """ShardedTpuMatcher.match_many (pipelined launch-all-then-pull)
    agrees with the oracle and with per-batch match_batch."""
    from vernemq_tpu.parallel.mesh import make_mesh
    from vernemq_tpu.parallel.sharded_match import ShardedTpuMatcher

    rng = random.Random(17)
    mesh = make_mesh(batch=2)
    m = ShardedTpuMatcher(mesh, max_levels=8)
    trie = SubscriptionTrie()
    l0 = [f"r{i}" for i in range(16)]
    l1 = [f"d{i}" for i in range(32)]
    l2 = [f"m{i}" for i in range(8)]
    with m.lock:
        for i in range(12000):
            r = rng.random()
            w = [rng.choice(l0), rng.choice(l1), rng.choice(l2)]
            f = (w if r < 0.6 else [w[0], "+", w[2]] if r < 0.8
                 else ["+", w[1], w[2]] if r < 0.9 else [w[0], w[1], "#"])
            m.table.add(list(f), i, None)
            trie.add(list(f), i, None)

    def topics(n):
        return [(rng.choice(l0), rng.choice(l1), rng.choice(l2))
                for _ in range(n)]

    batches = [topics(16), topics(16)]
    before = m.super_dispatches
    many = m.match_many(batches)
    assert m.super_dispatches == before + 1
    singles = [m.match_batch(b) for b in batches]
    for tb, rows_many, rows_single in zip(batches, many, singles):
        for t, r1, r2 in zip(tb, rows_many, rows_single):
            assert norm(r1) == norm(trie.match(list(t))), t
            assert norm(r1) == norm(r2), t


