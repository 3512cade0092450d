"""Ask the v5e's compiler, without a chip, whether the device programs of
the main path compile at the real size.

The TPU compiler is installed in the sandbox and compiles for a chip that
is described, not attached (``jax.experimental.topologies``). Nothing
runs here — a passing compile says nothing about results or times — but
a program Mosaic/XLA refuses, or one that cannot fit 16 GB of HBM, fails
here at no chip cost.

Rules this file keeps (one process may load libtpu at a time, and the
driver runs the suite under several xdist workers): the topology is
described inside a module-scoped fixture, never at import and never in
conftest; every compile runs in the test's own process; all such tests
live in this one file; the persistent compile cache is off around them
(a described-device executable can be written to it but not read back).

Geometry is the broker's own: the table a ``TpuRegView`` matcher builds
for ``chip_smoke.build_corpus``'s mix at 1,000,000 subscriptions with
``tpu_initial_capacity=1<<20`` (warm-loaded in trie order: 3,219,456
rows), shapes and statics straight from ``TpuMatcher._flat_prep`` — what
``chip_smoke.py`` dispatches.
"""

import os
import random
import sys
import warnings

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_SUBS = 1_000_000
HBM_BYTES = 16e9  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def matcher():
    """The 1M-subscription table, built once on the CPU backend with the
    knobs the registry hands a TpuRegView matcher (config defaults)."""
    from chip_smoke import build_corpus
    from vernemq_tpu.broker.config import DEFAULTS
    from vernemq_tpu.models.tpu_matcher import TpuMatcher

    from vernemq_tpu.models.trie import SubscriptionTrie

    m = TpuMatcher(initial_capacity=1 << 20,
                   max_fanout=DEFAULTS["tpu_max_fanout"],
                   flat_avg=DEFAULTS["tpu_flat_avg"])
    # the broker's device table warm-loads from the registry's trie
    # (TpuRegView.matcher), so rows arrive in trie order — and the
    # region layout, hence S and the window geometry, depends on it
    trie = SubscriptionTrie()
    build_corpus(random.Random(42), N_SUBS, trie)
    for fw, key, opts in trie.entries():
        m.table.add(list(fw), key, opts)
    del trie
    with m.lock:
        m.sync()
    assert m._bucketed and m._operands is not None and m._meta is not None
    return m


def _prep(m, n, align=0, live=None):
    """(args, statics) of a batch of ``n`` publishes — shapes depend on
    the padded batch and the table geometry only, not on the topics.
    ``live``: the table's own counts of live rows in region 0 and the
    g-buckets unless given (they decide which phases the program holds)."""
    topics = [("warmup", "ladder", str(i)) for i in range(n)]
    pw, pl, pd, pb, gb = m._encode_batch_ex(topics)
    S = int(m._dev_arrays[0].shape[0])
    args, statics, _left = m._flat_prep(
        m._reg_start, m._reg_end, m._glob_pad, live or m._live,
        m._ops_bits, S, pw, pl, pd, pb, gb, n, align=align)
    return args, statics


def _sds(x, sharding):
    import jax

    x = np.asarray(x) if not hasattr(x, "dtype") else x
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)


def _table_sds(m, sharding):
    F_t, t1 = m._operands
    return (_sds(F_t, sharding), _sds(t1, sharding),
            _sds(m._meta, sharding))


def _total_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes + ma.generated_code_size_in_bytes)


_COMPILED = {}  # a program is compiled once for the tests that read it


def _compile_packed(m, one_chip, n, live=None):
    from vernemq_tpu.ops import match_kernel as K

    if ("packed", n, live) not in _COMPILED:
        args, statics = _prep(m, n, live=live)
        packed = K.flat_pack_args(args)
        _COMPILED["packed", n, live] = \
            K.match_extract_windowed_flat_packed.lower(
                *_table_sds(m, one_chip), _sds(packed, one_chip),
                **K._packed_geometry(args), **statics).compile()
    return _COMPILED["packed", n, live]


@pytest.mark.parametrize("n", [4096, 9], ids=["B4096", "Bmin"])
def test_packed_match_compiles(matcher, one_chip, n):
    """The default path: what ``K.call_packed`` runs,
    at the collector's full window and at the smallest flush the device
    serves (``tpu_host_batch_threshold=8`` → 9 pubs → Bpad 16)."""
    compiled = _compile_packed(matcher, one_chip, n)
    assert _total_bytes(compiled) < HBM_BYTES


def test_match_many_compiles(matcher, one_chip):
    """The K-window super-batch (``K.match_many``: scanned executable,
    donated staging) at the default ``tpu_super_batch_k``."""
    from vernemq_tpu.broker.config import DEFAULTS
    from vernemq_tpu.ops import match_kernel as K

    k_windows = DEFAULTS["tpu_super_batch_k"]
    args, statics = _prep(matcher, 4096)
    vecs = np.stack([K.flat_pack_args(args)] * k_windows)
    with warnings.catch_warnings():
        # donation is a free-at-dispatch hint here (see call_match_many)
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        compiled = K.match_many.lower(
            *_table_sds(matcher, one_chip), _sds(vecs, one_chip),
            **K._packed_geometry(args), **statics).compile()
    assert _total_bytes(compiled) < HBM_BYTES


def _compile_wide(m, one_chip, U):
    from vernemq_tpu.ops import match_kernel as K

    if ("wide", U) not in _COMPILED:
        S, L = int(m._dev_arrays[0].shape[0]), m.table.L
        statics = m._wide_statics(S, m._glob_pad, m._reg_start,
                                  m._reg_end, m._live, m._ops_bits)
        z = np.zeros((U, 3), np.int64)
        packed = K.wide_pack_args(np.zeros((U, L), np.int32),
                                  np.zeros(U, np.int32),
                                  np.zeros(U, np.int32), z, z)
        _COMPILED["wide", U] = K.wide_mask_packed.lower(
            *_table_sds(m, one_chip), _sds(packed, one_chip),
            U=U, L=L, **statics).compile()
    return _COMPILED["wide", U]


def test_wide_mask_compiles(matcher, one_chip):
    """The wide pass (``K.call_wide``: the whole bit mask of a publish's
    regions, for what the flat form's caps cut off) at both of its rungs
    and the 1M table's widest windows."""
    from vernemq_tpu.models.tpu_matcher import WIDE_RUNGS

    for U in WIDE_RUNGS:
        assert _total_bytes(_compile_wide(matcher, one_chip, U)) < HBM_BYTES


D_TOP = 128  # the top of the pre-warmed delta ladder (tpu_delta_warm_max)


def _delta_rows(m):
    """Host operands of a Dpad=128 delta in ``delta_pack_args``' order:
    slots, words [D, L], eff_len, and the three flag vectors."""
    z, zb = np.zeros(D_TOP, np.int32), np.zeros(D_TOP, bool)
    return (z, np.zeros((D_TOP, m.table.words.shape[1]), np.int32), z,
            zb, zb, zb)


def _compile_delta(m, one_chip):
    from vernemq_tpu.ops import match_kernel as K

    if "delta" not in _COMPILED:
        packed = K.delta_pack_args(*_delta_rows(m))
        _COMPILED["delta"] = K.apply_delta_fused.lower(
            *(_sds(a, one_chip) for a in m._dev_arrays),
            *_table_sds(m, one_chip), _sds(packed, one_chip),
            D=D_TOP, L=m.table.words.shape[1],
            id_bits=m._ops_bits).compile()
    return _COMPILED["delta"]


def test_delta_scatter_compiles(matcher, one_chip):
    """The SUBSCRIBE/UNSUBSCRIBE write-through (the donating fused
    scatter ``_apply_delta_device_inner`` picks) at the top of the
    pre-warmed ladder, Dpad=128."""
    assert _total_bytes(_compile_delta(matcher, one_chip)) < HBM_BYTES


_FLAT = ("unpack_transport", "flat_combine", "probe_a")


@pytest.mark.parametrize("program,live,scopes,absent", [
    # the 1M corpus holds ``+/w/w`` filters and none with both first
    # levels wild, so its own program is probe A + probe B; the forms
    # around it are compiled from other counts on the same geometry
    ("packed", None, _FLAT + ("probe_b",), ("dense_region0",)),
    ("packed", (0, 0), _FLAT, ("dense_region0", "probe_b")),
    ("packed", (1, 1), _FLAT + ("dense_region0", "probe_b"), ()),
    ("delta", None, ("delta_scatter",), ()),
    ("wide", None, ("wide_mask",), ())],
    ids=["packed", "packed_a", "packed_gab", "delta", "wide"])
def test_device_programs_name_their_phases(matcher, one_chip, program, live,
                                           scopes, absent):
    """``jax.named_scope`` reaches the v5e's compiled program: every phase
    of the match and the delta scatter is the ``op_name`` of instructions
    that survived optimisation, which is where a device trace's
    operations are attributed from (``benchmark/trace/spans.py``) — and a
    phase whose rows hold nothing live is not in the program at all."""
    assert matcher._live[0] == 0 < matcher._live[1]
    compiled = {
        "packed": lambda: _compile_packed(matcher, one_chip, 9, live),
        "delta": lambda: _compile_delta(matcher, one_chip),
        "wide": lambda: _compile_wide(matcher, one_chip, 8)}[program]()
    text = compiled.as_text()
    for scope in scopes:
        assert f"/{scope}/" in text, scope
    for scope in absent:
        assert f"/{scope}/" not in text, scope


def test_pallas_match_compiles(matcher, one_chip):
    """The Pallas tile matcher through Mosaic (``interpret=False``) —
    interpret mode, which every other Pallas test uses, cannot see what
    the chip's compiler refuses."""
    from vernemq_tpu.ops import pallas_match as P

    m = matcher
    args, statics = _prep(m, 4096, align=P.SEG_BLK)
    F_t, t1 = m._operands
    table = (F_t, t1) + tuple(m._dev_arrays[1:5])
    compiled = P.match_extract_windowed_flat_pallas.lower(
        *(_sds(a, one_chip) for a in table),
        *(_sds(a, one_chip) for a in args),
        **statics, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _total_bytes(compiled) < HBM_BYTES


def test_mesh_match_compiles_sharded(matcher, topo):
    """The mesh-native matcher's program (``tpu_mesh="1x4"``) over the
    four described devices: compiles, and each device holds about a
    quarter of the table — nothing put the whole of it on one chip."""
    from jax.sharding import NamedSharding

    from vernemq_tpu.parallel.mesh import (MATCHER_PARTITION_RULES,
                                           MATCHER_STATE_NAMES, make_mesh,
                                           match_partition_rules)
    from vernemq_tpu.parallel.mesh_match import MeshMatcher

    m = matcher
    t = m.table
    mesh = make_mesh(topo.devices, batch=1)
    swm = MeshMatcher(t, mesh, max_fanout=m.max_fanout,
                      flat_avg=m.flat_avg, merge=True)
    S, glob = t.cap, t.gb_end
    pinned = {"S": S, "glob": glob, "bits": t.id_bits, "dev": None,
              "reg_start": t.reg_start.copy(),
              "reg_end": (t.reg_start + t.reg_cap).copy(), "ng": t.NG}
    n = 4096
    topics = [("warmup", "ladder", str(i)) for i in range(n)]
    pw, pl, pd, pb, _gb = m._encode_batch_ex(topics)
    p = swm._prep_encoded(pw, pl, pd, pb, n, pinned=pinned)
    fn = swm._fn_for(*p["geom"], glob=glob, S=S, bits=t.id_bits)

    F_t, t1 = m._operands
    full = dict(zip(MATCHER_STATE_NAMES[:6],
                    (F_t, t1) + tuple(m._dev_arrays[1:5])))
    named = dict(full)
    for name, a in full.items():
        named["g/" + name] = a[:, :glob] if a.ndim == 2 else a[:glob]
    specs = match_partition_rules(MATCHER_PARTITION_RULES, named)
    state = [_sds(named[nm], NamedSharding(mesh, specs[nm]))
             for nm in MATCHER_STATE_NAMES]
    # per-dispatch operands enter unsharded (host arrays): the kernel's
    # own in_specs shard them
    from jax.sharding import PartitionSpec

    rep = NamedSharding(mesh, PartitionSpec())
    compiled = fn.lower(*state, *(_sds(a, rep) for a in p["args"])).compile()
    table_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                      for a in full.values())
    per_device = compiled.memory_analysis().argument_size_in_bytes
    assert per_device < 0.5 * table_bytes, (per_device, table_bytes)
    assert _total_bytes(compiled) < HBM_BYTES


# ------------------------------------------------------------------------
# The other programs the broker can dispatch. Each case DRIVES the seat
# that dispatches the program once on the CPU backend, at the knobs the
# broker hands it, records the call (argument shapes, dtypes, static
# keywords), and asks the v5e's compiler for exactly that signature.

def _record_call(monkeypatch, module, name, drive):
    """``(args, kwargs)`` of the call ``drive()`` makes to
    ``module.name`` that moved most bytes (a seat may compile a small
    shape first)."""
    from tests.test_tpu_match import spy_kernel_call

    calls = spy_kernel_call(monkeypatch, name, module)
    try:
        drive()
    finally:
        monkeypatch.undo()
    assert calls, f"{name} was not dispatched"
    args, kwargs, _out = max(calls, key=lambda c: sum(
        int(np.prod(np.shape(a))) for a in c[0]))
    return module, args, kwargs


def _unbucketed_call(monkeypatch, _m, name):
    """The full-scan path of a table under the bucketing size
    (``TpuMatcher._match_batch_phased``, ``bucketed`` false) at the
    collector's full window: the VPU scan at the default
    ``tpu_initial_capacity``, the MXU scan at the largest unbucketed
    table."""
    from vernemq_tpu.broker.config import DEFAULTS
    from vernemq_tpu.models.tpu_matcher import TpuMatcher
    from vernemq_tpu.ops import match_kernel as K

    capacity = {"match_extract": DEFAULTS["tpu_initial_capacity"],
                "match_extract_mxu": 4096}[name]
    m = TpuMatcher(initial_capacity=capacity,
                   max_fanout=DEFAULTS["tpu_max_fanout"],
                   flat_avg=DEFAULTS["tpu_flat_avg"])
    assert not m.table.bucketed
    for i in range(64):
        m.table.add(["fleet", f"dev{i}", "+"], i, None)
    topics = [("fleet", f"dev{i % 64}", "temp") for i in range(4096)]
    return _record_call(monkeypatch, K, name, lambda: m.match_batch(topics))


def _unfused_delta_call(_monkeypatch, m, name):
    """The delta of a table without coded operands (``id_bits`` 0, a
    vocabulary past 24 bits: ``_apply_delta_device_inner``'s last
    branch) at the 1M table's geometry, Dpad=128."""
    from vernemq_tpu.ops import match_kernel as K

    slots, words, eff, hh, fw, ac = _delta_rows(m)
    if name == "apply_delta":
        return K, (*m._dev_arrays, slots, words, eff, hh, fw, ac), {}
    return K, (m._meta, slots, eff, hh, fw, ac), {}


def _retained_call(monkeypatch, _m, name):
    """``RetainedIndex`` at the ``tpu_retained_*`` defaults over 20,000
    retained topics, a full replay batch of filters, in the posture it
    takes off the CPU (coded dense phase on the device, ``k`` =
    max_fanout)."""
    from vernemq_tpu.broker.config import DEFAULTS
    from vernemq_tpu.broker.retain import RetainStore
    from vernemq_tpu.ops import reverse_kernel as RK
    from vernemq_tpu.retained.index import RetainedIndex

    holder = {}
    store = RetainStore(
        on_dirty=lambda mp, t, v: holder["idx"].on_retain(t, v))
    idx = holder["idx"] = RetainedIndex(
        store, initial_capacity=DEFAULTS["tpu_retained_initial_capacity"],
        max_fanout=DEFAULTS["tpu_retained_max_fanout"])
    idx.async_rebuild = False
    idx.dense_policy, idx.dense_mode = "device", "coded"
    idx.extract_k = idx.max_fanout
    rng = random.Random(3)
    for i in range(20_000):
        store.insert("", (f"site{rng.randrange(64)}",
                          f"dev{rng.randrange(512)}", f"m{i % 16}"), i)
    n = DEFAULTS["tpu_retained_max_batch"]
    filters = [(f"site{i % 64}", "+", f"m{i % 16}") if i % 4
               else ("+", f"dev{i % 512}", "#") for i in range(n)]
    return _record_call(monkeypatch, RK, name,
                        lambda: idx.match_filters(filters))


def _predicate_call(monkeypatch, _m, name):
    """``FilterEngine._dispatch`` over a collector window of 4,096
    publishes against sixteen predicate subscriptions (and, for
    ``predicate_phase``, a windowed aggregate)."""
    import json

    from vernemq_tpu.cluster.metadata import MetadataStore
    from vernemq_tpu.filters.engine import FilterEngine
    from vernemq_tpu.filters.schema_registry import SchemaRegistry
    from vernemq_tpu.ops import predicate_kernel as PK
    from vernemq_tpu.protocol.types import SubOpts

    reg = SchemaRegistry(MetadataStore("n1"), "n1")
    reg.set_schema("", "s/+/t", "value:number,unit:enum(c|f)")
    eng = FilterEngine(reg, device_gate=lambda: True)
    eng.emit = lambda *a: None
    exprs = [f"$gt(value,{10 * i})" for i in range(8)] + \
            [f"$range(value,{i},{i + 50})" for i in range(8)]
    if name == "predicate_phase":
        exprs.append("$avg(value,64)")
    rows = []
    for i, expr in enumerate(exprs):
        o = SubOpts()
        o.filter_expr = expr
        eng.on_sub_delta("add", "", o)
        rows.append((("s", "+", "t"), ("", f"c{i}"), o))
    topic = ("s", "a", "t")
    items = [(topic, eng.encode("", topic, json.dumps(
        {"value": i % 100, "unit": "c"}).encode())) for i in range(4096)]
    results = [list(rows) for _ in items]
    return _record_call(monkeypatch, PK, name,
                        lambda: eng.filter_batch("", items, results))


@pytest.mark.parametrize("name,build", [
    ("match_extract", _unbucketed_call),
    ("match_extract_mxu", _unbucketed_call),
    ("apply_delta", _unfused_delta_call),
    ("apply_delta_meta", _unfused_delta_call),
    ("reverse_match", _retained_call),
    ("eval_pairs", _predicate_call),
    ("predicate_phase", _predicate_call),
], ids=lambda v: v if isinstance(v, str) else "")
def test_dispatchable_program_compiles(matcher, one_chip, monkeypatch,
                                       name, build):
    """Every program the broker can dispatch beside the main path's
    compiles for the v5e at the signature its seat builds, and fits."""
    module, args, kwargs = build(monkeypatch, matcher, name)
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        compiled = getattr(module, name).lower(
            *(_sds(a, one_chip) for a in args), **kwargs).compile()
    assert _total_bytes(compiled) < HBM_BYTES


def test_sharded_delta_scatter_compiles(matcher, topo):
    """``apply_delta_windowed_fused`` — the sharded seats' ONE fused
    delta scatter (``ShardedWindowedMatcher._sync_delta``) — over the
    four described devices with the shardings ``ShardedTpuMatcher.
    _build_device`` places: operands and metadata split over 'sub', the
    dense g-zone mirrors replicated; Dpad=128."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from vernemq_tpu.ops import match_kernel as K
    from vernemq_tpu.parallel.mesh import make_mesh

    m = matcher
    mesh = make_mesh(topo.devices, batch=1)
    sF, s1 = NamedSharding(mesh, P(None, "sub")), NamedSharding(mesh, P("sub"))
    rep2, rep1 = NamedSharding(mesh, P(None, None)), NamedSharding(mesh, P(None))
    glob = m.table.gb_end
    full = tuple(m._operands) + tuple(m._dev_arrays[1:5])
    state = [_sds(a, sF if a.ndim == 2 else s1) for a in full] + [
        _sds(a[:, :glob] if a.ndim == 2 else a[:glob],
             rep2 if a.ndim == 2 else rep1) for a in full]
    compiled = K.apply_delta_windowed_fused.lower(
        *state, _sds(K.delta_pack_args(*_delta_rows(m)), rep1), D=D_TOP,
        L=m.table.words.shape[1], id_bits=m._ops_bits, glob=glob).compile()
    assert _total_bytes(compiled) < HBM_BYTES
