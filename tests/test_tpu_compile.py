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
for the ``bench.build_corpus`` mix at 1,000,000 subscriptions with
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
    from bench import build_corpus
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


def _prep(m, n, align=0):
    """(args, statics) of a batch of ``n`` publishes — shapes depend on
    the padded batch and the table geometry only, not on the topics."""
    topics = [("warmup", "ladder", str(i)) for i in range(n)]
    pw, pl, pd, pb, gb = m._encode_batch_ex(topics)
    S = int(m._dev_arrays[0].shape[0])
    args, statics, _left = m._flat_prep(
        m._reg_start, m._reg_end, m._glob_pad, m._ops_bits, S,
        pw, pl, pd, pb, gb, n, align=align)
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


def _compile_packed(m, one_chip, n):
    from vernemq_tpu.ops import match_kernel as K

    if ("packed", n) not in _COMPILED:
        args, statics = _prep(m, n)
        packed = K.flat_pack_args(args)
        _COMPILED["packed", n] = \
            K.match_extract_windowed_flat_packed.lower(
                *_table_sds(m, one_chip), _sds(packed, one_chip),
                **K._packed_geometry(args), **statics).compile()
    return _COMPILED["packed", n]


@pytest.mark.parametrize("n", [4096, 9], ids=["B4096", "Bmin"])
def test_packed_match_compiles(matcher, one_chip, n):
    """The default path (``tpu_packed_io``): what ``K.call_packed`` runs,
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


def _compile_delta(m, one_chip):
    from vernemq_tpu.ops import match_kernel as K

    if "delta" not in _COMPILED:
        D = 128
        L = m.table.words.shape[1]
        z = np.zeros(D, np.int32)
        zb = np.zeros(D, bool)
        packed = K.delta_pack_args(z, np.zeros((D, L), np.int32), z,
                                   zb, zb, zb)
        _COMPILED["delta"] = K.apply_delta_fused.lower(
            *(_sds(a, one_chip) for a in m._dev_arrays),
            *_table_sds(m, one_chip), _sds(packed, one_chip),
            D=D, L=L, id_bits=m._ops_bits).compile()
    return _COMPILED["delta"]


def test_delta_scatter_compiles(matcher, one_chip):
    """The SUBSCRIBE/UNSUBSCRIBE write-through (the donating fused
    scatter ``_apply_delta_device_inner`` picks) at the top of the
    pre-warmed ladder, Dpad=128."""
    assert _total_bytes(_compile_delta(matcher, one_chip)) < HBM_BYTES


@pytest.mark.parametrize("program,scopes", [
    ("packed", ("unpack_transport", "dense_region0", "probe_a", "probe_b",
                "flat_combine")),
    ("delta", ("delta_scatter",))])
def test_device_programs_name_their_phases(matcher, one_chip, program,
                                           scopes):
    """``jax.named_scope`` reaches the v5e's compiled program: every phase
    of the match and the delta scatter is the ``op_name`` of instructions
    that survived optimisation, which is where a device trace's
    operations are attributed from (``benchmark/trace/spans.py``)."""
    compiled = (_compile_packed(matcher, one_chip, 9)
                if program == "packed" else _compile_delta(matcher,
                                                           one_chip))
    text = compiled.as_text()
    for scope in scopes:
        assert f"/{scope}/" in text, scope


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
