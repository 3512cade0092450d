"""Pallas window-tile matcher — the fused-VMEM variant of the production
windowed match path (``match_kernel.match_extract_windowed_flat``).

Why a hand-written kernel when XLA already fuses the coded matmul into the
bit-pack (``match_kernel._window_tiles_sel``)? Two measured failure modes
of the XLA path on this hardware (see the docstrings there):

1. The ``[TP, seg]`` f32 mismatch intermediate *must* fuse through the
   ``_pack_mask`` reshape or it materialises in HBM (up to 256MB at the
   SEG_CAP geometry) — and past certain shapes that fusion OOMs the
   compile outright. Pallas makes the constraint structural: the grid
   walks ``SEG_BLK``-column chunks of each window, the mismatch block
   lives in VMEM, and only the 16x-smaller packed words are written out.
2. Per-tile ``dynamic_slice`` of six table arrays costs a gather-shaped
   HBM read per tile. Here the window walk is the grid itself: the
   scalar-prefetched window starts drive the BlockSpec index maps, so
   Mosaic double-buffers the streamed F/t1/meta blocks while the MXU
   works (the idiomatic Pallas pipeline pattern).

The kernel fuses, per (tile, chunk) grid step: coded matmul (MXU,
bf16-exact — operand construction unchanged from
``match_kernel.build_operands``), the length/$/liveness epilogue, the
probe row-split, and bit-packing. Packing avoids in-kernel minor-axis
reshapes (hostile on TPU lane layouts) by computing each 16-bit pack word
as an exact bf16 matmul against a banded power-of-two weight matrix:
products are powers of two ≤ 2^15 and 16-term f32 sums < 2^16 — exact.
The two uint16 halves combine into the uint32 words that
``extract_indices_packed`` consumes, outside the kernel.

Windows must start on ``SEG_BLK`` boundaries (BlockSpec index maps select
whole blocks): ``tpu_matcher.prepare_windows(align=SEG_BLK)`` floors each
window start, and ``window_params(align=SEG_BLK)`` widens ``seg_max`` by
one block so flooring never strands a region group (leftover pubs would
fall to the exact host path — correct but slow).

Correctness is exercised on every backend via interpret mode (the module
self-selects ``interpret=True`` off-TPU); that the real lowering compiles
is checked against the described chip in ``tests/test_tpu_compile.py``.
A lowering failure propagates — nothing swaps in the XLA kernel.

Reference seam: this is still ``vmq_reg_trie.erl:358-383`` (the per-level
trie walk) recast as dense linear algebra; the tile/window decomposition
mirrors the first-two-edge narrowing described in models/tpu_table.py.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import match_kernel as K

SEG_BLK = 2048  # window chunk walked per grid step (and start alignment)


def use_interpret() -> bool:
    """Interpret mode everywhere except a TPU backend (CPU tests and the
    virtual multichip mesh run the same kernel semantics in pure JAX)."""
    return jax.devices()[0].platform != "tpu"


def _tile_kernel(glob_pad: int, wild_rows: bool, TP: int):
    """Build the kernel body (static geometry closed over)."""

    def kernel(start_ref, F_ref, t1_ref, eff_ref, flags_ref, G_ref,
               plt_ref, pdt_ref, out_ref):
        t = pl.program_id(0)
        c = pl.program_id(1)
        G = G_ref[0]                    # [TP, K] bf16
        F = F_ref[:]                    # [K, SEG_BLK] bf16
        mm = lax.dot_general(
            G, F, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) + t1_ref[:]                   # [TP, SEG_BLK] via [1, SEG_BLK]
        # Mosaic will not broadcast or truncate i1 vectors at the
        # [TP, SEG_BLK] shape, so every per-row / per-pub condition is
        # carried as int32 0/1, broadcast by integer arithmetic, and
        # compared once, at full shape, at the end.
        eff = eff_ref[:]                # [1, SEG_BLK] int32
        flags = flags_ref[:]
        hh = flags & 1
        fw = (flags >> 1) & 1
        act = (flags >> 2) & 1
        plen = plt_ref[0]               # [TP, 1] int32
        pd = pdt_ref[0]                 # [TP, 1] int32 0/1
        # region 0 is matched by the dense phase; guard the window's
        # overlap with it (windows are clamped into [row_lo, S))
        rows = (start_ref[t] + c) * SEG_BLK + lax.broadcasted_iota(
            jnp.int32, (1, SEG_BLK), 1)
        row_ok = jnp.where(rows >= glob_pad, 1, 0)
        # probe split: A-windows match concrete-first rows only,
        # B-windows wildcard-first rows only (no double counting)
        col_bad = 1 - act * row_ok * (fw if wild_rows else 1 - fw)
        # length rule: '#' rows take any publish at least as long, the
        # rest an exact length
        diff = plen - eff               # [TP, SEG_BLK] int32
        len_bad = jnp.where(diff == 0, 0, jnp.where(diff > 0, 1 - hh, 1))
        bad = len_bad + pd * fw + col_bad
        m = (mm == 0.0) & (bad == 0)
        # pack 16 mask columns per output word: banded weight matrix of
        # powers of two, bf16-exact products, f32 sums < 2^16 — exact
        i = lax.broadcasted_iota(jnp.int32, (SEG_BLK, SEG_BLK // 16), 0)
        j = lax.broadcasted_iota(jnp.int32, (SEG_BLK, SEG_BLK // 16), 1)
        W = jnp.where(i // 16 == j, 1 << (i % 16), 0).astype(jnp.bfloat16)
        packed = lax.dot_general(
            jnp.where(m, 1.0, 0.0).astype(jnp.bfloat16), W,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        out_ref[0] = packed.astype(jnp.int32)

    return kernel


def window_tiles_packed(F_t, t1_2d, eff_2d, flags_2d, Gt, plt, pdt,
                        start_blk, *, seg_max: int, glob_pad: int,
                        wild_rows: bool, interpret: bool) -> jax.Array:
    """Run the fused tile matcher: returns packed16 [T, TP, seg_max//16]
    int32 (each word holds 16 mask bits of its window chunk)."""
    Kd, _S = F_t.shape
    T, TP, _ = Gt.shape
    NC = seg_max // SEG_BLK
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(T, NC),
        in_specs=[
            pl.BlockSpec((Kd, SEG_BLK),
                         lambda t, c, s: (0, s[t] + c)),
            pl.BlockSpec((1, SEG_BLK), lambda t, c, s: (0, s[t] + c)),
            pl.BlockSpec((1, SEG_BLK), lambda t, c, s: (0, s[t] + c)),
            pl.BlockSpec((1, SEG_BLK), lambda t, c, s: (0, s[t] + c)),
            pl.BlockSpec((1, TP, Kd), lambda t, c, s: (t, 0, 0)),
            pl.BlockSpec((1, TP, 1), lambda t, c, s: (t, 0, 0)),
            pl.BlockSpec((1, TP, 1), lambda t, c, s: (t, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, TP, SEG_BLK // 16),
                               lambda t, c, s: (t, 0, c)),
    )
    return pl.pallas_call(
        _tile_kernel(glob_pad, wild_rows, TP),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, TP, seg_max // 16), jnp.int32),
        interpret=interpret,
    )(start_blk, F_t, t1_2d, eff_2d, flags_2d, Gt, plt, pdt)


def _probe_pallas(F_t, t1, sub_eff_len, flags, pub_words, pub_len,
                  pub_dollar, t_sel, t_start, *, id_bits, k, seg_max,
                  glob_pad, wild_rows, interpret):
    """One probe (A or B) through the Pallas tile matcher; same contract
    as the XLA ``_window_tiles_sel``: ``(tidx [T,TP,k] absolute slot ids,
    tvalid, tcount)``. Tile pub rows are gathered device-side from the
    [T, TP] selectors (as in the XLA path); extraction runs once, batched
    over all T·TP rows, instead of per tile."""
    Kd = F_t.shape[0]
    T, TP = t_sel.shape
    G_all = K.build_pub_operand(pub_words, id_bits)          # [B, K]
    flat_sel = t_sel.reshape(-1)
    Gt = jnp.take(G_all, flat_sel, axis=0).reshape(T, TP, Kd)
    plt = jnp.take(pub_len, flat_sel).reshape(T, TP, 1)
    pdt = jnp.take(pub_dollar.astype(jnp.int32),
                   flat_sel).reshape(T, TP, 1)
    packed16 = window_tiles_packed(
        F_t, t1.reshape(1, -1), sub_eff_len.reshape(1, -1), flags,
        Gt, plt, pdt, t_start // SEG_BLK,
        seg_max=seg_max, glob_pad=glob_pad, wild_rows=wild_rows,
        interpret=interpret)
    p = packed16.astype(jnp.uint32)
    p32 = p[..., 0::2] | (p[..., 1::2] << 16)   # [T, TP, seg/32]
    idx, valid, cnt = K.extract_indices_packed(
        p32.reshape(T * TP, -1), k, 2048)
    idx = idx.reshape(T, TP, k) + t_start[:, None, None]
    return idx, valid.reshape(T, TP, k), cnt.reshape(T, TP)


@functools.partial(jax.jit,
                   static_argnames=("id_bits", "k", "glob_pad", "seg_max",
                                    "seg2_max", "gc", "C", "interpret"))
def match_extract_windowed_flat_pallas(
    F_t: jax.Array, t1: jax.Array, sub_eff_len: jax.Array,
    has_hash: jax.Array, first_wild: jax.Array, active: jax.Array,
    pub_words: jax.Array, pub_len: jax.Array, pub_dollar: jax.Array,
    n_real: jax.Array,
    t_sel: jax.Array, t_start: jax.Array,
    t2_sel: jax.Array, t2_start: jax.Array,
    a_tile: jax.Array, a_pos: jax.Array,
    b_tile: jax.Array, b_pos: jax.Array,
    *, id_bits: int, k: int, glob_pad: int, seg_max: int, seg2_max: int,
    gc: int, C: int, interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Drop-in for :func:`match_kernel.match_extract_windowed_flat` with
    the probe phases on the Pallas tile matcher (same dense phase, same
    flat compaction, same return contract). Callers must prep windows
    with ``align=SEG_BLK`` so every ``t_start`` is block-aligned."""
    B = pub_words.shape[0]
    real = jnp.arange(B, dtype=jnp.int32) < n_real

    parts = []  # of the phases compiled in (``gc`` / ``seg2_max`` 0: out)
    if gc:
        parts.append(K._dense_region0(
            F_t, t1, sub_eff_len, has_hash, first_wild, active,
            pub_words, pub_len, pub_dollar, id_bits=id_bits, k=k,
            glob_pad=glob_pad, gc=gc))

    flags = (has_hash.astype(jnp.int32)
             | (first_wild.astype(jnp.int32) << 1)
             | (active.astype(jnp.int32) << 2)).reshape(1, -1)
    tidx, tvalid, tcount = _probe_pallas(
        F_t, t1, sub_eff_len, flags, pub_words, pub_len, pub_dollar,
        t_sel, t_start, id_bits=id_bits, k=k, seg_max=seg_max,
        glob_pad=glob_pad, wild_rows=False, interpret=interpret)
    parts.append(K._gather_parts(tidx, tvalid, tcount, a_tile, a_pos))
    if seg2_max:
        t2idx, t2valid, t2count = _probe_pallas(
            F_t, t1, sub_eff_len, flags, pub_words, pub_len, pub_dollar,
            t2_sel, t2_start, id_bits=id_bits, k=k, seg_max=seg2_max,
            glob_pad=glob_pad, wild_rows=True, interpret=interpret)
        parts.append(
            K._gather_parts(t2idx, t2valid, t2count, b_tile, b_pos))
    return K._flat_combine(real, k, C, parts)
