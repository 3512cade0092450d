"""Batched wildcard topic matching as dense JAX ops — the TPU replacement
for the per-publish ETS trie walk (``vmq_reg_trie.erl:358-383``).

Representation (SURVEY.md §7.1 step 4): subscriptions live in HBM as padded
segment arrays over interned word ids —

- ``sub_words`` int32 [S, L]: word ids, ``PLUS_ID`` for ``+``, ``HASH_ID``
  for ``#``, ``PAD_ID`` beyond the filter length;
- ``sub_eff_len`` int32 [S]: number of *concrete* levels (excludes a
  trailing ``#``);
- ``has_hash`` bool [S]: filter ends in ``#``;
- ``first_wild`` bool [S]: level-0 word is a wildcard (for MQTT-4.7.2-1);
- ``active`` bool [S]: slot liveness (unsubscribed slots stay allocated).

A batch of publishes is matched in one device call: a filter matches iff
every concrete level equals the publish word or is ``+``, and the length
constraint holds (``== eff_len`` without ``#``, ``>= eff_len`` with — a
trailing ``#`` also matches its parent level), and the ``$``-rule holds.
This is exactly ``vmq_topic.erl:53-66`` + ``vmq_reg_trie.erl:283-288``
vectorised over [B, S].

The level loop runs as ``lax.fori_loop`` carrying a [B, S] accumulator so
the [B, S, L] comparison tensor is never materialised; XLA fuses the
per-level compare+and into one pass over the subscription table (HBM-bound:
~S*L*4 bytes read per batch). Publish batches are chunked by the caller to
bound the [B, S] working set.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

PAD_ID = 0
PLUS_ID = 1
HASH_ID = 2
FIRST_WORD_ID = 3  # real words intern from here


def match_mask_unrolled(
    sub_words, sub_eff_len, has_hash, first_wild, active,
    pub_words, pub_len, pub_dollar,
) -> jax.Array:
    """Boolean match matrix [B, S], the level loop statically unrolled:
    one fused elementwise pass over [B, S] (XLA cannot fuse across
    ``fori_loop`` iterations) that also fuses into downstream
    reductions."""
    L = sub_words.shape[1]
    len_ok = jnp.where(
        has_hash[None, :],
        pub_len[:, None] >= sub_eff_len[None, :],
        pub_len[:, None] == sub_eff_len[None, :],
    )
    acc = len_ok & (~(pub_dollar[:, None] & first_wild[None, :])) & active[None, :]
    for l in range(L):
        ok_l = (
            (sub_words[:, l][None, :] == pub_words[:, l][:, None])
            | (sub_words[:, l] == PLUS_ID)[None, :]
            | (l >= sub_eff_len)[None, :]
        )
        acc = acc & ok_l
    return acc


def extract_indices(
    mask: jax.Array, k: int, block: int = 512
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Exact sort-free compaction of a [B, S] boolean mask into the first
    ``k`` matched indices per row.

    ``lax.top_k`` over [B, 1M] costs seconds on TPU; this is the
    bandwidth-shaped replacement: per-block match counts → cumulative block
    offsets → for each output position j, binary-search the block containing
    the j-th match, gather just that 512-wide block, and locate the match
    with an intra-block rank compare. O(B·S) streaming + O(B·k·block)
    gather — no sort anywhere.

    Returns (idx [B,k] int32, valid [B,k] bool, count [B] int32).
    """
    B, S = mask.shape
    nblk = S // block
    m = mask.reshape(B, nblk, block)
    blk_cnt = jnp.sum(m, axis=2, dtype=jnp.int32)  # [B, nblk]
    blk_cum = jnp.cumsum(blk_cnt, axis=1)  # inclusive
    count = blk_cum[:, -1]
    targets = jnp.broadcast_to(
        jnp.arange(k, dtype=jnp.int32)[None, :], (B, k)
    )  # j-th match per row
    # block holding the j-th match: first blk with cum > j, computed as a
    # compare-reduce (#blocks with cum <= j) — vmap'd searchsorted costs
    # B·k dependent binary-search gathers, ~50ms at this shape on TPU;
    # the dense reduction fuses into one VPU pass
    blk = jnp.sum(
        (blk_cum[:, None, :] <= targets[:, :, None]).astype(jnp.int32),
        axis=2,
    )  # [B, k]
    blk_c = jnp.minimum(blk, nblk - 1)
    prev_cum = jnp.where(
        blk_c > 0,
        jnp.take_along_axis(blk_cum, jnp.maximum(blk_c - 1, 0), axis=1),
        0,
    )
    offset = targets - prev_cum  # rank of the match within its block
    gathered = jnp.take_along_axis(
        m, blk_c[:, :, None], axis=1
    )  # [B, k, block]
    wcum = jnp.cumsum(gathered.astype(jnp.int32), axis=2)  # [B, k, block]
    # position of the (offset+1)-th set bit: #entries with wcum <= offset
    pos = jnp.sum((wcum <= offset[:, :, None]).astype(jnp.int32), axis=2)
    idx = blk_c * block + jnp.minimum(pos, block - 1)
    valid = targets < count[:, None]
    return idx.astype(jnp.int32), valid, count


def _run_chunked(one, pub_words, pub_len, pub_dollar, chunk: int):
    """Apply ``one((pw, plen, pd)) -> (idx, valid, count)`` over the publish
    batch, optionally in ``chunk``-sized pieces via ``lax.map`` to bound the
    [B, S] working set (B must divide by ``chunk``). lax.map serialises the
    chunks — only worth it when [B, S] would not fit."""
    if chunk and pub_words.shape[0] > chunk:
        B = pub_words.shape[0]
        n = B // chunk
        idx, valid, count = lax.map(
            one,
            (
                pub_words.reshape(n, chunk, -1),
                pub_len.reshape(n, chunk),
                pub_dollar.reshape(n, chunk),
            ),
        )
        return idx.reshape(B, -1), valid.reshape(B, -1), count.reshape(B)
    return one((pub_words, pub_len, pub_dollar))


@functools.partial(jax.jit, static_argnames=("k", "chunk"))
def match_extract(
    sub_words: jax.Array,
    sub_eff_len: jax.Array,
    has_hash: jax.Array,
    first_wild: jax.Array,
    active: jax.Array,
    pub_words: jax.Array,
    pub_len: jax.Array,
    pub_dollar: jax.Array,
    k: int = 256,
    chunk: int = 0,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Full-scan match for an unbucketed table: unrolled fused mask +
    sort-free extraction. Returns ``(idx [B, k] int32, valid [B, k] bool,
    count [B] int32)``; ``count`` may exceed ``k`` (truncated fanout: the
    caller matches that publish on the host). ``chunk`` > 0 runs the
    batch in pieces of that size (B must divide by it)."""
    S = sub_words.shape[0]
    block = 512 if S % 512 == 0 and S >= 512 else S

    def one(args):
        pw, plen, pd = args
        m = match_mask_unrolled(sub_words, sub_eff_len, has_hash,
                                first_wild, active, pw, plen, pd)
        return extract_indices(m, k, block)

    return _run_chunked(one, pub_words, pub_len, pub_dollar, chunk)

def _pack_mask(mask: jax.Array) -> jax.Array:
    """[B, S] bool → [B, S/32] uint32 bit-pack. XLA fuses this into the
    mask computation, so the bool matrix never reaches HBM — 32x less
    write traffic than materialising [B, S] bytes."""
    B, S = mask.shape
    bits = mask.reshape(B, S // 32, 32).astype(jnp.uint32)
    weights = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))
    return jnp.sum(bits * weights[None, None, :], axis=2, dtype=jnp.uint32)


def extract_indices_packed(
    packed: jax.Array, k: int, block: int = 512,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Sort-free compaction over a bit-packed mask ([B, S/32] uint32).

    Same contract as :func:`extract_indices` but all bookkeeping runs on
    popcounts of the packed words: per-block counts → cumulative block
    offsets → locate the block of the j-th match by compare-reduce → rank
    the bit inside the block's words. The heavy [B, k, block]-bool gather
    of the unpacked path shrinks to [B, k, block/32] words, and both
    prefix sums run on the MXU (see inline notes — minor-axis reductions
    have hostile lane layouts on TPU).
    """
    B, W = packed.shape
    wpb = block // 32  # words per block
    nblk = W // wpb
    pc = lax.population_count(packed).astype(jnp.int32)  # [B, W]
    # cumulative block counts as ONE bf16 matmul against a prefix-indicator
    # matrix: cum[b, n] = Σ_w pc[b, w]·(w//wpb ≤ n). A reshape+sum over the
    # small trailing axis costs ~14ms at this shape (bad lane layout); the
    # MXU does it in ~1ms. Exact: pc ≤ 32 (bf16-exact), sums < 2^24 (fp32
    # accumulate).
    word_blk = jnp.arange(W, dtype=jnp.int32) // wpb
    prefix = (word_blk[:, None] <= jnp.arange(nblk, dtype=jnp.int32)[None, :])
    blk_cum = lax.dot_general(
        pc.astype(jnp.bfloat16), prefix.astype(jnp.bfloat16),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(jnp.int32)  # [B, nblk] inclusive cumulative counts
    count = blk_cum[:, -1]
    targets = jnp.broadcast_to(jnp.arange(k, dtype=jnp.int32)[None, :], (B, k))
    # compare-reduce instead of vmap'd searchsorted (see extract_indices)
    blk = jnp.sum(
        (blk_cum[:, None, :] <= targets[:, :, None]).astype(jnp.int32),
        axis=2,
    )
    blk_c = jnp.minimum(blk, nblk - 1)
    prev_cum = jnp.where(
        blk_c > 0,
        jnp.take_along_axis(blk_cum, jnp.maximum(blk_c - 1, 0), axis=1),
        0,
    )
    offset = targets - prev_cum  # rank of the target match in its block
    words = jnp.take_along_axis(
        packed.reshape(B, nblk, wpb), blk_c[:, :, None], axis=1
    )  # [B, k, wpb]
    wpc = lax.population_count(words).astype(jnp.int32)
    # inclusive per-word popcount prefix via triangular matmul (same layout
    # argument as blk_cum; wpc ≤ 32, prefix sums ≤ block — exact)
    tri = (jnp.arange(wpb, dtype=jnp.int32)[:, None]
           <= jnp.arange(wpb, dtype=jnp.int32)[None, :])
    wcum = lax.dot_general(
        wpc.reshape(B * k, wpb).astype(jnp.bfloat16), tri.astype(jnp.bfloat16),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(jnp.int32).reshape(B, k, wpb)
    widx = jnp.sum((wcum <= offset[:, :, None]).astype(jnp.int32), axis=2)
    widx_c = jnp.minimum(widx, wpb - 1)
    prior = jnp.where(
        widx_c > 0,
        jnp.squeeze(jnp.take_along_axis(
            wcum, jnp.maximum(widx_c - 1, 0)[:, :, None], axis=2), 2),
        0,
    )
    bit_rank = offset - prior  # rank of the bit inside its 32-bit word
    word = jnp.squeeze(
        jnp.take_along_axis(words, widx_c[:, :, None], axis=2), 2
    )  # [B, k] uint32
    # position p of the (bit_rank+1)-th set bit: the unique p with bit p set
    # and popcount(word & (2^p - 1)) == bit_rank
    p_range = jnp.arange(32, dtype=jnp.uint32)
    below = (jnp.uint32(1) << p_range) - jnp.uint32(1)  # [32]
    cnt_below = lax.population_count(
        word[:, :, None] & below[None, None, :]
    ).astype(jnp.int32)  # [B, k, 32]
    bit_set = ((word[:, :, None] >> p_range[None, None, :]) & 1).astype(jnp.int32)
    ind = (cnt_below == bit_rank[:, :, None]) & (bit_set == 1)
    pos_bit = jnp.sum(
        jnp.arange(32, dtype=jnp.int32)[None, None, :] * ind.astype(jnp.int32),
        axis=2,
    )
    idx = blk_c * block + widx_c * 32 + pos_bit
    valid = targets < count[:, None]
    return idx.astype(jnp.int32), valid, count


@functools.partial(jax.jit, static_argnames=("id_bits",))
def build_operands(
    sub_words: jax.Array,  # int32 [S, L]
    sub_eff_len: jax.Array,  # int32 [S]
    id_bits: int = 16,
) -> Tuple[jax.Array, jax.Array]:
    """Precompute the MXU match operands for a subscription table.

    A filter matches a publish iff every concrete level's word id equals
    the publish word id. With ids split into ``id_bits/8`` byte planes,
    ``mismatch = Σ_l w_l Σ_d (s_{l,d} − p_{l,d})² == 0`` is that equality
    (w_l = 0 on ``+`` levels and beyond eff_len). The quadratic expands so
    the whole [B, S] mismatch matrix is ONE matmul plus a per-sub scalar:

        mismatch = G(pub) @ F(sub)ᵀ + t1(sub)

    with F/G chosen so every bf16 operand is exact (representable as
    n·2^e, n < 256) and every product < 2^17 (fp32 accumulation exact):

      16-bit ids (K = 5L):  F = [2wc₀, 2wc₁, 65536w, 256w, w]
      24-bit ids (K = 6L):  F = [2wc₀, 2wc₁, 2wc₂, 65536w, 256w, w]
      both:                 G = [−p₀, (−p₁, −p₂,) q»16, (q»8)&255, q&255]
    where q = Σ_d p_d² < 2^18, so its base-256 planes are ≤ 2, ≤ 255,
    ≤ 255 — every one bf16-exact (a single »8 split would leave odd
    values > 256 in the top plane, which bf16 cannot represent).

    This replaces the 12L byte-split layout of the original matcher: the
    MXU pads the contraction dim to 128 either way, but F is the term the
    matmul streams from HBM every batch — 4L halves that traffic vs 6L
    and is 3x less than 12L. F is returned TRANSPOSED [K, S]: the minor
    dimension must be the long one or TPU lane padding would inflate
    [S, K<128] storage ~4x.

    Returns ``(F_t bf16 [K, S], t1 f32 [S])``.
    """
    S, L = sub_words.shape
    lvl = jnp.arange(L, dtype=jnp.int32)
    w = ((sub_words != PLUS_ID) & (lvl[None, :] < sub_eff_len[:, None]))
    wf = w.astype(jnp.float32)
    s = sub_words
    if id_bits == 16:
        planes = [(s & 255), ((s >> 8) & 255)]
    else:
        planes = [(s & 255), ((s >> 8) & 255), ((s >> 16) & 255)]
    splits = [65536.0, 256.0, 1.0]
    pf = [c.astype(jnp.float32) for c in planes]
    parts = [2.0 * wf * c for c in pf] + [m * wf for m in splits]
    F = jnp.concatenate(parts, axis=1)  # [S, K]
    t1 = sum(jnp.sum(wf * c * c, axis=1) for c in pf)  # Σ w·s² [S]
    return F.T.astype(jnp.bfloat16), t1


def build_pub_operand(pub_words: jax.Array, id_bits: int = 16) -> jax.Array:
    """G [B, K] bf16 for a publish batch (see :func:`build_operands`)."""
    p = pub_words
    if id_bits == 16:
        planes = [(p & 255), ((p >> 8) & 255)]
    else:
        planes = [(p & 255), ((p >> 8) & 255), ((p >> 16) & 255)]
    pf = [c.astype(jnp.float32) for c in planes]
    q = sum(c * c for c in planes)  # int32: < 2^18
    qparts = [(q >> 16).astype(jnp.float32),
              ((q >> 8) & 255).astype(jnp.float32),
              (q & 255).astype(jnp.float32)]
    G = jnp.concatenate([-c for c in pf] + qparts, axis=1)
    return G.astype(jnp.bfloat16)


def coded_mismatch(F_t: jax.Array, t1: jax.Array, G: jax.Array) -> jax.Array:
    """[B, S] f32 mismatch: 0 exactly where all concrete levels match."""
    mm = lax.dot_general(
        G, F_t, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return mm + t1[None, :]


def _mxu_mask(
    sub_words: jax.Array,   # int32 [S, L]
    sub_eff_len: jax.Array,
    has_hash: jax.Array,
    first_wild: jax.Array,
    active: jax.Array,
    pub_words: jax.Array,   # int32 [B, L]
    pub_len: jax.Array,
    pub_dollar: jax.Array,
) -> jax.Array:
    """Match mask computed on the MXU instead of the VPU.

    A filter matches iff every *concrete* level equals the publish word —
    i.e. ``Σ_l w_l·(s_l − p_l)² == 0`` with weight ``w_l = 0`` on ``+``
    levels and beyond ``eff_len``. The squared distance expands into three
    matmul-shaped terms:

        Σ w·s²  (per-sub scalar)  −2·(w·s)@p  +  w@(p²)

    so the whole [B, S] mismatch matrix is ONE ``[B, 6L]·[6L, S]`` matmul —
    the systolic array does in a few ms what the elementwise level scan
    spreads over ~10x the time in VPU traffic. Word ids are split into
    bytes (three sub-features per level) so every product stays < 2^16 and
    the fp32 accumulation (precision=HIGHEST — the default truncates
    operands to bfloat16, which cannot hold p²) is exact: equality of all
    byte planes ⇔ equality of ids (ids < 2^24). Length/$/active rules are
    the same cheap elementwise epilogue as the VPU path, fused by XLA into
    the matmul output."""
    S, L = sub_words.shape
    B = pub_words.shape[0]
    s, p = sub_words, pub_words
    sb = jnp.stack([s & 255, (s >> 8) & 255, (s >> 16) & 255], axis=2)
    pb = jnp.stack([p & 255, (p >> 8) & 255, (p >> 16) & 255], axis=2)
    sbf = sb.reshape(S, 3 * L).astype(jnp.float32)
    pbf = pb.reshape(B, 3 * L).astype(jnp.float32)
    lvl = jnp.arange(L, dtype=jnp.int32)
    w = ((s != PLUS_ID) & (lvl[None, :] < sub_eff_len[:, None]))
    w3 = jnp.repeat(w, 3, axis=1).astype(jnp.float32)  # [S, 3L] byte layout
    # every matmul operand is an integer ≤ 256 → EXACT in bfloat16 (8-bit
    # mantissa), products < 2^17 accumulate exactly in the MXU's fp32 —
    # so a cheap single-pass bf16 matmul is bit-exact. That needs the
    # oversized features split: −2·s·p duplicates the (w·s, −p) pair, and
    # p² (16-bit) splits into (256·w, p²>>8) + (w, p²&255).
    ws = w3 * sbf                       # ≤ 255
    p2 = pbf * pbf                      # ≤ 65025 (split below)
    F = jnp.concatenate([ws, ws, 256.0 * w3, w3], axis=1)      # [S, 12L]
    G = jnp.concatenate(
        [-pbf, -pbf, jnp.floor(p2 / 256.0), p2 % 256.0], axis=1)  # [B, 12L]
    t1 = jnp.sum(ws * sbf, axis=1)      # Σ w·s²  [S]
    mm = lax.dot_general(
        G.astype(jnp.bfloat16), F.astype(jnp.bfloat16),
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [B, S]
    mismatch = mm + t1[None, :]
    len_ok = jnp.where(
        has_hash[None, :],
        pub_len[:, None] >= sub_eff_len[None, :],
        pub_len[:, None] == sub_eff_len[None, :],
    )
    dollar_ok = ~(pub_dollar[:, None] & first_wild[None, :])
    return (mismatch == 0.0) & len_ok & dollar_ok & active[None, :]


@functools.partial(jax.jit, static_argnames=("k", "chunk"))
def match_extract_mxu(
    sub_words: jax.Array,
    sub_eff_len: jax.Array,
    has_hash: jax.Array,
    first_wild: jax.Array,
    active: jax.Array,
    pub_words: jax.Array,
    pub_len: jax.Array,
    pub_dollar: jax.Array,
    k: int = 256,
    chunk: int = 0,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """MXU-matmul match + bit-packed extraction — the fast production path
    (same contract as :func:`match_extract`)."""
    S = sub_words.shape[0]
    block = 2048
    packed_ok = S % block == 0 and S >= block

    def one(args):
        pw, plen, pd = args
        m = _mxu_mask(sub_words, sub_eff_len, has_hash, first_wild,
                      active, pw, plen, pd)
        if packed_ok:
            return extract_indices_packed(_pack_mask(m), k, block)
        return extract_indices(m, k, S if S < 512 else 512)
    return _run_chunked(one, pub_words, pub_len, pub_dollar, chunk)

def _epilogue(pub_len, pub_dollar, eff, hh, fw, act) -> jax.Array:
    """Length / $-rule / liveness mask [B, Sseg] (vmq_topic.erl:53-66 +
    vmq_reg_trie.erl:283-288), applied on top of the mismatch==0 test."""
    len_ok = jnp.where(
        hh[None, :],
        pub_len[:, None] >= eff[None, :],
        pub_len[:, None] == eff[None, :],
    )
    return len_ok & ~(pub_dollar[:, None] & fw[None, :]) & act[None, :]


def _window_tiles_sel(F_t, t1, sub_eff_len, has_hash, first_wild, active,
                      pub_words, pub_len, pub_dollar, t_sel, t_start, *,
                      id_bits, k, seg_max, glob_pad, wild_rows):
    """Unrolled window-tile group: tile i matmuls a traced-start
    ``dynamic_slice`` window of ``seg_max`` contiguous rows, against the
    TP pubs GATHERED from the batch by its [TP] selector row (shipping
    [T, TP] selectors instead of duplicated [T, TP, L] word rows cuts the
    host→device argument bytes ~8x). ``wild_rows`` selects which rows this group
    may match: probe A (level-0 buckets) matches only concrete-first
    rows, probe B (level-1 g-buckets) only wildcard-first rows — the
    split is what makes A- and B-windows unable to duplicate each other's
    matches even over the relocation spare tail. Pad slots select pub
    row 0; their matches are computed but never gathered into any pub's
    result (a_tile/a_pos only name real slots)."""
    Kd = F_t.shape[0]
    T = t_sel.shape[0]
    j = jnp.arange(seg_max, dtype=jnp.int32)
    touts = []
    for ti in range(T):
        sel = t_sel[ti]
        pwt = jnp.take(pub_words, sel, axis=0)   # [TP, L] tiny gather
        plt = jnp.take(pub_len, sel)
        pdt = jnp.take(pub_dollar, sel)
        start = t_start[ti]
        Fseg = lax.dynamic_slice(F_t, (0, start), (Kd, seg_max))
        t1s = lax.dynamic_slice(t1, (start,), (seg_max,))
        effs = lax.dynamic_slice(sub_eff_len, (start,), (seg_max,))
        hhs = lax.dynamic_slice(has_hash, (start,), (seg_max,))
        fws = lax.dynamic_slice(first_wild, (start,), (seg_max,))
        acts = lax.dynamic_slice(active, (start,), (seg_max,))
        Gt = build_pub_operand(pwt, id_bits)
        mm = lax.dot_general(
            Gt, Fseg, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) + t1s[None, :]
        rowok = j[None, :] >= (glob_pad - start)  # region 0 never re-matched
        split = fws[None, :] if wild_rows else ~fws[None, :]
        m = (mm == 0.0) & _epilogue(plt, pdt, effs, hhs, fws, acts) \
            & rowok & split
        i2, v2, c2 = extract_indices_packed(_pack_mask(m), k, 2048)
        touts.append((i2 + start, v2, c2))
    return (jnp.stack([o[0] for o in touts]),
            jnp.stack([o[1] for o in touts]),
            jnp.stack([o[2] for o in touts]))


def _dense_region0(F_t, t1, sub_eff_len, has_hash, first_wild, active,
                   pub_words, pub_len, pub_dollar, *, id_bits, k, glob_pad,
                   gc):
    """Phase 1 of the windowed kernels: every publish × region 0 (filters
    whose first two levels are wildcards), in ``gc`` pub chunks. Returns
    ``(gidx [B,k], gvalid [B,k], gcount [B])``."""
    B = pub_words.shape[0]
    gouts = []
    for c in range(0, B, gc):
        sl = slice(c, c + gc)
        G = build_pub_operand(pub_words[sl], id_bits)
        mm = lax.dot_general(
            G, F_t[:, :glob_pad], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) + t1[None, :glob_pad]
        m = (mm == 0.0) & _epilogue(
            pub_len[sl], pub_dollar[sl], sub_eff_len[:glob_pad],
            has_hash[:glob_pad], first_wild[:glob_pad], active[:glob_pad])
        gouts.append(extract_indices_packed(_pack_mask(m), k, 2048))
    return (jnp.concatenate([o[0] for o in gouts], axis=0),
            jnp.concatenate([o[1] for o in gouts], axis=0),
            jnp.concatenate([o[2] for o in gouts], axis=0))


def _gather_parts(tidx, tvalid, tcount, tile, pos):
    """Gather tile results back to publish order: pub i's probe result is
    tile ``tile[i]`` slot ``pos[i]`` (``tile < 0`` = pub has no window in
    this probe). Returns ``(idx [B,k], valid [B,k], cnt [B])``."""
    ok = tile >= 0
    tt = jnp.maximum(tile, 0)
    idx = tidx[tt, pos]
    valid = tvalid[tt, pos] & ok[:, None]
    cnt = jnp.where(ok, tcount[tt, pos], 0)
    return idx, valid, cnt


def _flat_combine(real, k, C, parts):
    """Flat compaction of the per-pub result parts of the phases the
    program holds, in phase order (each an ``(idx, valid, cnt)`` triple):
    prefix-sum the clamped counts, scatter every matched slot id into one
    [C] buffer. See :func:`match_extract_windowed_flat` for the
    contract."""
    clip = jnp.zeros(real.shape, bool)
    cnts = []
    for _idx, _valid, cnt in parts:
        clip = clip | (cnt > k)
        cnts.append(jnp.minimum(jnp.where(real, cnt, 0), k))
    total = sum(cnts)
    pre = jnp.cumsum(total) - total               # exclusive prefix
    j = jnp.arange(k, dtype=jnp.int32)[None, :]
    flat = jnp.zeros((C,), jnp.int32)
    base = pre
    for (idx, valid, _cnt), cnt in zip(parts, cnts):
        # extraction guarantees rank j holds the j-th match (j < count)
        pos = base[:, None] + j
        p = jnp.where(valid & real[:, None] & (j < cnt[:, None]), pos, C)
        flat = flat.at[p].set(idx, mode="drop")
        base = base + cnt
    overflow = ((pre + total > C) | clip) & real
    return (flat, pre.astype(jnp.int32), total.astype(jnp.int32), overflow)


@functools.partial(jax.jit,
                   static_argnames=("id_bits", "k", "glob_pad", "seg_max",
                                    "seg2_max", "gc", "C"))
def match_extract_windowed_flat(
    F_t: jax.Array,          # bf16 [K, S] coded operands (build_operands)
    t1: jax.Array,           # f32 [S]
    sub_eff_len: jax.Array,  # int32 [S]
    has_hash: jax.Array,     # bool [S]
    first_wild: jax.Array,   # bool [S]
    active: jax.Array,       # bool [S]
    pub_words: jax.Array,    # int32 [B, L]  original batch order
    pub_len: jax.Array,      # int32 [B]
    pub_dollar: jax.Array,   # bool [B]
    n_real: jax.Array,       # int32 scalar: real pubs (rest is padding)
    t_sel: jax.Array,        # int32 [T, TP]  probe-A tile pub selectors
    t_start: jax.Array,      # int32 [T]
    t2_sel: jax.Array,       # int32 [T2, TP] probe-B tile pub selectors
    t2_start: jax.Array,     # int32 [T2]
    a_tile: jax.Array,       # int32 [B] probe-A tile per pub (-1 = none)
    a_pos: jax.Array,        # int32 [B] slot within that tile
    b_tile: jax.Array,       # int32 [B] probe-B tile per pub (-1 = none)
    b_pos: jax.Array,        # int32 [B]
    *,
    id_bits: int,
    k: int,
    glob_pad: int,
    seg_max: int,
    seg2_max: int,
    gc: int,
    C: int,                  # flat result capacity (slots)
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """The production match path — ONE fused executable per batch, with
    device-side FLAT COMPACTION.

    Up to three match phases against the two-level bucket layout
    (models/tpu_table.py — the trie's first- and second-edge narrowing
    as dense windows; the per-publish ETS walk of
    ``vmq_reg_trie.erl:358-383`` recast as batched matmuls):

    1. DENSE: every publish × region 0 (filters whose first TWO levels
       are wildcards — a residual sliver), in ``gc`` pub chunks.
    2. PROBE A: publishes tiled by their level-0 word's bucket; windows
       match only concrete-first rows.
    3. PROBE B: publishes tiled by their level-1 word's g-bucket
       (wildcard-first filters with a concrete level 1); windows match
       only wildcard-first rows.

    A phase costs its extractions — ``[slots, k, 64]`` gathers a dense
    chunk or a window tile — whatever the rows behind its mask hold, so
    a program holds a phase only if its rows can match at all: probe A
    always, the dense phase unless ``gc`` is 0, probe B unless
    ``seg2_max`` is 0. The caller decides from the live rows of region 0
    and of the g-buckets in the table snapshot the device arrays were
    built from (``TpuMatcher._geometry``): a table of concrete-first
    filters runs probe A alone, and the first wildcard-first SUBSCRIBE
    changes the statics — another program, compiled off the serving
    path like any cold signature. ``glob_pad`` keeps its value either
    way: probe A's row guard needs it.

    Design notes (measured on the TPU runtime): per-execution overhead
    is ~5ms regardless of op count, ``lax.map`` serialises tile
    launches, variable tile counts recompile, F-window gathers are
    10-60x slower than the matmuls they feed, and [B, S] f32
    intermediates OOM the compile past B=1024 — hence static unrolled
    tiles over contiguous ``dynamic_slice`` windows and a pub-chunked
    dense phase. Exact: the coded matmul is bit-exact (build_operands)
    and the probe split + row guard make double counting impossible.

    The padded per-part ``(idx [·,k], valid, count)`` results never
    leave the device: tile
    results are gathered back to publish order, a prefix sum over per-pub
    totals assigns each publish a contiguous range, and all matched slot
    ids scatter into ONE ``[C]`` buffer. The host round trip shrinks from
    ~15MB of padded idx/valid arrays to ``4C + O(B)`` bytes (~2MB at
    B=4096) — fewer bytes across the host↔device link per batch, and
    less resolve-side memory traffic.

    Up-side traffic shrinks the same way: tiles are [T, TP] pub
    *selectors* (gathered on device) instead of duplicated [T, TP, L]
    word rows.

    Returns ``(flat [C] int32, pre [B] int32, total [B] int32,
    overflow [B] bool)``: publish i's matched slot ids are
    ``flat[pre[i] : pre[i]+total[i]]`` unless ``overflow[i]`` (flat
    capacity exhausted or a part clipped at k — exact host fallback, the
    same escape hatch as the padded path's count>k contract).
    """
    return _windowed_flat_core(
        F_t, t1, sub_eff_len, has_hash, first_wild, active,
        pub_words, pub_len, pub_dollar, n_real, t_sel, t_start,
        t2_sel, t2_start, a_tile, a_pos, b_tile, b_pos,
        id_bits=id_bits, k=k, glob_pad=glob_pad, seg_max=seg_max,
        seg2_max=seg2_max, gc=gc, C=C)


def _windowed_flat_core(F_t, t1, sub_eff_len, has_hash, first_wild, active,
                        pub_words, pub_len, pub_dollar, n_real,
                        t_sel, t_start, t2_sel, t2_start,
                        a_tile, a_pos, b_tile, b_pos, *,
                        id_bits, k, glob_pad, seg_max, seg2_max, gc, C):
    """Shared body of the flat windowed kernels (plain and packed-I/O)."""
    # the named scopes go into the operations' metadata and nowhere
    # else: a device trace attributes each operation's time to its phase
    B = pub_words.shape[0]
    real = jnp.arange(B, dtype=jnp.int32) < n_real
    parts = []  # of the phases compiled in, in phase order

    if gc:
        with jax.named_scope("dense_region0"):
            parts.append(_dense_region0(
                F_t, t1, sub_eff_len, has_hash, first_wild, active,
                pub_words, pub_len, pub_dollar,
                id_bits=id_bits, k=k, glob_pad=glob_pad, gc=gc))

    args = (F_t, t1, sub_eff_len, has_hash, first_wild, active,
            pub_words, pub_len, pub_dollar)
    with jax.named_scope("probe_a"):
        tidx, tvalid, tcount = _window_tiles_sel(
            *args, t_sel, t_start, id_bits=id_bits, k=k,
            seg_max=seg_max, glob_pad=glob_pad, wild_rows=False)
        parts.append(_gather_parts(tidx, tvalid, tcount, a_tile, a_pos))
    if seg2_max:
        with jax.named_scope("probe_b"):
            t2idx, t2valid, t2count = _window_tiles_sel(
                *args, t2_sel, t2_start, id_bits=id_bits, k=k,
                seg_max=seg2_max, glob_pad=glob_pad, wild_rows=True)
            parts.append(
                _gather_parts(t2idx, t2valid, t2count, b_tile, b_pos))

    # flat compaction: pad pubs contribute nothing; each real pub owns
    # the contiguous range [pre, pre+total). Budget with counts CLAMPED
    # to k: at most k entries per part are ever extracted, and a pub
    # whose raw count exceeds k is host-matched anyway (clip flag) —
    # charging the raw count would let one mega-fanout pub reserve its
    # entire raw fanout and cascade spurious capacity overflows (= slow
    # exact host scans) across the rest of the batch.
    with jax.named_scope("flat_combine"):
        return _flat_combine(real, k, C, parts)


@jax.jit
def pack_meta(sub_eff_len, has_hash, first_wild, active):
    """Fuse the four per-slot metadata arrays into ONE int32 [S] word
    (eff_len in bits 0-15, has_hash/first_wild/active at bits 16-18).
    Built once per table sync; the packed-I/O kernel takes this single
    device-resident argument instead of four (fewer host↔device
    transfers per batch)."""
    return _pack_meta_vals(sub_eff_len, has_hash, first_wild, active)


def flat_pack_args(args) -> "np.ndarray":
    """Host side of the packed transport: concatenate every per-batch
    host argument of :func:`match_extract_windowed_flat` into ONE int32
    vector (uploaded as a single transfer: every argument is its own
    host→device transfer, so 12 small uploads cost more than one medium
    one). Layout must mirror the unpacking in
    :func:`_unpack_transport` (the single device-side decoder)."""
    (pw, pl, pd, n_real, t_sel, t_start, t2_sel, t2_start,
     a_tile, a_pos, b_tile, b_pos) = args
    return np.concatenate([
        np.ascontiguousarray(pw, dtype=np.int32).ravel(),
        np.asarray(pl, dtype=np.int32).ravel(),
        np.asarray(pd, dtype=np.int32).ravel(),
        np.asarray([n_real], dtype=np.int32),
        np.ascontiguousarray(t_sel, dtype=np.int32).ravel(),
        np.asarray(t_start, dtype=np.int32).ravel(),
        np.ascontiguousarray(t2_sel, dtype=np.int32).ravel(),
        np.asarray(t2_start, dtype=np.int32).ravel(),
        np.asarray(a_tile, dtype=np.int32).ravel(),
        np.asarray(a_pos, dtype=np.int32).ravel(),
        np.asarray(b_tile, dtype=np.int32).ravel(),
        np.asarray(b_pos, dtype=np.int32).ravel(),
    ])


def _pack_meta_vals(el, hh, fw, ac):
    return (el.astype(jnp.int32)
            | (hh.astype(jnp.int32) << 16)
            | (fw.astype(jnp.int32) << 17)
            | (ac.astype(jnp.int32) << 18))


@functools.partial(jax.jit, donate_argnums=(0,))
def apply_delta_meta(meta, slots, el, hh, fw, ac):
    """O(dirty) scatter of the pack_meta word for changed slots —
    mirrors apply_delta's donate/scatter design so a delta sync never
    rebuilds (or reallocates) the full [S] meta buffer."""
    return meta.at[slots].set(_pack_meta_vals(el, hh, fw, ac))


@jax.jit
def apply_delta_meta_copy(meta, slots, el, hh, fw, ac):
    """Non-donating variant for when an in-flight match holds ``meta``."""
    return meta.at[slots].set(_pack_meta_vals(el, hh, fw, ac))


def _packed_geometry(args) -> dict:
    """Static shape geometry of one packed batch, derived from the arg
    shapes — the ONE place every call_* helper reads the contract."""
    B, L = args[0].shape
    T, TP = args[4].shape
    return dict(B=B, L=L, T=T, TP=TP, T2=args[6].shape[0])


def call_packed(F_t, t1, meta, args, statics):
    """The one call shape for the packed transport: derives the static
    geometry from the arg shapes, packs the host args, invokes the
    kernel. The matcher and the tests all go through here so the
    flat_pack_args layout and the kernel's shape contract cannot
    drift apart. (``device.dispatch`` fault-injection point: the
    robustness harness exercises TPU dispatch failure here.)"""
    from ..robustness import faults

    faults.inject("device.dispatch")
    return match_extract_windowed_flat_packed(
        F_t, t1, meta, flat_pack_args(args),
        **_packed_geometry(args), **statics)


def unpack_flat_result(out, B: int, C: int):
    """Decode :func:`match_extract_windowed_flat_packed`'s single result
    vector ``[C + 3B]`` into ``(flat [C], pre [B], total [B],
    overflow [B] bool)`` — the one place that knows the packed layout.
    ``B`` is the PADDED batch (args[0].shape[0]), not the real pub
    count."""
    return (out[:C], out[C:C + B], out[C + B:C + 2 * B],
            out[C + 2 * B:C + 3 * B].astype(bool))


@functools.partial(jax.jit,
                   static_argnames=("B", "L", "T", "TP", "T2", "id_bits",
                                    "k", "glob_pad", "seg_max", "seg2_max",
                                    "gc", "C"))
def match_extract_windowed_flat_packed(
    F_t: jax.Array,          # bf16 [K, S] coded operands (build_operands)
    t1: jax.Array,           # f32 [S]
    meta: jax.Array,         # int32 [S] pack_meta word
    packed: jax.Array,       # int32 [·] flat_pack_args transport vector
    *,
    B: int, L: int, T: int, TP: int, T2: int,
    id_bits: int, k: int, glob_pad: int, seg_max: int, seg2_max: int,
    gc: int, C: int,
) -> jax.Array:
    """Packed-I/O variant of :func:`match_extract_windowed_flat`: 4 call
    arguments instead of 18, ONE host→device transfer (the ``packed``
    vector) and ONE device→host transfer (the concatenated int32
    result) per batch — 4 result pulls + 12 argument uploads become
    1 + 1.

    Returns one int32 ``[C + 3B]`` vector: ``flat = out[:C]``,
    ``pre = out[C:C+B]``, ``total = out[C+B:C+2B]``,
    ``overflow = out[C+2B:].astype(bool)`` — same contract as the
    unpacked kernel's four arrays.
    """
    return _packed_core(F_t, t1, meta, packed, B=B, L=L, T=T, TP=TP,
                        T2=T2, id_bits=id_bits, k=k, glob_pad=glob_pad,
                        seg_max=seg_max, seg2_max=seg2_max, gc=gc, C=C)


def _unpack_meta(meta):
    """The pack_meta word -> ``(eff_len, has_hash, first_wild, active)``."""
    return (meta & 0xFFFF, ((meta >> 16) & 1).astype(bool),
            ((meta >> 17) & 1).astype(bool), ((meta >> 18) & 1).astype(bool))


def _unpack_transport(meta, packed, B, L, T, TP, T2):
    """THE decoder of the flat_pack_args layout + pack_meta word — the
    single counterpart to the host-side packers; every packed kernel
    entry point goes through here so the layout cannot drift between
    variants. Returns the 18-arg tail of the unpacked kernels."""
    eff, hh, fw, act = _unpack_meta(meta)
    o = 0
    pw = packed[o:o + B * L].reshape(B, L); o += B * L
    pl = packed[o:o + B]; o += B
    pd = packed[o:o + B].astype(bool); o += B
    n_real = packed[o]; o += 1
    t_sel = packed[o:o + T * TP].reshape(T, TP); o += T * TP
    t_start = packed[o:o + T]; o += T
    t2_sel = packed[o:o + T2 * TP].reshape(T2, TP); o += T2 * TP
    t2_start = packed[o:o + T2]; o += T2
    a_tile = packed[o:o + B]; o += B
    a_pos = packed[o:o + B]; o += B
    b_tile = packed[o:o + B]; o += B
    b_pos = packed[o:o + B]; o += B
    return (eff, hh, fw, act, pw, pl, pd, n_real, t_sel, t_start,
            t2_sel, t2_start, a_tile, a_pos, b_tile, b_pos)


def _packed_core(F_t, t1, meta, packed, *, B, L, T, TP, T2, id_bits, k,
                 glob_pad, seg_max, seg2_max, gc, C):
    """Unpack + match + repack (shared by the jitted packed entry point
    and the K-batch scan of :func:`match_many`)."""
    with jax.named_scope("unpack_transport"):
        unpacked = _unpack_transport(meta, packed, B, L, T, TP, T2)
    flat, pre, total, overflow = _windowed_flat_core(
        F_t, t1, *unpacked,
        id_bits=id_bits, k=k, glob_pad=glob_pad, seg_max=seg_max,
        seg2_max=seg2_max, gc=gc, C=C)
    return jnp.concatenate([flat, pre, total, overflow.astype(jnp.int32)])


def _match_many_body(
    F_t, t1, meta,
    packed_stack,            # int32 [N, P] staged transport vectors
    *,
    B: int, L: int, T: int, TP: int, T2: int,
    id_bits: int, k: int, glob_pad: int, seg_max: int, seg2_max: int,
    gc: int, C: int,
):
    def step(_, p):
        out = _packed_core(F_t, t1, meta, p, B=B, L=L, T=T, TP=TP, T2=T2,
                           id_bits=id_bits, k=k, glob_pad=glob_pad,
                           seg_max=seg_max, seg2_max=seg2_max, gc=gc, C=C)
        return None, out

    _, outs = lax.scan(step, None, packed_stack)
    return outs


#: The multi-batch entry point: K packed batches run inside ONE
#: executable (``lax.scan`` over the stacked transport vectors) and all
#: their result vectors ``[K, C + 3B]`` come back in ONE host pull. The
#: staging block is DONATED — the matcher re-stages a fresh super-batch
#: every dispatch, so keeping the previous stack alive only doubles HBM
#: footprint; donation lets XLA reuse the staging allocation across
#: dispatches. No host sync happens between the K scan iterations: K
#: round trips become 1.
match_many = functools.partial(
    jax.jit,
    static_argnames=("B", "L", "T", "TP", "T2", "id_bits", "k",
                     "glob_pad", "seg_max", "seg2_max", "gc", "C"),
    donate_argnums=(3,),
)(_match_many_body)


def call_match_many(F_t, t1, meta, preps, statics, device=None):
    """Super-batch dispatch (the tentpole path of the K-batch pipeline):
    pack each prepped batch's host args, stack them into ONE staging
    block, upload it as ONE transfer and run all K batches inside ONE
    executable via :func:`match_many` (donated staging, scan on device,
    zero host syncs between batches). ``device`` pins the staging upload
    (double-buffering callers stage batch k+1 while batch k runs).
    Returns the ``[K, C + 3B]`` stacked device result — decode with
    :func:`unpack_many_results`."""
    import warnings

    from ..robustness import faults

    faults.inject("device.dispatch")
    vecs = np.stack([flat_pack_args(a) for a in preps])
    if device is not None:
        vecs = jax.device_put(vecs, device)
    with warnings.catch_warnings():
        # the staging block rarely aliases an output shape, so XLA warns
        # the donation was "not usable" at compile time; donation is a
        # free-at-dispatch hint here, not an aliasing requirement
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        return match_many(
            F_t, t1, meta, vecs, **_packed_geometry(preps[0]), **statics)


def unpack_many_results(out, B: int, C: int):
    """Decode :func:`match_many`'s stacked ``[K, C + 3B]`` result into K
    ``(flat, pre, total, overflow)`` tuples with ONE host pull (none
    where the caller pulled already, to time the pull by itself)."""
    o = np.asarray(out)
    return [unpack_flat_result(o[i], B, C) for i in range(o.shape[0])]


def wide_pack_args(pw, pl, pd, a_win, b_win) -> "np.ndarray":
    """Host side of the wide pass's transport: the ``U`` publishes' coded
    levels, lengths and ``$`` flags and their two windows (``[U, 3]``
    each: first row of the window, first and end row of the publish's
    OWN region inside it), ONE int32 vector. Mirrored by
    :func:`wide_mask_packed`."""
    return np.concatenate([
        np.ascontiguousarray(pw, dtype=np.int32).ravel(),
        np.asarray(pl, dtype=np.int32).ravel(),
        np.asarray(pd, dtype=np.int32).ravel(),
        np.ascontiguousarray(a_win, dtype=np.int32).T.ravel(),
        np.ascontiguousarray(b_win, dtype=np.int32).T.ravel(),
    ])


@functools.partial(jax.jit,
                   static_argnames=("U", "L", "id_bits", "glob_pad", "wa",
                                    "wb"))
def wide_mask_packed(
    F_t: jax.Array,          # bf16 [K, S] coded operands (build_operands)
    t1: jax.Array,           # f32 [S]
    meta: jax.Array,         # int32 [S] pack_meta word
    packed: jax.Array,       # int32 [U*(L+8)] wide_pack_args vector
    *,
    U: int, L: int, id_bits: int, glob_pad: int, wa: int, wb: int,
) -> jax.Array:
    """The WIDE result of the windowed match: for each of ``U`` publishes
    the bit-packed match mask of ALL the rows it can match — region 0,
    its level-0 bucket's region (a window of ``wa`` rows) and, where the
    table has g-buckets (``wb`` > 0), its level-1 g-bucket's region — so a
    fan-out is bounded by the table and by no ``k``. The flat form
    (:func:`match_extract_windowed_flat_packed`) answers a publish with
    at most ``k`` ids a part and ``C`` a batch; what it flags
    ``overflow`` is answered here, and the host turns bits into slot ids
    (``TpuMatcher._wide_pass``).

    The same exact test as the flat form (coded mismatch == 0 and the
    length / ``$`` / liveness epilogue), a window's rows confined to the
    publish's own region ``[lo, hi)``: a bucket's region holds only
    concrete-first rows and a g-bucket's only wildcard-first ones, so no
    row is counted twice. The windows are walked one publish at a time
    (``lax.map``): a fan-out burst repeats few topics, and each
    ``[1, K] x [K, w]`` product reads its window once.

    Returns uint32 ``[U, (glob_pad + wa + wb) / 32]``: bit ``j`` of a
    row is region-0 row ``j``, then row ``a_start + j - glob_pad`` of
    the first window, then of the second."""
    with jax.named_scope("wide_mask"):
        eff, hh, fw, act = _unpack_meta(meta)
        o = U * L
        pw = packed[:o].reshape(U, L)
        pl = packed[o:o + U]
        pd = packed[o + U:o + 2 * U].astype(bool)
        win = packed[o + 2 * U:].reshape(6, U)
        Kd = F_t.shape[0]
        G = build_pub_operand(pw, id_bits)
        mm = lax.dot_general(
            G, F_t[:, :glob_pad], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) + t1[None, :glob_pad]
        m0 = (mm == 0.0) & _epilogue(
            pl, pd, eff[:glob_pad], hh[:glob_pad], fw[:glob_pad],
            act[:glob_pad])
        parts = [_pack_mask(m0)]

        def window(width, start, lo, hi):
            j = jnp.arange(width, dtype=jnp.int32)

            def one(x):
                g, plen, pdol, s0, r0, r1 = x
                Fseg = lax.dynamic_slice(F_t, (0, s0), (Kd, width))
                t1s = lax.dynamic_slice(t1, (s0,), (width,))
                sl = lambda a: lax.dynamic_slice(a, (s0,), (width,))
                mw = lax.dot_general(
                    g[None, :], Fseg, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) + t1s[None, :]
                row = s0 + j
                m = (mw == 0.0) & _epilogue(
                    plen[None], pdol[None], sl(eff), sl(hh), sl(fw),
                    sl(act)) & ((row >= r0) & (row < r1))[None, :]
                return _pack_mask(m)[0]

            return lax.map(one, (G, pl, pd, start, lo, hi))

        parts.append(window(wa, win[0], win[1], win[2]))
        if wb:
            parts.append(window(wb, win[3], win[4], win[5]))
        return jnp.concatenate(parts, axis=1)


def call_wide(F_t, t1, meta, pw, pl, pd, a_win, b_win, statics):
    """The one call shape of the wide pass (``device.dispatch`` fault
    point, as :func:`call_packed`)."""
    from ..robustness import faults

    faults.inject("device.dispatch")
    U, L = pw.shape
    return wide_mask_packed(F_t, t1, meta,
                            wide_pack_args(pw, pl, pd, a_win, b_win),
                            U=U, L=L, **statics)


def unpack_wide_bits(words: "np.ndarray", glob_pad: int, wa: int,
                     a_start: int, b_start: int) -> "np.ndarray":
    """One publish's row of :func:`wide_mask_packed` -> its matched slot
    ids (int32, ascending inside each of the three parts)."""
    nz = np.flatnonzero(np.unpackbits(
        np.ascontiguousarray(words).view(np.uint8), bitorder="little"))
    a = nz >= glob_pad
    b = nz >= glob_pad + wa
    return (nz + a * (a_start - glob_pad)
            + b * (b_start - a_start - wa)).astype(np.int32)


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4))
def apply_delta(
    sub_words: jax.Array,
    sub_eff_len: jax.Array,
    has_hash: jax.Array,
    first_wild: jax.Array,
    active: jax.Array,
    slots: jax.Array,  # int32 [D] target slot per delta row
    d_words: jax.Array,  # int32 [D, L]
    d_eff_len: jax.Array,  # int32 [D]
    d_has_hash: jax.Array,  # bool [D]
    d_first_wild: jax.Array,  # bool [D]
    d_active: jax.Array,  # bool [D]
):
    """Scatter a delta batch of subscription rows into the device-resident
    table — the trie-delta stream (BASELINE config 5): subscribe/unsubscribe
    events accumulate host-side and apply in one scatter instead of
    re-uploading the table (the analog of vmq_reg_trie consuming
    subscriber-db change events incrementally).

    The table arrays are DONATED: without donation every functional
    ``.at[].set`` copies the full S-row array (~500MB of HBM for a
    128-slot delta at 5M subs); with donation XLA scatters in place. Callers must drop their old
    references (TpuMatcher.sync reassigns _dev_arrays from the return)."""
    sub_words = sub_words.at[slots].set(d_words)
    sub_eff_len = sub_eff_len.at[slots].set(d_eff_len)
    has_hash = has_hash.at[slots].set(d_has_hash)
    first_wild = first_wild.at[slots].set(d_first_wild)
    active = active.at[slots].set(d_active)
    return sub_words, sub_eff_len, has_hash, first_wild, active


# non-donating variants: used while a dispatched match still holds the
# current buffers (donating them mid-flight would invalidate the match's
# args — TpuMatcher.sync picks per call via its in-flight counter)
apply_delta_copy = jax.jit(apply_delta.__wrapped__)


def delta_pack_args(slots, words, eff, hh, fw, ac):
    """Host side of the fused delta transport: slots + all per-slot delta
    fields as ONE int32 vector ``[D*(L+5)]``. The unfused path uploads
    six arrays and dispatches two jit calls per delta sync; one vector
    + one call collapses it to a single round trip."""
    import numpy as np

    return np.concatenate([
        np.asarray(slots, dtype=np.int32).ravel(),
        np.ascontiguousarray(words, dtype=np.int32).ravel(),
        np.asarray(eff, dtype=np.int32).ravel(),
        np.asarray(hh, dtype=np.int32).ravel(),
        np.asarray(fw, dtype=np.int32).ravel(),
        np.asarray(ac, dtype=np.int32).ravel(),
    ])


@functools.partial(jax.jit, static_argnames=("D", "L", "id_bits"),
                   donate_argnums=(0, 1, 2, 3, 4, 5, 6, 7))
@jax.named_scope("delta_scatter")
def apply_delta_fused(
    sub_words, sub_eff_len, has_hash, first_wild, active,  # table [S,·]
    F_t, t1,                                               # coded operands
    meta,                                                  # pack_meta [S]
    packed,                                                # delta_pack_args
    *, D: int, L: int, id_bits: int,
):
    """ONE scatter call updating every device-resident structure (base
    table arrays, coded F/t1 operands, packed meta word) from one packed
    delta vector. All eight state arrays are DONATED — same in-place
    contract as :func:`apply_delta`; callers reassign from the return.

    Returns ``((sub_words, eff, hh, fw, ac), (F_t, t1), meta)``.
    """
    o = 0
    slots = packed[o:o + D]; o += D
    w = packed[o:o + D * L].reshape(D, L); o += D * L
    e = packed[o:o + D]; o += D
    nh = packed[o:o + D].astype(bool); o += D
    nf = packed[o:o + D].astype(bool); o += D
    na = packed[o:o + D].astype(bool)
    sub_words = sub_words.at[slots].set(w)
    sub_eff_len = sub_eff_len.at[slots].set(e)
    has_hash = has_hash.at[slots].set(nh)
    first_wild = first_wild.at[slots].set(nf)
    active = active.at[slots].set(na)
    F_d, t1_d = build_operands(w, e, id_bits)
    F_t = F_t.at[:, slots].set(F_d)
    t1 = t1.at[slots].set(t1_d)
    meta = meta.at[slots].set(_pack_meta_vals(e, nh, nf, na))
    return ((sub_words, sub_eff_len, has_hash, first_wild, active),
            (F_t, t1), meta)


apply_delta_fused_copy = jax.jit(apply_delta_fused.__wrapped__,
                                 static_argnames=("D", "L", "id_bits"))


@functools.partial(jax.jit, static_argnames=("D", "L", "id_bits", "glob"),
                   donate_argnums=tuple(range(12)))
@jax.named_scope("delta_scatter")
def apply_delta_windowed_fused(
    F_t, t1, eff, hh, fw, act,          # 'sub'-sharded full-table arrays
    Fg, t1g, effg, hhg, fwg, actg,      # replicated dense g-zone mirrors
    packed,                             # delta_pack_args vector
    *, D: int, L: int, id_bits: int, glob: int,
):
    """ONE fused scatter updating the sharded windowed matcher's whole
    device state (full-table operands + the replicated dense-zone
    mirrors) from one packed delta vector. The eager path this replaces
    dispatched up to TEN separate scatters per flush (four metadata
    arrays, the operand pair, and the same again for the g-zone) and
    minted a fresh compile signature per dirty-in-zone COUNT via its
    data-dependent ``slots[gsel]`` slice — the delta_apply_ms_p99 long
    pole. Here the g-zone mirror is updated shape-stably: slots outside
    the zone are routed to the out-of-range index ``glob`` and dropped
    by the scatter (``mode="drop"``), so one compile per Dpad rung
    serves every flush.

    All twelve state arrays are DONATED (callers reassign from the
    return, same contract as :func:`apply_delta`); use the ``_copy``
    variant while a dispatched match still holds them.

    Returns the twelve arrays in input order.
    """
    o = 0
    slots = packed[o:o + D]; o += D
    w = packed[o:o + D * L].reshape(D, L); o += D * L
    e = packed[o:o + D]; o += D
    nh = packed[o:o + D].astype(bool); o += D
    nf = packed[o:o + D].astype(bool); o += D
    na = packed[o:o + D].astype(bool)
    F_d, t1_d = build_operands(w, e, id_bits)
    F_t = F_t.at[:, slots].set(F_d)
    t1 = t1.at[slots].set(t1_d)
    eff = eff.at[slots].set(e)
    hh = hh.at[slots].set(nh)
    fw = fw.at[slots].set(nf)
    act = act.at[slots].set(na)
    gs = jnp.where(slots < glob, slots, glob)  # OOB → dropped below
    Fg = Fg.at[:, gs].set(F_d, mode="drop")
    t1g = t1g.at[gs].set(t1_d, mode="drop")
    effg = effg.at[gs].set(e, mode="drop")
    hhg = hhg.at[gs].set(nh, mode="drop")
    fwg = fwg.at[gs].set(nf, mode="drop")
    actg = actg.at[gs].set(na, mode="drop")
    return (F_t, t1, eff, hh, fw, act, Fg, t1g, effg, hhg, fwg, actg)


apply_delta_windowed_fused_copy = jax.jit(
    apply_delta_windowed_fused.__wrapped__,
    static_argnames=("D", "L", "id_bits", "glob"))
