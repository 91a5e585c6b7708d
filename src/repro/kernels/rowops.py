"""Shared row-tile compute bodies used by multiple Pallas kernels.

The bitwise-parity contract between the single-kernel fused forward
(kernels/fused_gemm.py), the two-kernel chain (prologue → w4a4 GEMM) and the
standalone hadamard / actquant kernels (tests/test_kernels_prologue.py and
tests/test_kernels_fused.py acceptance) holds because all of them import
THESE implementations — the butterfly order, the scale-then-round operation
order, the prologue body, the K-chunked/R-tiled projection accumulation and
the int4 nibble layout live in exactly one place.

K-split slab bodies
-------------------

The K-split fused grid streams the activation row in (bm, bk) slabs, so the
whole-row bodies decompose into slab-shaped pieces with EXACTLY the same
float ops:

  * ``fwht_rows(x, d)`` ==(bitwise)== ``fwht_cross_rows`` applied to the
    concatenation of per-chunk ``fwht_intra_rows``: butterflies at distance
    h < bk never cross a bk-aligned chunk boundary, so the first log2(bk)
    sweeps run per chunk; the remaining sweeps pair whole chunks; the
    1/sqrt(d) normalization happens once at the end in both spellings.
  * per-token amax is a max-reduction — chunk-wise ``jnp.maximum`` folding
    is exactly the whole-row max (max is exact on floats).
  * ``q = clip(round(x/s))`` is elementwise — chunk-wise application with
    the whole-row scale is the whole-row quantization.

Group-wise activation scales (paper Table 2, g = 128)
-----------------------------------------------------

Per-group quantization replaces the (bm, 1) per-token scale with a
(bm, d/g) SCALE PLANE: one scale per g contiguous K features.  Scale groups
are aligned to K-chunks (the plan layer snaps bk to a multiple of g, see
``snap_bk_to_group``), so a chunk always holds whole groups and

  * the per-group amax needs NO cross-chunk fold — ``group_amax`` on a
    chunk computes exactly the same reductions as on the whole row,
  * the int8 GEMM must rescale per group BEFORE f32 accumulation: the
    canonical order is ``gemm_chunk_grouped`` (per chunk: int32 dots over
    the groups in ascending-K order, each rescaled and summed in f32) with
    the per-chunk results accumulated across chunks in ascending-K order.
    All three kernel paths issue these same dots in this same order.
  * zero-padded K tails are exact: a padded group's amax is 0, the scale
    guard clamps it to 1, its quantized values are 0, and the group's
    rescaled partial sum is an exact f32 +0.0.

``group = d`` (one group spanning the row) reproduces per-token
quantization bit for bit: the reductions, the guard and the scale·round are
the same scalar ops on the same operands.
  * the (x·V) projection is canonically a (bk, br)-tiled accumulation
    (``project_rows_tiled`` / per-chunk ``project_chunk_rows`` summed in
    ascending-K order) — all three kernel paths issue these same dots in
    this same order, which is what keeps them bitwise identical.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


# TPU vreg lane width.  Mosaic needs the last dim of every block to be a
# multiple of it (or the whole array dim), so K, N and R tiles are lane
# multiples; M tiles only need the 8-row sublane multiple round_pow2 gives.
LANES = 128


def round_pow2(m: int) -> int:
    """Largest power of two ≤ max(m, 8) (block-size clamp helper)."""
    p = 8
    while p * 2 <= m:
        p *= 2
    return p


def lane_tile(dim: int, cap: int) -> int:
    """Power-of-two lane tile for an N or R extent: ``cap`` clamped to the
    problem, never below one lane width (short extents pad up to it)."""
    return min(cap, max(LANES, round_pow2(dim)))


def k_tile(k: int, cap: int) -> int:
    """K-chunk tile: a lane tile, except that K below one lane width is
    ONE whole chunk (online rotation needs K unpadded).  Layers that narrow
    exist only at test sizes, which run interpreted."""
    if k >= LANES:
        return lane_tile(k, cap)
    p = 8
    while p < k:
        p *= 2
    return p


def default_proj_tiles(k: int, r: int, bk=None, br=None):
    """Default (bk, br) projection tiles: 512-capped lane tiles.  THE one
    spelling of the default — the prologue and fused kernels and the
    ops-layer plan table all derive their fallback tiles from here, so
    direct kernel callers and the dispatched paths agree on the (bk, br)
    accumulation order the bitwise contract needs."""
    if bk is None:
        bk = k_tile(k, 512)
    if br is None:
        br = lane_tile(r, 512)
    return bk, br


def fwht_rows(y: jnp.ndarray, d: int) -> jnp.ndarray:
    """Normalized Walsh-Hadamard transform over the last axis of a (bm, d)
    f32 tile, d a power of two: log2(d) butterfly sweeps in registers/VMEM."""
    bm = y.shape[0]
    h = 1
    while h < d:
        y = y.reshape(bm, d // (2 * h), 2, h)
        a = y[:, :, 0, :]
        b = y[:, :, 1, :]
        y = jnp.stack([a + b, a - b], axis=2)
        h *= 2
    return y.reshape(bm, d) * (1.0 / (d**0.5))


def fwht_intra_rows(y: jnp.ndarray, bk: int) -> jnp.ndarray:
    """UNNORMALIZED butterfly sweeps h = 1..bk/2 on one (bm, bk) K-chunk.

    These are exactly the first log2(bk) sweeps of the whole-row transform:
    for h < bk a butterfly pairs elements i and i+h, which live in the same
    bk-aligned chunk, so the sweeps run chunk-local with the identical
    (a+b, a-b) operand pairing."""
    bm = y.shape[0]
    h = 1
    while h < bk:
        y = y.reshape(bm, bk // (2 * h), 2, h)
        a = y[:, :, 0, :]
        b = y[:, :, 1, :]
        y = jnp.stack([a + b, a - b], axis=2)
        h *= 2
    return y.reshape(bm, bk)


def fwht_cross_rows(y: jnp.ndarray, d: int, bk: int) -> jnp.ndarray:
    """Butterfly sweeps h = bk..d/2 across bk-chunks + the 1/sqrt(d)
    normalization, on a (bm, d) row whose chunks already went through
    :func:`fwht_intra_rows`.  ``fwht_cross_rows(intra-chunks) `` is bitwise
    equal to ``fwht_rows`` on the raw row (same scalar pairings, same op
    order, one trailing normalization multiply in both)."""
    bm = y.shape[0]
    n_c = d // bk
    z = y.reshape(bm, n_c, bk)
    g = 1
    while g < n_c:
        z = z.reshape(bm, n_c // (2 * g), 2, g, bk)
        a = z[:, :, 0]
        b = z[:, :, 1]
        z = jnp.stack([a + b, a - b], axis=2)
        g *= 2
    return z.reshape(bm, d) * (1.0 / (d**0.5))


def row_amax(x: jnp.ndarray) -> jnp.ndarray:
    """Per-token |x| max of a (bm, d) tile -> (bm, 1).  Chunk-wise folding
    with jnp.maximum reproduces the whole-row value exactly."""
    return jnp.max(jnp.abs(x), axis=-1, keepdims=True)


def snap_bk_to_group(bk: int, group: int) -> int:
    """Largest ``unit · 2^j ≤ bk`` (minimum one unit): with group-wise
    activation scales a K-chunk must hold WHOLE scale groups, so the unit
    is ``group`` — and ``lcm(group, LANES)`` once chunks are lane tiles
    (``bk ≥ LANES``; a shorter bk is the one whole-K chunk).  The
    power-of-two multiple keeps the plan layer's halving shrink-to-fit
    closed over the constraint (every halving above the unit is still a
    multiple of it)."""
    snapped = group if bk < LANES else math.lcm(group, LANES)
    while snapped * 2 <= bk:
        snapped *= 2
    return snapped


def _lane_groups(shape, group: int) -> jnp.ndarray:
    """Scale-group id of every element of a (bm, d) tile: lane // group."""
    return jax.lax.broadcasted_iota(jnp.int32, shape, 1) // group


def group_amax(x: jnp.ndarray, group: int) -> jnp.ndarray:
    """Per-group |x| max of a (bm, d) tile -> (bm, d // group).  Groups are
    contiguous along K; because chunks hold whole groups, chunk-wise
    application computes exactly the whole-row result.  Each group's max
    reduces the full-width tile with the other groups masked to 0 (|x| ≥ 0,
    so the mask is exact): Mosaic cannot split the lane dim into groups."""
    bm, d = x.shape
    assert d % group == 0, (d, group)
    ax = jnp.abs(x)
    gid = _lane_groups(x.shape, group)
    pid = _lane_groups((bm, d // group), 1)
    plane = jnp.zeros((bm, d // group), x.dtype)
    for gi in range(d // group):
        a = jnp.max(jnp.where(gid == gi, ax, 0.0), axis=-1, keepdims=True)
        plane = jnp.where(pid == gi, a, plane)
    return plane


def quantize_rows_grouped(x: jnp.ndarray, s: jnp.ndarray, qmax: int,
                          group: int) -> jnp.ndarray:
    """Elementwise q = clip(round(x/s)) with one scale per K group.  Safe to
    apply per chunk with the matching slice of the scale plane.  The plane
    is broadcast to the tile by selects (exact), not by a lane reshape."""
    bm, d = x.shape
    gid = _lane_groups(x.shape, group)
    se = jnp.zeros_like(x)
    for gi in range(d // group):
        se = jnp.where(gid == gi, s[:, gi:gi + 1], se)
    return jnp.clip(jnp.round(x / se), -qmax - 1, qmax).astype(jnp.int8)


def amax_to_scale(amax: jnp.ndarray, qmax: int, clip_ratio: float):
    """Paper §2 scale: zero-guarded amax → s = c·amax/qmax."""
    amax = jnp.where(amax <= 0.0, 1.0, amax)
    return clip_ratio * amax / qmax


def quantize_rows(x: jnp.ndarray, s: jnp.ndarray, qmax: int) -> jnp.ndarray:
    """Elementwise q = clip(round(x/s)) on the symmetric int grid — safe to
    apply per K-chunk once the whole-row scale is known."""
    return jnp.clip(jnp.round(x / s), -qmax - 1, qmax).astype(jnp.int8)


def scale_round_quantize(x: jnp.ndarray, qmax: int, clip_ratio: float,
                         group: int = None):
    """amax → scale → round (the composition of the slab bodies).  Per-token
    (``group=None``) returns (q int8, s f32 (bm, 1)); group-wise returns the
    (bm, d // group) scale plane instead."""
    if group is None:
        s = amax_to_scale(row_amax(x), qmax, clip_ratio)
        return quantize_rows(x, s, qmax), s
    s = amax_to_scale(group_amax(x, group), qmax, clip_ratio)
    return quantize_rows_grouped(x, s, qmax, group), s


def gemm_chunk_grouped(xq_chunk: jnp.ndarray, w_chunk: jnp.ndarray,
                       s_chunk: jnp.ndarray, group: int) -> jnp.ndarray:
    """ONE K-chunk of the group-rescaled int4 GEMM: per scale group in
    ascending-K order, an int8×int8→int32 dot rescaled by that group's
    activation scale, summed in f32.  xq_chunk: (bm, bk) int8; w_chunk:
    (bk, bn) int8; s_chunk: (bm, bk // group) f32.  This is THE canonical
    dequant-in-the-K-loop spelling — the fused, chained and unfused GEMMs
    all issue these dots in this order, which keeps grouped outputs bitwise
    identical across paths (cross-chunk accumulation is ascending-K f32
    adds of these per-chunk results).

    The rescale-and-sum is an unrolled multiply-add chain in ascending
    group order: Mosaic cannot lower the batched ``dot_general`` that once
    carried it.  All three paths call this one body, and the interpret-mode
    parity tests pin that they still agree bit for bit."""
    out = None
    for gi in range(xq_chunk.shape[1] // group):  # ascending K
        part = int_dot(xq_chunk[:, gi * group:(gi + 1) * group],
                       w_chunk[gi * group:(gi + 1) * group, :])
        term = part.astype(jnp.float32) * s_chunk[:, gi:gi + 1]
        out = term if out is None else out + term
    return out


def int_dot(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """(m, k) int8 × (k, n) int8 → exact (m, n) int32.  The precision is
    pinned: under a caller's ``default_matmul_precision("highest")`` Mosaic
    would be asked for an f32 contraction of integer operands, and refuses."""
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               precision=jax.lax.Precision.DEFAULT,
                               preferred_element_type=jnp.int32)


def f32_dot(a: jnp.ndarray, b: jnp.ndarray, b_contract: int = 0):
    """f32 contraction of a's last dim with b's dim ``b_contract``, at
    full f32 precision on every backend whatever the caller's default
    matmul precision (the low-rank term is computed in f32)."""
    return jax.lax.dot_general(
        a.astype(jnp.float32), b.astype(jnp.float32),
        (((1,), (b_contract,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def project_chunk_rows(x_chunk: jnp.ndarray, v_tile: jnp.ndarray):
    """ONE (bm, bk) × (bk, br) projection partial — the canonical dot every
    path issues per (K-chunk, R-tile).  f32 in, f32 out."""
    return f32_dot(x_chunk, v_tile)


def project_rows_tiled(x: jnp.ndarray, v: jnp.ndarray, bk: int, br: int):
    """The canonical K-chunked, R-tiled (x·V): per R-tile, sum the per-chunk
    dots in ascending-K order.  x: (bm, k_pad) f32, v: (k_pad, r_pad); both
    padded to the tile multiples.  This is the jnp spelling of the exact
    accumulation the kernels perform across grid steps (the unfused path
    runs THIS; the prologue/fused kernels accumulate the same
    ``project_chunk_rows`` partials in the same order)."""
    k_pad = x.shape[1]
    r_pad = v.shape[1]
    assert k_pad % bk == 0 and r_pad % br == 0, (k_pad, r_pad, bk, br)
    cols = []
    for rr in range(r_pad // br):
        acc = None
        for kk in range(k_pad // bk):
            part = project_chunk_rows(
                x[:, kk * bk:(kk + 1) * bk],
                v[kk * bk:(kk + 1) * bk, rr * br:(rr + 1) * br])
            acc = part if acc is None else acc + part
        cols.append(acc)
    return cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=1)


def prologue_rows(x, v, qmax: int, clip_ratio: float, rotate: bool, d: int,
                  group: int = None):
    """The full activation-prologue row body on a (bm, d) f32 tile: optional
    WHT rotation, per-token (or per-group) quantization, and the (x·V)
    projection.  Returns (q int8, s f32 (bm, 1) or the (bm, d // group)
    scale plane, xv f32 (bm, R) or None)."""
    if rotate:
        x = fwht_rows(x, d)
    q, s = scale_round_quantize(x, qmax, clip_ratio, group=group)
    xv = None
    if v is not None:
        xv = f32_dot(x, v)
    return q, s, xv


def dequant_rows_grouped(q: jnp.ndarray, s: jnp.ndarray,
                         group: int) -> jnp.ndarray:
    """THE canonical group dequant body: int rows (bm, d) + the (bm,
    d // group) scale plane → f32 rows, as ONE elementwise multiply over
    the group reshape.  The KV-cache path is built on this — the jnp
    paged serving gather (via ``serve.kvquant.dequantize_kv``) and the
    dequant-fused flash-attention kernels all call it, so the dequantized
    operands entering their attention math are bitwise identical (the
    same single-spelling discipline as :func:`gemm_chunk_grouped`)."""
    bm, d = q.shape
    assert d % group == 0, (d, group)
    x = q.astype(jnp.float32).reshape(bm, d // group, group) * s[..., None]
    return x.reshape(bm, d)


def even_odd_perm(bk: int) -> jnp.ndarray:
    """(bk, bk) int8 0/1 permutation taking a chunk's K columns to
    ``[0, 2, 4, … | 1, 3, 5, …]`` under a right-multiply."""
    row = jax.lax.broadcasted_iota(jnp.int32, (bk, bk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (bk, bk), 1)
    src = jnp.where(col < bk // 2, 2 * col, 2 * col - bk + 1)
    return (row == src).astype(jnp.int8)


def split_even_odd(q: jnp.ndarray, perm: jnp.ndarray) -> jnp.ndarray:
    """(bm, bk) int8 K-chunk -> ``[q[:, 0::2] | q[:, 1::2]]``, the activation
    layout the nibble planes of :func:`unpack_int4_planes` contract against.
    An exact int8 dot with ``perm = even_odd_perm(bk)``: Mosaic cannot
    legalize the lane-strided load that would say it directly."""
    return int_dot(q, perm).astype(jnp.int8)


def unpack_int4_planes(wp: jnp.ndarray):
    """(BK//2, BN) uint8 -> the two (BK//2, BN) int8 nibble planes in
    [-8, 7]: ``lo`` holds the chunk's K rows 0, 2, 4, …, ``hi`` rows
    1, 3, 5, ….  Each nibble is sign-extended by two int32 shifts, and the
    planes are never interleaved back into K order, so
    ``int_dot(xe, lo) + int_dot(xo, hi)`` over the even/odd halves of
    :func:`split_even_odd` is the chunk's exact int32 GEMM partial."""
    w = wp.astype(jnp.int32)
    lo = (w << 28) >> 28
    hi = (w << 24) >> 28
    return lo.astype(jnp.int8), hi.astype(jnp.int8)


def gemm_chunk_planes(xq_chunk: jnp.ndarray, wp: jnp.ndarray) -> jnp.ndarray:
    """ONE K-chunk of the per-token int4 GEMM from the packed bytes, without
    rebuilding K order: xq_chunk (bm, bk) int8 laid out by
    :func:`split_even_odd`, wp the (bk//2, bn) uint8 chunk.  Exact int32
    sums, so equal to ``int_dot(xq, unpack_int4_rows(wp))`` bit for bit."""
    h = xq_chunk.shape[1] // 2
    lo, hi = unpack_int4_planes(wp)
    return int_dot(xq_chunk[:, :h], lo) + int_dot(xq_chunk[:, h:], hi)


def gemm_chunk_grouped_planes(xq_chunk: jnp.ndarray, wp: jnp.ndarray,
                              s_chunk: jnp.ndarray,
                              group: int) -> jnp.ndarray:
    """:func:`gemm_chunk_grouped` from the packed bytes, without rebuilding
    K order: xq_chunk (bm, bk) int8 laid out by :func:`split_even_odd`, wp
    the (bk//2, bn) uint8 chunk, s_chunk (bm, bk // group) f32.  Group gi's
    K rows [gi·g, (gi+1)·g) are plane rows [⌈gi·g/2⌉, ⌈(gi+1)·g/2⌉) of the
    even plane and [⌊gi·g/2⌋, ⌊(gi+1)·g/2⌋) of the odd one (for an even g
    both are g/2 rows from gi·g/2), so each group's int32 partial is the
    exact sum of two plane partials and the rescale-and-sum keeps
    :func:`gemm_chunk_grouped`'s ascending order bit for bit."""
    h = xq_chunk.shape[1] // 2
    xe, xo = xq_chunk[:, :h], xq_chunk[:, h:]
    lo, hi = unpack_int4_planes(wp)
    out = None
    for gi in range(xq_chunk.shape[1] // group):  # ascending K
        a, b = gi * group, (gi + 1) * group
        ev = slice(-(-a // 2), -(-b // 2))
        od = slice(a // 2, b // 2)
        part = (int_dot(xe[:, ev], lo[ev, :])
                + int_dot(xo[:, od], hi[od, :]))
        term = part.astype(jnp.float32) * s_chunk[:, gi:gi + 1]
        out = term if out is None else out + term
    return out


def unpack_int4_rows(wp: jnp.ndarray) -> jnp.ndarray:
    """(BK//2, BN) uint8 -> (BK, BN) int8 in [-8, 7]; even rows = low nibble.
    Packed rows interleave (2i, 2i+1): stack on a new axis, then fold.  The
    nibbles are shifted out as int32: Mosaic has no 8-bit vector shift."""
    w = wp.astype(jnp.int32)
    lo = w & 0xF
    hi = (w >> 4) & 0xF
    lo = jnp.where(lo >= 8, lo - 16, lo)
    hi = jnp.where(hi >= 8, hi - 16, hi)
    bk2, bn = wp.shape
    w = jnp.stack([lo, hi], axis=1)  # (BK//2, 2, BN)
    return w.reshape(bk2 * 2, bn).astype(jnp.int8)
