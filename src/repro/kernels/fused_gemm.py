"""Single-kernel fused W4A4+LRC forward: prologue + GEMM in ONE pallas call.

PR 2 fused the activation prologue into the GEMM kernel, but its (M, N) grid
kept every K-side operand WHOLE in VMEM: the (bm, K) f32 activation slab,
the full K×R V factor, and a (K//2, bn) packed-weight column slab.  Those
residencies were the VMEM ceilings that demoted the fused path exactly in
the paper's headline regime (rank ≈ 10-30% of the weight matrix at large K)
and kept prefill on the two-kernel chain.

This version splits the reduction across the grid: (M-tile, N-visit,
K-chunk, R-tile), K/R innermost, with

  * the packed-weight slab streamed per (K-chunk, N-visit) — (bk//2, bn),
  * V streamed per (K-chunk, R-tile) — (bk, br), never whole,
  * the int4 GEMM partial-summing across K-chunks in a (bm, bn) int32
    scratch accumulator,
  * ``xv`` accumulating across K-chunks in a (bm, r_pad) f32 scratch,
    R-tile by R-tile, via the canonical ``rowops.project_chunk_rows``
    partials in ascending-K order (bitwise-shared with the chained and
    unfused paths),
  * only the inherently-resident pieces left in VMEM scratch: the int8
    ``xq`` row (bm × k_pad bytes — the point of the fusion is that it never
    touches HBM), ``sx`` and ``xv``.

N-visit 0 is the PROLOGUE SWEEP: it walks the K-chunks once before any GEMM
work (the per-token scale needs the whole row's amax before any chunk can be
quantized).  Two prologue variants trade an HBM re-read against VMEM:

  resident — the (possibly rotated) f32 row is stashed in a (bm, k_pad)
      scratch slab during the sweep (rotation REQUIRES this: the cross-chunk
      butterfly stages need every chunk; ``rowops.fwht_intra_rows`` runs per
      chunk at stash time, ``fwht_cross_rows`` at the end of the sweep —
      bitwise equal to the whole-row transform).  x is read from HBM once.
  streamed — no f32 slab: the sweep only folds the per-chunk amax, and the
      first GEMM visit re-streams the x chunks to quantize and project them
      on the fly.  One extra M×K read of x; rotate=False only.

The ops-layer per-slab feasibility model picks the variant (and shrinks
tiles) instead of demoting the path, so the fused kernel now serves all
three regimes — decode, mixed AND prefill — at any rank.

Per grid step (i, j, kk, rr), K_pad = K rounded up to bk, R_pad to br:

  j == 0          : prologue sweep (see above); no output write
  j >= 1, rr == 0 : int8×int8→int32 partial sum of xq[:, kk·bk:] against the
                    streamed weight chunk's two nibble planes into the acc
                    scratch (see NIBBLE PLANES below)
  j == 1          : xv[:, rr·br:] += x_rot chunk · V tile  (projection rides
                    the first GEMM visit, when V streams)
  last (kk, rr)   : epilogue acc·sx·sw (+ xv Uᵀ) → one HBM write of the
                    (bm, bn) output tile for N-visit j-1

K is consumed UNPADDED by the prologue math (zero pad columns are exact for
amax/quantize/project; rotation requires K = K_pad, power of two), so the
integer accumulation over padded chunks is exact and all paths stay bitwise
identical in interpret mode.

NIBBLE PLANES: packed byte row r of a weight chunk holds K rows 2r (low
nibble) and 2r+1 (high nibble).  The GEMM step never interleaves them back
into K order (a sublane gather/rotate/select per element on every grid
step): it sign-extends the two (bk/2, bn) planes by shifts and contracts
each against the matching half of the xq chunk, which the prologue stores
once per M tile as ``[even K columns | odd K columns]`` — an exact int8 dot
with a 0/1 permutation, since Mosaic refuses the lane-strided load.  Int32
sums are exact in any order, so the output keeps its bits.  The packed
weight layout is untouched.  ``traces`` counts the kernel's traces by
spelling.

GROUP-WISE activation scales (``act_group``, paper Table 2 g = 128): the
(bm, 1) per-token scale becomes the (bm, K_pad/g) scale plane in the same
VMEM scratch, with bk a multiple of g so a K-chunk always holds whole
groups.  The prologue sweep computes each chunk's group scales CHUNK-LOCALLY
(grouped amax needs no cross-chunk fold — the streamed variant drops its
fold entirely), and the dequant moves from the epilogue into the K loop:
each GEMM chunk's int32 group partials rescale by their group's activation
scale before the f32 accumulation (``rowops.gemm_chunk_grouped``, the
canonical order shared with the chained/unfused GEMM), so the accumulator
scratch is f32 and the epilogue multiplies only the weight scales.  A
group's rows stay contiguous in each nibble plane, so its partial is the
sum of two exact plane partials (``rowops.gemm_chunk_grouped_planes``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.rowops import (
    amax_to_scale,
    default_proj_tiles,
    even_odd_perm,
    f32_dot,
    fwht_cross_rows,
    fwht_intra_rows,
    gemm_chunk_grouped_planes,
    gemm_chunk_planes,
    group_amax,
    project_chunk_rows,
    quantize_rows,
    quantize_rows_grouped,
    row_amax,
    split_even_odd,
)

_VARIANTS = ("resident", "streamed")

# Kernel traces by the GEMM step's unpack spelling (counted at trace time,
# not per call): "planes" contracts the packed chunk's two nibble planes
# against the even/odd-split xq chunk, for every scale group.
traces = {"planes": 0}


def _body(x_ref, v_ref, wp_ref, sw_ref, u_ref, out_ref,
          xq_s, sx_s, xv_s, rot_s, acc_s, *,
          qmax: int, clip_ratio: float, rotate: bool, resident: bool,
          k_pad: int, bk: int, br: int, n_k: int, n_r: int, group):
    j = pl.program_id(1)
    kk = pl.program_id(2)
    rr = pl.program_id(3)
    last_kr = (kk == n_k - 1) & (rr == n_r - 1)

    # ---- prologue sweep (N-visit 0) -------------------------------------
    if resident:
        @pl.when((j == 0) & (rr == 0))
        def _stash():
            xc = x_ref[...].astype(jnp.float32)
            if rotate:
                xc = fwht_intra_rows(xc, bk)
            rot_s[:, pl.ds(kk * bk, bk)] = xc

        @pl.when((j == 0) & last_kr)
        def _finalize():
            row = rot_s[...]
            if rotate:
                row = fwht_cross_rows(row, k_pad, bk)
                rot_s[...] = row
            perm = even_odd_perm(bk)
            if group is None:
                s = amax_to_scale(row_amax(row), qmax, clip_ratio)
                sx_s[...] = s

                def put(c, carry):
                    cols = pl.ds(pl.multiple_of(c * bk, bk), bk)
                    xq_s[:, cols] = split_even_odd(
                        quantize_rows(rot_s[:, cols], s, qmax), perm)
                    return carry

                jax.lax.fori_loop(0, n_k, put, 0)
            else:
                # groups never cross a chunk: scale and quantize chunk by
                # chunk into the (n_k, bm, bk // g) scale-plane scratch
                for c in range(n_k):
                    chunk = row[:, c * bk:(c + 1) * bk]
                    s = amax_to_scale(group_amax(chunk, group), qmax,
                                      clip_ratio)
                    sx_s[c] = s
                    xq_s[:, c * bk:(c + 1) * bk] = split_even_odd(
                        quantize_rows_grouped(chunk, s, qmax, group), perm)
    elif group is None:
        @pl.when((j == 0) & (rr == 0))
        def _fold_amax():
            a = row_amax(x_ref[...].astype(jnp.float32))
            prev = jnp.where(kk == 0, jnp.zeros_like(a), sx_s[...])
            amax = jnp.maximum(prev, a)
            # the last chunk's fold doubles as the scale conversion
            sx_s[...] = jnp.where(kk == n_k - 1,
                                  amax_to_scale(amax, qmax, clip_ratio), amax)

        @pl.when((j == 1) & (rr == 0))
        def _quantize_chunk():
            xq_s[:, pl.ds(kk * bk, bk)] = split_even_odd(quantize_rows(
                x_ref[...].astype(jnp.float32), sx_s[...], qmax),
                even_odd_perm(bk))
    else:
        # streamed + grouped: groups never cross a chunk, so each chunk's
        # scales finalize chunk-locally on the sweep — no cross-chunk fold
        @pl.when((j == 0) & (rr == 0))
        def _group_scales():
            a = group_amax(x_ref[...].astype(jnp.float32), group)
            sx_s[kk] = amax_to_scale(a, qmax, clip_ratio)

        @pl.when((j == 1) & (rr == 0))
        def _quantize_chunk_grouped():
            xq_s[:, pl.ds(kk * bk, bk)] = split_even_odd(
                quantize_rows_grouped(x_ref[...].astype(jnp.float32),
                                      sx_s[kk], qmax, group),
                even_odd_perm(bk))

    # ---- low-rank projection rides the first GEMM visit (V streams) -----
    if xv_s is not None:
        @pl.when(j == 1)
        def _project():
            xc = (rot_s[:, pl.ds(kk * bk, bk)] if resident
                  else x_ref[...].astype(jnp.float32))
            part = project_chunk_rows(xc, v_ref[...])
            prev = xv_s[:, pl.ds(rr * br, br)]
            xv_s[:, pl.ds(rr * br, br)] = jnp.where(kk == 0, part, prev + part)

    # ---- int4 GEMM partial sum over the K-chunks -------------------------
    @pl.when((j >= 1) & (rr == 0))
    def _gemm_chunk():
        @pl.when(kk == 0)
        def _zero():
            acc_s[...] = jnp.zeros_like(acc_s)

        # the chunk's nibble planes against its even/odd xq halves: no
        # sublane interleave back into K order
        xq = xq_s[:, pl.ds(kk * bk, bk)]
        if group is None:
            acc_s[...] += gemm_chunk_planes(xq, wp_ref[...])
        else:
            # dequant in the K loop: the chunk's groups rescale before the
            # f32 accumulation (canonical gemm_chunk_grouped order)
            acc_s[...] += gemm_chunk_grouped_planes(xq, wp_ref[...],
                                                    sx_s[kk], group)

    # ---- epilogue: one HBM write per (M-tile, N-tile) --------------------
    @pl.when((j >= 1) & last_kr)
    def _epilogue():
        if group is None:
            out = acc_s[...].astype(jnp.float32) * sx_s[...] * sw_ref[...]
        else:
            out = acc_s[...] * sw_ref[...]  # activation scales already in
        if xv_s is not None:
            out = out + f32_dot(xv_s[...], u_ref[...], b_contract=1)
        out_ref[...] = out


@functools.partial(
    jax.jit,
    static_argnames=("bits", "clip_ratio", "rotate", "bm", "bn", "bk", "br",
                     "variant", "act_group", "interpret"),
)
def fused_w4a4_lrc_kernel(
    x: jnp.ndarray,  # (M, K) float — K UNPADDED (prologue semantics)
    v,  # (K, R) f32 or None
    wpacked: jnp.ndarray,  # (Kp//2, N) uint8, Kp = K rounded up to bk
    sw: jnp.ndarray,  # (1, N) f32
    u,  # (N, R) f32 or None
    bits: int = 4,
    clip_ratio: float = 1.0,
    rotate: bool = False,
    bm: int = 128,
    bn: int = 128,
    bk: int = 256,
    br: int = None,  # R-tile of the streamed V (default: rowops lane tile)
    variant: str = "resident",  # resident | streamed prologue (see module doc)
    act_group: int = None,  # None = per-token scales; else bk % act_group == 0
    *,
    interpret: bool,
):
    """One pallas call for the whole W4A4+LRC forward; returns (M, N) f32."""
    m, k = x.shape
    k_pad = wpacked.shape[0] * 2
    n = wpacked.shape[1]
    assert m % bm == 0 and n % bn == 0 and k_pad % bk == 0, \
        (m, n, k, k_pad, bm, bn, bk)
    assert k_pad >= k, (k_pad, k)
    assert variant in _VARIANTS, variant
    resident = variant == "resident"
    if rotate:
        assert k & (k - 1) == 0, \
            f"online rotation needs power-of-two K, got {k}"
        assert k_pad == k, (k, k_pad)
        assert resident, "rotation's cross-chunk butterflies need the " \
                         "resident row slab"
    if act_group is not None:
        # chunks hold whole scale groups; pad K columns form whole (exact)
        # zero groups whose guarded scale quantizes them to 0
        assert k % act_group == 0, (k, act_group)
        assert bk % act_group == 0, (bk, act_group)
    qmax = 2 ** (bits - 1) - 1
    with_lr = v is not None
    traces["planes"] += 1

    if k_pad > k:
        x = jnp.pad(x, ((0, 0), (0, k_pad - k)))

    r_pad = 0
    if with_lr:
        r = v.shape[1]
        br = default_proj_tiles(k, r, bk, br)[1]
        r_pad = r + (-r) % br
        v = jnp.asarray(v, jnp.float32)
        if (k_pad > k) or (r_pad > r):
            v = jnp.pad(v, ((0, k_pad - k), (0, r_pad - r)))
        if r_pad > r:
            u = jnp.pad(jnp.asarray(u, jnp.float32), ((0, 0), (0, r_pad - r)))
    n_k = k_pad // bk
    n_r = max(r_pad // br, 1) if with_lr else 1

    # N-visit 0 is the prologue sweep; visits 1..n/bn do GEMM work for
    # output column j-1.
    grid = (m // bm, n // bn + 1, n_k, n_r)
    kw = dict(qmax=qmax, clip_ratio=clip_ratio, rotate=rotate,
              resident=resident, k_pad=k_pad, bk=bk, br=br, n_k=n_k, n_r=n_r,
              group=act_group)

    # x chunks stream during the prologue sweep (and, for the streamed
    # variant, again on the first GEMM visit); later visits pin chunk 0 so
    # consecutive fetches dedupe.
    x_reads = (lambda j: j == 0) if resident else (lambda j: j <= 1)
    in_specs = [
        pl.BlockSpec((bm, bk),
                     lambda i, j, kk, rr: (i, jnp.where(x_reads(j), kk, 0))),
    ]
    operands = [x]
    if with_lr:
        in_specs.append(pl.BlockSpec(
            (bk, br),
            lambda i, j, kk, rr: (jnp.where(j == 1, kk, 0),
                                  jnp.where(j == 1, rr, 0))))  # V tile
        operands.append(v)
    in_specs += [
        pl.BlockSpec((bk // 2, bn),
                     lambda i, j, kk, rr: (jnp.where(j == 0, 0, kk),
                                           jnp.maximum(j - 1, 0))),  # W chunk
        pl.BlockSpec((1, bn),
                     lambda i, j, kk, rr: (0, jnp.maximum(j - 1, 0))),  # sw
    ]
    operands += [wpacked, sw]
    scratch = [
        pltpu.VMEM((bm, k_pad), jnp.int8),  # xq residency
        # sx: per-token column (amax accumulator first on the streamed
        # sweep) or the per-group scale plane, one (bm, bk // g) slab per
        # K-chunk so the GEMM visit indexes it on the leading dim
        pltpu.VMEM((bm, 1) if act_group is None
                   else (n_k, bm, bk // act_group), jnp.float32),
    ]
    if with_lr:
        in_specs.append(pl.BlockSpec(
            (bn, r_pad), lambda i, j, kk, rr: (jnp.maximum(j - 1, 0), 0)))  # U
        operands.append(u)
        scratch.append(pltpu.VMEM((bm, r_pad), jnp.float32))  # xv accumulator
    if resident:
        scratch.append(pltpu.VMEM((bm, k_pad), jnp.float32))  # f32 row slab
    # GEMM partial sums: int32 per-token (rescale in the epilogue); f32
    # grouped (each chunk's groups rescale before accumulation)
    scratch.append(pltpu.VMEM(
        (bm, bn), jnp.int32 if act_group is None else jnp.float32))

    def kernel(*refs):
        i = 0
        x_ref = refs[i]; i += 1
        v_ref = None
        if with_lr:
            v_ref = refs[i]; i += 1
        wp_ref = refs[i]; i += 1
        sw_ref = refs[i]; i += 1
        u_ref = None
        if with_lr:
            u_ref = refs[i]; i += 1
        out_ref = refs[i]; i += 1
        xq_s = refs[i]; i += 1
        sx_s = refs[i]; i += 1
        xv_s = None
        if with_lr:
            xv_s = refs[i]; i += 1
        rot_s = None
        if resident:
            rot_s = refs[i]; i += 1
        acc_s = refs[i]
        _body(x_ref, v_ref, wp_ref, sw_ref, u_ref, out_ref,
              xq_s, sx_s, xv_s, rot_s, acc_s, **kw)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn),
                               lambda i, j, kk, rr: (i, jnp.maximum(j - 1, 0))),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=scratch,
        # M tiles are independent (megacore-splittable); the N/K/R visits of
        # one M tile share the prologue's scratch residency and the partial
        # sums, and must stay sequential.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary",
                                 "arbitrary"),
        ),
        name="fused_w4a4_lrc_kernel",
        interpret=interpret,
    )(*operands)
