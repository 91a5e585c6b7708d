"""Fused activation prologue: rotate -> quantize -> low-rank project.

The W4A4+LRC serving path needs three activation-side products before the
quantized GEMM can run:

  x_rot = x @ H          (QuaRot online Walsh-Hadamard rotation, optional)
  xq,sx = Q_a(x_rot)     (per-token int4-grid quantization, paper §2)
  xv    = x_rot @ V      (the low-rank projection half of (xV)Uᵀ)

Unfused these are three independent HBM passes over the activations (plus a
rotated-x round-trip) — exactly the "data movement is important" regime the
paper's §5 measures as a 23-52% latency tax, and LQER identifies as
activation-bandwidth-bound at decode batch sizes.  This kernel performs all
three on a row tile of ``x`` held in VMEM: the grid walks M tiles once, each
tile is read from HBM a single time, and ``xq``/``sx``/``xv`` are emitted
directly — no rotated-x or float intermediate ever returns to HBM.

V streaming (K-chunked, R-tiled)
--------------------------------

V is NOT held whole in VMEM: the grid is (M-tile, K-chunk, R-tile) and V
arrives in (bk, br) tiles, so the resident V footprint is one tile instead
of the full K×R×4 bytes — the 8 MB ceiling that used to demote rank ≥ 1024
at large K to the unfused path is gone.  ``xv`` accumulates directly in its
(bm, r_pad) output block (revisited across the K/R steps of one M tile) via
the canonical ``rowops.project_chunk_rows`` partials in ascending-K order —
the SAME dots in the SAME order the single-kernel fused path and the
unfused ``project_rows_tiled`` issue, which is what keeps all three paths
bitwise identical.  ``xq``/``sx`` are computed whole-row on the first
(K-chunk 0, R-tile 0) visit — the x row slab is VMEM-resident anyway.

Semantics are bit-identical to the three-pass reference chain
(`hadamard.fwht_kernel` → `actquant.act_quant_kernel` → tiled ``x_rot @ V``)
for float32 inputs: the butterfly, the amax guard, and the scale-then-round
all reuse the same rowops bodies.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.rowops import (
    default_proj_tiles,
    fwht_rows,
    project_chunk_rows,
    prologue_rows,
    scale_round_quantize,
    snap_bk_to_group,
)


def _kernel_lr(x_ref, v_ref, q_ref, s_ref, xv_ref, rot_ref, *,
               qmax: int, clip_ratio: float, rotate: bool,
               k: int, bk: int, br: int, group):
    kk = pl.program_id(1)
    rr = pl.program_id(2)

    @pl.when((kk == 0) & (rr == 0))
    def _quantize():
        row = x_ref[...].astype(jnp.float32)
        if rotate:
            row = fwht_rows(row, k)
            rot_ref[...] = row
        q, s = scale_round_quantize(row, qmax, clip_ratio, group=group)
        q_ref[...] = q
        s_ref[...] = s

    src = rot_ref if rotate else x_ref
    chunk = src[:, pl.ds(kk * bk, bk)].astype(jnp.float32)
    part = project_chunk_rows(chunk, v_ref[...])
    prev = xv_ref[:, pl.ds(rr * br, br)]
    xv_ref[:, pl.ds(rr * br, br)] = jnp.where(kk == 0, part, prev + part)


def _kernel_nolr(x_ref, q_ref, s_ref, *,
                 qmax: int, clip_ratio: float, rotate: bool, d: int, group):
    q, s, _ = prologue_rows(x_ref[...].astype(jnp.float32), None,
                            qmax, clip_ratio, rotate, d, group=group)
    q_ref[...] = q
    s_ref[...] = s


@functools.partial(
    jax.jit,
    static_argnames=("bits", "clip_ratio", "rotate", "bm", "bk", "br",
                     "act_group", "interpret"),
)
def fused_prologue_kernel(
    x: jnp.ndarray,  # (M, K)
    v,  # (K, R) or None
    bits: int = 4,
    clip_ratio: float = 1.0,
    rotate: bool = False,
    bm: int = 128,
    bk: int = None,  # V-stream K-chunk (defaults per default_proj_tiles)
    br: int = None,  # V-stream R-tile
    act_group: int = None,  # None = per-token scales; else one per K group
    *,
    interpret: bool,
):
    """One grid pass over row tiles: returns (xq int8, sx f32[, xv f32]).

    ``sx`` is the (M, 1) per-token scale, or — with ``act_group`` — the
    (M, K // act_group) per-group scale plane (groups contiguous along K,
    computed from the VMEM-resident row with the shared rowops bodies).
    ``rotate`` applies the normalized WHT over K (requires K a power of two)
    before quantization and projection, matching fwht_kernel → act_quant_kernel
    → the tiled x_rot @ V run back-to-back.  With a low-rank V the grid is
    (M-tile, K-chunk, R-tile) and V streams in (bk, br) tiles — it is never
    whole in VMEM.
    """
    m, k = x.shape
    assert m % bm == 0, (m, bm)
    if rotate:
        assert k & (k - 1) == 0, f"online rotation needs power-of-two K, got {k}"
    if act_group is not None:
        assert k % act_group == 0, (k, act_group)
    n_s = 1 if act_group is None else k // act_group
    qmax = 2 ** (bits - 1) - 1

    if v is None:
        grid = (m // bm,)
        q, s = pl.pallas_call(
            functools.partial(_kernel_nolr, qmax=qmax, clip_ratio=clip_ratio,
                              rotate=rotate, d=k, group=act_group),
            grid=grid,
            in_specs=[pl.BlockSpec((bm, k), lambda i: (i, 0))],
            out_specs=[
                pl.BlockSpec((bm, k), lambda i: (i, 0)),
                pl.BlockSpec((bm, n_s), lambda i: (i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((m, k), jnp.int8),
                jax.ShapeDtypeStruct((m, n_s), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",)),
            name="fused_prologue_kernel",
            interpret=interpret,
        )(x)
        return q, s, None

    r = v.shape[1]
    bk, br = default_proj_tiles(k, r, bk, br)
    if act_group is not None:
        bk = snap_bk_to_group(bk, act_group)  # chunks hold whole groups
    k_pad = k + (-k) % bk
    r_pad = r + (-r) % br
    n_s_pad = 1 if act_group is None else k_pad // act_group
    if rotate:
        assert k_pad == k, (k, bk)  # pow2 K, pow2 bk ≤ K always divides
    if k_pad > k:
        x = jnp.pad(x, ((0, 0), (0, k_pad - k)))
    vp = jnp.asarray(v, jnp.float32)
    if (k_pad > k) or (r_pad > r):
        vp = jnp.pad(vp, ((0, k_pad - k), (0, r_pad - r)))

    grid = (m // bm, k_pad // bk, r_pad // br)
    scratch = []
    if rotate:
        scratch.append(pltpu.VMEM((bm, k_pad), jnp.float32))  # rotated row

    def kernel(x_ref, v_ref, q_ref, s_ref, xv_ref, *rest):
        rot_ref = rest[0] if rotate else None
        _kernel_lr(x_ref, v_ref, q_ref, s_ref, xv_ref, rot_ref,
                   qmax=qmax, clip_ratio=clip_ratio, rotate=rotate,
                   k=k, bk=bk, br=br, group=act_group)

    q, s, xv = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            # x row slab: same block for every (kk, rr) visit of one M tile
            pl.BlockSpec((bm, k_pad), lambda i, kk, rr: (i, 0)),
            pl.BlockSpec((bk, br), lambda i, kk, rr: (kk, rr)),  # V tile
        ],
        out_specs=[
            pl.BlockSpec((bm, k_pad), lambda i, kk, rr: (i, 0)),
            pl.BlockSpec((bm, n_s_pad), lambda i, kk, rr: (i, 0)),
            # xv doubles as the accumulator: revisited across (kk, rr)
            pl.BlockSpec((bm, r_pad), lambda i, kk, rr: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, k_pad), jnp.int8),
            jax.ShapeDtypeStruct((m, n_s_pad), jnp.float32),
            jax.ShapeDtypeStruct((m, r_pad), jnp.float32),
        ],
        scratch_shapes=scratch,
        # M tiles are independent; the (kk, rr) visits of one M tile share
        # the xv block residency and must stay sequential.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        name="fused_prologue_kernel",
        interpret=interpret,
    )(x, vp)
    return q[:, :k], s[:, :n_s], xv[:, :r]
