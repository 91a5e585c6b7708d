"""Fused W4A4 matmul with low-rank epilogue (the paper's §5 "future work").

Computes   out = (Xq · Wq) · s_x · s_w  +  (X V) Uᵀ

  Xq       (M, K)    int8, per-token-quantized activations (int4 grid)
  s_x      (M, 1)    f32 per-token scales — or, with ``group``, the
                     (M, K//group) per-group scale plane (paper Table 2)
  Wpacked  (K//2, N) uint8 — two int4 weights per byte along K
  s_w      (1, N)    f32 per-output-channel scales
  XV       (M, R)    f32 — the small (X V) matmul, precomputed (R ≪ K)
  U        (N, R)    f32/bf16

Grid (M/BM, N/BN, K/BK); K is the reduction axis, innermost.  Per-token, the
int32 accumulator lives in a VMEM scratch and the epilogue rescales once at
the last K step.  GROUP-WISE, the dequant moves INTO the K loop: BK is a
multiple of ``group`` (chunks hold whole scale groups), each K step streams
its (BM, BK//group) slice of the scale plane and accumulates the
group-rescaled partials in an f32 scratch via the canonical
``rowops.gemm_chunk_grouped`` order — the same dots in the same order the
fused kernel issues, which keeps the paths bitwise identical.  Either way
the last K step adds the low-rank tile contribution (XV_tile @ U_tileᵀ)
before the single HBM write of the output tile — the low-rank FLOPs ride
the MXU alongside the quantized GEMM instead of a second HBM pass.

Weight unpacking happens in VMEM: low nibble = even-K rows, high = odd.
TPU adaptation notes: v5e has no int4 MXU — int4 is the STORAGE format
(halving weight HBM traffic, the decode bottleneck); compute runs
int8×int8→int32 on the MXU, matching Ampere's int4-storage/int8-compute
reality the paper measured with Cutlass.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.rowops import (f32_dot, gemm_chunk_grouped, int_dot,
                                  unpack_int4_rows as _unpack_block)


def _body(xq_ref, sx_ref, wp_ref, sw_ref, xv_ref, u_ref, out_ref, acc_ref, *,
          n_k: int, group):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w_blk = _unpack_block(wp_ref[...])  # (BK, BN) int8
    if group is None:
        acc_ref[...] += int_dot(xq_ref[...], w_blk)
    else:
        # dequant in the K loop: this chunk's groups rescaled before the
        # f32 accumulation (canonical order shared with the fused kernel)
        acc_ref[...] += gemm_chunk_grouped(xq_ref[...], w_blk, sx_ref[...],
                                           group)

    @pl.when(k == n_k - 1)
    def _epilogue():
        if group is None:
            out = acc_ref[...].astype(jnp.float32) * sx_ref[...] * sw_ref[...]
        else:
            out = acc_ref[...] * sw_ref[...]  # activation scales already in
        if xv_ref is not None:
            out = out + f32_dot(xv_ref[...], u_ref[...], b_contract=1)
        out_ref[...] = out


def _kernel_lr(xq_ref, sx_ref, wp_ref, sw_ref, xv_ref, u_ref, out_ref, acc_ref,
               *, n_k: int, group):
    _body(xq_ref, sx_ref, wp_ref, sw_ref, xv_ref, u_ref, out_ref, acc_ref,
          n_k=n_k, group=group)


def _kernel_nolr(xq_ref, sx_ref, wp_ref, sw_ref, out_ref, acc_ref, *,
                 n_k: int, group):
    _body(xq_ref, sx_ref, wp_ref, sw_ref, None, None, out_ref, acc_ref,
          n_k=n_k, group=group)


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "group", "interpret"),
)
def w4a4_lowrank_matmul_kernel(
    xq: jnp.ndarray,  # (M, K) int8
    sx: jnp.ndarray,  # (M, 1) f32 per-token, or (M, K//group) scale plane
    wpacked: jnp.ndarray,  # (K//2, N) uint8
    sw: jnp.ndarray,  # (1, N) f32
    xv,  # (M, R) f32 or None
    u,  # (N, R) or None
    bm: int = 128,
    bn: int = 128,
    bk: int = 256,
    group: int = None,  # None = per-token scales; else BK % group == 0
    *,
    interpret: bool,
):
    m, k = xq.shape
    n = wpacked.shape[1]
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    n_k = k // bk
    with_lr = xv is not None
    if group is None:
        n_sb = 1  # one per-token scale column, pinned across K steps
        sx_spec = pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0))
    else:
        assert bk % group == 0, (bk, group)  # chunks hold whole groups
        assert sx.shape[1] == k // group, (sx.shape, k, group)
        n_sb = bk // group  # this chunk's slice of the scale plane
        # (n_k, M, bk // g): a chunk's slice is a whole trailing dim, which
        # keeps the block legal where bk // g is not a lane multiple
        sx = sx.reshape(m, n_k, n_sb).transpose(1, 0, 2)
        sx_spec = pl.BlockSpec((None, bm, n_sb), lambda i, j, kk: (kk, i, 0))

    grid = (m // bm, n // bn, n_k)
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),  # xq
        sx_spec,  # sx (per-token column or per-chunk plane slice)
        pl.BlockSpec((bk // 2, bn), lambda i, j, kk: (kk, j)),  # wpacked
        pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),  # sw
    ]
    operands = [xq, sx, wpacked, sw]
    if with_lr:  # rank-0 calls simply omit the LR operands from the signature
        r = xv.shape[1]
        in_specs += [
            pl.BlockSpec((bm, r), lambda i, j, kk: (i, 0)),  # xv
            pl.BlockSpec((bn, r), lambda i, j, kk: (j, 0)),  # u
        ]
        operands += [xv, u]
        kernel = functools.partial(_kernel_lr, n_k=n_k, group=group)
    else:
        kernel = functools.partial(_kernel_nolr, n_k=n_k, group=group)

    acc_dtype = jnp.int32 if group is None else jnp.float32
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        # Mosaic pipeline: M/N tiles are independent (megacore-splittable);
        # K carries the accumulator and must stay sequential + innermost.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        name="w4a4_lowrank_matmul_kernel",
        interpret=interpret,
    )(*operands)
    return out
