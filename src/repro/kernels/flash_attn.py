"""Flash-attention forward kernel (blocked online-softmax, Pallas).

The dry-run shows prefill cells are memory-bound on the materialized
(B,H,S,S) logits (§Roofline) — e.g. gemma-7b prefill_32k moves TBs of
attention scores through HBM.  This kernel keeps each (BQ × BKV) score tile
in VMEM with running (m, l, acc) statistics, so attention bytes drop from
O(S²) to O(S·D) — the classic flash-attention restructuring, here as the
TPU-native companion of the W4A4 serving path.

Layout: q (B*H, S, D), k/v (B*H, S, D) — the wrapper folds batch/head dims
and un-groups GQA.  Causal masking is computed arithmetically per tile (no
mask tensor in HBM).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.quantizers import unpack_int4
from repro.kernels.rowops import dequant_rows_grouped

NEG_INF = -1e30


def _dequant_tile(qrows: jnp.ndarray, srows: jnp.ndarray, *, group: int,
                  packed: bool) -> jnp.ndarray:
    """Dequantize one KV tile inside the online-softmax loop: (rows,
    d_packed) int + (rows, d // group) f32 scales → (rows, d) f32, via
    the canonical ``rowops.dequant_rows_grouped`` spelling (+ the
    ``pack_int4`` nibble unpack for int4 pools).  The scale plane rides
    the loop exactly like ``gemm_chunk_grouped`` carries the activation
    scale plane through the GEMM's K loop — quantized KV never
    round-trips HBM at full width."""
    if packed:
        qrows = unpack_int4(qrows)
    return dequant_rows_grouped(qrows, srows, group)


def _kernel(q_ref, k_ref, v_ref, o_ref, *, scale: float, causal: bool,
            bq: int, bkv: int, skv: int):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale  # (BQ, D)
    d = q.shape[-1]
    m = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((bq, 1), jnp.float32)
    acc = jnp.zeros((bq, v_ref.shape[-1]), jnp.float32)  # (BQ, Dv)

    n_kv = skv // bkv

    def body(j, carry):
        m, l, acc = carry
        k = jax.lax.dynamic_slice_in_dim(k_ref[0], j * bkv, bkv, axis=0)
        v = jax.lax.dynamic_slice_in_dim(v_ref[0], j * bkv, bkv, axis=0)
        s = q @ k.astype(jnp.float32).T  # (BQ, BKV)
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
            kpos = j * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + p @ v.astype(jnp.float32)
        return m_new, l, acc

    m, l, acc = jax.lax.fori_loop(0, n_kv, body, (m, l, acc))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _paged_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref, *,
                  scale: float, page_size: int, n_pages: int):
    """One (sequence, kv-head) program of paged decode attention.

    The KV gather happens HERE, per page id from the block table — scores
    stream page-by-page through the online-softmax statistics, so a
    sequence's KV never needs to be contiguous (or even materialized
    gathered) in HBM.  Positions at and past ``length`` are masked to
    NEG_INF, which is what makes the result invariant to whatever garbage
    the unowned / null pages hold."""
    q = q_ref[0, 0].astype(jnp.float32) * scale  # (G, D) grouped queries
    bt = bt_ref[0]  # (MPB,) page ids, null page 0 past the owned prefix
    length = len_ref[0]  # valid kv positions, incl. the current token
    kpool = k_ref[0]  # (NP, P, D) this kv-head's slice of the pool
    vpool = v_ref[0]
    g = q.shape[0]
    m = jnp.full((g, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((g, 1), jnp.float32)
    acc = jnp.zeros((g, vpool.shape[-1]), jnp.float32)

    def body(j, carry):
        m, l, acc = carry
        pid = bt[j]
        k = jax.lax.dynamic_index_in_dim(kpool, pid, 0, keepdims=False)
        v = jax.lax.dynamic_index_in_dim(vpool, pid, 0, keepdims=False)
        s = q @ k.astype(jnp.float32).T  # (G, P)
        kpos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (g, page_size), 1)
        s = jnp.where(kpos < length, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + p @ v.astype(jnp.float32)
        return m_new, l, acc

    m, l, acc = jax.lax.fori_loop(0, n_pages, body, (m, l, acc))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _kernel_quant(q_ref, k_ref, ks_ref, v_ref, vs_ref, o_ref, *,
                  scale: float, causal: bool, bq: int, bkv: int, skv: int,
                  group: int, packed: bool):
    """The dense kernel body with quantized K/V: each (BKV, d_packed) tile
    and its scale rows dequantize in-loop (``_dequant_tile``) right before
    the score/accumulate dots — the rest is byte-for-byte the f32 body."""
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale  # (BQ, D)
    d = q.shape[-1]
    m = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((bq, 1), jnp.float32)
    acc = jnp.zeros((bq, d), jnp.float32)

    n_kv = skv // bkv

    def body(j, carry):
        m, l, acc = carry
        kq = jax.lax.dynamic_slice_in_dim(k_ref[0], j * bkv, bkv, axis=0)
        ks = jax.lax.dynamic_slice_in_dim(ks_ref[0], j * bkv, bkv, axis=0)
        vq = jax.lax.dynamic_slice_in_dim(v_ref[0], j * bkv, bkv, axis=0)
        vs = jax.lax.dynamic_slice_in_dim(vs_ref[0], j * bkv, bkv, axis=0)
        k = _dequant_tile(kq, ks, group=group, packed=packed)
        v = _dequant_tile(vq, vs, group=group, packed=packed)
        s = q @ k.T  # (BQ, BKV)
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
            kpos = j * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + p @ v
        return m_new, l, acc

    m, l, acc = jax.lax.fori_loop(0, n_kv, body, (m, l, acc))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _paged_kernel_quant(bt_ref, len_ref, q_ref, k_ref, ks_ref, v_ref,
                        vs_ref, o_ref, *, scale: float, page_size: int,
                        n_pages: int, group: int, packed: bool):
    """The paged gather body with quantized pages: each gathered page's
    data rows AND scale rows index through the same block-table entry, and
    the page dequantizes in-loop before the score/accumulate dots — f32 KV
    never round-trips HBM.  Masking is unchanged (dtype-independent), so
    garbage in unowned/null pages still contributes exactly 0."""
    q = q_ref[0, 0].astype(jnp.float32) * scale  # (G, D)
    bt = bt_ref[0]
    length = len_ref[0]
    kpool = k_ref[0]    # (NP, P, d_packed)
    kspool = ks_ref[0]  # (NP, P, n_groups)
    vpool = v_ref[0]
    vspool = vs_ref[0]
    g = q.shape[0]
    d = q.shape[-1]
    m = jnp.full((g, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((g, 1), jnp.float32)
    acc = jnp.zeros((g, d), jnp.float32)

    def body(j, carry):
        m, l, acc = carry
        pid = bt[j]
        kq = jax.lax.dynamic_index_in_dim(kpool, pid, 0, keepdims=False)
        ks = jax.lax.dynamic_index_in_dim(kspool, pid, 0, keepdims=False)
        vq = jax.lax.dynamic_index_in_dim(vpool, pid, 0, keepdims=False)
        vs = jax.lax.dynamic_index_in_dim(vspool, pid, 0, keepdims=False)
        k = _dequant_tile(kq, ks, group=group, packed=packed)  # (P, D)
        v = _dequant_tile(vq, vs, group=group, packed=packed)
        s = q @ k.T  # (G, P)
        kpos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (g, page_size), 1)
        s = jnp.where(kpos < length, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + p @ v
        return m_new, l, acc

    m, l, acc = jax.lax.fori_loop(0, n_pages, body, (m, l, acc))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_flash_attention_kernel(
    q: jnp.ndarray,            # (B, H, D) one decode token per sequence
    k_pages: jnp.ndarray,      # (NP, P, KH, D) shared page pool
    v_pages: jnp.ndarray,      # (NP, P, KH, Dv)
    block_table: jnp.ndarray,  # (B, MPB) int32 page ids (0 = null page)
    lengths: jnp.ndarray,      # (B,) int32 valid kv count, incl. current token
    scale: float,
    *,
    interpret: bool,
):
    """Decode attention against a PAGED KV pool (the serving engine's cache
    layout): each sequence reads its pages through its block-table row, so
    no per-request contiguous KV copy is ever materialized.  GQA is handled
    natively — grid is (B, KH) and each program computes all H/KH query
    heads of its group against one gathered page stream.  Returns (B, H, Dv).
    """
    b, h, d = q.shape
    n_pages_total, page_size, kh, dv = v_pages.shape
    g = h // kh
    mpb = block_table.shape[1]
    qg = q.reshape(b, kh, g, d)  # heads grouped by kv head
    kp = k_pages.transpose(2, 0, 1, 3)  # (KH, NP, P, D)
    vp = v_pages.transpose(2, 0, 1, 3)
    out = pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, page_size=page_size,
                          n_pages=mpb),
        grid=(b, kh),
        in_specs=[
            pl.BlockSpec((1, mpb), lambda i, j: (i, 0)),
            pl.BlockSpec((1,), lambda i, j: (i,)),
            pl.BlockSpec((1, 1, g, d), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, n_pages_total, page_size, d), lambda i, j: (j, 0, 0, 0)),
            pl.BlockSpec((1, n_pages_total, page_size, dv), lambda i, j: (j, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, dv), lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kh, g, dv), q.dtype),
        name="paged_flash_attention_kernel",
        interpret=interpret,
    )(jnp.asarray(block_table, jnp.int32), jnp.asarray(lengths, jnp.int32),
      qg, kp, vp)
    return out.reshape(b, h, dv)


@functools.partial(jax.jit, static_argnames=("scale", "causal", "bq", "bkv", "interpret"))
def flash_attention_kernel(
    q: jnp.ndarray,  # (BH, Sq, D)
    k: jnp.ndarray,  # (BH, Skv, D)
    v: jnp.ndarray,  # (BH, Skv, Dv)
    scale: float,
    causal: bool = True,
    bq: int = 128,
    bkv: int = 128,
    *,
    interpret: bool,
):
    bh, sq, d = q.shape
    skv = k.shape[1]
    assert sq % bq == 0 and skv % bkv == 0, (sq, skv, bq, bkv)
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal, bq=bq, bkv=bkv, skv=skv),
        grid=(bh, sq // bq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, skv, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, skv, v.shape[-1]), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, v.shape[-1]), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, v.shape[-1]), q.dtype),
        name="flash_attention_kernel",
        interpret=interpret,
    )(q, k, v)


@functools.partial(jax.jit, static_argnames=(
    "scale", "group", "packed", "causal", "bq", "bkv", "interpret"))
def flash_attention_quant_kernel(
    q: jnp.ndarray,         # (BH, Sq, D)
    k_quant: jnp.ndarray,   # (BH, Skv, D | D//2) int8 / packed uint8
    k_scales: jnp.ndarray,  # (BH, Skv, D // group) f32
    v_quant: jnp.ndarray,
    v_scales: jnp.ndarray,
    scale: float,
    group: int,
    packed: bool,
    causal: bool = True,
    bq: int = 128,
    bkv: int = 128,
    *,
    interpret: bool,
):
    """``flash_attention_kernel`` over quantized K/V: the scale planes ride
    as two extra inputs blocked exactly like their data tensors, and each
    tile dequantizes in VMEM — the f32 KV stream never touches HBM."""
    bh, sq, d = q.shape
    skv = k_quant.shape[1]
    dp = k_quant.shape[-1]
    n_g = k_scales.shape[-1]
    assert sq % bq == 0 and skv % bkv == 0, (sq, skv, bq, bkv)
    return pl.pallas_call(
        functools.partial(_kernel_quant, scale=scale, causal=causal, bq=bq,
                          bkv=bkv, skv=skv, group=group, packed=packed),
        grid=(bh, sq // bq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, skv, dp), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, skv, n_g), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, skv, dp), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, skv, n_g), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        name="flash_attention_quant_kernel",
        interpret=interpret,
    )(q, k_quant, k_scales, v_quant, v_scales)


@functools.partial(jax.jit, static_argnames=(
    "scale", "group", "packed", "interpret"))
def paged_flash_attention_quant_kernel(
    q: jnp.ndarray,            # (B, H, D) one decode token per sequence
    k_pages: jnp.ndarray,      # (NP, P, KH, D | D//2) int8 / packed uint8
    k_scales: jnp.ndarray,     # (NP, P, KH, D // group) f32 scale planes
    v_pages: jnp.ndarray,
    v_scales: jnp.ndarray,
    block_table: jnp.ndarray,  # (B, MPB) int32 page ids (0 = null page)
    lengths: jnp.ndarray,      # (B,) int32 valid kv count, incl. current token
    scale: float,
    group: int,
    packed: bool,
    *,
    interpret: bool,
):
    """``paged_flash_attention_kernel`` over a QUANTIZED page pool: the
    scale-plane sidecar pools ride as two extra inputs under the same
    block-table indexing, and each gathered page dequantizes in VMEM.
    Returns (B, H, D)."""
    b, h, d = q.shape
    n_pages_total, page_size, kh, dp = k_pages.shape
    n_g = k_scales.shape[-1]
    g = h // kh
    mpb = block_table.shape[1]
    qg = q.reshape(b, kh, g, d)  # heads grouped by kv head
    kp = k_pages.transpose(2, 0, 1, 3)   # (KH, NP, P, dp)
    ksp = k_scales.transpose(2, 0, 1, 3)  # (KH, NP, P, n_g)
    vp = v_pages.transpose(2, 0, 1, 3)
    vsp = v_scales.transpose(2, 0, 1, 3)
    out = pl.pallas_call(
        functools.partial(_paged_kernel_quant, scale=scale,
                          page_size=page_size, n_pages=mpb, group=group,
                          packed=packed),
        grid=(b, kh),
        in_specs=[
            pl.BlockSpec((1, mpb), lambda i, j: (i, 0)),
            pl.BlockSpec((1,), lambda i, j: (i,)),
            pl.BlockSpec((1, 1, g, d), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, n_pages_total, page_size, dp), lambda i, j: (j, 0, 0, 0)),
            pl.BlockSpec((1, n_pages_total, page_size, n_g), lambda i, j: (j, 0, 0, 0)),
            pl.BlockSpec((1, n_pages_total, page_size, dp), lambda i, j: (j, 0, 0, 0)),
            pl.BlockSpec((1, n_pages_total, page_size, n_g), lambda i, j: (j, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kh, g, d), q.dtype),
        name="paged_flash_attention_quant_kernel",
        interpret=interpret,
    )(jnp.asarray(block_table, jnp.int32), jnp.asarray(lengths, jnp.int32),
      qg, kp, ksp, vp, vsp)
    return out.reshape(b, h, d)
