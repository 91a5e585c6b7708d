"""jit'd public wrappers around the Pallas kernels.

Responsibilities: shape padding to block multiples (weights, scales and the
low-rank factors are zero-padded, so odd MLP widths never crash the pallas
path), execution-plan selection per serving regime (decode / mixed /
prefill) — kernel path AND (BM, BN, BK, BR) tiles —, per-slab VMEM
feasibility (tiles shrink to fit the budget before the path ever demotes),
interpret-mode selection (interpret=True on CPU — validates the kernel
bodies; compiled Mosaic on real TPU), and the end-to-end entry
``w4a4_lrc_forward`` used by ``QLinear(impl="pallas"/"fused")`` and the
serving engine.

ALL execution config lives in an explicit, immutable
:class:`~repro.kernels.context.KernelContext` (block table, VMEM budgets,
default impl, interpret flag, per-layer plan overrides) threaded through
every entry point as ``ctx=``.  ``ctx=None`` falls back to the
process-default context (:func:`default_context`), which is itself an
immutable value — two engines holding different contexts never race each
other.  Build contexts with ``KernelContext()``,
``KernelContext.from_json("results/block_table.json")`` and the
``with_*`` builders; introspect plan resolution with ``ctx.explain(...)``.

Three kernel paths, strongest fusion first:

  fused   — ONE pallas kernel (kernels/fused_gemm.py): K-split (M, N,
            K-chunk, R-tile) grid; the activation prologue sweeps the
            K-chunks on each M-tile's first N visit, the int4 GEMM
            partial-sums across the same chunks, and V/W stream per chunk —
            no operand slab is whole in VMEM and xq never touches HBM.
            Two prologue variants: "resident" (f32 row slab in scratch, one
            x read; required for rotation) and "streamed" (no slab, one
            extra x read).
  chained — TWO kernels (prologue → w4a4 GEMM); xq/sx/xv make one HBM
            round-trip between them.  V streams in (bk, br) tiles here too.
  unfused — three activation passes (rotate, quantize, tiled project) + the
            GEMM kernel.  Final fallback when even the prologue kernel's
            row slab cannot fit.

All three are bitwise identical in interpret mode: they share the row bodies
in kernels/rowops.py (including the canonical K-chunked/R-tiled projection
accumulation order) and integer accumulation is exact under any K split.
That parity contract covers BOTH scale granularities: per-token (M, 1)
scales and — when ``act_spec.group_size`` is set (paper Table 2, g = 128) —
the per-group (M, K/g) scale plane, with the plan layer snapping BK to a
multiple of g so K-chunks hold whole scale groups (see
:meth:`KernelContext.resolve_plan`).

The old module-global mutators (``load_block_table`` / ``set_vmem_budgets``)
finished their one-release deprecation window and are GONE — build a
:class:`KernelContext` (``from_json`` / ``with_vmem_budgets``) and pass it
via ``ctx=``.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.core.quantizers import QuantSpec
from repro.kernels.actquant import act_quant_kernel
from repro.kernels.context import (
    DEFAULT_BLOCK_TABLE,
    FUSED_VMEM_BYTES_MAX,
    KERNEL_PATHS,
    PROLOGUE_V_BYTES_MAX,
    KernelContext,
    Plan,
    fused_vmem_bytes as _fused_vmem_bytes,
    gemm_regime,
    prologue_vmem_bytes as _prologue_vmem_bytes,
)
from repro.kernels.fused_gemm import fused_w4a4_lrc_kernel
from repro.kernels.hadamard import fwht_kernel
from repro.kernels.prologue import fused_prologue_kernel
from repro.kernels.rowops import (lane_tile, project_rows_tiled,
                                  snap_bk_to_group)
from repro.kernels.w4a4 import w4a4_lowrank_matmul_kernel
from repro.kernels.flash_attn import (flash_attention_kernel,
                                      flash_attention_quant_kernel,
                                      paged_flash_attention_kernel,
                                      paged_flash_attention_quant_kernel)

__all__ = [
    "KernelContext", "Plan", "gemm_regime", "default_context",
    "set_default_context", "select_plan", "select_blocks", "resolve_plan",
    "fused_variant", "fused_vmem_budget", "prologue_vmem_budget",
    "w4a4_lrc_forward", "w4a4_lowrank_matmul", "act_quant", "fwht",
    "fused_prologue", "flash_attention", "flash_attention_quant",
    "paged_flash_attention", "paged_flash_attention_quant",
    # process-default reset (alias of set_default_context(None), used by
    # tests and legacy scripts)
    "reset_block_table",
]

# Back-compat aliases for the analytic default constants (immutable).
_FUSED_VMEM_BYTES_MAX = FUSED_VMEM_BYTES_MAX
_PROLOGUE_V_BYTES_MAX = PROLOGUE_V_BYTES_MAX
_KERNEL_PATHS = KERNEL_PATHS

# ---------------------------------------------------------------------------
# process-default context (the ONLY module state; an immutable value swapped
# atomically — set_default_context is the only writer, every reader goes
# through default_context())
# ---------------------------------------------------------------------------

_DEFAULT_CONTEXT: Optional[KernelContext] = None


def default_context() -> KernelContext:
    """The process-default :class:`KernelContext`, used whenever an entry
    point is called with ``ctx=None``.  Prefer constructing and passing an
    explicit context; this exists so zero-config callers keep working."""
    global _DEFAULT_CONTEXT
    if _DEFAULT_CONTEXT is None:
        _DEFAULT_CONTEXT = KernelContext()
    return _DEFAULT_CONTEXT


def set_default_context(ctx: Optional[KernelContext]) -> KernelContext:
    """Swap the process-default context (``None`` resets to the analytic
    defaults).  Returns the PREVIOUS default so callers can restore it."""
    global _DEFAULT_CONTEXT
    prev = default_context()
    if ctx is not None and not isinstance(ctx, KernelContext):
        raise TypeError(f"expected a KernelContext or None, got "
                        f"{type(ctx).__name__}")
    _DEFAULT_CONTEXT = ctx
    return prev


def _ctx(ctx: Optional[KernelContext]) -> KernelContext:
    return default_context() if ctx is None else ctx


def reset_block_table():
    """Reset the process-default context to the analytic defaults.  Alias
    of ``set_default_context(None)`` — note this resets the WHOLE default
    context (block table, budgets, impl, interpret, layer overrides), a
    superset of what the pre-KernelContext version cleared."""
    set_default_context(None)


def fused_vmem_budget(ctx: KernelContext = None) -> int:
    return _ctx(ctx).fused_vmem_bytes


def prologue_vmem_budget(ctx: KernelContext = None) -> int:
    return _ctx(ctx).prologue_vmem_bytes


def _interpret(ctx: KernelContext = None) -> bool:
    return _ctx(ctx).interpret_mode()


def _pad_to(x, mult, axis):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x, size
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), size


# ---------------------------------------------------------------------------
# execution-plan selection / resolution (thin wrappers over the context)
# ---------------------------------------------------------------------------


def select_plan(m: int, k: int, n: int, r: int = 0, regime: str = None,
                ctx: KernelContext = None, layer: str = None) -> Plan:
    """Table execution :class:`Plan` for a (M, K, N, R) problem — per-layer
    override merged over the regime entry, no VMEM feasibility applied (see
    :func:`resolve_plan`).  ``regime`` overrides the M-derived serving
    regime; unknown strings raise."""
    return _ctx(ctx).select_plan(m, k, n, r, regime=regime, layer=layer)


def select_blocks(m: int, k: int, n: int, r: int = 0, regime: str = None,
                  ctx: KernelContext = None, layer: str = None) -> Plan:
    """:class:`Plan` for a (M, K, N, R) problem (alias of
    :func:`select_plan`; read the tiles off ``.bm/.bn/.bk/.br``)."""
    return select_plan(m, k, n, r, regime=regime, ctx=ctx, layer=layer)


def resolve_plan(m: int, k: int, n: int, r: int = 0, rotate: bool = False,
                 regime: str = None, ctx: KernelContext = None,
                 layer: str = None, act_group: int = None) -> Plan:
    """The executable :class:`Plan` for a (M, K, N, R) problem: the
    block-table plan with per-slab VMEM feasibility applied — tiles shrink
    to fit the budget first; the path demotes (fused → chained → unfused)
    only when no tiling fits.  ``act_group`` (per-group activation scales)
    snaps BK to a lane-tile multiple of the group and adds the (M, K/g)
    scale plane to the working-set model."""
    return _ctx(ctx).resolve_plan(m, k, n, r, rotate=rotate, regime=regime,
                                  layer=layer, act_group=act_group)


def fused_variant(k: int, r: int, bm: int, bn: int, bk: int, br: int,
                  rotate: bool, ctx: KernelContext = None,
                  act_group: int = None) -> str:
    """Prologue variant for FORCED-fused execution at fixed tiles: resident
    when it fits the budget (or rotation requires it), else streamed."""
    return _ctx(ctx).fused_variant(k, r, bm, bn, bk, br, rotate,
                                   act_group=act_group)


# ---------------------------------------------------------------------------
# single-kernel wrappers
# ---------------------------------------------------------------------------


def act_quant(x: jnp.ndarray, spec: QuantSpec, bm: int = 128,
              ctx: KernelContext = None):
    """Activation quantization. x: (M, K) -> (q int8, s).  Per-token
    (``spec.group_size`` None) s is (M, 1); per-group it is the
    (M, K // group) scale plane (K must divide into whole groups)."""
    if spec.group_size is not None:
        assert x.shape[-1] % spec.group_size == 0, \
            f"act_group {spec.group_size} must divide K={x.shape[-1]}"
    xp, m = _pad_to(x, bm, 0)
    q, s = act_quant_kernel(
        xp, bits=spec.bits, clip_ratio=spec.clip_ratio, bm=bm,
        group=spec.group_size, interpret=_interpret(ctx),
    )
    return q[:m], s[:m]


def fwht(x: jnp.ndarray, bm: int = 256, ctx: KernelContext = None):
    xp, m = _pad_to(x, bm, 0)
    return fwht_kernel(xp, bm=bm, interpret=_interpret(ctx))[:m]


def fused_prologue(x: jnp.ndarray, v, spec: QuantSpec,
                   rotate: bool = False, bm: int = 128,
                   bk: int = None, br: int = None,
                   ctx: KernelContext = None):
    """Single-HBM-pass activation prologue: optional WHT rotation, per-token
    or per-group quantization, and the (x·V) projection, from one row-tile
    read of x.  V streams in (bk, br) tiles — it is never whole in VMEM.

    x: (M, K); v: (K, R) or None.  Returns (xq, sx, xv-or-None) — sx is
    (M, 1) per-token or the (M, K // group) scale plane."""
    if spec.group_size is not None:
        assert x.shape[-1] % spec.group_size == 0, \
            f"act_group {spec.group_size} must divide K={x.shape[-1]}"
    xp, m = _pad_to(x, bm, 0)
    q, s, xv = fused_prologue_kernel(
        xp, None if v is None else jnp.asarray(v, jnp.float32),
        bits=spec.bits, clip_ratio=spec.clip_ratio, rotate=rotate, bm=bm,
        bk=bk, br=br, act_group=spec.group_size, interpret=_interpret(ctx),
    )
    return q[:m], s[:m], None if xv is None else xv[:m]


# ---------------------------------------------------------------------------
# W4A4 + LRC forward (fused / chained / unfused)
# ---------------------------------------------------------------------------


def _pad_gemm_operands(xq, sx, wpacked, w_scale, u, xv, bm, bn, bk, br,
                       act_group=None):
    """Zero-pad every GEMM operand to its block multiple.  Zero weight
    nibbles/scales/U-rows contribute nothing, so padded K/N/R columns are
    exact; padded M rows are sliced off the output.  With group-wise scales
    the (M, K/g) plane pads along the group axis too — padded groups hold
    only zero xq columns, so their int32 partials are 0 and the rescaled
    term is an exact f32 +0.0 whatever the pad scale value."""
    xqp, _ = _pad_to(xq, bm, 0)
    xqp, _ = _pad_to(xqp, bk, 1)
    sxp, _ = _pad_to(sx, bm, 0)
    if act_group is not None:
        sxp, _ = _pad_to(sxp, bk // act_group, 1)
    wp, _ = _pad_to(wpacked, bk // 2, 0)  # K//2 rows
    wp, _ = _pad_to(wp, bn, 1)
    sw, _ = _pad_to(w_scale.reshape(1, -1), bn, 1)
    if u is not None:
        u, _ = _pad_to(jnp.asarray(u, jnp.float32), bn, 0)
        u, _ = _pad_to(u, br, 1)  # R-tile multiple: same epilogue dot shape
        xv, _ = _pad_to(xv, bm, 0)
        xv, _ = _pad_to(xv, br, 1)
    return xqp, sxp, wp, sw, u, xv


def _project_tiles(xr, v, bm: int, bk: int, br: int):
    """(x·V) for the unfused fallback, computed per (bm, K) row tile with
    EXACTLY the K-chunked/R-tiled accumulation the kernels issue
    (rowops.project_rows_tiled) — keeps the three paths bitwise identical.
    Returns the (M, r_pad) projection (padded R columns are exact zeros)."""
    k = xr.shape[1]
    k_pad = k + (-k) % bk
    r = v.shape[1]
    r_pad = r + (-r) % br
    xrp = jnp.pad(xr.astype(jnp.float32), ((0, 0), (0, k_pad - k)))
    vp = jnp.pad(jnp.asarray(v, jnp.float32),
                 ((0, k_pad - k), (0, r_pad - r)))
    tiles = [
        project_rows_tiled(xrp[t:t + bm], vp, bk, br)
        for t in range(0, xr.shape[0], bm)
    ]
    return tiles[0] if len(tiles) == 1 else jnp.concatenate(tiles, axis=0)


def _forward_fused(xp, wpacked, w_scale, u, v, act_spec, rotate,
                   bm, bn, bk, br, variant, interpret):
    """Single-kernel path: pad the weight-side operands, hand the UNPADDED-K
    activations to kernels/fused_gemm.py (the in-kernel prologue must not see
    pad columns), emit the output straight from the one pallas call."""
    wp, _ = _pad_to(wpacked, bk // 2, 0)
    wp, _ = _pad_to(wp, bn, 1)
    sw, _ = _pad_to(w_scale.reshape(1, -1), bn, 1)
    up = None
    if v is not None:
        up, _ = _pad_to(jnp.asarray(u, jnp.float32), bn, 0)
        v = jnp.asarray(v, jnp.float32)
    return fused_w4a4_lrc_kernel(
        xp, v, wp, sw, up,
        bits=act_spec.bits, clip_ratio=act_spec.clip_ratio, rotate=rotate,
        bm=bm, bn=bn, bk=bk, br=br, variant=variant,
        act_group=act_spec.group_size, interpret=interpret,
    )


def w4a4_lrc_forward(
    x: jnp.ndarray,  # (M, K) float
    wpacked: jnp.ndarray,  # (K//2, N) uint8
    w_scale: jnp.ndarray,  # (N,)
    u,  # (N, R) or None
    v,  # (K, R) or None
    act_spec: QuantSpec,
    rotate: bool = False,
    blocks=None,  # optional (bm, bn, bk[, br]) override; default: plan table
    impl: str = None,  # None -> ctx.impl; auto | fused | chained | unfused
    ctx: KernelContext = None,  # None -> the process-default context
    layer: str = None,  # per-layer override key into ctx.overrides
):
    """The full W4A4+LRC serving hot path.

    Execution config comes from ``ctx`` (a :class:`KernelContext`; ``None``
    falls back to the process default).  ``impl=None`` defers to
    ``ctx.impl`` (usually ``"auto"``): the block-table plan — with any
    per-layer override for ``layer`` — plus per-slab VMEM feasibility
    (:func:`resolve_plan`): the K-split fused kernel's tiles shrink to fit
    the budget before the path ever demotes, so fused serves every regime
    and rank unless nothing fits; then the two-kernel prologue → GEMM chain
    (V streamed); then the unfused three-pass chain.  Explicit ``impl``
    values force a path — "fused"/"chained" trust the caller on VMEM fit.

    ``rotate`` applies the online Walsh-Hadamard rotation (K power of two)
    inside the prologue.  ``act_spec.group_size`` switches the per-token
    scales for the per-group (M, K/g) scale plane on every path: BK snaps
    to a multiple of g (K-chunks hold whole scale groups) and the GEMM
    dequant moves into the K loop.  All operands are zero-padded to block
    multiples, so arbitrary M/K/N (odd MLP widths included) take the pallas
    path.  The three paths are bitwise identical in interpret mode (shared
    row bodies, shared K-chunk/R-tile accumulation order, exact integer
    accumulation) — under ANY context, since the context only picks the
    tiling.
    """
    ctx = _ctx(ctx)
    m0, k = x.shape
    n = wpacked.shape[1]
    r = 0 if v is None else v.shape[-1]
    group = act_spec.group_size
    if group is not None:
        assert k % group == 0, f"act_group {group} must divide K={k}"

    if impl is None:
        impl = ctx.impl
    variant = None
    if impl == "auto":
        path, bm, bn, bk, br, variant = ctx.resolve_plan(
            m0, k, n, r, rotate=rotate, layer=layer, act_group=group)
    elif impl not in KERNEL_PATHS:
        raise ValueError(f"unknown impl {impl!r}; "
                         f"expected auto or one of {KERNEL_PATHS}")
    else:
        path = impl
        _, bm, bn, bk, br, variant = ctx.select_plan(m0, k, n, r,
                                                     layer=layer)
    if blocks is not None:
        bm, bn, bk = blocks[:3]
        if len(blocks) > 3:
            br = blocks[3]
        br = lane_tile(r, br)
        variant = None
    if group is not None:
        bk = snap_bk_to_group(bk, group)  # K-chunks hold whole scale groups
    if path == "fused" and variant is None:
        variant = ctx.fused_variant(k, r, bm, bn, bk, br, rotate,
                                    act_group=group)

    if rotate:
        assert k & (k - 1) == 0, \
            f"online rotation needs power-of-two K, got {k}"
        if variant == "streamed":
            variant = "resident"  # rotation needs the f32 row slab
    interpret = ctx.interpret_mode()
    # run the prologue on the M-padded activations directly — its outputs
    # stay bm-aligned so the GEMM padding below never re-pads axis 0
    xp, _ = _pad_to(x, bm, 0)

    if path == "fused":
        out = _forward_fused(xp, wpacked, w_scale, u if r else None,
                             v if r else None, act_spec, rotate,
                             bm, bn, bk, br, variant, interpret)
        return out[:m0, :n]

    if path == "chained":
        xq, sx, xv = fused_prologue_kernel(
            xp, jnp.asarray(v, jnp.float32) if r else None,
            bits=act_spec.bits, clip_ratio=act_spec.clip_ratio,
            rotate=rotate, bm=bm, bk=bk, br=br, act_group=group,
            interpret=interpret,
        )
    else:  # unfused: three activation passes over the row tiles
        xr = fwht(xp, bm=bm, ctx=ctx) if rotate else xp
        xq, sx = act_quant(xr, act_spec, bm=bm, ctx=ctx)
        xv = _project_tiles(xr, v, bm, bk, br) if r else None

    xqp, sxp, wp, sw, up, xvp = _pad_gemm_operands(
        xq, sx, wpacked, w_scale, u if r else None, xv, bm, bn, bk, br,
        act_group=group)
    out = w4a4_lowrank_matmul_kernel(
        xqp, sxp, wp, sw, xvp, up,
        bm=bm, bn=bn, bk=bk, group=group, interpret=interpret,
    )
    return out[:m0, :n]


def w4a4_lowrank_matmul(
    x: jnp.ndarray,
    wpacked: jnp.ndarray,
    w_scale: jnp.ndarray,
    u,
    v,
    act_spec: QuantSpec,
    bm: int = None,
    bn: int = None,
    bk: int = None,
    ctx: KernelContext = None,
):
    """Back-compat alias for :func:`w4a4_lrc_forward` (no online rotation)."""
    blocks = None
    if bm is not None or bn is not None or bk is not None:
        m0, k = x.shape
        n = wpacked.shape[1]
        r = 0 if v is None else v.shape[-1]
        d = select_blocks(m0, k, n, r, ctx=ctx)
        blocks = (bm or d.bm, bn or d.bn, bk or d.bk)
    return w4a4_lrc_forward(x, wpacked, w_scale, u, v, act_spec,
                            blocks=blocks, ctx=ctx)


def flash_attention(q, k, v, scale: float, causal: bool = True,
                    bq: int = 128, bkv: int = 128,
                    ctx: KernelContext = None):
    """GQA flash attention. q: (B, Sq, H, D); k/v: (B, Skv, KH, D[v]).
    Folds batch×head, repeats KV heads across their query group."""
    b, sq, h, d = q.shape
    kh = k.shape[2]
    g = h // kh
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), g, axis=1).reshape(b * h, k.shape[1], d)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), g, axis=1).reshape(b * h, v.shape[1], v.shape[-1])
    bq = min(bq, sq)
    bkv = min(bkv, k.shape[1])
    out = flash_attention_kernel(qf, kf, vf, scale, causal=causal,
                                 bq=bq, bkv=bkv, interpret=_interpret(ctx))
    return out.reshape(b, h, sq, -1).transpose(0, 2, 1, 3)


def paged_flash_attention(q, k_pages, v_pages, block_table, lengths,
                          scale: float, ctx: KernelContext = None):
    """Decode attention against the serving engine's paged KV pool.
    q: (B, H, D) one token per sequence; k/v_pages: (NP, P, KH, D[v]);
    block_table: (B, MPB) int32; lengths: (B,) valid kv positions including
    the current token.  The page gather runs inside the kernel — no
    contiguous per-request KV copy is materialized.  Returns (B, H, Dv)."""
    return paged_flash_attention_kernel(
        q, k_pages, v_pages, block_table, lengths, scale,
        interpret=_interpret(ctx))


def flash_attention_quant(q, k_quant, k_scales, v_quant, v_scales,
                          scale: float, kv_spec, causal: bool = True,
                          bq: int = 128, bkv: int = 128,
                          ctx: KernelContext = None):
    """``flash_attention`` over quantized K/V (dense prefill layout).
    q: (B, Sq, H, D); k/v_quant: (B, Skv, KH, D | D//2) int8/packed uint8
    with f32 scale planes (B, Skv, KH, D // group).  ``kv_spec`` is a
    :class:`repro.serve.kvquant.KVSpec`; dequant happens per tile inside
    the kernel, so f32 KV never round-trips HBM."""
    b, sq, h, d = q.shape
    kh = k_quant.shape[2]
    g = h // kh
    skv = k_quant.shape[1]
    group = kv_spec.group_for(d)
    packed = kv_spec.dtype == "int4"

    def fold(t):
        return jnp.repeat(t.transpose(0, 2, 1, 3), g, axis=1) \
            .reshape(b * h, skv, t.shape[-1])

    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    bq = min(bq, sq)
    bkv = min(bkv, skv)
    out = flash_attention_quant_kernel(
        qf, fold(k_quant), fold(k_scales), fold(v_quant), fold(v_scales),
        scale, group, packed, causal=causal, bq=bq, bkv=bkv,
        interpret=_interpret(ctx))
    return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)


def paged_flash_attention_quant(q, k_pages, k_scales, v_pages, v_scales,
                                block_table, lengths, scale: float, kv_spec,
                                ctx: KernelContext = None):
    """``paged_flash_attention`` over a QUANTIZED page pool.  q: (B, H, D);
    k/v_pages: (NP, P, KH, D | D//2) int8/packed uint8; k/v_scales: the f32
    (NP, P, KH, D // group) scale-plane sidecar indexed by the SAME block
    table.  Pages dequantize per gather inside the kernel (the
    ``gemm_chunk_grouped`` in-loop rescale pattern).  Returns (B, H, D)."""
    d = q.shape[-1]
    return paged_flash_attention_quant_kernel(
        q, k_pages, k_scales, v_pages, v_scales, block_table, lengths,
        scale, kv_spec.group_for(d), kv_spec.dtype == "int4",
        interpret=_interpret(ctx))
