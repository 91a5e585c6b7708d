"""Quantized linear layer — the paper's computational scheme (Figure 1):

      y = Ŵ · Q_a(x)  +  U Vᵀ x

with Ŵ int4 (packed two-per-byte), Q_a the on-the-fly activation quantizer,
and U, Vᵀ the full-precision low-rank correction acting on the UNQUANTIZED x.

Four execution paths (static ``impl`` field):
  sim    — fake-quant float math; reference semantics for CPU tests/benches.
  int8   — integer GEMM (int8×int8→int32) with per-token rescale; the
           TPU-native lowering used by the dry-run (MXU int8 path).
  pallas — Pallas kernels behind the autotune plan table (kernels/ops.py):
           single-kernel fused forward where the working set fits VMEM,
           prologue→GEMM chain otherwise (the paper's "future work" fusion).
  fused  — force the single-kernel path (kernels/fused_gemm.py): prologue +
           int4 GEMM + LRC epilogue in ONE pallas call, xq never in HBM.

Group-wise activation scales (``act_group``, paper Table 2) run on every
path: the pallas kernels emit/consume the per-group (M, K/g) scale plane
(BK snapped to a multiple of g by the plan layer) — a grouped layer no
longer demotes to the jnp int8 GEMM.

Weight layout in models is (d_in, d_out) with ``y = x @ w``; the LRC solver's
(d_out, d_in) result is transposed at pack time.
"""

from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING, Optional

import jax
import jax.numpy as jnp

if TYPE_CHECKING:  # import-light: the kernel stack loads lazily at apply
    from repro.kernels.context import KernelContext

from repro.core.quantizers import (
    QuantSpec,
    pack_int4,
    unpack_int4,
    quantize_act,
    fake_quant_act,
)


def _static(**kw):
    return dataclasses.field(metadata=dict(static=True), **kw)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class QLinear:
    """Pytree holding one quantized weight matrix + its LRC correction."""

    qweight: jnp.ndarray  # uint8 (d_in//2, d_out) — int4 packed along d_in
    w_scale: jnp.ndarray  # f32 (d_out,) per-output-channel
    u: Optional[jnp.ndarray]  # bf16 (d_out, k) or None
    v: Optional[jnp.ndarray]  # bf16 (d_in, k) or None

    bits: int = _static(default=4)
    act_bits: int = _static(default=4)
    act_group: Optional[int] = _static(default=None)
    clip_ratio: float = _static(default=1.0)
    impl: str = _static(default="int8")  # sim | int8 | pallas | fused
    # Kernel execution config: an immutable (hashable) KernelContext rides
    # as pytree-static metadata, so two models in one process can hold
    # different block tables / VMEM budgets without racing any global.
    # None -> the process-default context (repro.kernels.ops.default_context).
    ctx: Optional["KernelContext"] = _static(default=None)
    # Layer name (e.g. the param-tree path) keying per-layer plan overrides
    # in ctx.overrides; None disables name-based lookup (shape-based
    # (K, N, R) overrides still apply).
    name: Optional[str] = _static(default=None)
    # Tensor-parallel placement: "column" (N-sharded W/U, replicated V),
    # "row" (K-sharded W/V, replicated U, one psum) or None (single-device).
    # Set by distributed.tp.shard_params; apply dispatches through
    # tp_qlinear_apply when tagged and a mesh is ambient.
    parallel: Optional[str] = _static(default=None)

    @property
    def d_in(self) -> int:
        # trailing dims: layer-stacked (scan) leaves carry lead dims
        return self.qweight.shape[-2] * 2

    @property
    def d_out(self) -> int:
        return self.qweight.shape[-1]

    @property
    def act_spec(self) -> QuantSpec:
        return QuantSpec(
            bits=self.act_bits, clip_ratio=self.clip_ratio, group_size=self.act_group
        )


def make_qlinear(
    q_out_in: jnp.ndarray,  # int8 (d_out, d_in) from the LRC/GPTQ solver
    scales: jnp.ndarray,  # (d_out, 1)
    u: Optional[jnp.ndarray] = None,
    v: Optional[jnp.ndarray] = None,
    *,
    act_bits: int = 4,
    act_group: Optional[int] = None,
    clip_ratio: float = 1.0,
    impl: str = "sim",
    lr_dtype=jnp.bfloat16,
    ctx: Optional["KernelContext"] = None,
    name: Optional[str] = None,
) -> QLinear:
    q_in_out = jnp.asarray(q_out_in, jnp.int8).T  # (d_in, d_out)
    packed = pack_int4(q_in_out.T).T  # pack along d_in
    return QLinear(
        qweight=packed,
        w_scale=jnp.asarray(scales, jnp.float32).reshape(-1),
        u=None if u is None else jnp.asarray(u, lr_dtype),
        v=None if v is None else jnp.asarray(v, lr_dtype),
        act_bits=act_bits,
        act_group=act_group,
        clip_ratio=clip_ratio,
        impl=impl,
        ctx=ctx,
        name=name,
    )


def _unpack_w(q: QLinear) -> jnp.ndarray:
    """packed (d_in//2, d_out) -> int8 (d_in, d_out)."""
    return unpack_int4(q.qweight.T).T


def _lowrank(q: QLinear, x: jnp.ndarray) -> jnp.ndarray:
    """(x V) Uᵀ on the unquantized activations, in the LR dtype."""
    xv = x.astype(q.v.dtype) @ q.v  # (..., k)
    return xv @ q.u.T.astype(q.v.dtype)  # (..., d_out)


def _apply_sim(q: QLinear, x: jnp.ndarray) -> jnp.ndarray:
    w = _unpack_w(q).astype(jnp.float32) * q.w_scale[None, :]
    xq = fake_quant_act(x, q.act_spec).astype(jnp.float32)
    y = xq @ w
    if q.u is not None:
        y = y + _lowrank(q, x).astype(jnp.float32)
    return y.astype(x.dtype)


def _apply_int8(q: QLinear, x: jnp.ndarray) -> jnp.ndarray:
    """Integer GEMM path. Per-token scales; optional per-group-128 scales."""
    wq = _unpack_w(q)  # int8 (d_in, d_out)
    xq, sx = quantize_act(x, q.act_spec)  # int8, f32
    dims = (((x.ndim - 1,), (0,)), ((), ()))
    if q.act_group is None:
        acc = jax.lax.dot_general(xq, wq, dims, preferred_element_type=jnp.int32)
        y = acc.astype(jnp.float32) * sx * q.w_scale
    else:
        g = q.act_group
        d_in, d_out = wq.shape
        ng = d_in // g
        xg = xq.reshape(*x.shape[:-1], ng, g)
        wg = wq.reshape(ng, g, d_out)
        accg = jnp.einsum(
            "...nk,nkd->...nd", xg, wg, preferred_element_type=jnp.int32
        )
        y = jnp.sum(accg.astype(jnp.float32) * sx[..., None], axis=-2) * q.w_scale
    if q.u is not None:
        y = y + _lowrank(q, x).astype(jnp.float32)
    return y.astype(x.dtype)


def _apply_pallas(q: QLinear, x: jnp.ndarray,
                  kernel_impl: Optional[str] = None) -> jnp.ndarray:
    """Pallas kernel paths.  Execution config comes from the layer's
    KernelContext (``q.ctx``; None -> the process default) with any
    per-layer plan override keyed by ``q.name`` or the layer's (K, N, R)
    shape.  ``kernel_impl=None`` defers to ``ctx.impl`` (usually "auto":
    the plan table with VMEM feasibility); ``"fused"`` pins the
    single-kernel path.

    Precision note: the kernels compute the (xV)Uᵀ correction in f32 VMEM
    from the (bf16-stored) factors, so outputs differ from the int8 path —
    which matmuls in the LR storage dtype — by ~bf16 epsilon of the LR term
    (the kernel paths are the more accurate of the two)."""
    from repro.kernels import ops

    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    y = ops.w4a4_lrc_forward(
        x2, q.qweight, q.w_scale, q.u, q.v, act_spec=q.act_spec,
        impl=kernel_impl, ctx=q.ctx, layer=q.name,
    )
    return y.reshape(*lead, q.d_out).astype(x.dtype)


def qlinear_apply(q: QLinear, x: jnp.ndarray) -> jnp.ndarray:
    if q.parallel is not None:
        # mesh-tagged layer: run the shard_map TP path (falls back to the
        # plain apply when no mesh is ambient, and strips the tag inside
        # the shard body, so this cannot recurse)
        from repro.distributed.tp import tp_qlinear_apply

        return tp_qlinear_apply(q, x)
    if q.impl == "sim":
        return _apply_sim(q, x)
    if q.impl == "int8":
        return _apply_int8(q, x)
    if q.impl in ("pallas", "fused"):
        # group-wise calibrated layers (paper Table 2) run the kernel paths
        # too: the prologue emits the (M, K/g) scale plane and the GEMM
        # dequantizes per group inside the K loop — no int8 demotion
        return _apply_pallas(q, x, None if q.impl == "pallas" else "fused")
    raise ValueError(f"unknown impl {q.impl!r}")


def apply_linear(w, x: jnp.ndarray) -> jnp.ndarray:
    """Dispatch: plain array → dense matmul; QLinear → W4A4+LRC path, under
    the ``qlinear`` named scope (the device trace's ops of the kernel, its
    prologue and the pads and converts around it carry it)."""
    if isinstance(w, QLinear):
        with jax.named_scope("qlinear"):
            return qlinear_apply(w, x)
    return x @ w.astype(x.dtype)


RETAG_IMPLS = ("sim", "int8", "pallas", "fused", "auto")


def retag_qlinear_impl(params, impl: Optional[str],
                       ctx: Optional["KernelContext"] = None):
    """Switch every QLinear leaf in a param tree to another execution path
    (e.g. the serving engine retags to "pallas" so decode runs the fused
    kernels) and/or attach a :class:`KernelContext`.  Non-QLinear leaves
    pass through unchanged.

    ``impl`` must be one of ``sim | int8 | pallas | fused | auto``, or None
    to leave every leaf's impl untouched (ctx-only attach) — typos raise
    ValueError instead of silently tagging an unusable impl.  ``"auto"``
    resolves at retag time: "pallas" when a compiled backend is attached,
    otherwise each leaf keeps its calibrated impl (the pallas interpreter
    would only slow CPU reference semantics down).  ``ctx`` is attached to
    every leaf when given (None leaves contexts unchanged)."""
    if impl is not None and impl not in RETAG_IMPLS:
        raise ValueError(f"unknown impl {impl!r}; "
                         f"expected one of {RETAG_IMPLS}")
    resolved = impl
    if impl == "auto":
        resolved = "pallas" if jax.default_backend() != "cpu" else None

    def _retag(leaf):
        if isinstance(leaf, QLinear):
            changes = {} if resolved is None else {"impl": resolved}
            if ctx is not None:
                changes["ctx"] = ctx
            return dataclasses.replace(leaf, **changes) if changes else leaf
        return leaf

    return jax.tree.map(_retag, params,
                        is_leaf=lambda l: isinstance(l, QLinear))
