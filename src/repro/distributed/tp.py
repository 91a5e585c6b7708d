"""Tensor-parallel W4A4+LRC forward under shard_map.

The fused quantized matmul (``ops.w4a4_lrc_forward`` via ``qlinear_apply``)
is threaded through a mesh "model" axis in the two classic flavours, and the
low-rank factors U/V follow the weight's sharding so the LRC epilogue adds
ZERO extra collectives (the same invariant ``ep.py`` maintains for MoE):

  column-parallel (wq/wk/wv/wg/wu — the (None, "tp") rules):
      W  N-sharded:   qweight (K//2, N/tp), w_scale (N/tp,)
      U  N-sharded:   u (N/tp, R)            — rows follow the output shard
      V  replicated:  v (K, R)
      x  replicated → local y is an exact column block of the global y.
      NO collective: output stays "model"-sharded for the next op.

  row-parallel (wo/wd — the ("tp", None) rules):
      W  K-sharded:   qweight (K/tp//2, N)
      U  replicated:  u (N, R)
      V  K-sharded:   v (K/tp, R)            — x_s @ V_s is a partial of xV
      x  K-sharded  → local y = Ŵ_s·Q_a(x_s) + U·(V_sᵀ x_s) is a PARTIAL sum
      of the global output, with the LRC partial already merged in, so ONE
      ``psum`` finishes both the GEMM and the correction.

Because every shard sees its own local (K, N, R), the kernel plan resolves
through ``KernelContext``'s shape-keyed overrides at the LOCAL shape — each
shard gets its own feasible fused tiling with no extra plumbing.

Numerics contract (documented in docs/serving.md):
  * column-parallel outputs are BITWISE identical to single-device (each
    shard computes an independent output-column block over the full K);
  * replicate-tagged layers (no rule, or an infeasible one) also run under
    shard_map — x is gathered to replicated and every shard runs the
    identical full-shape apply, which is BITWISE.  (Left to GSPMD, a
    replicated weight against a sharded producer may be lowered as a split
    contraction + all-reduce, which is not.)
  * row-parallel outputs match to a few ulp: the partial (GEMM + LRC,
    both K-sharded) stays f32 through the psum and is rounded to the
    activation dtype once, but the blocked K reduction reassociates the
    f32 sum (~eps_f32), and — the dominant term when low-rank factors are
    present — the bf16-STORED V means each shard's x_s@V_s partial is
    re-rounded to bf16 before the psum, where single-device rounds the
    full-K contraction once.  Net drift is a few ulp of the LR storage
    dtype (bf16), f32-ulp-level for LRC-free layers.  Downstream 4-bit
    activation quantizers can amplify a residual shift into a code flip,
    so end-to-end logits are close but not bitwise.  Row-parallel REQUIRES group-wise activation scales
    with ``act_group`` dividing K/tp (the quantization grid is then
    shard-invariant); per-token scales over a local K slice would be a
    semantics shift, so ``tp_feasible`` refuses and the layer replicates.
    Net: a mesh run with per-token scales (act_group=None) replicates the
    row layers and is bitwise at every QLinear boundary; a run with group
    scales is fully sharded with exactly one psum per row layer and
    ulp-level drift there.  END-TO-END the mesh engine is ulp-close but
    not guaranteed bitwise vs the single-device engine: the two are
    different XLA programs, and fusion/FMA grouping at resharding
    boundaries (e.g. rope next to a pool scatter) can differ by 1 ulp even
    in fully replicated sections.  What IS hard-guaranteed: run-to-run
    determinism of a given mesh (same program, same seed → bitwise
    identical token streams), which is what the recovery/chaos suites pin.
"""

from __future__ import annotations

import dataclasses
import math
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed.sharding import param_pspecs, to_shardings
from repro.launch.mesh import auto_mesh
from repro.quant.qlinear import QLinear


def parse_mesh(text: str) -> dict:
    """``"model=4,data=2"`` → {"model": 4, "data": 2} (order preserved)."""
    out: dict = {}
    for part in text.split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise ValueError(f"bad mesh axis {part!r}; expected name=size")
        name, _, size = part.partition("=")
        out[name.strip()] = int(size)
    if not out:
        raise ValueError(f"empty mesh spec {text!r}")
    return out


def build_mesh(spec) -> Mesh:
    """Mesh from a ``parse_mesh`` dict (or spec string); needs
    prod(sizes) == device count."""
    if isinstance(spec, str):
        spec = parse_mesh(spec)
    axes = tuple(spec.keys())
    shape = tuple(int(spec[a]) for a in axes)
    need = math.prod(shape)
    have = jax.device_count()
    if need != have:
        raise ValueError(
            f"mesh {dict(spec)} needs {need} devices, have {have} "
            "(set XLA_FLAGS=--xla_force_host_platform_device_count=N on CPU)")
    return auto_mesh(shape, axes)


def _axis_size(mesh, axis: str) -> int:
    try:
        return int(mesh.shape[axis])
    except (KeyError, TypeError):
        return 1


def parallel_kind(qweight_spec: P, axis: str = "model") -> Optional[str]:
    """Classify a qweight PartitionSpec (trailing dims (K//2, N)) as
    "column" (N sharded), "row" (K sharded) or None (replicated/expert)."""
    sp = tuple(qweight_spec) if qweight_spec is not None else ()
    if len(sp) < 2:
        return None
    sp = sp + (None,) * 2  # defensive: short specs mean trailing None
    sp = sp[: max(2, len(tuple(qweight_spec)))]
    lead, k_ax, n_ax = sp[:-2], sp[-2], sp[-1]
    if any(a == axis for a in lead):
        return None  # expert/stacked-lead sharding is EP territory
    if n_ax == axis and k_ax != axis:
        return "column"
    if k_ax == axis and n_ax != axis:
        return "row"
    return None


def tp_feasible(q: QLinear, kind: str, tp: int) -> bool:
    """Can this QLinear actually run ``kind``-parallel over ``tp`` shards?"""
    if tp <= 1:
        return False
    if kind == "column":
        if q.d_out % tp:
            return False
        if q.u is not None and q.u.shape[-2] % tp:
            return False
        return True
    if kind == "row":
        if q.qweight.shape[-2] % tp:  # packed K//2 must split
            return False
        if q.v is not None and q.v.shape[-2] % tp:
            return False
        if q.act_group is None:
            # per-token scales see only the local K slice — a semantics
            # shift, not a rounding change.  Row-parallel needs group-wise
            # activation scales so the quantization grid is shard-invariant.
            return False
        if (q.d_in // tp) % q.act_group:
            return False  # group boundary would straddle shards
        return True
    return False


def _strip(q: QLinear) -> QLinear:
    return dataclasses.replace(q, parallel=None)


def _field_specs(q: QLinear, kind: str, axis: str) -> QLinear:
    """QLinear-shaped pytree of PartitionSpecs for shard_map in_specs.
    Built by replacing the array fields, so the treedef (static metadata)
    matches the argument exactly."""
    if kind == "replicate":
        return dataclasses.replace(
            q,
            qweight=P(None, None),
            w_scale=P(None),
            u=None if q.u is None else P(None, None),
            v=None if q.v is None else P(None, None),
        )
    if kind == "column":
        return dataclasses.replace(
            q,
            qweight=P(None, axis),
            w_scale=P(axis),
            u=None if q.u is None else P(axis, None),
            v=None if q.v is None else P(None, None),
        )
    return dataclasses.replace(
        q,
        qweight=P(axis, None),
        w_scale=P(None),
        u=None if q.u is None else P(None, None),
        v=None if q.v is None else P(axis, None),
    )


def tp_qlinear_apply(q: QLinear, x: jnp.ndarray, axis: str = "model"):
    """Apply a ``parallel``-tagged QLinear under the ambient mesh.

    Falls back to the plain single-device apply when no mesh is active or
    the axis is trivial/infeasible, so tagged params stay runnable anywhere.
    """
    from repro.quant.qlinear import qlinear_apply

    kind = q.parallel
    mesh = jax.sharding.get_abstract_mesh()
    tp = _axis_size(mesh, axis)
    if mesh.empty or kind not in ("column", "row", "replicate") \
            or (kind != "replicate" and not tp_feasible(q, kind, tp)):
        return qlinear_apply(_strip(q), x)

    nlead = x.ndim - 1
    if kind == "replicate":
        # untagged-by-rule / infeasible layers still run under shard_map so
        # their numerics are pinned: x is gathered to replicated (exact data
        # movement) and every shard runs the identical full-shape apply.
        # Leaving these to GSPMD can silently split the contraction against
        # a sharded producer (partial dots + all-reduce), breaking the
        # bitwise contract.
        def local_fn(xl, ql):
            return qlinear_apply(_strip(ql), xl)

        x_spec = P(*([None] * (nlead + 1)))
        out_spec = P(*([None] * (nlead + 1)))
    elif kind == "column":
        def local_fn(xl, ql):
            return qlinear_apply(_strip(ql), xl)

        x_spec = P(*([None] * (nlead + 1)))
        out_spec = P(*([None] * nlead), axis)
    else:
        def local_fn(xl, ql):
            # local GEMM partial + local LRC partial (K-sharded V) are both
            # in y already — ONE psum finishes the row-parallel matmul AND
            # the low-rank correction.  The partial stays f32 through the
            # psum (bf16 x upcasts losslessly; every impl computes y in f32
            # and rounds only at the end) so the output is rounded to the
            # activation dtype ONCE, like single-device — pre-rounding the
            # partials would lose mantissa to cancellation across shards.
            y = qlinear_apply(_strip(ql), xl.astype(jnp.float32))
            y = jax.lax.psum(y, axis)
            return y.astype(xl.dtype)

        x_spec = P(*([None] * nlead), axis)
        out_spec = P(*([None] * (nlead + 1)))

    fn = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(x_spec, _field_specs(q, kind, axis)),
        out_specs=out_spec,
        check_vma=False,
        axis_names={axis},
    )
    return fn(x, q)


def local_kn_r(q: QLinear, kind: Optional[str], tp: int):
    """Per-shard (K, N, R) seen by the kernel plan under ``kind`` TP."""
    r = 0 if q.u is None else int(q.u.shape[-1])
    k, n = int(q.d_in), int(q.d_out)
    if kind == "column" and tp > 1:
        return (k, n // tp, r)
    if kind == "row" and tp > 1:
        return (k // tp, n, r)
    return (k, n, r)


def shard_params(params, mesh: Mesh, *, axis: str = "model",
                 replicate_dense: bool = True):
    """Tag + place a param tree for mesh serving.

    Every QLinear leaf whose sharding rule N- or K-shards the quantized
    weight gets ``parallel`` set ("column"/"row") and its fields device_put
    with the matching NamedShardings; infeasible leaves (divisibility,
    act_group straddling shards) fall back to replication with a warning.
    Non-QLinear leaves are replicated when ``replicate_dense`` (keeps dense
    matmuls bitwise identical to single-device — GSPMD never splits a
    contraction) or placed per the full rule table otherwise (MoE/EP).

    Returns ``(params, plan)`` where plan is a list of per-QLinear dicts
    (path, parallel, global/local (K, N, R)) for health()/introspection.
    """
    tp = _axis_size(mesh, axis)
    specs = param_pspecs(params, mesh)
    plan: list = []
    repl = NamedSharding(mesh, P())

    def _place(path, leaf, spec):
        from repro.distributed.sharding import _path_str
        if isinstance(leaf, QLinear):
            sp = tuple(spec.qweight) if spec.qweight is not None else ()
            if any(a == axis for a in sp[:-2]):
                # EP leaf: the leading (expert) dim is sharded.  Leave it
                # UNtagged — ep.py's shard_map owns these, and a TP tag
                # would nest shard_map inside its vmap'd body — and place
                # it per the rule spec so each device holds E/tp experts.
                plan.append({
                    "path": _path_str(path),
                    "parallel": "ep",
                    "global_knr": local_kn_r(leaf, None, 1),
                    "local_knr": local_kn_r(leaf, None, 1),
                    "act_group": leaf.act_group,
                    "impl": leaf.impl,
                    "ctx": leaf.ctx,
                })
                shardings = jax.tree.map(
                    lambda s: NamedSharding(mesh, s), spec,
                    is_leaf=lambda s: isinstance(s, P))
                return jax.device_put(leaf, shardings)
            kind = parallel_kind(spec.qweight, axis)
            if kind is not None and not tp_feasible(leaf, kind, tp):
                warnings.warn(
                    f"{_path_str(path)}: {kind}-parallel infeasible over "
                    f"{axis}={tp} (shape/act_group divisibility); "
                    "replicating", stacklevel=2)
                kind = None
            # replicated leaves are still TAGGED ("replicate") so they run
            # under shard_map — GSPMD left alone may split a replicated
            # weight against a sharded activation producer, which is not
            # bitwise.  Placement is plain replication either way.
            tagged = dataclasses.replace(leaf, parallel=kind or "replicate")
            if kind is None:
                shardings = jax.tree.map(lambda _: repl, tagged)
            else:
                shardings = jax.tree.map(
                    lambda s: NamedSharding(mesh, s),
                    _stacked_field_specs(tagged, kind, axis, spec),
                    is_leaf=lambda s: isinstance(s, P))
            plan.append({
                "path": _path_str(path),
                "parallel": kind,
                "global_knr": local_kn_r(leaf, None, 1),
                "local_knr": local_kn_r(leaf, kind, tp),
                "act_group": leaf.act_group,
                "impl": leaf.impl,
                "ctx": leaf.ctx,
            })
            return jax.device_put(tagged, shardings)
        if replicate_dense:
            return jax.device_put(leaf, repl)
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    out = jax.tree_util.tree_map_with_path(
        _place, params, specs,
        is_leaf=lambda l: isinstance(l, QLinear))
    return out, plan


def _stacked_field_specs(q: QLinear, kind: str, axis: str, guarded: QLinear):
    """Placement specs for a possibly layer-stacked QLinear: the trailing
    two dims follow ``_field_specs``; leading (scan) dims stay unsharded.
    ``guarded`` (the param_pspecs result) supplies the lead-dim count."""
    flat = _field_specs(q, kind, axis)

    def pad(spec, g_spec, arr):
        if spec is None or arr is None:
            return None
        lead = arr.ndim - len(tuple(spec))
        return P(*([None] * lead), *tuple(spec))

    return dataclasses.replace(
        q,
        qweight=pad(flat.qweight, guarded.qweight, q.qweight),
        w_scale=pad(flat.w_scale, guarded.w_scale, q.w_scale),
        u=pad(flat.u, guarded.u, q.u),
        v=pad(flat.v, guarded.v, q.v),
    )


def shard_kv_pool(pool, mesh: Mesh, data_axis: str = "data"):
    """Replicated-then-data-sharded KV paging: every leaf is replicated over
    "model"; the page axis (dim 1 of (L, NP, P, ...) pools) is sharded over
    ``data_axis`` when the page count divides it.  Page gathers/scatters are
    pure data movement, so this never perturbs decode numerics."""
    dsz = _axis_size(mesh, data_axis)

    def _one(leaf):
        if getattr(leaf, "ndim", 0) >= 2 and dsz > 1 \
                and leaf.shape[1] % dsz == 0:
            spec = P(None, data_axis)
        else:
            spec = P()
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    return jax.tree.map(_one, pool)
