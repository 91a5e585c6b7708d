"""Expert parallelism: capacity-based sort dispatch under shard_map.

Pattern ("EP without all-to-all"): tokens are replicated across the "model"
axis (they are only data-sharded), routed-expert weights are sharded over
"model" (E_local = E / tp per device).  Each device

  1. selects the (token, slot) pairs routed to ITS experts,
  2. argsorts them by local expert id and packs into an (E_local, C, D)
     capacity buffer (overflow dropped — standard capacity-factor semantics),
  3. runs the grouped GEMM over its local experts,
  4. scatters the outputs back to token positions weighted by the router
     probs, and
  5. psum's over "model" so every device ends with the combined output.

The only inter-device communication is the final psum — the same collective
a row-parallel TP matmul needs — so MoE layers add no *extra* collective
phases, and the per-device FLOPs are the true top-k expert FLOPs (no E×
one-hot-GEMM inflation).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _capacity(n_tokens: int, top_k: int, n_experts: int, factor: float) -> int:
    c = int(n_tokens * top_k * factor / max(1, n_experts))
    return max(8, -(-c // 8) * 8)  # round up to 8


def _local_expert_compute(cfg, weights_local, xbuf):
    """xbuf: (E_local, C, D) -> (E_local, C, D) through each local expert."""
    import dataclasses

    from repro.quant.qlinear import QLinear, qlinear_apply

    def one(wg, wu, wd, xb):
        if isinstance(wg, QLinear):
            # we are already inside ep's shard_map body: strip any TP tag so
            # qlinear_apply cannot recurse into a nested shard_map
            wg, wu, wd = (dataclasses.replace(w, parallel=None)
                          for w in (wg, wu, wd))
            g = qlinear_apply(wg, xb)
            u = qlinear_apply(wu, xb)
            h = jax.nn.silu(g) * u
            return qlinear_apply(wd, h)
        g = xb @ wg.astype(xb.dtype)
        u = xb @ wu.astype(xb.dtype)
        h = jax.nn.silu(g) * u
        return h @ wd.astype(xb.dtype)

    return jax.vmap(one)(weights_local["wg"], weights_local["wu"], weights_local["wd"], xbuf)


def experts_ep(cfg, p, x, weights, top_idx, axis: str = "model",
               with_stats: bool = False):
    """x: (T, D) tokens (replicated over ``axis``); weights: (T, E) router
    weights; top_idx: (T, K).  Expert weights p["experts"] sharded over
    ``axis`` on their leading dim.  Returns (T, D), or
    ``((T, D), dropped)`` with ``with_stats`` — ``dropped`` is the global
    int32 count of (token, slot) assignments past expert capacity this
    call (the same psum the combine already needs; no extra collective)."""
    axis = axis or "model"
    mesh = jax.sharding.get_abstract_mesh()
    tp = mesh.shape[axis]
    e_total = cfg.n_experts
    e_local = e_total // tp
    t, d = x.shape
    k = top_idx.shape[-1]
    cap = _capacity(t, k, e_total, cfg.capacity_factor)

    def local_fn(xl, wl, idxl, experts_local):
        # which shard am I
        me = jax.lax.axis_index(axis)
        lo = me * e_local
        flat_idx = idxl.reshape(-1)  # (T*K,) global expert ids
        flat_tok = jnp.repeat(jnp.arange(t), k)
        flat_w = jnp.take_along_axis(wl, idxl, axis=-1).reshape(-1)
        mine = (flat_idx >= lo) & (flat_idx < lo + e_local)
        local_e = jnp.where(mine, flat_idx - lo, e_local)  # e_local = trash bin
        # slot within expert via stable sort order
        order = jnp.argsort(local_e, stable=True)
        sorted_e = local_e[order]
        # position of each sorted element within its expert group
        pos_in_group = jnp.arange(t * k) - jnp.searchsorted(sorted_e, sorted_e, side="left")
        keep = (sorted_e < e_local) & (pos_in_group < cap)
        dst_e = jnp.where(keep, sorted_e, e_local)
        dst_c = jnp.where(keep, pos_in_group, 0)
        src_tok = flat_tok[order]
        # gather tokens into (E_local+1, C, D); last row is the trash bin
        xbuf = jnp.zeros((e_local + 1, cap, d), xl.dtype)
        xbuf = xbuf.at[dst_e, dst_c].set(jnp.where(keep[:, None], xl[src_tok], 0.0))
        ybuf = _local_expert_compute(cfg, experts_local, xbuf[:e_local])
        # scatter back, weighted
        contrib = ybuf[dst_e.clip(0, e_local - 1), dst_c] * jnp.where(
            keep, flat_w[order], 0.0
        )[:, None].astype(x.dtype)
        out = jnp.zeros_like(xl).at[src_tok].add(contrib)
        if with_stats:
            # capacity-overflow accounting: assignments routed to MY experts
            # minus those that landed in a capacity slot.  Summed alongside
            # the combine psum — the collective count stays at one.
            dropped = (mine.sum().astype(jnp.int32)
                       - keep.sum().astype(jnp.int32))
            return (jax.lax.psum(out, axis),
                    jax.lax.psum(dropped, axis))
        return jax.lax.psum(out, axis)

    in_specs = (
        P(),  # x replicated over the manual axis
        P(),
        P(),
        jax.tree.map(lambda _: _expert_spec(axis), p["experts"]),
    )
    fn = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(P(), P()) if with_stats else P(),
        check_vma=False,
        axis_names={axis},
    )
    return fn(x, weights.astype(x.dtype), top_idx, p["experts"])


def _expert_spec(axis):
    return P(axis)  # shard leading (expert) dim; rest replicated
