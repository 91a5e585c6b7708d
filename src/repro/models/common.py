"""Shared transformer building blocks (pure functions, bf16-friendly).

Every matmul goes through :func:`repro.quant.qlinear.apply_linear`, which
dispatches on the weight leaf type: a plain array runs a dense matmul; a
``QLinear`` pytree runs the paper's W4A4 + low-rank-correction path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.quant.qlinear import apply_linear


def rms_norm(x: jnp.ndarray, gamma: jnp.ndarray, eps: float) -> jnp.ndarray:
    with jax.named_scope("norm"):
        dt = x.dtype
        x = x.astype(jnp.float32)
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        x = x * jax.lax.rsqrt(var + eps)
        return (x * gamma.astype(jnp.float32)).astype(dt)


def layer_norm(x: jnp.ndarray, gamma: jnp.ndarray, beta: jnp.ndarray, eps: float) -> jnp.ndarray:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    return (x * gamma.astype(jnp.float32) + beta.astype(jnp.float32)).astype(dt)


def sinusoidal_positions(length: int, dim: int) -> jnp.ndarray:
    """Whisper-style sinusoidal embeddings (length, dim)."""
    half = dim // 2
    freqs = jnp.exp(-jnp.log(10000.0) * jnp.arange(half) / (half - 1))
    ang = jnp.arange(length)[:, None] * freqs[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotary embedding. x: (..., seq, heads, head_dim); positions: (..., seq)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions[..., :, None].astype(jnp.float32) * freqs[None, :]
    cos = jnp.cos(ang)[..., :, None, :]  # (..., seq, 1, half)
    sin = jnp.sin(ang)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


FREE = "free"  # unconstrained marker for shard_hint


def shard_hint(x: jnp.ndarray, axes: tuple) -> jnp.ndarray:
    """Soft sharding constraint (no-op without a mesh).

    §Perf finding: without this, GSPMD shards attention's HEAD_DIM (e.g.
    96→6 per device) instead of the head axis, computing partial logits on
    every device and ALL-REDUCING the full (B,H,S,S) tensor — 256 GiB per
    layer for phi-3 prefill_32k.  Constraining q/k/v to head-sharded layout
    removes that collective entirely and shards the logits 16-way.

    ``axes`` entries: mesh-axis name (shard, with divisibility guard →
    FREE), None (force replicated), or FREE (leave to GSPMD).
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    P = jax.sharding.PartitionSpec
    spec = []
    for dim, a in zip(x.shape, axes):
        if a is None:
            spec.append(None)  # explicit replication
        elif a != FREE and a in mesh.shape and dim % mesh.shape[a] == 0:
            spec.append(a)
        else:
            spec.append(P.UNCONSTRAINED)
    if all(s is P.UNCONSTRAINED for s in spec):
        return x
    return jax.lax.with_sharding_constraint(x, P(*spec))


def attn_qkv_hints(q, k, v):
    """Sharding scheme for attention inputs (B, S, H|K, D):

      * heads divide the model axis → head-sharded (classic TP attention);
      * otherwise, for prefill, shard the QUERY-SEQUENCE over the model axis
        and replicate the (small) K/V — context-parallel attention: logits
        stay seq-sharded, no partial-contraction all-reduce (the smollm-class
        fix, §Perf);
      * decode (q_len == 1) is left to GSPMD (logits are tiny).
    """
    mesh = jax.sharding.get_abstract_mesh()
    if "model" not in mesh.shape:
        return q, k, v
    tp = mesh.shape["model"]
    if q.shape[2] % tp == 0 and k.shape[2] % tp == 0:
        hint = (FREE, FREE, "model", FREE)
        return shard_hint(q, hint), shard_hint(k, hint), shard_hint(v, hint)
    if q.shape[1] > 1 and q.shape[1] % tp == 0:
        q = shard_hint(q, (FREE, "model", None, None))
        k = shard_hint(k, (FREE, FREE, None, None))
        v = shard_hint(v, (FREE, FREE, None, None))
    return q, k, v


def cache_update(cache_arr, update, offset, axis: int = 1):
    """dynamic_update_slice along ``axis`` at ``offset`` with dtype-consistent
    indices (x64 mode in the calibration process must not leak int64)."""
    zero = jnp.zeros((), offset.dtype) if hasattr(offset, "dtype") else 0
    idx = [zero] * cache_arr.ndim
    idx[axis] = offset
    return jax.lax.dynamic_update_slice(cache_arr, update.astype(cache_arr.dtype), tuple(idx))


def causal_mask(q_len: int, kv_len: int, q_offset) -> jnp.ndarray:
    """(q_len, kv_len) boolean mask; query i attends kv j iff j <= i+offset."""
    qi = jnp.arange(q_len)[:, None] + q_offset
    kj = jnp.arange(kv_len)[None, :]
    return kj <= qi


def prefix_lm_mask(q_len: int, kv_len: int, prefix_len: int, q_offset) -> jnp.ndarray:
    """PaliGemma-style: bidirectional over the prefix, causal after."""
    m = causal_mask(q_len, kv_len, q_offset)
    kj = jnp.arange(kv_len)[None, :]
    return m | (kj < prefix_len)


def attention(
    q: jnp.ndarray,  # (B, Sq, H, Dq)
    k: jnp.ndarray,  # (B, Skv, K, Dq)
    v: jnp.ndarray,  # (B, Skv, K, Dv)
    mask,  # (Sq, Skv) or per-row (B, Sq, Skv) bool, or None
    scale: float,
) -> jnp.ndarray:
    """GQA attention: H query heads grouped over K kv heads. Returns
    (B, Sq, H, Dv).  Softmax in f32.

    A 2-D mask is shared across the batch; a 3-D mask carries one (Sq, Skv)
    plane per batch row — the batched-decode case where co-tenant requests
    sit at different sequence lengths.  Masked positions contribute exactly
    0.0 to the output (exp(-1e30 - m) underflows), so results are bitwise
    invariant to whatever finite garbage sits in masked cache slots."""
    b, sq, h, dq = q.shape
    kheads = k.shape[2]
    g = h // kheads
    q = q.reshape(b, sq, kheads, g, dq)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", q, k).astype(jnp.float32) * scale
    if mask is not None:
        m = mask[None, None, None] if mask.ndim == 2 else mask[:, None, None]
        logits = jnp.where(m, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return out.reshape(b, sq, h, v.shape[-1])


def sharded_attention(q, k, v, mask, scale: float):
    """:func:`attention` with its partitioning pinned under a mesh.

    Sharding CONSTRAINTS pin tensor layouts but not GSPMD's op strategy —
    left alone it may still split attention's reduction dims (head_dim in
    the logit einsum, sequence in softmax/PV), computing partials plus an
    f32 all-reduce that is not bitwise vs single-device.  Running the whole
    attention in a shard_map makes the partitioning exact by construction:
    batch over "data" and heads over "model" when divisible (both batched
    dims — every (row, head) is computed whole on one shard, zero
    collectives in the body), everything replicated otherwise.  Falls back
    to the plain call when no mesh is ambient."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return attention(q, k, v, mask, scale)
    P = jax.sharding.PartitionSpec
    axes = dict(mesh.shape)
    dp, tp = axes.get("data", 1), axes.get("model", 1)
    bax = "data" if (dp > 1 and q.shape[0] % dp == 0) else None
    hax = "model" if (tp > 1 and q.shape[2] % tp == 0
                      and k.shape[2] % tp == 0) else None
    qs = P(bax, None, hax, None)
    kvs = P(bax, None, hax, None)
    if mask is None:
        ins = (qs, kvs, kvs)
        args = (q, k, v)
        fn = lambda ql, kl, vl: attention(ql, kl, vl, None, scale)
    else:
        ms = P(None, None) if mask.ndim == 2 else P(bax, None, None)
        ins = (qs, kvs, kvs, ms)
        args = (q, k, v, mask)
        fn = lambda ql, kl, vl, ml: attention(ql, kl, vl, ml, scale)
    out = jax.shard_map(fn, mesh=mesh, in_specs=ins,
                        out_specs=P(bax, None, hax, None),
                        check_vma=False,
                        axis_names={a for a in (bax, hax) if a})(*args)
    return out


def mlp_block(p: dict, x: jnp.ndarray, act: str) -> jnp.ndarray:
    """Gated MLP: SwiGLU (silu) or GeGLU (gelu)."""
    with jax.named_scope("mlp"):
        g = apply_linear(p["wg"], x)
        u = apply_linear(p["wu"], x)
        if act == "silu":
            h = jax.nn.silu(g) * u
        else:
            h = jax.nn.gelu(g, approximate=True) * u
        return apply_linear(p["wd"], h)


def paged_cache_update(
    pages: jnp.ndarray,      # (NP, P, K, hd) one layer's page pool
    update: jnp.ndarray,     # (B, S, K, hd) new k or v rows
    block_table: jnp.ndarray,  # (B, MPB) int32 page ids, 0 = null page
    positions: jnp.ndarray,  # (B, S) absolute token positions
    valid: jnp.ndarray,      # (B, S) bool; False rows write to the null page
) -> jnp.ndarray:
    """Scatter per-token k/v rows into a paged pool.

    Token at absolute position p for batch row b lands in page
    ``block_table[b, p // P]`` at slot ``p % P``.  Invalid rows (padding,
    inactive slots) are redirected to page 0 — the reserved null page that
    the allocator never hands out — so a single fixed-shape scatter serves
    prefill chunks and masked batched decode alike.  Valid writes are
    page-disjoint across requests (each page has exactly one owner), so the
    scatter has no cross-request write conflicts; only null-page writes may
    collide, and nothing ever reads the null page unmasked."""
    b, s = positions.shape
    page_size = pages.shape[1]
    page = jnp.take_along_axis(block_table, positions // page_size, axis=1)
    page = jnp.where(valid, page, 0)
    within = positions % page_size
    return pages.at[page.reshape(-1), within.reshape(-1)].set(
        update.astype(pages.dtype).reshape(b * s, *update.shape[2:]))


def paged_cache_update_quantized(
    pages: jnp.ndarray,      # (NP, P, K, hd_packed) quantized page pool
    scales: jnp.ndarray,     # (NP, P, K, n_groups) f32 scale-plane sidecar
    update: jnp.ndarray,     # (B, S, K, hd) new k or v rows (float)
    block_table: jnp.ndarray,
    positions: jnp.ndarray,
    valid: jnp.ndarray,
    kv_spec,
):
    """Quantize-then-scatter: this step's k/v rows quantize through the
    canonical ``serve.kvquant`` spelling and land — data bytes AND scale
    plane — under exactly the :func:`paged_cache_update` page/slot
    indexing.  Quantization happens per token row BEFORE placement, so the
    stored bytes are invariant to which page a token lands in; the
    engine's bitwise page-placement/co-tenancy invariances carry over to
    quantized specs unchanged."""
    from repro.serve.kvquant import quantize_kv

    b, s = positions.shape
    page_size = pages.shape[1]
    page = jnp.take_along_axis(block_table, positions // page_size, axis=1)
    page = jnp.where(valid, page, 0)
    within = positions % page_size
    q, sc = quantize_kv(update, kv_spec)
    flat_p, flat_w = page.reshape(-1), within.reshape(-1)
    pages = pages.at[flat_p, flat_w].set(
        q.astype(pages.dtype).reshape(b * s, *q.shape[2:]))
    scales = scales.at[flat_p, flat_w].set(
        sc.astype(scales.dtype).reshape(b * s, *sc.shape[2:]))
    return pages, scales


def paged_gqa_attention_block(
    p: dict,
    x: jnp.ndarray,          # (B, S, D)
    positions: jnp.ndarray,  # (B, S)
    valid: jnp.ndarray,      # (B, S)
    cfg,
    mask,                    # (B, S, MPB * P) per-row bool
    pages_k: jnp.ndarray,    # (NP, P, K, hd)
    pages_v: jnp.ndarray,
    block_table: jnp.ndarray,  # (B, MPB)
):
    """GQA attention against a paged KV pool.  Writes this step's k/v into
    the owning pages, gathers each row's pages into a dense (B, MPB*P, ...)
    view, and attends under the caller's per-row mask.  Returns
    (out (B,S,D), new_pages_k, new_pages_v)."""
    b, s, _ = x.shape
    h, kh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = apply_linear(p["wq"], x).reshape(b, s, h, hd)
    k = apply_linear(p["wk"], x).reshape(b, s, kh, hd)
    v = apply_linear(p["wv"], x).reshape(b, s, kh, hd)
    q, k, v = attn_qkv_hints(q, k, v)
    if cfg.rope_theta > 0:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    with jax.named_scope("kv_write"):
        pages_k = paged_cache_update(pages_k, k, block_table, positions, valid)
        pages_v = paged_cache_update(pages_v, v, block_table, positions, valid)
    with jax.named_scope("attention"):
        kc = pages_k[block_table].reshape(b, -1, kh, hd).astype(x.dtype)
        vc = pages_v[block_table].reshape(b, -1, kh, hd).astype(x.dtype)
        out = sharded_attention(q, kc, vc, mask, scale=1.0 / (hd**0.5))
    out = apply_linear(p["wo"], out.reshape(b, s, h * hd))
    return out, pages_k, pages_v


def paged_gqa_attention_block_quantized(
    p: dict,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    valid: jnp.ndarray,
    cfg,
    mask,
    pages_k: jnp.ndarray,       # (NP, P, K, hd_packed) quantized pool
    pages_v: jnp.ndarray,
    scales_k: jnp.ndarray,      # (NP, P, K, n_groups) f32 sidecar
    scales_v: jnp.ndarray,
    block_table: jnp.ndarray,
    kv_spec,
):
    """The quantized-KV sibling of :func:`paged_gqa_attention_block`: k/v
    quantize at append time (``paged_cache_update_quantized``), the gather
    dequantizes each row's pages through THE canonical
    ``serve.kvquant.dequantize_kv`` spelling, and the identical
    :func:`attention` math runs on the dequantized f32 values — so the jnp
    serving path and the dequant-fused flash kernels attend over bitwise
    the same operands.  The f32/bf16 path stays in the separate function
    above, untouched: a float spec traces exactly the pre-KVSpec graph.

    Returns (out, new_pages_k, new_pages_v, new_scales_k, new_scales_v)."""
    from repro.serve.kvquant import dequantize_kv

    b, s, _ = x.shape
    h, kh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = apply_linear(p["wq"], x).reshape(b, s, h, hd)
    k = apply_linear(p["wk"], x).reshape(b, s, kh, hd)
    v = apply_linear(p["wv"], x).reshape(b, s, kh, hd)
    q, k, v = attn_qkv_hints(q, k, v)
    if cfg.rope_theta > 0:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    with jax.named_scope("kv_write"):
        pages_k, scales_k = paged_cache_update_quantized(
            pages_k, scales_k, k, block_table, positions, valid, kv_spec)
        pages_v, scales_v = paged_cache_update_quantized(
            pages_v, scales_v, v, block_table, positions, valid, kv_spec)
    phd = kv_spec.packed_head_dim(hd)
    n_g = kv_spec.n_groups(hd)
    with jax.named_scope("attention"):
        kc = dequantize_kv(pages_k[block_table].reshape(b, -1, kh, phd),
                           scales_k[block_table].reshape(b, -1, kh, n_g),
                           kv_spec, hd).astype(x.dtype)
        vc = dequantize_kv(pages_v[block_table].reshape(b, -1, kh, phd),
                           scales_v[block_table].reshape(b, -1, kh, n_g),
                           kv_spec, hd).astype(x.dtype)
        out = sharded_attention(q, kc, vc, mask, scale=1.0 / (hd**0.5))
    out = apply_linear(p["wo"], out.reshape(b, s, h * hd))
    return out, pages_k, pages_v, scales_k, scales_v


def gqa_attention_block(
    p: dict,
    x: jnp.ndarray,  # (B, S, D)
    positions: jnp.ndarray,  # (B, S)
    cfg,
    mask,
    cache=None,  # optional dict(k=(B,Smax,K,hd), v=..., offset scalar)
):
    """Returns (out (B,S,D), new_cache)."""
    b, s, _ = x.shape
    h, kh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = apply_linear(p["wq"], x).reshape(b, s, h, hd)
    k = apply_linear(p["wk"], x).reshape(b, s, kh, hd)
    v = apply_linear(p["wv"], x).reshape(b, s, kh, hd)
    q, k, v = attn_qkv_hints(q, k, v)  # heads- or seq-sharded (§Perf)
    if cfg.rope_theta > 0:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    new_cache = None
    if cache is not None:
        off = cache["offset"]
        kc = cache_update(cache["k"], k, off)
        vc = cache_update(cache["v"], v, off)
        new_cache = dict(k=kc, v=vc, offset=off + s)
        k, v = kc.astype(x.dtype), vc.astype(x.dtype)
    out = attention(q, k, v, mask, scale=1.0 / (hd**0.5))
    out = apply_linear(p["wo"], out.reshape(b, s, h * hd))
    return out, new_cache
