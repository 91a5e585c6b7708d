"""Dense decoder-only transformer family (smollm / phi-3 / phi-4 / gemma,
plus the paligemma backbone).  Layers are scanned (params stacked on axis 0)
so HLO size and compile time stay flat in depth — required for the 512-device
dry-run of 30-80 layer models.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.remat import maybe_remat, scan_layers
from repro.models.common import (
    causal_mask,
    gqa_attention_block,
    mlp_block,
    paged_gqa_attention_block,
    paged_gqa_attention_block_quantized,
    prefix_lm_mask,
    rms_norm,
)


def _init_linear(key, d_in, d_out, dtype, scale=None):
    scale = scale if scale is not None else d_in**-0.5
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)


def init_layer_params(cfg, key, dtype):
    ks = jax.random.split(key, 8)
    h, kh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    return {
        "attn_norm": jnp.ones((cfg.d_model,), dtype),
        "attn": {
            "wq": _init_linear(ks[0], cfg.d_model, h * hd, dtype),
            "wk": _init_linear(ks[1], cfg.d_model, kh * hd, dtype),
            "wv": _init_linear(ks[2], cfg.d_model, kh * hd, dtype),
            "wo": _init_linear(ks[3], h * hd, cfg.d_model, dtype),
        },
        "mlp_norm": jnp.ones((cfg.d_model,), dtype),
        "mlp": {
            "wg": _init_linear(ks[4], cfg.d_model, cfg.d_ff, dtype),
            "wu": _init_linear(ks[5], cfg.d_model, cfg.d_ff, dtype),
            "wd": _init_linear(ks[6], cfg.d_ff, cfg.d_model, dtype),
        },
    }


def init_params(cfg, key, max_seq: int = 0):
    dtype = jnp.dtype(cfg.dtype)
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    layers = jax.vmap(lambda k: init_layer_params(cfg, k, jnp.float32))(layer_keys)
    layers = jax.tree.map(lambda a: a.astype(dtype), layers)
    params = {
        "embed": (jax.random.normal(k_embed, (cfg.vocab_size, cfg.d_model)) * 0.02).astype(dtype),
        "layers": layers,
        "final_norm": jnp.ones((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = _init_linear(k_head, cfg.d_model, cfg.vocab_size, dtype)
    return params


def decoder_layer(cfg, lp, x, positions, mask, cache=None):
    """One pre-norm block. Returns (x, new_cache_slice)."""
    h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    a, new_cache = gqa_attention_block(lp["attn"], h, positions, cfg, mask, cache)
    x = x + a
    h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    x = x + mlp_block(lp["mlp"], h, cfg.act)
    return x, new_cache


def run_layers(cfg, layers, x, positions, mask, cache=None):
    """Scan the stacked layer params over x. cache: dict with k/v stacked
    (L, B, S, K, hd) and scalar 'offset', or None."""
    if cache is None:

        def body(xc, lp):
            y, _ = decoder_layer(cfg, lp, xc, positions, mask, None)
            return y, None

        x, _ = scan_layers(cfg, maybe_remat(cfg, body), x, layers)
        return x, None

    offset = cache["offset"]

    def body(xc, xs):
        lp, ck, cv = xs
        y, nc = decoder_layer(
            cfg, lp, xc, positions, mask, dict(k=ck, v=cv, offset=offset)
        )
        return y, (nc["k"], nc["v"])

    x, (nk, nv) = scan_layers(cfg, body, x, (layers, cache["k"], cache["v"]))
    new_cache = dict(k=nk, v=nv, offset=offset + positions.shape[-1])
    return x, new_cache


def embed_tokens(cfg, params, tokens):
    x = params["embed"][tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model**0.5, x.dtype)
    return x


def unembed(cfg, params, x):
    # rotation fusion (QuaRot) may materialize an explicit lm_head for tied
    # models (final-norm γ cannot be folded into a shared embedding)
    with jax.named_scope("unembed"):
        if "lm_head" in params:
            head = params["lm_head"]
        else:
            head = params["embed"].T
        logits = (x @ head.astype(x.dtype)).astype(jnp.float32)
        if cfg.logit_softcap > 0:
            logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
        return logits


def forward(cfg, params, tokens, prefix_len: int = 0, embeds=None):
    """Teacher-forcing forward. tokens: (B, S) int32.  ``embeds`` (B, P, D)
    optionally prepends precomputed frontend embeddings (VLM stub)."""
    x = embed_tokens(cfg, params, tokens)
    if embeds is not None:
        x = jnp.concatenate([embeds.astype(x.dtype), x], axis=1)
        prefix_len = max(prefix_len, embeds.shape[1])
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    if prefix_len > 0:
        mask = prefix_lm_mask(s, s, prefix_len, 0)
    else:
        mask = causal_mask(s, s, 0)
    x, _ = run_layers(cfg, params["layers"], x, positions, mask)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return unembed(cfg, params, x)


def init_cache(cfg, batch: int, max_seq: int, dtype=jnp.bfloat16):
    kh, hd = cfg.n_kv_heads, cfg.head_dim
    shape = (cfg.n_layers, batch, max_seq, kh, hd)
    return dict(
        k=jnp.zeros(shape, dtype),
        v=jnp.zeros(shape, dtype),
        offset=jnp.zeros((), jnp.int32),
    )


def prefill(cfg, params, tokens, cache, prefix_len: int = 0, embeds=None):
    x = embed_tokens(cfg, params, tokens)
    if embeds is not None:
        x = jnp.concatenate([embeds.astype(x.dtype), x], axis=1)
        prefix_len = max(prefix_len, embeds.shape[1])
    b, s, _ = x.shape
    kv_len = cache["k"].shape[2]
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    base = causal_mask(s, kv_len, 0) if prefix_len == 0 else prefix_lm_mask(s, kv_len, prefix_len, 0)
    # mask out not-yet-written cache slots beyond s handled by causal bound
    x, cache = run_layers(cfg, params["layers"], x, positions, base, cache)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return unembed(cfg, params, x[:, -1:]), cache


def init_paged_cache(cfg, num_pages: int, page_size: int, dtype=jnp.bfloat16,
                     kv_spec=None):
    """A paged KV pool shared by every in-flight request: page id indexes
    axis 1, page 0 is the reserved null page (never allocated; padding and
    inactive-slot writes are redirected there).

    A quantized ``kv_spec`` stores int8 (or pack_int4'd uint8) pages plus
    f32 scale-plane leaves ``k_scale``/``v_scale`` shaped
    ``(L, NP, P, kh, n_groups)`` — same page axis (1), so the engine's
    page-id rollback and the page-scoped fault surface
    (``FaultInjector.corrupt_pages``) cover the sidecar for free.  A float
    spec routes its dtype and builds exactly the two-leaf pool below."""
    kh, hd = cfg.n_kv_heads, cfg.head_dim
    if kv_spec is not None and kv_spec.is_quantized:
        shape = (cfg.n_layers, num_pages, page_size, kh,
                 kv_spec.packed_head_dim(hd))
        sshape = (cfg.n_layers, num_pages, page_size, kh,
                  kv_spec.n_groups(hd))
        return dict(k=jnp.zeros(shape, kv_spec.pool_dtype),
                    v=jnp.zeros(shape, kv_spec.pool_dtype),
                    k_scale=jnp.zeros(sshape, jnp.float32),
                    v_scale=jnp.zeros(sshape, jnp.float32))
    if kv_spec is not None:
        dtype = kv_spec.cache_dtype
    shape = (cfg.n_layers, num_pages, page_size, kh, hd)
    return dict(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


def paged_step(cfg, params, tokens, positions, valid, cache, block_table,
               sample_row=None, kv_spec=None):
    """One forward step against the paged KV pool — the single entry point
    for BOTH chunked prefill (B=1, S=chunk) and batched decode (B=slots,
    S=1), so the serving engine compiles exactly two traces per config.

    tokens (B, S) int32; positions (B, S) absolute token positions;
    valid (B, S) bool (False = padding / inactive slot: the KV write is
    redirected to the null page and the row's output is garbage the caller
    ignores); block_table (B, MPB) int32 page ids.  ``sample_row`` (B,)
    optionally selects one hidden row per batch entry before the unembed
    (the last real prompt token of a final prefill chunk), matching
    ``prefill``'s logits[:, -1:] shape.  Returns (logits (B, S|1, V),
    new_cache)."""
    x = embed_tokens(cfg, params, tokens)
    page_size = cache["k"].shape[2]
    kv_len = block_table.shape[1] * page_size
    kj = jnp.arange(kv_len)
    mask = (kj[None, None, :] <= positions[:, :, None]) & valid[:, :, None]

    # The spec branch happens HERE, at Python trace time: a float (or
    # absent) kv_spec traces exactly the pre-KVSpec graph — no scale
    # leaves, no extra ops — which is what keeps f32 serving bitwise
    # identical under the chaos + crash-recovery contract.
    if kv_spec is not None and kv_spec.is_quantized:

        def qbody(xc, xs):
            lp, pk, pv, sk, sv = xs
            h = rms_norm(xc, lp["attn_norm"], cfg.norm_eps)
            a, npk, npv, nsk, nsv = paged_gqa_attention_block_quantized(
                lp["attn"], h, positions, valid, cfg, mask, pk, pv, sk, sv,
                block_table, kv_spec)
            xc = xc + a
            h = rms_norm(xc, lp["mlp_norm"], cfg.norm_eps)
            xc = xc + mlp_block(lp["mlp"], h, cfg.act)
            return xc, (npk, npv, nsk, nsv)

        x, (nk, nv, nks, nvs) = scan_layers(
            cfg, qbody, x, (params["layers"], cache["k"], cache["v"],
                            cache["k_scale"], cache["v_scale"]))
        new_cache = dict(k=nk, v=nv, k_scale=nks, v_scale=nvs)
    else:

        def body(xc, xs):
            lp, pk, pv = xs
            h = rms_norm(xc, lp["attn_norm"], cfg.norm_eps)
            a, npk, npv = paged_gqa_attention_block(
                lp["attn"], h, positions, valid, cfg, mask, pk, pv,
                block_table)
            xc = xc + a
            h = rms_norm(xc, lp["mlp_norm"], cfg.norm_eps)
            xc = xc + mlp_block(lp["mlp"], h, cfg.act)
            return xc, (npk, npv)

        x, (nk, nv) = scan_layers(
            cfg, body, x, (params["layers"], cache["k"], cache["v"]))
        new_cache = dict(k=nk, v=nv)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if sample_row is not None:
        x = jax.vmap(
            lambda xb, r: jax.lax.dynamic_slice_in_dim(xb, r, 1))(x, sample_row)
    return unembed(cfg, params, x), new_cache


def decode_step(cfg, params, tokens, cache):
    """tokens: (B, 1). Attends to cache[0:offset] + self."""
    x = embed_tokens(cfg, params, tokens)
    b = x.shape[0]
    offset = cache["offset"]
    positions = jnp.broadcast_to(offset, (b, 1))
    kv_len = cache["k"].shape[2]
    kj = jnp.arange(kv_len)[None, :]
    mask = kj <= offset  # (1, kv_len)
    x, cache = run_layers(cfg, params["layers"], x, positions, mask, cache)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return unembed(cfg, params, x), cache
