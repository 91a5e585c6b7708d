"""Sequential per-layer LRC calibration — the paper's full pipeline:

  (1) QuaRot-style rotation fusion (repro.quant.rotate), then
  (2) "LRC works sequentially through the weight matrices of the model,
       computing activations for each weight matrix, obtaining the
       covariance and cross-covariances matrices needed to apply Algorithm 1
       ... before moving to the next layer."  (paper §3)

The walker keeps a running activation stream X (all calibration sequences),
and after solving each layer's weights it re-propagates the stream through
the QUANTIZED layer, so later layers calibrate against the actual deployed
inputs (same discipline as GPTQ/QuaRot).

Supported families: dense / vlm, ssm (in/out projections), moe (MLA
projections + shared and routed experts with per-expert statistics).
Checkpointed per layer → a killed calibration resumes where it stopped.
"""

from __future__ import annotations

import dataclasses
import pickle
from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.lrc import lrc_solve, quantize_baseline, svd_correction
from repro.core.numerics import ensure_x64
from repro.core.quantizers import QuantSpec, dequantize_weight
from repro.core.stats import accumulate_stats, finalize_stats, init_stats
from repro.core.hadamard import apply_rotation
from repro.models.common import (
    attention,
    causal_mask,
    mlp_block,
    prefix_lm_mask,
    rms_norm,
    rope,
)
from repro.models.transformer import embed_tokens
from repro.quant.policy import QuantPolicy
from repro.quant.qlinear import QLinear, apply_linear, make_qlinear
from repro.quant.rotate import rotate_model


# ---------------------------------------------------------------------------
# single-site solver
# ---------------------------------------------------------------------------


def collect_stats(acts, spec_a: QuantSpec, pre_rot: bool = False):
    """acts: (..., d) activation batch → finalized CalibStats (float64)."""
    ensure_x64()
    x = acts.reshape(-1, acts.shape[-1])
    if pre_rot:
        x = apply_rotation(x, x.shape[-1])
    st = init_stats(x.shape[-1])
    chunk = 65536
    for i in range(0, x.shape[0], chunk):
        st = accumulate_stats(st, x[i : i + chunk], spec_a)
    return finalize_stats(st)


def solve_site(w, stats, policy: QuantPolicy, pre_rot: bool = False,
               name: str = None) -> QLinear:
    """w: model-layout (d_in, d_out).  Solves Ŵ, (U, V) per the policy.
    ``name`` tags the QLinear (static metadata) so per-layer plan overrides
    in a KernelContext can target it by layer name."""
    w_paper = jnp.asarray(w, jnp.float64).T  # (d_out, d_in)
    spec_w = QuantSpec(bits=policy.bits)
    k = policy.rank(w.shape[0], w.shape[1])
    if policy.correction == "lrc" and k > 0:
        res = lrc_solve(
            w_paper, stats, spec_w, k=k,
            iters=policy.lrc_iters, quant_method=policy.quant_method,
        )
        q, s, u, v = res.qweight, res.scales, res.u, res.v
    elif policy.correction == "svd" and k > 0:
        q, s, w_hat = quantize_baseline(
            w_paper, stats, spec_w, quant_method=policy.quant_method, hessian="x"
        )
        u, v = svd_correction(w_paper, w_hat, k)
    else:
        q, s, _ = quantize_baseline(
            w_paper, stats, spec_w, quant_method=policy.quant_method, hessian="x"
        )
        u = v = None
    return make_qlinear(
        q, s, u, v,
        act_bits=policy.act_bits,
        # per-layer granularity: the policy's act_group_overrides can give
        # one layer its own scale group (or pin it back to per-token)
        act_group=policy.act_group_for(name),
        clip_ratio=policy.clip_ratio,
        impl=policy.impl,
        name=name,
    )


def _act_spec(policy: QuantPolicy) -> QuantSpec:
    return QuantSpec(
        bits=policy.act_bits, clip_ratio=policy.clip_ratio, group_size=policy.act_group
    )


# ---------------------------------------------------------------------------
# dense / vlm walker
# ---------------------------------------------------------------------------


def _dense_layer_walk(cfg, lp, x, positions, mask, policy):
    """Quantize one dense layer; returns (quantized layer params, new x)."""
    spec_a = _act_spec(policy)
    h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    st = collect_stats(h, spec_a)
    qattn = {}
    for name in ("wq", "wk", "wv"):
        qattn[name] = solve_site(lp["attn"][name], st, policy,
                                 name=f"attn/{name}")

    # attention with the QUANTIZED projections (deployment-faithful stream)
    b, s, _ = x.shape
    hh, kh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = apply_linear(qattn["wq"], h).reshape(b, s, hh, hd)
    k = apply_linear(qattn["wk"], h).reshape(b, s, kh, hd)
    v = apply_linear(qattn["wv"], h).reshape(b, s, kh, hd)
    if cfg.rope_theta > 0:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    pre_o = attention(q, k, v, mask, 1.0 / (hd**0.5)).reshape(b, s, hh * hd)

    st_o = collect_stats(pre_o, spec_a)
    qattn["wo"] = solve_site(lp["attn"]["wo"], st_o, policy, name="attn/wo")
    x = x + apply_linear(qattn["wo"], pre_o)

    h2 = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    st2 = collect_stats(h2, spec_a)
    qmlp = {
        "wg": solve_site(lp["mlp"]["wg"], st2, policy, name="mlp/wg"),
        "wu": solve_site(lp["mlp"]["wu"], st2, policy, name="mlp/wu"),
    }
    g = apply_linear(qmlp["wg"], h2)
    u = apply_linear(qmlp["wu"], h2)
    hidden = (jax.nn.silu(g) if cfg.act == "silu" else jax.nn.gelu(g, approximate=True)) * u
    st3 = collect_stats(hidden, spec_a)
    qmlp["wd"] = solve_site(lp["mlp"]["wd"], st3, policy, name="mlp/wd")
    x = x + apply_linear(qmlp["wd"], hidden)

    qlp = dict(lp)
    qlp["attn"] = qattn
    qlp["mlp"] = qmlp
    return qlp, x


def _stack_layers(layer_list):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layer_list)


def _quantize_dense(cfg, params, tokens, policy, patches=None, progress=None,
                    resume_dir: Optional[Path] = None):
    x = embed_tokens(cfg, params, tokens).astype(jnp.float32)
    if patches is not None:
        x = jnp.concatenate([patches.astype(x.dtype), x], axis=1)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    if cfg.family == "vlm" and patches is not None:
        mask = prefix_lm_mask(s, s, patches.shape[1], 0)
    else:
        mask = causal_mask(s, s, 0)

    new_layers = []
    for l in range(cfg.n_layers):
        ck = resume_dir / f"layer_{l:03d}.pkl" if resume_dir else None
        if ck is not None and ck.exists():
            with open(ck, "rb") as f:
                qlp, x = pickle.load(f)
            qlp = jax.tree.map(jnp.asarray, qlp)
            x = jnp.asarray(x)
        else:
            lp = jax.tree.map(lambda a: a[l], params["layers"])
            qlp, x = _dense_layer_walk(cfg, lp, x, positions, mask, policy)
            if ck is not None:
                ck.parent.mkdir(parents=True, exist_ok=True)
                with open(ck, "wb") as f:
                    pickle.dump(
                        (jax.tree.map(lambda a: jax.device_get(a), qlp),
                         jax.device_get(x)), f)
        new_layers.append(qlp)
        if progress:
            progress(l, cfg.n_layers)
    out = dict(params)
    out["layers"] = _stack_layers(new_layers)
    return out


# ---------------------------------------------------------------------------
# ssm walker
# ---------------------------------------------------------------------------


def _quantize_ssm(cfg, params, tokens, policy, progress=None, resume_dir=None):
    from repro.models.mamba2 import mamba_core

    spec_a = _act_spec(policy)
    x = embed_tokens(cfg, params, tokens).astype(jnp.float32)
    new_layers = []
    for l in range(cfg.n_layers):
        lp = jax.tree.map(lambda a: a[l], params["layers"])
        h = rms_norm(x, lp["norm"], cfg.norm_eps)
        st = collect_stats(h, spec_a)
        q_in = solve_site(lp["in_proj"], st, policy)
        lp_q = dict(lp, in_proj=q_in)
        y, _ = mamba_core(cfg, lp_q, h, None)
        st2 = collect_stats(y, spec_a)
        q_out = solve_site(lp["out_proj"], st2, policy)
        lp_q["out_proj"] = q_out
        x = x + apply_linear(q_out, y)
        new_layers.append(lp_q)
        if progress:
            progress(l, cfg.n_layers)
    out = dict(params)
    out["layers"] = _stack_layers(new_layers)
    return out


# ---------------------------------------------------------------------------
# moe (deepseek) walker
# ---------------------------------------------------------------------------


def _solve_expert_sites(cfg, experts, x_tok, weights, policy):
    """Per-expert statistics: each routed expert calibrates on the tokens the
    router actually sends it (paper quantizes Mixtral the same way)."""
    spec_a = _act_spec(policy)
    e = cfg.n_experts
    qg, qu, qd = [], [], []
    for ei in range(e):
        sel = weights[:, ei] > 0
        # guard: experts with too few routed tokens fall back to all tokens
        xt = jnp.where(sel[:, None], x_tok, 0.0)
        n_sel = int(jnp.sum(sel))
        xe = x_tok[sel] if n_sel >= 8 else x_tok
        st = collect_stats(xe, spec_a)
        wg, wu, wd = experts["wg"][ei], experts["wu"][ei], experts["wd"][ei]
        qge = solve_site(wg, st, policy)
        que = solve_site(wu, st, policy)
        hidden = jax.nn.silu(apply_linear(qge, xe)) * apply_linear(que, xe)
        st2 = collect_stats(hidden, spec_a)
        qde = solve_site(wd, st2, policy)
        qg.append(qge)
        qu.append(que)
        qd.append(qde)
    stack = lambda qs: jax.tree.map(lambda *xs: jnp.stack(xs), *qs)
    return {"wg": stack(qg), "wu": stack(qu), "wd": stack(qd)}


def _moe_layer_walk(cfg, lp, x, positions, mask, policy, moe: bool):
    from repro.models.mla import mla_attention_block
    from repro.models.moe import router_weights

    spec_a = _act_spec(policy)
    h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    st_h = collect_stats(h, spec_a)
    qattn = dict(lp["attn"])
    if "wq_a" in qattn:
        qattn["wq_a"] = solve_site(lp["attn"]["wq_a"], st_h, policy)
        cq = rms_norm(apply_linear(qattn["wq_a"], h), lp["attn"]["q_norm"], cfg.norm_eps)
        qattn["wq_b"] = solve_site(lp["attn"]["wq_b"], collect_stats(cq, spec_a), policy)
    else:
        qattn["wq"] = solve_site(lp["attn"]["wq"], st_h, policy)
    qattn["wkv_a"] = solve_site(lp["attn"]["wkv_a"], st_h, policy)
    kv = apply_linear(qattn["wkv_a"], h)
    c_kv = rms_norm(kv[..., : cfg.kv_lora_rank], lp["attn"]["kv_norm"], cfg.norm_eps)
    qattn["wkv_b"] = solve_site(lp["attn"]["wkv_b"], collect_stats(c_kv, spec_a), policy)

    # run quantized MLA to get pre-o activations: reuse block with wo = identity?
    # simpler: temporarily use FP wo to get attn out then subtract — instead we
    # capture pre-o by calling the block internals
    lp_tmp = dict(lp, attn=dict(qattn, wo=jnp.eye(lp["attn"]["wo"].shape[0], dtype=x.dtype)))
    pre_o, _ = mla_attention_block(cfg, lp_tmp["attn"], h, positions, mask, None)
    qattn["wo"] = solve_site(lp["attn"]["wo"], collect_stats(pre_o, spec_a), policy)
    x = x + apply_linear(qattn["wo"], pre_o)

    h2 = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    st2 = collect_stats(h2, spec_a)
    qlp = dict(lp, attn=qattn)
    if moe:
        qmoe = dict(lp["moe"])
        if "shared" in qmoe:
            qsh = {
                "wg": solve_site(qmoe["shared"]["wg"], st2, policy),
                "wu": solve_site(qmoe["shared"]["wu"], st2, policy),
            }
            hid = jax.nn.silu(apply_linear(qsh["wg"], h2)) * apply_linear(qsh["wu"], h2)
            qsh["wd"] = solve_site(qmoe["shared"]["wd"], collect_stats(hid, spec_a), policy)
            qmoe["shared"] = qsh
        xt = h2.reshape(-1, h2.shape[-1])
        weights, _ = router_weights(cfg, lp["moe"], xt)
        qmoe["experts"] = _solve_expert_sites(cfg, lp["moe"]["experts"], xt, weights, policy)
        qlp["moe"] = qmoe
        from repro.models.moe import moe_block

        x = x + moe_block(cfg, qmoe, h2, impl="dense")
    else:
        qmlp = {
            "wg": solve_site(lp["mlp"]["wg"], st2, policy),
            "wu": solve_site(lp["mlp"]["wu"], st2, policy),
        }
        hid = jax.nn.silu(apply_linear(qmlp["wg"], h2)) * apply_linear(qmlp["wu"], h2)
        qmlp["wd"] = solve_site(lp["mlp"]["wd"], collect_stats(hid, spec_a), policy)
        qlp["mlp"] = qmlp
        x = x + apply_linear(qmlp["wd"], hid)
    return qlp, x


def _quantize_moe(cfg, params, tokens, policy, progress=None, resume_dir=None):
    x = embed_tokens(cfg, params, tokens).astype(jnp.float32)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    mask = causal_mask(s, s, 0)
    out = dict(params)
    done = 0
    total = cfg.n_layers
    for group, moe in (("dense_layers", False), ("moe_layers", True)):
        if group not in params:
            continue
        n = jax.tree.leaves(params[group])[0].shape[0]
        new_layers = []
        for l in range(n):
            lp = jax.tree.map(lambda a: a[l], params[group])
            qlp, x = _moe_layer_walk(cfg, lp, x, positions, mask, policy, moe)
            new_layers.append(qlp)
            done += 1
            if progress:
                progress(done, total)
        out[group] = _stack_layers(new_layers)
    return out


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


def quantize_model(
    cfg,
    params,
    calib_tokens,
    policy: QuantPolicy,
    rotate: bool = True,
    patches=None,
    progress=None,
    resume_dir: Optional[str] = None,
):
    """Returns params with policy-selected weights replaced by solved
    QLinear leaves.  ``calib_tokens``: (n_seq, S) int32.

    The solves run in float64 (paper §3), but x64 is on only for the
    duration of the call: the process keeps its 32-bit default afterwards,
    so the model it goes on to serve traces with 32-bit indices and floats
    (Mosaic refuses 64-bit index arithmetic in the kernels' block maps)."""
    with jax.enable_x64(True):
        return _quantize(cfg, params, calib_tokens, policy, rotate, patches,
                         progress, resume_dir)


def _quantize(cfg, params, calib_tokens, policy, rotate, patches, progress,
              resume_dir):
    if rotate:
        params = rotate_model(cfg, params)
    rd = Path(resume_dir) if resume_dir else None
    if cfg.family in ("dense", "vlm"):
        return _quantize_dense(cfg, params, calib_tokens, policy,
                               patches=patches, progress=progress, resume_dir=rd)
    if cfg.family == "ssm":
        return _quantize_ssm(cfg, params, calib_tokens, policy,
                             progress=progress, resume_dir=rd)
    if cfg.family == "moe":
        return _quantize_moe(cfg, params, calib_tokens, policy,
                             progress=progress, resume_dir=rd)
    raise NotImplementedError(
        f"calibration walker not implemented for family {cfg.family!r}"
    )
