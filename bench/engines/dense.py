"""The dense family's side of the program: the program's model
configuration with the file's sizes, the served parameter tree built from
the benchmark's weights, and the read-back of the K/V the timed path
wrote into the page pool."""

from __future__ import annotations

import dataclasses

import jax
import numpy as np

from bench.reference.dense import LINEARS, Spec


def program_config(spec: Spec):
    """The program's model configuration, with the file's sizes; one that
    the dense reference does not compute is refused."""
    from repro.configs import get_config

    cfg = get_config(spec.arch)
    fixed = {"family": "dense", "act": "silu", "attn_kind": "gqa",
             "logit_softcap": 0.0, "embed_scale": False, "dtype": "bfloat16"}
    got = {k: getattr(cfg, k) for k in fixed}
    if got != fixed:
        raise ValueError(f"{spec.arch}: {got} is not the dense reference's "
                         f"{fixed}")
    return dataclasses.replace(
        cfg, n_layers=spec.layers, d_model=spec.d, n_heads=spec.heads,
        n_kv_heads=spec.kv_heads, head_dim=spec.head_dim, d_ff=spec.f,
        vocab_size=spec.vocab, tie_embeddings=spec.tied, norm_eps=spec.eps,
        rope_theta=spec.theta)


def program_params(spec: Spec, w):
    """The served parameter tree: the benchmark's arrays in ``QLinear``
    leaves (stacked over layers, as calibration stacks them)."""
    from repro.quant.qlinear import QLinear

    def qlin(name):
        p = w["lin"][name]
        return QLinear(qweight=p["qweight"], w_scale=p["w_scale"],
                       u=p["u"], v=p["v"], bits=4, act_bits=spec.act_bits,
                       act_group=None, clip_ratio=spec.clip, impl="pallas",
                       name=name)

    layers = {"attn_norm": w["attn_norm"], "mlp_norm": w["mlp_norm"],
              "attn": {}, "mlp": {}}
    for name in LINEARS:
        group, leaf = name.split("/")
        layers[group][leaf] = qlin(name)
    params = {"embed": w["embed"], "layers": layers,
              "final_norm": w["final_norm"]}
    if not spec.tied:
        params["lm_head"] = w["lm_head"]
    return params


def read_pool(eng, pages, n_positions: int, layers: int):
    """The K and V at the first ``n_positions`` positions of ``pages`` (a
    request's pages in position order), for the first ``layers`` layers:
    two f32 arrays (layers, n, kv_heads * hd).  The pool holds them as
    stored (a cache type of ``KV_DTYPES``)."""
    pages = np.asarray(pages, np.int32)
    out = []
    for leaf in ("k", "v"):
        a = eng.pool[leaf][:layers, pages]  # (l, np, P, kh, hd)
        a = np.asarray(jax.device_get(a), np.float32)
        out.append(a.reshape(layers, -1, a.shape[-2] * a.shape[-1])
                   [:, :n_positions])
    return out
