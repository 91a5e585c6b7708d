"""The dense family: a pre-norm decoder with Llama equations (RMSNorm,
rotary attention with grouped KV heads, SwiGLU MLP) whose seven linears
are W4A4 with the low-rank correction:

    y = (Q_a(x) @ W_int) * s_x * s_w  +  (x @ V) @ U^T

Q_a quantizes each token to the int4 grid with scale clip * max|x| / 7.

This module holds what the benchmark knows of the family without the
program: the sizes it reads from a configuration file (``load_spec``),
the seeded weights (``make_weights``), the plain reference and the work a
step requires (``step_work``).  Imports nothing of the program.

The reference stores activations, the KV cache and the logits in
bfloat16, so every value is rounded to bfloat16 where it is stored; every
product is summed in float32, and every float matmul runs at the precision
the caller names (``highest`` for the reference; see ``mm``).  Roundings
go through ``lax.reduce_precision``, which a compiler may not drop as it
may drop a pair of casts.  The forward is a causal pass over one whole
sequence, layer by layer, with no cache, no paging and no kernels.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Iterable, Tuple

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

from bench import work

# Every quantized linear of a block: name -> (d_in key, d_out key).
LINEARS = {
    "attn/wq": ("d", "q"),
    "attn/wk": ("d", "kv"),
    "attn/wv": ("d", "kv"),
    "attn/wo": ("q", "d"),
    "mlp/wg": ("d", "f"),
    "mlp/wu": ("d", "f"),
    "mlp/wd": ("f", "d"),
}
# the linears whose outputs are cached: layer 0's K and V are compared
KV_LINEARS = ("attn/wk", "attn/wv")
# the cache types the comparison reads: the pool holds K/V as stored
KV_DTYPES = ("bf16",)


@dataclasses.dataclass(frozen=True)
class Spec:
    name: str
    arch: str
    reference: str
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    f: int
    vocab: int
    tied: bool
    eps: float
    theta: float
    act_bits: int
    clip: float
    rank_frac: float
    slots: int
    max_seq: int
    page_size: int
    prefill_chunk: int
    kv_dtype: str

    def width(self, key: str) -> int:
        return {"d": self.d, "q": self.heads * self.head_dim,
                "kv": self.kv_heads * self.head_dim, "f": self.f}[key]

    def shape(self, lin: str):
        """(d_in, d_out, rank) of one quantized linear."""
        k, n = (self.width(w) for w in LINEARS[lin])
        return k, n, max(1, int(round(self.rank_frac * min(k, n))))


def load_spec(name: str, raw: dict) -> Spec:
    """The sizes of configuration ``name`` from its file's contents; what
    the reference cannot compute or compare is refused."""
    q, dep = raw["quant"], raw["deployment"]
    if q["bits"] != 4 or q["act_group"] is not None:
        raise ValueError(f"{name}: the dense reference computes per-token "
                         f"W4A4 only, not {q}")
    if dep["kv_dtype"] not in KV_DTYPES:
        raise ValueError(f"{name}: the dense reference compares a KV cache "
                         f"of {KV_DTYPES}, not {dep['kv_dtype']!r}")
    if raw.get("hidden_act", "silu") != "silu":
        raise ValueError(f"{name}: the dense reference's MLP is SwiGLU")
    return Spec(
        name=name, arch=raw["arch"], reference=raw["reference"],
        layers=raw["num_hidden_layers"], d=raw["hidden_size"],
        heads=raw["num_attention_heads"],
        kv_heads=raw["num_key_value_heads"], head_dim=raw["head_dim"],
        f=raw["intermediate_size"], vocab=raw["vocab_size"],
        tied=raw["tie_word_embeddings"], eps=raw["rms_norm_eps"],
        theta=raw["rope_theta"], act_bits=q["act_bits"],
        clip=q["clip_ratio"], rank_frac=q["rank_frac"],
        slots=dep["slots"], max_seq=dep["max_seq"],
        page_size=dep["page_size"], prefill_chunk=dep["prefill_chunk"],
        kv_dtype=dep["kv_dtype"])


F32, BF16 = jnp.float32, jnp.bfloat16
# (exponent bits, mantissa bits) of each storage type
BITS = {"bfloat16": (8, 7), "float8_e4m3fn": (4, 3)}


def rounded(x, dtype: str = "bfloat16"):
    """``x`` rounded to ``dtype``'s grid, kept in float32."""
    return jax.lax.reduce_precision(x.astype(F32), *BITS[dtype])


def mm(spec_str, a, b, precision: str):
    """``einsum(spec_str, a, b)`` of f32 operands, summed in f32.
    ``highest`` is f32 products; ``high`` is the three bf16 passes
    (hi*hi + hi*lo + lo*hi) a TPU runs for it, written out so that it means
    the same on every backend; ``default`` is one bf16 pass."""
    a, b = a.astype(F32), b.astype(F32)
    one = lambda x, y: jnp.einsum(spec_str, x, y,
                                  precision=jax.lax.Precision.HIGHEST)
    if precision == "highest":
        return one(a, b)
    ah, bh = rounded(a), rounded(b)
    if precision == "default":
        return one(ah, bh)
    if precision == "high":
        al, bl = rounded(a - ah), rounded(b - bh)
        return one(ah, bh) + (one(ah, bl) + one(al, bh))
    raise ValueError(f"unknown precision {precision!r}")


def unpack(packed):
    """(K/2, N) uint8, two int4 nibbles a byte along K -> (K, N) f32."""
    lo = (packed & 0xF).astype(jnp.int32)
    hi = (packed >> 4).astype(jnp.int32)
    lo = jnp.where(lo > 7, lo - 16, lo)
    hi = jnp.where(hi > 7, hi - 16, hi)
    k2, n = packed.shape
    return jnp.stack([lo, hi], axis=1).reshape(2 * k2, n).astype(F32)


def quantize(x, spec: Spec):
    """Per-token int4 activations: (x / s, the grid values, s)."""
    qmax = 2 ** (spec.act_bits - 1) - 1
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    s = jnp.where(amax > 0, spec.clip * amax, 1.0) / qmax
    t = x / s
    return t, jnp.clip(jnp.round(t), -qmax - 1, qmax), s


def qlinear_f32(p, x, spec: Spec, precision):
    """x (S, K) on the bf16 grid -> y (S, N) in float32, before storing."""
    _, xq, s = quantize(x, spec)
    # integer products and sums below 2**24 are exact in float32
    y = mm("sk,kn->sn", xq, unpack(p["qweight"]), "highest") \
        * s * p["w_scale"]
    return y + mm("sr,nr->sn", mm("sk,kr->sr", x, p["v"], precision),
                  p["u"], precision)


def rms_norm(x, gamma, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return rounded(x * gamma.astype(F32))


def rotary(x, positions, theta):
    """x (S, H, hd) at ``positions`` (S,)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = positions.astype(F32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return rounded(jnp.concatenate([x1 * cos - x2 * sin,
                                    x2 * cos + x1 * sin], -1))


def attention(q, k, v, spec: Spec, precision):
    """Causal GQA. q (S, H, hd), k/v (S, KH, hd), all on the bf16 grid."""
    s, h, hd = q.shape
    g = h // spec.kv_heads
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    scores = rounded(mm("qhd,khd->hqk", q, k, precision)) * hd ** -0.5
    causal = jnp.arange(s)[None, :, None] >= jnp.arange(s)[None, None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = rounded(jax.nn.softmax(scores, axis=-1))
    out = mm("hqk,khd->qhd", probs, v, precision)
    return rounded(out).reshape(s, h * hd)


@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def layer(spec: Spec, lw, x, precision, kv_dtype=None):
    """One decoder layer; ``lw`` holds this layer's slice of every leaf.
    Returns the layer's output and the K (after rotary) and V it caches,
    stored in ``kv_dtype`` when given (a control's lower precision)."""
    s = x.shape[0]
    pos = jnp.arange(s)
    lin = lambda name, a: rounded(qlinear_f32(lw["lin"][name], a, spec,
                                              precision))
    h = rms_norm(x, lw["attn_norm"], spec.eps)
    q = rotary(lin("attn/wq", h).reshape(s, spec.heads, spec.head_dim),
               pos, spec.theta)
    k = rotary(lin("attn/wk", h).reshape(s, spec.kv_heads, spec.head_dim),
               pos, spec.theta)
    v = lin("attn/wv", h).reshape(s, spec.kv_heads, spec.head_dim)
    if kv_dtype is not None:
        k, v = rounded(k, kv_dtype), rounded(v, kv_dtype)
    x = rounded(x + lin("attn/wo", attention(q, k, v, spec, precision)))
    h = rms_norm(x, lw["mlp_norm"], spec.eps)
    g = lin("mlp/wg", h)
    a = rounded(jax.nn.sigmoid(g) * g * lin("mlp/wu", h))
    x = rounded(x + lin("mlp/wd", a))
    return x, k.reshape(s, -1), v.reshape(s, -1)


@functools.partial(jax.jit, static_argnums=(0, 3))
def layer0_parts(spec: Spec, lw, x, precision):
    """What layer 0's K and V are made of: the normalised input's x / s,
    grid values and scale, and K (before rotary) and V in float32 before
    they are stored."""
    h = rms_norm(x, lw["attn_norm"], spec.eps)
    t, xq, s = quantize(h, spec)
    return {"t": t, "q": xq, "s": s,
            "yk": qlinear_f32(lw["lin"]["attn/wk"], h, spec, precision),
            "yv": qlinear_f32(lw["lin"]["attn/wv"], h, spec, precision)}


@functools.partial(jax.jit, static_argnums=(0, 3))
def head(spec: Spec, w, x, precision):
    x = rms_norm(x, w["final_norm"], spec.eps)
    hw = w["embed"].T if spec.tied else w["lm_head"]
    return rounded(mm("sd,dv->sv", x, hw, precision))


def layer_weights(w, i):
    return {"attn_norm": w["attn_norm"][i], "mlp_norm": w["mlp_norm"][i],
            "lin": {n: {k: a[i] for k, a in p.items()}
                    for n, p in w["lin"].items()}}


def forward(spec: Spec, w, tokens, precision: str = "highest",
            kv_layers: int = 0, kv_dtype: str = None):
    """Logits (S, V) f32 of one sequence ``tokens`` (S,) int32, and the K
    and V (S, kv_heads * hd) of its first ``kv_layers`` layers."""
    x = w["embed"][tokens].astype(F32)
    kv = []
    for i in range(spec.layers):
        x, k, v = layer(spec, layer_weights(w, i), x, precision, kv_dtype)
        if i < kv_layers:
            kv.append((k, v))
    return head(spec, w, x, precision), kv



def _round_np(x, dtype="bfloat16"):
    return np.asarray(x, np.float32).astype(getattr(ml_dtypes, dtype)) \
        .astype(np.float32)


def store_kv(spec: Spec, yk, yv, positions):
    """K and V as the cache stores them, from the float32 outputs (n,
    kv_heads * hd) of K's and V's linears: rounded to bfloat16, K after
    rotary at ``positions`` and rounded again.  NumPy, on the host."""
    k = _round_np(yk)
    n = k.shape[0]
    x = k.reshape(n, spec.kv_heads, spec.head_dim)
    half = spec.head_dim // 2
    freqs = (1.0 / (spec.theta ** (np.arange(half, dtype=np.float32)
                                   / half))).astype(np.float32)
    ang = np.asarray(positions).astype(np.float32)[:, None] * freqs[None, :]
    cos, sin = np.cos(ang)[:, None, :], np.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return _round_np(out).reshape(n, -1), _round_np(yv)


# ---------------------------------------------------------------- weights
# Seeded weights in the layout calibration produces, made on the device in
# one jitted call: int4 W packed two to a byte along d_in, f32 per-channel
# scales, bf16 LRC factors U (d_out, R) and V (d_in, R), bf16 embedding,
# norms and head.
#
# The scales keep every layer's activations finite and of order one: each
# linear maps unit-RMS inputs to unit-RMS outputs, the two linears that
# write into the residual stream (attn/wo, mlp/wd) are scaled by
# 1/sqrt(2 L), and the low-rank term carries a tenth of a layer's output,
# about what a calibrated rank-10% correction adds.  Embeddings are small
# (std 0.02) so that the layers, not the token's own embedding, carry the
# residual stream: with unit embeddings a tied head ranks the input token
# first at every position.  Logits have a standard deviation of about 3.

Q_STD = 2.5  # spread of the int4 grid values before clipping to [-8, 7]
LRC_SHARE = 0.1
LOGIT_STD = 3.0
EMBED_STD = 0.02


def _linear_weights(key, spec: Spec, name: str, gain: float):
    k, n, r = spec.shape(name)

    def one_layer(lkey):
        # one layer at a time, so the f32 draws never exist for all layers
        kq, ks, ku, kv = jax.random.split(lkey, 4)
        q = jnp.clip(jnp.round(jax.random.normal(kq, (k, n)) * Q_STD), -8, 7)
        nib = (q.astype(jnp.int32) & 0xF).astype(jnp.uint8)
        w_scale = (gain / (jnp.std(q) * k ** 0.5)) * jax.random.uniform(
            ks, (n,), minval=0.75, maxval=1.25)
        v = jax.random.normal(kv, (k, r)) * k ** -0.5
        u = jax.random.normal(ku, (n, r)) * (gain * LRC_SHARE * r ** -0.5)
        return {"qweight": nib[0::2] | (nib[1::2] << 4),
                "w_scale": w_scale.astype(jnp.float32),
                "u": u.astype(jnp.bfloat16), "v": v.astype(jnp.bfloat16)}

    return jax.lax.map(one_layer, jax.random.split(key, spec.layers))


@functools.partial(jax.jit, static_argnums=0)
def make_weights(spec: Spec, key):
    """The configuration's weights for the PRNG ``key``."""
    keys = jax.random.split(key, len(LINEARS) + 4)
    resid = (2 * spec.layers) ** -0.5
    lin = {name: _linear_weights(keys[i], spec, name,
                                 resid if name in ("attn/wo", "mlp/wd")
                                 else 1.0)
           for i, name in enumerate(LINEARS)}
    L, d, v = spec.layers, spec.d, spec.vocab
    w = {
        "embed": (jax.random.normal(keys[-1], (v, d)) * EMBED_STD
                  ).astype(jnp.bfloat16),
        "attn_norm": (1.0 + 0.1 * jax.random.normal(keys[-2], (L, d))
                      ).astype(jnp.bfloat16),
        "mlp_norm": (1.0 + 0.1 * jax.random.normal(keys[-3], (L, d))
                     ).astype(jnp.bfloat16),
        "lin": lin,
    }
    if spec.tied:
        # the head is the embedding: the final norm sets the logit scale
        w["final_norm"] = jnp.full((d,), LOGIT_STD / (EMBED_STD * d ** 0.5),
                                   jnp.bfloat16)
    else:
        w["final_norm"] = jnp.ones((d,), jnp.bfloat16)
        w["lm_head"] = (jax.random.normal(keys[-4], (d, v))
                        * (LOGIT_STD * d ** -0.5)).astype(jnp.bfloat16)
    return w


# ------------------------------------------------------------------- work
def linear_work(spec: Spec, m: int) -> dict:
    """All quantized linears of every layer on ``m`` tokens: the work of
    the fused kernel's calls in one step."""
    per_layer = work.add(*(work.qlinear(m, *spec.shape(name))
                           for name in LINEARS))
    return {key: v * spec.layers for key, v in per_layer.items()}


def step_work(spec: Spec, rows: Iterable[Tuple[int, int]],
              sampled: int) -> dict:
    """One call of the step program.  ``rows``: (first position, new
    tokens) of every real row; ``sampled``: rows that reach the unembed."""
    rows = list(rows)
    m = sum(n for _, n in rows)
    kvw = spec.kv_heads * spec.head_dim
    attn_ops = 0
    kv_read = 0
    for first, n in rows:
        # token at position p attends to p + 1 keys: QK^T and PV
        ctx = n * first + n * (n + 1) // 2
        attn_ops += 4 * spec.heads * spec.head_dim * ctx
        kv_read += first + n
    bf16, f32 = work.BF16, work.F32
    attn = {"int8_ops": 0,
            "float_ops": attn_ops * spec.layers,
            "bytes": 2 * bf16 * kvw * (kv_read + m) * spec.layers}
    head = {"int8_ops": 0, "float_ops": 2 * sampled * spec.d * spec.vocab,
            "bytes": bf16 * spec.d * spec.vocab + f32 * sampled * spec.vocab
            + bf16 * m * spec.d}
    norms = {"int8_ops": 0, "float_ops": 0,
             "bytes": bf16 * spec.d * (2 * spec.layers + 1)}
    return work.add(linear_work(spec, m), attn, head, norms)
