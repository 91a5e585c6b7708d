"""On-the-fly activation quantizer kernel (paper §2: scale-then-round by
c·max|x|).  One pass over the activations in VMEM produces the int grid
values and the scales — this is the "fast (simple!) scheme" the paper
requires for online quantization.  ``group`` switches the (M, 1) per-token
scale for the (M, K // group) per-group scale plane (paper Table 2,
g = 128); the group bodies live in rowops.py and are shared with the
prologue and fused kernels, so all paths quantize bitwise identically.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.rowops import scale_round_quantize


def _kernel(x_ref, q_ref, s_ref, *, qmax: int, clip_ratio: float, group):
    x = x_ref[...].astype(jnp.float32)
    q, s = scale_round_quantize(x, qmax, clip_ratio, group=group)
    q_ref[...] = q
    s_ref[...] = s


@functools.partial(jax.jit, static_argnames=("bits", "clip_ratio", "bm",
                                             "group", "interpret"))
def act_quant_kernel(
    x: jnp.ndarray,  # (M, K)
    bits: int = 4,
    clip_ratio: float = 1.0,
    bm: int = 128,
    group: int = None,  # None = per-token; else scales per K group
    *,
    interpret: bool,
):
    m, k = x.shape
    assert m % bm == 0, (m, bm)
    if group is not None:
        assert k % group == 0, (k, group)
    n_s = 1 if group is None else k // group
    qmax = 2 ** (bits - 1) - 1
    q, s = pl.pallas_call(
        functools.partial(_kernel, qmax=qmax, clip_ratio=clip_ratio,
                          group=group),
        grid=(m // bm,),
        in_specs=[pl.BlockSpec((bm, k), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((bm, k), lambda i: (i, 0)),
            pl.BlockSpec((bm, n_s), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, k), jnp.int8),
            jax.ShapeDtypeStruct((m, n_s), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),  # M tiles are independent
        ),
        name="act_quant_kernel",
        interpret=interpret,
    )(x)
    return q, s
