"""Blocked Walsh-Hadamard transform kernel (QuaRot online rotation, R3/R4).

Applies the normalized WHT over the last (power-of-two) axis of a row tile
held in VMEM: log2(D) butterfly sweeps, no HBM round-trips between stages.
Odd Kronecker factors (d = m·2^k) are applied by the wrapper as a small dense
matmul (repro.core.hadamard semantics).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.rowops import fwht_rows


def _kernel(x_ref, o_ref, *, d: int):
    y = fwht_rows(x_ref[...].astype(jnp.float32), d)
    o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def fwht_kernel(x: jnp.ndarray, bm: int = 256, *, interpret: bool):
    """x: (M, D) with D a power of two; returns x @ H_D (normalized)."""
    m, d = x.shape
    assert d & (d - 1) == 0, d
    assert m % bm == 0, (m, bm)
    return pl.pallas_call(
        functools.partial(_kernel, d=d),
        grid=(m // bm,),
        in_specs=[pl.BlockSpec((bm, d), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bm, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),  # M tiles are independent
        ),
        name="fwht_kernel",
        interpret=interpret,
    )(x)
