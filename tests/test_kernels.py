"""Per-kernel validation: shape/dtype sweeps against the pure-jnp oracles
(interpret=True executes the kernel bodies on CPU), plus hypothesis
property tests on the quantizer kernel's invariants."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.quantizers import QuantSpec, pack_int4
from repro.kernels import ops, ref
from repro.kernels.actquant import act_quant_kernel
from repro.kernels.hadamard import fwht_kernel
from repro.kernels.w4a4 import w4a4_lowrank_matmul_kernel


# ---------------------------------------------------------------------------
# int4 nibble planes (kernels/rowops.py)
# ---------------------------------------------------------------------------


def test_nibble_planes_are_even_and_odd_rows_of_unpack():
    """For every byte value, the low/high nibble planes are the even and
    odd K rows of the interleaved unpack, and an even/odd-split activation
    chunk contracted against them gives the interleaved GEMM's int32s."""
    from repro.kernels.rowops import (even_odd_perm, gemm_chunk_planes,
                                      int_dot, split_even_odd,
                                      unpack_int4_planes, unpack_int4_rows)

    wp = jnp.arange(256, dtype=jnp.uint8).reshape(2, 128)
    wp = jnp.concatenate([wp, wp[::-1, ::-1]], axis=0)  # (4, 128): bk 8
    lo, hi = unpack_int4_planes(wp)
    rows = np.asarray(unpack_int4_rows(wp))
    assert lo.dtype == hi.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(lo), rows[0::2])
    np.testing.assert_array_equal(np.asarray(hi), rows[1::2])

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.integers(-8, 8, (16, 8)), jnp.int8)
    split = np.asarray(split_even_odd(q, even_odd_perm(8)))
    np.testing.assert_array_equal(
        split, np.concatenate([q[:, 0::2], q[:, 1::2]], axis=1))
    np.testing.assert_array_equal(
        np.asarray(gemm_chunk_planes(jnp.asarray(split), wp)),
        np.asarray(int_dot(q, unpack_int4_rows(wp))))


@pytest.mark.parametrize("group", [1, 2, 3, 4, 6, 8, 24])
def test_grouped_nibble_planes_bitwise_equal_interleaved(group):
    """The grouped GEMM chunk from the nibble planes equals the canonical
    K-order spelling bit for bit, for even groups (g/2 rows a plane) and
    odd ones (whose rows straddle the planes)."""
    from repro.kernels.rowops import (even_odd_perm, gemm_chunk_grouped,
                                      gemm_chunk_grouped_planes,
                                      split_even_odd, unpack_int4_rows)

    bk = 24
    rng = np.random.default_rng(group)
    wp = jnp.asarray(rng.integers(0, 256, (bk // 2, 128)), jnp.uint8)
    q = jnp.asarray(rng.integers(-8, 8, (16, bk)), jnp.int8)
    s = jnp.asarray(rng.uniform(0.01, 1.0, (16, bk // group)), jnp.float32)
    want = gemm_chunk_grouped(q, unpack_int4_rows(wp), s, group)
    got = gemm_chunk_grouped_planes(split_even_odd(q, even_odd_perm(bk)),
                                    wp, s, group)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# w4a4 fused matmul
# ---------------------------------------------------------------------------


def _make_w4a4_problem(rng, m, k, n, r, dtype):
    xq = jnp.asarray(rng.integers(-8, 8, (m, k)), jnp.int8)
    sx = jnp.asarray(rng.uniform(0.01, 0.2, (m, 1)), jnp.float32)
    q = jnp.asarray(rng.integers(-8, 8, (n, k)), jnp.int8)  # (d_out, d_in)
    wpacked = pack_int4(q).T  # (k//2, n)
    sw = jnp.asarray(rng.uniform(0.01, 0.2, (1, n)), jnp.float32)
    xv = u = None
    if r:
        xv = jnp.asarray(rng.standard_normal((m, r)), jnp.float32)
        u = jnp.asarray(rng.standard_normal((n, r)), dtype)
    return xq, sx, wpacked, sw, xv, u


@pytest.mark.parametrize("m,k,n,r", [
    (16, 64, 32, 0),
    (16, 64, 32, 8),
    (32, 128, 64, 16),
    (8, 32, 128, 4),
])
@pytest.mark.parametrize("lr_dtype", [jnp.float32, jnp.bfloat16])
def test_w4a4_kernel_matches_ref(rng, m, k, n, r, lr_dtype):
    xq, sx, wpacked, sw, xv, u = _make_w4a4_problem(rng, m, k, n, r, lr_dtype)
    got = w4a4_lowrank_matmul_kernel(
        xq, sx, wpacked, sw, xv, None if u is None else jnp.asarray(u, jnp.float32),
        bm=8, bn=16, bk=32, interpret=True,
    )
    want = ref.w4a4_lowrank_matmul_ref(xq, sx, wpacked, sw, xv,
                                       None if u is None else jnp.asarray(u, jnp.float32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("blocks", [(8, 16, 32), (16, 32, 64), (8, 8, 128)])
def test_w4a4_kernel_block_shape_invariance(rng, blocks):
    bm, bn, bk = blocks
    xq, sx, wpacked, sw, xv, u = _make_w4a4_problem(rng, 32, 128, 64, 8, jnp.float32)
    got = w4a4_lowrank_matmul_kernel(xq, sx, wpacked, sw, xv, u,
                                     bm=bm, bn=bn, bk=bk, interpret=True)
    want = ref.w4a4_lowrank_matmul_ref(xq, sx, wpacked, sw, xv, u)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_w4a4_end_to_end_matches_qlinear_int8(rng):
    """ops.w4a4_lowrank_matmul (pallas path) == QLinear int8 path."""
    import dataclasses
    from repro.quant.qlinear import make_qlinear, qlinear_apply

    d_in, d_out, r = 128, 64, 8
    q = jnp.asarray(rng.integers(-8, 8, (d_out, d_in)), jnp.int8)
    s = jnp.asarray(rng.uniform(0.01, 0.1, (d_out, 1)), jnp.float32)
    u = jnp.asarray(rng.standard_normal((d_out, r)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((d_in, r)), jnp.float32)
    ql = make_qlinear(q, s, u, v, impl="int8", lr_dtype=jnp.float32)
    x = jnp.asarray(rng.standard_normal((24, d_in)), jnp.float32)
    a = qlinear_apply(ql, x)
    b = qlinear_apply(dataclasses.replace(ql, impl="pallas"), x)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# act quant
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,k", [(16, 64), (128, 32), (256, 512)])
@pytest.mark.parametrize("bits", [4, 8])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_actquant_kernel_matches_ref(rng, m, k, bits, dtype):
    x = jnp.asarray(rng.standard_normal((m, k)), dtype)
    q, s = act_quant_kernel(x, bits=bits, clip_ratio=0.9, bm=min(16, m), interpret=True)
    qr, sr = ref.act_quant_ref(x, bits=bits, clip_ratio=0.9)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)
    dq = np.abs(np.asarray(q, np.int32) - np.asarray(qr, np.int32))
    if dtype == jnp.float32:
        np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    else:
        # bf16 inputs land exactly on .5 grid ties; a 1-ulp difference in the
        # scale flips the round — allow ±1 on a vanishing fraction
        assert dq.max() <= 1
        assert (dq > 0).mean() < 1e-3


@settings(max_examples=30, deadline=None)
@given(
    m=st.integers(1, 8).map(lambda i: 8 * i),
    k=st.sampled_from([16, 64, 256]),
    bits=st.sampled_from([4, 8]),
    seed=st.integers(0, 2**31 - 1),
)
def test_actquant_property_roundtrip_bound(m, k, bits, seed):
    """|x - q·s| ≤ s/2 elementwise (within the clip range) and q on-grid."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((m, k)) * rng.uniform(0.1, 10), jnp.float32)
    q, s = act_quant_kernel(x, bits=bits, clip_ratio=1.0, bm=8, interpret=True)
    q = np.asarray(q, np.int32)
    s = np.asarray(s)
    qmax = 2 ** (bits - 1) - 1
    assert q.max() <= qmax and q.min() >= -qmax - 1
    recon = q * s
    assert np.all(np.abs(np.asarray(x) - recon) <= s / 2 + 1e-6)


# ---------------------------------------------------------------------------
# hadamard
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,d", [(8, 16), (32, 128), (16, 1024)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fwht_kernel_matches_ref(rng, m, d, dtype):
    x = jnp.asarray(rng.standard_normal((m, d)), dtype)
    got = fwht_kernel(x, bm=8, interpret=True)
    want = ref.fwht_ref(x)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=2e-2, atol=2e-2
    )


@settings(max_examples=20, deadline=None)
@given(d_log=st.integers(2, 9), seed=st.integers(0, 2**31 - 1))
def test_fwht_property_orthogonal(d_log, seed):
    """WHT preserves norms and double application is the identity."""
    d = 2 ** d_log
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((8, d)), jnp.float32)
    y = fwht_kernel(x, bm=8, interpret=True)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(y), axis=-1),
        np.linalg.norm(np.asarray(x), axis=-1),
        rtol=1e-4,
    )
    z = fwht_kernel(y, bm=8, interpret=True)
    np.testing.assert_allclose(np.asarray(z), np.asarray(x), rtol=1e-4, atol=1e-4)


def test_ops_padding_path(rng):
    """Non-multiple M exercises the pad/slice wrapper."""
    x = jnp.asarray(rng.standard_normal((13, 64)), jnp.float32)
    q, s = ops.act_quant(x, QuantSpec(bits=4))
    assert q.shape == (13, 64) and s.shape == (13, 1)
    y = ops.fwht(jnp.asarray(rng.standard_normal((7, 32)), jnp.float32))
    assert y.shape == (7, 32)

# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sq,skv,bq,bkv", [(32, 32, 8, 8), (64, 64, 16, 32), (16, 128, 16, 16)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_ref(rng, sq, skv, bq, bkv, causal):
    from repro.kernels.flash_attn import flash_attention_kernel

    if causal and sq != skv:
        pytest.skip("causal tile math assumes aligned q/kv starts")
    bh, d = 3, 16
    q = jnp.asarray(rng.standard_normal((bh, sq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((bh, skv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((bh, skv, d)), jnp.float32)
    got = flash_attention_kernel(q, k, v, 0.25, causal=causal, bq=bq, bkv=bkv,
                                 interpret=True)
    want = ref.flash_attention_ref(q, k, v, 0.25, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


def test_flash_attention_gqa_wrapper_matches_model_attention(rng):
    from repro.kernels.ops import flash_attention
    from repro.models.common import attention, causal_mask

    b, s, h, kh, d = 2, 32, 4, 2, 16
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, kh, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, kh, d)), jnp.float32)
    got = flash_attention(q, k, v, 0.25, causal=True, bq=8, bkv=8)
    want = attention(q, k, v, causal_mask(s, s, 0), 0.25)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


def test_paged_flash_attention_matches_dense_gather(rng):
    """The in-kernel page gather == dense attention over the logically
    contiguous KV, for shuffled non-contiguous page placements and per-
    sequence lengths; unowned/null pages hold garbage that must not leak."""
    from repro.kernels.ops import paged_flash_attention

    b, h, kh, d, page, mpb, npages = 3, 4, 2, 16, 4, 6, 16
    g = h // kh
    q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32)
    # pool full of garbage; only block-table-owned positions are real
    k_pages = jnp.asarray(rng.standard_normal((npages, page, kh, d)) * 50,
                          jnp.float32)
    v_pages = jnp.asarray(rng.standard_normal((npages, page, kh, d)) * 50,
                          jnp.float32)
    lengths = np.asarray([5, 24, 13], np.int32)
    ids = rng.permutation(np.arange(1, npages))  # non-contiguous, page 0 never owned
    bt = np.zeros((b, mpb), np.int32)
    taken = 0
    for i in range(b):
        n = -(-int(lengths[i]) // page)
        bt[i, :n] = ids[taken:taken + n]
        taken += n
    got = paged_flash_attention(q, k_pages, v_pages, bt, lengths, 0.25)

    # reference: gather each sequence's pages contiguously, truncate to its
    # length, plain softmax attention per query head
    for i in range(b):
        L = int(lengths[i])
        kk = k_pages[bt[i]].reshape(mpb * page, kh, d)[:L]
        vv = v_pages[bt[i]].reshape(mpb * page, kh, d)[:L]
        for hh in range(h):
            c = hh // g  # kv head of this query head's group
            s = (q[i, hh] * 0.25) @ kk[:, c].T
            want = jax.nn.softmax(s) @ vv[:, c]
            np.testing.assert_allclose(np.asarray(got[i, hh]),
                                       np.asarray(want),
                                       rtol=2e-4, atol=2e-4)
