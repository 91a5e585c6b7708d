"""Fused activation prologue (rotate → quantize → low-rank project) vs. the
three-pass reference chain, plus the end-to-end ``w4a4_lrc_forward`` path on
non-multiple-of-block shapes (all interpret mode)."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quantizers import QuantSpec, pack_int4
from repro.kernels import ops, ref
from repro.kernels.prologue import fused_prologue_kernel


def _assert_prologue_matches(x, v, rotate, bm):
    got_q, got_s, got_xv = fused_prologue_kernel(
        x, v, bits=4, clip_ratio=0.9, rotate=rotate, bm=bm, interpret=True
    )
    want_q, want_s, want_xv = ref.fused_prologue_ref(
        x, v, bits=4, clip_ratio=0.9, rotate=rotate
    )
    # acceptance: xq bitwise, sx/xv within 1e-5
    np.testing.assert_array_equal(np.asarray(got_q), np.asarray(want_q))
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               rtol=1e-5, atol=1e-5)
    if v is None:
        assert got_xv is None and want_xv is None
    else:
        np.testing.assert_allclose(np.asarray(got_xv), np.asarray(want_xv),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# kernel vs. three-pass reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,k,r", [
    (16, 64, 0),     # rank-0: quantize only
    (16, 64, 8),
    (32, 128, 16),
    (8, 256, 4),
])
@pytest.mark.parametrize("rotate", [False, True])
def test_prologue_matches_three_pass_ref(rng, m, k, r, rotate):
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((k, r)), jnp.float32) if r else None
    _assert_prologue_matches(x, v, rotate, bm=8)


def test_prologue_block_shape_invariance(rng):
    x = jnp.asarray(rng.standard_normal((32, 128)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((128, 8)), jnp.float32)
    for bm in (8, 16, 32):
        _assert_prologue_matches(x, v, rotate=True, bm=bm)


def test_prologue_bf16_inputs_close(rng):
    """bf16 activations: scales/projection track the reference within bf16
    noise (xq bitwise equality is only guaranteed for f32 inputs)."""
    x = jnp.asarray(rng.standard_normal((16, 128)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((128, 8)), jnp.float32)
    got_q, got_s, got_xv = fused_prologue_kernel(
        x, v, bits=4, clip_ratio=0.9, rotate=False, bm=8, interpret=True
    )
    want_q, want_s, want_xv = ref.fused_prologue_ref(
        x, v, bits=4, clip_ratio=0.9, rotate=False
    )
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(got_xv), np.asarray(want_xv),
                               rtol=1e-5, atol=1e-5)
    assert np.abs(np.asarray(got_q, np.int32) - np.asarray(want_q, np.int32)).max() <= 1


def test_ops_fused_prologue_nonmultiple_m(rng):
    """Wrapper pads/slices M that is not a block multiple."""
    x = jnp.asarray(rng.standard_normal((13, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((64, 5)), jnp.float32)
    q, s, xv = ops.fused_prologue(x, v, QuantSpec(bits=4, clip_ratio=0.9),
                                  rotate=True, bm=8)
    assert q.shape == (13, 64) and s.shape == (13, 1) and xv.shape == (13, 5)
    wq, ws, wxv = ref.fused_prologue_ref(x, v, bits=4, clip_ratio=0.9,
                                         rotate=True)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(wq))
    np.testing.assert_allclose(np.asarray(xv), np.asarray(wxv),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# end-to-end forward (prologue + GEMM/epilogue) with zero-padding
# ---------------------------------------------------------------------------


def _forward_ref(x, q_out_in, scales, u, v, spec, rotate=False):
    xq, sx, xv = ref.fused_prologue_ref(x, v, bits=spec.bits,
                                        clip_ratio=spec.clip_ratio,
                                        rotate=rotate)
    wpacked = pack_int4(q_out_in).T
    sw = scales.reshape(1, -1)
    return ref.w4a4_lowrank_matmul_ref(xq, sx, wpacked, sw, xv, u)


@pytest.mark.parametrize("m,k,n,r", [
    (16, 64, 32, 0),      # decode-regime, block-aligned, rank-0
    (13, 96, 80, 5),      # nothing is a multiple of any block size
    (24, 128, 100, 8),    # odd N only (odd-MLP-width case)
    (7, 64, 64, 3),       # tiny M
])
def test_w4a4_lrc_forward_matches_ref(rng, m, k, n, r):
    spec = QuantSpec(bits=4, clip_ratio=0.9)
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    q = jnp.asarray(rng.integers(-8, 8, (n, k)), jnp.int8)
    s = jnp.asarray(rng.uniform(0.01, 0.2, (n,)), jnp.float32)
    u = jnp.asarray(rng.standard_normal((n, r)), jnp.float32) if r else None
    v = jnp.asarray(rng.standard_normal((k, r)), jnp.float32) if r else None
    got = ops.w4a4_lrc_forward(x, pack_int4(q).T, s, u, v, spec)
    want = _forward_ref(x, q, s, u, v, spec)
    assert got.shape == (m, n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_w4a4_lrc_forward_rotated(rng):
    """Online rotation inside the prologue (pow2 K) end to end."""
    m, k, n, r = 12, 128, 48, 6
    spec = QuantSpec(bits=4, clip_ratio=0.9)
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    q = jnp.asarray(rng.integers(-8, 8, (n, k)), jnp.int8)
    s = jnp.asarray(rng.uniform(0.01, 0.2, (n,)), jnp.float32)
    u = jnp.asarray(rng.standard_normal((n, r)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((k, r)), jnp.float32)
    got = ops.w4a4_lrc_forward(x, pack_int4(q).T, s, u, v, spec, rotate=True)
    want = _forward_ref(x, q, s, u, v, spec, rotate=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_w4a4_lrc_forward_explicit_blocks(rng):
    """Caller-pinned blocks (the autotune-table override) stay exact."""
    m, k, n, r = 32, 128, 64, 8
    spec = QuantSpec(bits=4, clip_ratio=0.9)
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    q = jnp.asarray(rng.integers(-8, 8, (n, k)), jnp.int8)
    s = jnp.asarray(rng.uniform(0.01, 0.2, (n,)), jnp.float32)
    u = jnp.asarray(rng.standard_normal((n, r)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((k, r)), jnp.float32)
    want = _forward_ref(x, q, s, u, v, spec)
    for blocks in [(8, 16, 32), (16, 64, 64), (32, 32, 128)]:
        got = ops.w4a4_lrc_forward(x, pack_int4(q).T, s, u, v, spec,
                                   blocks=blocks)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


def test_select_blocks_regimes():
    """The autotune table keys on the serving regime and clamps to dims.
    select_blocks returns the full Plan NamedTuple (read .bm/.bn/.bk/.br)."""
    p = ops.select_blocks(16, 4096, 11008, 128)   # decode
    assert p.bm <= 16 and p.bn >= 128 and p.br <= 128
    assert ops.select_blocks(256, 4096, 11008, 128).bm == 128   # mixed
    assert ops.select_blocks(2048, 4096, 11008, 128).bm == 256  # prefill
    # tiny problems clamp every block below the table entry; N and R tiles
    # stop at one lane width (the TPU's block rule) and pad the extent up
    p4 = ops.select_blocks(8, 64, 32, 0)
    assert p4.bm <= 8 and p4.bn == 128 and p4.bk <= 64 and p4.br == 128


def test_qlinear_pallas_impl_matches_int8_odd_shapes(rng):
    """QLinear(impl=pallas) now survives non-multiple d_in/d_out widths."""
    from repro.quant.qlinear import make_qlinear, qlinear_apply

    d_in, d_out, r = 96, 80, 8
    q = jnp.asarray(rng.integers(-8, 8, (d_out, d_in)), jnp.int8)
    s = jnp.asarray(rng.uniform(0.01, 0.1, (d_out, 1)), jnp.float32)
    u = jnp.asarray(rng.standard_normal((d_out, r)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((d_in, r)), jnp.float32)
    ql = make_qlinear(q, s, u, v, impl="int8", lr_dtype=jnp.float32)
    x = jnp.asarray(rng.standard_normal((13, d_in)), jnp.float32)
    a = qlinear_apply(ql, x)
    b = qlinear_apply(dataclasses.replace(ql, impl="pallas"), x)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-3, atol=2e-3)


def test_qlinear_pallas_groupwise_runs_kernels(rng):
    """Group-wise-calibrated layers (paper Table 2) now run the kernel
    paths: impl='pallas' serves them with the (M, K/g) scale plane (the
    engine's auto-retag hits every leaf) and matches the grouped int8 GEMM
    reference semantics."""
    from repro.quant.qlinear import make_qlinear, qlinear_apply

    d_in, d_out, g = 128, 64, 32
    q = jnp.asarray(rng.integers(-8, 8, (d_out, d_in)), jnp.int8)
    s = jnp.asarray(rng.uniform(0.01, 0.1, (d_out, 1)), jnp.float32)
    ql = make_qlinear(q, s, act_group=g, impl="int8")
    x = jnp.asarray(rng.standard_normal((8, d_in)), jnp.float32)
    a = qlinear_apply(ql, x)
    b = qlinear_apply(dataclasses.replace(ql, impl="pallas"), x)
    # rank-0 integer math is exact on both paths
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-6, atol=1e-6)


def test_retag_qlinear_impl(rng):
    from repro.quant.qlinear import make_qlinear, retag_qlinear_impl

    q = jnp.asarray(rng.integers(-8, 8, (16, 32)), jnp.int8)
    s = jnp.ones((16, 1), jnp.float32)
    tree = {"a": make_qlinear(q, s, impl="sim"),
            "b": {"w": jnp.ones((4, 4)), "q": make_qlinear(q, s, impl="int8")}}
    out = retag_qlinear_impl(tree, "pallas")
    assert out["a"].impl == "pallas" and out["b"]["q"].impl == "pallas"
    np.testing.assert_array_equal(np.asarray(out["b"]["w"]), np.ones((4, 4)))


def test_w4a4_lrc_forward_large_r_fallback(rng):
    """When nothing fits the VMEM budgets (forced via an explicit context)
    the wrapper silently takes the unfused three-pass chain — results must
    be identical."""
    m, k, n, r = 16, 64, 32, 8
    spec = QuantSpec(bits=4, clip_ratio=0.9)
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    q = jnp.asarray(rng.integers(-8, 8, (n, k)), jnp.int8)
    s = jnp.asarray(rng.uniform(0.01, 0.2, (n,)), jnp.float32)
    u = jnp.asarray(rng.standard_normal((n, r)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((k, r)), jnp.float32)
    want = ops.w4a4_lrc_forward(x, pack_int4(q).T, s, u, v, spec, rotate=True)
    tiny = ops.KernelContext().with_vmem_budgets(fused=0, prologue=1)
    assert tiny.resolve_plan(m, k, n, r, rotate=True).path == "unfused"
    got = ops.w4a4_lrc_forward(x, pack_int4(q).T, s, u, v, spec, rotate=True,
                               ctx=tiny)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_prologue_byte_model_decode_win():
    """The roofline byte model records the fusion ladder at decode shapes:
    chained (PR 1 prologue) well below unfused, and the single-kernel fused
    path strictly below chained by exactly the eliminated xq/sx/xv
    round-trip (acceptance criterion).  The legacy boolean spelling keeps
    mapping onto unfused/chained."""
    from repro.launch.roofline import prologue_activation_bytes

    for k, n in [(4096, 11008), (5120, 13824), (8192, 28672)]:
        for r in (128, 256, 512, 1024):
            unfused = prologue_activation_bytes(16, k, r, rotate=True,
                                                path="unfused")
            chained = prologue_activation_bytes(16, k, r, rotate=True,
                                                path="chained")
            fused = prologue_activation_bytes(16, k, r, rotate=True,
                                              path="fused")
            assert unfused / chained >= 1.5, (k, r, unfused / chained)
            assert chained / fused >= 2.0, (k, r, chained / fused)
            # chained − fused = the M×K xq write+read (+ sx/xv round-trip)
            assert chained - fused == 2 * (16 * k + 4 * 16 + 4 * 16 * r)
            # legacy boolean spelling
            assert prologue_activation_bytes(16, k, r, rotate=True,
                                             fused=True) == chained
            assert prologue_activation_bytes(16, k, r, rotate=True,
                                             fused=False) == unfused
