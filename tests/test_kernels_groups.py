"""Group-wise activation scales through the kernel stack (paper Table 2,
g = 128): cross-path bitwise parity with the (M, K/g) scale plane, zero
padding at group boundaries, the g = K per-token degeneracy, bk/g
feasibility snapping in resolve_plan, and the QLinear fast-path acceptance
(grouped layers no longer demote to the jnp int8 GEMM).  All kernels run in
pallas interpret mode."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.common import make_w4a4_problem as _problem
from repro.core.quantizers import QuantSpec
from repro.kernels import ops
from repro.kernels.context import (KernelContext, fused_vmem_bytes,
                                   prologue_vmem_bytes)
from repro.kernels.fused_gemm import fused_w4a4_lrc_kernel
from repro.kernels.rowops import snap_bk_to_group


# ---------------------------------------------------------------------------
# cross-path bitwise parity with grouped scales (the PR acceptance)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,k,n,r,g", [
    (16, 256, 100, 16, 64),    # decode, odd N, rank > 0
    (13, 192, 80, 5, 64),      # odd everything; K = 3 groups
    (8, 256, 64, 0, 128),      # rank-0
    (64, 512, 96, 8, 128),     # mixed regime, the paper's g
])
@pytest.mark.parametrize("rotate", [False, True])
def test_grouped_bitwise_parity_across_paths(rng, m, k, n, r, g, rotate):
    if rotate and k & (k - 1):
        pytest.skip("online rotation needs power-of-two K")
    spec, x, wp, s, u, v = _problem(rng, m, k, n, r, act_group=g)
    outs = {
        impl: np.asarray(ops.w4a4_lrc_forward(x, wp, s, u, v, spec,
                                              rotate=rotate, impl=impl))
        for impl in ("fused", "chained", "unfused", "auto")
    }
    np.testing.assert_array_equal(outs["fused"], outs["chained"])
    np.testing.assert_array_equal(outs["fused"], outs["unfused"])
    np.testing.assert_array_equal(outs["fused"], outs["auto"])
    assert outs["fused"].shape == (m, n)


def test_grouped_matches_jnp_grouped_reference(rng):
    """The kernel-path grouped math equals the jnp int8 grouped GEMM
    (QLinear impl="int8") semantics: same quantizer grid, same per-group
    rescale — only f32 summation order differs, so allclose."""
    m, k, n, g = 16, 256, 64, 64
    spec, x, wp, s, u, v = _problem(rng, m, k, n, 0, act_group=g)
    got = np.asarray(ops.w4a4_lrc_forward(x, wp, s, u, v, spec,
                                          impl="fused"))
    from repro.core.quantizers import quantize_act, unpack_int4
    xq, sx = quantize_act(x, spec)
    wq = unpack_int4(wp.T).T.astype(jnp.int32)  # (K, N)
    accg = jnp.einsum("mgk,gkn->mgn",
                      xq.reshape(m, k // g, g).astype(jnp.int32),
                      wq.reshape(k // g, g, n))
    want = jnp.sum(accg.astype(jnp.float32) * sx[..., None], axis=1) * s
    np.testing.assert_allclose(got, np.asarray(want), rtol=1e-5, atol=1e-5)


def test_grouped_fused_variants_bitwise_equal(rng):
    """Resident vs. streamed prologue with grouped scales: the streamed
    sweep finalizes each chunk's group scales chunk-locally (no amax fold),
    which must reproduce the resident whole-row group reductions bit for
    bit."""
    m, k, n, r, g = 16, 512, 64, 8, 128
    spec, x, wp, s, u, v = _problem(rng, m, k, n, r, act_group=g)
    sw = s.reshape(1, -1)
    outs = [
        np.asarray(fused_w4a4_lrc_kernel(
            x, v, wp, sw, u, bits=4, clip_ratio=0.9, rotate=False,
            bm=16, bn=32, bk=128, br=8, variant=variant, act_group=g,
            interpret=True))
        for variant in ("resident", "streamed")
    ]
    np.testing.assert_array_equal(outs[0], outs[1])


# ---------------------------------------------------------------------------
# zero padding at a group boundary
# ---------------------------------------------------------------------------


def test_grouped_odd_width_pads_whole_groups(rng):
    """K = 192 with g = 64 under a bk = 128 tiling pads one whole zero
    group (k_pad = 256): the padded group's guarded scale quantizes only
    zeros, its rescaled partial sums are exact +0.0, and all three paths
    stay bitwise identical — with an odd N riding along."""
    m, k, n, r, g = 9, 192, 100, 5, 64
    spec, x, wp, s, u, v = _problem(rng, m, k, n, r, act_group=g)
    blocks = (8, 32, 128, 8)  # bk=128 -> k_pad=256 > K: a zero tail group
    outs = [np.asarray(ops.w4a4_lrc_forward(x, wp, s, u, v, spec,
                                            blocks=blocks, impl=impl))
            for impl in ("fused", "chained", "unfused")]
    np.testing.assert_array_equal(outs[0], outs[1])
    np.testing.assert_array_equal(outs[0], outs[2])
    # the padded tail changes nothing vs. a tiling with no K padding
    aligned = np.asarray(ops.w4a4_lrc_forward(x, wp, s, u, v, spec,
                                              blocks=(8, 32, 64, 8),
                                              impl="chained"))
    np.testing.assert_allclose(outs[0], aligned, rtol=1e-4, atol=1e-4)


def test_grouped_scale_plane_shape_and_padding(rng):
    """ops.act_quant / ops.fused_prologue emit the unpadded (M, K/g)
    plane; padded groups never leak out."""
    m, k, g = 9, 192, 64
    spec = QuantSpec(bits=4, clip_ratio=0.9, group_size=g)
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    q, s = ops.act_quant(x, spec)
    assert q.shape == (m, k) and s.shape == (m, k // g)
    v = jnp.asarray(rng.standard_normal((k, 8)), jnp.float32)
    q2, s2, xv = ops.fused_prologue(x, v, spec, bk=128)
    assert s2.shape == (m, k // g) and xv.shape == (m, 8)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q2))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(s2))


# ---------------------------------------------------------------------------
# g = K degenerates to per-token
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("impl", ["fused", "chained", "unfused"])
def test_group_equals_k_degenerates_to_per_token(rng, impl):
    """One group spanning the whole row IS per-token quantization: the
    same reductions, guard and scale·round on the same operands — outputs
    bitwise equal to the per-token path on every impl."""
    m, k, n, r = 8, 128, 64, 8
    spec_g, x, wp, s, u, v = _problem(rng, m, k, n, r, act_group=k)
    spec_t = dataclasses.replace(spec_g, group_size=None)
    got = np.asarray(ops.w4a4_lrc_forward(x, wp, s, u, v, spec_g, impl=impl))
    want = np.asarray(ops.w4a4_lrc_forward(x, wp, s, u, v, spec_t, impl=impl))
    np.testing.assert_array_equal(got, want)


def test_group_equals_k_scale_plane_matches_per_token(rng):
    m, k = 16, 256
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    qg, sg = ops.act_quant(x, QuantSpec(bits=4, clip_ratio=0.9, group_size=k))
    qt, st = ops.act_quant(x, QuantSpec(bits=4, clip_ratio=0.9))
    np.testing.assert_array_equal(np.asarray(qg), np.asarray(qt))
    np.testing.assert_array_equal(np.asarray(sg), np.asarray(st))


# ---------------------------------------------------------------------------
# bk/g feasibility snapping in resolve_plan
# ---------------------------------------------------------------------------


def test_snap_bk_to_group():
    assert snap_bk_to_group(512, 128) == 512   # already a multiple
    assert snap_bk_to_group(512, 96) == 384    # lcm(96, 128)
    assert snap_bk_to_group(256, 96) == 384    # lane chunks: up to the lcm
    assert snap_bk_to_group(100, 96) == 96     # whole-K chunk: one group
    assert snap_bk_to_group(64, 128) == 128    # g > bk snaps UP to g
    assert snap_bk_to_group(4096, 4096) == 4096  # g = K pins bk = K


def test_resolve_plan_snaps_bk_to_group_multiple():
    ctx = KernelContext()
    for g in (96, 128, 512):
        k = g * 20 if g != 512 else g * 8
        plan = ctx.resolve_plan(16, k, 512, 128, act_group=g)
        assert plan.bk % g == 0, (g, plan)
        assert plan.path == "fused"
    # the per-token plan is untouched by the new axis
    assert ctx.resolve_plan(16, 4096, 11008, 128) == \
        ctx.resolve_plan(16, 4096, 11008, 128, act_group=None)


def test_resolve_plan_group_must_divide_k():
    with pytest.raises(ValueError, match="act_group 96 must divide K"):
        KernelContext().resolve_plan(16, 4096, 11008, 128, act_group=96)


def test_resolve_plan_grouped_demotes_when_nothing_fits():
    """bk cannot shrink below one group, so a huge group under a tiny fused
    budget demotes — and the chained fit honors the same constraint."""
    ctx = KernelContext().with_vmem_budgets(fused=1 << 16)
    plan = ctx.resolve_plan(16, 8192, 512, 0, act_group=8192)
    assert plan.path != "fused"
    assert plan.bk % 8192 == 0
    # with both budgets zero the grouped plan lands on unfused, bk snapped
    none = KernelContext().with_vmem_budgets(fused=0, prologue=0) \
        .resolve_plan(16, 1024, 512, 0, act_group=256)
    assert none.path == "unfused" and none.bk % 256 == 0


def test_vmem_models_grow_scale_plane_bytes():
    """The working-set models charge the (bm, K/g) f32 plane: grouped
    footprints exceed per-token by exactly the extra plane bytes."""
    k, r, bm, bn, bk, br, g = 4096, 128, 16, 256, 512, 128, 128
    extra = bm * (k // g - 1) * 4
    assert fused_vmem_bytes(k, r, bm, bn, bk, br, True, act_group=g) \
        - fused_vmem_bytes(k, r, bm, bn, bk, br, True) == extra
    assert prologue_vmem_bytes(k, r, bm, bk, br, False, act_group=g) \
        - prologue_vmem_bytes(k, r, bm, bk, br, False) == extra


def test_explain_reports_group_snap_and_demotion():
    ctx = KernelContext()
    report = ctx.explain(16, 1920, 512, 128, act_group=96)
    assert "act_group=96" in report
    assert "multiple of" in report and "scale plane" in report
    assert "bk 512->384" in report  # decode table bk snapped
    tight = ctx.with_vmem_budgets(fused=0, prologue=0)
    report2 = tight.explain(16, 1920, 512, 128, act_group=96)
    assert "demoted fused->unfused" in report2
    assert "no multiple-of-96 bk tiling" in report2


# ---------------------------------------------------------------------------
# QLinear fast-path acceptance: no int8 demotion for grouped layers
# ---------------------------------------------------------------------------


def test_qlinear_fused_act_group_128_takes_fused_path(rng):
    """QLinear(impl="fused", act_group=128) runs the single-kernel pallas
    path — its output is BITWISE the fused kernel's, not the jnp int8
    GEMM's — and auto dispatch resolves the grouped shape to fused."""
    from repro.quant.qlinear import make_qlinear, qlinear_apply

    d_in, d_out, r, g = 256, 100, 16, 128
    q = jnp.asarray(rng.integers(-8, 8, (d_out, d_in)), jnp.int8)
    s = jnp.asarray(rng.uniform(0.01, 0.1, (d_out, 1)), jnp.float32)
    u = jnp.asarray(rng.standard_normal((d_out, r)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((d_in, r)), jnp.float32)
    ql = make_qlinear(q, s, u, v, act_group=g, impl="fused",
                      lr_dtype=jnp.float32)
    x = jnp.asarray(rng.standard_normal((8, d_in)), jnp.float32)
    got = qlinear_apply(ql, x)

    plan = KernelContext().resolve_plan(8, d_in, d_out, r, act_group=g)
    assert plan.path == "fused" and plan.bk % g == 0
    want = ops.w4a4_lrc_forward(
        x, ql.qweight, ql.w_scale, ql.u, ql.v, act_spec=ql.act_spec,
        impl="fused")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # and the reference int8 grouped GEMM agrees within f32-order tolerance
    int8_out = qlinear_apply(dataclasses.replace(ql, impl="int8"), x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(int8_out),
                               rtol=2e-3, atol=2e-3)
