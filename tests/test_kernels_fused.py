"""Single-kernel fused W4A4+LRC forward (kernels/fused_gemm.py) vs. the
two-kernel chain and the unfused three-pass path: bitwise cross-path parity
(the PR acceptance), the VMEM-budget fallback boundary, the execution-plan
table (select_plan / KernelContext.from_json / unknown-regime errors), and
the CI regression gate.  All kernels run in pallas interpret mode."""

import dataclasses
import json

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.common import make_w4a4_problem as _problem
from repro.kernels import ops, ref
from repro.kernels.context import KernelContext
from repro.kernels.fused_gemm import fused_w4a4_lrc_kernel

# (per-test isolation of the process-default KernelContext comes from the
# autouse _kernel_state_guard fixture in conftest.py)




# ---------------------------------------------------------------------------
# single kernel vs. two-kernel chain vs. unfused: BITWISE (acceptance)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,k,n,r", [
    (16, 64, 32, 0),      # decode, block-aligned, rank-0
    (8, 256, 100, 16),    # decode, odd MLP width
    (13, 96, 80, 5),      # decode, nothing is a multiple of anything
    (64, 128, 96, 8),     # mixed
    (300, 128, 100, 0),   # mixed, odd N, rank-0
    (520, 128, 72, 6),    # prefill regime
])
@pytest.mark.parametrize("rotate", [False, True])
def test_fused_bitwise_matches_chain_and_unfused(rng, m, k, n, r, rotate):
    if rotate and k & (k - 1):
        pytest.skip("online rotation needs power-of-two K")
    spec, x, wp, s, u, v = _problem(rng, m, k, n, r)
    outs = {
        impl: np.asarray(ops.w4a4_lrc_forward(x, wp, s, u, v, spec,
                                              rotate=rotate, impl=impl))
        for impl in ("fused", "chained", "unfused", "auto")
    }
    np.testing.assert_array_equal(outs["fused"], outs["chained"])
    np.testing.assert_array_equal(outs["fused"], outs["unfused"])
    np.testing.assert_array_equal(outs["fused"], outs["auto"])
    want = np.asarray(ref.w4a4_lrc_forward_ref(
        x, wp, s, u, v, bits=4, clip_ratio=0.9, rotate=rotate))
    assert outs["fused"].shape == (m, n)
    np.testing.assert_allclose(outs["fused"], want, rtol=1e-4, atol=1e-4)


def _plane_cases():
    """(act_group, variant, bk, k, rotate): per-token and grouped, resident
    and streamed, bk 256 and 512, K = k_pad (512) and K < k_pad (576);
    rotation (power-of-two K, resident) at K = 512; an odd group, whose
    rows straddle the two planes, at the one bk it snaps to."""
    cases = [(g, var, bk, k, False)
             for g in (None, 64) for var in ("resident", "streamed")
             for bk in (256, 512) for k in (512, 576)]
    cases += [(g, "resident", bk, 512, True)
              for g in (None, 64) for bk in (256, 512)]
    return cases + [(3, "resident", 384, 576, False)]


@pytest.mark.parametrize("group,variant,bk,k,rotate", _plane_cases())
def test_fused_nibble_planes_bitwise_equal_unfused(rng, group, variant, bk,
                                                   k, rotate):
    """The fused kernel's GEMM step contracts the packed chunk's nibble
    planes against the even/odd-split xq chunk: exact int32 sums, so its
    output equals the unfused path (interleaved unpack) bit for bit."""
    from repro.kernels import fused_gemm

    m, n, r, bm, bn, br = 16, 128, 8, 16, 128, 128
    spec, x, wp, s, u, v = _problem(rng, m, k, n, r, act_group=group)
    want = np.asarray(ops.w4a4_lrc_forward(
        x, wp, s, u, v, spec, rotate=rotate, blocks=(bm, bn, bk, br),
        impl="unfused"))
    k_pad = k + (-k) % bk
    wpp = jnp.pad(wp, ((0, (k_pad - k) // 2), (0, 0)))
    fused_w4a4_lrc_kernel.clear_cache()
    before = dict(fused_gemm.traces)
    got = np.asarray(fused_w4a4_lrc_kernel(
        x, v, wpp, s.reshape(1, -1), u, bits=4, clip_ratio=0.9,
        rotate=rotate, bm=bm, bn=bn, bk=bk, br=br, variant=variant,
        act_group=group, interpret=True))
    np.testing.assert_array_equal(got, want)
    assert {key: fused_gemm.traces[key] - before[key] for key in before} \
        == {"planes": 1}


def test_fused_kernel_direct_block_aligned(rng):
    """The raw kernel (no wrapper padding) against the pure-jnp oracle."""
    m, k, n, r = 32, 128, 64, 8
    spec, x, wp, s, u, v = _problem(rng, m, k, n, r)
    out = fused_w4a4_lrc_kernel(
        x, v, wp, s.reshape(1, -1), u,
        bits=4, clip_ratio=0.9, rotate=True, bm=16, bn=32, bk=64,
        interpret=True,
    )
    want = ref.w4a4_lrc_forward_ref(x, wp, s, u, v, bits=4, clip_ratio=0.9,
                                    rotate=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_fused_block_shape_invariance_rank0(rng):
    """Integer accumulation is exact under any K split, so at rank 0 every
    tiling of the fused kernel produces the same bits as the chain."""
    m, k, n = 24, 128, 64
    spec, x, wp, s, u, v = _problem(rng, m, k, n, 0)
    want = np.asarray(ops.w4a4_lrc_forward(x, wp, s, u, v, spec,
                                           impl="chained"))
    for blocks in [(8, 16, 32), (8, 64, 64), (16, 32, 128)]:
        got = np.asarray(ops.w4a4_lrc_forward(x, wp, s, u, v, spec,
                                              blocks=blocks, impl="fused"))
        np.testing.assert_array_equal(got, want)


def test_fused_block_shape_parity_lowrank(rng):
    """With a low-rank term the (bk, br)-chunked xv accumulation is part of
    the canonical math, so bits are identical ACROSS PATHS at one tiling
    (every tiling still agrees within f32 reassociation noise)."""
    m, k, n, r = 24, 128, 64, 8
    spec, x, wp, s, u, v = _problem(rng, m, k, n, r)
    ref_out = None
    for blocks in [(8, 16, 32, 8), (8, 64, 64, 8), (16, 32, 128, 8)]:
        outs = [np.asarray(ops.w4a4_lrc_forward(x, wp, s, u, v, spec,
                                                blocks=blocks, impl=impl))
                for impl in ("fused", "chained", "unfused")]
        np.testing.assert_array_equal(outs[0], outs[1])
        np.testing.assert_array_equal(outs[0], outs[2])
        if ref_out is None:
            ref_out = outs[0]
        else:
            np.testing.assert_allclose(outs[0], ref_out,
                                       rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# the _PROLOGUE_V_BYTES_MAX fallback boundary (satellite acceptance)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("r", [
    1024,  # k·r·4 = 8 MB exactly: ≤ budget, fused/chained stay eligible
    1032,  # just past 8 MB: auto demotes all the way to unfused
])
def test_v_bytes_boundary_bitwise_identical(rng, r):
    """Rank/K combos just under and over the 8 MB V budget produce bitwise
    identical outputs on the fused, chained and unfused paths — crossing the
    auto-dispatch boundary can never change serving results."""
    m, k, n = 8, 2048, 64
    v_bytes = k * r * 4
    assert (v_bytes <= ops._PROLOGUE_V_BYTES_MAX) == (r == 1024)
    spec, x, wp, s, u, v = _problem(rng, m, k, n, r)
    outs = {
        impl: np.asarray(ops.w4a4_lrc_forward(x, wp, s, u, v, spec,
                                              rotate=True, impl=impl))
        for impl in ("fused", "chained", "unfused", "auto")
    }
    np.testing.assert_array_equal(outs["fused"], outs["chained"])
    np.testing.assert_array_equal(outs["fused"], outs["unfused"])
    np.testing.assert_array_equal(outs["fused"], outs["auto"])


def test_fused_vmem_gate_demotes_to_chain(rng):
    """With the fused working-set budget forced to zero (via an explicit
    context — no global is touched), auto dispatch takes the two-kernel
    chain — and the bits cannot change."""
    spec, x, wp, s, u, v = _problem(rng, 16, 128, 64, 8)
    want = np.asarray(ops.w4a4_lrc_forward(x, wp, s, u, v, spec))
    tight = KernelContext().with_vmem_budgets(fused=0)
    assert tight.resolve_plan(16, 128, 64, 8).path == "chained"
    got = np.asarray(ops.w4a4_lrc_forward(x, wp, s, u, v, spec, ctx=tight))
    np.testing.assert_array_equal(got, want)


def test_unknown_impl_raises(rng):
    spec, x, wp, s, u, v = _problem(rng, 8, 64, 32, 0)
    with pytest.raises(ValueError, match="unknown impl"):
        ops.w4a4_lrc_forward(x, wp, s, u, v, spec, impl="warp")


# ---------------------------------------------------------------------------
# execution-plan table: regimes, unknown-regime errors, measured overlays
# ---------------------------------------------------------------------------


def test_select_plan_paths():
    path, bm, *_ = ops.select_plan(16, 4096, 11008, 128)    # decode
    assert path == "fused" and bm <= 16
    path2, *_ = ops.select_plan(256, 4096, 11008, 128)      # mixed
    assert path2 == "fused"
    # prefill flipped to the single-kernel path with the K-split grid
    path3, *_ = ops.select_plan(2048, 4096, 11008, 128)
    assert path3 == "fused"


def test_select_blocks_unknown_regime_raises():
    """select_blocks/select_plan no longer ignore unknown regime strings."""
    with pytest.raises(ValueError, match="unknown regime 'decoed'"):
        ops.select_blocks(16, 4096, 11008, 128, regime="decoed")
    with pytest.raises(ValueError, match="unknown regime"):
        ops.select_plan(16, 4096, 11008, regime="prefil")
    # explicit valid override still works
    assert ops.select_blocks(2048, 4096, 11008, 0, regime="decode") == \
        ops.select_blocks(16, 4096, 11008, 0)


def test_block_table_from_json_roundtrip(tmp_path):
    # no "br": pre-K-split tables stay loadable (br falls back to default)
    table = {"decode": {"path": "chained", "bm": 8, "bn": 128, "bk": 128,
                        "score_us": 1.0}}
    p = tmp_path / "block_table.json"
    p.write_text(json.dumps(table))
    ctx = KernelContext.from_json(p)
    plan = ctx.select_plan(16, 4096, 11008, 128)
    assert (plan.path, plan.bm, plan.bn, plan.bk) == ("chained", 8, 128, 128)
    assert plan.br == 128  # default 512 clamped to the rank's pow2
    # unlisted regimes keep the analytic defaults
    assert ctx.select_plan(256, 4096, 11008, 128).path == "fused"
    # the context is a value: the process default never saw the table
    assert ops.select_plan(16, 4096, 11008, 128).path == "fused"


@pytest.mark.parametrize("table,msg", [
    ({"decoed": {"path": "fused", "bm": 8, "bn": 128, "bk": 128}},
     "unknown regime"),
    ({"decode": {"path": "warp", "bm": 8, "bn": 128, "bk": 128}},
     "unknown kernel path"),
    ({"decode": {"path": "fused", "bm": 8}}, "missing keys"),
])
def test_block_table_rejects_malformed(tmp_path, table, msg):
    p = tmp_path / "bad.json"
    p.write_text(json.dumps(table))
    with pytest.raises(ValueError, match=msg):
        KernelContext.from_json(p)
    # a rejected table builds nothing — the process default is untouched
    assert ops.select_plan(16, 4096, 11008, 128).path == "fused"


def test_autotune_sweep_analytic(tmp_path):
    """The sweep harness produces a loadable table whose decode winner is
    the single-kernel path (it strictly dominates the chain on bytes)."""
    from benchmarks.autotune_blocks import autotune_sweep

    winners = autotune_sweep(measure=False, smoke=True)
    assert set(winners) == {"decode", "mixed", "prefill"}
    assert winners["decode"]["path"] == "fused"
    p = tmp_path / "table.json"
    p.write_text(json.dumps(winners))
    KernelContext.from_json(p)


# ---------------------------------------------------------------------------
# QLinear impl="fused" + engine retag
# ---------------------------------------------------------------------------


def test_qlinear_fused_impl_matches_int8_odd_shapes(rng):
    from repro.quant.qlinear import make_qlinear, qlinear_apply

    d_in, d_out, r = 96, 80, 8
    q = jnp.asarray(rng.integers(-8, 8, (d_out, d_in)), jnp.int8)
    s = jnp.asarray(rng.uniform(0.01, 0.1, (d_out, 1)), jnp.float32)
    u = jnp.asarray(rng.standard_normal((d_out, r)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((d_in, r)), jnp.float32)
    ql = make_qlinear(q, s, u, v, impl="int8", lr_dtype=jnp.float32)
    x = jnp.asarray(rng.standard_normal((13, d_in)), jnp.float32)
    a = qlinear_apply(ql, x)
    b = qlinear_apply(dataclasses.replace(ql, impl="fused"), x)
    c = qlinear_apply(dataclasses.replace(ql, impl="pallas"), x)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-3, atol=2e-3)
    # pallas (auto plan) and fused pin the same kernels at this shape
    np.testing.assert_array_equal(np.asarray(b), np.asarray(c))


def test_retag_to_fused(rng):
    from repro.quant.qlinear import make_qlinear, retag_qlinear_impl

    q = jnp.asarray(rng.integers(-8, 8, (16, 32)), jnp.int8)
    s = jnp.ones((16, 1), jnp.float32)
    tree = {"a": make_qlinear(q, s, impl="sim")}
    assert retag_qlinear_impl(tree, "fused")["a"].impl == "fused"
    with pytest.raises(ValueError, match="unknown impl"):
        retag_qlinear_impl(tree, "warp")
    with pytest.raises(ValueError, match="unknown impl"):
        retag_qlinear_impl(tree, "pallsa")  # typo must not tag silently


def test_qlinear_fused_groupwise_runs_kernels(rng):
    """Group-wise calibrated layers no longer demote to the jnp int8 GEMM:
    impl="fused" runs the pallas path with the (M, K/g) scale plane and
    matches the int8 reference semantics (grouped acceptance lives in
    tests/test_kernels_groups.py)."""
    from repro.quant.qlinear import make_qlinear, qlinear_apply

    d_in, d_out, g = 128, 64, 32
    q = jnp.asarray(rng.integers(-8, 8, (d_out, d_in)), jnp.int8)
    s = jnp.asarray(rng.uniform(0.01, 0.1, (d_out, 1)), jnp.float32)
    ql = make_qlinear(q, s, act_group=g, impl="int8")
    x = jnp.asarray(rng.standard_normal((8, d_in)), jnp.float32)
    a = qlinear_apply(ql, x)
    b = qlinear_apply(dataclasses.replace(ql, impl="fused"), x)
    c = qlinear_apply(dataclasses.replace(ql, impl="pallas"), x)
    # rank-0 int math is exact on both paths: same bits as the int8 GEMM
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(b), np.asarray(c))


# ---------------------------------------------------------------------------
# roofline byte model + CI regression gate
# ---------------------------------------------------------------------------


def test_byte_model_fused_strictly_below_chained_decode():
    """Acceptance: the single-kernel path eliminates the M×K xq write+read —
    activation bytes strictly below the PR 1 chained path at decode shapes,
    and independent of rank (everything but x lives in VMEM)."""
    from repro.launch.roofline import prologue_activation_bytes

    for k in (4096, 5120, 8192):
        for r in (0, 128, 256, 512, 1024):
            ch = prologue_activation_bytes(16, k, r, rotate=True,
                                           path="chained")
            fu = prologue_activation_bytes(16, k, r, rotate=True,
                                           path="fused")
            assert fu < ch, (k, r)
            assert fu == 16 * k * 2  # exactly one read of x, nothing else
            assert ch - fu == 2 * (16 * k + 4 * 16 + 4 * 16 * r)


def test_byte_model_unknown_path_raises():
    from repro.launch.roofline import prologue_activation_bytes

    with pytest.raises(ValueError, match="unknown path"):
        prologue_activation_bytes(16, 4096, 128, path="semi-fused")


def test_check_regression_gate(tmp_path):
    """The CI gate passes on a fresh baseline, fails on a regressed one and
    on a fused-not-below-chained violation."""
    from benchmarks.check_regression import check
    from benchmarks.latency_kernels import HEADER, analytic_rows

    rows = analytic_rows(ms=[16], sizes=[(4096, 11008)], ranks=[0, 128])
    good = tmp_path / "good.json"
    good.write_text(json.dumps(dict(header=HEADER, rows=rows)))
    assert check(good, 0.05) == []

    # shrink the baseline's fused byte column by 20% → current code "regressed"
    idx = HEADER.index("act_prologue_kb_fused")
    bad_rows = [list(r) for r in rows]
    for r in bad_rows:
        r[idx] = r[idx] * 0.8
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(dict(header=HEADER, rows=bad_rows)))
    failures = check(bad, 0.05)
    assert failures and all("act_prologue_kb_fused" in f for f in failures)

    # stale baseline (no matching shapes) must fail loudly, not pass silently
    stale = tmp_path / "stale.json"
    stale.write_text(json.dumps(dict(
        header=HEADER,
        rows=[["M999_1x1", 0] + [1.0] * (len(HEADER) - 2)])))
    assert any("stale" in f for f in check(stale, 0.05))


def test_committed_baseline_passes_gate():
    """The checked-in results/latency_kernels.json must be in sync with the
    current byte model — the same invariant the CI job enforces."""
    from pathlib import Path

    from benchmarks.check_regression import check

    baseline = Path(__file__).resolve().parents[1] / "results" / \
        "latency_kernels.json"
    assert check(baseline, 0.05) == []
