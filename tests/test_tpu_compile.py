"""Compile-only checks for a described TPU v5e: the W4A4+LRC kernels that
``ops.w4a4_lrc_forward`` resolves to at smollm-135m's and phi3-mini's
published layer widths, and one jitted ``paged_step`` of the quantized
model at full width.

Nothing runs here: the TPU compiler (installed with jaxlib) compiles for a
``v5e:2x2`` topology that is described, not attached, and refuses what the
chip's compiler would refuse (lane-misaligned blocks, vector ops Mosaic
cannot legalize, VMEM overuse).  The topology is described in a fixture, so
only the worker that runs this file loads the TPU library.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.quantizers import QuantSpec
from repro.kernels import ops
from repro.kernels.context import KernelContext
from repro.models import model as model_lib
from repro.quant.policy import QuantPolicy, path_str
from repro.quant.qlinear import QLinear

CFG = get_config("smollm-135m")
# (K, N, R) of every quantized layer at rank_frac=0.10: wq/wo, wk/wv, wg/wu, wd
SHAPES = [(576, 576, 58), (576, 192, 19), (576, 1536, 58), (1536, 576, 58)]
# phi3-mini at rank 307: attention and the MLP's up and down projections
PHI3_SHAPES = [(3072, 3072, 307), (3072, 8192, 307), (8192, 3072, 307)]
COMPILED = KernelContext(interpret=False)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_forward(one_chip, m, k, n, r, impl, group=None):
    spec = QuantSpec(bits=4, clip_ratio=0.9, group_size=group)

    def fwd(x, wp, sw, u, v):
        return ops.w4a4_lrc_forward(x, wp, sw, u, v, spec, impl=impl,
                                    ctx=COMPILED)

    args = (_sds((m, k), jnp.float32, one_chip),
            _sds((k // 2, n), jnp.uint8, one_chip),
            _sds((n,), jnp.float32, one_chip),
            _sds((n, r), jnp.bfloat16, one_chip),
            _sds((k, r), jnp.bfloat16, one_chip))
    # the served program is 32-bit, whatever an earlier test left x64 at
    with jax.enable_x64(False):
        return jax.jit(fwd).lower(*args).compile()


@pytest.mark.parametrize("m", [8, 700], ids=["decode", "prefill"])
@pytest.mark.parametrize("k,n,r", SHAPES)
def test_fused_per_token_compiles(one_chip, m, k, n, r):
    assert COMPILED.resolve_plan(m, k, n, r).path == "fused"
    compiled = _compile_forward(one_chip, m, k, n, r, "auto")
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("m", [8, 64], ids=["decode", "chunk"])
@pytest.mark.parametrize("k,n,r", PHI3_SHAPES)
def test_fused_phi3_widths_compiles(one_chip, m, k, n, r):
    """The plans phi3-mini's decode steps and 64-token chunks resolve to,
    with the GEMM step on the nibble planes: a lowering Mosaic refuses
    (a lane-strided load, say) shows only here, not in interpret mode."""
    from repro.kernels import fused_gemm

    assert COMPILED.resolve_plan(m, k, n, r).path == "fused"
    fused_gemm.fused_w4a4_lrc_kernel.clear_cache()
    before = fused_gemm.traces["planes"]
    compiled = _compile_forward(one_chip, m, k, n, r, "auto")
    assert "tpu_custom_call" in compiled.as_text()
    assert fused_gemm.traces["planes"] == before + 1


@pytest.mark.parametrize("m", [8, 200], ids=["decode", "mixed"])
@pytest.mark.parametrize("k,n,r", SHAPES)
def test_fused_group16_compiles(one_chip, m, k, n, r):
    assert COMPILED.resolve_plan(m, k, n, r, act_group=16).path == "fused"
    compiled = _compile_forward(one_chip, m, k, n, r, "auto", group=16)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("group", [None, 16], ids=["per_token", "g16"])
@pytest.mark.parametrize("k,n,r", SHAPES)
def test_fused_compiles_under_highest_precision(one_chip, k, n, r, group):
    """A caller's matmul precision (the `sim` reference runs at "highest")
    must not reach the kernels' int8 dots: Mosaic refuses an f32
    contraction of integer operands."""
    with jax.default_matmul_precision("highest"):
        compiled = _compile_forward(one_chip, 8, k, n, r, "auto", group=group)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("group", [None, 16], ids=["per_token", "g16"])
@pytest.mark.parametrize("k,n,r", SHAPES)
def test_chained_fallback_compiles(one_chip, k, n, r, group):
    compiled = _compile_forward(one_chip, 8, k, n, r, "chained", group=group)
    # prologue and GEMM: two kernels
    assert compiled.as_text().count("tpu_custom_call") >= 2


def _abstract_quantized_params(cfg, sharding):
    """Shapes of the calibrated model as the engine serves it on a chip:
    every policy-selected weight a QLinear retagged to the compiled pallas
    path, everything else as ``init_params`` makes it."""
    policy = QuantPolicy(rank_frac=0.10, clip_ratio=0.9)
    tree = jax.eval_shape(
        lambda: model_lib.init_params(cfg, jax.random.PRNGKey(0)))

    def leaf(path, a):
        if not policy.should_quantize(path_str(path), a.shape):
            return _sds(a.shape, a.dtype, sharding)
        *lead, d_in, d_out = a.shape
        r = policy.rank(d_in, d_out)
        return QLinear(
            qweight=_sds((*lead, d_in // 2, d_out), jnp.uint8, sharding),
            w_scale=_sds((*lead, d_out), jnp.float32, sharding),
            u=_sds((*lead, d_out, r), jnp.bfloat16, sharding),
            v=_sds((*lead, d_in, r), jnp.bfloat16, sharding),
            act_group=policy.act_group, clip_ratio=policy.clip_ratio,
            impl="pallas", ctx=COMPILED, name=path_str(path))

    return jax.tree_util.tree_map_with_path(leaf, tree)


@pytest.mark.parametrize("b,s", [(8, 1), (1, 700)], ids=["decode", "prefill"])
def test_paged_step_compiles_without_f64(one_chip, b, s):
    """The engine's jitted step at published widths (depth cut to 2 layers
    to keep the test quick) carries the kernels and no 64-bit float.
    Calibration, which solves in float64, hands the process back with x64
    off, so the step is traced 32-bit as the engine traces it."""
    cfg = dataclasses.replace(CFG, n_layers=2)
    page, pages_per_slot = 16, 64
    params = _abstract_quantized_params(cfg, one_chip)
    pool = jax.eval_shape(lambda: model_lib.init_paged_cache(
        cfg, 8 * pages_per_slot + 1, page, dtype=jnp.float32))
    pool = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip), pool)
    args = (params,
            _sds((b, s), jnp.int32, one_chip),
            _sds((b, s), jnp.int32, one_chip),
            _sds((b, s), jnp.bool_, one_chip),
            pool,
            _sds((b, pages_per_slot), jnp.int32, one_chip),
            _sds((b,), jnp.int32, one_chip))

    def step(params, tokens, positions, valid, cache, block_table, srow):
        return model_lib.paged_step(cfg, params, tokens, positions, valid,
                                    cache, block_table, srow)

    with jax.enable_x64(False):
        text = jax.jit(step).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert "f64[" not in text  # no 64-bit float value of any shape


@pytest.mark.parametrize("b,vocab", [(64, 49152), (8, 32064), (1, 49152)],
                         ids=["smollm-decode", "phi3-decode", "one-row"])
def test_sample_rows_compiles(one_chip, b, vocab):
    """The engine's one sampling program per step, over the step's
    (B, 1, V) logits at the benchmark cells' decode batches and
    vocabularies, and at the one row of a final chunk."""
    from repro.serve.sampling import sample_rows_packed

    args = (_sds((b, 1, vocab), jnp.float32, one_chip),
            _sds((2,), jnp.uint32, one_chip),
            _sds((b, 3), jnp.uint32, one_chip))
    sample_rows_packed.lower(*args).compile()
