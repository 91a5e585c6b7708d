"""Unit tests for the dry-run substrate: HLO collective parsing, roofline
terms, sharding rules (incl. the QLinear-suffix regression of §Perf exp-4),
config registry, and shape applicability."""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import ARCH_IDS, get_config
from repro.configs.shapes import SHAPES, applicable, cells
from repro.launch.roofline import (
    _shape_bytes,
    collective_bytes,
    model_flops,
    roofline_from_costs,
)


HLO_SAMPLE = """
HloModule test
ENTRY main {
  %p0 = bf16[128,256]{1,0} parameter(0)
  %p1 = bf16[128,256]{1,0} parameter(1)
  %ar = bf16[128,256]{1,0} all-reduce(%p0), replica_groups={}
  %ag = bf16[128,512]{1,0} all-gather(%p1), dimensions={1}
  %cp = f32[64]{0} collective-permute(%p0), source_target_pairs={{0,1}}
  ROOT %t = (bf16[128,256]{1,0}) tuple(%ar)
}
"""


def test_shape_bytes():
    assert _shape_bytes("bf16[128,256]{1,0}") == 128 * 256 * 2
    assert _shape_bytes("f32[10]") == 40
    assert _shape_bytes("(bf16[4,4], f32[2])") == 32 + 8
    assert _shape_bytes("u8[8,8]{1,0}") == 64
    assert _shape_bytes("pred[]") == 1


def test_collective_parsing():
    coll = collective_bytes(HLO_SAMPLE)
    assert coll["all-reduce"] == 128 * 256 * 2  # operand p0
    assert coll["all-gather"] == 128 * 256 * 2  # operand p1 (not the result)
    assert coll["collective-permute"] == 128 * 256 * 2
    counts = coll["_counts"]
    assert counts["all-reduce"] == 1 and counts["all-gather"] == 1


def test_roofline_terms_and_bottleneck():
    costs = dict(flops=197e12, bytes=819e9 * 2, coll={"all-reduce": 50e9},
                 coll_counts={"all-reduce": 1})
    rf = roofline_from_costs(costs, model_flops_total=197e12 * 256, n_chips=256)
    assert abs(rf["compute_term_s"] - 1.0) < 1e-9
    assert abs(rf["memory_term_s"] - 2.0) < 1e-9
    assert abs(rf["collective_term_s"] - 1.0) < 1e-9
    assert rf["bottleneck"] == "memory"
    assert abs(rf["useful_flops_ratio"] - 1.0) < 1e-9
    assert abs(rf["roofline_fraction"] - 0.5) < 1e-9


def test_model_flops_regimes():
    cfg = get_config("smollm-135m")
    tr = model_flops(cfg, SHAPES["train_4k"])
    pf = model_flops(cfg, SHAPES["prefill_32k"])
    de = model_flops(cfg, SHAPES["decode_32k"])
    assert tr > pf > de > 0
    # train = 6ND vs prefill 2ND with equal token counts
    assert abs(tr / (6 / 2) / (SHAPES["train_4k"].global_batch * SHAPES["train_4k"].seq_len)
               - pf / (SHAPES["prefill_32k"].global_batch * SHAPES["prefill_32k"].seq_len)) < 1e-3 * pf


def test_shape_applicability():
    assert not applicable(get_config("gemma-7b"), "long_500k")
    assert applicable(get_config("mamba2-370m"), "long_500k")
    assert applicable(get_config("zamba2-7b"), "long_500k")
    assert len(cells(get_config("gemma-7b"))) == 3
    assert len(cells(get_config("zamba2-7b"))) == 4
    # 40 assigned cells - 8 long_500k skips = 32 live
    assert sum(len(cells(get_config(a))) for a in ARCH_IDS) == 32


def test_config_registry_complete():
    assert len(ARCH_IDS) == 10
    for a in ARCH_IDS:
        cfg = get_config(a)
        assert cfg.name == a
        assert cfg.vocab_size > 0 and cfg.n_layers > 0


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------


def _mesh22():
    # AbstractMesh: rule logic only needs axis names/sizes (1-device CPU test)
    return jax.sharding.AbstractMesh((2, 2), ("data", "model"),
                                     axis_types=(jax.sharding.AxisType.Auto,) * 2)


def test_param_rules_shard_attention_and_mlp():
    from repro.distributed.sharding import param_pspecs

    mesh = _mesh22()
    tree = {
        "layers": {
            "attn": {"wq": jax.ShapeDtypeStruct((4, 64, 32), jnp.float32),
                     "wo": jax.ShapeDtypeStruct((4, 32, 64), jnp.float32)},
            "mlp": {"wg": jax.ShapeDtypeStruct((4, 64, 128), jnp.float32),
                    "wd": jax.ShapeDtypeStruct((4, 128, 64), jnp.float32)},
        },
        "embed": jax.ShapeDtypeStruct((1000, 64), jnp.float32),
    }
    specs = param_pspecs(tree, mesh, False)
    assert specs["layers"]["attn"]["wq"] == jax.sharding.PartitionSpec(None, None, "model")
    assert specs["layers"]["attn"]["wo"] == jax.sharding.PartitionSpec(None, "model", None)
    assert specs["layers"]["mlp"]["wd"] == jax.sharding.PartitionSpec(None, "model", None)
    assert specs["embed"] == jax.sharding.PartitionSpec("model", None)


def test_param_rules_match_qlinear_fields():
    """Regression for §Perf exp-4: QLinear suffixes must inherit the base
    weight's plan (the inner-$ anchor bug replicated every quantized
    weight)."""
    from repro.distributed.sharding import param_pspecs
    from repro.quant.qlinear import QLinear

    mesh = _mesh22()
    # real layout: (L layers stacked, E experts, d_in//2, d_out)
    ql = QLinear(
        qweight=jax.ShapeDtypeStruct((2, 8, 32, 64), jnp.uint8),
        w_scale=jax.ShapeDtypeStruct((2, 8, 64), jnp.float32),
        u=jax.ShapeDtypeStruct((2, 8, 64, 4), jnp.bfloat16),
        v=jax.ShapeDtypeStruct((2, 8, 64, 4), jnp.bfloat16),
    )
    tree = {"moe_layers": {"moe": {"experts": {"wg": ql}}}}
    specs = param_pspecs(tree, mesh, False)
    got = specs["moe_layers"]["moe"]["experts"]["wg"]
    P = jax.sharding.PartitionSpec
    assert got.qweight == P(None, "model", None, None)  # stacked + EP
    assert got.w_scale == P(None, "model", None)
    assert got.u == P(None, "model", None, None)
    assert got.v == P(None, "model", None, None)


def test_divisibility_fallback():
    from repro.distributed.sharding import ShardingFallback, param_pspecs

    mesh = _mesh22()
    # 3 kv heads * 17 = 51-wide projection: 51 % 2 != 0 -> replicate
    tree = {"layers": {"attn": {"wk": jax.ShapeDtypeStruct((2, 64, 51), jnp.float32)}}}
    with pytest.warns(ShardingFallback) as rec:
        specs = param_pspecs(tree, mesh, False)
    assert specs["layers"]["attn"]["wk"] == jax.sharding.PartitionSpec(None, None, None)
    # the warning is STRUCTURED: tooling (summarize --sharding) reads fields
    w = next(m.message for m in rec if isinstance(m.message, ShardingFallback))
    assert w.path == "layers/attn/wk"
    assert (w.dim_index, w.dim) == (2, 51)
    assert (w.axis, w.axis_size) == ("model", 2)


def test_describe_sharding_captures_fallbacks():
    from repro.distributed.sharding import describe_sharding

    mesh = _mesh22()
    tree = {"layers": {"attn": {"wk": jax.ShapeDtypeStruct((2, 64, 51), jnp.float32),
                                "wq": jax.ShapeDtypeStruct((2, 64, 64), jnp.float32)}}}
    # capture, don't warn: describe_sharding returns the plan as data
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        rows = describe_sharding(tree, mesh)
    by_path = {r["path"]: r for r in rows}
    assert by_path["layers/attn/wq"]["fallbacks"] == []
    fb = by_path["layers/attn/wk"]["fallbacks"]
    assert len(fb) == 1 and fb[0].dim == 51 and fb[0].axis == "model"


# ---------------------------------------------------------------------------
# TP comms-bytes model (the comms_kb_ benchmark columns)
# ---------------------------------------------------------------------------


def test_tp_comms_bytes_model():
    from repro.launch.roofline import (
        ep_combine_bytes_per_token,
        tp_psum_bytes_per_token,
    )

    # no mesh -> no collective -> zero payload
    assert tp_psum_bytes_per_token(1024, 1) == 0.0
    assert ep_combine_bytes_per_token(1024, 1) == 0.0
    # ring all-reduce: each element crosses the wire 2*(tp-1)/tp times, f32
    assert tp_psum_bytes_per_token(1024, 8) == 2 * 7 / 8 * 1024 * 4
    # the EP combine psum has the same shape as a row-parallel psum of d_model
    assert ep_combine_bytes_per_token(512, 4) == tp_psum_bytes_per_token(512, 4)
    # payload grows monotonically with tp (asymptote 2*width*bytes)
    assert (tp_psum_bytes_per_token(256, 2) < tp_psum_bytes_per_token(256, 4)
            < tp_psum_bytes_per_token(256, 8) < 2 * 256 * 4)


@settings(max_examples=20, deadline=None)
@given(b=st.sampled_from([1, 2, 4, 32, 128, 256]), seq=st.booleans())
def test_batch_pspec_never_invalid(b, seq):
    from repro.distributed.sharding import batch_pspec

    mesh = _mesh22()
    spec = batch_pspec(mesh, False, b, shard_seq=seq)
    # divisibility: if batch dim sharded, it must divide the dp size
    if spec[0] is not None:
        size = mesh.shape["data"]
        assert b % size == 0
