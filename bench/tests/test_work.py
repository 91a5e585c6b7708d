"""Operations and bytes at logical shapes, against hand counts, and the
peak table."""

import pytest

from bench import work
from bench.model import load_spec
from bench.reference import dense

SMOL = load_spec("smollm-135m")
PHI3 = load_spec("phi3-mini-3.8b")

# (m, k, n, r) -> (int8 ops, bf16 ops, bytes), worked out by hand:
#   int8  = 2 m k n
#   float = 2 m k r + 2 m r n
#   bytes = k n / 2 (int4 W) + 4 n (f32 scales) + 2 (k + n) r (bf16 U, V)
#           + 2 m k (bf16 x in) + 2 m n (bf16 y out)
HAND = {
    # smollm decode, 64 slots, mlp/wg
    (64, 576, 1536, 58): (113_246_208, 15_679_488, 963_840),
    # smollm prefill chunk of 512, mlp/wd
    (512, 1536, 576, 58): (905_969_664, 125_435_904, 2_852_352),
    # phi3 decode, 8 slots, attn/wq
    (8, 3072, 3072, 307): (150_994_944, 30_179_328, 8_601_600),
    # phi3 prefill chunk of 64, mlp/wu
    (64, 3072, 8192, 307): (3_221_225_472, 442_630_144, 20_973_568),
}


@pytest.mark.parametrize("shape", list(HAND), ids=lambda s: "x".join(
    map(str, s)))
def test_qlinear_counts(shape):
    w = work.qlinear(*shape)
    assert (w["int8_ops"], w["float_ops"], w["bytes"]) == HAND[shape]


def test_logical_ranks_and_widths():
    assert [SMOL.shape(n) for n in ("attn/wq", "attn/wk", "mlp/wd")] == [
        (576, 576, 58), (576, 192, 19), (1536, 576, 58)]
    assert [PHI3.shape(n) for n in ("attn/wk", "mlp/wu", "mlp/wd")] == [
        (3072, 3072, 307), (3072, 8192, 307), (8192, 3072, 307)]


@pytest.mark.parametrize("spec,attn", [(SMOL, 691_200), (PHI3, 3_932_160)],
                         ids=["smollm", "phi3"])
def test_step_attention_over_the_real_context(spec, attn):
    # a decode row at position 9 and a 4-token chunk from position 0 both
    # attend to 10 keys in all: 4 * heads * head_dim * 10 per layer
    head = 2 * spec.d * spec.vocab
    dec = dense.step_work(spec, [(9, 1)], sampled=1)
    assert dec["float_ops"] - dense.linear_work(spec, 1)["float_ops"] - head \
        == attn
    chunk = dense.step_work(spec, [(0, 4)], sampled=1)
    assert chunk["float_ops"] - dense.linear_work(spec, 4)["float_ops"] - head \
        == attn
    assert dec["int8_ops"] == dense.linear_work(spec, 1)["int8_ops"]


def test_decode_step_bytes_are_weights_kv_and_logits():
    spec = SMOL
    rows = [(99, 1)] * 3
    got = dense.step_work(spec, rows, sampled=3)["bytes"]
    lin = dense.linear_work(spec, 3)["bytes"]
    kv = 2 * 2 * spec.kv_heads * spec.head_dim * (3 * 100 + 3) * spec.layers
    head = 2 * spec.d * spec.vocab + 4 * 3 * spec.vocab + 2 * 3 * spec.d
    norms = 2 * spec.d * (2 * spec.layers + 1)
    assert got == lin + kv + head + norms


def test_peaks_are_keyed_by_device_kind():
    p = work.peaks("TPU v5 lite")
    assert (p["bf16_flops_per_s"], p["int8_ops_per_s"],
            p["hbm_bytes_per_s"]) == (197e12, 393e12, 819e9)
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")


def test_least_time_names_its_bound():
    p = work.peaks("TPU v5 lite")
    decode = work.qlinear(8, 3072, 3072, 307)
    t, bound = work.least_seconds(decode, p)
    assert bound == "memory" and t == pytest.approx(8_601_600 / 819e9)
    big = work.qlinear(4096, 3072, 8192, 307)
    t, bound = work.least_seconds(big, p)
    assert bound == "compute" and t == pytest.approx(
        big["int8_ops"] / 393e12 + big["float_ops"] / 197e12)
