import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import model
from repro.models.config import reduced
from repro.data.tokens import SyntheticCorpus
from repro.data.loader import batches, calib_sequences
from repro.serve.engine import Request, ServeEngine
from repro.serve.sampling import sample_token


def test_corpus_deterministic_and_structured():
    c = SyntheticCorpus(1024, seed=3)
    a = c.sequence(5, 256)
    b = c.sequence(5, 256)
    np.testing.assert_array_equal(a, b)
    assert a.min() >= 0 and a.max() < 1024
    # Zipf head concentration: top-32 tokens cover a large mass
    big = c.batch(0, 16, 256).ravel()
    top = np.bincount(big, minlength=1024).max()
    assert top > len(big) / 1024 * 4


def test_batches_replay_from_step():
    cfg = reduced(get_config("smollm-135m"))
    it1 = batches(cfg, 4, 16, seed=9)
    seq = [next(it1) for _ in range(5)]
    it2 = batches(cfg, 4, 16, seed=9, start_step=3)
    s3, b3 = next(it2)
    assert s3 == 3
    np.testing.assert_array_equal(np.asarray(seq[3][1]["tokens"]), np.asarray(b3["tokens"]))


def test_sampling_modes(rng):
    logits = jnp.asarray(rng.standard_normal((3, 50)), jnp.float32)
    g = sample_token(logits, jax.random.PRNGKey(0), temperature=0.0)
    np.testing.assert_array_equal(np.asarray(g), np.asarray(jnp.argmax(logits, -1)))
    t = sample_token(logits, jax.random.PRNGKey(0), temperature=1.0, top_k=5)
    assert t.shape == (3,)


def test_sampling_topk_halfprec_logits_finite(rng):
    """The top-k mask must be dtype-aware: a -1e30 fill overflows float16
    (max ~6.5e4) to -inf, which can NaN through temperature scaling /
    categorical; bf16 shares the mantissa problem at lower severity."""
    for dtype in (jnp.float16, jnp.bfloat16):
        logits = jnp.asarray(rng.standard_normal((4, 64)) * 8, dtype)
        t = sample_token(logits, jax.random.PRNGKey(1), temperature=0.7, top_k=3)
        assert t.shape == (4,)
        assert bool(jnp.all((t >= 0) & (t < 64)))
        # the dtype-aware mask stays finite (the old -1e30 fill is -inf in f16)
        vals, _ = jax.lax.top_k(logits, 3)
        masked = jnp.where(logits < vals[..., -1:], jnp.finfo(dtype).min, logits)
        assert bool(jnp.all(jnp.isfinite(masked.astype(jnp.float32))))
    assert not np.isfinite(np.float16(-1e30))  # what the fix guards against


@pytest.mark.parametrize("family_arch", ["smollm-135m", "mamba2-370m"])
def test_engine_matches_sequential_greedy(family_arch, rng):
    """Engine output == manual greedy decode — batching must not change
    results."""
    cfg = reduced(get_config(family_arch))
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    prompts = [np.asarray(rng.integers(0, cfg.vocab_size, (6,)), np.int32)
               for _ in range(3)]

    eng = ServeEngine(cfg, params, batch_slots=2, max_seq=32)
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=5))
    done = eng.run()
    assert sorted(done) == [0, 1, 2]
    # run() now returns structured terminal records, all FINISHED here
    assert all(done[i].ok and done[i].retries == 0 for i in range(3))

    # manual single-request reference
    for i, p in enumerate(prompts):
        cache = model.init_cache(cfg, 1, 32, dtype=jnp.float32)
        logits, cache = model.prefill(cfg, params, {"tokens": jnp.asarray(p[None])}, cache)
        toks = [int(jnp.argmax(logits[:, -1], -1)[0])]
        for _ in range(4):
            logits, cache = model.decode_step(
                cfg, params, jnp.asarray([[toks[-1]]], jnp.int32), cache
            )
            toks.append(int(jnp.argmax(logits[:, -1], -1)[0]))
        assert done[i].out_tokens == toks, (i, done[i].out_tokens, toks)


def test_calib_sequences_shape():
    cfg = reduced(get_config("smollm-135m"))
    c = calib_sequences(cfg, n_seq=4, seq_len=64)
    assert c.shape == (4, 64)


def test_grad_compression_close_to_exact():
    """int8-compressed psum ≈ exact mean; error feedback keeps bias ~0 over
    steps."""
    import os
    import subprocess
    import sys

    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import auto_mesh
from repro.train.compression import compressed_psum, zero_residual

mesh = auto_mesh((4,), ("data",))
rng = np.random.default_rng(0)
g_local = jnp.asarray(rng.standard_normal((4, 64, 32)), jnp.float32)

def f(g):
    def inner(gl):
        grads = {"w": gl}
        res = zero_residual(grads)
        out, _ = compressed_psum(grads, res, "data")
        return out["w"]
    return jax.shard_map(inner, mesh=mesh, in_specs=P("data"),
                         out_specs=P("data"))(g)

with jax.set_mesh(mesh):
    out = jax.jit(f)(g_local)
exact = jnp.mean(g_local, axis=0, keepdims=True)
err = float(jnp.abs(out[0] - exact[0]).max()) / float(jnp.abs(exact).max())
print("REL", err)
assert err < 0.05, err
print("OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "OK" in out.stdout
