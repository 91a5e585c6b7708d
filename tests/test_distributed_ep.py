"""EP (expert-parallel shard_map) vs dense-dispatch equivalence on a forced
8-device host mesh.  Runs in a subprocess so the 1-device tests elsewhere
keep their platform config."""

import os
import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding

from repro.configs import get_config
from repro.launch.mesh import auto_mesh
from repro.models.config import reduced
from repro.models import moe as moe_lib
import dataclasses

cfg = reduced(get_config("deepseek-v2-236b"), n_experts=8, moe_top_k=2,
              capacity_factor=8.0)  # high capacity => no drops => exact match
key = jax.random.PRNGKey(0)
p = moe_lib.init_moe_params(cfg, key, jnp.float32)
rng = np.random.default_rng(0)
x = jnp.asarray(rng.standard_normal((2, 16, cfg.d_model)), jnp.float32)

mesh = auto_mesh((2, 4), ("data", "model"))
with jax.set_mesh(mesh):
    dense = jax.jit(lambda p, x: moe_lib.moe_block(cfg, p, x, impl="dense"))(p, x)
    ep = jax.jit(lambda p, x: moe_lib.moe_block(cfg, p, x, impl="ep"))(p, x)
err = float(jnp.abs(dense - ep).max())
rel = err / float(jnp.abs(dense).max())
print("ERR", rel)
assert rel < 2e-5, rel

# with a tight capacity factor, EP drops tokens but stays finite
cfg2 = dataclasses.replace(cfg, capacity_factor=0.5)
with jax.set_mesh(mesh):
    ep2 = jax.jit(lambda p, x: moe_lib.moe_block(cfg2, p, x, impl="ep"))(p, x)
assert bool(jnp.all(jnp.isfinite(ep2)))
print("OK")
"""


def test_ep_matches_dense():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
    env.pop("JAX_PLATFORMS", None)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], env=env, capture_output=True, text=True,
        timeout=600,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "OK" in out.stdout


# ---- capacity semantics, single device (no subprocess needed) -----------

def test_capacity_rounds_up_to_eight():
    from repro.distributed.ep import _capacity

    assert _capacity(16, 2, 8, 1.25) == 8      # 5 -> rounds up to 8
    assert _capacity(100, 2, 8, 1.0) == 32     # 25 -> next multiple of 8
    assert _capacity(64, 2, 8, 1.0) == 16      # exact multiple stays put
    assert _capacity(1, 1, 64, 1.0) == 8       # floor: never below 8
    assert _capacity(8, 2, 0, 1.0) == 16       # max(1, e) guards div-by-zero


def _ep_problem(n_experts, capacity_factor, t=16, d=8, h=16, k=2, seed=0):
    """Raw-weight (non-QLinear) experts_ep problem on a 1-device mesh."""
    import types

    import numpy as np

    cfg = types.SimpleNamespace(n_experts=n_experts,
                                capacity_factor=capacity_factor)
    rng = np.random.default_rng(seed)
    p = {"experts": {
        "wg": rng.standard_normal((n_experts, d, h)).astype(np.float32),
        "wu": rng.standard_normal((n_experts, d, h)).astype(np.float32),
        "wd": rng.standard_normal((n_experts, h, d)).astype(np.float32),
    }}
    x = rng.standard_normal((t, d)).astype(np.float32)
    logits = rng.standard_normal((t, n_experts)).astype(np.float32)
    weights = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    top_idx = np.argsort(-weights, axis=-1)[:, :k].astype(np.int32)
    return cfg, p, x, weights, top_idx


def _run_ep(cfg, p, x, weights, top_idx, with_stats):
    import jax
    import jax.numpy as jnp

    from repro.distributed.ep import experts_ep
    from repro.launch.mesh import auto_mesh

    mesh = auto_mesh((1,), ("model",))
    with jax.set_mesh(mesh):
        return experts_ep(cfg, {"experts": {k_: jnp.asarray(v) for k_, v in
                                            p["experts"].items()}},
                          jnp.asarray(x), jnp.asarray(weights),
                          jnp.asarray(top_idx), with_stats=with_stats)


def test_ep_overflow_drop_deterministic():
    """Tight capacity: drops happen, are deterministic call-to-call, and
    the drop counter matches the numpy capacity-overflow reference."""
    import numpy as np

    from repro.distributed.ep import _capacity

    cfg, p, x, weights, top_idx = _ep_problem(4, 0.25, t=64)
    cap = _capacity(64, 2, 4, 0.25)
    counts = np.bincount(top_idx.reshape(-1), minlength=4)
    want_dropped = int(np.maximum(0, counts - cap).sum())
    assert want_dropped > 0, "test needs real overflow to mean anything"

    y1, d1 = _run_ep(cfg, p, x, weights, top_idx, with_stats=True)
    y2, d2 = _run_ep(cfg, p, x, weights, top_idx, with_stats=True)
    assert int(d1) == want_dropped, (int(d1), want_dropped)
    assert int(d2) == int(d1)
    assert np.array_equal(np.asarray(y1), np.asarray(y2)), \
        "overflow drop is not deterministic"


def test_ep_prob_weighted_combine_matches_dense():
    """Generous capacity (no drops): EP output equals the dense one-hot
    reference sum_k w[t,e_k] * expert_{e_k}(x_t)."""
    import numpy as np

    cfg, p, x, weights, top_idx = _ep_problem(4, 8.0)
    y, dropped = _run_ep(cfg, p, x, weights, top_idx, with_stats=True)
    assert int(dropped) == 0

    def silu(v):
        return v / (1.0 + np.exp(-v))

    ref = np.zeros_like(x)
    for t_ in range(x.shape[0]):
        for e in top_idx[t_]:
            h = silu(x[t_] @ p["experts"]["wg"][e]) * (x[t_] @ p["experts"]["wu"][e])
            ref[t_] += weights[t_, e] * (h @ p["experts"]["wd"][e])
    err = np.abs(np.asarray(y) - ref).max() / np.abs(ref).max()
    assert err < 1e-5, err
