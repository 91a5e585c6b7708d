"""Tensor-parallel W4A4+LRC under shard_map (distributed/tp.py) on a forced
8-device host mesh: layer-level numerics contract (every layer kind within
a few ulp of the bf16 LR dtype; row one psum), trace/HLO collective counts,
shape-keyed kernel-plan resolution at the LOCAL shard shape,
sharding-preserving retag, and the mesh-mode ServeEngine's run-to-run
determinism.  Subprocesses, so the 1-device tests elsewhere keep their
platform config."""

import os
import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import numpy as np
import jax, jax.numpy as jnp

from repro.configs import get_config
from repro.distributed import tp as tp_lib
from repro.launch.mesh import auto_mesh
from repro.models import model as model_lib
from repro.models.config import reduced
from repro.quant.calibrate import quantize_model
from repro.quant.policy import QuantPolicy
from repro.quant.qlinear import QLinear, qlinear_apply, retag_qlinear_impl

cfg = reduced(get_config("smollm-135m"))
params = model_lib.init_params(cfg, jax.random.PRNGKey(0))
calib = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab_size)
# act_group=16 divides every local K slice (wo: 64/4=16, wd: 128/4=32), so
# the row layers shard instead of falling back to replication
q = quantize_model(cfg, params, calib,
                   QuantPolicy(rank_frac=0.10, impl="sim", clip_ratio=0.9,
                               act_group=16))
mesh = auto_mesh((2, 4), ("data", "model"))
sp, plan = tp_lib.shard_params(q, mesh)
kinds = {e["path"]: e["parallel"] for e in plan}
assert kinds["layers/attn/wq"] == "column", kinds
assert kinds["layers/attn/wo"] == "row", kinds
assert kinds["layers/mlp/wd"] == "row", kinds
qls = [l for l in jax.tree.leaves(sp, is_leaf=lambda l: isinstance(l, QLinear))
       if isinstance(l, QLinear)]
assert qls and all(l.parallel in ("column", "row", "replicate") for l in qls)

# plan reports per-shard (K, N, R): row-parallel wo splits K by tp=4
wo_entry = next(e for e in plan if e["path"] == "layers/attn/wo")
gk, gn, gr = wo_entry["global_knr"]
lk, ln, lr = wo_entry["local_knr"]
assert (lk, ln, lr) == (gk // 4, gn, gr), wo_entry


def flat(ql, i=0):  # slice one layer out of a stacked (scan) leaf
    return dataclasses.replace(
        ql, qweight=ql.qweight[i], w_scale=ql.w_scale[i],
        u=None if ql.u is None else ql.u[i],
        v=None if ql.v is None else ql.v[i])


def get(tree, path):
    node = tree
    for part in path.split("/"):
        node = node[part]
    return node


rng = np.random.default_rng(0)


def lr_dtype_bound(ref, got):
    # the LRC factors are STORED bf16 and XLA may keep a bf16 intermediate
    # of (x V) Uᵀ at higher precision in one program and round it in
    # another (excess precision is on by default), so every TP layer kind
    # is held to a few ulp of the LR dtype, not to bitwise equality
    d = float(np.abs(np.asarray(ref) - np.asarray(got)).max())
    scale = float(np.abs(np.asarray(ref)).max())
    assert d <= max(1e-6, 4 * 2.0 ** -8 * scale), (d, scale)


# column-parallel: no collective, within the LR-dtype bound of the
# single-device jitted apply
col = flat(get(sp, "layers/attn/wq"))
xc = jnp.asarray(rng.standard_normal((8, col.d_in)), jnp.float32)
ref = jax.jit(lambda x: qlinear_apply(tp_lib._strip(col), x))(xc)
with jax.set_mesh(mesh):
    got = jax.jit(lambda x: qlinear_apply(col, x))(xc)
lr_dtype_bound(ref, got)

# replicate-tagged: the identical full-shape apply in the shard body
rep = dataclasses.replace(col, parallel="replicate")
with jax.set_mesh(mesh):
    got = jax.jit(lambda x: qlinear_apply(rep, x))(xc)
lr_dtype_bound(ref, got)

# row-parallel: ONE f32 psum
row = flat(get(sp, "layers/attn/wo"))
xo = jnp.asarray(rng.standard_normal((8, row.d_in)), jnp.float32)
ref = jax.jit(lambda x: qlinear_apply(tp_lib._strip(row), x))(xo)
with jax.set_mesh(mesh):
    got = jax.jit(lambda x: qlinear_apply(row, x))(xo)
# drift: the GEMM partial reassociates in f32 (~eps_f32), and K-splitting
# the x@V contraction re-rounds the bf16 partials
lr_dtype_bound(ref, got)

# trace-level collective counts: row = exactly ONE psum, zero gathers
# (the zero-extra-collective invariant: the LRC partial rides the same psum)
with jax.set_mesh(mesh):
    s_row = str(jax.make_jaxpr(lambda x: qlinear_apply(row, x))(xo))
    s_col = str(jax.make_jaxpr(lambda x: qlinear_apply(col, x))(xc))
assert s_row.count("psum") == 1, s_row.count("psum")
assert "all_gather" not in s_row
assert "psum" not in s_col and "all_gather" not in s_col

# compiled HLO of the row layer: exactly one all-reduce
with jax.set_mesh(mesh):
    hlo = jax.jit(lambda x: qlinear_apply(row, x)).lower(xo).compile().as_text()
n_ar = sum(1 for ln_ in hlo.splitlines()
           if " all-reduce(" in ln_ or " all-reduce-start(" in ln_)
assert n_ar == 1, f"row-parallel layer compiled to {n_ar} all-reduces"

# shape-keyed KernelContext override resolves at the LOCAL (K, N, R)
from repro.kernels.context import KernelContext
ctx = KernelContext().with_layer_overrides({(lk, ln, lr): {"bm": 4}})
p_local = ctx.resolve_plan(8, lk, ln, lr, act_group=row.act_group)
assert p_local.bm == 4, p_local
p_global = ctx.resolve_plan(8, gk, gn, gr, act_group=row.act_group)
assert p_global.bm != 4, "global shape must not hit the local-shape override"

# retag preserves NamedSharding on quantized + low-rank leaves
wq_before = get(sp, "layers/attn/wq")
rt = retag_qlinear_impl(sp, "int8")
wq_after = get(rt, "layers/attn/wq")
assert wq_after.impl == "int8"
assert wq_after.parallel == wq_before.parallel
for f in ("qweight", "w_scale", "u", "v"):
    a, b = getattr(wq_before, f), getattr(wq_after, f)
    if a is None:
        continue
    assert b.sharding == a.sharding, (f, a.sharding, b.sharding)

# infeasible act_group (does not divide K/tp) falls back to replication
q_bad = dataclasses.replace(tp_lib._strip(row), act_group=row.d_in // 4 + 1)
assert not tp_lib.tp_feasible(q_bad, "row", 4)
# ... and per-token scales (act_group=None) refuse row-parallel outright
q_tok = dataclasses.replace(tp_lib._strip(row), act_group=None)
assert not tp_lib.tp_feasible(q_tok, "row", 4)
print("TP_LAYER_OK")
"""

ENGINE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax, jax.numpy as jnp

from repro.configs import get_config
from repro.launch.mesh import auto_mesh
from repro.models import model as model_lib
from repro.models.config import reduced
from repro.quant.calibrate import quantize_model
from repro.quant.policy import QuantPolicy
from repro.serve.engine import ServeEngine
from repro.serve.lifecycle import Request

rng = np.random.default_rng(0)

# -- dense: full column+row sharding, run-to-run determinism + health ------
cfg = reduced(get_config("smollm-135m"))
params = model_lib.init_params(cfg, jax.random.PRNGKey(0))
calib = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab_size)
q = quantize_model(cfg, params, calib,
                   QuantPolicy(rank_frac=0.10, impl="sim", clip_ratio=0.9,
                               act_group=16))
mesh = auto_mesh((2, 4), ("data", "model"))
prompts = [rng.integers(0, cfg.vocab_size, size=6).astype(np.int32)
           for _ in range(3)]


def run():
    eng = ServeEngine(cfg, q, batch_slots=2, max_seq=32, seed=0,
                      kernel_impl="auto", mesh=mesh)
    for i, p in enumerate(prompts):
        assert eng.submit(Request(rid=i, prompt=p, max_new_tokens=4))
    recs = eng.run()
    return eng, {r: recs[r].out_tokens for r in recs}


e1, t1 = run()
e2, t2 = run()
assert all(t1[r] for r in t1)
assert t1 == t2, "mesh engine not run-to-run deterministic"
h = e1.health()["mesh"]
assert h["axes"] == {"data": 2, "model": 4}, h
pk = {p["parallel"] for p in h["decode_plans"].values()}
assert "column" in pk and "row" in pk, pk
# every decode plan resolved at the shard's LOCAL width, not the global one
widths = {cfg.d_model, cfg.d_ff, cfg.n_kv_heads * cfg.head_dim}
for p in h["decode_plans"].values():
    if p["parallel"] == "column":
        assert p["local"]["n"] * 4 in widths, (p, widths)

# -- MoE: expert-parallel decode, deterministic, drop counter surfaces -----
mcfg = reduced(get_config("deepseek-v2-236b"))
mparams = model_lib.init_params(mcfg, jax.random.PRNGKey(0))
mcalib = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, mcfg.vocab_size)
mq = quantize_model(mcfg, mparams, mcalib,
                    QuantPolicy(rank_frac=0.10, impl="sim", clip_ratio=0.9,
                                act_group=16))
mmesh = auto_mesh((1, 2), ("data", "model"))


def mrun():
    eng = ServeEngine(mcfg, mq, batch_slots=2, max_seq=32, seed=0,
                      kernel_impl="auto", mesh=mmesh)
    for i, p in enumerate(prompts[:2]):
        assert eng.submit(Request(rid=i, prompt=p, max_new_tokens=3))
    recs = eng.run()
    return eng, {r: recs[r].out_tokens for r in recs}


m1, mt1 = mrun()
m2, mt2 = mrun()
assert mt1 == mt2, "moe mesh engine not run-to-run deterministic"
mh = m1.health()["mesh"]
assert mh["moe_impl"] == "ep", mh
assert mh["ep_dropped"] >= 0
assert any(p["parallel"] == "ep" for p in mh["decode_plans"].values()), mh
print("TP_ENGINE_OK")
"""


def _run(script, marker):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout + out.stderr
    assert marker in out.stdout


def test_tp_layer_contract():
    _run(SCRIPT, "TP_LAYER_OK")


def test_tp_engine_determinism():
    _run(ENGINE_SCRIPT, "TP_ENGINE_OK")
