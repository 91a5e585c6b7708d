"""Smoke run of the W4A4+LRC serving path on a TPU, at smollm-135m's
published widths (30 layers, d=576, 9/3 heads, d_ff=1536, vocab 49152).

    python chip_smoke.py              # one chip
    python chip_smoke.py --four-chip  # TP serving on a four-chip host

One chip: calibrate the model (random weights from ``--seed``, the repo's
synthetic calibration corpus, ``QuantPolicy(rank_frac=0.10,
clip_ratio=0.9)``), serve 8 seeded requests through ``ServeEngine`` with
``kernel_impl="auto"`` and 8 slots — prompts in every GEMM regime
(decode ≤32, mixed 33-512, prefill >512 tokens), 32 new tokens each — and
compare the first-step logits of the compiled Pallas model against the
``sim`` retag of the same params at ``highest`` matmul precision.

``--four-chip``: only the mesh path — the same model calibrated with
act_group=16 (which divides the local K of both row layers) served through
``ServeEngine(mesh=model=4)``, and the single-device engine on device 0 it
is compared with.

Calibration solves in float64.  The TPU runs float64 only emulated (a
small LRC solve took 32 s there against 5 s on the host, and its losses
differed from the host's in the fourth digit), so calibration is placed on
the host CPU device.  Every phase raises on failure; the last line of
standard output, printed only when all passed, is one JSON object naming
the device.  Without a TPU the script exits 2 and prints no result.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

ARCH = "smollm-135m"
PROMPT_LENS = (20, 20, 20, 300, 300, 300, 700, 700)  # decode/mixed/prefill
NEW_TOKENS = 32
SLOTS = 8
MAX_SEQ = 1024

# First-step logits, Pallas model as served vs its `sim` retag at `highest`
# matmul precision.
# The reference keeps activations and the (x V) Uᵀ term in bf16 as served,
# while the kernels compute them in f32, and 4-bit activation rounding
# amplifies such differences layer by layer.  The same comparison on the
# CPU (interpreted kernels, bf16, d_model 64, vocab 4096) gave rel max-abs
# 0.20 / 0.20-0.34 / 0.28-0.34 and correlation 0.985-0.988 / 0.975-0.980 /
# 0.964-0.974 at depth 2 / 6 / 12; the bounds extrapolate to 30 layers
# with margin (a 2-layer rehearsal of this script gave rel max-abs up to
# 0.39, so the correlation carries the check).
PALLAS_REL_MAXABS = 1.0
PALLAS_MIN_CORR = 0.90
# Mesh (model=4) vs single-device logits: row-parallel layers sum their
# GEMM + LRC partials in another order.  On the CPU (4 host devices, depth
# 6, same reduced widths) a 20-token prompt matched exactly and a
# 100-token prompt gave rel max-abs 0.135, correlation 0.998.
TP_REL_MAXABS = 1.0
TP_MIN_CORR = 0.95
# The four-chip call is charged four times per second and calibration runs
# on the host at ~8 s a layer, so that path cuts depth (widths stay).
FOUR_CHIP_LAYERS = 4


def log(msg: str) -> None:
    print(msg, flush=True)


def calibrate(cfg, seed: int, act_group=None):
    """Random weights from ``seed``, calibrated on the host CPU device."""
    import jax

    from repro.data.loader import calib_sequences
    from repro.models import model as model_lib
    from repro.quant.calibrate import quantize_model
    from repro.quant.policy import QuantPolicy

    cpu = jax.devices("cpu")[0]
    t0 = time.perf_counter()
    with jax.default_device(cpu):
        params = model_lib.init_params(cfg, jax.random.PRNGKey(seed))
        calib = calib_sequences(cfg, n_seq=16, seq_len=64)
        params = quantize_model(
            cfg, params, calib,
            QuantPolicy(rank_frac=0.10, impl="sim", clip_ratio=0.9,
                        act_group=act_group))
        params = jax.block_until_ready(params)
    log(f"calibration: placed on {cpu} (float64 solves), "
        f"{cfg.n_layers} layers in {time.perf_counter() - t0:.1f}s")
    return params


def qlinears(params):
    import jax

    from repro.quant.qlinear import QLinear

    leaves = jax.tree.leaves(params, is_leaf=lambda l: isinstance(l, QLinear))
    return [l for l in leaves if isinstance(l, QLinear)]


def seeded_requests(cfg, seed: int):
    from repro.serve.lifecycle import Request

    rng = np.random.default_rng(seed)
    return [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, n)
                    .astype(np.int32), max_new_tokens=NEW_TOKENS)
            for i, n in enumerate(PROMPT_LENS)]


def serve(cfg, params, seed: int, **engine_kw):
    """One engine run over the seeded requests; raises unless every
    request FINISHED with its full token budget."""
    from repro.serve.engine import ServeEngine
    from repro.serve.lifecycle import RequestState

    eng = ServeEngine(cfg, params, batch_slots=SLOTS, max_seq=MAX_SEQ,
                      seed=seed, kernel_impl="auto", **engine_kw)
    reqs = seeded_requests(cfg, seed)
    t0 = time.perf_counter()
    for r in reqs:
        if not eng.submit(r):
            raise RuntimeError(f"request {r.rid} rejected: {r.error}")
    done = eng.run()
    wall = time.perf_counter() - t0
    bad = {rid: f"{rec.status}: {rec.error}" for rid, rec in done.items()
           if rec.status is not RequestState.FINISHED
           or len(rec.out_tokens) != NEW_TOKENS}
    if bad or len(done) != len(reqs):
        raise RuntimeError(f"requests did not finish: {bad}")
    tokens = {rid: list(rec.out_tokens) for rid, rec in sorted(done.items())}
    return eng, tokens, wall


def check_compiled_path(eng):
    """Every QLinear is retagged to the compiled Pallas path."""
    from repro.kernels import ops

    impls = {q.impl for q in qlinears(eng.params)}
    if not impls <= {"pallas", "fused"}:
        raise RuntimeError(f"QLinear leaves not on the Pallas path: {impls}")
    ctxs = {q.ctx for q in qlinears(eng.params)}
    for ctx in ctxs:
        ctx = ops.default_context() if ctx is None else ctx
        if ctx.interpret_mode():
            raise RuntimeError("kernels would run in interpret mode")
    log(f"kernel path: {len(qlinears(eng.params))} QLinear leaves tagged "
        f"{sorted(impls)}, interpret_mode=False")


def print_plans(eng):
    plan = eng.health()["decode_plan"]
    log(f"decode plans at M={plan['m']} ({plan['regime']} regime):")
    for shape, p in sorted(plan["shapes"].items()):
        log(f"  {shape}: {p['path']} bm={p['bm']} bn={p['bn']} bk={p['bk']} "
            f"br={p['br']} variant={p['variant']}")


def logits_error(got, want):
    """(max-abs error, max-abs error over max |want|, correlation)."""
    got = np.asarray(got, np.float64).ravel()
    want = np.asarray(want, np.float64).ravel()
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        raise RuntimeError("non-finite logits")
    err = float(np.abs(got - want).max())
    return err, err / float(np.abs(want).max()), float(
        np.corrcoef(got, want)[0, 1])


def compare_with_sim(cfg, params, tokens):
    """First-step logits of ``params`` retagged to the Pallas kernels, as
    served, against their ``sim`` retag at ``highest`` matmul precision."""
    import jax

    from repro.models import model as model_lib
    from repro.quant.qlinear import retag_qlinear_impl

    fwd = jax.jit(lambda p, t: model_lib.forward(cfg, p, {"tokens": t}))
    got = fwd(retag_qlinear_impl(params, "pallas"), tokens)
    with jax.default_matmul_precision("highest"):
        want = fwd(retag_qlinear_impl(params, "sim"), tokens)
    return logits_error(got, want)


def check_bound(name, err, rel, corr, max_rel, min_corr):
    log(f"{name}: max-abs {err:.4e}, rel {rel:.4e} (bound {max_rel}), "
        f"corr {corr:.7f} (bound {min_corr})")
    if not (rel <= max_rel and corr >= min_corr):
        raise RuntimeError(f"{name} outside its bound")


def one_chip(cfg, seed: int):
    import jax

    params = jax.device_put(calibrate(cfg, seed), jax.devices()[0])
    eng, toks, cold = serve(cfg, params, seed)
    check_compiled_path(eng)
    print_plans(eng)
    _, toks2, warm = serve(cfg, params, seed)
    if toks2 != toks:
        raise RuntimeError("token streams differ between two runs")
    n_tok = sum(len(t) for t in toks.values())
    log(f"serve: {len(toks)} requests FINISHED, {n_tok} tokens, prompt "
        f"lengths {list(PROMPT_LENS)}; first run {cold:.1f}s (includes "
        f"compiling {eng.health()['traces']['paged']} step programs), "
        f"second run {warm:.1f}s, compile ~{cold - warm:.1f}s")
    for n in sorted(set(PROMPT_LENS)):
        prompt = seeded_requests(cfg, seed)[PROMPT_LENS.index(n)].prompt
        check_bound(f"logits vs sim, {n}-token prompt",
                    *compare_with_sim(cfg, params, prompt[None, :]),
                    PALLAS_REL_MAXABS, PALLAS_MIN_CORR)


def four_chip(cfg, seed: int):
    import jax

    from repro.distributed.tp import build_mesh
    from repro.models import model as model_lib

    if len(jax.devices()) != 4:
        raise RuntimeError(f"--four-chip needs 4 devices, "
                           f"have {len(jax.devices())}")
    cfg = dataclasses.replace(cfg, n_layers=FOUR_CHIP_LAYERS)
    params = calibrate(cfg, seed, act_group=16)
    mesh = build_mesh("model=4")
    eng, toks, cold = serve(cfg, params, seed, mesh=mesh)
    _, toks2, warm = serve(cfg, params, seed, mesh=mesh)
    if toks2 != toks:
        raise RuntimeError("mesh token streams differ between two runs")
    check_compiled_path(eng)
    mh = eng.health()["mesh"]
    kinds = {}
    for p in mh["decode_plans"].values():
        kinds[p["parallel"] or "replicated"] = (
            kinds.get(p["parallel"] or "replicated", 0) + p["layers"])
    log(f"mesh: axes={mh['axes']} layers_by_kind={kinds}")
    for key, p in sorted(mh["decode_plans"].items()):
        log(f"  {key}: {p['path']} bm={p['bm']} bn={p['bn']} bk={p['bk']} "
            f"br={p['br']} variant={p['variant']}")
    if not {"column", "row"} <= set(kinds):
        raise RuntimeError(f"mesh lacks column or row layers: {kinds}")
    log(f"mesh serve: {len(toks)} requests FINISHED twice with identical "
        f"tokens; first run {cold:.1f}s, second {warm:.1f}s")

    single = jax.device_put(params, jax.devices()[0])
    seng, stoks, swall = serve(cfg, single, seed)
    same = sum(toks[r] == stoks[r] for r in toks)
    log(f"single-device serve on {jax.devices()[0]}: {swall:.1f}s, "
        f"{same}/{len(toks)} token streams equal to the mesh run")

    fwd = jax.jit(lambda p, t: model_lib.forward(cfg, p, {"tokens": t}))
    for n in sorted(set(PROMPT_LENS)):
        prompt = seeded_requests(cfg, seed)[PROMPT_LENS.index(n)].prompt
        with jax.set_mesh(mesh):
            got = fwd(eng.params, prompt[None, :])
        want = fwd(seng.params, prompt[None, :])
        check_bound(f"mesh vs single-device logits, {n}-token prompt",
                    *logits_error(got, want), TP_REL_MAXABS, TP_MIN_CORR)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the model=4 mesh path and the "
                         "single-device engine it is compared with")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX found {dev.platform} devices", file=sys.stderr)
        return 2
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.configs import get_config
    from repro.launch.compile_cache import use_compile_cache

    log(f"device: {dev.device_kind} x{len(jax.devices())}, compile cache "
        f"{use_compile_cache()}")
    cfg = get_config(ARCH)
    (four_chip if args.four_chip else one_chip)(cfg, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
