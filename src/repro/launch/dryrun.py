import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count on first init).  Placeholder host devices exist ONLY for this dry-run.

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

import jax  # noqa: E402

from repro.configs import ARCH_IDS, get_config  # noqa: E402
from repro.configs.shapes import SHAPES, applicable  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.roofline import (  # noqa: E402
    combine_costs,
    extract_costs,
    memory_info,
    model_flops,
    roofline_from_costs,
)
from repro.launch.specs import build_cell  # noqa: E402

RESULTS_DIR = Path(__file__).resolve().parents[3] / "results" / "dryrun"


def _compile_cell(arch, shape_name, mesh, multi_pod, cfg_override=None):
    cell = build_cell(arch, shape_name, mesh, multi_pod, cfg_override=cfg_override)
    with jax.set_mesh(mesh):
        jitted = jax.jit(cell["fn"], in_shardings=cell["in_shardings"])
        lowered = jitted.lower(*cell["args"])
        compiled = lowered.compile()
    return compiled, cell


def _shrunk(cfg, **depth):
    return dataclasses.replace(cfg, unroll=True, **depth)


def extrapolated_costs(arch, shape_name, mesh, multi_pod, base_cfg=None):
    """Per-device flops/bytes/collectives at FULL depth, from 2-3 small
    UNROLLED compiles (XLA cost_analysis counts while bodies once — see
    repro.models.remat.scan_layers).  Exact for homogeneous layer stacks.
    ``base_cfg``: optional config override (§Perf hillclimb variants)."""
    cfg = base_cfg if base_cfg is not None else get_config(arch)
    fam = cfg.family

    def costs_for(cfg_k):
        compiled, _ = _compile_cell(arch, shape_name, mesh, multi_pod, cfg_override=cfg_k)
        return extract_costs(compiled)

    if fam == "moe":
        nd, nm = cfg.n_dense_layers, cfg.n_layers - cfg.n_dense_layers
        a = costs_for(_shrunk(cfg, n_layers=2, n_dense_layers=1))  # 1d + 1m
        b = costs_for(_shrunk(cfg, n_layers=3, n_dense_layers=1))  # 1d + 2m
        c = costs_for(_shrunk(cfg, n_layers=3, n_dense_layers=2))  # 2d + 1m
        moe_unit = combine_costs(b, a, 1.0, -1.0)
        dense_unit = combine_costs(c, a, 1.0, -1.0)
        base = combine_costs(a, combine_costs(moe_unit, dense_unit, 1.0, 1.0), 1.0, -1.0)
        total = combine_costs(base, moe_unit, 1.0, float(nm))
        total = combine_costs(total, dense_unit, 1.0, float(nd))
        return total
    if fam == "hybrid":
        e = cfg.hybrid_attn_every
        c6 = costs_for(_shrunk(cfg, n_layers=e))          # base + e·m + 1·app
        c7 = costs_for(_shrunk(cfg, n_layers=e + 1))      # + 1 mamba
        c12 = costs_for(_shrunk(cfg, n_layers=2 * e))     # base + 2e·m + 2·app
        m_unit = combine_costs(c7, c6, 1.0, -1.0)
        # app = (c12 - c6) - e·m
        app_unit = combine_costs(combine_costs(c12, c6, 1.0, -1.0), m_unit, 1.0, -float(e))
        base = combine_costs(c6, combine_costs(m_unit, app_unit, float(e), 1.0), 1.0, -1.0)
        n_apps = cfg.n_layers // e
        total = combine_costs(base, m_unit, 1.0, float(cfg.n_layers))
        total = combine_costs(total, app_unit, 1.0, float(n_apps))
        return total
    if fam == "encdec":
        a = costs_for(_shrunk(cfg, n_layers=1, n_encoder_layers=1))
        b = costs_for(_shrunk(cfg, n_layers=2, n_encoder_layers=1))
        c = costs_for(_shrunk(cfg, n_layers=1, n_encoder_layers=2))
        dec_unit = combine_costs(b, a, 1.0, -1.0)
        enc_unit = combine_costs(c, a, 1.0, -1.0)
        base = combine_costs(a, combine_costs(dec_unit, enc_unit, 1.0, 1.0), 1.0, -1.0)
        total = combine_costs(base, dec_unit, 1.0, float(cfg.n_layers))
        total = combine_costs(total, enc_unit, 1.0, float(cfg.n_encoder_layers))
        return total
    # dense / vlm / ssm: homogeneous stack
    a = costs_for(_shrunk(cfg, n_layers=1))
    b = costs_for(_shrunk(cfg, n_layers=2))
    unit = combine_costs(b, a, 1.0, -1.0)
    base = combine_costs(a, unit, 1.0, -1.0)
    return combine_costs(base, unit, 1.0, float(cfg.n_layers))


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: Path,
             with_costs: bool = True) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    t0 = time.time()
    # 1) FULL-depth scanned compile: proves lowering + gives true memory
    compiled, cell = _compile_cell(arch, shape_name, mesh, multi_pod)
    t_compile = time.time() - t0
    mem = compiled.memory_analysis()
    print(mem)  # proves it fits (bytes-per-device breakdown)
    print({k: compiled.cost_analysis()[k]
           for k in ("flops", "bytes accessed")
           if k in compiled.cost_analysis()})
    mem_rec = memory_info(compiled)
    # 2) cost extrapolation from small unrolled variants
    if with_costs:
        costs = extrapolated_costs(arch, shape_name, mesh, multi_pod)
    else:
        costs = extract_costs(compiled)
    mf = model_flops(get_config(arch), SHAPES[shape_name])
    rf = roofline_from_costs(costs, mf, n_chips, mem_rec)
    rec = dict(
        arch=arch,
        shape=shape_name,
        mesh="2x16x16" if multi_pod else "16x16",
        n_chips=n_chips,
        kind=cell["kind"],
        status="ok",
        compile_s=round(t_compile, 1),
        total_s=round(time.time() - t0, 1),
        **rf,
    )
    out_dir.mkdir(parents=True, exist_ok=True)
    name = f"{arch}__{shape_name}__{'multi' if multi_pod else 'single'}.json"
    (out_dir / name).write_text(json.dumps(rec, indent=2, default=str))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=str(RESULTS_DIR))
    ap.add_argument("--skip-done", action="store_true")
    ap.add_argument("--no-costs", action="store_true",
                    help="skip the unrolled cost extrapolation (compile-only)")
    args = ap.parse_args()
    out_dir = Path(args.out)

    cells = []
    if args.all:
        for arch in ARCH_IDS:
            cfg = get_config(arch)
            for shape in SHAPES:
                if applicable(cfg, shape):
                    cells.append((arch, shape, args.multi_pod))
    else:
        cells.append((args.arch, args.shape, args.multi_pod))

    failures = 0
    for arch, shape, mp in cells:
        name = f"{arch}__{shape}__{'multi' if mp else 'single'}.json"
        if args.skip_done and (out_dir / name).exists():
            rec = json.loads((out_dir / name).read_text())
            if rec.get("status") == "ok":
                print(f"[skip] {name}")
                continue
        print(f"=== {arch} × {shape} ({'2x16x16' if mp else '16x16'}) ===", flush=True)
        try:
            rec = run_cell(arch, shape, mp, out_dir, with_costs=not args.no_costs)
            print(
                f"  ok: bottleneck={rec['bottleneck']} "
                f"step_bound={rec['step_time_bound_s']:.4f}s "
                f"useful={rec['useful_flops_ratio']:.3f} "
                f"roofline_frac={rec['roofline_fraction']:.3f} "
                f"(total {rec['total_s']}s)",
                flush=True,
            )
        except Exception as e:  # noqa: BLE001
            failures += 1
            out_dir.mkdir(parents=True, exist_ok=True)
            (out_dir / name).write_text(
                json.dumps(
                    dict(arch=arch, shape=shape, status="fail",
                         error=f"{type(e).__name__}: {e}"),
                    indent=2,
                )
            )
            print(f"  FAIL: {type(e).__name__}: {e}", flush=True)
            traceback.print_exc()
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
