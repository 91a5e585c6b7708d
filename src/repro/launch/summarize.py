"""Aggregate results/dryrun/*.json into the EXPERIMENTS.md tables.

    PYTHONPATH=src python -m repro.launch.summarize [--dir results/dryrun]

With ``--sharding`` the tool instead prints the fully resolved mesh
placement plan for one architecture — param path → PartitionSpec, with
every divisibility fallback (a rule that wanted to shard a dim that does
not divide its mesh axis) marked inline — without materialising a model
(``jax.eval_shape`` over an AbstractMesh, so no devices are needed):

    PYTHONPATH=src python -m repro.launch.summarize \
        --sharding smollm-135m --mesh model=4,data=2 [--full]
"""

from __future__ import annotations

import argparse
import json
from pathlib import Path


def fmt_s(x):
    if x >= 1:
        return f"{x:.2f}s"
    if x >= 1e-3:
        return f"{x*1e3:.1f}ms"
    return f"{x*1e6:.0f}us"


def fmt_b(x):
    for unit in ("B", "KB", "MB", "GB", "TB"):
        if x < 1024:
            return f"{x:.1f}{unit}"
        x /= 1024
    return f"{x:.1f}PB"


def load(dir_path: Path):
    recs = []
    for p in sorted(dir_path.glob("*.json")):
        recs.append(json.loads(p.read_text()))
    return recs


def table(recs, mesh_filter: str):
    rows = []
    for r in recs:
        if r.get("status") != "ok" or r.get("mesh") != mesh_filter:
            continue
        mem = r.get("memory", {})
        hbm = (mem.get("argument_size_in_bytes", 0) + mem.get("temp_size_in_bytes", 0)
               + mem.get("output_size_in_bytes", 0))
        rows.append(dict(
            arch=r["arch"], shape=r["shape"], kind=r["kind"],
            compute=r["compute_term_s"], memory=r["memory_term_s"],
            coll=r["collective_term_s"], bottleneck=r["bottleneck"],
            bound=r["step_time_bound_s"], useful=r["useful_flops_ratio"],
            frac=r["roofline_fraction"], hbm=hbm,
        ))
    return rows


def sharding_report(arch: str, mesh_spec: str, use_reduced: bool) -> int:
    """Print path → PartitionSpec for every param of ``arch`` under the
    mesh, flagging divisibility fallbacks.  Exit 0 always — fallbacks are
    a property of the (config, mesh) pair, not an error."""
    import jax

    from repro.configs import get_config
    from repro.distributed.sharding import describe_sharding
    from repro.distributed.tp import parse_mesh
    from repro.models import model as model_lib
    from repro.models.config import reduced

    cfg = get_config(arch)
    if use_reduced:
        cfg = reduced(cfg)
    spec = parse_mesh(mesh_spec)
    mesh = jax.sharding.AbstractMesh(
        tuple(spec.values()), tuple(spec.keys()),
        axis_types=(jax.sharding.AxisType.Auto,) * len(spec))
    tree = jax.eval_shape(
        lambda: model_lib.init_params(cfg, jax.random.PRNGKey(0)))
    rows = describe_sharding(tree, mesh)
    n_fb = sum(len(r["fallbacks"]) for r in rows)
    print(f"sharding plan: {cfg.name}"
          f"{' (reduced)' if use_reduced else ''} on mesh {dict(spec)} "
          f"({len(rows)} leaves, {n_fb} divisibility fallback(s))\n")
    wpath = max(len(r["path"]) for r in rows)
    wshape = max(len(str(r["shape"])) for r in rows)
    for r in rows:
        mark = ""
        if r["fallbacks"]:
            mark = "  <- " + "; ".join(
                f"dim {f.dim_index} ({f.dim}) !% {f.axis}={f.axis_size}"
                for f in r["fallbacks"])
        print(f"  {r['path']:<{wpath}}  {str(r['shape']):<{wshape}}  "
              f"{r['spec']}{mark}")
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="results/dryrun")
    ap.add_argument("--markdown", action="store_true", default=True)
    ap.add_argument("--sharding", default=None, metavar="ARCH",
                    help="print the resolved param-path -> PartitionSpec "
                         "plan for ARCH under --mesh instead of the dryrun "
                         "tables (divisibility fallbacks marked inline)")
    ap.add_argument("--mesh", default="model=4,data=2",
                    help="mesh axes for --sharding, e.g. model=4,data=2 "
                         "(AbstractMesh — no devices needed)")
    ap.add_argument("--full", action="store_true",
                    help="use the full (non-reduced) config for --sharding")
    args = ap.parse_args()
    if args.sharding:
        raise SystemExit(sharding_report(args.sharding, args.mesh,
                                         not args.full))
    recs = load(Path(args.dir))
    for mesh in ("16x16", "2x16x16"):
        rows = table(recs, mesh)
        if not rows:
            continue
        print(f"\n### Mesh {mesh} ({'256' if mesh == '16x16' else '512'} chips)\n")
        print("| arch | shape | compute | memory | collective | bottleneck | "
              "step bound | useful | roofline | HBM/dev |")
        print("|---|---|---|---|---|---|---|---|---|---|")
        for r in rows:
            print(
                f"| {r['arch']} | {r['shape']} | {fmt_s(r['compute'])} | "
                f"{fmt_s(r['memory'])} | {fmt_s(r['coll'])} | **{r['bottleneck']}** | "
                f"{fmt_s(r['bound'])} | {r['useful']:.3f} | {r['frac']:.4f} | "
                f"{fmt_b(r['hbm'])} |"
            )
    fails = [r for r in recs if r.get("status") != "ok"]
    if fails:
        print("\nFAILURES:")
        for r in fails:
            print(f"  {r['arch']} x {r['shape']}: {r.get('error', '?')}")


if __name__ == "__main__":
    main()
