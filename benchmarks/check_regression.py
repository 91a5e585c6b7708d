"""CI roofline regression gate for the W4A4+LRC kernel byte model.

Recomputes the analytic roofline rows (benchmarks/latency_kernels.py) from
the CURRENT code and compares them against the committed baseline
``results/latency_kernels.json``:

  * every activation-byte column (``act_prologue_kb_{unfused,chained,fused}``,
    i.e. ``prologue_activation_bytes`` on all three kernel paths) and every
    predicted-latency column may not regress more than ``--tolerance``
    (default 5%) over the baseline;
  * the fused single-kernel path must stay STRICTLY below the chained path's
    activation bytes at decode shapes (the PR acceptance invariant: the M×K
    xq write+read is eliminated).

With ``--serve`` the gate instead compares a freshly measured serving run
(``results/BENCH_serve_smoke.json`` from ``benchmarks.serve_latency
--smoke``) against the committed ``results/BENCH_serve.json``.  The gate
guards its DETERMINISTIC efficiency columns — ``decode_calls_per_token`` (must stay
exactly ``1/batch``: one batched decode call per engine step),
``prefill_chunks_per_prompt`` and ``kv_bytes_per_token`` (the quantized-KV
footprint per cached token; growth means the paged pools or scale planes
got fatter) — which are token-count invariant, so smoke rows compare
against the full baseline directly.

Exit status 1 on any violation — wire this after the bench-smoke step in CI.

    PYTHONPATH=src python -m benchmarks.check_regression \
        [--baseline results/latency_kernels.json] [--tolerance 0.05]
    PYTHONPATH=src python -m benchmarks.check_regression --serve \
        [--serve-current results/BENCH_serve_smoke.json]
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

from benchmarks.latency_kernels import HEADER, analytic_rows

# columns the gate protects: every predicted-latency, activation-byte and
# attention-KV-byte column the CURRENT code emits (lower is better,
# >tolerance growth fails).  Derived from HEADER so a new column added by a
# kernel change is guarded automatically — and a baseline that predates it
# fails with a clear "regenerate" message instead of a KeyError.
_GUARDED = [h for h in HEADER
            if h.startswith("us_") or h.startswith("act_prologue_kb_")
            or h.startswith("attn_kb_") or h.startswith("comms_kb_")]


def check(baseline_path: Path, tolerance: float) -> list[str]:
    try:
        baseline = json.loads(baseline_path.read_text())
    except (OSError, json.JSONDecodeError) as e:
        return [f"baseline {baseline_path} is unreadable ({e}); "
                "regenerate it with: PYTHONPATH=src python -m "
                "benchmarks.latency_kernels"]
    if not isinstance(baseline, dict) or "header" not in baseline \
            or "rows" not in baseline:
        return [f"baseline {baseline_path} lacks header/rows; regenerate it "
                "with: PYTHONPATH=src python -m benchmarks.latency_kernels"]
    b_idx = {h: i for i, h in enumerate(baseline["header"])}
    missing = [c for c in _GUARDED + ["matrix", "ranks"] if c not in b_idx]
    if missing:
        return [f"baseline {baseline_path} lacks columns {missing} that the "
                "current benchmark emits — the committed baseline predates "
                "this code; regenerate it with: PYTHONPATH=src python -m "
                "benchmarks.latency_kernels"]
    short = [r for r in baseline["rows"] if len(r) < len(baseline["header"])]
    if short:
        return [f"baseline {baseline_path} has {len(short)} row(s) shorter "
                f"than its header ({len(baseline['header'])} columns); "
                "regenerate it with: PYTHONPATH=src python -m "
                "benchmarks.latency_kernels"]
    b_rows = {(r[b_idx["matrix"]], r[b_idx["ranks"]]): r
              for r in baseline["rows"]}
    c_idx = {h: i for i, h in enumerate(HEADER)}

    failures = []
    matched = 0
    for row in analytic_rows():
        key = (row[c_idx["matrix"]], row[c_idx["ranks"]])
        base = b_rows.get(key)
        if base is None:
            continue  # new shape, nothing to regress against
        matched += 1
        for col in _GUARDED:
            b, c = base[b_idx[col]], row[c_idx[col]]
            if not (isinstance(b, (int, float)) and isinstance(c, (int, float))):
                continue
            if b > 0 and c > b * (1.0 + tolerance):
                failures.append(
                    f"{key[0]} r={key[1]} {col}: {c} vs baseline {b} "
                    f"(+{(c / b - 1) * 100:.1f}% > {tolerance * 100:.0f}%)")
        # decode-shape invariant: the single kernel must beat the chain
        if key[0].startswith("M16_"):
            fu = row[c_idx["act_prologue_kb_fused"]]
            ch = row[c_idx["act_prologue_kb_chained"]]
            if not fu < ch:
                failures.append(
                    f"{key[0]} r={key[1]}: fused activation bytes {fu} kB "
                    f"not strictly below chained {ch} kB")
    if matched == 0:
        failures.append(
            f"no baseline rows matched current shapes — baseline "
            f"{baseline_path} is stale; regenerate it")
    return failures


# serving-efficiency columns the --serve gate protects.  Both are exact
# consequences of the engine's batching structure (see
# benchmarks/serve_latency.py), so ANY growth over baseline is a structural
# regression — but the shared --tolerance still applies for symmetry.
_SERVE_GUARDED = ["decode_calls_per_token", "prefill_chunks_per_prompt",
                  "kv_bytes_per_token"]
_SERVE_KEY = ["batch", "page_size", "prefill_chunk", "kv_dtype"]
_SERVE_REGEN = ("regenerate them with: PYTHONPATH=src python -m "
                "benchmarks.serve_latency (baseline) and "
                "PYTHONPATH=src python -m benchmarks.serve_latency --smoke "
                "(current)")


def _load_table(path: Path, needed: list[str]):
    """Load a benchmarks.common.record() table; return (err, idx, rows)."""
    try:
        data = json.loads(path.read_text())
    except (OSError, json.JSONDecodeError) as e:
        return f"{path} is unreadable ({e}); {_SERVE_REGEN}", None, None
    if not isinstance(data, dict) or "header" not in data or "rows" not in data:
        return f"{path} lacks header/rows; {_SERVE_REGEN}", None, None
    idx = {h: i for i, h in enumerate(data["header"])}
    missing = [c for c in needed if c not in idx]
    if missing:
        return (f"{path} lacks columns {missing} — it predates this code; "
                f"{_SERVE_REGEN}"), None, None
    short = [r for r in data["rows"] if len(r) < len(data["header"])]
    if short:
        return (f"{path} has {len(short)} row(s) shorter than its header; "
                f"{_SERVE_REGEN}"), None, None
    return None, idx, data["rows"]


def check_serve(baseline_path: Path, current_path: Path,
                tolerance: float) -> list[str]:
    needed = _SERVE_GUARDED + _SERVE_KEY
    err, b_idx, b_raw = _load_table(baseline_path, needed)
    if err:
        return [err]
    err, c_idx, c_rows = _load_table(current_path, needed)
    if err:
        return [err]
    b_rows = {tuple(r[b_idx[k]] for k in _SERVE_KEY): r for r in b_raw}

    failures = []
    matched = 0
    for row in c_rows:
        key = tuple(row[c_idx[k]] for k in _SERVE_KEY)
        tag = f"B={key[0]} page={key[1]} chunk={key[2]} kv={key[3]}"
        # structural invariant: ONE batched decode call per engine step,
        # independent of any baseline — 1/batch exactly
        cpt = row[c_idx["decode_calls_per_token"]]
        if abs(cpt - 1.0 / key[0]) > 1e-4:
            failures.append(
                f"{tag}: decode_calls_per_token {cpt} != 1/batch "
                f"({1.0 / key[0]:.6f}) — decode is no longer one batched "
                "call per step")
        base = b_rows.get(key)
        if base is None:
            continue  # new grid point, nothing to regress against
        matched += 1
        for col in _SERVE_GUARDED:
            b, c = base[b_idx[col]], row[c_idx[col]]
            if not (isinstance(b, (int, float)) and isinstance(c, (int, float))):
                continue
            if b > 0 and c > b * (1.0 + tolerance):
                failures.append(
                    f"{tag} {col}: {c} vs baseline {b} "
                    f"(+{(c / b - 1) * 100:.1f}% > {tolerance * 100:.0f}%)")
    if matched == 0:
        failures.append(
            f"no baseline rows matched current serve grid — baseline "
            f"{baseline_path} is stale; {_SERVE_REGEN}")
    return failures


def main(argv=None) -> int:
    results = Path(__file__).resolve().parents[1] / "results"
    ap = argparse.ArgumentParser()
    ap.add_argument("--baseline",
                    default=str(results / "latency_kernels.json"))
    ap.add_argument("--tolerance", type=float, default=0.05)
    ap.add_argument("--serve", action="store_true",
                    help="gate the serving benchmark instead of the kernel "
                         "roofline (compares --serve-current against "
                         "--serve-baseline)")
    ap.add_argument("--serve-baseline",
                    default=str(results / "BENCH_serve.json"))
    ap.add_argument("--serve-current",
                    default=str(results / "BENCH_serve_smoke.json"))
    args = ap.parse_args(argv)

    if args.serve:
        failures = check_serve(Path(args.serve_baseline),
                               Path(args.serve_current), args.tolerance)
        name = "serving regression gate"
        detail = (f"baseline {args.serve_baseline}, "
                  f"current {args.serve_current}")
    else:
        failures = check(Path(args.baseline), args.tolerance)
        name = "roofline regression gate"
        detail = f"baseline {args.baseline}"
    if failures:
        print(f"{name} FAILED:")
        for f in failures:
            print(f"  {f}")
        return 1
    print(f"{name} passed (tolerance {args.tolerance * 100:.0f}%, {detail})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
