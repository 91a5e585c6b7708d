"""Benchmark harness — one entry per paper table/figure.

Prints ``name,us_per_call,derived`` CSV summary lines plus each table's full
CSV; detailed JSON lands in results/."""

from __future__ import annotations

import time


def main() -> None:
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    from benchmarks import (
        fig2_rank_sweep,
        fig3_quantizer,
        latency_kernels,
        table1_w4a4,
        table2_groups,
        table3_weightonly,
    )

    summary = []
    for name, mod in [
        ("table1_w4a4", table1_w4a4),
        ("table2_groups", table2_groups),
        ("table3_weightonly", table3_weightonly),
        ("fig2_rank_sweep", fig2_rank_sweep),
        ("fig3_quantizer", fig3_quantizer),
        ("latency_kernels", latency_kernels),
    ]:
        t0 = time.time()
        derived = mod.run()
        us = (time.time() - t0) * 1e6
        summary.append((name, us, _derived_str(name, derived)))
        print()
    print("name,us_per_call,derived")
    for name, us, derived in summary:
        print(f"{name},{us:.0f},{derived}")


def _derived_str(name: str, derived) -> str:
    try:
        if name == "table1_w4a4":
            gap = (derived["FP16"][1] - derived["LRC (1)"][1]) / max(
                1e-9, derived["FP16"][1] - derived["QuaRot"][1]
            )
            return f"lrc_closes_{100 * (1 - gap):.0f}pct_of_gap"
        if name == "fig2_rank_sweep":
            fp_acc, curves = derived
            acc30 = curves[(None, 0.30)][1]
            return f"rank30_acc_within_{abs(fp_acc - acc30):.4f}_of_fp"
        if name == "latency_kernels":
            return "fused_kernel_roofline_table"
    except Exception:  # noqa: BLE001
        pass
    return "ok"


if __name__ == "__main__":
    main()
