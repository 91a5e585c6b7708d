"""The work an algorithm requires, counted at logical shapes: real tokens
(no padded rows), unpadded K and R, and every operand at the dtype it is
stored in (int4 W, f32 scales, bf16 U/V, activations, KV and weights).
What an implementation adds (padding, f32 copies of U/V) is not counted,
so removing it raises the shares computed from these counts.

Operations are split by the unit they need at peak: int8 MXU operations
(the W4A4 GEMM) and bf16 MXU operations (the low-rank term, attention, the
unembedding).  This module counts one W4A4+LRC linear and holds the peak
table; each family counts a whole step of its own
(``bench/reference/<family>.py``, ``step_work``).
"""

from __future__ import annotations

import json
from typing import Tuple

from bench.model import BENCH_DIR

BF16, F32 = 2, 4


def peaks(device_kind: str, data=BENCH_DIR) -> dict:
    """The peak table row of ``device_kind``; a device not in the table is
    an error."""
    table = json.loads((data / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def qlinear(m: int, k: int, n: int, r: int) -> dict:
    """One W4A4+LRC linear on ``m`` tokens."""
    return {
        "int8_ops": 2 * m * k * n,
        "float_ops": 2 * m * k * r + 2 * m * r * n,
        "bytes": (k * n // 2 + F32 * n + BF16 * (k + n) * r
                  + BF16 * m * k + BF16 * m * n),
    }


def add(*works: dict) -> dict:
    out = {"int8_ops": 0, "float_ops": 0, "bytes": 0}
    for w in works:
        for key in out:
            out[key] += w[key]
    return out


def least_seconds(work: dict, peak: dict) -> Tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    compute = (work["int8_ops"] / peak["int8_ops_per_s"]
               + work["float_ops"] / peak["bf16_flops_per_s"])
    memory = work["bytes"] / peak["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")


def compute_seconds(work: dict, peak: dict) -> float:
    """The operations' time at each unit's peak (the numerator of mfu)."""
    return (work["int8_ops"] / peak["int8_ops_per_s"]
            + work["float_ops"] / peak["bf16_flops_per_s"])
