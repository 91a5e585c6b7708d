"""End-to-end numbers of one run, from the host clock: every statistic is
taken over all requests due in the window, or all tokens emitted in it."""

from __future__ import annotations

import math
from typing import Dict, List, Optional

import numpy as np


def percentile(values, q: float) -> float:
    """The q-th percentile (linear interpolation); NaN when empty."""
    values = np.asarray(values, np.float64)
    return float(np.percentile(values, q)) if values.size else math.nan


def window_stats(due: Dict[int, float], first_token: Dict[int, Optional[float]],
                 token_times: Dict[int, List[float]], finished: Dict[int, bool],
                 w0: float, w1: float, cutoff: float) -> dict:
    """``due``: rid -> due time of every request sent (an open loop's
    schedule, or the moment a closed-loop client sent it); ``first_token``:
    rid -> time of its first token or None; ``token_times``: rid -> time of
    every committed token; ``finished``: rid -> whether it ran to its end
    (or was let go by the harness after the window).
    The window is [w0, w1); ``cutoff`` is when the run stopped waiting.

    A request due in the window is attempted; it fails unless it gets its
    first token and finishes.  Its TTFT runs from its due time, so a stall
    delays every request due behind it; one that never got a first token
    counts with the time it waited until the cutoff."""
    in_window = [r for r, t in due.items() if w0 <= t < w1]
    ttft = []
    failed = 0
    for r in in_window:
        ft = first_token.get(r)
        if ft is None or not finished.get(r, False):
            failed += 1
        ttft.append((ft if ft is not None else cutoff) - due[r])
    gaps = []
    n_tokens = 0
    for times in token_times.values():
        for i, t in enumerate(times):
            if w0 <= t < w1:
                n_tokens += 1
                if i > 0:
                    gaps.append(t - times[i - 1])
    return {
        "attempted": len(in_window),
        "failed": failed,
        "ttft_s": ttft,
        "itl_s": gaps,
        "tokens": n_tokens,
        "ttft_p95_ms": percentile(ttft, 95) * 1e3,
        "itl_mean_ms": (float(np.mean(gaps)) * 1e3 if gaps else math.nan),
        "itl_p95_ms": percentile(gaps, 95) * 1e3,
        "output_tok_per_s": n_tokens / (w1 - w0),
    }
