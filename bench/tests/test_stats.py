"""End-to-end statistics: TTFT from the due time, rates and tails over the
whole window, attempted and failed counted right."""

import math

import pytest

from bench.stats import percentile, window_stats


def _stats(due, first, tokens, finished, w0=0.0, w1=10.0, cutoff=12.0):
    return window_stats(due, first, tokens, finished, w0, w1, cutoff)


def test_ttft_runs_from_the_due_time_so_a_stall_delays_every_later_one():
    # one iteration stalls from t=2 to t=5: the three requests due in the
    # stall get their first token only at 5.1, whenever they were sent
    due = {1: 1.0, 2: 2.5, 3: 3.0, 4: 4.0, 5: 6.0}
    first = {1: 1.1, 2: 5.1, 3: 5.1, 4: 5.1, 5: 6.1}
    tokens = {r: [t] for r, t in first.items()}
    st = _stats(due, first, tokens, {r: True for r in due})
    assert st["ttft_s"] == pytest.approx([0.1, 2.6, 2.1, 1.1, 0.1])
    assert st["ttft_p95_ms"] == pytest.approx(
        percentile([0.1, 2.6, 2.1, 1.1, 0.1], 95) * 1e3)


def test_rate_mean_and_tail_cover_the_whole_window():
    due = {1: 0.5, 2: 20.0}
    tokens = {1: [1.0, 2.0, 4.0, 9.0, 11.0], 2: [20.5, 21.0]}
    first = {1: 1.0, 2: 20.5}
    st = _stats(due, first, tokens, {1: True, 2: True})
    # tokens at 1, 2, 4 and 9 fall in [0, 10); gaps ending in it: 1, 2, 5
    assert st["tokens"] == 4
    assert st["output_tok_per_s"] == pytest.approx(0.4)
    assert st["itl_mean_ms"] == pytest.approx((1 + 2 + 5) / 3 * 1e3)
    assert st["itl_p95_ms"] == pytest.approx(percentile([1, 2, 5], 95) * 1e3)
    assert st["attempted"] == 1


def test_attempted_and_failed():
    due = {1: -1.0, 2: 0.0, 3: 5.0, 4: 9.9, 5: 10.0}
    first = {1: 0.5, 2: 0.5, 3: 5.5, 4: None, 5: 10.5}
    tokens = {1: [0.5], 2: [0.5], 3: [5.5], 5: [10.5]}
    finished = {1: True, 2: True, 3: False, 4: False, 5: True}
    st = _stats(due, first, tokens, finished)
    # due in [0, 10): 2, 3, 4; 3 never finished, 4 never got a token
    assert st["attempted"] == 3 and st["failed"] == 2
    # a request cut off counts with the time it waited until the cutoff
    assert st["ttft_s"] == pytest.approx([0.5, 0.5, 12.0 - 9.9])


def test_empty_window_reads_nan():
    st = _stats({}, {}, {}, {})
    assert st["attempted"] == 0 and math.isnan(st["ttft_p95_ms"])
    assert math.isnan(st["itl_mean_ms"])
