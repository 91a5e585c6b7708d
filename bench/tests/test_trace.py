"""The trace reduction, on a hand-made trace with known answers and on a
small recorded one: two steps of smollm-135m on a TPU v5 lite (one prefill
chunk, one 64-slot decode step), cut from a profiler trace of the serving
engine, with runs of non-kernel ops merged to keep the file small."""

from pathlib import Path

import jax
import numpy as np
import pytest

from bench import trace, work
from bench.model import load_spec
from bench.reference import dense

DATA = Path(__file__).resolve().parent / "data"


def _plane(pid, name, lines):
    meta, body = {}, []
    for lid, (lname, events) in enumerate(lines, 1):
        evs = ""
        for ev_name, start_us, dur_us in events:
            mid = meta.setdefault(ev_name, len(meta) + 1)
            evs += (f" events {{ metadata_id: {mid} offset_ps: "
                    f"{int(start_us * 1e6)} duration_ps: "
                    f"{int(dur_us * 1e6)} }}")
        body.append(f"lines {{ id: {lid} name: \"{lname}\" timestamp_ns: 0"
                    f"{evs} }}")
    md = "".join(f"event_metadata {{ key: {i} value {{ id: {i} name: "
                 f"\"{n}\" }} }} " for n, i in meta.items())
    return (f"planes {{ id: {pid} name: \"{name}\" " + " ".join(body)
            + " " + md + "}\n")


def _session(us):
    return ('planes { id: 9 name: "Task Environment" '
            'stats { metadata_id: 1 uint64_value: 1000 } '
            f'stats {{ metadata_id: 2 uint64_value: {1000 + us * 1000} }} '
            'stat_metadata { key: 1 value { id: 1 name: '
            '"profile_start_time" } } '
            'stat_metadata { key: 2 value { id: 2 name: '
            '"profile_stop_time" } } }\n')


# device: a chunk execution [0, 16) us with a kernel [2, 6) and a fusion
# [5, 15) that overlaps it; a decode execution [30, 40) with a kernel
# [31, 33) and a copy [33, 39); host: the chunk call [0, 3), sampling from
# 16 to 29, the decode call, and nothing after 41 up to the session's end
# at 50
HAND = (
    _plane(1, "/device:TPU:0", [
        ("XLA Modules", [("jit__paged(1)", 0, 16), ("jit__paged(2)", 30, 10)]),
        ("XLA Ops", [("%fused_w4a4_lrc_kernel.3 = f32[] op", 2, 4),
                     ("%fusion.7 = f32[] op", 5, 10),
                     ("%fused_w4a4_lrc_kernel.3 = f32[] op", 31, 2),
                     ("%copy.2 = bf16[] op", 33, 6)])]),
    _plane(2, "/host:CPU", [("python", [
        ("bench.step.chunk", 0, 3), ("bench.sample", 16, 13),
        ("bench.decode_tick", 29, 12), ("bench.step.decode", 29.5, 0.5)])]),
    _session(50))


def _hand():
    return trace.Trace(jax.profiler.ProfileData.from_text_proto(
        "".join(HAND)))


def test_busy_is_the_union_of_op_intervals():
    tr = _hand()
    assert tr.window_s == pytest.approx(50e-6)
    busy = sum(e - s for s, e in trace.merged([(s, e) for s, e, _
                                               in tr.ops]))
    # [2, 15) and [31, 39): 13 + 8 us, the overlap counted once
    assert busy == pytest.approx(21e-6)


def test_busy_is_averaged_over_the_chips_in_the_trace():
    second = _plane(3, "/device:TPU:1", [
        ("XLA Ops", [("%fusion.1 = f32[] op", 0, 5)])])
    tr = trace.Trace(jax.profiler.ProfileData.from_text_proto(
        "".join(HAND) + second))
    assert len(tr.ops_by_device) == 2
    spec = load_spec("smollm-135m")
    calls = [("chunk", 0, 3e-6, np.zeros((1, 64), np.int32),
              np.arange(64)[None] < 2),
             ("decode", 29.5e-6, 30e-6, np.full((64, 1), 5, np.int32),
              np.arange(64)[:, None] < 1)]
    out = trace.summarize(tr, spec, work.peaks("TPU v5 lite"), calls)
    # 21 us on the first chip, 5 us on the second
    assert out["busy_s"] == pytest.approx(13e-6)


def test_steps_are_told_apart_and_matched_to_their_calls():
    steps = trace.classify_steps(_hand())
    assert [(i, k) for _, _, i, k in steps] == [(0, "chunk"), (1, "decode")]


def test_summary_on_the_hand_made_trace():
    spec = load_spec("smollm-135m")
    peak = work.peaks("TPU v5 lite")
    calls = [("chunk", 0, 1, np.array([[0] * 4]), np.array([[1, 1, 0, 0]])),
             ("decode", 2, 3, np.array([[5], [7]]), np.array([[1], [0]]))]
    out = trace.summarize(_hand(), spec, peak, calls)
    assert out["busy_s"] == pytest.approx(21e-6)
    ch, de = out["calls"]["chunk"], out["calls"]["decode"]
    assert ch["n"] == de["n"] == 1
    assert ch["device_s"] == pytest.approx(16e-6)
    assert de["device_s"] == pytest.approx(10e-6)
    assert ch["kernel_s"] == pytest.approx(4e-6)
    assert de["kernel_s"] == pytest.approx(2e-6)
    # the chunk had two real tokens at positions 0 and 1, the decode step
    # one real row at position 5
    want = work.compute_seconds(dense.step_work(spec, [(0, 2)], 1), peak)
    assert ch["compute_s"] == pytest.approx(want)
    want = work.compute_seconds(dense.step_work(spec, [(5, 1)], 1), peak)
    assert de["compute_s"] == pytest.approx(want)
    idle = dict(out["breakdown"]["idle_gaps"])
    # gaps: [0, 2) in the chunk call, [15, 31) around the middle of
    # sampling (23), [39, 50) with its middle (44.5) past every span
    assert idle["step.chunk"] == pytest.approx(2e-6)
    assert idle["sample"] == pytest.approx(16e-6)
    assert idle["outside any span"] == pytest.approx(11e-6)
    ops = dict(out["breakdown"]["device_ops"])
    assert ops == pytest.approx({"fusion": 10e-6, "copy": 6e-6,
                                 "fused_w4a4_lrc_kernel": 6e-6})


def test_recorded_trace():
    text = (DATA / "smollm_steps.xspace.pbtxt").read_text()
    tr = trace.Trace(jax.profiler.ProfileData.from_text_proto(text))
    steps = trace.classify_steps(tr)
    assert [k for _, _, _, k in steps] == ["chunk", "decode"]
    spec = load_spec("smollm-135m")
    calls = [("chunk", 0, 1, np.arange(512)[None], np.ones((1, 512), bool)),
             ("decode", 2, 3, np.full((64, 1), 40), np.ones((64, 1), bool))]
    out = trace.summarize(tr, spec, work.peaks("TPU v5 lite"), calls)
    ch, de = out["calls"]["chunk"], out["calls"]["decode"]
    # 7 linears x 30 layers, each one fused kernel call
    assert sum(ch["bound"].values()) == sum(de["bound"].values()) == 210
    assert 0 < ch["kernel_s"] < ch["device_s"] < out["window_s"]
    assert 0 < de["kernel_s"] < de["device_s"]
    assert 0 < out["busy_s"] < out["window_s"]
    # the recorded steps: a 48.9 ms chunk and an 84.0 ms decode step
    assert ch["device_s"] == pytest.approx(48.88e-3, rel=1e-3)
    assert de["device_s"] == pytest.approx(84.02e-3, rel=1e-3)
    # roofline shares stay at or under 100%
    assert 0 < de["kernel_least_s"] <= de["kernel_s"]
    assert 0 < ch["kernel_least_s"] <= ch["kernel_s"]
    idle = dict(out["breakdown"]["idle_gaps"])
    assert "sample" in idle
