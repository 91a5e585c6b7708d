"""The reduction of the program's own trace marks (``bench/scopes.py``) and
the four metrics that read it, on a hand-made trace with known answers and
on a recorded one: one iteration of smollm-135m on a TPU v5 lite (a
192-token prefill chunk and a 64-row decode step with its sampling), cut
from a ``--trace 1`` run of ``smollm-rag``.  In the recorded trace each run
of consecutive ops of one named scope in a step execution is merged into
one op named after its longest op, and the ops of the per-row sampling
programs are left out, to keep the file small."""

import types
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import scopes, trace, work
from bench.model import load_spec
from bench.run import load_reader

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
METRICS = ("attention_roofline.decode", "attention_roofline.prefill",
           "serve.sample_us_per_row", "serve.host_ms_per_iter")
QLINEAR = ("jit(_paged)/while/body/closed_call/qlinear/"
           "jit(fused_w4a4_lrc_kernel)/fused_w4a4_lrc_kernel/pallas_call:")
ATTENTION = "jit(_paged)/while/body/closed_call/attention/gather:"
MLP = "jit(_paged)/while/body/closed_call/mlp/mul:"
KV_MERGED = ("jit(_paged)/while/body/closed_call/kv_write/scatter;"
             "jit(_paged)/while/body/closed_call/attention/reshape:")


def _events(events):
    return " ".join(
        f"events {{ metadata_id: {mid} offset_ps: {round(s * 1e6)} "
        f"duration_ps: {round((e - s) * 1e6)}{stats} }}"
        for mid, s, e, stats in events)


def _stats(args):
    ids = {"admitted": 1, "rid": 2, "tokens": 3, "chunks": 4, "step": 5,
           "rows": 6}
    return "".join(f" stats {{ metadata_id: {ids[k]} int64_value: {v} }}"
                   for k, v in args.items())


def hand_trace(marks=True):
    """Device (us): a chunk execution of program 11 over [1, 16) (a while
    loop [1, 15) holding the kernel [2, 6), a gather [6, 9) and a copy
    [9, 15), then a fusion [15, 16)), a sampling program [17, 18), a decode
    execution of program 22 over [22, 32) (the op name of program 11's
    gather, here under ``mlp``, [22, 24); an attention op whose path is a
    reference [24, 28); a merged op [28, 31)).  Host: admission runs the
    chunk, then a prefill tick and a decode step with two sampled rows.
    ``marks=False``: the same without the op paths and ``serve.*``
    spans, as a program without these marks leaves it."""
    md = {  # metadata id: (name, program id, path stat)
        1: ("jit__paged(11)", None, ""), 2: ("jit__paged(22)", None, ""),
        3: ("jit__argmax(33)", None, ""),
        4: ("%while.1 = f32[] op", 11, 'str_value: "jit(_paged)/while:"'),
        5: ("%fused_w4a4_lrc_kernel.3 = f32[] op", 11,
            f'str_value: "{QLINEAR}"'),
        6: ("%fusion.7 = f32[] op", 11, f'str_value: "{ATTENTION}"'),
        7: ("%copy.2 = bf16[] op", 11,
            'str_value: "jit(_paged)/while/body/dynamic_slice:"'),
        8: ("%fusion.8 = f32[] op", 11, ""),
        9: ("%argmax.1 = s32[] op", None, ""),
        10: ("%fusion.7 = f32[] op", 22, f'str_value: "{MLP}"'),
        11: ("%fusion.9 = f32[] op", 22, "ref_value: 3"),
        12: ("%fusion.11 = f32[] op", 22, f'str_value: "{KV_MERGED}"'),
    }

    def meta(i):
        name, pid, path = md[i]
        st = ""
        if marks and path:
            st += f" stats {{ metadata_id: 1 {path} }}"
        if marks and pid is not None:
            st += f" stats {{ metadata_id: 2 uint64_value: {pid} }}"
        return (f'event_metadata {{ key: {i} value {{ id: {i} '
                f'name: "{name}"{st} }} }}')

    device = (
        'planes { id: 1 name: "/device:TPU:0" '
        'lines { id: 1 name: "XLA Modules" timestamp_ns: 0 '
        + _events([(1, 1, 16, ""), (3, 17, 18, ""), (2, 22, 32, "")])
        + ' } lines { id: 2 name: "XLA Ops" timestamp_ns: 0 '
        + _events([(4, 1, 15, ""), (5, 2, 6, ""), (6, 6, 9, ""),
                   (7, 9, 15, ""), (8, 15, 16, ""), (9, 17, 18, ""),
                   (10, 22, 24, ""), (11, 24, 28, ""), (12, 28, 31, "")])
        + " } " + " ".join(meta(i) for i in md)
        + ' stat_metadata { key: 1 value { id: 1 name: "tf_op" } }'
        ' stat_metadata { key: 2 value { id: 2 name: "program_id" } }'
        ' stat_metadata { key: 3 value { id: 3 name: '
        '"jit(_paged)/while/body/closed_call/attention/dot_general:" } }'
        " }\n")
    spans = [  # name, start, end, args
        ("bench.admit", 0, 16.8, {}), ("serve.admit", 0.05, 16.8,
                                       {"admitted": 1}),
        ("serve.prefill.chunk", 0.1, 16.7, {"rid": 5, "tokens": 3}),
        ("bench.step.chunk", 0.2, 0.4, {}),
        ("serve.prefill.wait", 0.4, 16.1, {}),
        ("bench.prefill_tick", 16.8, 16.9, {}),
        ("serve.prefill", 16.82, 16.88, {"chunks": 0}),
        ("bench.decode_tick", 16.9, 45, {}),
        ("serve.decode", 17, 44, {"step": 7, "rows": 2}),
        ("serve.decode.prepare", 17, 17.5, {}),
        ("bench.step.decode", 17.6, 17.8, {}),
        ("serve.decode.wait", 17.8, 31.5, {}),
        ("serve.sample", 31.5, 43, {"rows": 2}),
        ("bench.sample", 32, 35, {}), ("bench.sample", 36, 39, {}),
        ("serve.commit", 43, 43.8, {"tokens": 2}),
    ]
    spans = [s for s in spans if marks or not s[0].startswith("serve.")]
    names = {n: i for i, n in enumerate(dict.fromkeys(n for n, *_ in spans),
                                        1)}
    host = (
        'planes { id: 2 name: "/host:CPU" '
        'lines { id: 1 name: "python3" timestamp_ns: 0 '
        + _events([(names[n], s, e, _stats(a)) for n, s, e, a in spans])
        + " } " + " ".join(
            f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
            for n, i in names.items())
        + "".join(f' stat_metadata {{ key: {i} value {{ id: {i} name: '
                  f'"{k}" }} }}' for k, i in (("admitted", 1), ("rid", 2),
                                              ("tokens", 3), ("chunks", 4),
                                              ("step", 5), ("rows", 6)))
        + " }\n")
    session = ('planes { id: 9 name: "Task Environment" '
               'stats { metadata_id: 1 uint64_value: 1000 } '
               'stats { metadata_id: 2 uint64_value: 51000 } '
               'stat_metadata { key: 1 value { id: 1 name: '
               '"profile_start_time" } } '
               'stat_metadata { key: 2 value { id: 2 name: '
               '"profile_stop_time" } } }\n')
    return jax.profiler.ProfileData.text_proto_to_serialized_xspace(
        device + host + session)


# the harness's log of the two step calls: a 3-token chunk at position 0
# and a decode step with one real row at position 5
CALLS = [("chunk", 0.1, 0.2, np.array([[0, 1, 2, 3]]),
          np.array([[1, 1, 1, 0]], bool)),
         ("decode", 0.3, 0.4, np.array([[5], [7]]), np.array([[1], [0]],
                                                             bool))]


def _run(tmp_path, data, calls=CALLS, cell_spec="smollm-135m"):
    (tmp_path / "run.xplane.pb").write_bytes(data)
    return types.SimpleNamespace(
        trace_dir=tmp_path, trace_window=(0.0, 1.0),
        spec=load_spec(cell_spec), peak=work.peaks("TPU v5 lite"),
        stepper=types.SimpleNamespace(calls=calls))


def _summary(data, calls=CALLS):
    pd = jax.profiler.ProfileData.from_serialized_xspace(data)
    return scopes.summarize(trace.Trace(pd),
                            scopes.Marks(pd, scopes.op_paths(data)),
                            load_spec("smollm-135m"),
                            work.peaks("TPU v5 lite"), calls)


@pytest.mark.parametrize("path,scope", [
    (QLINEAR, "qlinear"), (ATTENTION, "attention"), (MLP, "mlp"),
    (KV_MERGED, "kv_write"),
    ("jit(_paged)/while/body/closed_call/mlp/qlinear/reshape:", "qlinear"),
    ("jit(_paged)/norm/rsqrt:", "norm"),
    ("jit(_paged)/unembed/dot_general:", "unembed"),
    ("jit(_paged)/while/body/dynamic_update_slice:", None), ("", None)])
def test_an_op_belongs_to_its_innermost_scope(path, scope):
    assert scopes.scope_of(path) == scope


def test_op_paths_are_read_from_the_op_metadata():
    paths = scopes.op_paths(hand_trace())
    assert paths == {
        (11, "%while.1 = f32[] op"): "jit(_paged)/while:",
        (11, "%fused_w4a4_lrc_kernel.3 = f32[] op"): QLINEAR,
        (11, "%fusion.7 = f32[] op"): ATTENTION,
        (11, "%copy.2 = bf16[] op"): "jit(_paged)/while/body/dynamic_slice:",
        (22, "%fusion.7 = f32[] op"): MLP,
        # a path stored as a reference to a stat name
        (22, "%fusion.9 = f32[] op"):
            "jit(_paged)/while/body/closed_call/attention/dot_general:",
        (22, "%fusion.11 = f32[] op"): KV_MERGED,
    }
    assert scopes.op_paths(hand_trace(marks=False)) == {}


def test_device_time_by_scope_and_idle_gap_owners():
    out = _summary(hand_trace())
    ch, de = out["calls"]["chunk"], out["calls"]["decode"]
    us = lambda d: {k: v * 1e6 for k, v in d.items()}
    assert ch["n"] == de["n"] == 1
    # the while loop is not counted beside its body
    assert us(ch["by_scope"]) == pytest.approx(
        {"qlinear": 4, "attention": 3, "unscoped": 7})
    assert us(ch["unscoped_ops"]) == pytest.approx({"copy": 6, "fusion": 1})
    # one op name, two programs, two scopes
    assert us(de["by_scope"]) == pytest.approx(
        {"mlp": 2, "attention": 4, "kv_write": 3})
    assert ch["attention_s"] == pytest.approx(3e-6)
    assert de["attention_s"] == pytest.approx(4e-6)
    idle = {(b, s): t * 1e6 for b, s, t in out["idle"]}
    # gaps [0, 1), [16, 17), [18, 22), [31, 50) and their middles
    assert idle == pytest.approx({
        ("admit", "serve.prefill.wait"): 1,
        ("admit", "serve.prefill.chunk"): 1,
        ("decode_tick", "serve.decode.wait"): 4,
        ("decode_tick", "serve.sample"): 19})


def test_host_self_times_leave_out_the_waits():
    out = _summary(hand_trace())
    spans = {n: v["self_s"] * 1e6 for n, v in out["spans"].items()}
    assert spans["serve.admit"] == pytest.approx(16.75 - 15.7)
    assert spans["serve.prefill.chunk"] == pytest.approx(16.6 - 15.7)
    assert spans["serve.decode"] == pytest.approx(27 - 13.7)
    assert spans["serve.decode.wait"] == pytest.approx(13.7)
    assert out["decode_iterations"] == 1
    assert out["host_self_s"] * 1e6 == pytest.approx(
        (16.75 - 15.7) + 0.06 + (27 - 13.7))
    assert out["sample_s"] * 1e6 == pytest.approx(11.5)
    assert out["sampled_rows"] == 2


def test_each_metric_on_the_hand_made_trace(tmp_path):
    run = _run(tmp_path, hand_trace())
    got = {m: load_reader(m, ROOT)(run) for m in METRICS}
    spec, peak = run.spec, run.peak
    per_layer = spec.layers

    def share(ops, kv_tokens, q_tokens, device_s):
        flops = 4 * spec.heads * spec.head_dim * ops * per_layer
        nbytes = 2 * (2 * spec.kv_heads * spec.head_dim * kv_tokens
                      + 2 * spec.heads * spec.head_dim * q_tokens) * per_layer
        least = max(flops / peak["bf16_flops_per_s"],
                    nbytes / peak["hbm_bytes_per_s"])
        return 100 * least / device_s

    # decode: one row at position 5 attends to 6 keys; chunk: tokens at
    # 0, 1, 2 attend to 1 + 2 + 3 keys over a 3-token context
    assert got["attention_roofline.decode"] == pytest.approx(
        share(6, 6, 1, 4e-6))
    assert got["attention_roofline.prefill"] == pytest.approx(
        share(6, 3, 3, 3e-6))
    assert got["serve.sample_us_per_row"] == pytest.approx(11.5 / 2)
    assert got["serve.host_ms_per_iter"] == pytest.approx(
        ((16.75 - 15.7) + 0.06 + (27 - 13.7)) * 1e-3)
    # the summary is reduced once and kept on the run
    assert run.scopes is scopes.of_run(run)


def test_no_marks_no_metric(tmp_path):
    run = _run(tmp_path, hand_trace(marks=False))
    assert {m: load_reader(m, ROOT)(run) for m in METRICS} == dict.fromkeys(
        METRICS)
    untraced = types.SimpleNamespace()
    assert {m: load_reader(m, ROOT)(untraced) for m in METRICS} == \
        dict.fromkeys(METRICS)


# the recorded iteration's two step calls: a chunk of 192 tokens at
# position 512, and 64 decode rows at these positions
RECORDED_DECODE = [
    478, 422, 572, 340, 435, 703, 734, 706, 459, 705, 734, 734, 734, 712,
    407, 689, 455, 734, 623, 704, 644, 541, 734, 651, 525, 617, 708, 734,
    383, 666, 524, 426, 734, 734, 488, 612, 734, 681, 601, 501, 734, 713,
    507, 734, 734, 734, 709, 608, 681, 713, 441, 533, 690, 697, 557, 579,
    734, 527, 734, 711, 711, 730, 718, 622]
RECORDED_CALLS = [
    ("chunk", 0.1, 0.2, 512 + np.arange(512)[None],
     np.arange(512)[None] < 192),
    ("decode", 0.3, 0.4, np.asarray(RECORDED_DECODE)[:, None],
     np.ones((64, 1), bool))]


def test_recorded_trace(tmp_path):
    data = (DATA / "smollm_scopes.xplane.pb").read_bytes()
    out = _summary(data, RECORDED_CALLS)
    ch, de = out["calls"]["chunk"], out["calls"]["decode"]
    # the recorded steps: a 29.3 ms chunk and a 34.5 ms decode step, every
    # scope in both; the pool's copy leads what no scope holds
    assert ch["device_s"] == pytest.approx(29.29e-3, rel=1e-3)
    assert de["device_s"] == pytest.approx(34.49e-3, rel=1e-3)
    for c in (ch, de):
        assert set(c["by_scope"]) == set(scopes.SCOPES) | {scopes.UNSCOPED}
        assert sum(c["by_scope"].values()) <= c["device_s"]
        assert next(iter(c["unscoped_ops"])) == "copy"
        assert 0 < c["attention_least_s"] < c["attention_s"]
    # decode attention gathers 64 rows' full block tables: 15.7 ms
    assert de["attention_s"] == pytest.approx(15.68e-3, rel=1e-3)
    idle = {(b, s): t for b, s, t in out["idle"]}
    assert idle[("sample", "serve.sample")] > 0.4
    assert out["decode_iterations"] == 1 and out["sampled_rows"] == 64

    run = _run(tmp_path, data, RECORDED_CALLS)
    got = {m: load_reader(m, ROOT)(run) for m in METRICS}
    assert got["attention_roofline.decode"] == pytest.approx(7.25, abs=0.01)
    assert got["attention_roofline.prefill"] == pytest.approx(5.41,
                                                              abs=0.01)
    assert got["serve.sample_us_per_row"] == pytest.approx(7561.3, abs=0.1)
    assert got["serve.host_ms_per_iter"] == pytest.approx(496.1, abs=0.1)
