"""Reduction of a profiler trace of the window to the per-layer metrics'
inputs.  Only JAX is needed to read the trace.

- Busy time is the union of the intervals of a device's ``XLA Ops``
  events, averaged over the devices in the trace; the traced window is
  the profiling session.
- The n-th execution of the step program (``XLA Modules`` events named
  ``jit__paged(...)``) belongs to the n-th step call of the harness (the
  ``bench.step.<kind>`` host spans), so decode calls and prefill chunks
  are told apart, and the call's rows give its work.
- Kernel time is the sum of the ``fused_w4a4_lrc_kernel`` ops inside those
  executions.  Steps, kernels and the breakdown are read on the first
  device.
- An idle gap belongs to the innermost ``bench.*`` host span around its
  middle: what the host was doing while the device waited.
"""

from __future__ import annotations

import bisect
import collections
import re
from pathlib import Path

import numpy as np

from bench import work as work_lib
from bench.adapter import SPAN_PREFIX
from bench.model import family

STEP_MODULE = "jit__paged("
KERNEL = "fused_w4a4_lrc_kernel"
CONTAINERS = {"while", "conditional", "call"}
TOP = 10


def load(trace_dir):
    import jax

    files = sorted(Path(trace_dir).glob("**/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    return jax.profiler.ProfileData.from_file(str(files[-1]))


def op_name(event_name: str) -> str:
    """'%fusion.12 = f32[...] fusion(...)' -> 'fusion'."""
    head = event_name.split(" = ")[0].lstrip("%")
    return re.sub(r"\.\d+(\..*)?$", "", head)


def merged(intervals):
    """The union of (start, end) intervals, as disjoint sorted intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class Trace:
    """The events the reduction reads, times in seconds from session
    start."""

    def __init__(self, pd, device: str = "/device:TPU:0"):
        planes = {p.name: p for p in pd.planes}
        if device not in planes:
            raise ValueError(f"the trace has no {device} plane")
        ev = lambda e: (e.start_ns * 1e-9, (e.start_ns + e.duration_ns)
                        * 1e-9, e.name)

        def line(plane, name):
            lines = {l.name: l for l in plane.lines}
            return [ev(e) for e in lines[name].events] if name in lines \
                else []

        prefix = device.rpartition(":")[0] + ":"
        self.ops_by_device = [
            [o for o in line(p, "XLA Ops") if o[1] > o[0]]
            for n, p in sorted(planes.items()) if n.startswith(prefix)
            and n[len(prefix):].isdigit()]
        self.ops = [o for o in line(planes[device], "XLA Ops") if o[1] > o[0]]
        self.modules = line(planes[device], "XLA Modules")
        self.spans = sorted(ev(e) for line in planes["/host:CPU"].lines
                            for e in line.events
                            if e.name.startswith(SPAN_PREFIX))
        env = dict(planes["Task Environment"].stats) \
            if "Task Environment" in planes else {}
        start, stop = env.get("profile_start_time"), env.get(
            "profile_stop_time")
        self.window_s = ((int(stop) - int(start)) * 1e-9 if start and stop
                         else max(e for _, e, _ in self.ops + self.spans))


def classify_steps(tr: Trace):
    """[(start, end, index of its step call, kind)]: each execution of the
    step program with the step call that issued it.  The host and device
    clocks of a trace can disagree by a millisecond, so executions are
    matched to calls by order: the harness starts and stops tracing
    between iterations, and every call is waited on before the next, so
    the n-th execution in the trace is the n-th call."""
    calls = [name[len(SPAN_PREFIX) + len("step."):] for _, _, name
             in tr.spans if name.startswith(SPAN_PREFIX + "step.")]
    mods = sorted((s, e) for s, e, name in tr.modules
                  if name.startswith(STEP_MODULE))
    if len(mods) != len(calls):
        raise ValueError(f"{len(mods)} step executions in the trace but "
                         f"{len(calls)} step calls")
    return [(s, e, i, calls[i]) for i, (s, e) in enumerate(mods)]


def call_rows(call):
    """(rows, sampled) of one logged step call: (first position, new
    tokens) of each real row, and the rows that reach the unembedding."""
    kind, _, _, positions, valid = call
    pos, val = np.asarray(positions), np.asarray(valid)
    if kind == "chunk":
        n = int(val[0].sum())
        return [(int(pos[0, 0]), n)], 1
    rows = [(int(pos[b, 0]), 1) for b in range(val.shape[0]) if val[b, 0]]
    return rows, len(rows)


def reduce(run, drv) -> dict:
    t0, t1 = run.trace_window
    return summarize(Trace(load(run.trace_dir)), run.spec, run.peak,
                     [c for c in drv.calls if t0 <= c[1] <= t1])


def summarize(tr: Trace, spec, peak, traced) -> dict:
    """``traced``: the harness's step calls made while tracing, in order
    (``Stepper.calls`` entries)."""
    fam = family(spec.reference)
    steps = classify_steps(tr)
    ops_sorted = sorted(tr.ops)
    op_starts = [s for s, _, _ in ops_sorted]
    calls = {k: {"n": 0, "device_s": 0.0, "kernel_s": 0.0, "compute_s": 0.0,
                 "kernel_least_s": 0.0, "bound": collections.Counter()}
             for k in ("decode", "chunk")}
    for s, e, i, kind in steps:
        if i >= len(traced) or traced[i][0] != kind:
            continue
        c = calls[kind]
        c["n"] += 1
        c["device_s"] += e - s
        j = bisect.bisect_left(op_starts, s)
        while j < len(ops_sorted) and ops_sorted[j][0] < e:
            if op_name(ops_sorted[j][2]) == KERNEL:
                c["kernel_s"] += ops_sorted[j][1] - ops_sorted[j][0]
            j += 1
        rows, sampled = call_rows(traced[i])
        c["compute_s"] += work_lib.compute_seconds(
            fam.step_work(spec, rows, sampled), peak)
        m = sum(n for _, n in rows)
        for name in fam.LINEARS:
            least, bound = work_lib.least_seconds(
                work_lib.qlinear(m, *spec.shape(name)), peak)
            c["kernel_least_s"] += least * spec.layers
            c["bound"][bound] += spec.layers
    for c in calls.values():
        c["bound"] = dict(c["bound"])

    busy = merged([(s, e) for s, e, _ in tr.ops])
    busy_s = float(np.mean([sum(e - s for s, e in merged(
        [(s, e) for s, e, _ in ops])) for ops in tr.ops_by_device]))
    # time by op name; loop containers (while) would count their body twice
    self_time = collections.Counter()
    for s, e, name in tr.ops:
        base = op_name(name)
        if base not in CONTAINERS:
            self_time[base] += e - s
    idle = collections.Counter()
    bounds = [(0.0, 0.0)] + [tuple(b) for b in busy] + [(tr.window_s,) * 2]
    span_starts = [s for s, _, _ in tr.spans]
    for (_, g0), (g1, _) in zip(bounds, bounds[1:]):
        if g1 <= g0:
            continue
        mid = (g0 + g1) / 2
        owner = "outside any span"
        for k in range(bisect.bisect_right(span_starts, mid) - 1, -1, -1):
            s, e, name = tr.spans[k]
            if s <= mid < e:
                owner = name[len(SPAN_PREFIX):]
                break
        idle[owner] += g1 - g0
    return {
        "window_s": tr.window_s,
        "busy_s": busy_s,
        "calls": calls,
        "breakdown": {
            "device_ops": [[n, t] for n, t in self_time.most_common(TOP)],
            "idle_gaps": [[n, t] for n, t in idle.most_common(TOP)],
        },
    }
