"""What the program's own trace marks say, reduced from a ``--trace 1`` run.

The program names two things in the profiler's trace (``src/repro``):

- host spans ``serve.*`` of ``ServeEngine`` (``serve.admit``,
  ``serve.prefill``, ``serve.prefill.chunk``, ``serve.prefill.wait``,
  ``serve.decode``, ``serve.decode.prepare``, ``serve.decode.wait``,
  ``serve.sample``, ``serve.commit``), with their arguments (``rid``,
  ``step``, ``rows``, ``tokens``, ...) as stats of the event;
- ``jax.named_scope``s in the step program (``attention``, ``kv_write``,
  ``mlp``, ``norm``, ``unembed``, and ``qlinear`` around every W4A4+LRC
  linear), which reach each device op's op_name path: on a TPU, the
  ``tf_op`` stat of the op's event metadata.

From them this module computes, once per run:

- the device time of the step program's decode and chunk calls by the
  innermost scope of each op, and the unscoped remainder;
- the owner of every idle gap of the device: the innermost ``serve.*`` span
  around its middle, beside the ``bench.*`` span that ``bench/trace.py``
  gives it;
- the inputs of the per-layer metrics that read these marks:
  ``attention_roofline.decode`` and ``.prefill``,
  ``serve.sample_us_per_row`` and ``serve.host_ms_per_iter``.

A trace of a program without these marks has no scoped op and no span,
and each of those metrics then reads ``None``.

    python3 bench/scopes.py <trace_dir>

prints both tables for the newest trace under ``trace_dir``.  Without the
harness's log of step calls it gives no least time.
"""

from __future__ import annotations

import bisect
import collections
import sys
from pathlib import Path

if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import trace as trace_lib  # noqa: E402
from bench import work as work_lib  # noqa: E402

SCOPES = ("attention", "kv_write", "mlp", "norm", "unembed", "qlinear")
UNSCOPED = "unscoped"
SPAN_PREFIX = "serve."
WAIT_SUFFIX = ".wait"
# the spans whose host self time is the host's part of an iteration
HOST_SPANS = ("serve.admit", "serve.prefill", "serve.decode")
# the stat of a device op's metadata that holds its op_name path
OP_PATH_STAT = "tf_op"
DEVICE = "/device:TPU:0"


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of a serialized protobuf
    message: an int for a varint, bytes for the other wire types."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            val, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            val, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"unknown protobuf wire type {wire}")
        yield num, val


def _text(buf) -> str:
    return bytes(buf).decode()


def op_paths(xspace: bytes, device: str = DEVICE) -> dict:
    """{(program id, op event name): op_name path} of the device's ops, from
    the ``tf_op`` and ``program_id`` stats of each op's event metadata in a
    serialized XSpace.  ``jax.profiler.ProfileData`` gives an event's own
    stats but not its metadata's, where the TPU profiler keeps both.  Field
    numbers are those of tsl/profiler/protobuf/xplane.proto: XSpace.planes
    1; XPlane.name 2, event_metadata 4, stat_metadata 5 (map entries: key
    1, value 2); XEventMetadata.name 2, stats 5; XStat.metadata_id 1,
    uint64 3, int64 4, str 5, ref 7; XStatMetadata.name 2."""
    for num, plane in _fields(memoryview(xspace)):
        if num != 1:
            continue
        fields = list(_fields(plane))
        if not any(n == 2 and _text(v) == device for n, v in fields):
            continue
        stat_names = {}
        for n, entry in fields:
            if n == 5:
                e = dict(_fields(entry))
                stat_names[e.get(1, 0)] = _text(
                    dict(_fields(e.get(2, b""))).get(2, b""))
        out = {}
        for n, entry in fields:
            if n != 4:
                continue
            name, stats = "", {}
            for f, v in _fields(dict(_fields(entry)).get(2, b"")):
                if f == 2:
                    name = _text(v)
                elif f == 5:
                    st = dict(_fields(v))
                    key = stat_names.get(st.get(1))
                    if key not in (OP_PATH_STAT, "program_id"):
                        continue
                    stats[key] = (stat_names.get(st[7]) if 7 in st
                                  else _text(st[5]) if 5 in st
                                  else st.get(3, st.get(4)))
            if OP_PATH_STAT in stats and "program_id" in stats:
                out[(int(stats["program_id"]), name)] = stats[OP_PATH_STAT]
        return out
    return {}


def scope_of(path: str):
    """The innermost of ``SCOPES`` in an op_name path, or None.  XLA joins
    the paths of ops it merged with ``;``: the first is read."""
    for part in reversed(path.split(";")[0].split("/")):
        if part in SCOPES:
            return part
    return None


class Marks:
    """The device ops with their scope and the ``serve.*`` host spans, in
    the times of ``bench.trace.Trace``.  An op's scope is looked up under
    the program whose execution holds it: two programs may hold ops of
    one name."""

    def __init__(self, pd, paths: dict, device: str = DEVICE):
        sec = lambda e: (e.start_ns * 1e-9, (e.start_ns + e.duration_ns)
                         * 1e-9)
        dev = pd.find_plane_with_name(device)
        lines = {l.name: l for l in dev.lines} if dev is not None else {}
        # (start, end, program id) of every program execution, from its
        # event's name, "<module>(<program id>)"
        mods = sorted((*sec(e), e.name.rpartition("(")[2][:-1])
                      for e in getattr(lines.get("XLA Modules"), "events",
                                       ()))
        mod_starts = [m[0] for m in mods]
        self.ops = []   # (start, end, op name, scope or None)
        for e in getattr(lines.get("XLA Ops"), "events", ()):
            if e.duration_ns <= 0:
                continue
            s, t = sec(e)
            k = bisect.bisect_right(mod_starts, s) - 1
            pid = mods[k][2] if k >= 0 and s < mods[k][1] else ""
            path = paths.get((int(pid), e.name), "") if pid.isdigit() \
                else ""
            self.ops.append((s, t, trace_lib.op_name(e.name),
                             scope_of(path)))
        self.ops.sort()
        host = pd.find_plane_with_name("/host:CPU")
        self.spans = sorted(   # (start, end, name, args)
            (*sec(e), e.name, dict(e.stats))
            for line in (host.lines if host is not None else ())
            for e in line.events if e.name.startswith(SPAN_PREFIX))


def load(trace_dir):
    """(ProfileData, op paths) of the newest trace under ``trace_dir``."""
    import jax

    files = sorted(Path(trace_dir).glob("**/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    data = files[-1].read_bytes()
    return (jax.profiler.ProfileData.from_serialized_xspace(data),
            op_paths(data))


def attention_work(spec, rows) -> dict:
    """The work attention requires in one step call, all layers: each real
    row's queries against its real context (a token at position p attends
    to p + 1 keys), K and V of that context read once in bf16 at the KV
    heads, and the queries read and the outputs written in bf16."""
    ops = kv = q = 0
    for first, n in rows:
        ops += 4 * spec.heads * spec.head_dim * (n * first + n * (n + 1) // 2)
        kv += 2 * spec.kv_heads * spec.head_dim * (first + n)
        q += 2 * spec.heads * spec.head_dim * n
    return {"int8_ops": 0, "float_ops": ops * spec.layers,
            "bytes": work_lib.BF16 * (kv + q) * spec.layers}


def self_times(spans):
    """{name: [(host self time, args)]} of ``spans``: each span's duration
    less the ``*.wait`` spans nested in it, in seconds."""
    waits = [(s, e) for s, e, n, _ in spans if n.endswith(WAIT_SUFFIX)]
    starts = [s for s, _ in waits]
    out = collections.defaultdict(list)
    for s, e, name, args in spans:
        inner = 0.0
        if not name.endswith(WAIT_SUFFIX):
            j = bisect.bisect_left(starts, s)
            while j < len(waits) and waits[j][0] < e:
                inner += min(waits[j][1], e) - waits[j][0]
                j += 1
        out[name].append((e - s - inner, args))
    return out


def innermost(spans, starts, t):
    """The name of the innermost of ``spans`` (sorted by start, nested)
    that holds time ``t``, or None."""
    for k in range(bisect.bisect_right(starts, t) - 1, -1, -1):
        s, e, name = spans[k][:3]
        if s <= t < e:
            return name
    return None


def summarize(tr, marks, spec=None, peak=None, traced=None) -> dict:
    """``tr``: the run's ``bench.trace.Trace``; ``marks``: its ``Marks``;
    ``traced``: the harness's step calls made while tracing, in order
    (``Stepper.calls`` entries), which give each call's rows and so its
    least time.  Without them the calls' kinds come from the
    ``bench.step.*`` spans alone."""
    calls = {k: {"n": 0, "device_s": 0.0, "by_scope": collections.Counter(),
                 "unscoped_ops": collections.Counter(),
                 "attention_least_s": 0.0}
             for k in ("decode", "chunk")}
    starts = [o[0] for o in marks.ops]
    for s, e, i, kind in trace_lib.classify_steps(tr):
        if traced is not None and (i >= len(traced)
                                   or traced[i][0] != kind):
            continue
        c = calls[kind]
        c["n"] += 1
        c["device_s"] += e - s
        j = bisect.bisect_left(starts, s)
        while j < len(marks.ops) and marks.ops[j][0] < e:
            o0, o1, name, scope = marks.ops[j]
            j += 1
            if name in trace_lib.CONTAINERS:
                continue   # a loop's time is its body's ops'
            c["by_scope"][scope or UNSCOPED] += o1 - o0
            if scope is None:
                c["unscoped_ops"][name] += o1 - o0
        if traced is not None:
            rows, _ = trace_lib.call_rows(traced[i])
            c["attention_least_s"] += work_lib.least_seconds(
                attention_work(spec, rows), peak)[0]
    for c in calls.values():
        c["attention_s"] = c["by_scope"].get("attention", 0.0)
        c["by_scope"] = dict(c["by_scope"].most_common())
        c["unscoped_ops"] = dict(c["unscoped_ops"].most_common(
            trace_lib.TOP))

    # idle gaps of the first device, as bench/trace.py finds them
    busy = trace_lib.merged([(s, e) for s, e, _ in tr.ops])
    bounds = [(0.0, 0.0)] + [tuple(b) for b in busy] + [(tr.window_s,) * 2]
    bench_starts = [s for s, _, _ in tr.spans]
    serve_starts = [s for s, _, _, _ in marks.spans]
    idle = collections.Counter()
    for (_, g0), (g1, _) in zip(bounds, bounds[1:]):
        if g1 <= g0:
            continue
        mid = (g0 + g1) / 2
        outer = innermost(tr.spans, bench_starts, mid)
        idle[(outer[len(trace_lib.SPAN_PREFIX):] if outer
              else "outside any span",
              innermost(marks.spans, serve_starts, mid)
              or "outside any serve span")] += g1 - g0

    selfs = self_times(marks.spans)
    sample = selfs.get("serve.sample", [])
    return {
        "calls": calls,
        "idle": [[b, s, t] for (b, s), t in idle.most_common()],
        "spans": {n: {"count": len(v), "self_s": sum(t for t, _ in v)}
                  for n, v in sorted(selfs.items())},
        "decode_iterations": len(selfs.get("serve.decode", [])),
        "host_self_s": sum(t for n in HOST_SPANS
                           for t, _ in selfs.get(n, [])),
        # a sample span holds no wait: its self time is its duration
        "sample_s": sum(t for t, _ in sample),
        "sampled_rows": sum(a.get("rows", 0) for _, a in sample),
    }


def reduce(run) -> dict:
    t0, t1 = run.trace_window
    pd, paths = load(run.trace_dir)
    return summarize(trace_lib.Trace(pd), Marks(pd, paths), run.spec,
                     run.peak,
                     [c for c in run.stepper.calls if t0 <= c[1] <= t1])


def of_run(run):
    """The run's summary, reduced once and kept on the run; None for an
    untraced run."""
    if not hasattr(run, "scopes"):
        run.scopes = (reduce(run) if getattr(run, "trace_dir", None)
                      else None)
    return run.scopes


def attention_share(run, kind):
    """Share of its least time (%) that attention reaches in the step
    calls of ``kind``; None without ops in the ``attention`` scope."""
    c = ((of_run(run) or {}).get("calls") or {}).get(kind)
    if not c or c["attention_s"] <= 0 or c["attention_least_s"] <= 0:
        return None
    return 100.0 * c["attention_least_s"] / c["attention_s"]


def table(summary) -> str:
    """Both tables of ``summary`` as text."""
    lines = []
    for kind, c in summary["calls"].items():
        if not c["n"]:
            continue
        lines.append(f"{kind}: {c['n']} calls, {c['device_s'] * 1e3:.3f} ms "
                     f"on the device, by innermost scope:")
        for scope, t in c["by_scope"].items():
            lines.append(f"  {scope:<10} {t * 1e3:10.3f} ms "
                         f"{100 * t / c['device_s']:6.2f}%")
        for name, t in c["unscoped_ops"].items():
            lines.append(f"    {UNSCOPED} {name:<38} {t * 1e3:10.3f} ms")
        if c["attention_s"] > 0 and c["attention_least_s"] > 0:
            share = 100 * c["attention_least_s"] / c["attention_s"]
            lines.append(f"  attention least time "
                         f"{c['attention_least_s'] * 1e3:.3f} ms: "
                         f"{share:.2f}% of its roofline")
    lines.append("idle gaps of the device, by bench span and serve span:")
    for outer, inner, t in summary["idle"]:
        lines.append(f"  {outer:<18} {inner:<24} {t * 1e3:10.3f} ms")
    lines.append("serve spans: count, host self time (less *.wait):")
    for name, v in summary["spans"].items():
        lines.append(f"  {name:<24} {v['count']:6d} "
                     f"{v['self_s'] * 1e3:10.3f} ms")
    return "\n".join(lines)


def main(argv) -> int:
    if len(argv) != 1:
        print("usage: python3 bench/scopes.py <trace_dir>", file=sys.stderr)
        return 2
    pd, paths = load(argv[0])
    print(table(summarize(trace_lib.Trace(pd), Marks(pd, paths))))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
