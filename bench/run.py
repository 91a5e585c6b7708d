"""Run one cell of the benchmark once, on the chip it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data, found by name: its entry in
BENCHMARK.json, its configuration file, its traffic mix in bench/traffic/,
its rate, lead-in and limits in bench/cells/<cell>.json, and each per-layer
metric's reader in bench/metrics/<metric>.py.

A run makes the weights on the device from the seed, builds the engine,
warms up the cell's two step shapes (set-up, timed as ``setup_s``), offers
the traffic, measures for ``--seconds`` seconds, keeps serving until every
request due in the window has its first token and then until enough
requests have ended to compare, and compares a sample of those with the
plain reference.  The last
line of standard output is one JSON object; the compared numbers and their
limits are also the last lines of standard error.  Without a TPU, or with
fewer chips than the cell asks for, it exits 2 and prints no result.
With ``--trace 1`` the first seconds of the window are traced and the line
carries the cell's per-layer metrics instead of its end-to-end ones.
"""

from __future__ import annotations

import os
import time


def _process_age() -> float:
    """Seconds since this process started (the kernel's start time)."""
    try:
        start = int(open("/proc/self/stat").read().rpartition(")")[2]
                    .split()[19]) / os.sysconf("SC_CLK_TCK")
        return float(open("/proc/uptime").read().split()[0]) - start
    except (OSError, ValueError, IndexError):
        return 0.0


T_START = time.perf_counter() - _process_age()
# the TPU runtime's logs would go to a fixed path outside the checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

from bench import stats as stats_lib  # noqa: E402
from bench import traffic as traffic_lib  # noqa: E402
from bench.model import load_spec  # noqa: E402

CACHE_DIR = ROOT / ".bench_cache"
TRACE_SECONDS = 4.0      # traced part of the window (--trace 1), at least
STEP_KINDS = {"chunk", "decode"}  # ... and until it holds a call of each
TAIL_LIMIT_S = 60.0      # longest wait past the window for a first token
DRAIN_LIMIT_S = 120.0    # longest serving past the cutoff
WARM_RID = 10 ** 9


def log(msg):
    print(msg, file=sys.stderr, flush=True)


class Compiles:
    """Counts the programs JAX builds or loads from its persistent cache,
    with their names and times, from JAX's own monitoring events and debug
    log."""

    def __init__(self):
        import jax

        self.count = 0
        self.compiled = []   # (function name, seconds)
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)
        jax.monitoring.register_event_listener(self._hit)
        handler = logging.Handler(logging.DEBUG)
        handler.emit = self._record
        for name in ("jax._src.dispatch", "jax._src.interpreters.pxla"):
            lg = logging.getLogger(name)
            lg.setLevel(logging.DEBUG)
            lg.addHandler(handler)
            lg.propagate = False

    def _event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1

    def _hit(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def _record(self, rec):
        msg = rec.getMessage()
        if msg.startswith("Finished XLA compilation of "):
            name, _, secs = msg[len("Finished XLA compilation of "):] \
                .rpartition(" in ")
            self.compiled.append((name, float(secs.split()[0])))


class Run:
    """One run of one cell: what the metric readers read."""

    def __init__(self, root, manifest, cell, seed, seconds, trace):
        self.root = root
        self.manifest = manifest
        self.cell = cell
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        conf = next(c for c in manifest["configs"]
                    if c["name"] == cell["config"])
        data = root / "bench"
        self.spec = load_spec(conf["name"], root / conf["file"])
        self.mix = traffic_lib.load_mix(cell["traffic"], data)
        self.params = json.loads(
            (data / "cells" / f"{cell['name']}.json").read_text())
        self.due = {}           # rid -> due time (host clock)
        self.requests = {}      # rid -> Request
        self.trace_summary = None
        self.peak = None
        self.window = None      # (w0, w1)


def load_reader(name, root):
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def setup(run, compiles):
    """Weights, engine and warm-up of the cell's two step shapes."""
    import jax

    from bench import adapter
    from bench.weights import make_weights

    spec = run.spec
    t0 = time.perf_counter()
    w = jax.block_until_ready(make_weights(spec, run.seed))
    t1 = time.perf_counter()
    drv = adapter.Stepper(
        adapter.make_engine(spec, w, run.seed, run.cell["chips"]), spec)
    t2 = time.perf_counter()
    # every slot decodes (the sampling path slices each row), one prompt
    # spans two chunks (the finite check between chunks)
    rng = np.random.default_rng(0)
    for i in range(spec.slots):
        n = spec.prefill_chunk + 1 if i == 0 else 8
        drv.submit(WARM_RID + i, rng.integers(0, spec.vocab, n), 2)
    while drv.iteration():
        pass
    bad = [r for r in range(WARM_RID, WARM_RID + spec.slots)
           if not drv.record(r).ok]
    if bad:
        raise RuntimeError(f"warm-up requests failed: {bad}")
    run.setup_parts = {"before": t0, "weights": t1 - t0, "engine": t2 - t1,
                       "warm-up": time.perf_counter() - t2}
    return w, drv


def serve(run, drv, compiles):
    """Offer the traffic, measure the window, and serve on until every
    request due in the window has its first token (the cutoff).  From the
    cutoff on, requests that end keep their K/V for the comparison; the
    traffic goes on until enough have, and the rest are let go.
    Returns (compiles in the window, cutoff time)."""
    import jax

    spec, mix, cp = run.spec, run.mix, run.params
    lead, W = cp["lead_s"], run.seconds
    clock = drv.clock
    t0 = clock()
    w0, w1 = t0 + lead, t0 + lead + W
    run.window = (w0, w1)
    if mix["loop"] == "open":
        items = traffic_lib.open_loop(mix, run.seed, cp["rate_per_s"],
                                      -lead, W + TAIL_LIMIT_S, spec.vocab,
                                      cp.get("first_rid", 0))
        pending = sorted(items, key=lambda it: it.due)
        queues = None
    else:
        queues = traffic_lib.closed_loop(mix, run.seed, spec.slots,
                                         cp["per_client"], spec.vocab,
                                         cp.get("first_rid", 0))
        pending = []
        sent = [None] * len(queues)   # rid each client waits on
    nxt = 0
    tracing = False
    trace_dir = (run.root / CACHE_DIR.name / "trace"
                 / f"{run.cell['name']}-{run.seed}")
    compiles_w0 = None
    compiles_window = None
    sending = True
    cutoff = None

    def first_tokens_due():
        return all(drv.token_times.get(r) for r, t in run.due.items()
                   if w0 <= t < w1)

    while True:
        now = clock()
        if sending:
            if queues is None:
                while nxt < len(pending) and w0 + pending[nxt].due <= now:
                    it = pending[nxt]
                    run.requests[it.rid] = drv.submit(it.rid, it.prompt,
                                                      it.max_new)
                    run.due[it.rid] = w0 + it.due
                    nxt += 1
            else:
                for c, q in enumerate(queues):
                    prev = sent[c]
                    if prev is not None and not run.requests[prev].done:
                        continue
                    if not q:
                        if cutoff is not None:
                            continue   # past the window: the client stops
                        raise RuntimeError(f"client {c} ran out of requests;"
                                           f" raise per_client")
                    it = q.pop(0)
                    due = now if prev is None else drv.token_times[prev][-1]
                    run.requests[it.rid] = drv.submit(it.rid, it.prompt,
                                                      it.max_new)
                    run.due[it.rid] = due
                    sent[c] = it.rid
        if compiles_w0 is None and now >= w0:
            compiles_w0 = compiles.count
            if run.trace:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 2
                shutil.rmtree(trace_dir, ignore_errors=True)
                drv.spans = True
                jax.profiler.start_trace(str(trace_dir),
                                         profiler_options=opts)
                tracing = True
                trace_t0 = clock()
        if tracing and (now >= w1 or now >= trace_t0 + TRACE_SECONDS and {
                c[0] for c in drv.calls if c[1] >= trace_t0} >= STEP_KINDS):
            jax.profiler.stop_trace()
            trace_t1 = clock()
            tracing = False
            drv.spans = False
        if compiles_window is None and now >= w1:
            compiles_window = compiles.count - compiles_w0
        if cutoff is None and now >= w1 and (first_tokens_due()
                                             or now >= w1 + TAIL_LIMIT_S):
            # the window's requests are served: from here on, a request
            # that ends leaves its K/V for the comparison
            cutoff = now
            drv.keep_kv = True
        if cutoff is not None and (sending or drv.eng.queue or any(
                drv.eng.slot_req)):
            if len(checkable(run, drv)) >= cp["check_requests"]:
                # enough to compare: the rest need not end
                sending = False
                drv.cancel_queued()
                for req in list(drv.eng.slot_req):
                    if req is not None:
                        drv.eng.cancel(req.rid)
            elif (nxt == len(pending) if queues is None
                  else not any(queues)):
                sending = False   # the traffic is spent
        if not drv.iteration():
            if not sending:
                break
            if queues is None and nxt < len(pending):
                time.sleep(max(0.0, min(w0 + pending[nxt].due - clock(),
                                        0.002)))
        if cutoff is not None and clock() > cutoff + DRAIN_LIMIT_S:
            raise RuntimeError(f"{len(checkable(run, drv))} requests to "
                               f"compare {DRAIN_LIMIT_S} s after the window")
    if run.trace:
        run.trace_window = (trace_t0, trace_t1)
        run.trace_dir = trace_dir
    return compiles_window, cutoff


def finished_or_cut(record) -> bool:
    """A request that ran to its end, or that the harness cancelled once
    enough had finished to compare (after the window: not a failure)."""
    return record is not None and record.status.value in ("finished",
                                                          "cancelled")


def checkable(run, drv):
    """The finished requests whose layer-0 K/V the stepper kept: those
    that ended after the run stopped sending.  (The pool gives an ended
    request's pages to the next, so the K/V of earlier ones is gone.)"""
    return sorted(r for r in drv.kept_kv
                  if drv.record(r) is not None and drv.record(r).ok)


def sample_for_check(run, drv):
    """The sampled requests: drawn from the seed among the checkable
    ones, with the longest among them."""
    ok = checkable(run, drv)
    if not ok:
        raise RuntimeError("no finished request to compare")
    size = lambda r: len(run.requests[r].prompt) + len(
        run.requests[r].out_tokens)
    longest = max(ok, key=size)
    rng = np.random.default_rng(run.seed)
    rest = [r for r in ok if r != longest]
    n = min(len(rest), run.params["check_requests"] - 1)
    picked = [longest] + [rest[i] for i in sorted(
        rng.choice(len(rest), n, replace=False))]
    out = []
    for r in picked:
        req = run.requests[r]
        k, v = drv.kept_kv[r]
        out.append((np.asarray(req.prompt), list(req.out_tokens), k, v))
    return out


def result_metrics(run, names, st):
    metrics = {}
    for m in names:
        if m["name"] == "setup_s":
            val = run.setup_s
        elif m["name"] in st:
            val = st[m["name"]]
        else:
            val = load_reader(m["name"], run.root)(run)
        if val is None or (isinstance(val, float) and math.isnan(val)):
            continue
        metrics[m["name"]] = {"value": float(val), "unit": m["unit"]}
    return metrics


def applies(metric, cell_name):
    return cell_name in metric.get("workloads", [cell_name])


_COMPILES = None


def use_cache(root: Path):
    """JAX's persistent compilation cache at a fixed path in the checkout,
    for every program, however quick to compile."""
    import jax

    path = root / CACHE_DIR.name / "jax"
    path.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # no eviction: its bookkeeping files broke writes when the environment
    # set a size limit, and every run then compiled everything again
    jax.config.update("jax_compilation_cache_max_size", -1)


def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: int = 0, require_tpu: bool = True, fault=None):
    """One run of one cell.  Returns (exit code, result line or None, the
    run's state).  ``root`` holds BENCHMARK.json and the cell's data;
    ``require_tpu=False`` skips the look for a chip (the tests' CPU runs);
    ``fault(stepper)``, when given, breaks the timed path after set-up (the
    tests that see ``correct`` come out false)."""
    global _COMPILES
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in manifest["workloads"]}
    if workload not in cells:
        log(f"unknown workload {workload!r}; known: {sorted(cells)}")
        return 2, None, None
    cell = cells[workload]

    import jax

    devices = jax.devices()
    dev = devices[0]
    if require_tpu and (dev.platform != "tpu"
                        or len(devices) < cell["chips"]):
        log(f"needs {cell['chips']} TPU chip(s); JAX found "
            f"{len(devices)} {dev.platform} device(s)")
        return 2, None, None
    use_cache(root)
    if _COMPILES is None:
        _COMPILES = Compiles()
    compiles = _COMPILES
    n_before, named_before = compiles.count, len(compiles.compiled)
    hits_before = compiles.cache_hits
    t_setup = time.perf_counter() if n_before else T_START
    run = Run(root, manifest, cell, seed, seconds, trace)
    from bench.work import peaks
    run.peak = peaks(dev.device_kind, root / "bench")

    w, drv = setup(run, compiles)
    run.setup_s = time.perf_counter() - t_setup
    setup_compiles = compiles.compiled[named_before:]
    parts = run.setup_parts
    parts["before"] -= t_setup
    log(f"setup {run.setup_s:.3f}s ("
        + ", ".join(f"{k} {v:.2f}s" for k, v in parts.items()) + "): "
        f"{compiles.count - n_before} programs, "
        f"{compiles.cache_hits - hits_before} of them from the persistent "
        f"cache: " + ", ".join(f"{n} {s:.2f}s" for n, s in setup_compiles))
    if fault is not None:
        fault(drv)

    in_window, cutoff = serve(run, drv, compiles)
    log(f"compiles in the window: {in_window}")
    if in_window:
        raise RuntimeError(f"{in_window} programs compiled in the window")
    memory_peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)

    w0, w1 = run.window
    reqs = run.requests
    st = stats_lib.window_stats(
        run.due, {r: (drv.token_times.get(r) or [None])[0] for r in reqs},
        drv.token_times,
        {r: finished_or_cut(drv.record(r)) for r in reqs}, w0, w1, cutoff)
    run.stats = st
    run.stepper = drv
    samples = sample_for_check(run, drv)
    if run.trace:
        from bench import trace as trace_lib
        run.trace_summary = trace_lib.reduce(run, drv)

    # the program's state goes before the reference runs
    drv.eng.pool = None
    drv.eng = None
    gc.collect()

    from bench import check
    run.seq_len = traffic_lib.longest(run.mix)
    run.seq_len += (-run.seq_len) % 128
    cmp = check.compare_served(run.spec, w, samples, run.seq_len)
    numbers = cmp.numbers()
    correct, lines = check.verdict(numbers, run.params["limits"])
    log(f"compared {len(samples)} requests, {cmp.tokens} served tokens, "
        f"{cmp.positions} K/V positions: "
        + ", ".join(f"{k} {v:.4g}" for k, v in numbers.items()))

    kind = "per_layer" if run.trace else "end_to_end"
    names = [m for m in manifest[kind] if applies(m, cell["name"])]
    metrics = result_metrics(run, names, st)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(memory_peak)}
    out = {"correct": bool(correct), "attempted": st["attempted"],
           "failed": st["failed"], "metrics": metrics, "device": device}
    if run.trace:
        ts = run.trace_summary
        device["busy_s"] = ts["busy_s"]
        device["window_s"] = ts["window_s"]
        out["breakdown"] = ts["breakdown"]
    out["check"] = {k: {"value": numbers[k], "limit": run.params["limits"][k]}
                    for k in sorted(run.params["limits"])}
    for line in lines:
        log(line)
    run.weights, run.samples, run.numbers = w, samples, numbers
    return 0, out, run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    rc, out, _ = run_cell(ROOT, args.workload, args.seed, args.seconds,
                          args.trace)
    if out is not None:
        print(json.dumps(out), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
