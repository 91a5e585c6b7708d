"""The one file of the benchmark that drives the program: it builds
``ServeEngine`` over the parameters that the configuration's family
(``bench/engines/<family>.py``) makes of the benchmark's weights, and runs the body of ``ServeEngine.run()`` one iteration at a time, so that the
harness can submit due requests between iterations.  It reaches into the
engine's private step methods because the engine has no public single-step
call yet.

It records what the engine does not expose: the time of every committed
token, the kind and time of every step call (a ``(1, chunk)`` prefill chunk
or a ``(slots, 1)`` decode step), and, once ``keep_kv`` is set (after the
window), the K/V that a request's pages hold when it ends, read back
before the pool gives the pages to another request.  When tracing, it wraps admission, prefill chunks, decode steps
and sampling in host spans (``bench.*``), which label the device's idle
gaps in the trace.
"""

from __future__ import annotations

import contextlib
import time

import jax
import numpy as np

from bench.model import engine as engine_module

SPAN_PREFIX = "bench."


def make_engine(spec, w, seed: int, chips: int = 1):
    """``ServeEngine`` over the served parameters the configuration's
    family builds from ``w``.  On more than one chip the model is sharded
    over a mesh of the first ``chips`` devices (axis ``model``)."""
    from repro.serve.engine import ServeEngine
    from repro.serve.kvquant import KVSpec

    fam = engine_module(spec.reference)
    mesh = None
    if chips > 1:
        devices = jax.devices()[:chips]
        if len(devices) < chips:
            raise ValueError(f"{chips} chips asked for, {len(devices)} found")
        mesh = jax.sharding.Mesh(
            np.asarray(devices), ("model",),
            axis_types=(jax.sharding.AxisType.Auto,))
    return ServeEngine(
        fam.program_config(spec), fam.program_params(spec, w),
        batch_slots=spec.slots, max_seq=spec.max_seq, seed=seed,
        kernel_impl="auto", kv_spec=KVSpec(dtype=spec.kv_dtype),
        page_size=spec.page_size, prefill_chunk=spec.prefill_chunk,
        clock=time.perf_counter, mesh=mesh)


class Stepper:
    """One engine, driven iteration by iteration, with its records."""

    def __init__(self, eng, spec, clock=time.perf_counter):
        self.eng = eng
        self.read_pool = engine_module(spec.reference).read_pool
        self.clock = clock
        self.spans = False
        self.token_times = {}   # rid -> [time of each committed token]
        # (kind, host start, host end, positions, valid) per step call
        self.calls = []
        self.keep_kv = False
        self.kept_kv = {}   # rid -> layer 0's (K, V) when it ended
        paged, commit = eng._paged, eng._commit_token
        release, sample = eng._release_slot, eng._sample

        def paged_call(params, tokens, positions, valid, *rest):
            kind = "chunk" if tokens.shape[0] == 1 and tokens.shape[1] > 1 \
                else "decode"
            t0 = self.clock()
            with self.span("step." + kind):
                out = paged(params, tokens, positions, valid, *rest)
            self.calls.append((kind, t0, self.clock(), positions, valid))
            return out

        def commit_token(req, tok):
            commit(req, tok)
            self.token_times.setdefault(req.rid, []).append(self.clock())

        def release_slot(i):
            req = eng.slot_req[i]
            if self.keep_kv and req is not None and eng.alloc.holds(req.rid):
                # every position the timed path wrote: all but the last
                # served token, which is sampled and never fed back
                n = len(req.prompt) + len(req.out_tokens) - 1
                self.kept_kv[req.rid] = self.read_pool(
                    eng, list(eng.alloc.pages_of(req.rid)), n, 1)
            release(i)

        def sample_row(req, logits):
            with self.span("sample"):
                return sample(req, logits)

        eng._paged, eng._commit_token = paged_call, commit_token
        eng._release_slot, eng._sample = release_slot, sample_row

    def span(self, name):
        if not self.spans:
            return contextlib.nullcontext()
        return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)

    def submit(self, rid, prompt, max_new_tokens):
        from repro.serve.lifecycle import Request

        req = Request(rid=rid, prompt=np.asarray(prompt, np.int32),
                      max_new_tokens=int(max_new_tokens))
        if not self.eng.submit(req):
            raise RuntimeError(f"request {rid} rejected: {req.error}")
        return req

    def iteration(self):
        """The body of ``ServeEngine.run()``: admit (which runs a new
        request's first chunk), one chunk for every mid-prefill slot, one
        decode step.  Returns False when the engine is idle."""
        eng = self.eng
        eng.counters["steps"] += 1
        eng._expire_deadlines()
        with self.span("admit"):
            eng._admit()
        if not eng.queue and all(r is None for r in eng.slot_req):
            return False
        with self.span("prefill_tick"):
            eng._prefill_tick()
        with self.span("decode_tick"):
            eng._step()
        return True

    def cancel_queued(self):
        for req in list(self.eng.queue):
            self.eng.cancel(req.rid)

    def record(self, rid):
        return self.eng.records.get(rid)
