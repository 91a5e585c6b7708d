"""Serving-path latency: chunked prefill + ONE batched decode call per step,
as a function of batch size and page size.

Emits ``results/BENCH_serve.json`` (``results/BENCH_serve_smoke.json`` with
``--smoke``) in the shared ``benchmarks.common.record`` layout; the column
schema is documented in docs/serving.md.  Its columns are counts, not
times: ``decode_calls_per_token`` and ``prefill_chunks_per_prompt`` are
exact consequences of the engine's batching structure: one batched decode
call per engine step makes ``decode_calls_per_token == 1/batch`` whatever
the token count, and chunked prefill issues exactly
``ceil(prompt_len/chunk)`` forwards per prompt.  The CI regression gate
(``benchmarks.check_regression --serve``) fails if either grows — i.e. if
batching quietly degenerates back toward per-slot decode calls.  Both are
token-count invariant, so the --smoke rows (fewer new tokens) gate against
the committed full baseline.  Serving times come from the chip benchmark
(``bench/``) and the engine's ``serve.*`` trace spans.

Run on the reduced smollm config with synthetic FP weights: serving-path
latency structure (calls per token, chunk interleaving, page bookkeeping)
does not depend on the weight values, and FP keeps CI runtime flat.

    PYTHONPATH=src python -m benchmarks.serve_latency [--smoke]
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from benchmarks.common import record
from repro.configs import get_config
from repro.models import model as model_lib
from repro.models.config import reduced
from repro.serve.engine import Request, ServeEngine
from repro.serve.kvquant import KVSpec

HEADER = [
    "batch", "page_size", "prefill_chunk", "kv_dtype", "requests",
    "prompt_len", "new_tokens",
    "decode_calls", "decode_calls_per_token", "prefill_chunks_per_prompt",
    "paged_traces", "kv_bytes_per_token",
]

PROMPT_LEN = 24
MAX_SEQ = 64
# (batch, page_size, prefill_chunk, kv_dtype) — the acceptance grid: B in
# {1, 4, 16}, a page-size point, a chunked-prefill point,
# and the quantized-KV points (int8 per-head, int4 per-head) whose
# kv_bytes_per_token column the regression gate holds at the >=3x / >=5x
# reductions the paged pools deliver
CASES = [(1, 16, None, "f32"), (4, 16, None, "f32"), (16, 16, None, "f32"),
         (4, 8, None, "f32"), (4, 16, 8, "f32"),
         (4, 16, None, "int8"), (4, 16, None, "int4")]
SMOKE_CASES = [(1, 16, None, "f32"), (4, 16, None, "f32"),
               (4, 16, 8, "f32"), (4, 16, None, "int8")]


def _mk_engine(cfg, params, batch, page_size, chunk, kv_dtype):
    return ServeEngine(cfg, params, batch_slots=batch, max_seq=MAX_SEQ,
                       page_size=page_size, prefill_chunk=chunk,
                       kv_spec=KVSpec.from_flags(kv_dtype, None))


def _drive(cfg, params, batch, page_size, chunk, kv_dtype, new_tokens):
    """One wave of ``batch`` identical-length requests; returns the engine
    for counter inspection."""
    rng = np.random.default_rng(0)
    prompts = [np.asarray(rng.integers(0, cfg.vocab_size, (PROMPT_LEN,)),
                          np.int32) for _ in range(batch)]
    eng = _mk_engine(cfg, params, batch, page_size, chunk, kv_dtype)
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=new_tokens))
    done = eng.run()
    assert all(done[i].ok for i in range(batch)), \
        {i: (done[i].status, done[i].error) for i in done}
    assert all(len(done[i].out_tokens) == new_tokens for i in range(batch))
    # pages all came back on the terminal transitions
    assert eng.alloc.free_pages == eng.alloc.capacity
    eng.alloc.check()
    return eng


def bench_case(cfg, params, batch, page_size, chunk, kv_dtype, new_tokens):
    fns_traces = None
    # run twice: the first run compiles (the jitted fns are shared
    # process-wide per config, so the second run is pure execution)
    for it in range(2):
        eng = _drive(cfg, params, batch, page_size, chunk, kv_dtype,
                     new_tokens)
        if it == 0:
            fns_traces = dict(eng.health()["traces"])
    # retracing on the measured run would mean the engine's shapes are not
    # stable step-to-step — that is a bug, not a measurement artifact
    assert eng.health()["traces"] == fns_traces, "decode retraced while serving"

    decode_tokens = batch * (new_tokens - 1)  # first token comes from prefill
    decode_calls = eng.counters["decode_calls"]
    assert decode_calls == new_tokens - 1, (decode_calls, new_tokens)
    chunks = -(-PROMPT_LEN // (chunk or PROMPT_LEN))
    return [
        batch, page_size, 0 if chunk is None else chunk, kv_dtype, batch,
        PROMPT_LEN, new_tokens,
        decode_calls,
        round(decode_calls / decode_tokens, 6),
        chunks,
        eng.health()["traces"]["paged"],
        eng.health()["kv"]["bytes_per_token"],
    ]


def bench_rows(smoke: bool = False):
    cfg = reduced(get_config("smollm-135m"))
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0))
    cases = SMOKE_CASES if smoke else CASES
    new_tokens = 6 if smoke else 16
    return [bench_case(cfg, params, b, p, c, d, new_tokens)
            for b, p, c, d in cases]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small grid / few tokens for CI; writes "
                         "results/BENCH_serve_smoke.json")
    args = ap.parse_args(argv)
    rows = bench_rows(smoke=args.smoke)
    record("BENCH_serve_smoke" if args.smoke else "BENCH_serve", rows, HEADER)


if __name__ == "__main__":
    main()
