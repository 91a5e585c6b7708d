"""The marks the program leaves in a profiler trace: the engine's ``serve.*``
host spans with their arguments, the named scopes of the step program, and
the Pallas kernels' explicit names (the benchmark's trace readers find ops
by them, ``bench/scopes.py`` and ``bench/trace.py``)."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.models import model
from repro.models.config import reduced
from repro.serve.engine import Request, ServeEngine


def _spans(trace_dir):
    """[(start, end, name, args)] of the ``serve.*`` spans in the newest
    trace under ``trace_dir``."""
    files = sorted(trace_dir.glob("**/*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(str(files[-1]))
    host = pd.find_plane_with_name("/host:CPU")
    return sorted((e.start_ns, e.start_ns + e.duration_ns, e.name,
                   dict(e.stats))
                  for line in host.lines for e in line.events
                  if e.name.startswith("serve."))


def _within(inner, outer):
    return outer[0] <= inner[0] and inner[1] <= outer[1]


def test_engine_spans_under_the_profiler(tmp_path, rng):
    cfg = reduced(get_config("smollm-135m"))
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    prompts = [np.asarray(rng.integers(0, cfg.vocab_size, (n,)), np.int32)
               for n in (9, 5, 11)]

    def serve(profile):
        # unique (batch, max_seq, page, chunk): this test owns its traces
        eng = ServeEngine(cfg, params, batch_slots=3, max_seq=44,
                          page_size=4, prefill_chunk=4)
        t0 = eng.health()["traces"]["paged"]
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p, max_new_tokens=5))
        if profile:
            jax.profiler.start_trace(str(tmp_path))
        try:
            done = eng.run()
        finally:
            if profile:
                jax.profiler.stop_trace()
        assert all(done[i].ok for i in range(len(prompts)))
        return eng, eng.health()["traces"]["paged"] - t0, [
            done[i].out_tokens for i in range(len(prompts))]

    eng, traced, tokens = serve(profile=True)
    assert traced == 2   # one chunk shape, one decode shape
    spans = _spans(tmp_path)
    by = lambda name: [s for s in spans if s[2] == name]

    decode = by("serve.decode")
    assert decode and all({"step", "rows"} <= set(a) for *_, a in decode)
    assert [a["step"] for *_, a in decode] == sorted(
        {a["step"] for *_, a in decode})
    assert all(0 < a["rows"] <= 3 for *_, a in decode)
    waits = by("serve.decode.wait")
    assert len(waits) == eng.counters["decode_calls"] == len(decode)
    for name, phase in (("serve.decode.wait", "serve.decode"),
                        ("serve.decode.prepare", "serve.decode"),
                        ("serve.sample", "serve.decode"),
                        ("serve.commit", "serve.decode"),
                        ("serve.prefill.wait", "serve.prefill.chunk")):
        for s in by(name):
            assert any(_within(s, p) for p in by(phase)), (name, s)
    # every chunk runs from admission or from a prefill tick
    chunks = by("serve.prefill.chunk")
    assert len(chunks) == len(by("serve.prefill.wait")) == sum(
        -(-len(p) // 4) for p in prompts)
    assert all(any(_within(c, p) for p in by("serve.admit")
                   + by("serve.prefill")) for c in chunks)
    assert sum(a["tokens"] for *_, a in chunks) == sum(map(len, prompts))
    assert {a["rid"] for *_, a in chunks} == {0, 1, 2}
    assert sum(a["admitted"] for *_, a in by("serve.admit")) == 3
    # one batched sampling call per decode step
    assert len(by("serve.sample")) == len(decode)
    assert all(sum(_within(s, d) for s in by("serve.sample")) == 1
               for d in decode)
    assert sum(a["rows"] for *_, a in by("serve.sample")) == sum(
        a["tokens"] for *_, a in by("serve.commit")) == 3 * 4

    # the spans change nothing served, and tracing recompiles nothing
    _, retraced, untraced_tokens = serve(profile=False)
    assert retraced == 0
    assert untraced_tokens == tokens


def test_step_program_carries_the_named_scopes():
    cfg = dataclasses.replace(reduced(get_config("smollm-135m")), n_layers=1)
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    pool = model.init_paged_cache(cfg, 5, 4)
    b, s = 2, 1
    args = (params, jnp.zeros((b, s), jnp.int32), jnp.zeros((b, s), jnp.int32),
            jnp.ones((b, s), bool), pool, jnp.ones((b, 2), jnp.int32),
            jnp.zeros((b,), jnp.int32))
    text = jax.jit(lambda *a: model.paged_step(cfg, *a)).lower(
        *args).as_text(debug_info=True)
    paths = set(re.findall(r'loc\("([^"]*)"', text))
    for scope in ("attention", "kv_write", "mlp", "norm", "unembed"):
        assert any(scope in p.split("/") for p in paths), scope


def test_qlinear_scope_and_fused_kernel_name():
    """A fused QLinear runs under the ``qlinear`` scope, and its Pallas
    call is named ``fused_w4a4_lrc_kernel`` whatever the Python function
    that builds it is called: the benchmark reads the kernel's ops by that
    name."""
    from repro.quant.qlinear import apply_linear, make_qlinear

    rng = np.random.default_rng(0)
    k, n, r = 256, 128, 8
    q = rng.integers(-7, 8, (n, k)).astype(np.int8)
    s = rng.uniform(0.01, 0.02, (n, 1)).astype(np.float32)
    u = rng.normal(size=(n, r)).astype(np.float32)
    v = rng.normal(size=(k, r)).astype(np.float32)
    ql = make_qlinear(q, s, u, v, impl="fused")
    x = jnp.asarray(rng.normal(size=(8, k)), jnp.float32)

    def kernel_names(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                yield e.params["name"]
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from kernel_names(sub)

    closed = jax.make_jaxpr(lambda x: apply_linear(ql, x))(x)
    assert set(kernel_names(closed.jaxpr)) == {"fused_w4a4_lrc_kernel"}
    text = jax.jit(lambda x: apply_linear(ql, x)).lower(x).as_text(
        debug_info=True)
    assert "qlinear/" in text
