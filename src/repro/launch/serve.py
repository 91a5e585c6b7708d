"""Serving launcher: batched requests through a (quantized) model.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m \
        [--reduced] [--quantize [--act-group G]] [--requests 8] \
        [--new-tokens 16] \
        [--page-size 16] [--kv-pages N] [--prefill-chunk C] \
        [--kv-dtype int8|int4 --kv-group G] \
        [--mesh model=N,data=M] \
        [--block-table results/block_table.json] [--vmem-budget BYTES] \
        [--deadline-s 30] [--retries 2] [--queue-bound 64] \
        [--inject-faults K --fault-seed S --parity-check]

The model is served at its published widths; ``--reduced`` swaps in the
tiny same-family config (``models.config.reduced``) for CPU smoke runs.
Outside the chaos harnesses the launcher exits non-zero unless every
request ends FINISHED.

Mesh-sharded serving (docs/serving.md, "Sharded serving"): ``--mesh``
builds a device mesh (prod(sizes) must equal the visible device count —
on CPU set ``XLA_FLAGS=--xla_force_host_platform_device_count=N``) and
serves through it: column/row-parallel shard_map QLinear forwards with
the low-rank factors following the weight shard (zero extra collectives),
replicated-then-data-sharded KV paging, and expert-parallel MoE dispatch
when the expert count divides the "model" axis.  ``--act-group`` selects
group-wise activation scales at calibration time — REQUIRED for
row-parallel sharding (per-token scales over a local K slice would shift
semantics, so those layers replicate instead).  Both chaos harnesses run
under the mesh unchanged: a given mesh is run-to-run deterministic, so
the recovery parity contract holds shard-count by shard-count.

KV-cache knobs (docs/serving.md): ``--page-size`` sets the paged-KV page
granularity, ``--kv-pages`` shrinks the shared page pool (admission then
accounts in available pages, not max_seq), ``--prefill-chunk`` enables
chunked prefill so long prompts interleave with ongoing decode.
``--kv-dtype int8|int4`` stores pages quantized (plus f32 scale planes
under the same block tables; ``--kv-group`` sets the scale granularity
along head_dim) with dequant fused into the attention inner loop — see
docs/serving.md "KV quantization".  Crash recovery reads the KV spec back
from the journal's open record, so a restore never needs the flags.

The kernel execution config (--block-table / --vmem-budget) is assembled
into one immutable ``KernelContext`` handed to the engine — no
process-global kernel state is mutated, so several launchers/engines can
coexist with different plan tables.  ``--impl`` selects the QLinear
execution path separately, via the engine's ``retag_qlinear_impl`` pass
(it is NOT recorded on the context).

Robustness knobs map 1:1 onto the engine's request lifecycle
(serve/lifecycle.py): per-request deadlines, bounded retries with
backoff, a bounded admission queue, and a stall watchdog.  With
``--inject-faults K`` a seeded ``FaultInjector`` (serve/faults.py)
targets K of the N requests with hard faults; the launcher then asserts
the structured split — exactly K FAILED/TIMED_OUT records, N-K FINISHED
— and exits non-zero on any mismatch or engine crash.  ``--parity-check``
additionally replays the same requests fault-free and asserts the
untargeted completions are bitwise identical.  CI runs this as the
chaos-smoke step.

Crash-recovery chaos (docs/serving.md, "Crash recovery")::

    PYTHONPATH=src python -m repro.launch.serve --reduced --requests 8 \
        --journal /tmp/rec/journal.wal --ckpt-dir /tmp/rec \
        --snapshot-every 4 --crash-after 2 [--crash-phase decode] \
        --parity-check

``--crash-after K`` schedules one seeded ``process_crash`` fault: the
engine dies (``SimulatedCrash`` unwinds ``run()``) on the K-th hit of the
chosen phase for a seed-picked rid, mid-flight, leaving only the
write-ahead journal and the last snapshot.  The launcher then calls
``ServeEngine.restore`` and asserts the recovery contract: the journal
replays cleanly, every request terminates EXACTLY once (``collate``
rejects double delivery or double terminals), and — with
``--parity-check`` — every token stream is bitwise identical to an
uninterrupted fault-free run.  Exits non-zero (and dumps
``results/serve_recovery_failure.json``) on any violation.  ``--journal``
and ``--ckpt-dir`` also work without ``--crash-after`` to journal /
snapshot a normal serve run.
"""

import argparse
import json
import os
import sys
import time


def _positive_int(s):
    v = int(s)
    if v <= 0:
        raise argparse.ArgumentTypeError(f"must be a positive integer, got {s}")
    return v


def build_context(block_table=None, vmem_budget=None):
    """CLI flags -> KernelContext (None when no flag was given); the shared
    mapping lives in repro.kernels.context.context_from_flags."""
    from repro.kernels.context import context_from_flags

    return context_from_flags(block_table, vmem_budget)


def _print_failure_summary(done, health, injector=None):
    from repro.serve.lifecycle import RequestState

    by_status = {}
    for rec in done.values():
        by_status.setdefault(rec.status.value, []).append(rec)
    print("request status: " + "  ".join(
        f"{status}={len(recs)}" for status, recs in sorted(by_status.items())))
    for rec in sorted(done.values(), key=lambda r: r.rid):
        if rec.status is RequestState.FINISHED:
            continue
        print(f"  rid {rec.rid}: {rec.status.value} "
              f"[{rec.error_kind}] after {rec.retries} retries, "
              f"{rec.new_tokens} token(s) — {rec.error}")
    counters = health["counters"]
    print(f"engine health: retries={counters['retries']} "
          f"slot_failures={counters['slot_failures']} "
          f"dead_slots={health['dead_slots']} "
          f"steps={counters['steps']} stalled={health['stalled']}")
    if injector is not None:
        print(f"fault injector: {json.dumps(injector.summary())}")


def _dump_recovery_failure(path, payload):
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, default=str)
    print(f"wrote failure report to {path}", file=sys.stderr)


def _crash_recovery_harness(args, cfg, params, ctx, run_engine,
                            mesh=None) -> int:
    """Kill the engine mid-run with a seeded process_crash, restore from
    journal+snapshot, and assert the recovery contract (exactly-once
    terminals; bitwise-equal streams with --parity-check).  Returns the
    process exit code."""
    import tempfile

    import numpy as np
    from repro.serve.engine import ServeEngine
    from repro.serve.faults import FaultInjector, FaultSpec, SimulatedCrash
    from repro.serve.journal import (JournalCorruption, JournalWriter,
                                     collate, read_journal)
    from repro.serve.lifecycle import RequestState

    workdir = args.ckpt_dir or tempfile.mkdtemp(prefix="serve_recovery_")
    jpath = args.journal or os.path.join(workdir, "journal.wal")
    snap_dir = os.path.join(workdir, "snapshots")
    snap_every = args.snapshot_every or 4
    rng = np.random.default_rng(args.fault_seed)
    crash_rid = int(rng.integers(0, args.requests))
    spec = FaultSpec(kind="process_crash", phase=args.crash_phase,
                     rid=crash_rid, at_call=args.crash_after)
    print(f"recovery chaos: scheduled process_crash at {args.crash_phase} "
          f"hit {args.crash_after} of rid {crash_rid} "
          f"(seed {args.fault_seed}); journal={jpath} "
          f"snapshots={snap_dir} every {snap_every} steps")

    crashed = None
    try:
        run_engine(FaultInjector([spec]),
                   journal=JournalWriter(jpath, overwrite=True),
                   snapshot_dir=snap_dir, snapshot_every=snap_every)
    except SimulatedCrash as e:
        crashed = e
    if crashed is None:
        print(f"RECOVERY CHAOS MISBEHAVED: the crash point was never hit "
              f"(rid {crash_rid} finished in fewer than "
              f"{args.crash_after + 1} {args.crash_phase} calls?)",
              file=sys.stderr)
        return 1
    print(f"engine died as scheduled: {crashed}")

    t0 = time.time()
    try:
        eng = ServeEngine.restore(cfg, params, jpath, snapshot_dir=snap_dir,
                                  snapshot_every=snap_every,
                                  kernel_impl=args.impl, ctx=ctx,
                                  max_retries=args.retries,
                                  stall_patience=args.stall_patience,
                                  mesh=mesh)
        done = eng.run()
        eng.journal.close()
        col = collate(read_journal(jpath).records)
    except JournalCorruption as e:
        print(f"RECOVERY FAILED: {e}", file=sys.stderr)
        _dump_recovery_failure("results/serve_recovery_failure.json",
                               {"error": str(e), "journal": jpath})
        return 1
    dt = time.time() - t0
    n_resumed = len(col.recovers)
    print(f"restored + drained in {dt:.2f}s "
          f"({len(done)} records, {n_resumed} recover marker(s))")

    problems = []
    # exactly-once termination: collate() above already raised on a double
    # terminal or a non-contiguous token stream; what remains is coverage
    missing = [rid for rid in range(args.requests) if rid not in col.terminals]
    if missing:
        problems.append(f"rids {missing} never reached a journaled terminal")
    not_finished = [r.rid for r in done.values()
                    if r.status is not RequestState.FINISHED]
    if not_finished:
        problems.append(f"rids {not_finished} did not finish cleanly: "
                        f"{[str(done[r].status) for r in not_finished]}")
    for rid, rec in done.items():
        if col.tokens.get(rid, []) != rec.out_tokens:
            problems.append(f"rid {rid}: journal stream != record stream")

    if args.parity_check:
        _, clean = run_engine(None)
        mismatched = [rid for rid in sorted(clean)
                      if done[rid].out_tokens != clean[rid].out_tokens]
        if mismatched:
            problems.append(f"streams for rids {mismatched} are not "
                            f"bitwise equal to the uninterrupted run")
        else:
            print(f"parity OK: all {len(clean)} recovered streams bitwise "
                  f"identical to the uninterrupted run (crash target "
                  f"rid {crash_rid} included)")

    if problems:
        for p in problems:
            print(f"RECOVERY VIOLATION: {p}", file=sys.stderr)
        _dump_recovery_failure(
            "results/serve_recovery_failure.json",
            {"problems": problems, "journal": jpath,
             "health": eng.health(),
             "records": {rid: {"status": str(r.status),
                               "tokens": r.out_tokens,
                               "error_kind": r.error_kind}
                         for rid, r in sorted(done.items())}})
        return 1
    print(f"recovery chaos OK: {len(done)} requests terminated exactly "
          f"once across the crash")
    return 0


def main():
    from repro.kernels.context import vmem_budget_arg

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action="store_true",
                    help="serve the tiny same-family config (CPU smoke "
                         "runs) instead of the published widths")
    ap.add_argument("--quantize", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--page-size", type=_positive_int, default=16,
                    help="paged-KV page granularity in tokens; pages are "
                         "allocated lazily as sequences cross page "
                         "boundaries and freed on terminal transitions")
    ap.add_argument("--kv-pages", type=_positive_int, default=None,
                    help="total pages in the shared KV pool (default sizes "
                         "the pool so exhaustion is impossible: "
                         "slots*ceil(max_seq/page_size)+1).  Shrinking it "
                         "makes admission account in available pages and "
                         "surfaces kv_pages_exhausted failures")
    ap.add_argument("--prefill-chunk", type=_positive_int, default=None,
                    help="chunked prefill width in tokens; long prompts "
                         "prefill one chunk per engine step, interleaved "
                         "with ongoing batched decode (default: whole "
                         "prompt in one forward)")
    ap.add_argument("--kv-dtype", default="f32",
                    choices=("f32", "bf16", "int8", "int4"),
                    help="KV-cache storage dtype (serve/kvquant.KVSpec): "
                         "int8/int4 store quantized pages plus f32 scale "
                         "planes under the same block tables, with dequant "
                         "fused into the attention gather; f32 (default) "
                         "is bitwise identical to the pre-KVSpec engine")
    ap.add_argument("--kv-group", type=_positive_int, default=None,
                    help="scale-group size along head_dim for quantized "
                         "--kv-dtype (e.g. 128); default: one scale per "
                         "(token, kv-head)")
    ap.add_argument("--impl", default="auto",
                    choices=("auto", "sim", "int8", "pallas", "fused"),
                    help="QLinear execution path for decode; auto = pallas "
                         "kernels on TPU (single-kernel fused forward per "
                         "the plan table), calibrated impl on CPU; fused "
                         "pins the single-kernel path")
    ap.add_argument("--block-table", default=None,
                    help="path to measured autotune winners "
                         "(results/block_table.json from "
                         "benchmarks/autotune_blocks.py) to overlay on the "
                         "analytic kernel plan table; may carry 'vmem' "
                         "(budget overrides) and 'layers' (per-layer plan "
                         "overrides) entries")
    ap.add_argument("--vmem-budget", type=vmem_budget_arg, default=None,
                    help="override the kernel VMEM working-set budgets "
                         "(positive bytes) used by plan resolution — both "
                         "the fused single-kernel budget and the chained "
                         "prologue budget; applied after --block-table, so "
                         "the CLI wins.  Use to probe real-TPU ceilings.")
    # -- request-lifecycle knobs (serve/lifecycle.py) -----------------------
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request wall-clock deadline in seconds; "
                         "expired requests come back as TIMED_OUT records")
    ap.add_argument("--retries", type=int, default=2,
                    help="bounded per-step retry budget before a request "
                         "is FAILED and its slot quarantined")
    ap.add_argument("--retry-backoff-s", type=float, default=0.0,
                    help="base backoff between retries (doubles per attempt)")
    ap.add_argument("--queue-bound", type=int, default=None,
                    help="admission-queue depth limit; overflow is handled "
                         "per --queue-policy as REJECTED records")
    ap.add_argument("--queue-policy", default="reject_new",
                    choices=("reject_new", "drop_oldest"))
    ap.add_argument("--stall-patience", type=int, default=64,
                    help="steps without progress before the watchdog aborts "
                         "run() with a stall report")
    # -- chaos (serve/faults.py) --------------------------------------------
    ap.add_argument("--inject-faults", type=int, default=0, metavar="K",
                    help="target K of the N requests with seeded hard "
                         "faults; the run then ASSERTS exactly K "
                         "FAILED/TIMED_OUT + N-K FINISHED records and "
                         "exits 1 on mismatch")
    ap.add_argument("--fault-seed", type=int, default=0)
    ap.add_argument("--fault-kinds", default="exception,nan_logits,cache_corruption",
                    help="comma-separated hard fault kinds to sample from "
                         "(slow_step only fails requests via --deadline-s, "
                         "so it is not in the default pool)")
    ap.add_argument("--fault-phase", default="decode",
                    choices=("prefill", "decode", "sampling"))
    ap.add_argument("--parity-check", action="store_true",
                    help="replay the same requests fault-free and assert "
                         "the untargeted completions are bitwise identical")
    # -- crash recovery (serve/journal.py + engine snapshot/restore) --------
    ap.add_argument("--journal", default=None, metavar="PATH",
                    help="write-ahead request journal path; every submit/"
                         "token/terminal is fsync'd here before it becomes "
                         "visible (enables crash recovery)")
    ap.add_argument("--ckpt-dir", default=None, metavar="DIR",
                    help="engine snapshot directory (atomic tmp-rename "
                         "checkpoints of the paged pool / caches + "
                         "allocator + lifecycle state)")
    ap.add_argument("--snapshot-every", type=int, default=0, metavar="N",
                    help="snapshot the engine every N steps (step "
                         "boundaries only); requires --ckpt-dir")
    ap.add_argument("--crash-after", type=int, default=None, metavar="K",
                    help="crash-recovery chaos: kill the engine with a "
                         "seeded process_crash on the K-th --crash-phase "
                         "hit of a seed-picked rid, then restore from "
                         "journal+snapshot and assert every request "
                         "terminates exactly once (bitwise-equal streams "
                         "with --parity-check)")
    ap.add_argument("--crash-phase", default="decode",
                    choices=("prefill", "decode", "sampling"))
    # -- mesh-sharded serving (distributed/tp.py + ep.py) -------------------
    ap.add_argument("--mesh", default=None, metavar="SPEC",
                    help="serve through a device mesh, e.g. model=4,data=2 "
                         "(prod of sizes must equal the device count; on "
                         "CPU set XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=N).  Column/row-parallel QLinear "
                         "forwards under shard_map, data-sharded KV pages, "
                         "expert-parallel MoE when n_experts divides "
                         "'model'")
    ap.add_argument("--act-group", type=_positive_int, default=None,
                    help="group-wise activation scales for --quantize "
                         "(paper Table 2 g, e.g. 16/128).  Required for "
                         "row-parallel TP: the group grid must divide the "
                         "local K slice, or those layers replicate")
    args = ap.parse_args()
    if args.crash_after is not None and args.crash_after < 0:
        ap.error("--crash-after must be >= 0")
    if args.crash_after is not None and args.inject_faults:
        ap.error("--crash-after and --inject-faults are separate chaos "
                 "harnesses; pick one")

    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.launch.compile_cache import use_compile_cache
    from repro.models import model as model_lib
    from repro.models.config import reduced as reduce_cfg
    from repro.serve.engine import ServeEngine
    from repro.serve.faults import FaultInjector
    from repro.serve.kvquant import KVSpec
    from repro.serve.lifecycle import Request, RequestState

    use_compile_cache()
    ctx = build_context(args.block_table, args.vmem_budget)
    if args.block_table:
        print(f"loaded kernel plan table from {args.block_table}")
    if args.vmem_budget is not None:
        print(f"kernel VMEM budgets set to {args.vmem_budget} bytes")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0))

    if args.quantize:
        from repro.data.loader import calib_sequences
        from repro.quant.calibrate import quantize_model
        from repro.quant.policy import QuantPolicy

        calib = calib_sequences(cfg, n_seq=16, seq_len=64)
        params = quantize_model(
            cfg, params, calib,
            QuantPolicy(rank_frac=0.10, impl="sim", clip_ratio=0.9,
                        act_group=args.act_group),
        )
        print("serving the W4A4+LRC quantized model"
              + (f" (act_group={args.act_group})" if args.act_group else ""))

    mesh = None
    if args.mesh:
        from repro.distributed.tp import build_mesh

        mesh = build_mesh(args.mesh)
        print(f"serving through mesh {dict(mesh.shape)} "
              f"({jax.device_count()} devices)")

    injector = None
    if args.inject_faults > 0:
        kinds = tuple(k.strip() for k in args.fault_kinds.split(",") if k.strip())
        injector = FaultInjector.sample(
            range(args.requests), k=args.inject_faults, seed=args.fault_seed,
            kinds=kinds, phase=args.fault_phase,
            repeat=args.retries + 4,  # outlast the retry budget
        )
        print(f"injecting seeded faults (seed {args.fault_seed}) into "
              f"{args.inject_faults}/{args.requests} requests: "
              f"rids {sorted(injector.targets)}")

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, 12).astype(np.int32)
               for _ in range(args.requests)]

    kv_spec = KVSpec.from_flags(args.kv_dtype, args.kv_group)
    if kv_spec.is_quantized:
        print(f"KV cache stored as {kv_spec.describe()} "
              f"(dequant fused into the attention gather)")

    def run_engine(inj, **crash_safety):
        eng = ServeEngine(
            cfg, params, batch_slots=args.slots, max_seq=args.max_seq,
            page_size=args.page_size, kv_pages=args.kv_pages,
            prefill_chunk=args.prefill_chunk, kv_spec=kv_spec,
            kernel_impl=args.impl, ctx=ctx,
            max_retries=args.retries, retry_backoff_s=args.retry_backoff_s,
            queue_limit=args.queue_bound, queue_policy=args.queue_policy,
            default_deadline_s=args.deadline_s,
            stall_patience=args.stall_patience, injector=inj,
            mesh=mesh,
            **crash_safety,
        )
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p.copy(),
                               max_new_tokens=args.new_tokens))
        return eng, eng.run()

    if args.crash_after is not None:
        sys.exit(_crash_recovery_harness(args, cfg, params, ctx, run_engine,
                                         mesh=mesh))

    crash_safety = {}
    if args.journal:
        from repro.serve.journal import JournalWriter

        crash_safety["journal"] = JournalWriter(args.journal, overwrite=True)
    if args.ckpt_dir:
        crash_safety.update(snapshot_dir=args.ckpt_dir,
                            snapshot_every=args.snapshot_every)

    t0 = time.time()
    eng, done = run_engine(injector, **crash_safety)
    if eng.journal is not None:
        eng.journal.close()
    dt = time.time() - t0
    total = sum(len(r.out_tokens) for r in done.values())
    finished = [r for r in done.values() if r.ok]
    print(f"{len(done)} requests ({len(finished)} finished), {total} tokens, "
          f"{dt:.2f}s -> {total / max(dt, 1e-9):.1f} tok/s")
    kv = eng.health()["kv"]
    if "bytes_per_token" in kv:
        print(f"kv cache: {kv['layout']}, "
              f"{kv['bytes_per_token']} B/token (all layers, K+V incl. "
              f"scale planes)")
    mh = eng.health()["mesh"]
    if mh is not None:
        kinds = {}
        for p in mh["decode_plans"].values():
            key = p["parallel"] or "replicated"
            kinds[key] = kinds.get(key, 0) + p["layers"]
        print(f"mesh: axes={mh['axes']} moe_impl={mh['moe_impl']} "
              f"ep_dropped={mh['ep_dropped']} layers_by_kind={kinds}")
    _print_failure_summary(done, eng.health(), injector)

    ok = True
    if injector is None:
        if len(finished) != len(done):
            print(f"SERVE FAILED: {len(done) - len(finished)} of {len(done)} "
                  f"requests did not finish", file=sys.stderr)
            ok = False
    else:
        # the acceptance split: exactly K structured failures, N-K clean
        failures = {r.rid for r in done.values()
                    if r.status in (RequestState.FAILED, RequestState.TIMED_OUT)}
        expect = injector.targets
        if failures != expect or len(finished) != args.requests - len(expect):
            print(f"CHAOS MISMATCH: expected failures {sorted(expect)}, "
                  f"got {sorted(failures)} "
                  f"({len(finished)} finished)", file=sys.stderr)
            ok = False
        else:
            print(f"chaos split OK: {len(expect)} structured failures, "
                  f"{len(finished)} completions, engine exited cleanly")
        if args.parity_check:
            _, clean = run_engine(None)
            mismatched = [
                rid for rid in sorted(set(done) - expect)
                if done[rid].out_tokens != clean[rid].out_tokens
            ]
            if mismatched:
                print(f"PARITY MISMATCH for untargeted rids {mismatched}",
                      file=sys.stderr)
                ok = False
            else:
                print(f"parity OK: {len(set(done) - expect)} untargeted "
                      f"requests bitwise identical to the fault-free run")
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
