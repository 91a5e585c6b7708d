"""Continuous-batching serving engine: ONE batched decode step over a paged
KV cache, with the hardened request lifecycle of ``serve/lifecycle.py``.

Design (the full guide lives in ``docs/serving.md``):

- **One decode call per step.**  All active slots advance through a single
  jitted forward per engine step — tokens ``(B, 1)``, an active-slot
  ``valid`` mask for empty / faulted slots — instead of B per-slot calls.
  ``counters["decode_calls"]`` counts exactly one per step with any active
  decoder, regardless of occupancy.
- **Paged KV cache** (attention families; ``model.PAGED_FAMILIES``).  Slots
  share one page pool (``model.init_paged_cache``); ``serve/paging.py``
  owns the free-list allocator and per-request page lists, the engine keeps
  a host-side ``(B, pages_per_slot)`` block table.  Pages are allocated at
  admission (prompt) and at decode-boundary crossings, freed as a unit on
  every terminal transition.  Page 0 is the reserved null page: writes for
  padding / inactive / faulted slots are redirected there, which is what
  makes a masked slot's garbage provably invisible to its neighbors.
- **Stacked decode** (``model.STACKED_FAMILIES``: recurrent state, no
  positional cache to page).  Slots live as rows of one stacked cache;
  prefill runs B=1 and is inserted via ``model.insert_cache_row``; decode
  is the same single batched call.
- **Legacy slot loop** (vlm / hybrid / moe).  Their caches carry a shared
  scalar offset that cannot differ per row, so they keep the per-slot
  contiguous caches and per-slot decode calls of the previous engine.
- **Chunked prefill** (paged mode, ``prefill_chunk=``).  A long prompt
  prefills in fixed-size chunks, one chunk per engine step, so decode for
  co-tenant requests keeps advancing between chunks instead of stalling
  behind one long prompt.  Chunks are padded to a fixed width (one trace),
  non-final chunks run a finite-logits check so corruption can never be
  committed silently, and only the final chunk samples.  The default
  (``None``) prefills the whole prompt in one chunk at admission.

The lifecycle contract is unchanged from the per-slot engine and the chaos
suite proves it still holds under paging:

- **Admission control.**  ``submit()`` validates prompts (length vs. the
  block-table width ``max_seq``, pool capacity in PAGES, token ids, budgets,
  deadlines, unique rid) with a bounded queue; at admission time a request
  additionally waits in queue (FIFO) until the free list covers its prompt
  — page-accounting backpressure instead of a blind slot grab.
- **Failure isolation.**  Faults are applied per slot: an injected
  exception drops the slot from the step's ``valid`` mask (its KV writes
  redirect to the null page), cache corruption poisons ONLY that request's
  pages (``FaultInjector.corrupt_pages``) or stacked row, and sampling is
  per-row.  A failed attempt commits nothing for that slot — its pages are
  rolled back to the pre-step pool, its length/tokens do not advance — so
  a retry restarts from clean committed state on the NEXT engine step
  (bounded by ``max_retries`` with exponential backoff, then the slot is
  quarantined and a FAILED record emitted; ``slot_failure_limit``
  consecutive request failures kill the slot).
- **Deadlines & budgets, liveness, fault injection.**  Unchanged: per-
  request deadlines checked queued and in flight, ``cancel()``, the stall
  watchdog + ``stall_report``, injectable clock/sleep, ``health()``
  snapshots (now including page-pool stats and the resolved decode-regime
  kernel plan at the REAL batched M = ``batch_slots``).

Sampling keys derive only from (engine seed, rid, token index), and masked
attention positions contribute exactly zero weight — together these make
the chaos suite's strongest assert hold: untargeted requests are bitwise
identical to a fault-free run, regardless of WHICH pages a request lands
on, which slot it occupies, or what its co-tenants are doing.

**Crash safety** (``journal=`` / ``snapshot_dir=`` / ``snapshot_every=``;
full guide in docs/serving.md, "Crash recovery"):

- every externally visible effect — an accepted submit, a committed token,
  a terminal record — is appended (fsync'd) to the write-ahead journal
  BEFORE the in-memory effect happens, so the journal is always at or
  ahead of engine state;
- ``snapshot()`` persists the full decode state (paged pool + allocator +
  block tables, or the stacked/slot caches) atomically through the
  checkpoint path, at engine-step boundaries only;
- ``ServeEngine.restore`` = latest restorable snapshot + journal replay:
  slots whose journaled token count matches the snapshot resume in place;
  anything newer than the snapshot (or with no usable snapshot at all)
  re-prefills over ``prompt + journaled tokens`` — and because sampling
  keys depend only on (seed, rid, token index), the recovered continuation
  is bitwise identical to the uninterrupted run, with every journaled
  token delivered exactly once.

``run()`` returns ``{rid: RequestRecord}`` — structured terminal records,
not live request objects.  Works with FP or quantized (QLinear) params.
"""

from __future__ import annotations

import functools
import json
import time
import warnings
from types import SimpleNamespace
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.ckpt import (CheckpointError, CheckpointManager,
                                   load_leaf)
from repro.models import model as model_lib
from repro.serve.faults import FaultInjector, InjectedFault, SimulatedCrash
from repro.serve.kvquant import KVSpec
from repro.serve.journal import (JournalError, JournalWriter, collate,
                                 read_journal)
from repro.serve.lifecycle import (ErrorKind, Request, RequestRecord,
                                   RequestState, TERMINAL_STATES)
from repro.serve.paging import PageAllocator
from repro.serve.sampling import (NonFiniteLogitsError, count_non_finite,
                                  non_finite_error, sample_rows_packed)

# Host spans of the serving phases (``serve.*``): they land in the
# profiler's own trace, on the device ops' clock, whenever a profiler session
# runs, and cost about a microsecond each when none does, so they are always
# opened.  Their keyword arguments and ``set_metadata`` values (``rid``,
# ``step``, ``rows``, ...) become stats of the trace event.
span = jax.profiler.TraceAnnotation


class PagesExhausted(RuntimeError):
    """The free list could not cover a page allocation (admission raced, or
    the pool was sized below ``batch_slots * pages_per_slot``).  Retried
    like any transient fault — a co-tenant finishing frees pages — then
    surfaces as a FAILED record with ``error_kind == 'kv_pages_exhausted'``.
    """


@functools.lru_cache(maxsize=16)
def _model_fns(cfg, kv_spec: KVSpec = KVSpec(), moe_impl: str = "dense",
               with_stats: bool = False, mesh=None) -> SimpleNamespace:
    """Per-(config, kv-spec, moe-impl, mesh) jitted step functions, shared
    by every engine instance in the process (all key parts are hashable —
    ``mesh`` participates because shard_map captures the ambient mesh at
    TRACE time, so two engines over different meshes must not share traces)
    — N engines over the same key stop paying N compilations.

    ``traces`` counts retracings (incremented at trace time, not per call):
    the paged engine compiles exactly two ``paged`` traces per (config,
    kv spec) — one (1, chunk) prefill shape, one (B, 1) decode shape — and
    the test suite asserts that.  The f32 spec selects the pre-KVSpec trace
    verbatim (``transformer.paged_step`` branches at Python trace time), so
    its serving stays bitwise identical."""
    traces = {"prefill": 0, "decode": 0, "paged": 0}

    @jax.jit
    def _prefill(params, tokens, cache):
        traces["prefill"] += 1
        return model_lib.prefill(
            cfg, params, {"tokens": tokens, "moe_impl": moe_impl}, cache)

    @jax.jit
    def _decode(params, tokens, cache):
        traces["decode"] += 1
        return model_lib.decode_step(cfg, params, tokens, cache,
                                     moe_impl=moe_impl,
                                     with_stats=with_stats)

    @jax.jit
    def _paged(params, tokens, positions, valid, cache, block_table,
               sample_row):
        traces["paged"] += 1
        return model_lib.paged_step(cfg, params, tokens, positions, valid,
                                    cache, block_table, sample_row,
                                    kv_spec=kv_spec)

    return SimpleNamespace(prefill=_prefill, decode=_decode, paged=_paged,
                           traces=traces)


def _classify_error(e: BaseException) -> Tuple[ErrorKind, str]:
    if isinstance(e, InjectedFault):
        kind = ErrorKind.INJECTED
    elif isinstance(e, NonFiniteLogitsError):
        kind = ErrorKind.NON_FINITE_LOGITS
    elif isinstance(e, PagesExhausted):
        kind = ErrorKind.KV_PAGES_EXHAUSTED
    elif isinstance(e, SimulatedCrash):
        # a crash normally unwinds run() entirely; this only fires if a
        # caller catches it and asks for a post-mortem classification
        kind = ErrorKind.SIMULATED_CRASH
    else:
        kind = ErrorKind.EXCEPTION
    msg = f"{type(e).__name__}: {e}"
    return kind, msg[:500]


class ServeEngine:
    def __init__(self, cfg, params, batch_slots: int = 4, max_seq: int = 256,
                 eos_id: Optional[int] = None, seed: int = 0,
                 kernel_impl: Optional[str] = "auto", ctx=None, *,
                 kv_spec: Optional[KVSpec] = None,
                 page_size: int = 16, kv_pages: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 max_retries: int = 2, retry_backoff_s: float = 0.0,
                 queue_limit: Optional[int] = None,
                 queue_policy: str = "reject_new",
                 default_deadline_s: Optional[float] = None,
                 slot_failure_limit: int = 3, stall_patience: int = 64,
                 injector: Optional[FaultInjector] = None,
                 clock: Callable[[], float] = time.monotonic,
                 sleep_fn: Callable[[float], None] = time.sleep,
                 journal: Optional[JournalWriter] = None,
                 snapshot_dir: Optional[str] = None,
                 snapshot_every: int = 0, snapshot_keep: int = 3,
                 mesh=None):
        assert cfg.family in ("dense", "vlm", "ssm", "hybrid", "moe"), cfg.family
        if queue_policy not in ("reject_new", "drop_oldest"):
            raise ValueError(f"unknown queue_policy {queue_policy!r}; "
                             f"one of ('reject_new', 'drop_oldest')")
        if max_retries < 0 or retry_backoff_s < 0:
            raise ValueError("max_retries and retry_backoff_s must be >= 0")
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, got {prefill_chunk}")
        if snapshot_every < 0:
            raise ValueError(f"snapshot_every must be >= 0, got {snapshot_every}")
        if snapshot_every and snapshot_dir is None:
            raise ValueError("snapshot_every > 0 requires snapshot_dir")
        # Decode runs W4A4+LRC through the pallas kernels (single-kernel
        # fused forward at decode/mixed shapes, prologue→GEMM chain past the
        # VMEM gate) whenever a compiled backend is attached; "auto" keeps
        # the calibrated impl on CPU where the pallas interpreter would only
        # slow the reference semantics down.  Pass an explicit impl
        # ("fused"/"pallas"/"int8"/"sim") to force a path.
        #
        # ``ctx`` is this engine's KernelContext (block table, VMEM budgets,
        # default kernel path, per-layer plan overrides).  It is attached to
        # every QLinear leaf as pytree-static metadata, so two engines in
        # one process can serve under DIFFERENT plan tables/budgets without
        # touching any global; None uses the process-default context.
        # kernel_impl=None attaches the ctx WITHOUT touching the calibrated
        # impls.
        if kernel_impl is not None or ctx is not None:
            from repro.quant.qlinear import retag_qlinear_impl

            params = retag_qlinear_impl(params, kernel_impl, ctx=ctx)
        # Mesh-sharded serving: tag + place the params (column/row-parallel
        # QLinears run the shard_map TP forward; everything else stays
        # replicated for dense families so non-collective math is bitwise
        # identical to single-device), and pick expert-parallel decode for
        # MoE configs when the expert count divides the "model" axis.
        # Ordering matters: retag FIRST (dataclasses.replace keeps array
        # identity, so placements survive), then shard.
        self.mesh = mesh
        self.tp_plan = None
        self._moe_impl = "dense"
        self._decode_stats = False
        self._ep_dropped = 0
        if mesh is not None:
            from repro.distributed import tp as tp_lib

            tp = tp_lib._axis_size(mesh, "model")
            if cfg.family == "moe" and tp > 1:
                if cfg.n_experts % tp == 0:
                    self._moe_impl = "ep"
                    self._decode_stats = True
                else:
                    warnings.warn(
                        f"n_experts={cfg.n_experts} does not divide "
                        f"model={tp}; MoE dispatch stays dense under the "
                        "mesh")
            params, self.tp_plan = tp_lib.shard_params(
                params, mesh, replicate_dense=(cfg.family != "moe"))
        self.ctx = ctx
        self.cfg = cfg
        self.params = params
        self.b = batch_slots
        self.max_seq = max_seq
        self.eos_id = eos_id
        self.seed = seed
        self.base_key = jax.random.PRNGKey(seed)

        # crash safety: write-ahead journal + snapshot schedule.  The open
        # record (below, once the mode is known) pins the shape config a
        # restored engine must be rebuilt with.
        self.journal = journal
        self.snapshot_every = snapshot_every
        self._ckpt = (CheckpointManager(snapshot_dir, every=1,
                                        keep=snapshot_keep)
                      if snapshot_dir is not None else None)
        self._journaled_submits: set = set()
        self._journaled_terminals: set = set()

        self.max_retries = max_retries
        self.retry_backoff_s = retry_backoff_s
        self.queue_limit = queue_limit
        self.queue_policy = queue_policy
        self.default_deadline_s = default_deadline_s
        self.slot_failure_limit = slot_failure_limit
        self.stall_patience = stall_patience
        self.injector = injector
        self.clock = clock
        self.sleep_fn = sleep_fn

        # family -> decode-state layout; see the module docstring
        if cfg.family in model_lib.PAGED_FAMILIES:
            self.mode = "paged"
        elif cfg.family in model_lib.STACKED_FAMILIES:
            self.mode = "stacked"
        else:
            self.mode = "slots"
        if prefill_chunk is not None and self.mode != "paged":
            raise ValueError(
                f"prefill_chunk requires a paged family "
                f"{model_lib.PAGED_FAMILIES}, not {cfg.family!r}")
        # KV storage spec: ONE axis of the cache layout for every mode.
        # The default (f32) reproduces the pre-KVSpec engine bitwise; float
        # specs route the storage dtype everywhere (paged pool, stacked and
        # per-slot caches alike); quantized specs need the paged layout —
        # recurrent / offset-carrying caches have no pages to quantize.
        self.kv_spec = kv_spec if kv_spec is not None else KVSpec()
        if self.kv_spec.is_quantized:
            if self.mode != "paged":
                raise ValueError(
                    f"kv dtype {self.kv_spec.dtype!r} requires the paged KV "
                    f"cache (families {model_lib.PAGED_FAMILIES}); "
                    f"{cfg.family!r} serves in {self.mode!r} mode")
            # surface bad geometry (odd head_dim for int4, group that does
            # not divide head_dim) at construction, not at first prefill
            self.kv_spec.packed_head_dim(cfg.head_dim)
            self.kv_spec.group_for(cfg.head_dim)
        self.page_size = page_size
        self.prefill_chunk = prefill_chunk
        self.alloc: Optional[PageAllocator] = None
        self.slot_caches: List = []
        if self.mode == "paged":
            # block-table width bounds positions to max_seq; the DEFAULT
            # pool exactly covers every slot at full length, so the free
            # list can only run dry when the caller shrinks kv_pages
            self.pages_per_slot = -(-max_seq // page_size)
            num_pages = (kv_pages if kv_pages is not None
                         else batch_slots * self.pages_per_slot + 1)
            self.alloc = PageAllocator(
                num_pages, page_size, sidecar=self.kv_spec.is_quantized)
            self.pool = model_lib.init_paged_cache(
                cfg, num_pages, page_size, dtype=jnp.float32,
                kv_spec=self.kv_spec)
            if mesh is not None:
                # replicated over "model", page axis data-sharded when it
                # divides — page gathers/scatters are pure data movement,
                # so placement never perturbs decode numerics
                from repro.distributed import tp as tp_lib

                self.pool = tp_lib.shard_kv_pool(self.pool, mesh)
            self.block_tables = np.zeros(
                (batch_slots, self.pages_per_slot), np.int32)
            self.lengths = np.zeros((batch_slots,), np.int32)
            self._prefill_off = [0] * batch_slots
        elif self.mode == "stacked":
            self.stacked_cache = model_lib.init_cache(
                cfg, batch_slots, max_seq, dtype=jnp.float32,
                kv_spec=self.kv_spec)
        else:
            # per-slot caches (B=1 each): these families' caches carry a
            # shared scalar offset, so slots cannot share a batched cache
            self.slot_caches = [self._fresh_cache() for _ in range(batch_slots)]

        self.slot_req: List[Optional[Request]] = [None] * batch_slots
        self.slot_fail_streak: List[int] = [0] * batch_slots
        self.slot_dead: List[bool] = [False] * batch_slots
        self.queue: List[Request] = []
        self.records: Dict[int, RequestRecord] = {}
        self.counters: Dict[str, int] = {
            "submitted": 0, "admitted": 0, "steps": 0, "retries": 0,
            "finished": 0, "failed": 0, "rejected": 0, "cancelled": 0,
            "timed_out": 0, "slot_failures": 0, "decode_calls": 0,
            "sample_calls": 0,
        }
        # rid -> consecutive failed attempts; a failed attempt retries on
        # the NEXT engine step (deferred retry) so the batched step stays
        # one forward per step even while some slot is flaky
        self._attempt_streak: Dict[int, int] = {}
        self._steps_since_progress = 0
        self.stall_report: Optional[dict] = None

        self._fns = _model_fns(cfg, self.kv_spec, self._moe_impl,
                               self._decode_stats, self.mesh)
        self._prefill = self._fns.prefill
        self._decode = self._fns.decode
        self._paged = self._fns.paged
        if self.mesh is not None:
            # every jitted call runs (and first traces) under the mesh, so
            # shard_map picks up the right ambient mesh at trace time
            def _with_mesh(fn, m=self.mesh):
                @functools.wraps(fn)
                def call(*args):
                    with jax.set_mesh(m):
                        return fn(*args)
                return call

            self._prefill = _with_mesh(self._prefill)
            self._decode = _with_mesh(self._decode)
            self._paged = _with_mesh(self._paged)
        self.decode_plan = self._resolve_decode_plan()

        self._journal("open", mode=self.mode, family=cfg.family,
                      batch_slots=batch_slots, max_seq=max_seq,
                      eos_id=eos_id, seed=seed, page_size=page_size,
                      kv_pages=(None if self.alloc is None
                                else self.alloc.num_pages),
                      prefill_chunk=prefill_chunk,
                      **self.kv_spec.to_meta())

    # -- public API ---------------------------------------------------------

    def submit(self, req: Request) -> bool:
        """Validate and enqueue; returns False (with a ``REJECTED`` record)
        when admission control refuses the request."""
        now = self.clock()
        req.submitted_at = now
        if req.deadline_s is None:
            req.deadline_s = self.default_deadline_s
        err = self._validate(req)
        if err is not None:
            if err[0] is ErrorKind.DUPLICATE_RID:
                # a second record cannot be indexed under the same rid —
                # reject the duplicate in place, leaving the original
                # request's record/queue entry untouched
                req.error_kind, req.error = err
                req.advance(RequestState.REJECTED, now)
                self.counters["rejected"] += 1
                return False
            self._finalize(req, RequestState.REJECTED, *err)
            return False
        if self.queue_limit is not None and len(self.queue) >= self.queue_limit:
            if self.queue_policy == "drop_oldest":
                oldest = self.queue.pop(0)
                self._finalize(oldest, RequestState.REJECTED,
                               ErrorKind.QUEUE_EVICTED,
                               f"evicted by rid {req.rid} under drop_oldest "
                               f"(queue_limit={self.queue_limit})")
            else:
                self._finalize(req, RequestState.REJECTED,
                               ErrorKind.QUEUE_FULL,
                               f"queue at limit {self.queue_limit}")
                return False
        # WAL: the submit record is durable BEFORE the request becomes
        # engine state — a crash one instruction later replays it.
        # Rejected submits are deliberately NOT journaled: their REJECTED
        # record was already returned synchronously, so recovery owes them
        # nothing (and must not emit a second terminal for the rid).
        if self.journal is not None:
            self.journal.append(
                "submit", rid=req.rid,
                prompt=[int(t) for t in np.asarray(req.prompt)],
                max_new_tokens=int(req.max_new_tokens),
                temperature=float(req.temperature),
                deadline_s=req.deadline_s)
            self._journaled_submits.add(req.rid)
        self.counters["submitted"] += 1
        self.queue.append(req)
        return True

    def cancel(self, rid: int) -> bool:
        """Cancel a queued or in-flight request; False if unknown/terminal."""
        for qi, req in enumerate(self.queue):
            if req.rid == rid:
                self.queue.pop(qi)
                self._finalize(req, RequestState.CANCELLED,
                               ErrorKind.CANCELLED, "cancelled while queued")
                return True
        for i, req in enumerate(self.slot_req):
            if req is not None and req.rid == rid:
                # applied immediately: free the slot (and its pages), keep
                # emitted tokens
                self._release_slot(i)
                self._finalize(req, RequestState.CANCELLED,
                               ErrorKind.CANCELLED, "cancelled in flight")
                return True
        return False

    def run(self, max_steps: int = 1024) -> Dict[int, RequestRecord]:
        """Drive until queue + slots drain; never raises for per-request
        failures.  Exhausting ``max_steps`` returns the survivors as
        ``TIMED_OUT`` records; a detected stall aborts with
        ``self.stall_report`` set."""
        self.stall_report = None
        for _ in range(max_steps):
            self.counters["steps"] += 1
            progressed = self._expire_deadlines()
            progressed |= self._admit()
            if not any(r is not None for r in self.slot_req) and not self.queue:
                break
            progressed |= self._prefill_tick()
            progressed |= self._step()
            self._steps_since_progress = (
                0 if progressed else self._steps_since_progress + 1)
            stall = self._stall_reason()
            if stall is not None:
                self.stall_report = {"reason": stall, "health": self.health()}
                self._drain_unfinished(ErrorKind.STALL,
                                       f"run() aborted: {stall}")
                return self.records
            # snapshot at the step boundary ONLY: no forward is in flight,
            # lengths/pool/allocator are mutually consistent
            if (self.snapshot_every and self._ckpt is not None
                    and self.counters["steps"] % self.snapshot_every == 0):
                self.snapshot()
        else:
            self._drain_unfinished(
                ErrorKind.STEP_LIMIT,
                f"engine step budget ({max_steps}) exhausted")
        return self.records

    def health(self) -> dict:
        """Live snapshot: slot states, queue depth, counters, liveness,
        page-pool accounting, trace counts, and the decode-regime kernel
        plan resolved at the engine's REAL batched M (= ``batch_slots``)."""
        slots = []
        for i in range(self.b):
            req = self.slot_req[i]
            slots.append({
                "slot": i,
                "state": ("dead" if self.slot_dead[i]
                          else req.state.value if req is not None else "idle"),
                "rid": None if req is None else req.rid,
                "tokens": 0 if req is None else len(req.out_tokens),
                "fail_streak": self.slot_fail_streak[i],
            })
        return {
            "slots": slots,
            "queue_depth": len(self.queue),
            "dead_slots": sum(self.slot_dead),
            "counters": dict(self.counters),
            "steps_since_progress": self._steps_since_progress,
            "stalled": self.stall_report is not None,
            "mode": self.mode,
            "kv": self._kv_health(),
            "kv_pages": None if self.alloc is None else self.alloc.stats(),
            "traces": dict(self._fns.traces),
            "decode_plan": self.decode_plan,
            "mesh": self._mesh_health(),
            "journal_seq": None if self.journal is None else self.journal.seq,
        }

    def _mesh_health(self) -> Optional[dict]:
        """``health()["mesh"]``: axis sizes, the per-shard decode plan at
        every distinct LOCAL (K, N, R) a TP-tagged QLinear resolves to
        (mirroring ``decode_plan`` but at the shard's shapes, where the
        shape-keyed ctx overrides apply), and the EP capacity-overflow drop
        counter.  None when the engine is single-device."""
        if self.mesh is None:
            return None
        axes = {str(k): int(v) for k, v in dict(self.mesh.shape).items()}
        plans: Dict[str, dict] = {}
        for entry in (self.tp_plan or []):
            k, n, r = entry["local_knr"]
            key = f"{entry['parallel'] or 'replicated'}:{k}x{n}r{r}"
            if key in plans:
                plans[key]["layers"] += 1
                continue
            ctx = entry.get("ctx") or self.ctx
            if ctx is None:
                from repro.kernels import ops

                ctx = ops.default_context()
            plan = ctx.resolve_plan(self.b, k, n, r,
                                    act_group=entry.get("act_group"))
            plans[key] = {
                "parallel": entry["parallel"], "layers": 1,
                "local": {"m": self.b, "k": k, "n": n, "r": r},
                "path": plan.path, "bm": plan.bm, "bn": plan.bn,
                "bk": plan.bk, "br": plan.br, "variant": plan.variant,
            }
        return {
            "axes": axes,
            "moe_impl": self._moe_impl,
            "ep_dropped": int(self._ep_dropped),
            "decode_plans": plans,
        }

    def _kv_health(self) -> dict:
        """``health()["kv"]``: the effective KV storage scheme and its HBM
        cost.  ``bytes_per_token`` (paged mode) is the all-layer K+V
        footprint of one token — data plus scale planes — computed by the
        canonical ``KVSpec.kv_bytes_per_token`` spelling; stacked mode has
        no per-token cache, so it reports the per-slot recurrent-state
        bytes its spec actually produced instead."""
        info = {"dtype": self.kv_spec.dtype, "group": self.kv_spec.group,
                "layout": self.kv_spec.describe()}
        if self.mode == "paged":
            info["bytes_per_token"] = (
                self.cfg.n_layers * self.kv_spec.kv_bytes_per_token(
                    self.cfg.n_kv_heads, self.cfg.head_dim))
        elif self.mode == "stacked":
            leaves = jax.tree.leaves(self.stacked_cache)
            info["state_bytes_per_slot"] = int(
                sum(l.size * l.dtype.itemsize for l in leaves)) // self.b
        return info

    # -- kernel-plan introspection ------------------------------------------

    def _resolve_decode_plan(self) -> Optional[dict]:
        """The kernel plan the batched decode step actually runs: QLinear
        flattens (B, 1, K) activations to an (M=B, K) GEMM, so the plan must
        be resolved at M = ``batch_slots``, not the per-slot M=1 the old
        slot-loop engine implied.  The top-level fields describe the
        largest QLinear (the dominant GEMM of the step); ``shapes`` maps
        every distinct "KxNrR" layer shape to its own plan.  None for FP
        params."""
        from repro.kernels.context import gemm_regime

        from repro.quant.qlinear import QLinear

        leaves = jax.tree.leaves(
            self.params, is_leaf=lambda x: isinstance(x, QLinear))
        qls = [l for l in leaves if isinstance(l, QLinear)]
        if not qls:
            return None

        def plan_of(q):
            ctx = q.ctx
            if ctx is None:
                from repro.kernels import ops
                ctx = ops.default_context()
            r = 0 if q.u is None else int(q.u.shape[-1])
            plan = ctx.resolve_plan(self.b, q.d_in, q.d_out, r,
                                    layer=q.name, act_group=q.act_group)
            return r, {"impl": q.impl, "path": plan.path, "bm": plan.bm,
                       "bn": plan.bn, "bk": plan.bk, "br": plan.br,
                       "variant": plan.variant}

        shapes = {}
        for q in qls:
            r, plan = plan_of(q)
            shapes.setdefault(f"{q.d_in}x{q.d_out}r{r}", plan)
        q = max(qls, key=lambda l: l.d_in * l.d_out)
        r, plan = plan_of(q)
        return {"m": self.b, "k": q.d_in, "n": q.d_out, "r": r,
                "regime": gemm_regime(self.b), **plan, "shapes": shapes}

    # -- admission ----------------------------------------------------------

    def _validate(self, req: Request) -> Optional[Tuple[ErrorKind, str]]:
        if (req.rid in self.records
                or any(q.rid == req.rid for q in self.queue)
                or any(r is not None and r.rid == req.rid for r in self.slot_req)):
            return (ErrorKind.DUPLICATE_RID,
                    f"rid {req.rid} already known to the engine")
        prompt = np.asarray(req.prompt)
        if prompt.ndim != 1 or prompt.size == 0:
            return (ErrorKind.EMPTY_PROMPT,
                    f"prompt must be a non-empty 1-D token "
                    f"array, got shape {prompt.shape}")
        if not np.issubdtype(prompt.dtype, np.integer):
            return (ErrorKind.BAD_TOKEN_IDS,
                    f"prompt dtype {prompt.dtype} is not integral")
        if prompt.min() < 0 or prompt.max() >= self.cfg.vocab_size:
            return (ErrorKind.BAD_TOKEN_IDS,
                    f"token ids outside [0, {self.cfg.vocab_size})")
        if len(prompt) >= self.max_seq:
            # max_seq bounds the position space (block-table width in paged
            # mode, contiguous cache region otherwise) — an oversized prompt
            # can never be admitted
            return (ErrorKind.PROMPT_TOO_LONG,
                    f"prompt length {len(prompt)} >= max_seq {self.max_seq}")
        if self.mode == "paged":
            # pool accounting: a prompt that needs more pages than the pool
            # HOLDS can never admit no matter how long it queues (transient
            # shortage is handled by FIFO backpressure in _admit instead)
            need = self.alloc.pages_for(len(prompt) + 1)
            if need > self.alloc.capacity:
                return (ErrorKind.KV_CAPACITY,
                        f"prompt needs {need} KV pages; pool capacity is "
                        f"{self.alloc.capacity} pages of {self.page_size}")
        if req.max_new_tokens < 1:
            return (ErrorKind.BAD_TOKEN_BUDGET,
                    f"max_new_tokens must be >= 1, got {req.max_new_tokens}")
        if req.deadline_s is not None and req.deadline_s <= 0:
            return (ErrorKind.BAD_DEADLINE,
                    f"deadline_s must be > 0, got {req.deadline_s}")
        return None

    def _admit(self) -> bool:
        with span("serve.admit") as sp:
            admitted = self._admit_queued()
            sp.set_metadata(admitted=admitted)
        return admitted > 0

    def _admit_queued(self) -> int:
        """Fill free slots from the queue; returns how many were admitted."""
        admitted = 0
        for i in range(self.b):
            # a slot that finishes/fails at prefill frees up immediately,
            # so keep pulling from the queue until it sticks or the queue
            # (or the slot's life) runs out
            while (not self.slot_dead[i] and self.slot_req[i] is None
                   and self.queue):
                if self.mode == "paged":
                    head = self.queue[0]
                    # a recovery-resumed request re-prefills over prompt +
                    # already-committed tokens, so charge the extended length
                    need = self.alloc.pages_for(
                        len(head.prompt) + len(head.out_tokens) + 1)
                    if need > self.alloc.free_pages:
                        # page-accounting backpressure: hold the queue in
                        # FIFO order until co-tenants free enough pages
                        # (all-idle implies all pages free, so this cannot
                        # deadlock for a prompt that passed _validate)
                        return admitted
                req = self.queue.pop(0)
                admitted += 1
                self._admit_one(i, req)
        return admitted

    def _admit_one(self, i: int, req: Request):
        req.advance(RequestState.PREFILLING, self.clock())
        self.counters["admitted"] += 1
        self.slot_req[i] = req
        # audit only: slot placement never affects outputs, so replay
        # ignores admit records — but post-mortems want the mapping
        self._journal("admit", rid=req.rid, slot=i)
        if self.mode == "paged":
            self._prefill_off[i] = 0
            self.lengths[i] = 0
            self._prefill_advance(i)
        else:
            self._slot_prefill(i, req)

    def _prefill_tick(self) -> bool:
        """Advance every mid-prefill slot by one chunk (paged mode), or
        retry a whole-prompt prefill whose last attempt failed."""
        progressed = False
        with span("serve.prefill") as sp:
            chunks = 0
            for i in range(self.b):
                req = self.slot_req[i]
                if req is None or req.state is not RequestState.PREFILLING:
                    continue
                chunks += 1
                if self.mode == "paged":
                    progressed |= self._prefill_advance(i)
                else:
                    progressed |= self._slot_prefill(i, req)
            sp.set_metadata(chunks=chunks)
        return progressed

    # -- prefill ------------------------------------------------------------

    def _prefill_advance(self, i: int) -> bool:
        """One guarded prefill-chunk attempt for slot ``i`` (paged mode).
        Nothing is committed on failure: the pool reference, chunk offset
        and length are untouched, so the retry replays the same chunk from
        clean state."""
        req = self.slot_req[i]
        with span("serve.prefill.chunk", rid=req.rid) as sp:
            return self._prefill_chunk(i, req, sp)

    def _prefill_chunk(self, i: int, req: Request, sp) -> bool:
        """The body of ``_prefill_advance``; ``sp`` is the attempt's
        ``serve.prefill.chunk`` span, which gets the chunk's token count."""
        prompt = np.asarray(req.prompt, np.int32)
        if req.out_tokens:
            # recovery resume: requests restored mid-stream re-prefill over
            # prompt + every journaled token, so the KV pool covers positions
            # [0, n+k) and the final chunk samples token index k = len(out)
            # — exactly the key the uninterrupted run would have used.  In
            # normal operation out_tokens is always empty while PREFILLING.
            prompt = np.concatenate(
                [prompt, np.asarray(req.out_tokens, np.int32)])
        n_prompt = int(prompt.size)
        got = self.alloc.ensure(req.rid, n_prompt)
        if got is None:
            self._attempt_failed(i, req, PagesExhausted(
                f"free list cannot cover "
                f"{self.alloc.pages_for(n_prompt)} prompt page(s) for rid "
                f"{req.rid} ({self.alloc.free_pages} free of "
                f"{self.alloc.capacity})"))
            return True
        if got:
            self._write_block_row(i, req.rid)
        off = self._prefill_off[i]
        chunk = self.prefill_chunk or n_prompt
        n = min(chunk, n_prompt - off)
        sp.set_metadata(tokens=n)
        final = off + n >= n_prompt
        fault = (self.injector.poll(req.rid, "prefill")
                 if self.injector is not None else None)
        try:
            pool_in = self.pool
            if fault is not None:
                if fault.kind == "slow_step":
                    self.injector.sleep(fault.seconds)
                elif fault.kind == "process_crash":
                    raise SimulatedCrash(
                        f"simulated crash at prefill of rid {req.rid} "
                        f"(chunk offset {off})")
                elif fault.kind == "exception":
                    raise InjectedFault(
                        f"injected prefill exception for rid {req.rid}")
                elif fault.kind == "cache_corruption":
                    pool_in = self.injector.corrupt_pages(
                        self.pool, self.alloc.pages_of(req.rid))
            tokens = np.zeros((1, chunk), np.int32)
            tokens[0, :n] = prompt[off:off + n]
            positions = off + np.arange(chunk, dtype=np.int32)[None, :]
            valid = (np.arange(chunk) < n)[None, :]
            srow = np.asarray([n - 1], np.int32)
            logits, new_pool = self._paged(
                self.params, jnp.asarray(tokens), jnp.asarray(positions),
                jnp.asarray(valid), pool_in,
                jnp.asarray(self.block_tables[i:i + 1]), jnp.asarray(srow))
            with span("serve.prefill.wait"):
                # the host's one wait on the chunk: sampling and the finite
                # check below read ready logits
                jax.block_until_ready(logits)
            if fault is not None and fault.kind in ("nan_logits", "inf_logits"):
                logits = self.injector.corrupt_logits(logits, fault.kind)
            if final:
                sfault = (self.injector.poll(req.rid, "sampling")
                          if self.injector is not None else None)
                if sfault is not None:
                    if sfault.kind == "slow_step":
                        self.injector.sleep(sfault.seconds)
                    elif sfault.kind == "process_crash":
                        raise SimulatedCrash(
                            f"simulated crash at sampling of rid {req.rid}")
                    elif sfault.kind == "exception":
                        raise InjectedFault(
                            f"injected sampling exception for rid {req.rid}")
                tok = int(self._sample(req, logits)[0])
            else:
                # non-final chunks never sample, but NaN must not reach the
                # committed pool — LQER-style blow-ups surface here, not
                # three chunks later in a co-tenant's decode
                self._check_finite(logits)
        except Exception as e:  # isolated: fails only this request
            self._attempt_failed(i, req, e)
            return True
        self.pool = new_pool
        self._prefill_off[i] = off + n
        self.lengths[i] = off + n
        self._attempt_streak.pop(req.rid, None)
        self.slot_fail_streak[i] = 0
        if final:
            self._finish_prefill(i, req, tok)
        return True

    def _slot_prefill(self, i: int, req: Request) -> bool:
        """One guarded whole-prompt B=1 prefill attempt (stacked / slots
        modes)."""
        stream = np.asarray(req.prompt, np.int32)
        if req.out_tokens:
            # recovery resume — see _prefill_advance for the arithmetic
            stream = np.concatenate(
                [stream, np.asarray(req.out_tokens, np.int32)])
        toks = jnp.asarray(stream[None, :], jnp.int32)
        fault = (self.injector.poll(req.rid, "prefill")
                 if self.injector is not None else None)
        try:
            cache_in = self._fresh_cache()
            if fault is not None:
                if fault.kind == "slow_step":
                    self.injector.sleep(fault.seconds)
                elif fault.kind == "process_crash":
                    raise SimulatedCrash(
                        f"simulated crash at prefill of rid {req.rid}")
                elif fault.kind == "exception":
                    raise InjectedFault(
                        f"injected prefill exception for rid {req.rid}")
                elif fault.kind == "cache_corruption":
                    cache_in = self.injector.corrupt_cache(cache_in)
            logits, new_cache = self._prefill(self.params, toks, cache_in)
            if fault is not None and fault.kind in ("nan_logits", "inf_logits"):
                logits = self.injector.corrupt_logits(logits, fault.kind)
            sfault = (self.injector.poll(req.rid, "sampling")
                      if self.injector is not None else None)
            if sfault is not None:
                if sfault.kind == "slow_step":
                    self.injector.sleep(sfault.seconds)
                elif sfault.kind == "process_crash":
                    raise SimulatedCrash(
                        f"simulated crash at sampling of rid {req.rid}")
                elif sfault.kind == "exception":
                    raise InjectedFault(
                        f"injected sampling exception for rid {req.rid}")
            tok = int(self._sample(req, logits)[0])
        except Exception as e:  # isolated: fails only this request
            self._attempt_failed(i, req, e)
            return True
        if self.mode == "stacked":
            self.stacked_cache = model_lib.insert_cache_row(
                self.stacked_cache, new_cache, i)
        else:
            self.slot_caches[i] = new_cache
        self._attempt_streak.pop(req.rid, None)
        self.slot_fail_streak[i] = 0
        self._finish_prefill(i, req, tok)
        return True

    def _finish_prefill(self, i: int, req: Request, tok: int):
        self._commit_token(req, tok)
        req.first_token_at = self.clock()
        # the prefill-sampled token obeys the SAME termination predicate as
        # decode tokens: max_new_tokens=1 means one token, and an EOS
        # emitted at prefill ends the request
        if self._should_finish(req, tok):
            self._release_slot(i)
            self._finalize(req, RequestState.FINISHED)
        else:
            req.advance(RequestState.DECODING, self.clock())

    # -- stepping -----------------------------------------------------------

    def _step(self) -> bool:
        if self.mode == "slots":
            return self._step_slots()
        active = [i for i in range(self.b)
                  if self.slot_req[i] is not None
                  and self.slot_req[i].state is RequestState.DECODING]
        if not active:
            return False
        with span("serve.decode", step=self.counters["steps"]) as sp:
            return self._batched_step(active, sp)

    def _batched_step(self, active: List[int], sp) -> bool:
        """One batched decode call over the ``active`` slots (paged and
        stacked modes), its one sampling call and its commit.  ``sp`` is the
        step's ``serve.decode`` span; it gets the call's row count."""
        progressed = False
        faults: Dict[int, object] = {}
        if self.injector is not None:
            for i in active:
                f = self.injector.poll(self.slot_req[i].rid, "decode")
                if f is not None:
                    faults[i] = f
                    if f.kind == "slow_step":
                        self.injector.sleep(f.seconds)
                    elif f.kind == "process_crash":
                        raise SimulatedCrash(
                            f"simulated crash at decode of rid "
                            f"{self.slot_req[i].rid}")
        with span("serve.decode.prepare"):
            if self.mode == "paged":
                # decode-boundary crossings allocate before the forward; a
                # dry free list fails ONLY that slot's attempt (deferred
                # retry — a co-tenant may free pages by the next step)
                for i in list(active):
                    req = self.slot_req[i]
                    got = self.alloc.ensure(req.rid,
                                            int(self.lengths[i]) + 1)
                    if got is None:
                        active.remove(i)
                        self._attempt_failed(i, req, PagesExhausted(
                            f"no free page for rid {req.rid} at position "
                            f"{int(self.lengths[i])} "
                            f"({self.alloc.free_pages} free of "
                            f"{self.alloc.capacity})"))
                        progressed = True
                    elif got:
                        self._write_block_row(i, req.rid)
                if not active:
                    return progressed

            # injected exceptions fire "before the forward": the slot drops
            # out of the valid mask (paged) / gets its row rolled back
            # (stacked), so the ONE batched call still runs for everyone
            # else
            excluded = {i for i in active
                        if i in faults and faults[i].kind == "exception"}
            included = [i for i in active if i not in excluded]
            corrupt = [i for i in included
                       if i in faults and faults[i].kind == "cache_corruption"]
            sp.set_metadata(rows=len(included))

            tokens = np.zeros((self.b, 1), np.int32)
            for i in active:
                tokens[i, 0] = self.slot_req[i].out_tokens[-1]
            if self.mode == "paged":
                valid = np.zeros((self.b, 1), bool)
                for i in included:
                    valid[i, 0] = True
                positions = self.lengths.astype(np.int32)[:, None]
                srow = np.zeros((self.b,), np.int32)

        self.counters["decode_calls"] += 1
        try:
            if self.mode == "paged":
                pool_in = self.pool
                for i in corrupt:
                    pool_in = self.injector.corrupt_pages(
                        pool_in, self.alloc.pages_of(self.slot_req[i].rid))
                logits, new_state = self._paged(
                    self.params, jnp.asarray(tokens), jnp.asarray(positions),
                    jnp.asarray(valid), pool_in,
                    jnp.asarray(self.block_tables), jnp.asarray(srow))
            else:
                cache_in = self.stacked_cache
                for i in corrupt:
                    cache_in = self.injector.corrupt_rows(cache_in, i)
                logits, new_state = self._decode(
                    self.params, jnp.asarray(tokens), cache_in)
            with span("serve.decode.wait"):
                # the host's one wait on the step: the sampling below
                # reads ready logits
                jax.block_until_ready(logits)
        except Exception as e:
            # the one batched call itself died: no slot committed anything,
            # every active request gets a (retryable) failed attempt
            for i in active:
                self._attempt_failed(i, self.slot_req[i], e)
            return True

        # per-row outcomes first (no engine mutation), THEN the state
        # commit+rollback, THEN the bookkeeping — _slot_failure frees pages,
        # which must not happen before the rollback reads them
        outcomes: Dict[int, Tuple[str, object]] = {}
        with span("serve.sample") as ssp:
            sampled: List[Optional[Request]] = [None] * self.b
            for i in active:
                req = self.slot_req[i]
                f = faults.get(i)
                if i in excluded:
                    outcomes[i] = ("fail", InjectedFault(
                        f"injected decode exception for rid {req.rid}"))
                    continue
                if f is not None and f.kind in ("nan_logits", "inf_logits"):
                    logits = logits.at[i].set(
                        self.injector.corrupt_logits(logits[i], f.kind))
                sfault = (self.injector.poll(req.rid, "sampling")
                          if self.injector is not None else None)
                if sfault is not None:
                    if sfault.kind == "slow_step":
                        self.injector.sleep(sfault.seconds)
                    elif sfault.kind == "process_crash":
                        # BaseException: escapes the step — nothing below
                        # commits
                        raise SimulatedCrash(
                            f"simulated crash at sampling of rid {req.rid}")
                    elif sfault.kind == "exception":
                        outcomes[i] = ("fail", InjectedFault(
                            f"injected sampling exception for rid "
                            f"{req.rid}"))
                        continue
                sampled[i] = req
            if any(r is not None for r in sampled):
                # every row of the step in one program over the full
                # (B, 1, V) logits (one compiled shape), read once; the
                # non-finite guard stays per row
                self.counters["sample_calls"] += 1
                try:
                    got = self._sample_rows(logits, sampled)
                except Exception as e:
                    # the one sampling call died: every row it held gets a
                    # (retryable) failed attempt, as for the step's call
                    got = [e] * self.b
                for i, req in enumerate(sampled):
                    if req is not None:
                        outcomes[i] = ("fail" if isinstance(got[i], Exception)
                                       else "ok", got[i])
            ssp.set_metadata(rows=len(included))

        with span("serve.commit") as csp:
            failed = [i for i in active if outcomes[i][0] == "fail"]
            if self.mode == "paged":
                # a failed attempt commits nothing: corrupted slots get their
                # pages restored from the pre-step pool (page-disjointness
                # makes the restore exact); excluded slots were never
                # written (valid mask → null page); other failures keep
                # their length, so the retry overwrites the same position
                rollback = sorted({
                    p for i in failed if i in corrupt
                    for p in self.alloc.pages_of(self.slot_req[i].rid)})
                if rollback:
                    ids = jnp.asarray(rollback, jnp.int32)
                    new_state = jax.tree.map(
                        lambda new, old: new.at[:, ids].set(old[:, ids]),
                        new_state, self.pool)
                self.pool = new_state
            else:
                # stacked rows all advance in the batched call — roll back
                # every failed slot's row to the pre-step cache
                if failed:
                    ids = jnp.asarray(failed, jnp.int32)
                    new_state = jax.tree.map(
                        lambda new, old: new.at[:, ids].set(old[:, ids]),
                        new_state, self.stacked_cache)
                self.stacked_cache = new_state

            committed = 0
            for i in active:
                req = self.slot_req[i]
                kind, val = outcomes[i]
                # a token OR a terminal/retry record is progress
                progressed = True
                if kind == "fail":
                    self._attempt_failed(i, req, val)
                    continue
                self._attempt_streak.pop(req.rid, None)
                self.slot_fail_streak[i] = 0
                self._commit_token(req, val)
                committed += 1
                if self.mode == "paged":
                    self.lengths[i] += 1
                if self._should_finish(req, val):
                    self._release_slot(i)
                    self._finalize(req, RequestState.FINISHED)
            csp.set_metadata(tokens=committed)
        return progressed

    def _step_slots(self) -> bool:
        """Legacy per-slot decode loop for families whose caches carry a
        shared scalar offset (vlm/hybrid/moe) — see docs/serving.md."""
        progressed = False
        for i, req in enumerate(self.slot_req):
            if req is None or req.state is not RequestState.DECODING:
                continue
            last = jnp.asarray([[req.out_tokens[-1]]], jnp.int32)
            fault = (self.injector.poll(req.rid, "decode")
                     if self.injector is not None else None)
            self.counters["decode_calls"] += 1
            try:
                cache_in = self.slot_caches[i]
                if fault is not None:
                    if fault.kind == "slow_step":
                        self.injector.sleep(fault.seconds)
                    elif fault.kind == "process_crash":
                        raise SimulatedCrash(
                            f"simulated crash at decode of rid {req.rid}")
                    elif fault.kind == "exception":
                        raise InjectedFault(
                            f"injected decode exception for rid {req.rid}")
                    elif fault.kind == "cache_corruption":
                        cache_in = self.injector.corrupt_cache(cache_in)
                out = self._decode(self.params, last, cache_in)
                if self._decode_stats:
                    logits, new_cache, stats = out
                    self._ep_dropped += int(stats["ep_dropped"])
                else:
                    logits, new_cache = out
                if fault is not None and fault.kind in ("nan_logits", "inf_logits"):
                    logits = self.injector.corrupt_logits(logits, fault.kind)
                sfault = (self.injector.poll(req.rid, "sampling")
                          if self.injector is not None else None)
                if sfault is not None:
                    if sfault.kind == "slow_step":
                        self.injector.sleep(sfault.seconds)
                    elif sfault.kind == "process_crash":
                        raise SimulatedCrash(
                            f"simulated crash at sampling of rid {req.rid}")
                    elif sfault.kind == "exception":
                        raise InjectedFault(
                            f"injected sampling exception for rid {req.rid}")
                tok = int(self._sample(req, logits)[0])
            except Exception as e:  # isolated: fails only this request
                self._attempt_failed(i, req, e)
                progressed = True
                continue
            self.slot_caches[i] = new_cache
            self._attempt_streak.pop(req.rid, None)
            self.slot_fail_streak[i] = 0
            self._commit_token(req, tok)
            progressed = True
            if self._should_finish(req, tok):
                self._release_slot(i)
                self._finalize(req, RequestState.FINISHED)
        return progressed

    # -- shared attempt / sampling helpers ----------------------------------

    def _attempt_failed(self, i: int, req: Request, e: BaseException):
        """Account one failed attempt.  Within the retry budget the request
        stays in its slot and the SAME phase replays next engine step from
        clean committed state (nothing was committed for it); past the
        budget it becomes a FAILED record via ``_slot_failure``."""
        streak = self._attempt_streak.get(req.rid, 0)
        if streak >= self.max_retries:
            self._attempt_streak.pop(req.rid, None)
            self._slot_failure(i, req, e)
            return
        self._attempt_streak[req.rid] = streak + 1
        req.retries += 1
        self.counters["retries"] += 1
        if self.retry_backoff_s > 0:
            self.sleep_fn(self.retry_backoff_s * (2 ** streak))

    def _check_finite(self, logits):
        n_nan, n_inf = jax.device_get(count_non_finite(logits))
        if n_nan or n_inf:
            raise non_finite_error("prefill-chunk", int(n_nan), int(n_inf),
                                   logits.size)

    def _sample_rows(self, logits, reqs):
        """One sampling program over ``logits`` ((B, V), or (B, S, V)
        sampled at each row's last position) and one host read.
        ``reqs[b]`` is row b's request, or None for a row whose result
        nobody reads.  Returns, per row, its token (int), its
        :class:`NonFiniteLogitsError` if the row holds NaN or Inf, or None
        where there is no request.

        Keys depend only on (engine seed, rid, token index): a request's
        tokens are invariant to slot placement, co-tenants, page layout,
        and retries — the property the chaos suite's bitwise-parity
        asserts rely on."""
        rows = np.zeros((len(reqs), 3), np.uint32)
        for b, req in enumerate(reqs):
            if req is not None:
                rows[b] = (req.rid, len(req.out_tokens),
                           np.float32(req.temperature).view(np.uint32))
        toks, n_nan, n_inf = jax.device_get(
            sample_rows_packed(logits, self.base_key, rows))
        return [None if req is None
                else non_finite_error("sampling", int(n_nan[b]),
                                      int(n_inf[b]), logits.shape[-1])
                if n_nan[b] or n_inf[b] else int(toks[b])
                for b, req in enumerate(reqs)]

    def _sample(self, req: Request, logits):
        """One request's token from ``logits`` ((1, V), or (1, S, V) sampled
        at its last position), as a (1,) int32 array; raises
        :class:`NonFiniteLogitsError` on NaN or Inf."""
        got, = self._sample_rows(logits, [req])
        if isinstance(got, NonFiniteLogitsError):
            raise got
        return np.asarray([got], np.int32)

    def _should_finish(self, req: Request, tok: int) -> bool:
        total = len(req.prompt) + len(req.out_tokens)
        return (
            len(req.out_tokens) >= req.max_new_tokens
            or (self.eos_id is not None and tok == self.eos_id)
            or total >= self.max_seq - 1
        )

    # -- crash safety: journal hooks, snapshot, restore ----------------------

    def _journal(self, kind: str, **fields):
        if self.journal is not None:
            self.journal.append(kind, **fields)

    def _commit_token(self, req: Request, tok: int):
        """Durably journal the token at its stream index, THEN append it to
        the request — the WAL ordering that makes delivery exactly-once:
        a crash between the two replays the journaled token; a crash before
        the journal write never shows the token anywhere."""
        tok = int(tok)
        if self.journal is not None and req.rid in self._journaled_submits:
            self.journal.append("token", rid=req.rid,
                                idx=len(req.out_tokens), token=tok)
        req.out_tokens.append(tok)

    def _state_tree(self):
        """The mode-specific array state a snapshot persists (and the
        ``like`` tree a restore loads against)."""
        if self.mode == "paged":
            return {"pool": self.pool,
                    "block_tables": np.array(self.block_tables),
                    "lengths": np.array(self.lengths)}
        if self.mode == "stacked":
            return {"cache": self.stacked_cache}
        return {"slot_caches": self.slot_caches}

    def snapshot(self) -> Optional[str]:
        """Persist the full decode state through the atomic checkpoint path
        (``.tmp``-rename, keep-``snapshot_keep`` rotation): the KV pool /
        caches, the page allocator + block tables, slot lifecycle states,
        chunked-prefill offsets, queue order and counters.  Must run at an
        engine-step boundary — ``run()`` calls it every ``snapshot_every``
        steps, when no forward is in flight and lengths / pool / allocator
        are mutually consistent.  Returns the checkpoint path, or None when
        no ``snapshot_dir`` is configured."""
        if self._ckpt is None:
            return None
        meta = {
            "mode": self.mode,
            "seed": self.seed,
            "batch_slots": self.b,
            "max_seq": self.max_seq,
            "page_size": self.page_size,
            "prefill_chunk": self.prefill_chunk,
            **self.kv_spec.to_meta(),
            "counters": dict(self.counters),
            "slot_dead": [bool(x) for x in self.slot_dead],
            "slot_fail_streak": [int(x) for x in self.slot_fail_streak],
            "queue": [q.rid for q in self.queue],
            "journal_seq": None if self.journal is None else self.journal.seq,
            "slots": [
                None if req is None else {
                    "rid": req.rid,
                    "state": req.state.value,
                    "n_out": len(req.out_tokens),
                    "prefill_off": (self._prefill_off[i]
                                    if self.mode == "paged" else 0),
                }
                for i, req in enumerate(self.slot_req)
            ],
        }
        if self.mode == "paged":
            meta["alloc"] = self.alloc.to_state()
        tree = {
            "state": self._state_tree(),
            # the variable-length JSON rides as a uint8 leaf; restore reads
            # it back via load_leaf because the like-tree protocol needs
            # fixed shapes
            "meta": np.frombuffer(json.dumps(meta).encode(), np.uint8),
        }
        step = self.counters["steps"]
        path = self._ckpt.save(step, tree)
        self._journal("snapshot", step=step, path=str(path))
        return path

    def _clear_slot_state(self, i: int, rid: int):
        """Drop a restored slot whose snapshot KV cannot be reused (its
        owner terminated after the snapshot, or the journal is ahead of the
        snapshot for this rid)."""
        if self.mode == "paged":
            if self.alloc.holds(rid):
                self.alloc.free(rid)
            self.block_tables[i, :] = 0
            self.lengths[i] = 0
            self._prefill_off[i] = 0
        elif self.mode == "slots":
            self.slot_caches[i] = self._fresh_cache()

    @classmethod
    def restore(cls, cfg, params, journal_path, *,
                snapshot_dir: Optional[str] = None,
                snapshot_every: int = 0, snapshot_keep: int = 3,
                fsync: bool = True, **engine_kwargs) -> "ServeEngine":
        """Recover a crashed engine: replay the write-ahead journal (the
        request truth — what exists, what was delivered, what terminated),
        then graft on the newest restorable snapshot (the KV accelerator).

        - A slot whose journaled token count equals the snapshot's resumes
          IN PLACE from the restored pool/caches; mid-prefill slots resume
          at their chunk offset.
        - Anything the journal knows that the snapshot does not — tokens
          committed after the snapshot, requests still queued, no usable
          snapshot at all — re-enqueues for a re-prefill over ``prompt +
          journaled tokens``.  Sampling keys depend only on (seed, rid,
          token index), so the continuation is bitwise identical either
          way; already-journaled tokens are never re-delivered.
        - A missing / stale / corrupt snapshot degrades to journal-only
          recovery with a warning; a corrupt journal interior raises
          :class:`~repro.serve.journal.JournalCorruption` instead (replay
          past lost records could double-deliver).

        ``engine_kwargs`` passes through operational knobs (injector,
        clock, retry budgets, kernel_impl, ...); the shape config
        (batch_slots, max_seq, seed, paging) always comes from the
        journal's ``open`` record — recovery with mismatched shapes cannot
        be bitwise and is refused at the source."""
        if "journal" in engine_kwargs:
            raise JournalError("restore() owns the journal; do not pass one")
        if "kv_spec" in engine_kwargs:
            raise JournalError(
                "restore() reads the KV spec from the journal's open "
                "record; do not pass kv_spec")
        replay = read_journal(journal_path)
        col = collate(replay.records)
        if not col.opens:
            raise JournalError(
                f"journal {journal_path} has no open record — not a serve "
                f"journal (or its head was lost)")
        opened = col.opens[0]
        eng = cls(cfg, params,
                  batch_slots=int(opened["batch_slots"]),
                  max_seq=int(opened["max_seq"]),
                  eos_id=opened["eos_id"],
                  seed=int(opened["seed"]),
                  page_size=int(opened["page_size"]),
                  kv_pages=opened["kv_pages"],
                  prefill_chunk=opened["prefill_chunk"],
                  kv_spec=KVSpec.from_meta(opened),
                  snapshot_dir=snapshot_dir,
                  snapshot_every=snapshot_every,
                  snapshot_keep=snapshot_keep,
                  **engine_kwargs)
        if opened["mode"] != eng.mode:
            raise JournalError(
                f"journal was written by a {opened['mode']!r}-mode engine "
                f"but cfg {cfg.name!r} resolves to {eng.mode!r}")
        now = eng.clock()

        def make_req(rid: int) -> Request:
            sub = col.submits[rid]
            req = Request(rid=rid,
                          prompt=np.asarray(sub["prompt"], np.int32),
                          max_new_tokens=int(sub["max_new_tokens"]),
                          temperature=float(sub["temperature"]),
                          deadline_s=sub.get("deadline_s"))
            req.out_tokens = list(col.tokens.get(rid, []))
            # deadlines re-anchor at restore: the crash was the engine's
            # fault, so a recovered request gets its full budget back
            req.submitted_at = now
            return req

        # terminal records journaled before the crash re-materialize as
        # records (their phase timings died with the process)
        for rid, term in col.terminals.items():
            toks = col.tokens.get(rid, [])
            eng.records[rid] = RequestRecord(
                rid=rid, status=RequestState(term["status"]),
                out_tokens=list(toks),
                prompt_tokens=len(col.submits[rid]["prompt"]),
                new_tokens=len(toks), retries=int(term.get("retries", 0)),
                error_kind=term.get("error_kind"), error=term.get("error"),
                timings={})
        eng._journaled_submits = set(col.submits)
        eng._journaled_terminals = set(col.terminals)
        # re-attach the journal (truncating any torn tail) BEFORE any
        # restore-time finalization, so e.g. an already-satisfied request
        # journals its terminal record like any other
        eng.journal = JournalWriter.reopen(journal_path, replay, fsync=fsync)

        def settle(req: Request) -> bool:
            """A journaled stream that already satisfies the termination
            predicate (the crash fell between the last token commit and
            its terminal record) finalizes now — never re-decodes."""
            if req.out_tokens and eng._should_finish(req,
                                                     req.out_tokens[-1]):
                req.advance(RequestState.PREFILLING, now)
                req.first_token_at = now
                eng._finalize(req, RequestState.FINISHED)
                return True
            return False

        # -- snapshot graft: best effort; any damage degrades to journal-
        # only recovery (slower — full re-prefills — never incorrect)
        snap_step, state, meta = None, None, None
        if snapshot_dir is not None:
            try:
                step, tree = eng._ckpt.restore_latest(
                    {"state": eng._state_tree()})
                if step is not None:
                    raw = load_leaf(eng._ckpt.dir / f"step_{step:08d}",
                                    "meta")
                    meta = json.loads(np.asarray(raw, np.uint8)
                                      .tobytes().decode())
                    state = tree["state"]
                    snap_step = step
            except (CheckpointError, ValueError) as e:
                warnings.warn(f"snapshot restore failed ({e}); recovering "
                              f"from the journal alone")
                snap_step, state, meta = None, None, None
        if meta is not None and (meta.get("mode") != eng.mode
                                 or meta.get("seed") != eng.seed
                                 or meta.get("batch_slots") != eng.b
                                 or KVSpec.from_meta(meta) != eng.kv_spec):
            warnings.warn("snapshot belongs to a different engine config; "
                          "recovering from the journal alone")
            snap_step, state, meta = None, None, None
        if meta is not None and eng.mode == "paged":
            try:
                restored_alloc = PageAllocator.from_state(meta["alloc"])
            except (KeyError, ValueError, TypeError) as e:
                warnings.warn(f"snapshot allocator state is corrupt ({e}); "
                              f"recovering from the journal alone")
                snap_step, state, meta = None, None, None

        placed = set()
        if meta is not None:
            eng.counters = dict(meta["counters"])
            eng.slot_dead = [bool(x) for x in meta["slot_dead"]]
            eng.slot_fail_streak = [int(x) for x in meta["slot_fail_streak"]]
            if eng.mode == "paged":
                eng.alloc = restored_alloc
                eng.pool = state["pool"]
                if eng.mesh is not None:
                    # snapshot leaves come back host-committed; re-apply the
                    # replicated-then-data-sharded placement so the restored
                    # engine decodes under the same shardings it saved with
                    from repro.distributed import tp as tp_lib

                    eng.pool = tp_lib.shard_kv_pool(eng.pool, eng.mesh)
                eng.block_tables = np.asarray(state["block_tables"],
                                              np.int32).copy()
                eng.lengths = np.asarray(state["lengths"], np.int32).copy()
            elif eng.mode == "stacked":
                eng.stacked_cache = state["cache"]
            else:
                eng.slot_caches = list(state["slot_caches"])
            for i, s in enumerate(meta["slots"]):
                if s is None:
                    continue
                rid = int(s["rid"])
                k = len(col.tokens.get(rid, []))
                if rid in col.terminals:
                    # terminated after the snapshot — only its pages matter
                    eng._clear_slot_state(i, rid)
                elif (s["state"] == RequestState.DECODING.value
                        and s["n_out"] == k and k > 0):
                    req = make_req(rid)
                    if settle(req):
                        eng._clear_slot_state(i, rid)
                        placed.add(rid)
                        continue
                    # journal and snapshot agree: continue decoding in place
                    req.advance(RequestState.PREFILLING, now)
                    req.first_token_at = now
                    req.advance(RequestState.DECODING, now)
                    eng.slot_req[i] = req
                    if eng.mode == "paged":
                        eng._prefill_off[i] = int(s.get("prefill_off", 0))
                    placed.add(rid)
                elif (s["state"] == RequestState.PREFILLING.value
                        and s["n_out"] == 0 and k == 0):
                    # mid-prefill at the snapshot: the pool already holds
                    # chunks [0, prefill_off); resume the next chunk
                    req = make_req(rid)
                    req.advance(RequestState.PREFILLING, now)
                    eng.slot_req[i] = req
                    if eng.mode == "paged":
                        eng._prefill_off[i] = int(s.get("prefill_off", 0))
                    placed.add(rid)
                else:
                    # journal is AHEAD of the snapshot for this rid (tokens
                    # committed after it): the snapshot KV is stale — drop
                    # it and re-prefill prompt + journaled tokens
                    eng._clear_slot_state(i, rid)

        # everything pending and not resumed in place re-enqueues in the
        # original submission order (includes the journal-only path);
        # already-satisfied streams finalize instead
        requeued = []
        for rid in col.pending():
            if rid in placed:
                continue
            req = make_req(rid)
            if settle(req):
                placed.add(rid)
            else:
                eng.queue.append(req)
                requeued.append(rid)

        eng._journal(
            "recover", snapshot_step=snap_step, torn_tail=replay.torn_tail,
            resumed=sorted(placed), requeued=requeued)
        return eng

    # -- failure handling / lifecycle ---------------------------------------

    def _slot_failure(self, i: int, req: Request, e: BaseException):
        """Quarantine the slot (release it — paged mode frees the pages —
        and bump the failure streak; ``slot_failure_limit`` consecutive
        request failures kill it) and fail ONLY this request with the
        captured error."""
        kind, msg = _classify_error(e)
        self._release_slot(i)
        self.slot_fail_streak[i] += 1
        self.counters["slot_failures"] += 1
        if self.slot_fail_streak[i] >= self.slot_failure_limit:
            self.slot_dead[i] = True
        self._finalize(req, RequestState.FAILED, kind, msg)

    def _write_block_row(self, i: int, rid: int):
        row = np.zeros((self.pages_per_slot,), np.int32)
        pages = self.alloc.pages_of(rid)
        row[:len(pages)] = pages
        self.block_tables[i] = row

    def _release_slot(self, i: int):
        req = self.slot_req[i]
        self.slot_req[i] = None
        if req is not None:
            self._attempt_streak.pop(req.rid, None)
        if self.mode == "paged":
            # terminal transition returns the pages; freed pages may hold
            # stale values, which is safe because a new owner rewrites every
            # position below its length and the mask hides the rest
            if req is not None:
                self.alloc.free(req.rid)
            self.block_tables[i, :] = 0
            self.lengths[i] = 0
            self._prefill_off[i] = 0
        elif self.mode == "slots":
            self.slot_caches[i] = self._fresh_cache()
        # stacked: nothing to reset — admission overwrites the whole row

    def _fresh_cache(self):
        return model_lib.init_cache(self.cfg, 1, self.max_seq,
                                    dtype=jnp.float32, kv_spec=self.kv_spec)

    def _finalize(self, req: Request, status: RequestState,
                  error_kind: Optional[str] = None,
                  error: Optional[str] = None):
        self._attempt_streak.pop(req.rid, None)
        req.error_kind = error_kind
        req.error = error
        # WAL: the terminal record is durable before it becomes visible in
        # self.records — and a rid terminates in the journal exactly once,
        # even if it was already terminal at restore time
        if (self.journal is not None and req.rid in self._journaled_submits
                and req.rid not in self._journaled_terminals):
            self._journaled_terminals.add(req.rid)
            self.journal.append(
                "terminal", rid=req.rid, status=status.value,
                error_kind=(None if error_kind is None else str(error_kind)),
                error=error, retries=req.retries,
                n_tokens=len(req.out_tokens))
        req.advance(status, self.clock())
        self.records[req.rid] = RequestRecord.from_request(req)
        self.counters[status.value] = self.counters.get(status.value, 0) + 1

    def _expire_deadlines(self) -> bool:
        now = self.clock()
        progressed = False
        for req in [q for q in self.queue]:
            at = req.deadline_at()
            if at is not None and now >= at:
                self.queue.remove(req)
                self._finalize(req, RequestState.TIMED_OUT,
                               ErrorKind.DEADLINE,
                               f"deadline ({req.deadline_s:.3f}s) expired "
                               f"while queued")
                progressed = True
        for i, req in enumerate(self.slot_req):
            if req is None:
                continue
            at = req.deadline_at()
            if at is not None and now >= at:
                self._release_slot(i)
                self._finalize(req, RequestState.TIMED_OUT,
                               ErrorKind.DEADLINE,
                               f"deadline ({req.deadline_s:.3f}s) expired "
                               f"after {len(req.out_tokens)} tokens")
                progressed = True
        return progressed

    def _stall_reason(self) -> Optional[str]:
        pending = bool(self.queue) or any(r is not None for r in self.slot_req)
        if pending and all(self.slot_dead):
            return (f"all {self.b} slots dead "
                    f"(slot_failure_limit={self.slot_failure_limit}) with "
                    f"{len(self.queue)} request(s) still queued")
        if self._steps_since_progress > self.stall_patience:
            return (f"no progress for {self._steps_since_progress} steps "
                    f"(stall_patience={self.stall_patience})")
        return None

    def _drain_unfinished(self, kind: str, msg: str):
        """Every request still queued or in a slot becomes a TIMED_OUT
        record — nothing silently vanishes from ``run()``'s return."""
        for i, req in enumerate(self.slot_req):
            if req is not None:
                self._release_slot(i)
                self._finalize(req, RequestState.TIMED_OUT, kind,
                               f"{msg}; in flight with "
                               f"{len(req.out_tokens)} token(s)")
        while self.queue:
            req = self.queue.pop(0)
            self._finalize(req, RequestState.TIMED_OUT, kind,
                           f"{msg}; still queued")
