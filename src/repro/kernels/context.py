"""Explicit, per-layer kernel execution config: :class:`KernelContext`.

The W4A4+LRC dispatch layer used to keep its execution config — the
regime-keyed plan table, the VMEM working-set budgets, the measured-winner
overlay — in module-global mutable state on ``kernels/ops.py``, which
forced one plan table and one budget on every layer of every model in the
process (two ``ServeEngine``s could race each other's globals).  The paper's
pipeline wants the opposite: *per-layer* decisions, because each projection
has its own (K, N, R) shape, rank fraction and rotation flag.

:class:`KernelContext` is the replacement: an immutable (frozen, hashable —
safe as pytree-static metadata and as a jit static argument) value object
holding

  * the regime plan table (decode / mixed / prefill → path + BM/BN/BK/BR),
  * the fused / prologue VMEM working-set budgets,
  * the default kernel impl ("auto" | "fused" | "chained" | "unfused"),
  * the interpret flag (None = auto: interpret on CPU, compiled on TPU),
  * optional per-layer plan overrides keyed by layer name or (K, N, R)
    shape, taking precedence over the table.

Construction::

    ctx = KernelContext()                          # analytic defaults
    ctx = KernelContext.from_json("results/block_table.json")
    ctx = ctx.with_vmem_budgets(fused=4 << 20)     # builders return copies
    ctx = ctx.with_layer_overrides({"mlp/wd": {"path": "chained", "bm": 8}})

Resolution::

    plan = ctx.resolve_plan(m, k, n, r, rotate=True, layer="mlp/wd")
    print(ctx.explain(m, k, n, r, rotate=True))    # per-regime report

``kernels/ops.py`` threads a ``ctx=`` through ``w4a4_lrc_forward`` /
``select_plan`` / ``resolve_plan`` (``None`` → the process-default
context).  The old global setters (``load_block_table`` /
``set_vmem_budgets``) finished their deprecation window and are gone.

Activation-scale granularity rides the same resolution:
``resolve_plan(..., act_group=g)`` snaps BK to a power-of-two multiple of
``lcm(g, 128)`` (K-chunks hold whole scale groups and stay lane tiles),
adds the per-group (M, K/g) scale plane to the VMEM working-set model, and
demotes a path only when no such tiling fits — ``explain(...,
act_group=g)`` reports the snap and any granularity-driven demotion.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
from pathlib import Path
from typing import NamedTuple, Optional

from repro.kernels.rowops import (LANES, default_proj_tiles, k_tile,
                                  lane_tile,
                                  round_pow2 as _round_pow2,
                                  snap_bk_to_group)

# Default working-set budget of the two-kernel chain's prologue (x row slab
# + rotated-row scratch + xq/sx/xv outputs + double-buffered V tiles).
# Historically this was the ceiling on a WHOLE-VMEM V; V now streams in
# (bk, br) tiles, so the budget gates the row slab instead and the 8 MB
# figure keeps the same "three quarters of a useful VMEM half" intent.
PROLOGUE_V_BYTES_MAX = 8 * 1024 * 1024

# Default working-set ceiling for the single-kernel fused path (resident
# scratch + double-buffered streamed blocks).  ~¾ of a v5e core's 16 MB
# VMEM, leaving room for Mosaic's pipelining overheads.  Tiles shrink to
# fit this before the path demotes (see KernelContext.resolve_plan).
FUSED_VMEM_BYTES_MAX = 12 * 1024 * 1024

# Analytic default execution plans: the kernel path plus (BM, BN, BK, BR).
# decode  (M ≤ 32):  single-kernel fused — the decode hot path is
#                    activation+weight-HBM-bound; tiny M tile, wide N×K
#                    tiles stream the weight matrix.
# mixed   (M ≤ 512): single-kernel fused, balanced tiles.
# prefill (M > 512): single-kernel fused as well since the K-split grid —
#                    the (BM, K) f32 row slab that used to crowd VMEM now
#                    either fits (resident) or is traded for one extra x
#                    read (streamed); the GEMM is MXU-bound at these M, and
#                    fused ≤ chained on activation bytes at every M.
DEFAULT_BLOCK_TABLE = {
    "decode": dict(path="fused", bm=16, bn=256, bk=512, br=512),
    "mixed": dict(path="fused", bm=128, bn=128, bk=256, br=512),
    "prefill": dict(path="fused", bm=256, bn=256, bk=256, br=512),
}

KERNEL_PATHS = ("fused", "chained", "unfused")
IMPLS = ("auto",) + KERNEL_PATHS
REGIMES = tuple(sorted(DEFAULT_BLOCK_TABLE))
VARIANTS = ("resident", "streamed")

_TILE_DIMS_REQUIRED = ("bm", "bn", "bk")
_TILE_DIMS_ALL = ("bm", "bn", "bk", "br")
_PLAN_KEYS = ("path", "bm", "bn", "bk", "br", "variant")
_VMEM_KEYS = ("fused_bytes_max", "prologue_bytes_max")


class Plan(NamedTuple):
    """A resolved execution plan: kernel path, tile dims, and (fused only)
    the prologue variant ("resident" | "streamed")."""
    path: str
    bm: int
    bn: int
    bk: int
    br: int
    variant: Optional[str] = None


def gemm_regime(m: int) -> str:
    if m <= 32:
        return "decode"
    if m <= 512:
        return "mixed"
    return "prefill"


# ---------------------------------------------------------------------------
# VMEM working-set byte models + shrink-to-fit (pure functions of a budget)
# ---------------------------------------------------------------------------


def fused_vmem_bytes(k: int, r: int, bm: int, bn: int, bk: int, br: int,
                     resident: bool, act_group: int = None) -> int:
    """Worst-case VMEM working set of the K-split fused kernel: resident
    scratch plus double-buffered streamed blocks.  ``act_group`` swaps the
    (bm, 1) per-token scale for the (bm, K/g) scale plane."""
    k_pad = k + (-k) % bk
    r_pad = (r + (-r) % br) if r else 0
    n_s = 1 if act_group is None else k_pad // act_group
    res = (
        bm * k_pad          # xq int8 residency
        + bm * n_s * 4      # sx (per-token column or per-group scale plane)
        + bm * bn * 4       # GEMM accumulator (int32 or grouped f32)
        + bk * bk           # int8 even/odd permutation of an xq chunk
    )
    if r:
        res += bm * r_pad * 4  # xv accumulator
    if resident:
        res += bm * k_pad * 4  # f32 (rotated) row slab
    stream = (
        bm * bk * 4         # x chunk (f32 upper bound)
        + (bk // 2) * bn    # packed-weight chunk
        + bn * 4            # sw
        + bm * bn * 4       # out tile
    )
    if r:
        stream += bk * br * 4 + bn * r_pad * 4  # V tile + U slab
    return res + 2 * stream


def prologue_vmem_bytes(k: int, r: int, bm: int, bk: int, br: int,
                        rotate: bool, act_group: int = None) -> int:
    """Working set of the standalone (chained-path) prologue kernel: the x
    row slab, the rotated-row scratch, the xq/sx/xv outputs and the
    double-buffered streamed V tiles."""
    k_pad = k + (-k) % bk if r else k
    r_pad = (r + (-r) % br) if r else 0
    n_s = 1 if act_group is None else k_pad // act_group
    # x slab + q out + s out (per-token column or per-group scale plane)
    b = bm * k_pad * 4 + bm * k_pad + bm * n_s * 4
    if rotate:
        b += bm * k_pad * 4  # rotated-row scratch
    if r:
        b += bm * r_pad * 4 + 2 * (bk * br * 4)  # xv out + V tiles
    return b


def _shrink_to_fit(bytes_fn, tiles: dict, mins: dict, budget: int):
    """Greedily halve tile dims (largest byte saving first, deterministic
    tie-break in ``mins`` key order) until ``bytes_fn(**tiles)`` fits
    ``budget``.  Returns the fitted tiles dict or None."""
    tiles = dict(tiles)
    while bytes_fn(**tiles) > budget:
        best = None
        for dim in mins:
            if tiles[dim] // 2 < mins[dim]:
                continue
            cand = dict(tiles)
            cand[dim] //= 2
            got = bytes_fn(**cand)
            if best is None or got < best[0]:
                best = (got, dim)
        if best is None:
            return None
        tiles[best[1]] //= 2
    return tiles


def _fit_fused(k: int, r: int, bm: int, bn: int, bk: int, br: int,
               rotate: bool, budget: int, variant_pin: str = None,
               act_group: int = None):
    """Feasible (bm, bn, bk, br, variant) for the fused kernel under
    ``budget``, shrinking tiles as needed; None when nothing fits.  The
    resident prologue is preferred (one x read); the streamed variant
    (rotate=False only) trades an extra x read for dropping the f32 row
    slab.  ``variant_pin`` restricts the search to one variant (a
    table/override pin); rotation still forces the resident slab.  With
    group-wise scales (``act_group``) BK starts snapped to a power-of-two
    multiple of ``lcm(g, 128)`` and can shrink no further than that unit —
    the halving search stays closed over the chunks-hold-whole-groups
    constraint."""
    if act_group is not None:
        bk = snap_bk_to_group(bk, act_group)
    mins = dict(bk=snap_bk_to_group(LANES, act_group) if act_group else LANES,
                br=LANES, bn=LANES, bm=min(bm, 8))
    variants = ("resident",) if rotate else ("resident", "streamed")
    if variant_pin is not None and not (rotate and variant_pin == "streamed"):
        variants = (variant_pin,)
    for variant in variants:
        def bytes_fn(bm, bn, bk, br, _res=(variant == "resident")):
            return fused_vmem_bytes(k, r, bm, bn, bk, br, _res,
                                    act_group=act_group)
        fit = _shrink_to_fit(bytes_fn, dict(bm=bm, bn=bn, bk=bk, br=br),
                             mins, budget)
        if fit is not None:
            return Plan("fused", fit["bm"], fit["bn"], fit["bk"], fit["br"],
                        variant)
    return None


def _fit_chained(k: int, r: int, bm: int, bn: int, bk: int, br: int,
                 rotate: bool, budget: int, act_group: int = None):
    """Feasible chained-path plan under the prologue budget, or None."""
    if act_group is not None:
        bk = snap_bk_to_group(bk, act_group)
    mins = dict(bk=snap_bk_to_group(LANES, act_group) if act_group else LANES,
                br=LANES, bm=min(bm, 8))

    def bytes_fn(bm, bk, br):
        return prologue_vmem_bytes(k, r, bm, bk, br, rotate,
                                   act_group=act_group)

    fit = _shrink_to_fit(bytes_fn, dict(bm=bm, bk=bk, br=br), mins, budget)
    if fit is None:
        return None
    return Plan("chained", fit["bm"], bn, fit["bk"], fit["br"], None)


# ---------------------------------------------------------------------------
# validation + freezing helpers (dict in, hashable tuples stored)
# ---------------------------------------------------------------------------


def _check_tile(where: str, dim: str, val) -> None:
    if not isinstance(val, int) or isinstance(val, bool) or val <= 0:
        raise ValueError(f"{where} tile dim {dim!r} must be a positive "
                         f"integer, got {val!r}")


def _validate_table_entry(regime: str, entry, where="block table") -> None:
    if not isinstance(entry, dict):
        raise ValueError(f"regime {regime!r} in {where} must map to an "
                         f"object, got {type(entry).__name__}")
    if entry.get("path") not in KERNEL_PATHS:
        raise ValueError(
            f"unknown kernel path {entry.get('path')!r} for regime "
            f"{regime!r}; expected one of {KERNEL_PATHS}")
    missing = set(_TILE_DIMS_REQUIRED) - set(entry)
    if missing:
        raise ValueError(f"regime {regime!r} missing keys {missing}")
    for dim in _TILE_DIMS_ALL:
        if dim in entry:  # br is optional (pre-K-split tables)
            _check_tile(f"regime {regime!r}", dim, entry[dim])
    if entry.get("variant", None) not in (None,) + VARIANTS:
        raise ValueError(f"regime {regime!r}: unknown prologue variant "
                         f"{entry['variant']!r}; expected one of {VARIANTS}")


def _validate_override_entry(key, entry, where="overrides") -> None:
    if not isinstance(entry, dict):
        raise ValueError(f"override {key!r} in {where} must map to an "
                         f"object, got {type(entry).__name__}")
    unknown = set(entry) - set(_PLAN_KEYS)
    if unknown:
        raise ValueError(f"override {key!r} has unknown plan keys "
                         f"{sorted(unknown)}; expected a subset of "
                         f"{_PLAN_KEYS}")
    if not entry:
        raise ValueError(f"override {key!r} is empty; give at least one of "
                         f"{_PLAN_KEYS}")
    if "path" in entry and entry["path"] not in KERNEL_PATHS:
        raise ValueError(f"override {key!r}: unknown kernel path "
                         f"{entry['path']!r}; expected one of {KERNEL_PATHS}")
    if "variant" in entry and entry["variant"] not in VARIANTS:
        raise ValueError(f"override {key!r}: unknown prologue variant "
                         f"{entry['variant']!r}; expected one of {VARIANTS}")
    for dim in _TILE_DIMS_ALL:
        if dim in entry:
            _check_tile(f"override {key!r}", dim, entry[dim])


def _freeze_entry(entry: dict) -> tuple:
    """Keep only plan keys (autotune rows carry score_us/shape_mknr etc.)
    and freeze to a sorted, hashable item tuple."""
    return tuple(sorted((k, v) for k, v in entry.items() if k in _PLAN_KEYS))


def _override_key(key):
    """Normalize an override key: a layer-name string, or a (K, N, R) shape
    (tuple/list of 3 ints, frozen to a tuple)."""
    if isinstance(key, str):
        return key
    if (isinstance(key, (tuple, list)) and len(key) == 3
            and all(isinstance(d, int) and not isinstance(d, bool)
                    for d in key)):
        return tuple(key)
    raise ValueError(f"override key {key!r} must be a layer-name string or "
                     f"a (K, N, R) int triple")


def _as_mapping(frozen) -> dict:
    return {k: dict(v) for k, v in frozen}


def vmem_budget_arg(text: str) -> int:
    """argparse type for ``--vmem-budget``: a positive integer byte count.
    Rejects non-integer and non-positive values with a clear error."""
    try:
        val = int(text)
    except (TypeError, ValueError):
        raise argparse.ArgumentTypeError(
            f"expected a positive integer number of bytes, got {text!r}")
    if val <= 0:
        raise argparse.ArgumentTypeError(
            f"expected a positive integer number of bytes, got {val}")
    return val


def context_from_flags(block_table=None, vmem_budget=None, impl=None):
    """The one CLI-flags -> KernelContext mapping (serve / roofline /
    benchmarks all share it): ``--block-table`` loads via
    :meth:`KernelContext.from_json`; ``--vmem-budget`` overrides BOTH
    budgets afterwards, so the CLI wins over the table's ``"vmem"`` entry;
    ``--impl`` sets the default kernel path.  Returns None when every flag
    is None (callers then use the process default)."""
    if block_table is None and vmem_budget is None and impl is None:
        return None
    ctx = (KernelContext.from_json(block_table) if block_table
           else KernelContext())
    if vmem_budget is not None:
        ctx = ctx.with_vmem_budgets(fused=vmem_budget, prologue=vmem_budget)
    if impl is not None:
        ctx = ctx.with_impl(impl)
    return ctx


# ---------------------------------------------------------------------------
# the context
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class KernelContext:
    """Immutable per-process/per-layer kernel execution config.  Hashable
    (all state is frozen into tuples), so it rides as pytree-static QLinear
    metadata and as a jit static argument without retrace surprises.

    ``block_table`` / ``overrides`` accept plain dicts at construction and
    are canonicalized; use :meth:`table` / :meth:`layer_overrides` to read
    them back as dicts."""

    block_table: tuple = None  # dict accepted; frozen in __post_init__
    fused_vmem_bytes: int = FUSED_VMEM_BYTES_MAX
    prologue_vmem_bytes: int = PROLOGUE_V_BYTES_MAX
    impl: str = "auto"  # default kernel path: auto | fused | chained | unfused
    interpret: Optional[bool] = None  # None = auto (interpret on CPU)
    overrides: tuple = ()  # per-layer plan overrides (name or (K, N, R))

    def __post_init__(self):
        table = self.block_table
        if table is None:
            table = DEFAULT_BLOCK_TABLE
        if isinstance(table, tuple):
            table = _as_mapping(table)
        if not isinstance(table, dict):
            raise ValueError(f"block_table must be a mapping, got "
                             f"{type(table).__name__}")
        unknown = set(table) - set(REGIMES)
        if unknown:
            raise ValueError(f"unknown regime {sorted(unknown)[0]!r} in "
                             f"block table; expected one of {list(REGIMES)}")
        merged = {r: dict(DEFAULT_BLOCK_TABLE[r]) for r in REGIMES}
        for regime, entry in table.items():
            _validate_table_entry(regime, entry)
            merged[regime] = dict(entry)
        object.__setattr__(self, "block_table", tuple(
            (r, _freeze_entry(merged[r])) for r in REGIMES))

        ovr = self.overrides
        if isinstance(ovr, tuple) and all(
                isinstance(e, tuple) and len(e) == 2 and
                isinstance(e[1], tuple) for e in ovr):
            ovr = _as_mapping(ovr)
        if not isinstance(ovr, dict):
            raise ValueError(f"overrides must be a mapping, got "
                             f"{type(ovr).__name__}")
        frozen = []
        for key, entry in ovr.items():
            key = _override_key(key)
            _validate_override_entry(key, entry)
            frozen.append((key, _freeze_entry(entry)))
        object.__setattr__(self, "overrides",
                           tuple(sorted(frozen, key=lambda e: str(e[0]))))

        for name in ("fused_vmem_bytes", "prologue_vmem_bytes"):
            val = getattr(self, name)
            if not isinstance(val, int) or isinstance(val, bool) or val < 0:
                raise ValueError(f"VMEM budget {name} must be a "
                                 f"non-negative int of bytes, got {val!r}")
        if self.impl not in IMPLS:
            raise ValueError(f"unknown impl {self.impl!r}; "
                             f"expected one of {IMPLS}")
        if self.interpret not in (None, True, False):
            raise ValueError(f"interpret must be None/True/False, got "
                             f"{self.interpret!r}")

    # -- construction --------------------------------------------------------

    @classmethod
    def default(cls) -> "KernelContext":
        return cls()

    @classmethod
    def from_dict(cls, table: dict, where: str = "block table",
                  **changes) -> "KernelContext":
        """Build a context from an already-parsed block-table dict (the
        format ``benchmarks/autotune_blocks.py`` writes): regime entries
        overlay the analytic defaults; the reserved top-level ``"vmem"``
        entry {"fused_bytes_max": .., "prologue_bytes_max": ..} sets the
        budgets; the reserved ``"layers"`` entry maps layer names (or
        "KxNrR" shape strings) to partial plan overrides.  Malformed tables
        raise ValueError and build nothing.  Extra ``changes`` kwargs (e.g.
        ``impl=``) are applied on top."""
        if not isinstance(table, dict):
            raise ValueError(f"{where} must be a JSON object, "
                             f"got {type(table).__name__}")
        vmem = table.get("vmem", {})
        if not isinstance(vmem, dict):
            raise ValueError(f"'vmem' entry in {where} must be "
                             f"an object, got {type(vmem).__name__}")
        unknown = set(vmem) - set(_VMEM_KEYS)
        if unknown:
            raise ValueError(f"unknown vmem budget keys {sorted(unknown)} "
                             f"in {where}; expected {_VMEM_KEYS}")
        for key, val in vmem.items():
            if not isinstance(val, int) or isinstance(val, bool) or val <= 0:
                raise ValueError(f"vmem budget {key!r} must be a positive "
                                 f"int of bytes, got {val!r}")
        layers = table.get("layers", {})
        if not isinstance(layers, dict):
            raise ValueError(f"'layers' entry in {where} must be "
                             f"an object, got {type(layers).__name__}")
        regimes = {k: v for k, v in table.items()
                   if k not in ("vmem", "layers")}
        for regime, entry in regimes.items():
            if regime not in REGIMES:
                raise ValueError(
                    f"unknown regime {regime!r} in {where}; "
                    f"expected one of {list(REGIMES)}")
            _validate_table_entry(regime, entry, where=where)
        kw = dict(
            block_table=regimes,
            overrides=layers,
            fused_vmem_bytes=vmem.get("fused_bytes_max",
                                      FUSED_VMEM_BYTES_MAX),
            prologue_vmem_bytes=vmem.get("prologue_bytes_max",
                                         PROLOGUE_V_BYTES_MAX),
        )
        kw.update(changes)
        return cls(**kw)

    @classmethod
    def from_json(cls, path, **changes) -> "KernelContext":
        """:meth:`from_dict` on a block-table JSON file; unreadable or
        invalid JSON raises ValueError."""
        try:
            table = json.loads(Path(path).read_text())
        except json.JSONDecodeError as e:
            raise ValueError(
                f"block table {path} is not valid JSON: {e}") from e
        except OSError as e:
            raise ValueError(f"cannot read block table {path}: {e}") from e
        return cls.from_dict(table, where=f"block table {path}", **changes)

    # -- builders (all return new contexts) ----------------------------------

    def with_overrides(self, **changes) -> "KernelContext":
        """General field-replace builder; re-validates the result.
        CAUTION: ``overrides=`` REPLACES the whole per-layer override set —
        use :meth:`with_layer_overrides` to MERGE new per-layer pins onto
        the existing ones."""
        return dataclasses.replace(self, **changes)

    def with_block_table(self, table) -> "KernelContext":
        return self.with_overrides(block_table=table)

    def with_vmem_budgets(self, fused: int = None,
                          prologue: int = None) -> "KernelContext":
        """Override the VMEM working-set budgets (bytes); ``None`` leaves a
        budget unchanged."""
        changes = {}
        if fused is not None:
            changes["fused_vmem_bytes"] = fused
        if prologue is not None:
            changes["prologue_vmem_bytes"] = prologue
        return self.with_overrides(**changes) if changes else self

    def with_impl(self, impl: str) -> "KernelContext":
        return self.with_overrides(impl=impl)

    def with_interpret(self, interpret: Optional[bool]) -> "KernelContext":
        return self.with_overrides(interpret=interpret)

    def with_layer_overrides(self, overrides: dict) -> "KernelContext":
        """Merge per-layer plan overrides (keyed by layer name or (K, N, R))
        onto the existing ones."""
        merged = self.layer_overrides()
        for key, entry in overrides.items():
            merged[_override_key(key)] = dict(entry)
        return self.with_overrides(overrides=merged)

    # -- introspection -------------------------------------------------------

    def table(self) -> dict:
        """The effective regime plan table as a plain dict."""
        return _as_mapping(self.block_table)

    def layer_overrides(self) -> dict:
        return _as_mapping(self.overrides)

    def table_entry(self, regime: str) -> dict:
        got = dict(self.block_table).get(regime)
        if got is None:
            raise ValueError(f"unknown regime {regime!r}; "
                             f"expected one of {list(REGIMES)}")
        return dict(got)

    def layer_plan(self, layer: Optional[str], k: int, n: int,
                   r: int = 0) -> Optional[dict]:
        """The per-layer partial plan override for this layer/shape, or
        None.  Lookup precedence: layer name, then the (K, N, R) shape
        triple, then its "KxNrR" string spelling."""
        ovr = dict(self.overrides)
        for key in (layer, (k, n, r), f"{k}x{n}r{r}"):
            if key is not None and key in ovr:
                return dict(ovr[key])
        return None

    def interpret_mode(self) -> bool:
        if self.interpret is not None:
            return self.interpret
        import jax

        return jax.default_backend() == "cpu"

    # -- plan selection / resolution -----------------------------------------

    def select_plan(self, m: int, k: int, n: int, r: int = 0,
                    regime: str = None, layer: str = None) -> Plan:
        """The table execution plan for a (M, K, N, R) problem — per-layer
        override merged over the regime entry, NO VMEM feasibility applied
        (see :meth:`resolve_plan`).

        ``regime`` overrides the M-derived serving regime; unknown strings
        raise.  Blocks are clamped to the actual dims; large ranks shrink BN
        so the U tile + f32 accumulator stay within VMEM."""
        if regime is None:
            regime = gemm_regime(m)
        entry = self.table_entry(regime)
        override = self.layer_plan(layer, k, n, r)
        if override:
            entry.update(override)
        bm = min(entry["bm"], _round_pow2(max(m, 8)))
        bn = lane_tile(n, entry["bn"])
        bk = k_tile(k, entry["bk"])
        if "br" in entry:
            br = lane_tile(r, entry["br"])
        else:  # pre-K-split tables: the shared kernel default
            br = default_proj_tiles(k, r)[1]
        if r >= 512:
            bn = min(bn, 128)
        return Plan(entry["path"], bm, bn, bk, br, entry.get("variant"))

    def fused_variant(self, k: int, r: int, bm: int, bn: int, bk: int,
                      br: int, rotate: bool, act_group: int = None) -> str:
        """Prologue variant for FORCED-fused execution at fixed tiles:
        resident when it fits the budget (or rotation requires it), else
        streamed."""
        if rotate:
            return "resident"
        if fused_vmem_bytes(k, r, bm, bn, bk, br, True,
                            act_group=act_group) <= self.fused_vmem_bytes:
            return "resident"
        return "streamed"

    def resolve_plan(self, m: int, k: int, n: int, r: int = 0,
                     rotate: bool = False, regime: str = None,
                     layer: str = None, act_group: int = None) -> Plan:
        """The executable plan for a (M, K, N, R) problem: the table plan
        (with any per-layer override) plus per-slab VMEM feasibility —
        tiles shrink to fit the budget first; the path demotes (fused →
        chained → unfused) only when no tiling fits.

        ``act_group`` (group-wise activation scales, paper Table 2) makes
        the granularity a plan axis: BK snaps to a power-of-two multiple of
        ``lcm(g, 128)`` (K-chunks must hold whole scale groups and stay
        lane tiles; ``g = K`` pins BK = K, degenerating to per-token), the
        (M, K/g) scale plane joins the working-set model, and BK shrinks no
        further than that unit — a path demotes when no such tiling fits
        its budget."""
        if act_group is not None and k % act_group:
            raise ValueError(f"act_group {act_group} must divide K={k}")
        sel = self.select_plan(m, k, n, r, regime=regime, layer=layer)
        path, bm, bn, bk, br = sel[:5]
        if act_group is not None:
            bk = snap_bk_to_group(bk, act_group)
        if path == "fused":
            # a table/override variant pin constrains the variant search but
            # NEVER bypasses feasibility — tiles still shrink to fit and the
            # path still demotes when nothing fits (rotation forces the
            # resident slab regardless of the pin)
            plan = _fit_fused(k, r, bm, bn, bk, br, rotate,
                              self.fused_vmem_bytes,
                              variant_pin=sel.variant, act_group=act_group)
            if plan is not None:
                return plan
            path = "chained"
        if path == "chained":
            plan = _fit_chained(k, r, bm, bn, bk, br, rotate,
                                self.prologue_vmem_bytes,
                                act_group=act_group)
            if plan is not None:
                return plan
        return Plan("unfused", bm, bn, bk, br, None)

    # -- introspection report -------------------------------------------------

    def explain(self, m: int, k: int, n: int, r: int = 0,
                rotate: bool = False, layer: str = None,
                act_group: int = None) -> str:
        """Human-readable plan-introspection report: for each serving regime,
        the table plan, the per-layer override (if one matches), the
        resolved path/tiles/variant, and the VMEM working set vs. budget.
        The regime the given M falls into is starred.  With ``act_group``
        the report names the granularity constraint (BK snapped to a
        multiple of g, scale plane in the working set) and flags resolved
        plans whose BK the snap changed or whose path demoted under it."""
        mib = 1024 * 1024
        active = gemm_regime(m)
        lines = [
            f"KernelContext.explain(m={m}, k={k}, n={n}, r={r}, "
            f"rotate={rotate}" + (f", layer={layer!r}" if layer else "")
            + (f", act_group={act_group}" if act_group else "") + ")",
            f"  impl={self.impl}  interpret="
            f"{'auto' if self.interpret is None else self.interpret}  "
            f"budgets: fused={self.fused_vmem_bytes / mib:.1f} MiB, "
            f"prologue={self.prologue_vmem_bytes / mib:.1f} MiB",
        ]
        if act_group:
            lines.append(
                f"  act_group={act_group}: bk snaps to a multiple of "
                f"{act_group} (K-chunks hold whole scale groups, floor "
                f"bk={snap_bk_to_group(LANES, act_group)}); the "
                f"(M, K/{act_group}) f32 scale plane "
                f"joins the working set; a path demotes when no such "
                f"tiling fits its budget")
        override = self.layer_plan(layer, k, n, r)
        if override:
            lines.append(f"  layer override: {override} "
                         f"(override > table > defaults)")
        for regime in ("decode", "mixed", "prefill"):
            entry = self.table_entry(regime)
            table_plan = self.select_plan(m, k, n, r, regime=regime,
                                          layer=layer)
            plan = self.resolve_plan(m, k, n, r, rotate=rotate,
                                     regime=regime, layer=layer,
                                     act_group=act_group)
            if plan.path == "fused":
                need = fused_vmem_bytes(k, r, plan.bm, plan.bn, plan.bk,
                                        plan.br, plan.variant != "streamed",
                                        act_group=act_group)
                budget = self.fused_vmem_bytes
            elif plan.path == "chained":
                need = prologue_vmem_bytes(k, r, plan.bm, plan.bk, plan.br,
                                           rotate, act_group=act_group)
                budget = self.prologue_vmem_bytes
            else:
                need = budget = None
            star = "*" if regime == active else " "
            table_s = (f"{entry['path']} bm={entry['bm']} bn={entry['bn']} "
                       f"bk={entry['bk']}"
                       + (f" br={entry['br']}" if "br" in entry else ""))
            plan_s = (f"{plan.path} bm={plan.bm} bn={plan.bn} bk={plan.bk} "
                      f"br={plan.br}"
                      + (f" variant={plan.variant}" if plan.variant else ""))
            notes = []
            if act_group:
                snapped = snap_bk_to_group(table_plan.bk, act_group)
                if snapped != table_plan.bk:
                    notes.append(f"bk {table_plan.bk}->{snapped} "
                                 f"(multiple of g={act_group})")
                if plan.path != table_plan.path:
                    notes.append(f"demoted {table_plan.path}->{plan.path}: "
                                 f"no multiple-of-{act_group} bk tiling "
                                 f"fits the {table_plan.path} budget")
            note_s = f"  ({'; '.join(notes)})" if notes else ""
            if need is None:
                fit_s = "vmem n/a (jnp fallback path)"
            else:
                fit_s = (f"vmem {need / mib:.2f}/{budget / mib:.2f} MiB "
                         f"({'fits' if need <= budget else 'OVER'})")
            lines.append(f" {star}[{regime:7s}] table: {table_s}  ->  "
                         f"resolved: {plan_s}  [{fit_s}]{note_s}")
        return "\n".join(lines)
