"""Backend capability table — THE single legality/layout oracle.

YASK's compiler owes its portability to one discipline: every
target's legality facts (vector fold shapes, alignment, intrinsics)
live in one target description that both code generation and
validation consult.  This module is the TPU-era equivalent: a frozen,
versioned table (schema ``yask_tpu.capability/1``) encoding what was
**probed on real hardware** (v5e, round 3 — see CLAUDE.md "Mosaic TC
rules"), consumed by every layer that used to bake the same numbers in
as module constants:

* ``lowering.tpu_tile_dims`` / ``VarGeom`` pad math — :meth:`tile_dims`;
* ``tile_planner.sublane_count`` / ``plan_blocks`` — :meth:`sublane_count`;
* ``pallas_stencil.vmem_limit_bytes`` / ``default_vmem_budget`` and
  the build's room test — :meth:`vmem_limit_bytes`,
  :meth:`plan_budget_bytes` and :meth:`vmem_need_bytes`, all read off
  the one live-value model (:attr:`vmem_live`);
* the auto-tuner's VMEM ladder — :attr:`vmem_ladder_mib`;
* the checker's ``mosaic`` / ``vmem`` passes — the same accessors, so
  the static model *cannot* drift from the runtime.

``tools/repo_lint.py``'s ``CAP-CONST`` rule flags raw lane/sublane/
VMEM-byte literals re-appearing in those modules; this file is the
only sanctioned home for them.  ``tools/checker_conformance.py``
differentially tests that the checker's static verdicts match what the
runtime actually does for randomized solutions.

Entries:

* ``tpu:v5e`` — the probed Mosaic TensorCore rules.
* ``cpu:interpret`` — the Pallas interpret-mode host.  It DELIBERATELY
  carries the TPU's legality facts (round-8 invariant: a CPU-host
  check must answer for Mosaic), differing only in the planning-budget
  default (VMEM is emulated under interpret; a loose budget only
  shapes planning).

Extension recipe (what a ``pallas:triton`` entry would fill in) is in
``docs/checking.md``.
"""

from __future__ import annotations

import os
from dataclasses import asdict, dataclass, field
from typing import Dict, Optional, Tuple

SCHEMA = "yask_tpu.capability/1"

#: env override for the default backend entry (tests / future targets)
_ENV_KNOB = "YT_BACKEND"


@dataclass(frozen=True)
class VmemLive:
    """One class of the live-value model: a kernel fusing at most
    ``max_fuse_steps`` steps of a program with at most ``max_stages``
    stages a step, which keeps scratch vars in-tile or (``scratch``
    false) has none, costs Mosaic, on top of the tiles the build
    counts, ``tiles`` result tiles (one result tile = one tile of every
    written var) of live SSA values and spill slots.  ``budget_mib`` is
    the class's default tile budget; ``evidence`` names the chip's
    acceptance/refusal pairs both were read from.  ``declared``: the
    row was read off the strip kernel, against the buffers it declares
    -- the build then counts a candidate's tiles as that kernel
    allocates them (no result tile for a var whose strips are stored
    into the ring slot it evicts), and the planner grows blocks while
    the budget holds them, with no result tile a fused sub-step on
    top.  The other rows were read off the whole-tile kernel against a
    count with a result tile a written var, and keep it.  The first
    row that covers a class is the class's: a declared row stands
    ahead of any row of more stages that would take its class."""

    max_fuse_steps: int
    max_stages: int
    tiles: float
    budget_mib: int
    evidence: str
    scratch: bool = False
    declared: bool = False

    def covers(self, fuse_steps: int, stages: int,
               scratch_vars: int = 0) -> bool:
        return (fuse_steps <= self.max_fuse_steps
                and stages <= self.max_stages
                and self.scratch == (scratch_vars > 0))


#: the v5e rows (shared by the interpret entry, which answers for
#: Mosaic).  "Used X of 128.00M" is libtpu's refusal text, in MiB.
V5E_VMEM_LIVE: Tuple[VmemLive, ...] = (
    VmemLive(
        max_fuse_steps=2, max_stages=1, tiles=0.75, budget_mib=112,
        declared=True,
        evidence="Re-read off the strip kernel by PR 51 (every cell "
                 "reads eval == 'strip' since PR 44), each candidate "
                 "compiled for a described v5e with the chip's libtpu "
                 "0.0.34 under a scoped limit set just over the buffers "
                 "it declares (the same compile passes just above the "
                 "total), tiles / Mosaic's own total in MiB: x/4 shard "
                 "256x1024x1024 at 16x24 skewed in y, both pipelines, "
                 "all three arms 106.31 / 'Scoped allocation with size "
                 "110.35M' = the buffers and 4.04 (0.40 result tiles), "
                 "input pipeline alone 65.81 / 69.85M; at 8x24, both, "
                 "88.59 / 92.63M; at 16x8 uniform, both, 84.38 / 88.39M; "
                 "2x2 shard at 16x16 uniform, both, four arms 101.25 / "
                 "102.33M; flagship 640^3 32x32, both, 106.00 / 110.52M "
                 "(0.43), input pipeline 64.00 / 68.52M, 16x32, both, "
                 "79.50 / 84.02M (0.57); 768^3 32x24, both, 107.25 / "
                 "110.33M; served 384^3 64x24, both, 90.00 / 93.55M; "
                 "overthrust 801x801x187 62x24, both, 55.08 / 58.38M = "
                 "0.75 result tiles, the largest reading and the row; "
                 "cube K=2 64x32, both, 89.25 / 92.21M; 640^3 64x64 "
                 "unpipelined 76.88 / 76.91M.  What is held on top goes "
                 "with the strip's registers (its spills), 0.03-4.5 MiB, "
                 "not with the tile.  (The whole-tile kernel, gone with "
                 "PR 44, held 5.7 result tiles: 'Used 172.34M of "
                 "128.00M' at 640^3 32x32, chip, PR 30.)  Budget 112 "
                 "from A/Bs on the chip, PR 51 (chiprun_out/pr51/*.out, "
                 ".chipwork/call1.sh, call2.sh; a 10-step call's GPts/s, "
                 "parent's plan -> -vmem_mb 88 -> 112): flagship 16x32 "
                 "12.50 -> 32x32 input pipeline 14.72 -> 32x32 both "
                 "pipelines (106.0 MiB) 16.18; 768^3 16x24 13.45 -> "
                 "32x24 15.83 -> 32x24 both (107.2) 17.46; the x/4 "
                 "shard's size on one chip 16x8 uniform 6.71 -> 16x24 "
                 "skewed 14.00 -> both 15.19 (8x24 both: 11.46); the 2x2 "
                 "shard's 16x8 6.72 -> 16x16 8.84 -> both 9.27; served "
                 "384^3 32x24 1.733 -> 64x24 1.747 -> both 1.755 (kernel "
                 "-8 %); cube's K=2 tail 32x32 30.35 -> 64x32 without "
                 "staging 29.44 -> with 30.52.  The blocks come with the "
                 "price at any budget from 68; 92 is the lowest that "
                 "gives cube's tail and the served kernel their output "
                 "staging, 108 the flagship and 768^3 theirs (worth 8-10 "
                 "%); above 112 nothing moves (the instruction cap ends "
                 "the growth); 768^3 needs 115.1 of the room's 115.2 by "
                 "this row.  On four chips at 1024^3 (x4_*.out, y4_*.out): "
                 "x/4 16x8 uniform 22.96 -> 16x24 skewed in y, input "
                 "pipeline (-vmem_mb 88) 41.25 -> both 43.91 (8x24 both: "
                 "35.43); the 2x2 grid 16x8 22.58 -> 16x16 28.21 -> both "
                 "29.36.  K=1 (PR 55; the class's own row, 7.4 result "
                 "tiles and budget 64, had been read at PR 30 off the "
                 "whole-tile kernel on ONE written var, 'Used 175.84M of "
                 "128.00M' at 640^3 32x64, and priced lbm_d3q19's "
                 "nineteen written vars' 42.9 MiB at 87.6): the same "
                 "reading on lbm_d3q19 256x256x512, tiles / 'Scoped "
                 "allocation with size': 4x8 both pipelines 37.12 / "
                 "27.86M, 8x8 both 61.88 / 47.45M, 8x16 both 82.50 / "
                 "65.93M (with the staging on, under the declared by the "
                 "moving populations' evicted slots), 8x16 input pipeline "
                 "55.62 / 65.30M (0.72 result tiles of nineteen, the "
                 "largest), 16x8 input pipeline 75.09 / 85.78M, 16x16 "
                 "unpipelined 50.62 / 60.29M, 8x32 input pipeline 83.44 / "
                 "92.51M, its K=2 8x16 input pipeline 66.75 / 78.12M "
                 "(0.71); iso3dfd r8 K=1 640^3 64x32 both 86.25 / 90.75M; "
                 "himeno K=1 256x256x512 16x64 both 88.59 / 97.38M (a "
                 "strip of 256 registers: 2.5 result tiles of its one "
                 "written var, the one reading over the row, inside the "
                 "room's headroom; tests/test_vmem_model.py DECLARED_K1)"),
    VmemLive(
        max_fuse_steps=1, max_stages=1, scratch=True, tiles=4.8,
        budget_mib=96,
        evidence="tti r4 K=1 (six scratch vars in-tile) 512^3: blocks "
                 "32x16, both pipelines, 114.0 MiB of tiles (7.5 a result "
                 "tile): refused, 'Used 149.80M of 128.00M vmem' = 4.77 "
                 "result tiles (with the input pipeline alone, 99.0 MiB: "
                 "'Used 134.80M' = 4.77 again); 16x32, both, the same "
                 "tiles: 'Used 138.33M' = 3.24, and with the input "
                 "pipeline alone accepted; 32x32 unpipelined, 96.75 MiB "
                 "(11.25): 'Used 137.47M' = 3.62; the row is the largest "
                 "(iso3dfd's 7.4 would read 169.5 where Mosaic said "
                 "149.80); 16x16 with both pipelines, 76.0 MiB (5.0): "
                 "accepted (need 100.0 by this row); all compiled for a "
                 "described v5e with the chip's libtpu 0.0.34, PR 35.  "
                 "Accepted and run on the chip, PR 35 "
                 "(chiprun_out/pr35/a1_ab.log, a 10-step call): -vmem_mb "
                 "64, 16x8 both pipelines at 57.0 MiB 1.182 s; 8x16 both "
                 "at 57.0 MiB 0.922 s; -vmem_mb 72, 16x16 input pipeline "
                 "at 66.0 MiB 0.818 s; the default, 16x16 both at 76.0 MiB "
                 "0.811 s (8x8 both at 42.8 MiB, the plan until PR 35: "
                 "1.281 s, ledger, PR 34).  Budget 96: any from 76.0 (the "
                 "fastest plan's tiles) to 106 (where the planner would "
                 "propose 32x16 and the build shrink it back) plans the "
                 "same; 96 is the tuner's rung"),
    VmemLive(
        max_fuse_steps=1, max_stages=2, tiles=0.6, budget_mib=112,
        evidence="ssg r4 K=1 (two stages) 320x320x384: blocks 32x16, "
                 "input pipeline, 120.75 MiB of tiles (24.75 a result "
                 "tile): refused, 'Used 135.54M of 128.00M vmem' = 0.60 "
                 "result tiles (16x32, the same tiles: 'Used 129.43M' = "
                 "0.35; the row is the larger); 16x16 with both "
                 "pipelines, 113.5 MiB (16.5): accepted (need 123.4 by "
                 "this row); all compiled for a described v5e with the "
                 "chip's libtpu 0.0.34, PR 31.  Accepted and run on the "
                 "chip, PR 31 "
                 "(chiprun_out/pr31/ab_mb*.log, -vmem_mb 64 / 96 / 112, "
                 "a 10-step call): 8x8 both pipelines at 63.8 MiB 0.509 "
                 "s, 16x8 both at 85.1 MiB 0.376 s, 16x16 input pipeline "
                 "at 80.5 MiB 0.284 s.  Budget 112: the lowest at which "
                 "the build picks the A/B's fastest plan.  The two-stage "
                 "evaluator's values are counted among the build's own "
                 "work tiles, hence so little on top; a four-stage "
                 "kernel (awp_abc) has no row"),
    VmemLive(
        max_fuse_steps=4, max_stages=1, tiles=8.7, budget_mib=64,
        evidence="iso3dfd r8 K=4 512^3, blocks 8x8, 48.1 MiB of tiles "
                 "(11.8 a result tile): refused, 'Used 149.99M of "
                 "128.00M vmem' = 8.64 result tiles (chip, PR 21); cube "
                 "r1 K=4 768^3, blocks 32x16, 39.4 MiB (4.4): runs in "
                 "every ledger line since PR 23 (need 77.4 by this "
                 "row).  No room measured above today's: budget as it "
                 "was"),
)


@dataclass(frozen=True)
class BackendCapability:
    """Legality + layout facts of one execution backend.

    Frozen: a capability is data, not policy — consumers derive their
    decisions from it but never mutate it.  All ``*_mib`` fields are
    MiB (the probed numbers are round MiB values); byte values come
    from the accessor methods.
    """

    #: registry key, e.g. ``"tpu:v5e"``
    name: str
    #: coarse family: ``"tpu"`` (real Mosaic) or ``"cpu"`` (interpret)
    kind: str

    # ---- register/DMA tiling (probed v5e, round 3) -------------------
    #: lane (last physical axis) tile extent — every dtype
    lane_tile: int = 128
    #: bytes per sublane tile row: sublane extent scales with element
    #: width (32 B ⇒ 8 for f32, 16 for bf16)
    sublane_tile_bytes: int = 32
    #: floor for the planner's sublane fold unit (f64's 4-row DMA tile
    #: still plans blocks in 8-row folds)
    min_sublane_fold: int = 8
    #: DMA windows on HBM/ANY refs need lane-tile-multiple sizes AND
    #: offsets (a full-extent slice of a non-multiple lane total is
    #: itself unaligned: physical tiled layout ≠ logical extent)
    dma_tile_aligned: bool = True
    #: misc axes must be physically FIRST (the trailing two axes belong
    #: to the sublane×lane tiling)
    misc_axes_first: bool = True
    #: only the solution-minor domain dim may ride the lane axis of a
    #: DMA-windowed var (anything else needs pid-dependent non-aligned
    #: offsets → pallas fallback)
    minor_dim_lane_only: bool = True
    #: no-domain-dim vars ride SMEM with static scalar reads
    smem_scalars: bool = True
    #: skew write-back windows on the sublane axis must stay
    #: sublane-tile aligned (shifted output DMAs)
    sublane_aligned_writes: bool = True

    # ---- in-kernel op vocabulary (Mosaic TC rejections, probed) ------
    #: op classes the kernel generator must never emit (static region
    #: inserts go through lax.pad + broadcasted_iota masks instead)
    banned_kernel_ops: Tuple[str, ...] = (
        "dynamic_update_slice", "scatter", "sort", "gather",
        "1d_iota_on_lane_axis",
    )
    #: expression-node vocabulary the in-kernel evaluator can lower
    #: with legal patterns (the checker's MOSAIC-KERNEL-OPS rule)
    kernel_expr_nodes: Tuple[str, ...] = (
        "ConstExpr", "VarPoint", "IndexExpr", "FirstIndexExpr",
        "LastIndexExpr", "NegExpr", "AddExpr", "MultExpr", "SubExpr",
        "DivExpr", "ModExpr", "FuncExpr", "CompExpr", "AndExpr",
        "OrExpr", "NotExpr", "EqualsExpr",
    )

    # ---- VMEM: one live-value model (probed v5e; PR 21, 30, 31) -------
    #: Mosaic's default scoped VMEM limit before CompilerParams raises it
    vmem_default_scope_mib: int = 16
    #: probed usable scoped VMEM (v5e takes ≥ this)
    vmem_probed_mib: int = 120
    #: cap for the requested scoped limit (safely below the probed
    #: 120..128 range)
    vmem_limit_cap_mib: int = 128
    #: THE live-value model, per (fuse depth, stages, in-tile scratch)
    #: class, first match wins: what Mosaic's scoped allocation holds on top of the
    #: build's tiles, and the class's default tile budget.  Every row
    #: names the chip runs it came from.
    vmem_live: Tuple[VmemLive, ...] = V5E_VMEM_LIVE
    #: a class no row covers: live values ≈ this many more copies of
    #: ALL the tiles (the round-3 guess), and the default budget is the
    #: scoped limit divided by one more than it — today's 64 MiB
    vmem_live_unmeasured_copies: float = 1.0
    #: share of the scoped limit a measured class's plans leave free
    #: (the model's refusals and acceptances agree within 3 %)
    vmem_headroom: float = 0.1
    #: fixed default budget of a host whose VMEM is emulated (the
    #: interpret entry); None takes it from the model
    emulated_plan_budget_mib: Optional[int] = None
    #: the auto-tuner's VMEM-budget ladder rungs
    vmem_ladder_mib: Tuple[int, ...] = (64, 96, 120)

    #: free-form provenance notes (probe round, hardware)
    notes: Dict[str, str] = field(default_factory=dict)

    # ---- derived accessors -------------------------------------------

    def tile_dims(self, dtype) -> Tuple[int, int]:
        """(sublane, lane) DMA/register tile extents of the last two
        physical axes for ``dtype`` (8×128 for f32, 16×128 for bf16).
        THE single definition behind ``lowering.tpu_tile_dims``."""
        import numpy as np
        esize = np.dtype(dtype).itemsize
        sub = max(1, self.sublane_tile_bytes // max(1, esize))
        return sub, self.lane_tile

    def sublane_count(self, dtype) -> int:
        """The planner's sublane fold unit for ``dtype``: the DMA
        sublane tile, floored at :attr:`min_sublane_fold` (f64's 4-row
        tile still folds in 8s)."""
        return max(self.min_sublane_fold, self.tile_dims(dtype)[0])

    def vmem_live_row(self, fuse_steps: int, stages: int,
                      scratch_vars: int = 0) -> Optional[VmemLive]:
        """The :attr:`vmem_live` row of a kernel fusing ``fuse_steps``
        steps of a ``stages``-stage program that keeps
        ``scratch_vars`` scratch vars in-tile, or None where the chip
        has measured nothing for that class (a kernel with scratch
        tiles is not one without: ``tti``'s whole-tile kernel read 4.8
        result tiles where ``iso3dfd``'s read 7.4)."""
        for row in self.vmem_live:
            if row.covers(fuse_steps, stages, scratch_vars):
                return row
        return None

    def vmem_need_bytes(self, fuse_steps: int, stages: int,
                        tile_bytes: int, result_bytes: int,
                        scratch_vars: int = 0) -> int:
        """Mosaic's scoped VMEM need for a kernel whose build counts
        ``tile_bytes`` of tiles, ``result_bytes`` of them one result
        tile per written var: the tiles plus the class's live values
        (``row.tiles`` result tiles), or, unmeasured, plus
        :attr:`vmem_live_unmeasured_copies` of everything.  THE single
        model behind the build's room test and the checker's spill
        rule."""
        row = self.vmem_live_row(fuse_steps, stages, scratch_vars)
        if row is None:
            return int((1.0 + self.vmem_live_unmeasured_copies)
                       * tile_bytes)
        return int(tile_bytes + row.tiles * result_bytes)

    def vmem_room_bytes(self, fuse_steps: int, stages: int,
                        scratch_vars: int = 0) -> int:
        """What a plan's :meth:`vmem_need_bytes` may reach: the scoped
        limit's cap, less the headroom where the class is measured (an
        unmeasured class's default budget already is the limit divided
        by its guess)."""
        cap = self.vmem_limit_cap_mib * 2 ** 20
        if self.vmem_live_row(fuse_steps, stages, scratch_vars) is None:
            return cap
        return int(cap * (1.0 - self.vmem_headroom))

    def vmem_limit_bytes(self, vmem_budget: int) -> int:
        """Scoped Mosaic VMEM limit requested for a tile budget: room
        for the unmeasured guess on top of the budget, capped below the
        probed ceiling (every default budget asks for the cap).  THE
        single definition the kernel's CompilerParams and the checker's
        spill model share."""
        return int(min(self.vmem_limit_cap_mib * 2 ** 20,
                       (1.0 + self.vmem_live_unmeasured_copies)
                       * vmem_budget))

    def plan_budget_bytes(self, fuse_steps: int = 1, stages: int = 1,
                          scratch_vars: int = 0) -> int:
        """Default Pallas tile-planning budget of a (fuse depth,
        stages, in-tile scratch) class (``-vmem_mb`` overrides): the
        class's row, else the scoped limit divided by the unmeasured
        guess."""
        if self.emulated_plan_budget_mib is not None:
            return self.emulated_plan_budget_mib * 2 ** 20
        row = self.vmem_live_row(fuse_steps, stages, scratch_vars)
        if row is not None:
            return row.budget_mib * 2 ** 20
        return int(self.vmem_limit_cap_mib
                   / (1.0 + self.vmem_live_unmeasured_copies)) * 2 ** 20

    def vmem_ladder_bytes(self) -> Tuple[int, ...]:
        return tuple(mb * 2 ** 20 for mb in self.vmem_ladder_mib)

    def to_json(self) -> dict:
        """Schema-stamped dict (``yask_tpu.capability/1``)."""
        out = {"schema": SCHEMA}
        out.update(asdict(self))
        return out


_REGISTRY: Dict[str, BackendCapability] = {}


def register_capability(cap: BackendCapability) -> BackendCapability:
    """Register a backend entry (the extension point: a new target is
    a table entry plus — at most — a new kernel emitter, never edits
    to the planner/checker constants)."""
    if cap.name in _REGISTRY:
        raise ValueError(f"duplicate backend capability '{cap.name}'")
    _REGISTRY[cap.name] = cap
    return cap


def backend_names() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


#: the probed v5e TensorCore rules — every number here has hardware
#: provenance (CLAUDE.md "Mosaic TC rules", docs/checking.md)
TPU_V5E = register_capability(BackendCapability(
    name="tpu:v5e", kind="tpu",
    notes={"provenance": "probed on v5e, rounds 3-5, PR 21, PR 30, PR 31, "
                         "PR 35",
           "vmem": "scoped limit raised via CompilerParams; >=120 MiB "
                   "usable; live SSA values per (fuse depth, stages, "
                   "in-tile scratch) class in vmem_live, each row with "
                   "its chip runs"},
))

#: Pallas interpret mode on a CPU host.  Legality facts DELIBERATELY
#: model the TPU (round-8 invariant: a CPU-host check must answer for
#: Mosaic); only the planning budget is looser — VMEM is emulated, the
#: budget only shapes planning.
CPU_INTERPRET = register_capability(BackendCapability(
    name="cpu:interpret", kind="cpu",
    emulated_plan_budget_mib=100,
    notes={"provenance": "mirror of tpu:v5e legality by design",
           "vmem": "emulated; budget shapes planning only"},
))


def get_capability(name: Optional[str] = None) -> BackendCapability:
    """THE accessor every consumer reads the table through.

    ``name`` picks an entry; ``None`` resolves ``YT_BACKEND`` and
    falls back to ``tpu:v5e`` — legality questions always answer for
    the real target, even on a CPU host (checker invariant)."""
    key = name or os.environ.get(_ENV_KNOB) or "tpu:v5e"
    try:
        return _REGISTRY[key]
    except KeyError:
        raise KeyError(
            f"unknown backend capability '{key}'; registered: "
            f"{', '.join(backend_names())}") from None


#: TPU ``device_kind`` (as JAX reports it) → capability entry.  Only
#: kinds whose limits were probed are here; another TPU generation is
#: an error until its own entry exists — it must not borrow v5e's.
TPU_KIND_ENTRIES: Dict[str, str] = {"TPU v5 lite": "tpu:v5e"}


def capability_for_platform(platform: str,
                            device_kind: str = "") -> BackendCapability:
    """Map a jax platform (+ TPU ``device_kind``) to its capability
    entry: a TPU resolves by kind through :data:`TPU_KIND_ENTRIES` and
    raises for a kind that is not there; anything else plans as the
    interpret host."""
    if platform != "tpu":
        return get_capability("cpu:interpret")
    if device_kind not in TPU_KIND_ENTRIES:
        raise KeyError(
            f"no backend capability entry for TPU device kind "
            f"'{device_kind}'; known: "
            f"{', '.join(sorted(TPU_KIND_ENTRIES))}")
    return get_capability(TPU_KIND_ENTRIES[device_kind])
